"""Fully-fused Pallas TPU pull-gossip round: PRNG + gather + OR in one kernel.

Round 1 measured the XLA hot path honestly: at N=10M the per-round cost is
ONE uint32 gather at ~8 ns/element (HBM random access, latency-bound), so a
27-round pull run is pinned at ~2.28 s no matter how the surrounding ops
fuse (bench.py, ops/pallas_sampling.py).  This module removes the HBM
gather entirely: for a single rumor the whole 10M-node infection bitmap is
1.25 MB packed along the NODE dimension — it fits in VMEM with room to
spare, so one ``pallas_call`` can hold the entire cluster state on-chip and
do partner sampling (TPU hardware PRNG), digest gather, and OR-merge at VPU
rate with zero HBM traffic for the gather.

Layout
------
Node ``n`` lives at bit ``n & 31`` of word ``(n >> 5)``; words are stored
row-major in a ``uint32[R, 128]`` table (R rows of 128 lanes).  N is padded
up to ``R*128*32``; phantom nodes are masked to zero every round, so a pull
that lands on a phantom behaves exactly like a pull from an uninfected node.

Partner sampling (the TPU-shaped part)
--------------------------------------
Mosaic exposes per-element dynamic gather only *within* a 128-lane row
(``take_along_axis(axis=1)`` -> ``tpu.dynamic_gather``); cross-row
per-element gather does not exist.  So the kernel factors the partner draw
``(row t, lane m, bit c)`` into hardware-friendly stages:

1. **Per-lane row shifts.** Draw 128 iid shifts ``s_j ~ U[0, R)`` and build
   ``rot[i, j] = table[(i - s_j) mod R, j]`` with ceil(log2 R) conditional
   *static* ``pltpu.roll`` stages along the row axis (roll by ``2^k`` where
   bit k of ``s_j`` is set — a binary decomposition of the shift, selected
   per lane).
2. **Per-element lane choice.** For each destination bit-plane k, each
   destination word (i, j) draws ``m ~ U[0, 128)`` and lane-gathers
   ``rot[i, m]`` — i.e. the partner word is ``table[(i - s_m) mod R, m]``.
3. **Per-element bit choice.** Draw ``c ~ U[0, 32)`` and take bit ``c`` of
   the partner word as the pulled infection bit for plane k.

Distributional contract (stated honestly, tested in tests/test_pallas_round
.py): the partner of every destination node is EXACTLY uniform over the
padded node set — ``m`` is uniform over lanes, ``(i - s_m)`` is uniform
over rows given any ``m`` (each ``s_j`` is uniform and independent), and
``c`` is uniform over bits.  What differs from the iid threefry sampler
(ops/sampling.py) is the *joint*: destination nodes that pick the same lane
``m`` in the same round share that lane's row shift ``s_m`` (128 shifts per
round), and self-pulls are not excluded (probability 1/N, a no-op for SI).
Per-node marginals — the quantity that drives the mean-field coverage
recurrence c' = 1-(1-c)^2 — are identical, and the measured curves match
the threefry path round-for-round at bench scale (see tests).

This is the fused kernel VERDICT.md round 1 asked for ("sampling + gather +
OR in one pallas_call"); the reference hot path being batched is the
per-neighbor fan-out loop of /root/reference/main.go:72-88.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
BITS = 32
NODES_PER_ROW = LANES * BITS            # 4096 nodes per table row
_ROUND_MIX = 1000003                    # seed-mixing prime (ops/pallas_sampling)


def n_rows(n: int) -> int:
    """Rows (multiple of 8 for vreg alignment) covering n nodes."""
    r = -(-n // NODES_PER_ROW)
    return max(8, -(-r // 8) * 8)


def padded_n(n: int) -> int:
    return n_rows(n) * NODES_PER_ROW


def node_pack(infected: jax.Array) -> jax.Array:
    """bool[N] -> node-packed uint32[R, 128] table (phantoms zero)."""
    n = infected.shape[0]
    rows = n_rows(n)
    flat = jnp.zeros((rows * NODES_PER_ROW,), jnp.uint32)
    flat = flat.at[:n].set(infected.astype(jnp.uint32))
    words = flat.reshape(rows * LANES, BITS)
    weights = (jnp.uint32(1) << jnp.arange(BITS, dtype=jnp.uint32))
    packed = jnp.sum(words * weights[None, :], axis=1, dtype=jnp.uint32)
    return packed.reshape(rows, LANES)


def node_unpack(table: jax.Array, n: int) -> jax.Array:
    """node-packed uint32[R, 128] -> bool[n]."""
    flat_words = table.reshape(-1)
    shifts = jnp.arange(BITS, dtype=jnp.uint32)
    bits = (flat_words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1)[:n].astype(bool)


def coverage_node_packed(table: jax.Array, n: int) -> jax.Array:
    """Infected fraction over the REAL n nodes (phantoms are kept zero)."""
    pop = jnp.sum(jax.lax.population_count(table), dtype=jnp.uint32)
    return pop.astype(jnp.float32) / jnp.float32(n)


# VMEM budget for the fused kernels: the live set is ~4 table-sized
# buffers (aliased in/out table, rot, the rolled temp, acc), kept under
# v5e's 128 MB with headroom for Mosaic's own temporaries.
_VMEM_LIMIT_BYTES = 110 * 1024 * 1024
TABLE_COPIES = 4


def _rotate_rows(table: jax.Array, sbits: jax.Array, rows: int) -> jax.Array:
    """Stage 1 of the partner draw (shared by both fused kernels):
    ``rot[i, j] = table[(i - s_j) mod rows, j]`` with per-lane shifts
    ``s_j = sbits[0, j] mod rows``, built from ceil(log2 rows) conditional
    *static* rolls — a binary decomposition of the shift, selected per
    lane.  (Modulo bias rows/2^32 < 1e-6: documented.)"""
    s = (sbits[0:1, :] % jnp.uint32(rows)).astype(jnp.int32)   # [1, 128]
    rot = table
    shift = 1
    while shift < rows:
        rolled = pltpu.roll(rot, shift, 0)
        take = (s & shift) != 0                                # [1, 128]
        rot = jnp.where(take, rolled, rot)
        shift <<= 1
    return rot


def _rotate_rows_xla(table: jax.Array, sbits: jax.Array,
                     rows: int) -> jax.Array:
    """:func:`_rotate_rows` as plain XLA (``jnp.roll`` in place of
    ``pltpu.roll`` — same function, bitwise).  Stage 1 of the staged
    big-table path and of the reference interpret lowering."""
    s = (sbits[0:1, :] % jnp.uint32(rows)).astype(jnp.int32)   # [1, 128]
    rot = table
    shift = 1
    while shift < rows:
        take = (s & shift) != 0
        rot = jnp.where(take, jnp.roll(rot, shift, axis=0), rot)
        shift <<= 1
    return rot


def interpret_impl(interpret):
    """Normalize the ``interpret`` argument of the Pallas entry points.

    ``False`` -> None (compiled TPU lowering).  ``True``/'reference' ->
    ``'reference'``: the pure-JAX lowering of the kernel math, with the
    hardware PRNG reproduced as the Mosaic interpreter defines it
    off-TPU (all-zero draws) — compiled by XLA, so driver-level
    interpret runs (CPU tests, the multichip dry run) execute as
    ordinary jitted programs instead of paying a Python interpreter
    callback per pallas_call per plane per round (the 8-device dry
    run's fused families sat at ~360-460 ms steady for exactly that
    reason).  ``'mosaic'`` -> the real Mosaic interpreter (kernel-body
    tests)."""
    if not interpret:
        return None
    if interpret is True or interpret == "reference":
        return "reference"
    if interpret == "mosaic":
        return "mosaic"
    raise ValueError(f"interpret must be a bool, 'reference' or 'mosaic'; "
                     f"got {interpret!r}")


def interpret_params(on):
    """The ``interpret=`` argument of a ``pallas_call``."""
    return pltpu.InterpretParams() if on else False


def _phantom_word_keep(rows: int, n_valid_words: int, tail_mask: int):
    """uint32[rows, 128] keep-mask zeroing phantom words (and the tail
    word's phantom bits) — the reference twin of the kernels' inline
    phantom masking."""
    word_id = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    full = word_id < (n_valid_words - (1 if tail_mask else 0))
    keep = jnp.where(full, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    if tail_mask:
        keep = jnp.where(word_id == n_valid_words - 1,
                         jnp.uint32(tail_mask), keep)
    return keep


def _fused_round_ref(table, n: int, fanout: int, inject_bits,
                     drop_threshold, alive_table,
                     plane_sharing: int, cut_bits=None) -> jax.Array:
    """Pure-JAX reference of :func:`_fused_round_kernel` (single-rumor,
    node-packed).  Bitwise-equal to the Mosaic interpreter on the same
    operands (tests/test_pallas_round.py); hardware-PRNG draws reproduce
    the interpreter's off-TPU stub (zeros).  ``drop_threshold`` is a
    plain traced scalar here — the reference twin of the real path's
    SMEM operand, bitwise-pinned against it like every fused twin."""
    rows = table.shape[0]
    inject = inject_bits is not None
    if inject:
        sbits = jnp.asarray(inject_bits[0], jnp.uint32)
        rbits = jnp.asarray(inject_bits[1], jnp.uint32)
    else:
        sbits = jnp.zeros((8, LANES), jnp.uint32)
    src = table & alive_table if alive_table is not None else table
    rot = _rotate_rows_xla(src, sbits, rows)
    rot_cut = (_rotate_rows_xla(cut_bits, sbits, rows)
               if cut_bits is not None else None)
    thr = jnp.asarray(drop_threshold, jnp.int32).astype(jnp.uint32)

    acc = table
    for k in range(0, BITS, plane_sharing):
        for f in range(fanout):
            rb = (rbits[(k // plane_sharing) * fanout + f] if inject
                  else jnp.zeros((rows, LANES), jnp.uint32))
            for j in range(plane_sharing):
                sh = jnp.uint32(12 * j)
                m = ((rb >> sh) & jnp.uint32(LANES - 1)).astype(jnp.int32)
                c = (rb >> (sh + jnp.uint32(7))) & jnp.uint32(BITS - 1)
                partner = jnp.take_along_axis(rot, m, axis=1)
                bit = (partner >> c) & jnp.uint32(1)
                keep = (rb >> jnp.uint32(12)) >= thr
                bit = jnp.where(keep, bit, jnp.uint32(0))
                if cut_bits is not None:
                    pside = (jnp.take_along_axis(rot_cut, m, axis=1)
                             >> c) & jnp.uint32(1)
                    dside = (cut_bits >> jnp.uint32(k + j)) & jnp.uint32(1)
                    bit = jnp.where(pside == dside, bit, jnp.uint32(0))
                if alive_table is not None:
                    bit = bit & ((alive_table >> jnp.uint32(k + j))
                                 & jnp.uint32(1))
                acc = acc | (bit << jnp.uint32(k + j))

    n_valid_words = -(-n // BITS)
    tail = n % BITS
    tail_mask = ((1 << tail) - 1) if tail else 0
    return acc & _phantom_word_keep(rows, n_valid_words, tail_mask)


def _fused_mr_round_ref(table, n: int, fanout: int, inject_bits,
                        drop_threshold, alive_words,
                        cut_words=None) -> jax.Array:
    """Pure-JAX reference of :func:`_fused_mr_kernel` (multi-rumor,
    one-word-per-node).  Same contract as :func:`_fused_round_ref`."""
    rows = table.shape[0]
    inject = inject_bits is not None
    if inject:
        sbits_all = jnp.asarray(inject_bits[0], jnp.uint32)
        rbits_all = jnp.asarray(inject_bits[1], jnp.uint32)
    src = table & alive_words if alive_words is not None else table
    thr = jnp.asarray(drop_threshold, jnp.int32).astype(jnp.uint32)

    acc = table
    for f in range(fanout):
        sbits = (sbits_all[f] if inject
                 else jnp.zeros((8, LANES), jnp.uint32))
        rot = _rotate_rows_xla(src, sbits, rows)
        rb = (rbits_all[f] if inject
              else jnp.zeros((rows, LANES), jnp.uint32))
        m = (rb & jnp.uint32(LANES - 1)).astype(jnp.int32)
        partner = jnp.take_along_axis(rot, m, axis=1)
        keep = (rb >> jnp.uint32(12)) >= thr
        partner = jnp.where(keep, partner, jnp.uint32(0))
        if cut_words is not None:
            rot_cut = _rotate_rows_xla(cut_words, sbits, rows)
            pside = jnp.take_along_axis(rot_cut, m, axis=1)
            partner = jnp.where(pside == cut_words, partner,
                                jnp.uint32(0))
        if alive_words is not None:
            partner = partner & alive_words
        acc = acc | partner

    node_id = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    return jnp.where(node_id < n, acc, jnp.uint32(0))


def _fused_call(kernel, rows: int, seed, round_, table, inject_bits,
                interpret: bool, round_salt: int = 0, alive_table=None,
                drop_threshold=0, cut_words=None):
    """Shared pallas_call plumbing for the fused kernels: SMEM seed pair,
    the SMEM fault scalar (the 20-bit drop threshold as a scalar-prefetch
    operand — a traced RUNTIME value since the operand PR, so a fault
    sweep over drop rates/ramps re-enters one executable), VMEM table
    aliased into the output, optional injected-bits operands, optional
    alive-bitmap operand, optional partition side-mask operand (fault
    masks — after the inject pair, matching the kernels' ``rest``
    unpack order).

    Donation contract: the whole-table value kernels ALWAYS declare the
    ``{2: 0}`` table->output alias.  It is safe because nothing after
    this call reads the pre-round table — the entry points consume their
    table operand exactly once, and the jit wrappers never donate the
    caller's own buffers — and it is what lets the compiled
    while_loop/scan drivers update the table in place every round
    (pallas_call lowers to a custom call; without the declared alias XLA
    cannot reuse the buffer and copies the full table per round).  The
    staged big-table path has a subtler per-draw rule — see the
    donation-contract comment in :func:`_fused_mr_round_big`."""
    seeds = jnp.stack([jnp.asarray(seed, jnp.int32) * jnp.int32(_ROUND_MIX),
                       jnp.asarray(round_, jnp.int32)
                       ^ jnp.int32(round_salt)])
    fault = jnp.asarray(drop_threshold, jnp.int32).reshape((1,))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM)]
    operands = [seeds, fault, table]
    if inject_bits is not None:
        sbits, rbits = inject_bits
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM),
                     pl.BlockSpec(memory_space=pltpu.VMEM)]
        operands += [jnp.asarray(sbits, jnp.uint32),
                     jnp.asarray(rbits, jnp.uint32)]
    if alive_table is not None:
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)]
        operands += [jnp.asarray(alive_table, jnp.uint32)]
    if cut_words is not None:
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)]
        operands += [jnp.asarray(cut_words, jnp.uint32)]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        input_output_aliases={2: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret_params(interpret),
    )(*operands)


def _fused_round_kernel(seed_ref, fault_ref, tin_ref, *rest, rows: int,
                        fanout: int, n_valid_words: int, tail_mask: int,
                        inject: bool, has_alive: bool = False,
                        plane_sharing: int = 1, has_cut: bool = False):
    """One pull round, entirely in VMEM.  See module doc for the scheme.

    ``inject=True`` replaces the hardware PRNG with caller-supplied bit
    arrays (extra operands) so the kernel *math* — rolls, gather, bit
    planes, masking — is unit-testable on CPU, where the Mosaic
    interpreter stubs ``prng_random_bits`` with zeros (tests/test_pallas.py
    round-1 finding).  The TPU path draws the same shapes from the hw PRNG.

    Fault operands (static SI semantics round 4, runtime operands since
    the operand PR): ``has_alive`` adds an alive-bitmap operand
    (node-packed like the table) — dead nodes SERVE nothing (their bits
    are cleared from the rotation source) and ACQUIRE nothing (plane
    contributions masked by the destination's alive bit); their own
    initial bits stay put, like the XLA path's dark nodes.  The 20-bit
    drop threshold (round(drop_prob * 2^20)) rides ``fault_ref`` — an
    SMEM SCALAR, not a compile-time constant — and drops an individual
    pull when the free bits 12..31 of its draw fall below it; bits 0..6
    are the lane and 7..11 the bit choice, so the drop coin is
    independent of the partner choice.  The compare always runs
    (threshold 0 keeps every pull — bitwise the old elided lowering),
    which is what lets drop-rate RAMPS move the threshold per round
    with zero recompiles.  ``has_cut`` adds the partition SIDE mask
    (render_cut_bits: bit b of word w is 1 iff node 32w+b sits at or
    above the cut; -1 renders every real node on one side — inert):
    the mask rotates through the SAME per-lane shifts as the table, so
    the partner's side comes out of one extra in-row gather, and a
    pull is kept only when both endpoints share a side — the
    lost-for-this-round-only semantics of ops/nemesis.same_side."""
    if inject:
        if has_alive and has_cut:
            sbits_ref, rbits_ref, alive_ref, cut_ref, tout_ref = rest
        elif has_alive:
            sbits_ref, rbits_ref, alive_ref, tout_ref = rest
        elif has_cut:
            sbits_ref, rbits_ref, cut_ref, tout_ref = rest
        else:
            sbits_ref, rbits_ref, tout_ref = rest
    else:
        if has_alive and has_cut:
            alive_ref, cut_ref, tout_ref = rest
        elif has_alive:
            alive_ref, tout_ref = rest
        elif has_cut:
            cut_ref, tout_ref = rest
        else:
            (tout_ref,) = rest
        pltpu.prng_seed(seed_ref[0], seed_ref[1])
    table = tin_ref[:]
    alive = alive_ref[:] if has_alive else None
    cut_tab = cut_ref[:] if has_cut else None
    thr = fault_ref[0].astype(jnp.uint32)

    # Stage 1: one shared rotation per round (all bit planes and fanout
    # draws reuse it; the MR kernel rotates per fanout draw instead).
    # Dead nodes serve nothing: cleared from the rotation SOURCE only —
    # their own accumulated bits are untouched.  The partition side
    # mask rides the same rotation so the partner's side is one more
    # in-row gather.
    if inject:
        sbits = sbits_ref[:]
    else:
        sbits = pltpu.bitcast(pltpu.prng_random_bits((8, LANES)), jnp.uint32)
    rot = _rotate_rows(table & alive if has_alive else table, sbits, rows)
    rot_cut = _rotate_rows(cut_tab, sbits, rows) if has_cut else None

    # Stages 2+3: per destination bit-plane k, draw (lane m, bit c) per
    # word, gather the partner word in-row, pull bit c into plane k.
    # ``plane_sharing=2`` (round-5 opt-in — the roofline's PRNG-harvest
    # candidate): a PAIR of adjacent planes splits one 32-bit draw —
    # plane j of the pair uses bits 12j..12j+6 (lane) and 12j+7..12j+11
    # (bit choice), disjoint bits of one uniform word, so per-node
    # partner marginals stay exactly uniform while the PRNG word count
    # halves.  A DIFFERENT stream from sharing=1 (engine-level
    # statistical contract, like fused-vs-threefry); incompatible with
    # the drop coin (which owns bits 12..31 at sharing=1), enforced by
    # the caller.
    acc = table
    for k in range(0, BITS, plane_sharing):
        for f in range(fanout):
            if inject:
                rb = rbits_ref[(k // plane_sharing) * fanout + f]
            else:
                rb = pltpu.bitcast(pltpu.prng_random_bits((rows, LANES)),
                                   jnp.uint32)
            for j in range(plane_sharing):
                sh = jnp.uint32(12 * j)
                m = ((rb >> sh) & jnp.uint32(LANES - 1)).astype(jnp.int32)
                c = (rb >> (sh + jnp.uint32(7))) & jnp.uint32(BITS - 1)
                partner = jnp.take_along_axis(rot, m, axis=1)
                bit = (partner >> c) & jnp.uint32(1)
                keep = (rb >> jnp.uint32(12)) >= thr
                bit = jnp.where(keep, bit, jnp.uint32(0))
                if has_cut:
                    pside = (jnp.take_along_axis(rot_cut, m, axis=1)
                             >> c) & jnp.uint32(1)
                    dside = (cut_tab >> jnp.uint32(k + j)) & jnp.uint32(1)
                    bit = jnp.where(pside == dside, bit, jnp.uint32(0))
                if has_alive:
                    bit = bit & ((alive >> jnp.uint32(k + j))
                                 & jnp.uint32(1))
                acc = acc | (bit << jnp.uint32(k + j))

    # Zero phantom words so phantom nodes never read as infected.
    word_id = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    full = word_id < (n_valid_words - (1 if tail_mask else 0))
    keep = jnp.where(full, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    if tail_mask:
        keep = jnp.where(word_id == n_valid_words - 1,
                         jnp.uint32(tail_mask), keep)
    tout_ref[:] = acc & keep


@functools.partial(jax.jit,
                   static_argnames=("n", "fanout", "interpret",
                                    "plane_sharing"))
def _fused_pull_round_jit(table, seed, round_, drop_threshold, n: int,
                          fanout: int, interpret, inject_bits,
                          alive_table, plane_sharing: int,
                          cut_words) -> jax.Array:
    if interpret_impl(interpret) == "reference":
        return _fused_round_ref(table, n, fanout, inject_bits,
                                drop_threshold, alive_table,
                                plane_sharing, cut_words)
    rows = table.shape[0]
    n_valid_words = -(-n // BITS)
    tail = n % BITS
    tail_mask = ((1 << tail) - 1) if tail else 0
    kernel = functools.partial(
        _fused_round_kernel, rows=rows, fanout=fanout,
        n_valid_words=n_valid_words, tail_mask=tail_mask,
        inject=inject_bits is not None,
        has_alive=alive_table is not None,
        plane_sharing=plane_sharing,
        has_cut=cut_words is not None)
    return _fused_call(kernel, rows, seed, round_, table, inject_bits,
                       interpret, alive_table=alive_table,
                       drop_threshold=drop_threshold, cut_words=cut_words)


def fused_pull_round(table: jax.Array, seed: jax.Array, round_: jax.Array,
                     n: int, fanout: int = 1, interpret: bool = False,
                     inject_bits=None, drop_threshold=0,
                     alive_table=None, plane_sharing: int = 1,
                     cut_words=None) -> jax.Array:
    """Apply one fused pull round to a node-packed table. Pure; jittable.

    ``inject_bits`` (tests only): a ``(sbits uint32[8,128], rbits
    uint32[fanout*32//plane_sharing, rows, 128])`` pair replacing the
    hardware PRNG — see _fused_round_kernel.  ``drop_threshold`` is a
    RUNTIME operand since the operand PR (an SMEM scalar on the real
    path, a traced scalar in the reference lowering) — pass the 20-bit
    int OR a traced per-round value from a nemesis drop table;
    ``alive_table`` is the node-packed alive bitmap and ``cut_words``
    the partition side mask (:func:`render_cut_bits`); all default off
    and leave the fault-free trajectory bitwise unchanged.
    ``plane_sharing=2`` halves the PRNG words per round by splitting one
    draw's disjoint bit-fields across an adjacent plane pair — an
    OPT-IN different stream (kernel docstring); requires no drop coin
    and no partition (their bits/side gathers overlap the pair split).

    ``interpret`` may be a bool or an impl name: ``True``/'reference'
    is the pure-JAX reference lowering (fast, compiled by XLA — the
    driver-test and dry-run path), 'mosaic' the real Mosaic interpreter
    (kernel-body tests; see :func:`interpret_impl`).
    """
    if plane_sharing not in (1, 2):
        raise ValueError(f"plane_sharing must be 1 or 2, "
                         f"got {plane_sharing}")
    # plane sharing requires a provably-ZERO drop coin: a traced
    # threshold cannot be proven zero at trace time, so it is rejected
    # outright — silently correlated drops (the coin bits overlap the
    # pair split) would be worse than the refusal
    concrete_zero = (isinstance(drop_threshold, (int, float))
                     and not drop_threshold)
    if plane_sharing > 1 and (not concrete_zero or cut_words is not None):
        raise ValueError(
            "plane_sharing=2 splits the draw's bit-fields across a "
            "plane pair and leaves no room for the 20-bit drop coin "
            "(concrete or traced) or the partition side gather; use "
            "plane_sharing=1 with drop_prob/partition faults")
    return _fused_pull_round_jit(table, seed, round_,
                                 jnp.asarray(drop_threshold, jnp.int32),
                                 n, fanout, interpret, inject_bits,
                                 alive_table, plane_sharing, cut_words)


# ---------------------------------------------------------------------------
# Multi-rumor variant: one VMEM element = one node's 32-rumor digest word.
# ---------------------------------------------------------------------------
#
# The factored partner draw above works on ANY [rows, 128] uint32 table; for
# up to 32 rumors the element at (row i, lane j) holds node ``i*128 + j``'s
# rumor word (models/si_packed layout, one word per node).  A pull is then
# ONE in-row gather of the partner's whole word OR-ed into the destination —
# no bit-plane loop at all, because a real pull exchanges the full digest
# (one partner per node per round, all rumors ride the same exchange,
# exactly models/si.py's semantics).  At 10M nodes the table is 40 MB —
# VMEM-resident on v5e.  Same distributional contract as the single-rumor
# kernel: partner uniform over the padded node set, 128 shared per-lane row
# shifts per (round, fanout) draw, self-pulls not excluded (1/N no-op).

def mr_rows(n: int) -> int:
    """Rows (multiple of 8) covering n nodes at one word per node."""
    r = -(-n // LANES)
    return max(8, -(-r // 8) * 8)


def word_pack(seen: jax.Array) -> jax.Array:
    """bool[N, R<=32] -> uint32[mr_rows(N), 128] one-word-per-node table."""
    n, r = seen.shape
    if r > BITS:
        raise ValueError(f"multirumor fused kernel holds <= {BITS} rumors "
                         f"per word; got {r}")
    weights = (jnp.uint32(1) << jnp.arange(r, dtype=jnp.uint32))
    words = jnp.sum(seen.astype(jnp.uint32) * weights[None, :], axis=1,
                    dtype=jnp.uint32)
    rows = mr_rows(n)
    flat = jnp.zeros((rows * LANES,), jnp.uint32).at[:n].set(words)
    return flat.reshape(rows, LANES)


def word_unpack(table: jax.Array, n: int, rumors: int) -> jax.Array:
    """uint32[rows, 128] -> bool[n, rumors]."""
    flat = table.reshape(-1)[:n]
    shifts = jnp.arange(rumors, dtype=jnp.uint32)
    return ((flat[:, None] >> shifts[None, :]) & jnp.uint32(1)).astype(bool)


def coverage_words(table: jax.Array, n: int, rumors: int) -> jax.Array:
    """Min-over-rumors infected fraction (phantom words stay zero)."""
    shifts = jnp.arange(rumors, dtype=jnp.uint32)
    per_rumor = jnp.sum(
        ((table.reshape(-1)[:, None] >> shifts[None, :]) & jnp.uint32(1)
         ).astype(jnp.float32), axis=0)
    return jnp.min(per_rumor) / jnp.float32(n)


def _fused_mr_kernel(seed_ref, fault_ref, tin_ref, *rest, rows: int,
                     fanout: int, n: int, inject: bool,
                     has_alive: bool = False, has_cut: bool = False):
    """One multi-rumor pull round, table fully VMEM-resident.

    Fault operands (round 4's static masks, runtime operands since the
    operand PR; same contract as _fused_round_kernel, adapted to the
    one-word-per-NODE layout): the alive operand holds 0xFFFFFFFF for
    alive nodes and 0 for dead ones — dead nodes serve nothing
    (cleared from the rotation source) and acquire nothing (the
    gathered partner word is AND-masked), while their own word stays
    put.  The 20-bit drop threshold rides the ``fault_ref`` SMEM
    scalar and drops a whole pull (all rumors ride one exchange) on
    bits 12..31 of its draw; the lane choice uses bits 0..6, so the
    coin is independent.  The compare always runs (threshold 0 keeps
    everything — bitwise the old elided lowering).  ``has_cut`` adds
    the partition side-word mask (render_cut_words: 0xFFFFFFFF at or
    above the cut): it rotates through the SAME per-lane shifts as the
    table per fanout draw, the partner's side is one extra in-row
    gather, and cross-side pulls are destroyed for this round only."""
    if inject:
        if has_alive and has_cut:
            sbits_ref, rbits_ref, alive_ref, cut_ref, tout_ref = rest
        elif has_alive:
            sbits_ref, rbits_ref, alive_ref, tout_ref = rest
        elif has_cut:
            sbits_ref, rbits_ref, cut_ref, tout_ref = rest
        else:
            sbits_ref, rbits_ref, tout_ref = rest
    else:
        if has_alive and has_cut:
            alive_ref, cut_ref, tout_ref = rest
        elif has_alive:
            alive_ref, tout_ref = rest
        elif has_cut:
            cut_ref, tout_ref = rest
        else:
            (tout_ref,) = rest
        pltpu.prng_seed(seed_ref[0], seed_ref[1])
    table = tin_ref[:]
    alive = alive_ref[:] if has_alive else None
    cut_w = cut_ref[:] if has_cut else None
    thr = fault_ref[0].astype(jnp.uint32)
    src = table & alive if has_alive else table

    acc = table
    for f in range(fanout):
        # fresh per-lane row shifts per fanout draw (128 iid shifts)
        if inject:
            sbits = sbits_ref[f]
        else:
            sbits = pltpu.bitcast(pltpu.prng_random_bits((8, LANES)),
                                  jnp.uint32)
        rot = _rotate_rows(src, sbits, rows)
        # per-element lane choice -> partner's whole rumor word
        if inject:
            rb = rbits_ref[f]
        else:
            rb = pltpu.bitcast(pltpu.prng_random_bits((rows, LANES)),
                               jnp.uint32)
        m = (rb & jnp.uint32(LANES - 1)).astype(jnp.int32)
        partner = jnp.take_along_axis(rot, m, axis=1)
        keep = (rb >> jnp.uint32(12)) >= thr
        partner = jnp.where(keep, partner, jnp.uint32(0))
        if has_cut:
            rot_cut = _rotate_rows(cut_w, sbits, rows)
            pside = jnp.take_along_axis(rot_cut, m, axis=1)
            partner = jnp.where(pside == cut_w, partner, jnp.uint32(0))
        if has_alive:
            partner = partner & alive
        acc = acc | partner

    # zero phantom words (node id >= n)
    node_id = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    tout_ref[:] = jnp.where(node_id < n, acc, jnp.uint32(0))


# --- Big-table multi-rumor path: XLA rotation + grid-blocked gather -----
#
# The value kernel holds ~4 table-sized VMEM windows; at N=10M (38.15 MiB
# one-word-per-node table) that is an XLA-measured 152.7 MiB — OOM against
# the 128 MiB chip.  Attempts to squeeze the whole round into one
# whole-table kernel bottom out around 132-134 MiB (3 windows + register
# spill slots), so the big path splits the round on its natural seam
# instead:
#
#   * Stage 1 (XLA): the per-lane row rotation ``rot[i, j] =
#     table[(i - s_j) mod rows, j]`` as ceil(log2 rows) static
#     ``jnp.roll`` + lane-select stages.  Pure blocked data movement —
#     XLA streams it through HBM with no table-sized VMEM resident, at
#     HBM bandwidth (~17 stages x 2 x 38 MiB ≈ 1.3 GB ≈ 2 ms/round at
#     10M nodes).
#   * Stage 2 (Pallas, grid over row blocks): per-element lane choice +
#     in-row partner-word gather (``tpu.dynamic_gather`` — the part XLA
#     cannot do efficiently) + OR-merge + phantom masking, with
#     block-sized double-buffered windows (3 x 512 KiB).
#
# Peak VMEM is block-sized, so this path has NO upper bound on n.  The
# 128 per-lane shifts come from a threefry draw (tiny, XLA stage); the
# per-block gather bits come from the hardware PRNG seeded per block —
# the distributional contract (exactly uniform per-node partner
# marginals, 128 shared per-lane row shifts per round) is identical to
# the value kernel, and on injected bits the two are bitwise-equal
# (tests/test_pallas_round.py).

_MR_GATHER_BLOCK = 1024   # rows per grid step (512 KiB windows)


def _mr_gather_kernel(seed_ref, fault_ref, tin_ref, rot_ref, *rest, n: int,
                      block: int, inject: bool, has_alive: bool = False,
                      has_cut: bool = False):
    """Grid step: partner lane-gather from the pre-rotated table + OR.
    Fault operands as in _fused_mr_kernel — the rotation source is
    already serve-masked by the caller's XLA stage (which also rotated
    the partition side mask when ``has_cut``: sbits live in the XLA
    stage on this path, so the side rotation happens there and this
    kernel only lane-gathers the partner's side); this kernel applies
    the drop coin (the ``fault_ref`` SMEM scalar), the side compare,
    and the destination's acquire mask."""
    b = pl.program_id(0)
    if inject:
        if has_alive and has_cut:
            rbits_ref, alive_ref, rot_cut_ref, cut_ref, tout_ref = rest
        elif has_alive:
            rbits_ref, alive_ref, tout_ref = rest
        elif has_cut:
            rbits_ref, rot_cut_ref, cut_ref, tout_ref = rest
        else:
            rbits_ref, tout_ref = rest
        rb = rbits_ref[0]
    else:
        if has_alive and has_cut:
            alive_ref, rot_cut_ref, cut_ref, tout_ref = rest
        elif has_alive:
            alive_ref, tout_ref = rest
        elif has_cut:
            rot_cut_ref, cut_ref, tout_ref = rest
        else:
            (tout_ref,) = rest
        # per-block stream: fold the block id into the round seed word
        # (prng_set_seed_32 rejects a third traced operand)
        pltpu.prng_seed(seed_ref[0],
                        seed_ref[1] + b * jnp.int32(-1640531527))
        rb = pltpu.bitcast(pltpu.prng_random_bits((block, LANES)),
                           jnp.uint32)
    m = (rb & jnp.uint32(LANES - 1)).astype(jnp.int32)
    partner = jnp.take_along_axis(rot_ref[:], m, axis=1)
    keep = (rb >> jnp.uint32(12)) >= fault_ref[0].astype(jnp.uint32)
    partner = jnp.where(keep, partner, jnp.uint32(0))
    if has_cut:
        pside = jnp.take_along_axis(rot_cut_ref[:], m, axis=1)
        partner = jnp.where(pside == cut_ref[:], partner, jnp.uint32(0))
    if has_alive:
        partner = partner & alive_ref[:]
    node_id = ((jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 0)
                + b * block) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1))
    tout_ref[:] = jnp.where(node_id < n, tin_ref[:] | partner,
                            jnp.uint32(0))


def _fused_mr_round_big(table: jax.Array, seed, round_, n: int,
                        interpret: bool, inject_bits,
                        drop_threshold=0,
                        alive_words=None, fanout: int = 1,
                        cut_words=None) -> jax.Array:
    """One multi-rumor pull round via the staged big-table path.
    Fault masks as in the value kernel: the serve mask is applied to the
    rotation SOURCE in the XLA stage, the drop coin and acquire mask in
    the grid kernel.

    ``fanout > 1`` (round 5, VERDICT r4 task 8) runs the two stages once
    per draw, OR-accumulating into the running table — the value
    kernel's per-fanout loop unrolled at the stage level.  Every draw's
    rotation reads the PRE-round serve-masked table (matching the value
    kernel, whose rotation source is fixed while ``acc`` accumulates),
    and each draw gets its own shift/gather streams (draw 0's streams
    are byte-identical to the old fanout-1 lowering, so existing
    digests and fanout-1 trajectories are unchanged).  Cost is
    ~fanout x the fanout-1 HBM traffic — the natural price of more
    draws on a table too big for VMEM."""
    rows = table.shape[0]
    block = min(_MR_GATHER_BLOCK, rows)
    impl = interpret_impl(interpret)

    if inject_bits is not None:
        sbits_all = jnp.asarray(inject_bits[0], jnp.uint32)  # [F, 8, 128]
        rbits_all = jnp.asarray(inject_bits[1], jnp.uint32)  # [F, rows, 128]
    else:
        base = jax.random.PRNGKey(
            jnp.uint32(jnp.asarray(seed, jnp.int32)) * jnp.uint32(_ROUND_MIX)
            + jnp.uint32(0x5D0))
        rkey = jax.random.fold_in(base, jnp.asarray(round_, jnp.int32))

    rows_pad = -(-rows // block) * block
    zpad = (jnp.zeros((rows_pad - rows, LANES), jnp.uint32)
            if rows_pad != rows else None)

    def _padded(x):
        return x if zpad is None else jnp.concatenate([x, zpad], axis=0)

    src = table if alive_words is None else table & alive_words
    alive_p = None if alive_words is None else _padded(alive_words)
    cut_p = None if cut_words is None else _padded(cut_words)
    thr = jnp.asarray(drop_threshold, jnp.int32)
    thr_u = thr.astype(jnp.uint32)
    # pad the accumulator ONCE and feed it back padded between draws
    # (the kernel zeroes pad rows in its output anyway); re-padding and
    # re-slicing per draw would add two full-table HBM copies per draw
    acc_p = _padded(table)
    for f in range(fanout):
        if inject_bits is not None:
            sbits = sbits_all[f]
        else:
            # draw 0 keeps the pre-round-5 stream byte-identical; later
            # draws fold the static draw index into the round key
            kf = rkey if f == 0 else jax.random.fold_in(rkey, f)
            sbits = jax.random.bits(kf, (8, LANES), jnp.uint32)

        # Stage 1 (XLA): per-lane row rotation, binary decomposition —
        # always from the PRE-round serve-masked table.  The partition
        # side mask rides the same shifts (the sbits live HERE on the
        # staged path, so the side rotation is an XLA stage too).
        rot = _padded(_rotate_rows_xla(src, sbits, rows))
        rot_cut_p = (None if cut_words is None
                     else _padded(_rotate_rows_xla(cut_words, sbits,
                                                   rows)))

        # Stage 2: lane choice + in-row gather + OR + mask.  Rows pad up
        # to a block multiple (pad rows are phantom nodes — the kernel
        # masks them to zero) so every grid step sees a full block.
        rbits = None
        if inject_bits is not None:
            rbits = rbits_all[f:f + 1]
            if zpad is not None:
                rbits = jnp.concatenate(
                    [rbits, jnp.zeros((1, rows_pad - rows, LANES),
                                      jnp.uint32)], axis=1)

        if impl == "reference":
            # whole-table jnp twin of the grid kernel (the per-block
            # split is pure blocking; with no inject the hw-PRNG draw is
            # the interpreter's off-TPU stub, zeros)
            rb = (rbits[0] if rbits is not None
                  else jnp.zeros((rows_pad, LANES), jnp.uint32))
            m = (rb & jnp.uint32(LANES - 1)).astype(jnp.int32)
            partner = jnp.take_along_axis(rot, m, axis=1)
            keep = (rb >> jnp.uint32(12)) >= thr_u
            partner = jnp.where(keep, partner, jnp.uint32(0))
            if cut_p is not None:
                pside = jnp.take_along_axis(rot_cut_p, m, axis=1)
                partner = jnp.where(pside == cut_p, partner,
                                    jnp.uint32(0))
            if alive_p is not None:
                partner = partner & alive_p
            node_id = (jax.lax.broadcasted_iota(
                jnp.int32, (rows_pad, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(
                    jnp.int32, (rows_pad, LANES), 1))
            acc_p = jnp.where(node_id < n, acc_p | partner, jnp.uint32(0))
            continue

        # draw 0's per-block salt is the pre-round-5 constant; later
        # draws perturb seeds[1] with a static odd multiplier
        seeds = jnp.stack(
            [jnp.asarray(seed, jnp.int32) * jnp.int32(_ROUND_MIX),
             jnp.asarray(round_, jnp.int32)
             ^ jnp.int32(0x5D0 + 0x51ED * f)])
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec((block, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((block, LANES), lambda i: (i, 0))]
        operands = [seeds, thr.reshape((1,)), acc_p, rot]
        if rbits is not None:
            in_specs.append(pl.BlockSpec((1, block, LANES),
                                         lambda i: (0, i, 0)))
            operands.append(rbits)
        if alive_p is not None:
            in_specs.append(pl.BlockSpec((block, LANES), lambda i: (i, 0)))
            operands.append(alive_p)
        if cut_p is not None:
            in_specs += [pl.BlockSpec((block, LANES), lambda i: (i, 0)),
                         pl.BlockSpec((block, LANES), lambda i: (i, 0))]
            operands += [rot_cut_p, cut_p]
        kernel = functools.partial(_mr_gather_kernel, n=n, block=block,
                                   inject=inject_bits is not None,
                                   has_alive=alive_words is not None,
                                   has_cut=cut_words is not None)
        # Donation contract for the staged path's table operand (the
        # whole-table kernels' simpler rule is at _fused_call; operand
        # index 2 = the table, after the seed pair and the SMEM fault
        # scalar):
        #   * draws f >= 1 always alias {2: 0}: their table operand is
        #     the previous draw's output — dead after this call — so XLA
        #     reuses the buffer in place.
        #   * draw 0 aliases ONLY in a fanout-1 round.  With fanout > 1
        #     every later draw's stage-1 rotation still reads the same
        #     pre-round table buffer (``src``), so a declared draw-0
        #     alias makes XLA re-materialize that still-live buffer via
        #     copy-insertion — a hidden full-table HBM copy per round.
        #     Skipping the alias keeps the table live with no copy; only
        #     the fanout-1 round is in-place, which is the only case the
        #     hot while_loop drivers ever relied on.
        #   * never alias the CALLER's concrete array (block-aligned
        #     rows + eager invocation): donating it would invalidate the
        #     caller's buffer (ADVICE r2).
        eager_caller_buffer = (acc_p is table
                               and not isinstance(table, jax.core.Tracer))
        no_alias = eager_caller_buffer or (f == 0 and fanout > 1)
        acc_p = pl.pallas_call(
            kernel,
            grid=(rows_pad // block,),
            out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.uint32),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
            input_output_aliases={} if no_alias else {2: 0},
            interpret=interpret_params(interpret),
        )(*operands)
    return acc_p[:rows] if rows_pad != rows else acc_p


def _mr_wants_big(table_bytes: int, fanout: int) -> bool:
    """True when the value kernel cannot fit in VMEM (TABLE_COPIES live
    table windows — the same bound check_fused_fits enforces, one
    constant so routing and eligibility can never drift).  The staged
    big-table path covers ANY fanout since round 5 (multi-pass
    accumulation, ~fanout x the HBM traffic — VERDICT r4 task 8);
    ``fanout`` stays in the signature so the routing contract keeps one
    arity across rounds."""
    del fanout
    return TABLE_COPIES * table_bytes > _VMEM_LIMIT_BYTES


def render_alive_words(alive: jax.Array, n: int) -> jax.Array:
    """bool[n] -> the fused engines' one-word-per-NODE [mr_rows(n), 128]
    mask (0xFFFFFFFF alive, 0 dead/phantom) — the ONE rendering of this
    geometry (ops/nemesis.fused_base_words shares it).  In-trace safe."""
    rows = mr_rows(n)
    flat = jnp.zeros((rows * LANES,), jnp.uint32).at[:n].set(
        jnp.where(alive, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)))
    return flat.reshape(rows, LANES)


def render_cut_words(cut, n: int) -> jax.Array:
    """The per-round partition SIDE mask in the fused one-word-per-NODE
    geometry — rendered by the ONE :func:`render_alive_words` geometry
    (the alive-word trick extended to cut words): 0xFFFFFFFF for real
    nodes at or above the cut, 0 below (and for phantoms).  A closed
    window (``cut < 0``) renders every real node on one side, which is
    value-inert in the kernels' side compare — the compiled churn loops
    pass THIS mask every round so partition-free and partition-bearing
    scenarios share one executable.  In-trace safe (``cut`` traced)."""
    ids = jnp.arange(n, dtype=jnp.int32)
    return render_alive_words(ids >= jnp.asarray(cut, jnp.int32), n)


def render_cut_bits(cut, n: int) -> jax.Array:
    """:func:`render_cut_words`'s node-packed twin for the single-rumor
    kernel: bit ``b`` of word ``w`` is 1 iff node ``32w + b`` sits at
    or above the cut (phantom bits 0) — the :func:`node_pack` geometry.
    In-trace safe."""
    ids = jnp.arange(n, dtype=jnp.int32)
    return node_pack(ids >= jnp.asarray(cut, jnp.int32))


def fault_masks_word(fault, n: int, origin: int = 0):
    """(alive_words-or-None, drop_threshold) for the multi-rumor fused
    fault path: the one-word-per-NODE rendering of
    models/state.alive_mask — 0xFFFFFFFF for alive nodes, 0 for dead
    and phantom rows.  In-trace safe, like fault_masks_node_packed."""
    from gossip_tpu.models.state import alive_mask
    alive = alive_mask(fault, n, origin)
    alive_words = None if alive is None else render_alive_words(alive, n)
    return alive_words, drop_threshold_for(fault)


def coverage_words_alive(table: jax.Array, alive_words: jax.Array,
                         rumors: int) -> jax.Array:
    """Alive-weighted min-over-rumors fraction — the fault-run twin of
    :func:`coverage_words` (alive_words elements are 0xFFFFFFFF/0, so
    bit 0 counts alive nodes)."""
    masked = (table & alive_words).reshape(-1)
    n_alive = jnp.sum(alive_words.reshape(-1) & jnp.uint32(1),
                      dtype=jnp.uint32).astype(jnp.float32)
    shifts = jnp.arange(rumors, dtype=jnp.uint32)
    per_rumor = jnp.sum((masked[:, None] >> shifts[None, :])
                        & jnp.uint32(1), axis=0,
                        dtype=jnp.uint32).astype(jnp.float32) / n_alive
    return jnp.min(per_rumor)


def fused_mr_cov_fn(n: int, rumors: int, fault=None, origin: int = 0):
    """``table -> coverage`` for a multi-rumor fused run — the one place
    the alive-weighting choice lives (cf. fused_cov_fn)."""
    if fault is None or not fault.node_death_rate:
        return lambda t: coverage_words(t, n, rumors)

    def cov(t):
        alive_words, _ = fault_masks_word(fault, n, origin)
        return coverage_words_alive(t, alive_words, rumors)
    return cov


@functools.partial(jax.jit, static_argnames=("n", "fanout", "interpret"))
def _fused_mr_round_jit(table, seed, round_, drop_threshold, n: int,
                        fanout: int, interpret, inject_bits, alive_words,
                        cut_words) -> jax.Array:
    rows = table.shape[0]
    if _mr_wants_big(rows * LANES * 4, fanout):
        return _fused_mr_round_big(table, seed, round_, n, interpret,
                                   inject_bits,
                                   drop_threshold=drop_threshold,
                                   alive_words=alive_words, fanout=fanout,
                                   cut_words=cut_words)
    if interpret_impl(interpret) == "reference":
        return _fused_mr_round_ref(table, n, fanout, inject_bits,
                                   drop_threshold, alive_words, cut_words)
    kernel = functools.partial(_fused_mr_kernel, rows=rows, fanout=fanout,
                               n=n, inject=inject_bits is not None,
                               has_alive=alive_words is not None,
                               has_cut=cut_words is not None)
    # round_salt: distinct hw-PRNG stream from the single-rumor kernel
    return _fused_call(kernel, rows, seed, round_, table, inject_bits,
                       interpret, round_salt=0x5D0,
                       alive_table=alive_words,
                       drop_threshold=drop_threshold, cut_words=cut_words)


def fused_multirumor_pull_round(table: jax.Array, seed: jax.Array,
                                round_: jax.Array, n: int, fanout: int = 1,
                                interpret: bool = False,
                                inject_bits=None, drop_threshold=0,
                                alive_words=None,
                                cut_words=None) -> jax.Array:
    """One fused pull round on a one-word-per-node table.  Pure; jittable.

    Tables whose 4-window working set exceeds the VMEM budget route to the
    staged big-table path (XLA rotation + grid-blocked gather; fanout > 1
    multi-pass accumulates, round 5) — same math, block-sized VMEM, no
    upper bound on n.

    ``inject_bits`` (tests only): ``(sbits uint32[fanout, 8, 128], rbits
    uint32[fanout, rows, 128])`` replacing the hardware PRNG so the kernel
    math runs under the CPU interpreter.  ``drop_threshold`` is a
    RUNTIME operand since the operand PR (int or traced per-round
    scalar from a nemesis drop table — SMEM on the real path, traced in
    the reference lowering); ``alive_words``/``cut_words`` are the
    alive mask (fault_masks_word) and partition side mask
    (:func:`render_cut_words`); defaults leave the fault-free
    trajectory bitwise unchanged on BOTH routes."""
    return _fused_mr_round_jit(table, seed, round_,
                               jnp.asarray(drop_threshold, jnp.int32),
                               n, fanout, interpret, inject_bits,
                               alive_words, cut_words)


def fused_table_bytes(n: int, rumors: int) -> int:
    """Size of the fused kernel's VMEM table for this (n, rumors)."""
    rows = n_rows(n) if rumors == 1 else mr_rows(n)
    return rows * LANES * 4


def check_fused_fits(n: int, rumors: int, fanout: int = 1) -> int:
    """Raise ValueError if no fused-kernel variant can fit this (n, rumors,
    fanout) in VMEM; return the table size in bytes.  Callers get a
    friendly error instead of an XLA VMEM-exhausted compile failure.

    Multi-rumor tables whose 4-window value-kernel working set is over
    budget still run via the staged big-table path at any fanout
    (block-sized VMEM — no upper bound on n; the flagship 10M-node x
    32-rumor case lands here; fanout > 1 multi-pass accumulates at
    ~fanout x the HBM traffic, round 5)."""
    tb = fused_table_bytes(n, rumors)
    if TABLE_COPIES * tb <= _VMEM_LIMIT_BYTES:
        return tb
    if rumors > 1 and _mr_wants_big(tb, fanout):
        return tb
    layout = "node-packed bitmap" if rumors == 1 else "one-word-per-node"
    raise ValueError(
        f"fused kernel working set (~{TABLE_COPIES} x "
        f"{tb / (1 << 20):.0f} MiB {layout} table) exceeds the VMEM "
        f"budget at n={n}, rumors={rumors}, fanout={fanout}; reduce "
        "n, use engine='auto' (HBM-resident XLA kernels), or shard the "
        "node dimension")


def init_multirumor_state(n: int, rumors: int, origin: int = 0):
    """FusedState whose table is the one-word-per-node layout; rumor r
    starts at node (origin + r) % n (models/state.init_state contract)."""
    if rumors > BITS:
        raise ValueError(f"multirumor fused kernel holds <= {BITS} rumors")
    seen = jnp.zeros((n, rumors), jnp.bool_)
    origins = (origin + jnp.arange(rumors)) % n
    seen = seen.at[origins, jnp.arange(rumors)].set(True)
    return FusedState(table=word_pack(seen), round=jnp.int32(0),
                      msgs=jnp.float32(0.0))


def compiled_curve_fused(n: int, seed: int, fanout: int = 1,
                         max_rounds: int = 128, origin: int = 0,
                         interpret: bool = False, fault=None):
    """(scan, init): fixed-length ``lax.scan`` over the fused
    single-rumor kernel recording per-round coverage — the curve twin of
    :func:`compiled_until_fused` (no early exit; rounds-to-target is
    derived from the curve by the caller).  Same kernel, same fault
    masks, same alive-weighted coverage chooser."""
    drop_threshold = drop_threshold_for(fault)
    has_alive = fault is not None and bool(fault.node_death_rate)
    cov = fused_cov_fn(n, fault, origin)

    @functools.partial(jax.jit, donate_argnums=0)
    def scan(st: FusedState):
        def body(s, _):
            alive_tab = (fault_masks_node_packed(fault, n, origin)[0]
                         if has_alive else None)
            tab = fused_pull_round(s.table, seed, s.round, n, fanout,
                                   interpret,
                                   drop_threshold=drop_threshold,
                                   alive_table=alive_tab)
            s2 = FusedState(table=tab, round=s.round + 1,
                            msgs=s.msgs + 2.0 * fanout * n)
            return s2, cov(s2.table)
        return jax.lax.scan(body, st, None, length=max_rounds)

    return scan, init_fused_state(n, origin)


def compiled_until_fused_multirumor(n: int, rumors: int, seed: int,
                                    fanout: int = 1,
                                    target_coverage: float = 0.99,
                                    max_rounds: int = 128, origin: int = 0,
                                    interpret: bool = False, fault=None):
    """(loop, init): compiled while_loop to min-over-rumors target coverage
    using the multi-rumor fused kernel (hw PRNG — distributionally equal to
    but a different stream from the threefry path).  ``fault`` enables
    the kernel's static fault masks; the cond switches to the
    alive-weighted coverage (fused_mr_cov_fn)."""
    target = jnp.float32(target_coverage)
    drop_threshold = drop_threshold_for(fault)
    has_alive = fault is not None and bool(fault.node_death_rate)
    cov = fused_mr_cov_fn(n, rumors, fault, origin)

    def step(st: FusedState) -> FusedState:
        # alive words rebuilt IN-TRACE (loop-invariant, hoisted): no
        # O(N) constant baked into the donated jit below
        alive_words = (fault_masks_word(fault, n, origin)[0]
                       if has_alive else None)
        tab = fused_multirumor_pull_round(st.table, seed, st.round, n,
                                          fanout, interpret,
                                          drop_threshold=drop_threshold,
                                          alive_words=alive_words)
        return FusedState(table=tab, round=st.round + 1,
                          msgs=st.msgs + 2.0 * fanout * n)

    @functools.partial(jax.jit, donate_argnums=0)
    def loop(st: FusedState) -> FusedState:
        def cond(s):
            return (cov(s.table) < target) & (s.round < max_rounds)
        return jax.lax.while_loop(cond, step, st)

    return loop, init_multirumor_state(n, rumors, origin)


def compiled_curve_fused_multirumor(n: int, rumors: int, seed: int,
                                    fanout: int = 1, max_rounds: int = 128,
                                    origin: int = 0,
                                    interpret: bool = False, fault=None):
    """(scan, init): the curve twin of
    :func:`compiled_until_fused_multirumor` — fixed-length scan
    recording per-round min-over-rumors coverage (alive-weighted under
    deaths)."""
    drop_threshold = drop_threshold_for(fault)
    has_alive = fault is not None and bool(fault.node_death_rate)
    cov = fused_mr_cov_fn(n, rumors, fault, origin)

    @functools.partial(jax.jit, donate_argnums=0)
    def scan(st: FusedState):
        def body(s, _):
            alive_words = (fault_masks_word(fault, n, origin)[0]
                           if has_alive else None)
            tab = fused_multirumor_pull_round(
                s.table, seed, s.round, n, fanout, interpret,
                drop_threshold=drop_threshold, alive_words=alive_words)
            s2 = FusedState(table=tab, round=s.round + 1,
                            msgs=s.msgs + 2.0 * fanout * n)
            return s2, cov(s2.table)
        return jax.lax.scan(body, st, None, length=max_rounds)

    return scan, init_multirumor_state(n, rumors, origin)


class FusedState(NamedTuple):
    table: jax.Array        # uint32[R, 128] node-packed infection bitmap
    round: jax.Array        # int32
    msgs: jax.Array         # float32 — request+digest accounting, si parity


def init_fused_state(n: int, origin: int = 0) -> FusedState:
    if not 0 <= origin < n:
        raise ValueError(f"origin {origin} out of range for n={n}")
    word = origin >> 5
    table = (jnp.zeros((n_rows(n), LANES), jnp.uint32)
             .at[word // LANES, word % LANES].set(
                 jnp.uint32(1) << jnp.uint32(origin & (BITS - 1))))
    return FusedState(table=table, round=jnp.int32(0),
                      msgs=jnp.float32(0.0))


def coverage_node_packed_alive(table: jax.Array, alive_table: jax.Array):
    """Alive-weighted infected fraction: the fault-run twin of
    :func:`coverage_node_packed` (dead nodes are unreachable, not
    uninformed — si.coverage's weighting).  ``alive_table`` is the
    node-packed alive bitmap; phantoms are zero in BOTH tables."""
    pop = jnp.sum(jax.lax.population_count(table & alive_table),
                  dtype=jnp.uint32)
    n_alive = jnp.sum(jax.lax.population_count(alive_table),
                      dtype=jnp.uint32)
    return pop.astype(jnp.float32) / n_alive.astype(jnp.float32)


def drop_threshold_for(fault) -> int:
    """The static 20-bit drop threshold alone (round(drop_prob * 2^20))
    — for drivers that need the Python int WITHOUT paying the O(n)
    alive-mask build the full fault_masks_* helpers do."""
    drop_prob = 0.0 if fault is None else fault.drop_prob
    return int(round(drop_prob * (1 << 20))) if drop_prob else 0


def fault_masks_node_packed(fault, n: int, origin: int = 0):
    """(alive_table-or-None, drop_threshold) for the fused fault path —
    the node-packed rendering of models/state.alive_mask (static SI
    fault semantics: node_death_rate draws a static dead set, origin
    pinned alive; drop_prob drops individual pulls).  The 20-bit
    threshold quantizes drop_prob to 1/2^20 (< 1e-6), documented like
    the rotation's modulo bias.  Safe to call IN-TRACE: the bitmap is
    pure jnp from the fault config, so jitted callers rebuild it
    loop-invariantly (XLA hoists it) instead of closing over an O(N)
    inline constant — the bind_tables rule."""
    from gossip_tpu.models.state import alive_mask
    alive = alive_mask(fault, n, origin)
    alive_table = None if alive is None else node_pack(alive)
    return alive_table, drop_threshold_for(fault)


def fused_cov_fn(n: int, fault=None, origin: int = 0):
    """``table -> coverage`` for a fused run: alive-weighted exactly when
    the fault draws deaths.  The ONE place the weighting choice lives —
    the while-loop cond and the driver's final report both use it, so
    they can never disagree.  In-trace callers rebuild the alive bitmap
    per call (hoisted); eager callers pay one small draw."""
    if fault is None or not fault.node_death_rate:
        return lambda t: coverage_node_packed(t, n)

    def cov(t):
        alive_tab, _ = fault_masks_node_packed(fault, n, origin)
        return coverage_node_packed_alive(t, alive_tab)
    return cov


def compiled_until_fused(n: int, seed: int, fanout: int = 1,
                         target_coverage: float = 0.99,
                         max_rounds: int = 128, origin: int = 0,
                         interpret: bool = False, fault=None):
    """(loop, init): compiled while_loop to target coverage, fused kernel.

    Same contract as models/si_packed.compiled_until_packed: every node
    issues `fanout` pull requests per round, each answered by one digest
    (msgs += 2*fanout*N per round — phantom/self pulls are counted as real
    requests, matching the threefry path's accounting of dropped pulls;
    dropped and dead-partner pulls likewise).  ``fault`` (round 4)
    enables the kernel's static fault masks; the loop's target compare
    switches to the alive-weighted coverage (fused_cov_fn).
    """
    target = jnp.float32(target_coverage)
    drop_threshold = drop_threshold_for(fault)
    has_alive = fault is not None and bool(fault.node_death_rate)
    cov = fused_cov_fn(n, fault, origin)

    def step(st: FusedState) -> FusedState:
        # alive bitmap rebuilt IN-TRACE (loop-invariant, hoisted): no
        # O(N) constant baked into the donated jit below
        alive_tab = (fault_masks_node_packed(fault, n, origin)[0]
                     if has_alive else None)
        tab = fused_pull_round(st.table, seed, st.round, n, fanout,
                               interpret, drop_threshold=drop_threshold,
                               alive_table=alive_tab)
        return FusedState(table=tab, round=st.round + 1,
                          msgs=st.msgs + 2.0 * fanout * n)

    @functools.partial(jax.jit, donate_argnums=0)
    def loop(st: FusedState) -> FusedState:
        def cond(s):
            return (cov(s.table) < target) & (s.round < max_rounds)
        return jax.lax.while_loop(cond, step, st)

    return loop, init_fused_state(n, origin)
