"""Pallas TPU kernel: hardware-PRNG peer sampling.

The default sampler (ops/sampling.py) derives one threefry key per node —
``vmap(fold_in)`` over N keys costs ~16 ms at N=10M *standalone*.  This
kernel replaces the whole (keys + randint) pipeline with the TPU's native
PRNG (``pltpu.prng_seed`` / ``pltpu.prng_random_bits``), generating targets
at VPU rate, blocked over rows so the draw for a row depends only on
``(seed, round, block_index)`` — deterministic and independent of anything
outside the block, so results are reproducible run-to-run on any mesh that
keeps the same block size (we fix it at compile time).

**Measured outcome (v5e, N=10M packed pull, 2026-07): the threefry path
wins.**  84 ms/round (threefry, XLA fuses key derivation into the gather's
producer chain) vs 126 ms/round (this kernel: the ``pallas_call`` is a
fusion barrier — targets round-trip through HBM).  The kernel is kept as a
correct, hardware-tested alternative sampler and as the seed (sic) of a
future fully-fused pallas round (sampling + gather in one kernel would
remove the barrier); bench.py uses threefry.  Honest numbers beat wishful
kernels.

Trade-offs vs the threefry sampler, stated honestly:

  * DIFFERENT stream — trajectories are not bitwise comparable with the
    jax.random path (parity tests pin the threefry sampler; this one is the
    opt-in fast path, ``sampler="pallas"``).
  * Mapping uint32 -> [0, n) uses modulo, with selection bias n/2^32
    (< 0.25% at n=10M) — irrelevant for epidemic statistics, documented for
    completeness; chi-square uniformity is tested in tests/test_pallas.py.
  * Requires a real TPU; on CPU the public entry point falls back to the
    threefry sampler (interpret-mode is used only by the unit tests, since
    ``pltpu.prng_*`` interprets fine but slowly).

The reference has no sampling at all (it relays to every neighbor,
main.go:72-75); sampled fanout generalizes it (SURVEY.md §7 layer 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gossip_tpu.ops.pallas_round import interpret_impl, interpret_params

_BLOCK_ROWS = 4096          # fixed: part of the determinism contract


def _sampler_kernel(seed_ref, out_ref, *, n_total: int, k: int,
                    exclude_self: bool, block_rows: int):
    b = pl.program_id(0)
    # Per-block seed: mixes the caller's (seed, round) scalar with the block
    # index so blocks draw independent streams.
    # -1640531527 == 0x9E3779B9 (golden-ratio mix) as int32
    pltpu.prng_seed(seed_ref[0] + b * jnp.int32(-1640531527))
    bits = pltpu.bitcast(pltpu.prng_random_bits((block_rows, k)),
                         jnp.uint32)
    if exclude_self and n_total > 1:
        # draw in [0, n-1) then bump values >= own row id (shift trick —
        # same scheme as ops/sampling.sample_peers_complete)
        t = (bits % jnp.uint32(n_total - 1)).astype(jnp.int32)
        rows = (b * block_rows
                + jax.lax.broadcasted_iota(jnp.int32, (block_rows, k), 0))
        out_ref[:] = t + (t >= rows).astype(jnp.int32)
    else:
        out_ref[:] = (bits % jnp.uint32(n_total)).astype(jnp.int32)


def _pad_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("n_rows", "n_total", "k",
                                             "exclude_self", "interpret"))
def sample_targets_pallas(seed: jax.Array, n_rows: int, n_total: int,
                          k: int = 1, exclude_self: bool = True,
                          interpret: bool = False) -> jax.Array:
    """Uniform peers on the implicit complete graph -> int32[n_rows, k].

    ``seed`` is an int32 scalar; callers pass a per-round value (e.g.
    ``seed*prime + round``).  Hardware-PRNG twin of
    ops/sampling.sample_peers_complete (different stream — see module doc).
    """
    rows_pad = _pad_up(n_rows, _BLOCK_ROWS)
    grid = rows_pad // _BLOCK_ROWS
    if interpret_impl(interpret) == "reference":
        # pure-JAX reference of the kernel with the hw PRNG reproduced
        # as the Mosaic interpreter defines it off-TPU (all-zero draws)
        # — compiled by XLA; 'mosaic' forces the real interpreter
        bits = jnp.zeros((rows_pad, k), jnp.uint32)
        if exclude_self and n_total > 1:
            t = (bits % jnp.uint32(n_total - 1)).astype(jnp.int32)
            rows = jax.lax.broadcasted_iota(jnp.int32, (rows_pad, k), 0)
            return (t + (t >= rows).astype(jnp.int32))[:n_rows]
        return (bits % jnp.uint32(n_total)).astype(jnp.int32)[:n_rows]
    kernel = functools.partial(_sampler_kernel, n_total=n_total, k=k,
                               exclude_self=exclude_self,
                               block_rows=_BLOCK_ROWS)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows_pad, k), jnp.int32),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, k), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        # TPU-semantics interpreter (plain interpret=True lacks the TPU
        # PRNG primitives on CPU)
        interpret=interpret_params(interpret),
    )(jnp.asarray([seed], jnp.int32))
    return out[:n_rows]


def round_seed(base_seed: int, round_: jax.Array) -> jax.Array:
    """Fold (run seed, round) into the kernel's int32 seed scalar."""
    return (jnp.int32(base_seed) * jnp.int32(1000003)
            + round_.astype(jnp.int32))


def sample_peers_fast(base_seed: int, round_: jax.Array, n_rows: int,
                      n_total: int, k: int = 1,
                      exclude_self: bool = True) -> jax.Array:
    """Public entry: hardware PRNG on TPU, threefry fallback elsewhere.

    The fallback keeps CPU tests/dev runs working; it does NOT reproduce
    the TPU stream (both streams are valid uniform samplers)."""
    if jax.default_backend() == "tpu":
        return sample_targets_pallas(round_seed(base_seed, round_), n_rows,
                                     n_total, k, exclude_self)
    from gossip_tpu.ops.sampling import sample_peers_complete
    key = jax.random.fold_in(jax.random.key(base_seed),
                             round_.astype(jnp.uint32))
    ids = jnp.arange(n_rows, dtype=jnp.int32)
    return sample_peers_complete(key, ids, n_total, k, exclude_self)
