"""Rumor-plane sharding for the fused Pallas pull kernel: scale RUMORS,
not traffic.

The sharded SI kernels scale the NODE dimension and pay ICI for it every
round (all_gather / all_to_all of digest state — parallel/sharded.py,
sharded_sparse.py).  For massive multi-rumor broadcast the TPU-native
layout is the transpose: shard the RUMOR dimension.  SI pull semantics
(models/si.py, after the reference's whole-log exchange, main.go:126) give
every node ONE partner per round, and the partner's *entire* digest rides
that exchange — rumors never influence partner choice.  So the state
``uint32[W, rows, 128]`` (W word-planes of the one-word-per-node layout,
plane p holding rumors 32p..32p+31) can shard plane-wise across the mesh:
every device runs the SAME fused VMEM kernel (ops/pallas_round.py) on its
local planes, seeded identically, so the hardware PRNG reproduces the SAME
partner draw on every device — one global partner per node per round,
whole digest exchanged, and the merge needs **zero ICI traffic**.  The
only cross-device communication in the whole simulation is the scalar
coverage reduction in the loop condition.

This is the engine for the 10M-node multi-rumor flagship: 32 rumors per
chip-plane, R = 32*W rumors total.  Planes that fit the VMEM envelope run
the whole-table value kernel; bigger planes (N=10M is a 38 MiB table,
~4x that in live windows) route through the staged big-table path of
ops/pallas_round.py (XLA rotation + grid-blocked gather) — same math,
block-sized VMEM, no upper bound on n.  Node-dim sharding of the same
workload would all_gather O(N*W) words per round; here the per-round ICI
cost is a float.

Rumor padding: planes are always full 32-bit words; rumor columns beyond
``rumors`` (and whole planes beyond ``ceil(rumors/32)``, when W is padded
up to the mesh size) are initialized ALL-ONES for real nodes, so their
per-rumor coverage is 1.0 from round 0 and the min-over-rumors metric is
untouched.  Phantom *nodes* stay zero (kernel contract).

Testing: the kernel's inject path (tests-only explicit bit operands)
makes the sharded round bitwise-checkable on the 8-device CPU mesh —
every plane must equal the single-device multi-rumor kernel run with the
same bits (tests/test_sharded_fused.py).  The hw-PRNG path additionally
requires every device to draw the same stream — an EXECUTED assertion,
not an argument: :func:`assert_prng_invariant` runs one identically-
seeded round on one identical plane per device, all_gathers a
(popcount, weighted-mix) digest of each device's output, and requires
all rows equal (tests/test_sharded_fused.py TPU tier; also a
tools/hw_refresh.py step and part of the dryrun program).  The CPU
interpreter stubs the hardware PRNG, so off-TPU the check only proves
the program/collective plumbing; the invariant itself is a TPU artifact.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu.config import RunConfig
from gossip_tpu.ops.pallas_round import (
    BITS, LANES, coverage_words, coverage_words_alive, drop_threshold_for,
    fault_masks_word, fused_multirumor_pull_round, mr_rows, word_pack)

AXIS = "planes"


def make_plane_mesh(n_devices: int) -> Mesh:
    """1-D mesh over the rumor-plane axis."""
    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"need {n_devices} devices, have {len(devs)}")
    return Mesh(devs, (AXIS,))


def plane_count(rumors: int, n_devices: int) -> int:
    """Planes covering ``rumors``, padded up to a multiple of the mesh."""
    w = -(-rumors // BITS)
    return -(-w // n_devices) * n_devices


@functools.lru_cache(maxsize=32)
def _cached_plane_init(n: int, rumors: int, origin: int, mesh: Mesh):
    """Jitted builder of the initial plane stack, memoized per statics.

    The per-plane Python loop below runs ONCE at trace time; every later
    call is an executable-cache hit producing a fresh (donation-safe)
    device buffer under the plane sharding.  Before this, the dry run's
    steady re-entry rebuilt the stack with ~6 eager dispatches per plane
    per call — host-side driver overhead the device-resident loop then
    sat waiting on."""
    w_total = plane_count(rumors, mesh.shape[AXIS])

    def build():
        planes = []
        for p in range(w_total):
            lo = p * BITS
            real = max(0, min(rumors - lo, BITS))
            seen = jnp.concatenate(
                [jnp.zeros((n, real), jnp.bool_),
                 jnp.ones((n, BITS - real), jnp.bool_)], axis=1)
            if real:
                origins = (origin + lo + jnp.arange(real)) % n
                seen = seen.at[origins, jnp.arange(real)].set(True)
            planes.append(word_pack(seen))
        return jnp.stack(planes)

    return jax.jit(build,
                   out_shardings=NamedSharding(mesh, P(AXIS, None, None)))


def init_plane_state(n: int, rumors: int, mesh: Mesh,
                     origin: int = 0) -> jax.Array:
    """uint32[W, rows, 128] plane-sharded state; rumor r starts at node
    (origin + r) % n (models/state.init_state contract); padding rumor
    columns/planes are all-ones (coverage 1.0, inert under OR-merge)."""
    if not 0 <= origin < n:
        raise ValueError(f"origin {origin} out of range for n={n}")
    return _cached_plane_init(n, rumors, origin, mesh)()


def coverage_planes(planes: jax.Array, n: int) -> jax.Array:
    """Min-over-rumors infected fraction across every plane and bit.
    Padding rumors are all-ones (coverage 1.0) so they never win the min."""
    per_plane = jax.vmap(lambda t: coverage_words(t, n, BITS))(planes)
    return jnp.min(per_plane)


def coverage_planes_masked(planes: jax.Array, n: int,
                           alive_words=None) -> jax.Array:
    """The ONE plane-coverage body: plain min-over-rumors fraction, or
    the alive-weighted twin when a death mask rides along (padding
    rumors stay 1.0 under the weighting: every alive node holds their
    all-ones bits).  ``alive_words`` is a runtime OPERAND — the compiled
    drivers share one executable across fault configurations."""
    if alive_words is None:
        return coverage_planes(planes, n)
    per_plane = jax.vmap(
        lambda t: coverage_words_alive(t, alive_words, BITS))(planes)
    return jnp.min(per_plane)


@functools.lru_cache(maxsize=32)
def _cached_alive_words(fault, n: int, origin: int):
    """Jitted builder of the plane engine's alive mask (fault_masks_word
    rendering) — the steady-state twin of :func:`_cached_plane_init`:
    re-entering a faulted driver re-executes a cached program instead of
    dispatching the O(n) mask build eagerly per call."""
    return jax.jit(lambda: fault_masks_word(fault, n, origin)[0])


@functools.lru_cache(maxsize=32)
def _cached_churn_masks(fault, n: int, origin: int):
    """The churn-path mask operands, built ONCE per fault and cached as
    VALUES: ``(cov_words, base_words, die_words, rec_words, cut_tbl,
    thr_tbl)`` — the EVENTUAL alive words the cond/coverage compare
    against (ops/nemesis.fused_eventual_words: permanent churn deaths
    out of the denominator, transient ones in — the heal-convergence
    contract), the static base mask, the die/recover round tables, and
    (since the operand PR) the per-round partition-cut and 20-bit
    drop-threshold tables (ops/nemesis.fused_sched_tables) the
    compiled loop indexes by its round counter.  All runtime OPERANDS:
    a churn sweep over schedules — events, partition windows, AND
    drop-rate ramps — shares one compiled loop (the alive-mask
    runtime-operand trick, extended to cut words and the drop coin).

    Deliberately EAGER, not a per-fault ``jax.jit(build)`` closure: a
    fresh jit per fault bakes the schedule content as trace constants
    and pays one backend compile per SCENARIO — exactly the recompile
    class this PR deletes (the K-scenario compile-count pin in
    tests/test_sharded_fused.py counts it).  Eager builds dispatch
    shape-keyed primitive programs shared across every fault of the
    same shape class, and the lru_cache makes steady re-entry free.
    Caching device buffers is donation-safe here: the compiled loops
    donate only the plane stack, never the mask operands."""
    from gossip_tpu.ops import nemesis as NE
    cut_np, thr_np = NE.fused_sched_tables(fault, n)
    base = NE.fused_base_words(fault, n, origin)
    die_w, rec_w = NE.fused_word_tables(fault, n)
    return (NE.fused_eventual_words(base, die_w, rec_w), base,
            die_w, rec_w, jnp.asarray(cut_np, jnp.int32),
            jnp.asarray(thr_np, jnp.int32))


def fused_planes_cov_fn(n: int, fault=None, origin: int = 0):
    """``planes -> coverage`` — alive-weighted iff the fault draws
    deaths (cf. ops/pallas_round.fused_cov_fn); a fault-binding wrapper
    around :func:`coverage_planes_masked`, which the compiled drivers
    call directly with the mask as an operand.  Under a churn schedule
    the denominator is the EVENTUAL alive words (permanent churn deaths
    out, transient ones in — the heal-convergence contract the compiled
    churn loops already apply via :func:`_cached_churn_masks`)."""
    from gossip_tpu.ops import nemesis as NE
    if NE.get(fault) is not None:
        def cov_churn(p):
            eventual = _cached_churn_masks(fault, n, origin)[0]
            return coverage_planes_masked(p, n, eventual)
        return cov_churn
    if fault is None or not fault.node_death_rate:
        return lambda p: coverage_planes_masked(p, n)

    def cov(p):
        alive_words, _ = fault_masks_word(fault, n, origin)
        return coverage_planes_masked(p, n, alive_words)
    return cov


def make_sharded_fused_round_masked(n: int, mesh: Mesh, fanout: int = 1,
                                    interpret: bool = False,
                                    inject_bits=None,
                                    has_alive: bool = False,
                                    has_cut: bool = False):
    """The masked core of :func:`make_sharded_fused_round`:
    ``round_fn(planes, seed, round_, alive_words=None,
    drop_threshold=0, cut_words=None)`` with EVERY fault input as a
    runtime OPERAND (replicated over the mesh) instead of a
    trace-baked constant — the death mask, the 20-bit drop threshold
    (an SMEM scalar inside the kernel since the operand PR, so
    drop-rate sweeps and RAMPS re-enter one executable), and, with
    ``has_cut``, the partition side-word mask
    (ops/pallas_round.render_cut_words).  The compiled drivers built
    on this share one executable across every fault configuration of
    the same operand structure — a fault sweep over death rates,
    seeds, drop rates, ramps, or partition windows re-enters one
    cached program per shape instead of recompiling the whole
    shard_map loop per point.  Same values as the baked form: the
    masks are pure functions of the fault config over the REPLICATED
    node dimension, and they consume no hardware PRNG (the drop coin
    rides free bits of the existing partner draw; the side compare
    rides the partner rotation) — the zero-ICI same-stream invariant
    is untouched."""
    n_dev = mesh.shape[AXIS]

    def local_round(planes_l, seed, round_, thr, *masks):
        alive_words = masks[0] if has_alive else None
        cut_words = masks[1 if has_alive else 0] if has_cut else None
        w_local = planes_l.shape[0]
        outs = [fused_multirumor_pull_round(
                    planes_l[i], seed, round_, n, fanout, interpret,
                    inject_bits=inject_bits,
                    drop_threshold=thr,
                    alive_words=alive_words,
                    cut_words=cut_words)
                for i in range(w_local)]
        return jnp.stack(outs)

    in_specs = (P(AXIS, None, None), P(), P(), P())
    if has_alive:
        in_specs += (P(None, None),)
    if has_cut:
        in_specs += (P(None, None),)
    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, which the default shard_map VMA check rejects
    mapped = shard_map(
        local_round, mesh=mesh, in_specs=in_specs,
        out_specs=P(AXIS, None, None), check_vma=False)

    def round_fn(planes, seed, round_, alive_words=None,
                 drop_threshold=0, cut_words=None):
        if planes.shape[0] % n_dev:
            raise ValueError(f"{planes.shape[0]} planes do not divide "
                             f"over {n_dev} devices")
        if (alive_words is not None) != has_alive:
            raise ValueError("alive_words must be passed exactly when the "
                             "round was built with has_alive=True")
        if (cut_words is not None) != has_cut:
            raise ValueError("cut_words must be passed exactly when the "
                             "round was built with has_cut=True")
        masks = (alive_words,) if has_alive else ()
        if has_cut:
            masks += (cut_words,)
        return mapped(planes, jnp.asarray(seed, jnp.int32),
                      jnp.asarray(round_, jnp.int32),
                      jnp.asarray(drop_threshold, jnp.int32), *masks)

    return round_fn


def make_sharded_fused_round(n: int, mesh: Mesh, fanout: int = 1,
                             interpret: bool = False, inject_bits=None,
                             fault=None, origin: int = 0):
    """shard_map'd round: each device advances its local planes with the
    identically-seeded fused kernel — same partner draw on every device,
    zero ICI.  ``inject_bits`` (tests) is one (sbits, rbits) pair reused
    for every plane, which IS the semantic: one shared partner stream.

    ``fault`` threads the fault operands into every plane's kernel call
    — a fault-binding wrapper around
    :func:`make_sharded_fused_round_masked` that rebuilds the masks
    in-trace per call (loop-invariant or round-indexed, hoisted by
    jitted callers).  Under a churn schedule the FULL nemesis runs:
    events render the alive words per round from the die/recover word
    tables, partition windows render per-round side-word cut masks
    (ops/pallas_round.render_cut_words), and drop-rate ramps index the
    20-bit threshold table — all from the state's ABSOLUTE round
    counter, so checkpointed resume stays bitwise
    (ops/nemesis.fused_sched_tables; the two check_supported rejection
    rows this engine used to carry are deleted)."""
    from gossip_tpu.ops import nemesis as NE
    NE.check_supported(fault, engine="fused-planes")
    static_thr = drop_threshold_for(fault)
    has_churn = NE.get(fault) is not None
    has_alive = (fault is not None
                 and bool(fault.node_death_rate)) or has_churn
    core = make_sharded_fused_round_masked(
        n, mesh, fanout, interpret, inject_bits=inject_bits,
        has_alive=has_alive, has_cut=has_churn)
    if has_churn:
        # loop-invariant closure constants: converted ONCE here, not
        # per round_fn call (eager stepwise callers pay one transfer)
        cut_np, thr_np = NE.fused_sched_tables(fault, n)
        cut_tbl = jnp.asarray(cut_np, jnp.int32)
        thr_tbl = jnp.asarray(thr_np, jnp.int32)

    def round_fn(planes, seed, round_):
        from gossip_tpu.ops.pallas_round import render_cut_words
        if has_churn:
            base = NE.fused_base_words(fault, n, origin)
            die_w, rec_w = NE.fused_word_tables(fault, n)
            alive_words = NE.fused_alive_words_at(base, die_w, rec_w,
                                                  round_)
            # the ONE clamped steady-row lookup (ops/nemesis._idx)
            return core(planes, seed, round_, alive_words,
                        NE._idx(thr_tbl, round_),
                        render_cut_words(NE._idx(cut_tbl, round_), n))
        if has_alive:
            alive_words = fault_masks_word(fault, n, origin)[0]
            return core(planes, seed, round_, alive_words, static_thr)
        return core(planes, seed, round_, drop_threshold=static_thr)

    return round_fn


def prng_invariant_digests(n: int, mesh: Mesh, seed: int = 0,
                           round_: int = 1, fanout: int = 1,
                           interpret: bool = False) -> jax.Array:
    """Digest of one identically-seeded fused round per device.

    Every device builds the SAME deterministic non-trivial input plane,
    runs the SAME fused kernel with the SAME seed scalars, and digests
    its output as (total popcount, index-weighted mix) — two uint32s
    whose collision probability for diverged PRNG streams is ~2^-64.
    The digests ride one all_gather; equal rows == the zero-ICI
    same-stream invariant held on this mesh.  Returns uint32[n_dev, 2].
    """
    rows = mr_rows(n)

    def local(_dummy):
        i = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0)
        j = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1)
        table = ((i * jnp.uint32(2654435761)) ^ (j * jnp.uint32(40503))
                 ) | jnp.uint32(1)
        out = fused_multirumor_pull_round(
            table, jnp.int32(seed), jnp.int32(round_), n, fanout,
            interpret)
        pop = jnp.sum(jax.lax.population_count(out), dtype=jnp.uint32)
        # distinct odd weight per position (2x+1, not x|1 — OR-ing maps
        # even/odd lane pairs to the SAME weight, and a weight collision
        # plus permutation-invariant popcount would let a lane-pair swap
        # between diverged streams slip through)
        w = jnp.uint32(2) * (i * jnp.uint32(LANES) + j) + jnp.uint32(1)
        mix = jnp.sum(out * w, dtype=jnp.uint32)
        return jax.lax.all_gather(jnp.stack([pop, mix]), AXIS)

    mapped = shard_map(
        local, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(None, None),
        check_vma=False)
    return mapped(jnp.zeros((mesh.shape[AXIS],), jnp.int32))


def assert_prng_invariant(n: int, mesh: Mesh, seed: int = 0,
                          round_: int = 1, fanout: int = 1,
                          interpret: bool = False):
    """Raise unless every device drew the identical partner stream.
    Returns the digest table on success (an artifact to record)."""
    import numpy as np
    d = np.asarray(prng_invariant_digests(n, mesh, seed, round_, fanout,
                                          interpret))
    if not (d == d[0]).all():
        raise AssertionError(
            "zero-ICI plane-sharding PRNG invariant VIOLATED: devices "
            f"drew different partner streams; digests per device:\n{d}")
    if int(d[0, 0]) == 0:
        raise AssertionError(
            "degenerate digest (popcount 0) — the check input never "
            "reached the kernel")
    return d


def restore_plane_state(planes, mesh: Mesh):
    """Re-place host-loaded checkpoint planes under the plane sharding.
    The stack is already padded to the mesh (init_plane_state contract),
    so a same-mesh-shape resume is bitwise exact; the CLI fingerprint
    refuses a different device count."""
    return jax.device_put(jnp.asarray(planes),
                          NamedSharding(mesh, P(AXIS, None, None)))


def checkpointed_fused_planes(n: int, rumors: int, run: RunConfig,
                              mesh: Mesh, path: str, every: int = 50,
                              fanout: int = 1,
                              resume_state=None, want_curve: bool = False,
                              interpret: bool = False,
                              curve_prefix=(), extra_meta=None,
                              fault=None):
    """Fixed-budget plane-sharded fused run in compiled segments with
    atomic npz checkpoints — persistence for the flagship multi-rumor
    runs, the one scale long enough to need it (the reference loses all
    state on process death, main.go:22-26).  The checkpoint state is a
    :class:`~gossip_tpu.ops.pallas_round.FusedState` whose ``table``
    field carries the [W, rows, 128] plane stack; there is no PRNG key
    to persist — the kernel's hardware PRNG streams are a pure function
    of (seed, round), both in the config fingerprint / round counter.

    With ``want_curve`` the segments run as a scan recording
    min-over-rumors coverage per round (alive-weighted under a fault,
    like the non-checkpoint scan twins).  ``interpret`` is the
    CPU-interpreter path for tests (deterministic stubbed PRNG: resume
    bitwise-equality is still meaningful off-TPU).

    Returns ``(final_state, coverage, curve-or-None)``.
    """
    from gossip_tpu.ops.pallas_round import FusedState
    from gossip_tpu.utils.checkpoint import run_with_checkpoints
    # the FULL churn schedule — events, partition windows, drop-rate
    # ramps — runs in the segments exactly as in the straight fused
    # drivers: the round closure renders the alive words, per-round cut
    # mask, and drop threshold from the state's ABSOLUTE round counter,
    # which the checkpoint persists, so resume == straight run bitwise
    # (utils/checkpoint crash contract); the coverage denominator under
    # churn is the eventual alive words (fused_planes_cov_fn)
    round_fn = make_sharded_fused_round(n, mesh, fanout, interpret,
                                        fault=fault, origin=run.origin)
    cov_planes = fused_planes_cov_fn(n, fault, run.origin)

    def step(st: FusedState) -> FusedState:
        return FusedState(table=round_fn(st.table, run.seed, st.round),
                          round=st.round + 1,
                          msgs=st.msgs + 2.0 * fanout * n)

    if resume_state is None:
        state = FusedState(table=init_plane_state(n, rumors, mesh,
                                                  run.origin),
                           round=jnp.int32(0), msgs=jnp.float32(0.0))
    else:
        state = resume_state._replace(
            table=restore_plane_state(resume_state.table, mesh))

    curve_fn = None
    if want_curve:
        def curve_fn(s):
            return cov_planes(s.table)

    remaining = max(0, run.max_rounds - int(state.round))
    out = run_with_checkpoints(step, state, remaining, path, every=every,
                               curve_fn=curve_fn,
                               curve_prefix=curve_prefix,
                               extra_meta=extra_meta)
    final, curve = out if want_curve else (out, None)
    cov = float(cov_planes(final.table))
    return final, cov, curve


def _plane_recorder(n: int, fanout: int, mesh: Mesh):
    """In-loop metrics row for the plane-sharded fused drivers
    (ops/round_metrics).  ``msgs`` is the driver's own accounting
    (2*fanout*n transmissions per round, all W word-planes riding one
    exchange); ``offered`` counts every delivered digest bit including
    the all-ones rumor padding (an upper bound, consistent with the
    module contract); ``bytes`` is 4.0 — the scalar coverage reduction
    is the ONLY cross-device traffic, which is exactly the zero-ICI
    claim this plane makes checkable per round.  The previous round's
    bit count rides the carry as ONE scalar — re-reading the pre-step
    plane stack after the kernel call would extend its liveness across
    the aliased pallas_call and resurrect the copy-insertion full-table
    copy the donation contract exists to kill."""
    from gossip_tpu.ops import round_metrics as RM
    n_shards = mesh.shape[AXIS]

    def rec(m, prev_count, planes1):
        count = RM.count_planes(planes1)
        newly = count - prev_count
        offered = (jnp.float32(fanout * n)
                   * jnp.float32(planes1.shape[0] * BITS))
        return RM.record(
            m, newly=newly, msgs=2.0 * fanout * n,
            dup=RM.dup_estimate(offered, newly), bytes=4.0,
            front=RM.front_planes(planes1, n, n_shards)), count

    return rec


@functools.lru_cache(maxsize=32)
def _cached_curve_scan(n: int, seed: int, max_rounds: int, mesh: Mesh,
                       fanout: int, interpret: bool,
                       has_alive: bool, metrics: bool = False,
                       has_churn: bool = False):
    """The compiled curve-scan driver, memoized by EXACTLY the statics
    its trace bakes in (seed and max_rounds are closed-over literals) —
    not the whole RunConfig, whose unused fields (engine, checkpoint
    knobs) would fragment the cache, and NOT the fault config at all:
    the alive mask, the 20-bit drop threshold (per-round table under
    churn — so RAMPS ride free), and the partition cut table are all
    runtime OPERANDS (``*masks``), so a fault sweep over death rates,
    seeds, drop rates, ramps, or partition windows shares ONE compiled
    loop per operand structure instead of recompiling per point (the
    operand PR: only the two structure booleans below remain — they
    change the operand COUNT, never carry content).  Every argument is
    hashable (Mesh hashes structurally).  Re-entering the driver with
    the same statics — a sweep server, the RPC sidecar, the multichip
    dryrun's steady pass — reuses the jitted callable instead of
    retracing the whole shard_map program per call (VERDICT r4 task 7:
    driver-level steady timings must be executable-cache hits like
    every other family's).  The plane state is a runtime ARGUMENT, so
    different ``rumors`` shapes share one entry via jit's own cache.
    Convergence/coverage is computed ON DEVICE inside the scan — the
    steady path does no per-round host round-trip.  ``metrics`` bakes
    the round-metrics buffer carry into the program (ops/round_metrics
    — part of the memo key: the instrumented and bare loops are
    different executables).  Mask layouts: churn-free passes
    ``(thr,)`` (plus ``(thr, cov_words)`` under static deaths);
    ``has_churn`` switches to the ``(cov_words, base, die, rec,
    cut_tbl, thr_tbl)`` six-tuple of :func:`_cached_churn_masks` — the
    loop indexes the die/recover/cut/threshold tables by its own
    counter and renders the per-round side-word cut mask in-trace
    (render_cut_words, the alive-word trick extended to cut words),
    while the cond/coverage compare against the EVENTUAL alive
    words."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.ops.pallas_round import render_cut_words
    step = make_sharded_fused_round_masked(
        n, mesh, fanout, interpret,
        has_alive=has_alive or has_churn, has_cut=has_churn)
    rec = _plane_recorder(n, fanout, mesh) if metrics else None

    @functools.partial(jax.jit, donate_argnums=0)
    def scan(planes, *masks):
        if has_churn:
            cov_words, base_w, die_w, rec_w, cut_tbl, thr_tbl = masks
        else:
            thr0 = masks[0]
            cov_words = masks[1] if has_alive else None
        m0 = (RM.init(max_rounds, mesh.shape[AXIS],
                      "simulate_curve_sharded_fused") if rec else None)
        c0 = RM.count_planes(planes) if rec else None

        def body(c, _):
            planes_c, round_c, m, cnt = c
            if has_churn:
                aw = NE.fused_alive_words_at(base_w, die_w, rec_w,
                                             round_c)
                # the ONE clamped steady-row lookup (ops/nemesis._idx)
                planes_n = step(planes_c, seed, round_c, aw,
                                NE._idx(thr_tbl, round_c),
                                render_cut_words(
                                    NE._idx(cut_tbl, round_c), n))
            else:
                planes_n = step(planes_c, seed, round_c, cov_words,
                                thr0)
            if m is not None:
                m, cnt = rec(m, cnt, planes_n)
            return ((planes_n, round_c + 1, m, cnt),
                    coverage_planes_masked(planes_n, n, cov_words))
        (final, _, m, _), covs = jax.lax.scan(
            body, (planes, jnp.int32(0), m0, c0), None,
            length=max_rounds)
        return final, covs, m

    return scan


def _init_and_masks(n: int, rumors: int, run: RunConfig, mesh: Mesh,
                    fault, has_alive: bool, timing,
                    has_churn: bool = False):
    """(init_planes, masks): the cached-jitted state/mask builders shared
    by both simulate drivers.  With a ``timing`` dict the build is
    blocked-on and recorded as ``init_build_s`` — the driver-side
    component of the wall decomposition (backend._timing_meta folds it
    into ``driver_overhead_s``; the dry run reports it per family).
    ``has_churn`` builds the churn mask quadruple instead
    (:func:`_cached_churn_masks`)."""
    t0 = time.perf_counter()
    init = init_plane_state(n, rumors, mesh, run.origin)
    if has_churn:
        masks = _cached_churn_masks(fault, n, run.origin)
    elif has_alive:
        masks = (jnp.asarray(drop_threshold_for(fault), jnp.int32),
                 _cached_alive_words(fault, n, run.origin)())
    else:
        masks = (jnp.asarray(drop_threshold_for(fault), jnp.int32),)
    if timing is not None:
        jax.block_until_ready((init,) + masks)
        timing["init_build_s"] = time.perf_counter() - t0
    return init, masks


def simulate_curve_sharded_fused(n: int, rumors: int, run: RunConfig,
                                 mesh: Mesh, fanout: int = 1,
                                 interpret: bool = False, fault=None,
                                 timing=None):
    """(covs[max_rounds], final_planes): fixed-length scan over the
    plane-sharded round recording per-round min-over-rumors coverage —
    the curve twin of :func:`simulate_until_sharded_fused` (no early
    exit; the caller derives rounds-to-target from the curve).
    ``timing``: optional wall-decomposition dict (utils/trace
    maybe_aot_timed contract — AOT compile/steady split by default,
    ``{"aot": False}`` for a steady-only probe on the cached
    executable; plus ``init_build_s``, see :func:`_init_and_masks`)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    NE.check_supported(fault, engine="fused-planes")
    has_alive = fault is not None and bool(fault.node_death_rate)
    has_churn = NE.get(fault) is not None
    scan = _cached_curve_scan(n, run.seed, run.max_rounds, mesh, fanout,
                              interpret,
                              has_alive, RM.wanted(), has_churn)
    init, masks = _init_and_masks(n, rumors, run, mesh, fault, has_alive,
                                  timing, has_churn)
    final, covs, _ = maybe_aot_timed(scan, timing, init, *masks, label="fused")
    return covs, final


@functools.lru_cache(maxsize=32)
def _cached_until_loop(n: int, seed: int, max_rounds: int,
                       target_coverage: float, mesh: Mesh,
                       fanout: int, interpret: bool,
                       has_alive: bool, metrics: bool = False,
                       has_churn: bool = False):
    """The compiled until-target driver, memoized like
    :func:`_cached_curve_scan` (same key contract and rationale —
    fault content all operands, no fault config in the key — plus
    the target the cond compares against).  Returns ``loop(planes,
    *masks) -> (final_planes, rounds, coverage)`` — the reported
    coverage is computed INSIDE the program through the SAME chooser
    the cond used (one chooser for both, and one executable dispatch
    per steady call instead of loop + separate coverage).  The
    convergence check runs on device inside the while_loop cond; steady
    state does no per-round host round-trip.  ``metrics`` bakes the
    round-metrics buffer carry into the program (part of the memo
    key, as in :func:`_cached_curve_scan`, which also documents
    ``has_churn`` and the mask layouts)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.ops.pallas_round import render_cut_words
    step = make_sharded_fused_round_masked(
        n, mesh, fanout, interpret,
        has_alive=has_alive or has_churn, has_cut=has_churn)
    target = jnp.float32(target_coverage)
    rec = _plane_recorder(n, fanout, mesh) if metrics else None

    @functools.partial(jax.jit, donate_argnums=0)
    def loop(planes, *masks):
        if has_churn:
            cov_words, base_w, die_w, rec_w, cut_tbl, thr_tbl = masks
        else:
            thr0 = masks[0]
            cov_words = masks[1] if has_alive else None
        m0 = (RM.init(max_rounds, mesh.shape[AXIS],
                      "simulate_until_sharded_fused") if rec else None)
        c0 = RM.count_planes(planes) if rec else None

        def cond(c):
            planes_c, round_c, _, _ = c
            return ((coverage_planes_masked(planes_c, n, cov_words)
                     < target)
                    & (round_c < max_rounds))

        def body(c):
            planes_c, round_c, m, cnt = c
            if has_churn:
                aw = NE.fused_alive_words_at(base_w, die_w, rec_w,
                                             round_c)
                # the ONE clamped steady-row lookup (ops/nemesis._idx)
                planes_n = step(planes_c, seed, round_c, aw,
                                NE._idx(thr_tbl, round_c),
                                render_cut_words(
                                    NE._idx(cut_tbl, round_c), n))
            else:
                planes_n = step(planes_c, seed, round_c, cov_words,
                                thr0)
            if m is not None:
                m, cnt = rec(m, cnt, planes_n)
            return planes_n, round_c + 1, m, cnt

        final, rounds, m, _ = jax.lax.while_loop(
            cond, body, (planes, jnp.int32(0), m0, c0))
        return (final, rounds,
                coverage_planes_masked(final, n, cov_words), m)

    return loop


def simulate_until_sharded_fused(n: int, rumors: int, run: RunConfig,
                                 mesh: Mesh, fanout: int = 1,
                                 interpret: bool = False, fault=None,
                                 timing=None):
    """(rounds, coverage, msgs, final_planes): compiled while_loop to
    min-over-rumors target coverage on the plane-sharded state.

    msgs counts transmissions (request + whole-digest response per
    partner draw, all W words riding one exchange): 2*fanout*n/round.
    ``fault`` threads the static fault masks into every plane's kernel;
    the cond and the reported coverage switch to the alive-weighted
    metric (coverage_planes_masked — one chooser for both).  ``timing``:
    optional wall-decomposition dict (see the curve twin)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    NE.check_supported(fault, engine="fused-planes")
    has_alive = fault is not None and bool(fault.node_death_rate)
    has_churn = NE.get(fault) is not None
    loop = _cached_until_loop(n, run.seed, run.max_rounds,
                              run.target_coverage, mesh, fanout,
                              interpret,
                              has_alive, RM.wanted(), has_churn)
    init, masks = _init_and_masks(n, rumors, run, mesh, fault, has_alive,
                                  timing, has_churn)
    final, rounds, cov, _ = maybe_aot_timed(loop, timing, init, *masks,
                                            label="fused")
    rounds = int(rounds)
    cov = float(cov)
    msgs = 2.0 * fanout * n * rounds
    return rounds, cov, msgs, final
