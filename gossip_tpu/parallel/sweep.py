"""Ensemble sweeps: the data-parallel axis (SURVEY.md §2.3 "DP").

The reference runs one stochastic trajectory per process launch; asking
"how many rounds does this protocol *typically* take?" means re-running the
binary N times.  Here the trajectory ensemble is one ``vmap`` axis: S seeds
run the same jitted round step as a single batched XLA program, so ensemble
statistics (median/quantiles of rounds-to-target, curve bands) cost one
compile and one device pass.  On a mesh this is the second axis of the
north star ("multi-config sweep on a second mesh axis"); single-device it
is plain vmap.

Two batching axes live here:

* :func:`ensemble_curves` — S seeds of ONE config as a vmap batch (round 1).
* :func:`config_sweep_curves` — a batch of DISTINCT configs in one XLA
  program (round 2, VERDICT item 4): everything that does not change array
  shapes is a traced per-config scalar — (do_push, do_pull) mode flags,
  fanout (as a column mask under a shared k_max draw width), drop_prob,
  anti-entropy period, and seed.  push+pull are both computed and masked by
  the flags, so a mixed-mode batch costs one push-pull round per config —
  the price of one program instead of C compiles.  Only topology family/n,
  rumor count, and death masks stay structural (they change shapes or
  tables).

Round 3 added the TOPOLOGY axis (VERDICT r2 item 6): same-n explicit
families stack into one ``int32[F, n, D_max]`` traced table operand and
each point's ``topo_idx`` dynamic-slices its family — completing the
north star's "sweep fanout, mode, and graph topology" sentence in one
XLA program.

Round 4 batched the N axis too (VERDICT r3 item 6): different-n explicit
entries pad to ``n_max`` with PHANTOM rows (degree 0, sentinel
neighbors, masked out of liveness and coverage), so a families x sizes
grid is ONE program — `grid --family ring --ns 1000 10000` compiles
once (explicit families only — see _stack_topologies).  A point's
curve equals its solo run bitwise on the real prefix (per-node draws
are keyed by global id).

Later in round 4 the RUMOR axis joined them: per-point rumor counts
(``SweepPoint.rumors``) pad the state's R axis to the batch max with
ALL-FALSE phantom columns — never seeded, so they scatter nothing,
gather nothing, and flip no ``sender_active`` bit (msgs and the real
prefix stay bitwise equal to the solo run) — and the coverage min
masks them out per point.  `grid --rumors 1 4` is one program.

Finally, mixed-n IMPLICIT (complete-graph) batches joined too: a
complete graph has no table to stack, so each point's uniform partner
draw is bounded by its own n as a TRACED operand
(ops/sampling.sample_peers_complete) — randint's draw depends only on
the bound's value, so the solo static-bound trajectory reproduces
bitwise.  The one structural split left is implicit-vs-explicit:
stacked tables and traced bounds are different programs, so a batch
must be one kind or the other (each batches fully within its kind).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.si import coverage, make_si_round
from gossip_tpu.models.state import SimState, alive_mask, init_state
from gossip_tpu.ops import nemesis as NE
from gossip_tpu.ops.propagate import pull_merge, push_counts
from gossip_tpu.ops.sampling import (drop_mask, sample_peers,
                                     sample_peers_complete)
from gossip_tpu.topology.generators import Topology


@dataclasses.dataclass
class EnsembleResult:
    curves: np.ndarray          # float32[S, T] coverage per seed per round
    msgs: np.ndarray            # float32[S, T]
    rounds_to_target: np.ndarray  # int[S], -1 where never reached
    target: float

    @property
    def converged(self) -> np.ndarray:
        return self.rounds_to_target >= 0

    def summary(self) -> dict:
        r = self.rounds_to_target[self.converged]
        return {
            "seeds": int(len(self.rounds_to_target)),
            "converged": int(self.converged.sum()),
            "rounds_mean": float(r.mean()) if len(r) else None,
            "rounds_std": float(r.std()) if len(r) else None,
            "rounds_p50": float(np.median(r)) if len(r) else None,
            "rounds_p95": float(np.percentile(r, 95)) if len(r) else None,
            "final_coverage_mean": float(self.curves[:, -1].mean()),
            "msgs_mean": float(self.msgs[:, -1].mean()),
            "target": self.target,
        }




def _shard_ensemble(init, mesh, axis_name: str, n_seeds: int):
    """Place a stacked ensemble state under a 1-D seed-axis mesh (the
    batch is embarrassingly parallel, like the config sweep's mesh:
    sharding never changes values — pinned in tests).  Scalars-per-seed
    shard on the axis; per-seed arrays shard on their leading dim."""
    if mesh is None:
        return init
    from jax.sharding import NamedSharding, PartitionSpec as P
    if n_seeds % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"{n_seeds} seeds do not divide over the {axis_name} mesh "
            f"axis of size {mesh.shape[axis_name]}; pad the seed list "
            "or change the mesh")
    def place(x):
        spec = P(axis_name, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(place, init)

def ensemble_curves(proto: ProtocolConfig, topo: Topology, run: RunConfig,
                    seeds: Sequence[int],
                    fault: Optional[FaultConfig] = None, mesh=None,
                    axis_name: str = "seed") -> EnsembleResult:
    """Run |seeds| independent trajectories as ONE batched XLA program.
    ``mesh``: a 1-D device mesh shards the SEED axis (value-invariant,
    embarrassingly parallel — _shard_ensemble).  The SCENARIO-batched
    twin — one seed, K nemesis schedules vmapped through one compiled
    loop — is :func:`churn_sweep_curves`."""
    # tables as jit ARGUMENTS + liveness in-trace: no O(N) closure
    # constants in the compile request (models/swim.py doc)
    step, tables = make_si_round(proto, topo, fault, run.origin, tabled=True)
    # churn-path steps return (state, lost); the ensemble records no
    # per-round observables, so drop the lost count (ops/nemesis)
    step = NE.drop_lost(step, NE.get(fault))
    base = init_state(run, proto, topo.n)
    keys = jax.vmap(jax.random.key)(jnp.asarray(list(seeds), jnp.uint32))
    s = len(seeds)
    init = SimState(
        seen=jnp.broadcast_to(base.seen, (s,) + base.seen.shape),
        round=jnp.zeros((s,), jnp.int32),
        base_key=keys,
        msgs=jnp.zeros((s,), jnp.float32),
    )
    init = _shard_ensemble(init, mesh, axis_name, s)

    @jax.jit
    def scan(states, *tbl):
        # eventual alive set under churn (heal-convergence denominator)
        alive = NE.metric_alive(fault, topo.n, run.origin)
        def body(st, _):
            st = jax.vmap(lambda x: step(x, *tbl))(st)
            covs = jax.vmap(lambda x: coverage(x.seen, alive))(st)
            return st, (covs, st.msgs)
        return jax.lax.scan(body, states, None, length=run.max_rounds)

    _, (covs, msgs) = scan(init, *tables)
    curves = np.asarray(covs).T          # [S, T]
    return EnsembleResult(curves=curves, msgs=np.asarray(msgs).T,
                          rounds_to_target=_rounds_to_target(
                              curves, run.target_coverage),
                          target=run.target_coverage)


@dataclasses.dataclass
class ChurnSweepResult:
    """K nemesis scenarios through ONE compiled loop
    (:func:`churn_sweep_curves`).  ``curves``/``msgs`` are per-scenario
    per-round; ``dropped`` is the kernels' EXACT per-round destroyed-
    message count (drop coins + open cut) — the per-scenario nemesis
    observable the ledger records."""
    faults: tuple                 # the FaultConfigs, batch order
    curves: np.ndarray            # float32[K, T]
    msgs: np.ndarray              # float32[K, T]
    dropped: np.ndarray           # float32[K, T]
    rounds_to_target: np.ndarray  # int[K], -1 where never reached
    target: float

    def summaries(self):
        out = []
        for i, f in enumerate(self.faults):
            ch = f.churn
            out.append({
                "scenario": {"events": list(map(list, ch.events)),
                             "partitions": list(map(list,
                                                    ch.partitions)),
                             "ramp": (list(ch.ramp)
                                      if ch.ramp else None),
                             "drop_prob": f.drop_prob},
                "rounds_to_target": int(self.rounds_to_target[i]),
                "converged": bool(self.rounds_to_target[i] >= 0),
                "final_coverage": float(self.curves[i, -1]),
                "msgs_total": float(self.msgs[i, -1]),
                "dropped_total": float(self.dropped[i].sum()),
            })
        return out


@functools.lru_cache(maxsize=16)
def _cached_churn_sweep_scan(proto: ProtocolConfig, n: int,
                             have_table: bool,
                             fault_static: FaultConfig, origin: int,
                             max_rounds: int):
    """The scenario-batched churn sweep's compiled scan, memoized by
    EXACTLY the statics its trace bakes — schedule CONTENT is a runtime
    operand (ops/nemesis module doc), so every K-scenario family with
    the same static structure re-enters ONE compiled program, and even
    a DIFFERENT scenario stack of the same shapes is an in-process
    executable-cache hit (the _cached_pod_sweep_scan memo discipline).

    The returned callable takes ``(states, alive_stack, *tables)``:
    K-stacked SimState, the per-scenario EVENTUAL-alive coverage
    denominators ``bool[K, n]`` (a function of which churn deaths are
    permanent — content, so an operand), the (unstacked) topology
    tables, and the four stacked schedule operands of
    ``nemesis.build_stack``.  vmap maps the scenario axis through the
    one step; per-scenario trajectories are BITWISE the solo runs
    (same keys — pinned in tests/test_nemesis.py)."""
    rep_fault, topo_ph = NE.placeholder_trace_inputs(fault_static, n,
                                                     have_table)
    step, _ = make_si_round(proto, topo_ph, rep_fault, origin,
                            tabled=True)
    n_topo = 0 if topo_ph.implicit else 2

    def one(st, die, rec_, cut, drop, topo_tbl):
        return step(st, *topo_tbl, die, rec_, cut, drop)

    @jax.jit
    def scan(states, alive_stack, *tbl):
        topo_tbl, sched_tail = tbl[:n_topo], tbl[n_topo:]

        def body(sts, _):
            sts, lost = jax.vmap(
                lambda st, d, r, c, p: one(st, d, r, c, p, topo_tbl)
            )(sts, *sched_tail)
            # the coverage READOUT leaves the device as an EXACT
            # integer: min-over-rumors alive-entry count per scenario
            # (integer sums are order-exact in any lowering, unlike the
            # final division, which XLA fuses to a recip-mul in some
            # contexts and true division in others — a 1-ulp lottery).
            # The driver divides ONCE on the host in float32, which is
            # IEEE true division — bitwise the solo coverage() path.
            cnt = jax.vmap(
                lambda x, al: jnp.min(jnp.sum(
                    x & al[:, None], axis=0, dtype=jnp.int32)))(
                sts.seen, alive_stack)
            return sts, (cnt, sts.msgs, lost)
        return jax.lax.scan(body, states, None, length=max_rounds)
    return scan


def churn_sweep_curves(proto: ProtocolConfig, topo: Topology,
                       run: RunConfig, faults, mesh=None,
                       axis_name: str = "scenario",
                       timing=None) -> ChurnSweepResult:
    """Run K nemesis SCENARIOS — distinct churn/partition/ramp fault
    programs over one protocol config — as ONE batched XLA program:
    the schedule stack (ops/nemesis.build_stack) vmaps through the one
    compiled round loop as a ``[K, ...]`` runtime operand, so the whole
    scenario family costs one compile (and re-entering with a NEW
    family of the same shapes costs none: _cached_churn_sweep_scan).
    This is the Maelstrom move — one binary, every nemesis — for the
    batched simulator.

    Every fault must carry a churn schedule; the STATIC fault structure
    (death mask draw, scripted dead_nodes) must match across the stack
    because the step bakes it — ``drop_prob`` may vary freely (it only
    feeds the per-scenario drop table).  Scenario k's curve equals the
    solo ``simulate_curve(..., fault=faults[k])`` run BITWISE (same
    threefry keys; coverage over the scenario's own eventual-alive
    denominator).

    ``mesh``: a 1-D device mesh shards the SCENARIO axis (value-
    invariant, embarrassingly parallel — _shard_ensemble).  ``timing``:
    optional compile/steady AOT-split dict (utils/trace contract).
    Returns :class:`ChurnSweepResult` (curves / msgs / exact per-round
    ``dropped`` per scenario)."""
    faults = tuple(faults)
    if not faults:
        raise ValueError("need at least one churn FaultConfig")
    statics = {dataclasses.replace(f, churn=None, drop_prob=0.0)
               for f in faults}
    if len(statics) > 1:
        raise ValueError(
            "churn sweep scenarios must share the STATIC fault "
            "structure (node_death_rate/seed/dead_nodes are baked into "
            "the one compiled step); vary the churn schedule and "
            "drop_prob only")
    stack = NE.build_stack(faults, topo.n)       # validates churn too
    k = len(faults)
    # drop_prob is stripped from the memo key like the schedule: it
    # only feeds the per-scenario drop_tbl operand, never the trace
    scan = _cached_churn_sweep_scan(
        proto, topo.n, not topo.implicit,
        dataclasses.replace(faults[0], churn=None, drop_prob=0.0),
        run.origin, run.max_rounds)
    alive_stack = jnp.stack(
        [NE.eventual_alive(f, topo.n, run.origin) for f in faults])
    base = init_state(run, proto, topo.n)
    keys = jax.vmap(jax.random.key)(
        jnp.full((k,), run.seed, jnp.uint32))
    init = SimState(
        seen=jnp.broadcast_to(base.seen, (k,) + base.seen.shape),
        round=jnp.zeros((k,), jnp.int32),
        base_key=keys,
        msgs=jnp.zeros((k,), jnp.float32),
    )
    init = _shard_ensemble(init, mesh, axis_name, k)
    sched_ops = NE.sched_args(stack)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        put = lambda x: jax.device_put(                   # noqa: E731
            x, NamedSharding(mesh, P(axis_name,
                                     *([None] * (x.ndim - 1)))))
        alive_stack = put(alive_stack)
        sched_ops = tuple(put(x) for x in sched_ops)
    topo_tbl = () if topo.implicit else (topo.nbrs, topo.deg)
    from gossip_tpu.utils.trace import maybe_aot_timed
    _, (cnts, msgs, lost) = maybe_aot_timed(
        scan, timing, init, alive_stack, *topo_tbl, *sched_ops, label="sweep")
    # one true f32 division per cell (the scan emits exact integer
    # counts — see _cached_churn_sweep_scan's readout comment)
    denom = np.asarray(alive_stack.sum(axis=1)).astype(np.float32)
    curves = (np.asarray(cnts).T.astype(np.float32)
              / np.maximum(denom, 1.0)[:, None])
    return ChurnSweepResult(faults=faults, curves=curves,
                            msgs=np.asarray(msgs).T,
                            dropped=np.asarray(lost).T,
                            rounds_to_target=_rounds_to_target(
                                curves, run.target_coverage),
                            target=run.target_coverage)


@dataclasses.dataclass
class FusedChurnSweepResult:
    """K nemesis scenarios through the plane-sharded FUSED engine
    (:func:`fused_churn_sweep_curves`).  ``msgs`` is the fused
    accounting's closed form (2*fanout*n per round, every scenario —
    request+digest transmissions, dropped and dead-partner pulls
    counted like the solo fused drivers); there is no ``dropped``
    column because the fused kernels do not materialize per-round
    destroyed-message counts (the drop coin is resolved inside the
    kernel) — an honest absence, not a zero."""
    faults: tuple                 # the FaultConfigs, batch order
    curves: np.ndarray            # float32[K, T]
    msgs: np.ndarray              # float32[K, T]
    rounds_to_target: np.ndarray  # int[K], -1 where never reached
    target: float

    def summaries(self):
        out = []
        for i, f in enumerate(self.faults):
            ch = f.churn
            out.append({
                "scenario": {"events": list(map(list, ch.events)),
                             "partitions": list(map(list,
                                                    ch.partitions)),
                             "ramp": (list(ch.ramp)
                                      if ch.ramp else None),
                             "drop_prob": f.drop_prob},
                "rounds_to_target": int(self.rounds_to_target[i]),
                "converged": bool(self.rounds_to_target[i] >= 0),
                "final_coverage": float(self.curves[i, -1]),
                "msgs_total": float(self.msgs[i, -1]),
            })
        return out


def fused_churn_sweep_curves(n: int, rumors: int, run: RunConfig,
                             faults, mesh, fanout: int = 1,
                             interpret: bool = False,
                             timing=None) -> FusedChurnSweepResult:
    """Run K nemesis SCENARIOS — distinct churn/partition/ramp fault
    programs — through the plane-sharded FUSED Pallas engine for the
    cost of ONE compile.  The fused scenario batch amortizes by
    EXECUTABLE REUSE, not vmap: the memoized fused curve scan
    (parallel/sharded_fused._cached_curve_scan) keys WITHOUT the fault
    config — every scenario's schedule lowers to runtime operands (the
    per-round alive words, the partition cut table rendered to
    side-word masks in-trace, and the 20-bit drop-threshold table the
    SMEM scalar is indexed from) — so scenario 0 compiles the loop and
    scenarios 1..K-1 re-enter the same executable (compile-count
    pinned in tests/test_sharded_fused.py; a vmapped scenario axis is
    not a lowering the plane-sharded pallas_call program has, and the
    plane axis already occupies the mesh).

    Every fault must carry a churn schedule and the STATIC fault
    structure must match across the stack (the churn_sweep_curves
    contract: ``drop_prob`` may vary freely — it only moves the
    threshold table).  Scenario k's curve IS the solo
    ``simulate_curve_sharded_fused(..., fault=faults[k])`` run — the
    sweep calls exactly that driver, so per-scenario bitwise solo
    parity holds by construction (still pinned in tests, against
    drift).  ``timing`` (utils/trace contract) decomposes scenario 0
    only — the compile-bearing entry; later scenarios are steady
    re-entries by definition."""
    from gossip_tpu.parallel.sharded_fused import (
        simulate_curve_sharded_fused)
    faults = tuple(faults)
    if not faults:
        raise ValueError("need at least one churn FaultConfig")
    for f in faults:
        if NE.get(f) is None:
            raise ValueError(
                "fused churn sweep scenarios must each carry a churn "
                "schedule (static-only faults run the plain fused "
                "curve driver)")
        NE.check_supported(f, engine="fused-planes")
    statics = {dataclasses.replace(f, churn=None, drop_prob=0.0)
               for f in faults}
    if len(statics) > 1:
        raise ValueError(
            "churn sweep scenarios must share the STATIC fault "
            "structure (node_death_rate/seed/dead_nodes select the "
            "mask operand layout); vary the churn schedule and "
            "drop_prob only")
    curves = []
    for i, f in enumerate(faults):
        covs, _ = simulate_curve_sharded_fused(
            n, rumors, run, mesh, fanout=fanout, fault=f,
            interpret=interpret, timing=timing if i == 0 else None)
        curves.append(np.asarray(covs))
    curves = np.stack(curves)
    per_round = 2.0 * fanout * n
    msgs = np.broadcast_to(
        per_round * np.arange(1, run.max_rounds + 1, dtype=np.float32),
        curves.shape).copy()
    return FusedChurnSweepResult(
        faults=faults, curves=curves, msgs=msgs,
        rounds_to_target=_rounds_to_target(curves,
                                           run.target_coverage),
        target=run.target_coverage)


# ---------------------------------------------------------------------------
# Request-batched serving (the admission batcher's megabatch driver,
# rpc/batcher): K heterogeneous REQUESTS — distinct (mode, fanout-shared,
# drop, period, seed, origin, target, n-within-bucket, rumors, static
# fault, churn schedule) — through ONE compiled scan.  This generalizes
# churn_sweep_curves (one proto, K schedules) to per-request protocol
# operands, and config_sweep_curves (K protos, no schedules) to
# per-request nemesis schedule stacks.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """One serving request's simulation config, megabatch-shaped.

    The batch-key contract (rpc/batcher module doc): everything in here
    EXCEPT ``proto.fanout``, ``proto.exclude_self``, ``run.max_rounds``
    and the topology/n-bucket is a runtime OPERAND of the one compiled
    scan — mode flags, period, seed, origin, target, drop probability,
    the static death mask, and the whole churn schedule all vary freely
    within a batch without retracing.  ``fanout`` is the shared draw
    width because trajectories are a function of (config, draw width):
    only fanout == k reproduces the solo run bitwise (the
    config_sweep_curves k_max contract), and serving promises bitwise
    solo parity."""
    proto: ProtocolConfig
    run: RunConfig
    fault: Optional[FaultConfig]
    n: int

    def __post_init__(self):
        if self.proto.mode not in _MODE_FLAGS:
            raise ValueError(
                f"request batching supports {sorted(_MODE_FLAGS)}; got "
                f"{self.proto.mode!r} (flood/swim/rumor change the round "
                "structure — dispatch them solo)")
        if not self.proto.exclude_self:
            raise ValueError("request batching samples with the shared "
                             "exclude_self=True contract")
        if self.proto.period > 1 and self.proto.mode != C.ANTI_ENTROPY:
            raise ValueError("period > 1 is the anti-entropy cadence")
        if self.n < 2:
            raise ValueError("request batching needs n >= 2 (the traced "
                             "peer bound's self-exclusion shift)")


@dataclasses.dataclass
class RequestSweepResult:
    """K requests through one compiled scan: stacked per-round buffers
    plus the per-request readouts split back out of them
    (:meth:`metrics_rows`).  ``curves``/``msgs``/``dropped`` are
    [K, T]; ``state_digests`` are sha256 hexes of each request's final
    ``seen`` block truncated to its OWN (n, rumors) — bitwise the solo
    run's final state (pinned in tests/test_serving.py)."""
    specs: tuple
    curves: np.ndarray            # float32[K, T]
    msgs: np.ndarray              # float32[K, T]
    dropped: np.ndarray           # float32[K, T]
    rounds_to_target: np.ndarray  # int[K], -1 where never reached
    state_digests: tuple          # str[K]

    def metrics_rows(self):
        """Per-request round-metrics rows split out of the stacked
        buffers — the serving reply's observability payload (coverage
        curve, cumulative msgs, exact per-round destroyed-message
        counts) in ledger-friendly plain lists."""
        out = []
        for i, spec in enumerate(self.specs):
            out.append({
                "mode": spec.proto.mode, "n": spec.n,
                "rounds": int(self.curves.shape[1]),
                "coverage": [float(c) for c in self.curves[i]],
                "msgs": [float(m) for m in self.msgs[i]],
                "dropped": [float(d) for d in self.dropped[i]],
                "dropped_total": float(self.dropped[i].sum()),
                "rounds_to_target": int(self.rounds_to_target[i]),
            })
        return out


def _pow2_at_least(x: int, lo: int = 1) -> int:
    """The smallest power of two >= max(x, lo) — the serving bucket
    function (n-bucket, rumor bucket, batch-lane bucket)."""
    x = max(int(x), lo)
    return 1 << (x - 1).bit_length()


@functools.lru_cache(maxsize=16)
def _cached_request_sweep_scan(n_pad: int, k: int, r_max: int,
                               have_table: bool, need_push: bool,
                               need_pull: bool, have_ae: bool,
                               max_rounds: int):
    """The request megabatch's compiled scan, memoized by EXACTLY the
    statics its trace bakes: the pow2 n-bucket, the shared draw width
    ``k``, the rumor bucket, implicit-vs-table, the batch's
    half-elision switches, and the scan length.  Everything
    request-specific — mode flags, period, seed keys, per-request n
    and rumor count, static alive masks, metric denominators, and the
    four stacked nemesis schedule tables — arrives as runtime
    operands, so K compatible requests compile ONCE and every later
    same-bucket batch re-enters the executable (compile-count pinned
    in tests/test_serving.py, the _cached_churn_sweep_scan memo
    discipline).

    The callable takes ``(seen0, keys, msgs0, do_push, do_pull, do_ae,
    period, n_pt, r_pt, base_alive, metric_alive, die, rec, cut_tbl,
    drop_tbl, *topo_tables)`` — all leading-[K] stacks except the
    shared topology tables — and returns ``(final_seen, counts, msgs,
    lost)`` with [T, K] per-round buffers.  The coverage readout
    leaves the device as an EXACT integer count per request (the
    _cached_churn_sweep_scan rationale: integer sums are order-exact;
    the one division happens per request on the host, emulating the
    solo path's own lowering — see request_sweep_curves)."""
    if have_table:
        topo_ph = Topology(nbrs=jnp.zeros((0, 0), jnp.int32),
                           deg=jnp.zeros((0,), jnp.int32), n=n_pad,
                           family="placeholder")
    else:
        topo_ph = Topology(nbrs=None, deg=None, n=n_pad,
                           family="complete")
    colr = jnp.arange(r_max, dtype=jnp.int32)

    def one_req(seen, round_, base_key, msgs, do_push, do_pull, do_ae,
                period, n_pt, r_pt, base_alive, metric_alive,
                die, rec_, cut_row, drop_row, topo_tbl):
        nbrs, deg = topo_tbl if topo_tbl else (None, None)
        gids = jnp.arange(n_pad, dtype=jnp.int32)
        r = jnp.asarray(round_, jnp.int32)
        # per-round liveness / cut / drop from the request's OWN
        # schedule operands — the clamped steady-row lookup
        # (ops/nemesis._idx semantics, inlined over the [K, T] stack)
        down = (die <= r) & (r < rec_)
        alive = base_alive & ~down
        idx = jnp.minimum(jnp.maximum(r, 0), cut_row.shape[0] - 1)
        cut = cut_row[idx]
        dp = drop_row[idx]
        rkey = jax.random.fold_in(base_key, r)
        visible = seen & alive[:, None]
        delta, msgs_r, lost = _sweep_round_delta(
            rkey, r, gids, visible, alive, topo_ph, k, nbrs, deg,
            do_push, do_pull, do_ae, jnp.int32(k), dp, period, have_ae,
            scatter_n=n_pad, count_reduce=lambda c: c,
            gather=lambda v: v, need_push=need_push,
            need_pull=need_pull,
            peer_bound=(None if have_table else n_pt),
            cut=cut, want_lost=True)
        seen = seen | delta
        # integer coverage count: min over the request's REAL rumor
        # columns of its metric-alive entry count (phantom columns are
        # all-false and would win an unmasked min)
        cnt_r = jnp.sum(seen & metric_alive[:, None], axis=0,
                        dtype=jnp.int32)
        cnt = jnp.min(jnp.where(colr < r_pt, cnt_r,
                                jnp.int32(n_pad + 1)))
        return seen, msgs + msgs_r, cnt, lost

    @jax.jit
    def scan(seen0, seeds, msgs0, do_push, do_pull, do_ae, period,
             n_pt, r_pt, base_alive, metric_alive, die, rec_, cut_tbl,
             drop_tbl, *table):
        # key derivation INSIDE the compiled program: a host-side
        # vmapped jax.random.key over K seeds would be a fresh tiny
        # XLA program per distinct K — serving ticks vary K, and
        # steady-state serving must never compile.  Same key values as
        # the solo init_state (jax.random.key(seed)) by construction.
        keys = jax.vmap(jax.random.key)(seeds)

        def body(carry, round_):
            seen, msgs = carry
            seen, msgs, cnts, lost = jax.vmap(
                lambda s, key, m, a, b, c, p, npt, rpt, ba, ma, di, re,
                cu, dr: one_req(s, round_, key, m, a, b, c, p, npt,
                                rpt, ba, ma, di, re, cu, dr, table)
            )(seen, keys, msgs, do_push, do_pull,
              do_ae, period, n_pt, r_pt, base_alive, metric_alive,
              die, rec_, cut_tbl, drop_tbl)
            return (seen, msgs), (cnts, msgs, lost)
        (seen_f, _), out = jax.lax.scan(
            body, (seen0, msgs0),
            jnp.arange(max_rounds, dtype=jnp.int32))
        return (seen_f,) + out
    return scan


def request_sweep_curves(specs, topo: Optional[Topology] = None,
                         n_pad: Optional[int] = None, mesh=None,
                         axis_name: str = "request", lanes=None,
                         full: bool = False,
                         timing=None) -> RequestSweepResult:
    """Run K heterogeneous serving REQUESTS as ONE batched XLA program
    — the megabatch the admission batcher (rpc/batcher) dispatches per
    tick.  Every request's (mode, drop, period, seed, origin, target,
    static fault, churn schedule, n-within-bucket, rumors-within-
    bucket) is a runtime operand; the compiled scan is shared by the
    whole bucket (see :func:`_cached_request_sweep_scan` for the
    memo-key vs operand split, and docs/SERVING.md for the table).

    Bitwise contract (pinned in tests/test_serving.py): request i's
    coverage curve, cumulative msgs, rounds-to-target, and final seen
    state equal its SOLO ``runtime/simulator.simulate_curve`` dispatch
    byte for byte — same threefry streams (draws keyed by global id,
    so pow2 row padding is inert), same drop/cut order, and a host
    readout that emulates the solo coverage division exactly (the
    no-fault solo path lowers mean() as a recip-mul; the
    fault/churn-weighted path as a true division — both measured on
    this toolchain and reproduced per request below).

    ``topo``: None = the implicit complete family (requests may differ
    in n within the pow2 ``n_pad`` bucket — phantom rows are inert by
    the config_sweep ragged contract); a Topology = one shared
    explicit table (every request's n must equal it).  ``lanes`` pads
    the batch to a pow2 lane count with inert all-masked dummies so
    every batch size in a bucket shares one executable.  ``mesh``: an
    optional 1-D mesh shards the request axis (value-invariant,
    embarrassingly parallel — _shard_ensemble)."""
    specs = tuple(specs)
    if not specs:
        raise ValueError("need at least one RequestSpec")
    kset = {sp.proto.fanout for sp in specs}
    if len(kset) > 1:
        raise ValueError(
            f"request batch mixes fanouts {sorted(kset)}: the draw "
            "width is the one static the solo-bitwise contract pins "
            "(group by fanout in the batch key)")
    k = kset.pop()
    mrset = {sp.run.max_rounds for sp in specs}
    if len(mrset) > 1:
        raise ValueError(
            f"request batch mixes max_rounds {sorted(mrset)}: the scan "
            "length is static (group by max_rounds in the batch key)")
    max_rounds = mrset.pop()
    have_table = topo is not None
    if have_table:
        bad = [sp.n for sp in specs if sp.n != topo.n]
        if bad:
            raise ValueError(
                f"explicit-table requests must match the shared "
                f"topology's n={topo.n}; got {bad}")
        if n_pad is not None and n_pad != topo.n:
            raise ValueError("explicit-table batches keep n_pad == n")
        n_pad = topo.n
    else:
        want = _pow2_at_least(max(sp.n for sp in specs), 2)
        n_pad = want if n_pad is None else n_pad
        if n_pad < want:
            raise ValueError(f"n_pad={n_pad} below the batch's pow2 "
                             f"bucket {want}")
    r_max = _pow2_at_least(max(sp.proto.rumors for sp in specs))
    kN = len(specs)
    lanes = _pow2_at_least(kN) if lanes is None else lanes
    if lanes < kN:
        raise ValueError(f"lanes={lanes} below the batch size {kN}")
    # half-elision switches are batch-COMPOSITION statics; ``full=True``
    # (the serving batcher) pins all three ON so every tick of a bucket
    # shares ONE executable regardless of which modes happened to
    # coalesce — a masked absent half is bitwise inert (the disjoint-
    # RNG-tag elision contract in _sweep_round_delta), and steady-state
    # serving must never compile because a mode combination was new
    need_push = full or any(_MODE_FLAGS[sp.proto.mode][0]
                            for sp in specs)
    need_pull = full or any(_MODE_FLAGS[sp.proto.mode][1]
                            for sp in specs)
    have_ae = full or any(sp.proto.mode == C.ANTI_ENTROPY
                          for sp in specs)

    # -- per-request operand stacks (host-side; all CONTENT) ----------
    seen0 = np.zeros((lanes, n_pad, r_max), np.bool_)
    base_alive = np.zeros((lanes, n_pad), np.bool_)
    metric_alive = np.zeros((lanes, n_pad), np.bool_)
    weighted = []
    denoms = []
    from gossip_tpu.models.state import alive_mask
    for i, sp in enumerate(specs):
        # models/state.init_state's seeding formula (rumor r starts at
        # (origin + r) % n) in numpy — a jitted init per distinct
        # origin would be a tiny compile per request content
        cols = np.arange(sp.proto.rumors)
        seen0[i, (sp.run.origin + cols) % sp.n, cols] = True
        # fault-free requests (the common serving case) assemble their
        # masks with ZERO jax work — a jnp.ones per new n-within-bucket
        # would compile inside the serving window.  Fault-bearing masks
        # stay jax-side on purpose: the bernoulli death draw IS the
        # value the bitwise contract pins, and its tiny programs are
        # shape-keyed (warmed by the mix's first occurrence).
        am = alive_mask(sp.fault, sp.n, sp.run.origin)
        base_alive[i, :sp.n] = True if am is None else np.asarray(am)
        ma = NE.metric_alive(sp.fault, sp.n, sp.run.origin)
        weighted.append(ma is not None)
        if ma is None:
            metric_alive[i, :sp.n] = True
            denoms.append(float(sp.n))
        else:
            ma = np.asarray(ma)
            metric_alive[i, :sp.n] = ma
            denoms.append(float(ma.sum()))
    sched = NE.build_request_stack(
        [sp.fault for sp in specs], [sp.n for sp in specs], n_pad)
    # all remaining operand assembly is NUMPY by design: the lane
    # count varies tick to tick in serving, and any jnp op over a
    # K-sized input is a fresh tiny XLA program per distinct K —
    # steady-state serving assembles content with ZERO compiles (the
    # load-harness all-warm gate; only the memoized scan itself is a
    # compiled program, shared per bucket)
    pad = lanes - kN
    if pad:
        sched = NE.Schedule(
            die=np.concatenate([sched.die, np.full(
                (pad, n_pad), NE.NEVER, np.int32)]),
            rec=np.concatenate([sched.rec, np.full(
                (pad, n_pad), NE.NEVER, np.int32)]),
            cut_tbl=np.concatenate([sched.cut_tbl, np.full(
                (pad, sched.cut_tbl.shape[1]), -1, np.int32)]),
            drop_tbl=np.concatenate([sched.drop_tbl, np.zeros(
                (pad, sched.drop_tbl.shape[1]), np.float32)]))
    seeds = np.asarray([sp.run.seed for sp in specs] + [0] * pad,
                       np.uint32)

    def vec(fn, dtype, dummy):
        return np.asarray([fn(sp) for sp in specs] + [dummy] * pad,
                          dtype)

    # dummy lanes are fully inert: no half enabled, all-dead masks —
    # their draws exist but their deltas/counts are discarded
    do_push = vec(lambda sp: _MODE_FLAGS[sp.proto.mode][0], np.bool_,
                  False)
    do_pull = vec(lambda sp: _MODE_FLAGS[sp.proto.mode][1], np.bool_,
                  False)
    do_ae = vec(lambda sp: sp.proto.mode == C.ANTI_ENTROPY, np.bool_,
                False)
    period = vec(lambda sp: sp.proto.period, np.int32, 1)
    n_pt = vec(lambda sp: sp.n, np.int32, 2)
    r_pt = vec(lambda sp: sp.proto.rumors, np.int32, 1)

    scan = _cached_request_sweep_scan(n_pad, k, r_max, have_table,
                                      need_push, need_pull, have_ae,
                                      max_rounds)
    ops = [seen0, seeds,
           np.zeros((lanes,), np.float32), do_push, do_pull, do_ae,
           period, n_pt, r_pt, base_alive,
           metric_alive] + list(NE.sched_args(sched))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        if lanes % mesh.shape[axis_name] != 0:
            raise ValueError(
                f"{lanes} request lanes do not divide over the "
                f"{axis_name} mesh axis of size "
                f"{mesh.shape[axis_name]}")
        ops = [jax.device_put(x, NamedSharding(
            mesh, P(axis_name, *([None] * (x.ndim - 1))))) for x in ops]
    topo_tbl = (topo.nbrs, topo.deg) if have_table else ()
    from gossip_tpu.utils.trace import maybe_aot_timed
    seen_f, cnts, msgs, lost = maybe_aot_timed(scan, timing, *ops,
                                               *topo_tbl, label="sweep")

    # -- per-request readouts split back out of the stacked buffers --
    cnts = np.asarray(cnts).T[:kN]       # [K, T] exact integers
    msgs = np.asarray(msgs).T[:kN]
    lost = np.asarray(lost).T[:kN]
    seen_f = np.asarray(seen_f)
    curves = np.empty_like(cnts, dtype=np.float32)
    rtt = np.full(kN, -1, np.int64)
    digests = []
    import hashlib
    for i, sp in enumerate(specs):
        c = cnts[i].astype(np.float32)
        if weighted[i]:
            # the solo weighted readout is a true f32 division
            # (coverage()'s sum/w.sum() — measured lowering)
            curves[i] = c / np.float32(denoms[i])
        else:
            # the solo no-fault readout is jnp.mean, which lowers as a
            # reciprocal MULTIPLY (measured; true division differs by
            # 1 ulp on some counts) — emulate it exactly
            curves[i] = c * (np.float32(1.0) / np.float32(denoms[i]))
        hit = np.nonzero(curves[i] >= sp.run.target_coverage)[0]
        rtt[i] = int(hit[0]) + 1 if len(hit) else -1
        block = np.ascontiguousarray(
            seen_f[i, :sp.n, :sp.proto.rumors])
        digests.append(hashlib.sha256(block.tobytes()).hexdigest())
    return RequestSweepResult(specs=specs, curves=curves, msgs=msgs,
                              dropped=lost, rounds_to_target=rtt,
                              state_digests=tuple(digests))


@functools.lru_cache(maxsize=16)
def _cached_pod_sweep_scan(n: int, n_pad: int, nl: int, k_max: int,
                           have_ae: bool, need_push: bool, need_pull: bool,
                           multi: bool, have_table: bool, max_rounds: int,
                           origin: int, mesh, fault_static,
                           sweep_axis: str, node_axis: str):
    """The 2-D pod sweep's compiled scan, memoized by EXACTLY the
    statics its trace bakes in — max_rounds and origin, not the whole
    RunConfig, whose unused fields (seed: the sweep's seeds are
    per-point runtime operands) would fragment the cache (VERDICT r4
    task 7: re-entering the driver must be an executable-cache hit,
    not a whole-program retrace).

    Every array the trajectories depend on — seen blocks, seeds, the
    per-point flag vectors, and the (possibly family-stacked) topology
    tables — flows through the returned callable as a runtime ARGUMENT;
    the only topology facts baked into the trace are ``n`` and
    implicit-vs-table, which are part of this key.  The table branch
    gets a shape-empty placeholder whose ``.implicit`` is False so
    ``sample_peers`` dispatches to the table path (its row data always
    comes from the ``local_nbrs``/``local_deg`` arguments)."""
    from jax.sharding import PartitionSpec as P

    from gossip_tpu.parallel.sharded import local_rows, sharded_alive
    if have_table:
        topo_ph = Topology(nbrs=jnp.zeros((0, 0), jnp.int32),
                           deg=jnp.zeros((0,), jnp.int32), n=n,
                           family="placeholder")
    else:
        topo_ph = Topology(nbrs=None, deg=None, n=n, family="complete")

    def one_cfg_round(seen_l, round_, base_key, msgs,
                      do_push, do_pull, do_ae, fanout, dropp, period,
                      tidx, nbrs_l, deg_l):
        """One config's round on this node shard ([nl, R] rows)."""
        if multi:
            # per-config family slice of the node-sharded stack
            nbrs_l, deg_l = nbrs_l[tidx], deg_l[tidx]
        shard = jax.lax.axis_index(node_axis)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        # fault_static by name: the grid sweeps reject churn schedules
        # upstream (check_supported events=False), so this key carries
        # no schedule content — the staticcheck content-in-memo-key
        # naming contract (gossip_tpu/analysis/recompile.py)
        alive_l = local_rows(sharded_alive(fault_static, n, n_pad, origin),
                             node_axis, nl)
        rkey = jax.random.fold_in(base_key, round_)
        visible = seen_l & alive_l[:, None]

        def count_reduce(counts):
            # psum + own slice rather than psum_scatter: this runs under
            # vmap over the local configs
            return local_rows(jax.lax.psum(counts, node_axis), node_axis,
                              nl)

        delta, msgs_round = _sweep_round_delta(
            rkey, round_, gids, visible, alive_l, topo_ph, k_max,
            nbrs_l, deg_l, do_push, do_pull, do_ae, fanout, dropp, period,
            have_ae, scatter_n=n_pad, count_reduce=count_reduce,
            gather=lambda v: jax.lax.all_gather(v, node_axis, tiled=True),
            need_push=need_push, need_pull=need_pull)
        seen_new = seen_l | delta
        msgs_new = msgs + jax.lax.psum(msgs_round, node_axis)

        # coverage on-device (min over rumors of alive-weighted fraction)
        w = alive_l.astype(jnp.float32)
        cnt = jax.lax.psum(jnp.sum(seen_new * w[:, None], axis=0),
                           node_axis)                           # [R]
        denom = jax.lax.psum(jnp.sum(w), node_axis)
        cov = jnp.min(cnt / jnp.maximum(denom, 1.0))
        return seen_new, msgs_new, cov

    def local_block(seen_b, round_, keys_b, msgs_b,
                    dpush_b, dpull_b, dae_b, fan_b, drop_b, per_b, tidx_b,
                    *table):
        nbrs_l, deg_l = table if table else (None, None)
        return jax.vmap(
            lambda s, key, m, a, b, c, f, d, p, t: one_cfg_round(
                s, round_, key, m, a, b, c, f, d, p, t, nbrs_l, deg_l)
        )(seen_b, keys_b, msgs_b, dpush_b, dpull_b, dae_b, fan_b, drop_b,
          per_b, tidx_b)

    sw = P(sweep_axis)
    in_specs = [P(sweep_axis, node_axis, None), P(), sw, sw,
                sw, sw, sw, sw, sw, sw, sw]
    if multi:
        in_specs += [P(None, node_axis, None), P(None, node_axis)]
    elif have_table:
        in_specs += [P(node_axis, None), P(node_axis)]
    mapped = shard_map(local_block, mesh=mesh,
                           in_specs=tuple(in_specs),
                           out_specs=(P(sweep_axis, node_axis, None), sw,
                                      sw))

    @jax.jit
    def scan(seen, keys, msgs, *args):
        flags_, tbl = args[:7], args[7:]
        def body(carry, round_):
            seen, msgs = carry
            seen, msgs, covs = mapped(seen, round_, keys, msgs, *flags_,
                                      *tbl)
            return (seen, msgs), (covs, msgs)
        return jax.lax.scan(body, (seen, msgs),
                            jnp.arange(max_rounds, dtype=jnp.int32))

    return scan


def _pod_sweep_cache_stats(info, before=None) -> tuple:
    """(gauges, evicting) from ``lru_cache.cache_info()`` snapshots:
    the telemetry view of the pod-sweep scan memo.  ``evicting`` is
    the per-call thrash signature — THIS call missed (``misses`` grew
    past ``before``'s) while the memo was already full, so lru_cache
    evicted an entry to admit the new scan and some earlier shape's
    re-entry will now recompile the whole shard_map program.  Judged
    from the delta, not cumulative totals: a process that has seen 17
    distinct shapes over its lifetime is not thrashing when a later
    memo-hit sweep runs.  Pure function of the info tuples so the
    predicate is unit-testable without 17 real compiles."""
    gauges = {"pod_sweep_scan_cache_hits": info.hits,
              "pod_sweep_scan_cache_misses": info.misses,
              "pod_sweep_scan_cache_size": info.currsize,
              "pod_sweep_scan_cache_maxsize": info.maxsize}
    evicting = (before is not None
                and info.maxsize is not None
                and info.misses > before.misses
                and before.currsize >= info.maxsize)
    return gauges, evicting


def _emit_pod_sweep_cache_telemetry(before) -> None:
    """Sweep-end cache telemetry (the compile-once PR): gauges for the
    memoized scan's hit/miss/size, and a ``sweep_cache_eviction``
    warning event when this sweep's scan displaced a cached one — a
    grid of more than the memo's 16 distinct shape keys used to thrash
    and recompile silently.  ``before`` is the cache_info snapshot the
    sweep took before building its scan."""
    from gossip_tpu.utils import telemetry
    led = telemetry.current()
    gauges, evicting = _pod_sweep_cache_stats(
        _cached_pod_sweep_scan.cache_info(), before)
    # sync=False throughout: this emitter runs INSIDE whatever wall
    # the caller is timing around the sweep (the dry run's
    # hybrid_2d_sweep windows) — flush-only, no fsync latency in a
    # measured steady_ms (the driver_timing contract, utils/trace)
    for name, value in gauges.items():
        led.gauge(name, value, sync=False)
    if evicting:
        led.event(
            "sweep_cache_eviction", sync=False,
            **gauges,
            note="grid exceeds the pod-sweep scan memo (maxsize=16 "
                 "distinct shape keys): some re-entries recompile the "
                 "whole shard_map program; split the grid by shape or "
                 "raise _cached_pod_sweep_scan's maxsize")


def config_sweep_curves_2d(points, topo, run: RunConfig,
                           mesh, fault: Optional[FaultConfig] = None,
                           k_max: Optional[int] = None, rumors: int = 1,
                           sweep_axis: str = "sweep",
                           node_axis: str = "nodes",
                           timing=None) -> ConfigSweepResult:
    """The north star's full 2-D pod sweep: distinct configs sharded over
    ``sweep_axis`` AND every config's node dimension sharded over
    ``node_axis`` — one ``shard_map`` over a 2-D mesh, one XLA program.

    The config axis is embarrassingly parallel; the node axis uses the
    dense collectives of parallel/sharded.py (``psum`` count reduction,
    ``all_gather`` pull digests) *under vmap* — each device holds a
    ``[C_local, nl, R]`` block and the collectives batch over its local
    configs.  Same trajectory definition as :func:`config_sweep_curves`
    (same RNG keying by global node id, same shared-``k_max`` draw widths),
    so results are identical to the 1-D batch for any mesh shape.

    ``topo`` may be a SEQUENCE of same-n explicit topologies, exactly as
    in :func:`config_sweep_curves`: families stack into one
    ``int32[F, n_pad, D_max]`` operand whose ROWS shard over
    ``node_axis``, and each point's ``topo_idx`` dynamic-slices its
    family — the complete "sweep fanout, mode, and graph topology across
    a TPU pod" program.

    ``timing``: optional wall-decomposition dict (utils/trace
    .maybe_aot_timed contract) — the AOT path additionally routes the
    scan's compile through the GOSSIP_COMPILE_CACHE executable store
    (``timing["compile_cache"]`` records hit|miss|disabled), making
    the pod sweep warm-startable across processes like the other
    sharded drivers.  Sweep-end telemetry always reports the scan
    memo's hit/miss gauges and warns when the grid exceeded its 16
    shape keys (:func:`_emit_pod_sweep_cache_telemetry`).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from gossip_tpu.parallel.sharded import _pad_rows, pad_to_mesh
    points = tuple(points)
    if not points:
        raise ValueError("need at least one SweepPoint")
    if fault is not None and fault.drop_prob > 0.0:
        raise ValueError("per-config loss goes through SweepPoint.drop_prob;"
                         " FaultConfig.drop_prob would be ambiguous here")
    # the grid round body is its own lowering (no churn path yet):
    # reject a schedule loudly rather than silently running static-only
    NE.check_supported(fault, engine="config-sweep", events=False,
                       partitions=False, ramp=False)
    topos, multi, topo0 = _normalize_topos(topo, points)
    if multi and any(t.n != topo0.n for t in topos):
        raise ValueError(
            "the 2-D pod sweep shards ONE node dimension; mixed-n "
            "phantom batching is the 1-D config_sweep_curves path — "
            "run the pod sweep per n")
    eff_rumors_2d = {pt.rumors or rumors for pt in points}
    if len(eff_rumors_2d) > 1:
        raise ValueError(
            "the 2-D pod sweep carries ONE rumor axis; mixed-rumor "
            "phantom batching is the 1-D config_sweep_curves path — "
            "run the pod sweep per rumor count")
    rumors = eff_rumors_2d.pop()
    cN = len(points)
    p_sweep = mesh.shape[sweep_axis]
    if cN % p_sweep != 0:
        raise ValueError(f"{cN} configs do not divide over the "
                         f"{sweep_axis} axis of size {p_sweep}")
    n = topo0.n
    n_pad = pad_to_mesh(n, mesh, node_axis)
    nl = n_pad // mesh.shape[node_axis]
    k_max = k_max or max(pt.fanout for pt in points)
    if any(pt.fanout > k_max for pt in points):
        raise ValueError("k_max smaller than a point's fanout")
    have_ae = any(pt.mode == C.ANTI_ENTROPY for pt in points)
    # same static half-elision as config_sweep_curves (VERDICT r2 item 7)
    need_push = any(_MODE_FLAGS[pt.mode][0] for pt in points)
    need_pull = any(_MODE_FLAGS[pt.mode][1] for pt in points)
    have_table = not topo0.implicit
    if multi:
        nbrs_stack, deg_stack = _stack_topologies(topos)
        # family stack rows pad to the node mesh (sentinel n rows,
        # degree 0 — permanently dark, same as the single-family pad;
        # a zero-width pad is a no-op)
        tables = (jnp.pad(nbrs_stack, ((0, 0), (0, n_pad - n), (0, 0)),
                          constant_values=n),
                  jnp.pad(deg_stack, ((0, 0), (0, n_pad - n))))
    elif have_table:
        tables = (_pad_rows(topo0.nbrs, n_pad, n),
                  _pad_rows(topo0.deg, n_pad, 0))
    else:
        tables = ()

    cache_before = _cached_pod_sweep_scan.cache_info()
    scan = _cached_pod_sweep_scan(n, n_pad, nl, k_max, have_ae, need_push,
                                  need_pull, multi, have_table,
                                  run.max_rounds, run.origin, mesh,
                                  fault, sweep_axis, node_axis)

    proto_like = ProtocolConfig(mode=C.PUSH, fanout=k_max, rumors=rumors)
    base = init_state(run, proto_like, n)
    seen0 = _pad_rows(base.seen, n_pad, False)
    init_seen = jnp.broadcast_to(seen0, (cN,) + seen0.shape)
    keys = jax.vmap(jax.random.key)(
        jnp.asarray([pt.seed for pt in points], jnp.uint32))
    flags = [jnp.asarray([_MODE_FLAGS[pt.mode][0] for pt in points]),
             jnp.asarray([_MODE_FLAGS[pt.mode][1] for pt in points]),
             jnp.asarray([pt.mode == C.ANTI_ENTROPY for pt in points]),
             jnp.asarray([pt.fanout for pt in points], jnp.int32),
             jnp.asarray([pt.drop_prob for pt in points], jnp.float32),
             jnp.asarray([pt.period for pt in points], jnp.int32),
             jnp.asarray([pt.topo_idx for pt in points], jnp.int32)]
    init_seen = jax.device_put(
        init_seen, NamedSharding(mesh, P(sweep_axis, node_axis, None)))
    row = NamedSharding(mesh, P(sweep_axis))
    keys = jax.device_put(keys, row)
    flags = [jax.device_put(f, row) for f in flags]

    from gossip_tpu.utils.trace import maybe_aot_timed
    _, (covs, msgs) = maybe_aot_timed(scan, timing, init_seen, keys,
                                      jnp.zeros((cN,), jnp.float32),
                                      *flags, *tables, label="sweep")
    _emit_pod_sweep_cache_telemetry(cache_before)
    curves = np.asarray(covs).T
    return ConfigSweepResult(points=points, curves=curves,
                             msgs=np.asarray(msgs).T,
                             rounds_to_target=_rounds_to_target(
                                 curves, run.target_coverage),
                             target=run.target_coverage)


def _rounds_to_target(curves: np.ndarray, target: float) -> np.ndarray:
    """First 1-based round index reaching target per row; -1 if never."""
    hit = np.full(curves.shape[0], -1, np.int64)
    reached = curves >= target
    any_hit = reached.any(axis=1)
    hit[any_hit] = reached[any_hit].argmax(axis=1) + 1
    return hit


# ---------------------------------------------------------------------------
# Config sweep: distinct (mode, fanout, drop, period, seed) points batched
# into one compiled program.
# ---------------------------------------------------------------------------

# mode -> (do_push, do_pull); anti-entropy is a period-gated bidirectional
# exchange (pull + reverse delta, models/si.py semantics).
_MODE_FLAGS = {C.PUSH: (True, False), C.PULL: (False, True),
               C.PUSH_PULL: (True, True), C.ANTI_ENTROPY: (False, True)}


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One shape-invariant config point of a batched sweep.

    ``topo_idx`` selects the point's topology from the family stack when
    :func:`config_sweep_curves` is given a SEQUENCE of same-n explicit
    topologies (the north star's "sweep fanout, mode, and graph topology"
    axis — VERDICT r2 item 6); with a single topology it must stay 0."""
    mode: str = C.PUSH
    fanout: int = 1
    drop_prob: float = 0.0
    period: int = 1          # anti-entropy cadence (1 = every round)
    seed: int = 0
    topo_idx: int = 0
    rumors: int = 0          # 0 = the batch-level default (round 4:
    #                          mixed rumor counts batch by padding to
    #                          the max with inert all-false phantom
    #                          columns, masked out of the coverage min)

    def __post_init__(self):
        if self.mode not in _MODE_FLAGS:
            raise ValueError(
                f"config sweep supports {sorted(_MODE_FLAGS)}; got "
                f"{self.mode!r} (flood/swim change the round structure)")
        if self.fanout < 1:
            raise ValueError("fanout must be >= 1")
        if self.period < 1:
            raise ValueError("period must be >= 1")
        if self.period > 1 and self.mode != C.ANTI_ENTROPY:
            raise ValueError("period > 1 is the anti-entropy cadence; solo "
                             f"{self.mode!r} rounds ignore period, so a "
                             "batched point must not silently differ")
        if self.topo_idx < 0:
            raise ValueError("topo_idx must be >= 0")
        if self.rumors < 0:
            raise ValueError("rumors must be >= 0 (0 = batch default)")


@dataclasses.dataclass
class ConfigSweepResult:
    points: tuple                 # the SweepPoints, batch order
    curves: np.ndarray            # float32[C, T]
    msgs: np.ndarray              # float32[C, T]
    rounds_to_target: np.ndarray  # int[C], -1 where never reached
    target: float

    def summaries(self):
        out = []
        for i, pt in enumerate(self.points):
            out.append({
                "point": dataclasses.asdict(pt),
                "rounds_to_target": int(self.rounds_to_target[i]),
                "converged": bool(self.rounds_to_target[i] >= 0),
                "final_coverage": float(self.curves[i, -1]),
                "msgs_total": float(self.msgs[i, -1]),
            })
        return out


def _drop_targets(rkey, tag, gids, targets, drop_prob, sentinel):
    """apply_drop with a *traced* drop probability (always draws; a literal
    0.0 probability yields an all-False mask, so the where is a no-op and
    the result is bitwise identical to not drawing at all)."""
    dropped = drop_mask(rkey, tag, gids, targets.shape[1], drop_prob)
    return jnp.where(dropped, jnp.int32(sentinel), targets)


def _sweep_round_delta(rkey, round_, gids, visible, alive_l, topo, k_max,
                       nbrs, deg, do_push, do_pull, do_ae, fanout, dropp,
                       period, have_ae, scatter_n, count_reduce, gather,
                       need_push=True, need_pull=True, peer_bound=None,
                       cut=None, want_lost=False):
    """The ONE per-config sweep round body — shared by the single-device
    batch, the 2-D pod sweep, and the request-batched serving driver,
    which differ only in how scatter counts reduce (``count_reduce``),
    how the digest table is assembled (``gather``), and the scatter
    sentinel (``scatter_n``).  Returns (delta, msgs_this_round) for
    this row block — plus the nemesis ``lost`` count with
    ``want_lost=True``.

    ``need_push``/``need_pull`` are STATIC elision switches (VERDICT r2
    item 7): when no point in the batch pushes (resp. pulls), the whole
    half — its sampling, scatter/gather, and reduction — is never built,
    instead of being computed and masked.  Eliding a half cannot change
    the other half's trajectory: the halves draw from disjoint RNG tags
    (PUSH_TAG/PUSH_DROP_TAG vs PULL_TAG/PULL_DROP_TAG), same pattern as
    the ``have_ae`` elision of the reverse delta.

    ``peer_bound`` (mixed-n IMPLICIT batches): the point's own n as a
    traced scalar, bounding its uniform partner draw on the complete
    graph — randint with a traced bound reproduces the solo static-n
    draw bitwise (sample_peers_complete).  None keeps the static
    ``topo.n`` path, byte-identical to the pre-round-4 lowering.

    ``cut`` (the request-batched serving path): a traced per-round
    partition cut (ops/nemesis cut_tbl lookup, -1 = closed) applied
    AFTER the drop coins, in exactly models/si.make_si_round's churn
    order, so a batched request's trajectory stays bitwise the solo
    churn run.  ``want_lost=True`` additionally returns the kernels'
    EXACT destroyed-message count (drop coins + open cut) as a third
    output, gated per config by the same do_push/on masks as msgs."""
    n = topo.n
    col = jnp.arange(k_max, dtype=jnp.int32)[None, :]
    delta = jnp.zeros_like(visible)
    msgs = jnp.float32(0.0)
    lost = jnp.float32(0.0)

    def _peers(key):
        if peer_bound is not None:
            return sample_peers_complete(key, gids, peer_bound, k_max, True)
        return sample_peers(key, gids, topo, k_max, True,
                            local_nbrs=nbrs, local_deg=deg)

    def _cut(targets):
        # closed-cut rounds (cut = -1) are a bitwise no-op, so the
        # no-churn solo trajectory is reproduced exactly (ops/nemesis
        # same_side contract)
        if cut is None:
            return targets
        return NE.partition_targets(cut, gids, targets, n)

    if need_push:
        # push half (masked by do_push for non-push configs in the batch)
        pkey = jax.random.fold_in(rkey, si_mod.PUSH_TAG)
        targets0 = _peers(pkey)
        targets0 = jnp.where(col < fanout, targets0, jnp.int32(n))
        targets = _drop_targets(rkey, si_mod.PUSH_DROP_TAG, gids, targets0,
                                dropp, n)
        targets = _cut(targets)
        sender_active = jnp.any(visible, axis=1)
        valid = (targets < n) & sender_active[:, None]
        counts = push_counts(scatter_n,
                             jnp.where(valid, targets, scatter_n), visible)
        delta = (count_reduce(counts) > 0) & do_push
        msgs = jnp.where(do_push, jnp.sum(valid).astype(jnp.float32), 0.0)
        if want_lost:
            lost = lost + jnp.where(
                do_push,
                NE.lost_count(targets0, targets, sender_active, n), 0.0)

    if need_pull:
        # pull half (anti-entropy = bidirectional exchange gated by period)
        seen_all = gather(visible)
        qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
        partners0 = _peers(qkey)
        partners0 = jnp.where(col < fanout, partners0, jnp.int32(n))
        partners = _drop_targets(rkey, si_mod.PULL_DROP_TAG, gids,
                                 partners0, dropp, n)
        partners = _cut(partners)
        pulled = pull_merge(seen_all, partners, n)
        partners = jnp.where(alive_l[:, None], partners, n)
        n_req = jnp.sum(partners < n).astype(jnp.float32)
        on = do_pull & ((round_ % period) == 0)
        if want_lost:
            # post-alive-mask partners, alive requesters: a dead row's
            # slot carried no request to lose, and a quiescent AE round
            # sends nothing (`on` covers both; period == 1 keeps plain
            # pull always-on) — models/si.py's exact churn accounting
            lost = lost + jnp.where(
                on, NE.lost_count(partners0, partners, alive_l, n), 0.0)
        delta = delta | (pulled & on)
        if have_ae:
            # anti-entropy reverse delta: the initiator's state scatters
            # back into the partner's row (models/si.py) — built only
            # when the batch has an AE point
            bcounts = push_counts(
                scatter_n, jnp.where(partners < n, partners, scatter_n),
                visible)
            delta = delta | ((count_reduce(bcounts) > 0) & (on & do_ae))
        mfac = jnp.where(do_ae, 3.0, 2.0)
        msgs = msgs + jnp.where(on, mfac * n_req, 0.0)
    out = delta & alive_l[:, None]
    return (out, msgs, lost) if want_lost else (out, msgs)


def _normalize_topos(topo, points):
    """(topos, multi, topo0) from a Topology-or-sequence argument, with
    the ONE topo_idx range check both sweep entry points share."""
    topos = tuple(topo) if isinstance(topo, (list, tuple)) else (topo,)
    if any(pt.topo_idx >= len(topos) for pt in points):
        raise ValueError(
            f"a point's topo_idx is past the {len(topos)} supplied "
            "topolog(ies)")
    return topos, len(topos) > 1, topos[0]


def _stack_topologies(topos):
    """Explicit topologies -> (nbrs_stack[F, n_max, D_max],
    deg_stack[F, n_max]), neighbor columns padded with the shared
    sentinel ``n_max``.  The sentinel columns sit past every row's
    degree, so sampling (which draws indices < deg) can never touch them
    — a point's trajectory is independent of the OTHER entries in the
    stack.

    Entries may differ in ``n`` (round 4, VERDICT r3 item 6): smaller
    graphs pad to ``n_max`` with PHANTOM rows (degree 0, sentinel
    neighbors).  Phantoms are inert end to end: degree-0 sampling emits
    the sentinel, no real row's table contains a phantom id, and the
    sweep masks them out of liveness and coverage — so a point's
    trajectory on its real prefix is BITWISE the solo run at its own n
    (per-node draws are keyed by global id, the sharding-invariance
    contract in ops/sampling)."""
    n_max = max(t.n for t in topos)
    for t in topos:
        if t.implicit:
            raise ValueError(
                "a topology sweep needs explicit neighbor tables for "
                "every entry (the implicit complete graph has no table "
                "to stack, and its partner draw is bounded by a static "
                "n); sweep it as its own batch")
    d_max = max(t.width for t in topos)
    nbrs = jnp.stack([
        jnp.pad(t.nbrs, ((0, n_max - t.n), (0, d_max - t.width)),
                constant_values=n_max)
        for t in topos])
    deg = jnp.stack([jnp.pad(t.deg, (0, n_max - t.n)) for t in topos])
    return nbrs, deg


def config_sweep_curves(points, topo, run: RunConfig,
                        fault: Optional[FaultConfig] = None,
                        k_max: Optional[int] = None,
                        rumors: int = 1, mesh=None,
                        axis_name: str = "sweep",
                        _force_both: bool = False) -> ConfigSweepResult:
    """Run C distinct config points as ONE batched XLA program.

    ``topo`` is one Topology, or a SEQUENCE of explicit topologies — the
    topology axis of the north star's "sweep fanout, mode, and graph
    topology" sentence (VERDICT r2 item 6).  With a sequence, each
    point's ``topo_idx`` picks its entry from a stacked
    ``int32[F, n_max, D_max]`` table operand; one compile covers the
    whole families x modes x fanouts grid.  Entries may differ in n
    (round 4): smaller graphs pad with inert phantom rows and the
    point's coverage/liveness use its OWN n — so a families x sizes
    grid is one program too (mixed-n batches take no FaultConfig and
    need origin + rumors within the smallest n; see the errors below).
    A point's trajectory equals the solo single-topology batch BITWISE
    on its real prefix (same keys; the stack pads neighbor columns with
    the sentinel past each row's degree, which sampling never draws).

    ``fault`` contributes only the static death mask (shared structure);
    per-config loss goes through ``SweepPoint.drop_prob`` — a FaultConfig
    with drop_prob set here is rejected to keep the two channels distinct.

    ``k_max`` is the shared sampling width (default: max fanout in the
    batch).  Trajectories are a function of (point, k_max): a point whose
    fanout equals k_max reproduces the solo make_si_round trajectory
    BITWISE (same keys, same draw shapes); batch composition never changes
    results (tested in tests/test_config_sweep.py).

    ``mesh``: a 1-D device mesh shards the CONFIG axis — the north star's
    "sweep fanout, mode, topology across a TPU pod" DP axis.  Configs are
    independent, so the batch is embarrassingly parallel: the batched
    arrays are placed with a ``P(axis_name)`` sharding and XLA partitions
    the whole scan with zero cross-device traffic.  Results are the same
    trajectories in the same order (sharding never changes values).
    """
    points = tuple(points)
    if not points:
        raise ValueError("need at least one SweepPoint")
    if fault is not None and fault.drop_prob > 0.0:
        raise ValueError("per-config loss goes through SweepPoint.drop_prob;"
                         " FaultConfig.drop_prob would be ambiguous here")
    # the grid round body is its own lowering (no churn path yet):
    # reject a schedule loudly rather than silently running static-only
    NE.check_supported(fault, engine="config-sweep", events=False,
                       partitions=False, ramp=False)
    if mesh is not None and len(points) % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"{len(points)} configs do not divide over the {axis_name} "
            f"mesh axis of size {mesh.shape[axis_name]}; pad the batch "
            "(duplicate a point) or change the mesh")
    topos, multi, topo0 = _normalize_topos(topo, points)
    all_implicit = all(t.implicit for t in topos)
    if multi and not all_implicit and any(t.implicit for t in topos):
        raise ValueError(
            "a topology batch mixes implicit (complete) and explicit "
            "entries; the stacked-table operand and the traced-bound "
            "draw are different programs — batch them separately")
    n = max(t.n for t in topos)
    ragged = multi and any(t.n != n for t in topos)
    if ragged:
        # phantom-row batching (VERDICT r3 item 6): different-n entries
        # share one program.  The two channels that are seeded at a
        # point's own n in a solo run must be unambiguous here:
        if fault is not None:
            raise ValueError(
                "a mixed-n sweep takes no FaultConfig: the static death "
                "draw is shaped by each point's own n in a solo run, so "
                "a shared draw would silently change trajectories; run "
                "faulted points as a same-n batch")
        min_n = min(t.n for t in topos)
        worst_r = max((pt.rumors or rumors) for pt in points)
        if run.origin + worst_r > min_n:
            raise ValueError(
                f"origin {run.origin} + rumors {worst_r} exceeds the "
                f"smallest n ({min_n}) in the batch: rumor r seeds node "
                "(origin + r) % n, which would differ from the solo run "
                "on the smaller graphs")
    if multi:
        # the sweep's scatter sentinel and partner-validity bound is the
        # PADDED n; same-n stacks keep n == every entry's n (no change)
        topo0 = dataclasses.replace(topo0, n=n)
    k_max = k_max or max(pt.fanout for pt in points)
    if any(pt.fanout > k_max for pt in points):
        raise ValueError("k_max smaller than a point's fanout")
    cN = len(points)
    # Per-point rumor counts (round 4): pad the rumor axis to the batch
    # max; a point's phantom columns are ALL-FALSE forever (no origin
    # seed, so they never scatter, never gather, never flip a
    # sender_active bit — msgs and the real prefix stay bitwise equal
    # to the solo run) and are masked out of the coverage min (an inert
    # all-true column would instead cap reported coverage at
    # n*(1/n) != 1.0 in f32 on non-dyadic n).
    eff_rumors = [pt.rumors or rumors for pt in points]
    r_max = max(eff_rumors)
    mixed_rumors = len(set(eff_rumors)) > 1
    proto_like = ProtocolConfig(mode=C.PUSH, fanout=k_max, rumors=r_max)
    if multi and not all_implicit:
        tables = _stack_topologies(topos)
    elif topo0.implicit:
        # mixed-n COMPLETE graphs (round 4, the last structural axis):
        # no table to stack — each point's uniform draw is bounded by
        # its own n as a traced operand (sample_peers_complete)
        tables = ()
        if ragged and min(t.n for t in topos) < 2:
            raise ValueError("mixed-n complete batches need every "
                             "n >= 2 (the traced self-exclusion bound)")
    else:
        tables = (topo0.nbrs, topo0.deg)
    have_ae = any(pt.mode == C.ANTI_ENTROPY for pt in points)
    # static half-elision (VERDICT r2 item 7): a pure-push (resp. pure-
    # pull) batch never builds the other half.  _force_both is a
    # benchmarking hook proving the elision's win (tests only).
    need_push = _force_both or any(_MODE_FLAGS[pt.mode][0]
                                   for pt in points)
    need_pull = _force_both or any(_MODE_FLAGS[pt.mode][1]
                                   for pt in points)

    def one_round(seen, round_, base_key, msgs,
                  do_push, do_pull, do_ae, fanout, dropp, period, tidx,
                  n_pt, *tbl):
        if multi and tbl:
            # per-config family: one dynamic slice out of the stacked
            # table operand (tables are jit arguments — DESIGN.md §6)
            nbrs, deg = tbl[0][tidx], tbl[1][tidx]
        else:
            nbrs, deg = tbl if tbl else (None, None)
        # O(N) buffers in-trace: no inline constants in the compile request
        gids = jnp.arange(n, dtype=jnp.int32)
        alive = alive_mask(fault, n, run.origin)
        alive_b = jnp.ones((n,), jnp.bool_) if alive is None else alive
        if ragged:
            # phantom rows past this point's own n are never alive —
            # they cannot send, receive, or count.  For explicit tables
            # this is the second lock (their rows are already degree-0/
            # sentinel); for the tableless implicit case it is the ONLY
            # lock — the traced-bound draw targets [0, n_pt) but phantom
            # SENDERS exist, and this mask is what silences them.
            alive_b = alive_b & (gids < n_pt)
        rkey = jax.random.fold_in(base_key, round_)
        visible = seen & alive_b[:, None]
        delta, msgs_round = _sweep_round_delta(
            rkey, round_, gids, visible, alive_b, topo0, k_max, nbrs, deg,
            do_push, do_pull, do_ae, fanout, dropp, period, have_ae,
            scatter_n=n, count_reduce=lambda c: c, gather=lambda v: v,
            need_push=need_push, need_pull=need_pull,
            peer_bound=(n_pt if (ragged and topo0.implicit) else None))
        return seen | delta, round_ + 1, msgs + msgs_round

    batched = jax.vmap(one_round,
                       in_axes=(0,) * 12 + (None,) * len(tables))

    base = init_state(run, proto_like, n)
    if mixed_rumors:
        # zero the phantom columns per point in ONE broadcasted where
        # (base seeds all r_max origins; a point with fewer rumors must
        # not seed the rest)
        colr = jnp.arange(r_max)[None, None, :]
        ers = jnp.asarray(eff_rumors, jnp.int32)[:, None, None]
        init_seen = jnp.where(colr < ers, base.seen[None], False)
    else:
        init_seen = jnp.broadcast_to(base.seen, (cN,) + base.seen.shape)
    keys = jax.vmap(jax.random.key)(
        jnp.asarray([pt.seed for pt in points], jnp.uint32))
    do_push = jnp.asarray([_MODE_FLAGS[pt.mode][0] for pt in points])
    do_pull = jnp.asarray([_MODE_FLAGS[pt.mode][1] for pt in points])
    do_ae = jnp.asarray([pt.mode == C.ANTI_ENTROPY for pt in points])
    fanouts = jnp.asarray([pt.fanout for pt in points], jnp.int32)
    drops = jnp.asarray([pt.drop_prob for pt in points], jnp.float32)
    periods = jnp.asarray([pt.period for pt in points], jnp.int32)
    tidxs = jnp.asarray([pt.topo_idx for pt in points], jnp.int32)
    n_pts = jnp.asarray([topos[pt.topo_idx].n for pt in points], jnp.int32)
    rum_pts = jnp.asarray(eff_rumors, jnp.int32)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        row = NamedSharding(mesh, P(axis_name))
        init_seen = jax.device_put(
            init_seen, NamedSharding(mesh, P(axis_name, None, None)))
        keys = jax.device_put(keys, row)
        (do_push, do_pull, do_ae, fanouts, drops, periods, tidxs, n_pts,
         rum_pts) = (
            jax.device_put(x, row)
            for x in (do_push, do_pull, do_ae, fanouts, drops, periods,
                      tidxs, n_pts, rum_pts))

    @jax.jit
    def scan(seen, rounds, keys, msgs, *tbl):
        alive = alive_mask(fault, n, run.origin)
        colr = jnp.arange(r_max)

        def cov_fn(x, n_pt, r_pt):
            # One coverage body for every batching shape, ops chosen to
            # reproduce the solo paths BIT FOR BIT (tests assert curve
            # equality with solo runs):
            #  * ragged n — per-point divisor via recip-MUL, matching
            #    jnp.mean's lowering (true division differs by 1 ulp);
            #  * uniform n — models/si.coverage's exact expressions;
            #  * mixed rumors — phantom columns masked out of the min
            #    (they are all-false, so unmasked they would win it).
            if ragged:
                gids = jnp.arange(n, dtype=jnp.int32)
                w = (gids < n_pt).astype(jnp.float32)
                counts = jnp.sum(x.astype(jnp.float32) * w[:, None],
                                 axis=0)
                vals = counts * (1.0 / n_pt.astype(jnp.float32))
            elif alive is None:
                vals = jnp.mean(x.astype(jnp.float32), axis=0)
            else:
                w = alive.astype(jnp.float32)
                vals = (x.astype(jnp.float32) * w[:, None]).sum(0) / w.sum()
            if mixed_rumors:
                vals = jnp.where(colr < r_pt, vals, 2.0)
            return jnp.min(vals)

        cov_all = jax.vmap(cov_fn)

        def body(carry, _):
            seen, rounds, msgs = carry
            seen, rounds, msgs = batched(seen, rounds, keys, msgs, do_push,
                                         do_pull, do_ae, fanouts, drops,
                                         periods, tidxs, n_pts, *tbl)
            covs = cov_all(seen, n_pts, rum_pts)
            return (seen, rounds, msgs), (covs, msgs)
        return jax.lax.scan(body, (seen, rounds, msgs), None,
                            length=run.max_rounds)

    _, (covs, msgs) = scan(init_seen, jnp.zeros((cN,), jnp.int32), keys,
                           jnp.zeros((cN,), jnp.float32), *tables)
    curves = np.asarray(covs).T
    return ConfigSweepResult(points=points, curves=curves,
                             msgs=np.asarray(msgs).T,
                             rounds_to_target=_rounds_to_target(
                                 curves, run.target_coverage),
                             target=run.target_coverage)


def config_sweep_curves_partitioned(points, topo, run: RunConfig,
                                    fault: Optional[FaultConfig] = None,
                                    k_max: Optional[int] = None,
                                    rumors: int = 1) -> ConfigSweepResult:
    """Mode-partitioned sweep execution (VERDICT r2 item 7): split a
    MIXED grid into push-only / pull-only / push+pull buckets and batch
    each separately, so the pure buckets never build (or pay per round
    for) the other half.  Trajectories are IDENTICAL to the single batch:
    one shared ``k_max`` across buckets (trajectories are a function of
    (point, k_max)) and disjoint RNG tags between the halves.  Results
    are merged back in the caller's point order.

    Single-bucket grids fall through to :func:`config_sweep_curves`
    directly (whose static elision already skips the absent half).  A
    config-axis mesh is not supported here — bucket sizes rarely divide
    a mesh; shard the unpartitioned batch instead (elision still applies
    when the WHOLE grid is pure)."""
    points = tuple(points)
    if not points:
        raise ValueError("need at least one SweepPoint")
    k_max = k_max or max(pt.fanout for pt in points)

    buckets: dict = {}
    for i, pt in enumerate(points):
        buckets.setdefault(_MODE_FLAGS[pt.mode], []).append(i)
    if len(buckets) == 1:
        return config_sweep_curves(points, topo, run, fault, k_max, rumors)

    curves = np.zeros((len(points), run.max_rounds), np.float32)
    msgs = np.zeros_like(curves)
    for idxs in buckets.values():
        sub = config_sweep_curves([points[i] for i in idxs], topo, run,
                                  fault, k_max, rumors)
        curves[idxs] = sub.curves
        msgs[idxs] = sub.msgs
    return ConfigSweepResult(points=points, curves=curves, msgs=msgs,
                             rounds_to_target=_rounds_to_target(
                                 curves, run.target_coverage),
                             target=run.target_coverage)


# -- SIR rumor-mongering ensembles -----------------------------------------
#
# The classic rumor-mongering results (Demers et al. §1.4's tables) are
# DISTRIBUTIONS: residue and extinction time vary seed-to-seed because the
# whole process is a branching process near its critical point early on.
# One vmapped scan = |seeds| independent SIR trajectories in one XLA
# program, same shape as ensemble_curves but carrying the SIR state.

@dataclasses.dataclass
class RumorEnsembleResult:
    curves: np.ndarray             # float32[S, T] coverage per seed/round
    hot: np.ndarray                # float32[S, T] infective fraction
    msgs: np.ndarray               # float32[S, T]
    target: float

    @property
    def extinction_rounds(self) -> np.ndarray:
        """int[S]: first round with no hot pair (+1), -1 if none."""
        out = np.full(self.hot.shape[0], -1, np.int64)
        for i, h in enumerate(self.hot):
            idx = np.nonzero(h == 0.0)[0]
            if len(idx):
                out[i] = idx[0] + 1
        return out

    @property
    def residues(self) -> np.ndarray:
        return 1.0 - self.curves[:, -1]

    def summary(self) -> dict:
        ext = self.extinction_rounds
        done = ext >= 0
        # residue is an AT-EXTINCTION statistic: truncated (still-hot at
        # max_rounds) seeds would contribute transient not-yet-informed
        # mass and inflate the distribution, so they are excluded here —
        # like the extinction stats; raise max_rounds if terminated <
        # seeds
        res = self.residues[done]
        return {
            "seeds": int(len(ext)),
            "terminated": int(done.sum()),
            "extinction_rounds_mean": (float(ext[done].mean())
                                       if done.any() else None),
            "extinction_rounds_p95": (float(np.percentile(ext[done], 95))
                                      if done.any() else None),
            "residue_mean": float(res.mean()) if len(res) else None,
            "residue_p50": float(np.median(res)) if len(res) else None,
            "residue_p95": (float(np.percentile(res, 95))
                            if len(res) else None),
            "residue_max": float(res.max()) if len(res) else None,
            "coverage_mean": float(self.curves[:, -1].mean()),
            "msgs_mean": float(self.msgs[:, -1].mean()),
            "target": self.target,
        }


def ensemble_swim_curves(proto: ProtocolConfig, n: int, run: RunConfig,
                         seeds: Sequence[int], dead_nodes=(),
                         fail_round: int = 0,
                         fault: Optional[FaultConfig] = None,
                         topo: Optional[Topology] = None, mesh=None,
                         axis_name: str = "seed") -> EnsembleResult:
    """|seeds| independent SWIM failure-detection trajectories as ONE
    batched XLA program — the detection-LATENCY distribution for a fixed
    failure scenario across PRNG seeds (probe targets, proxy choices,
    and dissemination fan-outs all redraw per seed), which is the
    operational question SWIM answers ("how long until the cluster
    knows?").  Per-seed curves are bitwise identical to solo
    runtime/simulator.simulate_swim_curve runs with the same seed
    (tested); ``curves`` carries the per-round detection fraction, so
    ``rounds_to_target`` is rounds-to-detection."""
    from gossip_tpu.models import swim as SW
    dead = tuple(dead_nodes)
    step, tables = SW.make_swim_round(proto, n, dead, fail_round, fault,
                                      topo, tabled=True,
                                      max_rounds=run.max_rounds)
    base = SW.init_swim_state(n, proto.swim_subjects, 0)
    keys = jax.vmap(jax.random.key)(jnp.asarray(list(seeds), jnp.uint32))
    s = len(seeds)
    init = SW.SwimState(
        wire=jnp.broadcast_to(base.wire, (s,) + base.wire.shape),
        timer=jnp.broadcast_to(base.timer, (s,) + base.timer.shape),
        round=jnp.zeros((s,), jnp.int32),
        base_key=keys,
        msgs=jnp.zeros((s,), jnp.float32),
    )
    init = _shard_ensemble(init, mesh, axis_name, s)
    rotate = proto.swim_rotate
    epoch_rounds = SW.resolve_epoch_rounds(proto, n)

    @jax.jit
    def scan(states, *tbl):
        # observer denominator: base mask minus PERMANENT churn deaths
        # (matches simulate_swim_curve/until — a forever-down node
        # cannot observe; a recovering node stays in the denominator)
        alive_obs = SW.observer_alive(n, dead, fault)

        # metric targets: static scripted deaths + permanent churn
        # deaths (`dead` stays static-only for the kernel factory)
        targets = SW.detection_targets(dead, fault)

        def detection(st):
            window = SW.subject_window(st.round - 1, proto.swim_subjects,
                                       n, rotate, epoch_rounds)
            return SW.detection_fraction(
                SW.SwimState(st.wire[:n], st.timer[:n], st.round,
                             st.base_key, st.msgs), targets,
                alive_obs, subj_gids=window
            ) if targets else jnp.float32(0.0)

        def body(st, _):
            st = jax.vmap(lambda x: step(x, *tbl))(st)
            return st, (jax.vmap(detection)(st), st.msgs)
        return jax.lax.scan(body, states, None, length=run.max_rounds)

    _, (dets, msgs) = scan(init, *tables)
    curves = np.asarray(dets).T
    return EnsembleResult(curves=curves, msgs=np.asarray(msgs).T,
                          rounds_to_target=_rounds_to_target(
                              curves, run.target_coverage),
                          target=run.target_coverage)


def ensemble_rumor_curves(proto: ProtocolConfig, topo: Topology,
                          run: RunConfig, seeds: Sequence[int],
                          fault: Optional[FaultConfig] = None, mesh=None,
                          axis_name: str = "seed"
                          ) -> RumorEnsembleResult:
    """|seeds| independent SIR trajectories as ONE batched XLA program.
    Per-seed trajectories are bitwise identical to solo
    models/rumor.simulate_curve_rumor runs with the same seed (tested)."""
    from gossip_tpu.models.rumor import (RumorState, init_rumor_state,
                                         make_rumor_round, rumor_coverage)
    step, tables = make_rumor_round(proto, topo, fault, run.origin,
                                    tabled=True)
    step = NE.drop_lost(step, NE.get(fault))
    base = init_rumor_state(run, proto, topo.n)
    keys = jax.vmap(jax.random.key)(jnp.asarray(list(seeds), jnp.uint32))
    s = len(seeds)
    init = RumorState(
        seen=jnp.broadcast_to(base.seen, (s,) + base.seen.shape),
        hot=jnp.broadcast_to(base.hot, (s,) + base.hot.shape),
        cnt=jnp.broadcast_to(base.cnt, (s,) + base.cnt.shape),
        round=jnp.zeros((s,), jnp.int32),
        base_key=keys,
        msgs=jnp.zeros((s,), jnp.float32),
    )
    init = _shard_ensemble(init, mesh, axis_name, s)

    @jax.jit
    def scan(states, *tbl):
        # eventual alive set under churn — matches the solo
        # simulate_curve_rumor weighting (bitwise-parity contract)
        alive = NE.metric_alive(fault, topo.n, run.origin)
        hot_w = (None if alive is None else alive.astype(jnp.float32))

        def one_metrics(st):
            hot_any = jnp.any(st.hot, axis=1).astype(jnp.float32)
            frac = (jnp.mean(hot_any) if hot_w is None
                    else jnp.sum(hot_any * hot_w) / jnp.sum(hot_w))
            return rumor_coverage(st.seen, alive), frac, st.msgs

        def body(st, _):
            st = jax.vmap(lambda x: step(x, *tbl))(st)
            covs, hots, msgs = jax.vmap(one_metrics)(st)
            return st, (covs, hots, msgs)
        return jax.lax.scan(body, states, None, length=run.max_rounds)

    _, (covs, hots, msgs) = scan(init, *tables)
    return RumorEnsembleResult(curves=np.asarray(covs).T,
                               hot=np.asarray(hots).T,
                               msgs=np.asarray(msgs).T,
                               target=run.target_coverage)
