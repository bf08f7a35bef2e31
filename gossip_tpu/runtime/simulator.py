"""Round-batched simulation drivers (the ``jax-tpu`` backend).

Two drivers over the same round step:

  * :func:`simulate_curve` — ``lax.scan`` over a fixed number of rounds,
    recording the coverage curve + cumulative message counts.  This is the
    observability product the reference never had (SURVEY.md §5: Maelstrom
    computed everything externally).
  * :func:`simulate_until` — ``lax.while_loop`` until coverage >= target,
    for racing the wall-clock (the bench path).  No per-round host sync:
    the whole loop is one XLA program.

The Go-semantics event-driven backend (``go-native``) lives in
:mod:`gossip_tpu.runtime.gonative`; both implement "run this protocol config
to convergence", which is the Backend seam from BASELINE.json's north star.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models.si import coverage, make_si_round
from gossip_tpu.models.state import SimState, alive_mask, init_state
from gossip_tpu.topology.generators import Topology


@dataclasses.dataclass
class CurveResult:
    coverage: np.ndarray        # float32[T] min-over-rumors coverage after round t
    msgs: np.ndarray            # float32[T] cumulative messages after round t
    rounds_to_target: int       # first round index with coverage >= target (+1),
                                # or -1 if never reached
    final_coverage: float
    state: SimState


@dataclasses.dataclass
class UntilResult:
    rounds: int
    coverage: float
    msgs: float
    state: SimState


def _build(proto: ProtocolConfig, topo: Topology, run: RunConfig,
           fault: Optional[FaultConfig]):
    """step + its table args + init.  Tables travel as jit ARGUMENTS and the
    alive mask is rebuilt in-trace, so no O(N) buffer is inlined into the
    XLA compile request (models/swim.py doc)."""
    step, tables = make_si_round(proto, topo, fault, run.origin, tabled=True)
    init = init_state(run, proto, topo.n)
    return step, tables, init


def simulate_curve(proto: ProtocolConfig, topo: Topology, run: RunConfig,
                   fault: Optional[FaultConfig] = None) -> CurveResult:
    from gossip_tpu.ops import nemesis as NE
    step, tables, init = _build(proto, topo, run, fault)
    step = NE.drop_lost(step, NE.get(fault))

    @jax.jit
    def scan(init_state_, *tbl):
        alive = NE.metric_alive(fault, topo.n, run.origin)
        def body(state, _):
            state = step(state, *tbl)
            return state, (coverage(state.seen, alive), state.msgs)
        return jax.lax.scan(body, init_state_, None, length=run.max_rounds)

    final, (covs, msgs) = scan(init, *tables)
    covs = np.asarray(covs)
    msgs = np.asarray(msgs)
    hit = np.nonzero(covs >= run.target_coverage)[0]
    return CurveResult(
        coverage=covs,
        msgs=msgs,
        rounds_to_target=int(hit[0]) + 1 if len(hit) else -1,
        final_coverage=float(covs[-1]),
        state=final,
    )


def simulate_until(proto: ProtocolConfig, topo: Topology, run: RunConfig,
                   fault: Optional[FaultConfig] = None,
                   timing: Optional[dict] = None) -> UntilResult:
    """``timing``: pass a dict to get ``compile_s``/``steady_s`` filled
    via the AOT split (utils.trace.aot_timed) instead of one fused call —
    the hardware-table contract that walls never mix compile with
    steady state."""
    from gossip_tpu.ops import nemesis as NE
    step, tables, init = _build(proto, topo, run, fault)
    step = NE.drop_lost(step, NE.get(fault))
    target = jnp.float32(run.target_coverage)
    alive = NE.metric_alive(fault, topo.n, run.origin)  # host final metric

    @jax.jit
    def loop(init_state_, *tbl):
        alive_t = NE.metric_alive(fault, topo.n, run.origin)
        def cond(state):
            return ((coverage(state.seen, alive_t) < target)
                    & (state.round < run.max_rounds))
        def body(state):
            return step(state, *tbl)
        return jax.lax.while_loop(cond, body, init_state_)

    from gossip_tpu.utils.trace import maybe_aot_timed
    final = maybe_aot_timed(loop, timing, init, *tables, label="solo")
    return UntilResult(
        rounds=int(final.round),
        coverage=float(coverage(final.seen, alive)),
        msgs=float(final.msgs),
        state=final,
    )


def _swim_recorder(proto: ProtocolConfig, n: int, n_pad: int,
                   n_shards: int):
    """In-loop metrics row for the SWIM drivers (ops/round_metrics,
    failure-detection reading of the counters): ``newly`` is newly
    CONFIRMED-DEAD (subject, observer) wire entries — the detection
    front's growth; ``front`` the per-shard fraction of observers
    holding any confirmed death; ``offered`` the dissemination upper
    bound fanout*n*S (every diss message carries the full S-subject
    wire row); ``bytes`` the pmax contribution table's per-device
    egress (``4*n_pad*S``; 0 on a single device — SWIM's only
    collective is the wire merge).  The previous confirmed count rides
    the carry as one scalar (parallel/sharded._dense_recorder
    liveness rationale)."""
    from gossip_tpu.models.swim import DEAD_WIRE
    from gossip_tpu.ops import round_metrics as RM
    s_subj = proto.swim_subjects
    offered = float(proto.fanout * n * s_subj)
    per_round_bytes = (0.0 if n_shards == 1
                       else 4.0 * n_pad * s_subj + 4.0)

    def rec(m, prev, msgs0, s1, obs_pad):
        dead_tbl = s1.wire == DEAD_WIRE
        confirmed = jnp.sum(dead_tbl & obs_pad[:, None],
                            dtype=jnp.float32)
        newly = confirmed - prev
        return RM.record(
            m, newly=newly, msgs=s1.msgs - msgs0,
            dup=RM.dup_estimate(offered, newly),
            bytes=per_round_bytes,
            front=RM.front_bool(dead_tbl, obs_pad, n_shards)), confirmed

    def init_prev(state, obs_pad):
        return jnp.sum((state.wire == DEAD_WIRE) & obs_pad[:, None],
                       dtype=jnp.float32)

    return rec, init_prev


def _swim_obs_pad(alive_obs, n: int, n_pad: int):
    """The observer mask padded to the sharded row count (padding rows
    never observe; a no-op when unsharded)."""
    if n_pad == n:
        return alive_obs
    return jnp.zeros((n_pad,), jnp.bool_).at[:n].set(alive_obs)


def simulate_swim_curve(proto: ProtocolConfig, n: int, rounds: int,
                        dead_nodes=(), fail_round: int = 0,
                        fault: Optional[FaultConfig] = None,
                        topo: Optional[Topology] = None,
                        seed: int = 0, mesh=None, timing=None):
    """SWIM detection-fraction curve over ``rounds`` (lax.scan, one XLA
    program).  With ``mesh`` the sharded twin runs instead.  Returns
    (detection[T] as numpy, final SwimState).  ``timing``: optional
    compile/steady AOT-split dict (utils/trace.maybe_aot_timed); with
    an active run ledger the scan carries a round-metrics buffer stack
    (ops/round_metrics)."""
    from gossip_tpu.models import swim as SW
    # tabled=True: topology arrays enter the jitted scan as ARGUMENTS, not
    # closure constants — a closed-over 1M-row neighbor table would be
    # serialized inline into the compile request (models/swim doc).
    if mesh is None:
        step, tables = SW.make_swim_round(proto, n, tuple(dead_nodes),
                                          fail_round, fault, topo,
                                          tabled=True, max_rounds=rounds)
        init = SW.init_swim_state(n, proto.swim_subjects, seed)
    else:
        from gossip_tpu.parallel.sharded_swim import (
            init_sharded_swim_state, make_sharded_swim_round)
        step, tables = make_sharded_swim_round(proto, n, mesh,
                                               tuple(dead_nodes),
                                               fail_round, fault, topo,
                                               tabled=True,
                                               max_rounds=rounds)
        init = init_sharded_swim_state(n, proto, mesh, seed)
    # metric targets: static scripted deaths + permanent churn deaths
    # (the kernels got the static dead_nodes only — churn die/recover
    # timing lives in the schedule, not the fail_round mask)
    dead = SW.detection_targets(dead_nodes, fault)
    rotate = proto.swim_rotate
    epoch_rounds = SW.resolve_epoch_rounds(proto, n)
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    n_pad = int(init.wire.shape[0])
    n_shards = int(np.prod(list(mesh.shape.values()))) if mesh else 1
    rec, init_prev = (_swim_recorder(proto, n, n_pad, n_shards)
                      if RM.wanted() else (None, None))

    @jax.jit
    def scan(state, *tbl):
        # Observer population: nodes that stay alive after fail_round.
        # Without this mask, fault-dead observers sit in the denominator
        # and the detection fraction plateaus at the alive fraction, never
        # reaching the target.  Built in-trace: no O(N) inline constant.
        alive_obs = SW.observer_alive(n, tuple(dead_nodes), fault)
        obs_pad = _swim_obs_pad(alive_obs, n, n_pad)
        m0 = (RM.init(rounds, n_shards, "simulate_swim_curve")
              if rec else None)
        p0 = init_prev(state, obs_pad) if rec else None

        def body(carry, _):
            s0, m, prev = carry
            msgs0 = s0.msgs
            s = step(s0, *tbl)
            if m is not None:
                m, prev = rec(m, prev, msgs0, s, obs_pad)
            # observers: rows [0, n) — drops the mesh padding rows (a no-op
            # slice in the unsharded case); detection over the dead subjects
            # in the window of the round just executed (s.round - 1)
            window = SW.subject_window(s.round - 1, proto.swim_subjects, n,
                                       rotate, epoch_rounds)
            frac = SW.detection_fraction(
                SW.SwimState(s.wire[:n], s.timer[:n], s.round,
                             s.base_key, s.msgs), dead,
                alive_obs, subj_gids=window) if dead else 0.0
            return (s, m, prev), frac
        return jax.lax.scan(body, (state, m0, p0), None, length=rounds)

    (final, _, _), fracs = maybe_aot_timed(scan, timing, init, *tables,
                                           label="solo")
    return np.asarray(fracs), final


def simulate_swim_until(proto: ProtocolConfig, n: int, max_rounds: int,
                        target: float, dead_nodes=(), fail_round: int = 0,
                        fault: Optional[FaultConfig] = None,
                        topo: Optional[Topology] = None,
                        seed: int = 0, mesh=None,
                        timing: Optional[dict] = None):
    """SWIM to target detection (lax.while_loop, one XLA program) — the
    early-exit twin of :func:`simulate_swim_curve` for runs that don't
    need the curve: detection typically completes in ~40% of the curve
    driver's fixed budget, and this driver stops there.  Returns
    (rounds, detection, peak, final SwimState); rounds == final.round
    when the target was hit, max_rounds otherwise (caller compares
    detection).  ``peak`` is the best detection seen over the run — under
    a rotating subject window the final round's detection can drop back
    toward 0 after the window leaves the dead node's epoch, so the peak,
    not the final, is the rotating headline number."""
    from gossip_tpu.models import swim as SW
    if mesh is None:
        step, tables = SW.make_swim_round(proto, n, tuple(dead_nodes),
                                          fail_round, fault, topo,
                                          tabled=True, max_rounds=max_rounds)
        init = SW.init_swim_state(n, proto.swim_subjects, seed)
    else:
        from gossip_tpu.parallel.sharded_swim import (
            init_sharded_swim_state, make_sharded_swim_round)
        step, tables = make_sharded_swim_round(proto, n, mesh,
                                               tuple(dead_nodes),
                                               fail_round, fault, topo,
                                               tabled=True,
                                               max_rounds=max_rounds)
        init = init_sharded_swim_state(n, proto, mesh, seed)
    # metric targets: static scripted deaths + permanent churn deaths
    dead = SW.detection_targets(dead_nodes, fault)
    rotate = proto.swim_rotate
    epoch_rounds = SW.resolve_epoch_rounds(proto, n)
    tgt = jnp.float32(target)
    from gossip_tpu.ops import round_metrics as RM
    n_pad = int(init.wire.shape[0])
    n_shards = int(np.prod(list(mesh.shape.values()))) if mesh else 1
    rec, init_prev = (_swim_recorder(proto, n, n_pad, n_shards)
                      if RM.wanted() else (None, None))

    @jax.jit
    def loop(state, *tbl):
        alive_obs = SW.observer_alive(n, tuple(dead_nodes), fault)
        obs_pad = _swim_obs_pad(alive_obs, n, n_pad)
        m0 = (RM.init(max_rounds, n_shards, "simulate_swim_until")
              if rec else None)
        p0 = init_prev(state, obs_pad) if rec else None

        def detection(s):
            window = SW.subject_window(s.round - 1, proto.swim_subjects, n,
                                       rotate, epoch_rounds)
            return SW.detection_fraction(
                SW.SwimState(s.wire[:n], s.timer[:n], s.round,
                             s.base_key, s.msgs), dead,
                alive_obs, subj_gids=window) if dead else jnp.float32(0.0)

        def cond(carry):
            s, det, _, _, _ = carry
            return (det < tgt) & (s.round < max_rounds)

        def body(carry):
            s0, _, peak, m, prev = carry
            msgs0 = s0.msgs
            s = step(s0, *tbl)
            if m is not None:
                m, prev = rec(m, prev, msgs0, s, obs_pad)
            det = detection(s)
            return s, det, jnp.maximum(peak, det), m, prev

        return jax.lax.while_loop(
            cond, body,
            (state, jnp.float32(0.0), jnp.float32(0.0), m0, p0))

    from gossip_tpu.utils.trace import maybe_aot_timed
    final, det, peak, _, _ = maybe_aot_timed(loop, timing, init, *tables,
                                             label="solo")
    return int(final.round), float(det), float(peak), final


def checkpointed_swim(proto: ProtocolConfig, n: int, run: RunConfig,
                      path: str, every: int = 50, dead_nodes=(),
                      fail_round: int = 0,
                      fault: Optional[FaultConfig] = None,
                      topo: Optional[Topology] = None, mesh=None,
                      resume_state=None, want_curve: bool = False,
                      curve_prefix=(), extra_meta=None):
    """Fixed-budget SWIM run in compiled segments with atomic npz
    checkpoints — the failure-detection twin of the SI ``--checkpoint``
    engines (utils/checkpoint.run_with_checkpoints; the reference loses
    all state on process death, main.go:22-26).  The rotating subject
    window needs no host-side driver — ``subject_window`` is computed
    in-trace from ``state.round`` — so the generic segment runner drives
    it unchanged and resume is bitwise (tests/test_checkpoint_sharded).

    ``want_curve`` records the per-round detection fraction; the final
    detection is computed from the final state either way.  With
    ``mesh`` the node-sharded twin runs (resume re-places the padded
    rows via restore_sharded_swim_state).  Returns
    ``(final_state, detection, curve-or-None)``.

    Churn schedules (events + drop ramps; the SWIM factories reject
    partitions — membership overlay) run in the segments exactly as in
    the straight drivers: the step indexes its ABSOLUTE ``state.round``,
    which the checkpoint persists, so resume == straight run bitwise
    under an active fault program (utils/checkpoint crash contract;
    tests/test_crash_safety.py pins detection 1.0 on the scheduled
    permanent crash across a kill).  ``detection_targets`` already
    folds permanent churn deaths into the metric target set, and
    ``observer_alive`` drops them from the observer denominator.
    """
    from gossip_tpu.models import swim as SW
    from gossip_tpu.utils.checkpoint import run_with_checkpoints
    dead = tuple(dead_nodes)
    rotate = proto.swim_rotate
    epoch_rounds = SW.resolve_epoch_rounds(proto, n)
    if mesh is None:
        step, tables = SW.make_swim_round(proto, n, dead, fail_round,
                                          fault, topo, tabled=True,
                                          max_rounds=run.max_rounds)
        state = (resume_state if resume_state is not None
                 else SW.init_swim_state(n, proto.swim_subjects, run.seed))
    else:
        from gossip_tpu.parallel.sharded_swim import (
            init_sharded_swim_state, make_sharded_swim_round,
            restore_sharded_swim_state)
        step, tables = make_sharded_swim_round(proto, n, mesh, dead,
                                               fail_round, fault, topo,
                                               tabled=True,
                                               max_rounds=run.max_rounds)
        state = (restore_sharded_swim_state(resume_state, mesh)
                 if resume_state is not None
                 else init_sharded_swim_state(n, proto, mesh, run.seed))

    # metric targets: static scripted deaths + permanent churn deaths
    # (`dead` stays static-only — it scripts the kernels' fail_round mask)
    targets = SW.detection_targets(dead, fault)

    def detection(s):
        # same in-trace construction as simulate_swim_curve's body:
        # detection of the round just executed (window at s.round - 1),
        # observers sliced to the real rows
        alive_obs = SW.observer_alive(n, dead, fault)
        window = SW.subject_window(s.round - 1, proto.swim_subjects, n,
                                   rotate, epoch_rounds)
        return SW.detection_fraction(
            SW.SwimState(s.wire[:n], s.timer[:n], s.round,
                         s.base_key, s.msgs), targets,
            alive_obs, subj_gids=window) if targets else jnp.float32(0.0)

    curve_fn = detection if want_curve else None
    remaining = max(0, run.max_rounds - int(state.round))
    out = run_with_checkpoints(step, state, remaining, path, every=every,
                               step_args=tables, curve_fn=curve_fn,
                               curve_prefix=curve_prefix,
                               extra_meta=extra_meta)
    final, curve = out if want_curve else (out, None)
    if curve:
        det = float(curve[-1])    # the scan already computed it
    elif int(final.round):
        det = float(jax.jit(detection)(final))
    else:
        det = 0.0
    return final, det, curve


def compiled_until(proto: ProtocolConfig, topo: Topology, run: RunConfig,
                   fault: Optional[FaultConfig] = None):
    """Lowered/compiled while-loop runner + fresh init state, for benchmarks
    that must separate compile time from run time.  The returned loop takes
    (state, *tables); pass the returned tables through."""
    from gossip_tpu.ops import nemesis as NE
    step, tables, init = _build(proto, topo, run, fault)
    step = NE.drop_lost(step, NE.get(fault))
    target = jnp.float32(run.target_coverage)

    @partial(jax.jit, donate_argnums=0)
    def loop(state, *tbl):
        alive = NE.metric_alive(fault, topo.n, run.origin)
        def cond(s):
            return ((coverage(s.seen, alive) < target)
                    & (s.round < run.max_rounds))
        def body(s):
            return step(s, *tbl)
        return jax.lax.while_loop(cond, body, state)

    return loop, init, tables
