"""Node-dimension sharding: the round step over a `jax.sharding.Mesh`.

This is the TPU-pod scale path (SURVEY.md §7 layer 4, §2.3): the reference
distributes by running one OS process per cluster node under Maelstrom
(reference main.go — node identity via ``node.ID()``, topology keyed by node
id); here the node dimension is an array axis sharded across devices with
``jax.shard_map``, and the reference's stdin/stdout JSON "network" (SURVEY.md
§2.4) becomes XLA collectives over ICI:

  * **push**   — each shard scatter-adds its outgoing rumors into an
    ``int32[N, R]`` count table, reduced to the owning shard with
    ``psum_scatter`` (addition *is* an XLA collective reduction; boolean OR is
    not — ``counts > 0`` recovers the OR, see ops/propagate.push_counts).
  * **pull / flood** — the visible digest table is ``all_gather``-ed
    (``bool[N, R]``: 1 byte/node/rumor, 10 MB at 10M nodes — cheap on ICI)
    and each shard gathers its sampled rows locally.
  * **coverage / message counters** — ``psum``.

Bitwise parity with the single-device kernel (tests/test_sharding.py) holds
because every random draw is keyed by (base_key, round, *global* node id) —
see ops/sampling — so mesh shape never changes the trajectory.

Nodes are padded to a multiple of the mesh size; padding rows are permanently
dead (never sample, never receive, excluded from coverage).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.si import coverage
from gossip_tpu.models.state import (SimState, alive_mask, bind_tables,
                                     init_state)
from gossip_tpu.ops.propagate import flood_gather, pull_merge, push_counts
from gossip_tpu.ops.sampling import apply_drop, drop_mask, sample_peers
from gossip_tpu.topology.generators import Topology


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = "nodes") -> Mesh:
    """1-D device mesh over the node axis (the SP/CP analog — SURVEY.md §5:
    the scaled long dimension is nodes, not tokens)."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available")
        devs = devs[:n_devices]
    return Mesh(devs, (axis_name,))


def pad_to_mesh(n: int, mesh: Mesh, axis_name: str) -> int:
    p = mesh.shape[axis_name]
    return math.ceil(n / p) * p


def _pad_rows(x: jax.Array, n_pad: int, fill) -> jax.Array:
    n = x.shape[0]
    if n == n_pad:
        return x
    pad_shape = (n_pad - n,) + x.shape[1:]
    return jnp.concatenate([x, jnp.full(pad_shape, fill, x.dtype)], axis=0)


def sharded_alive(fault: Optional[FaultConfig], n: int, n_pad: int,
                  origin: int) -> jax.Array:
    """Combined liveness mask over padded rows: real & not-dead.

    Unlike the single-device kernel (which skips masking entirely when there
    are no faults), the sharded kernel always carries this mask because the
    padding rows must stay dark."""
    alive = alive_mask(fault, n, origin)
    if alive is None:
        alive = jnp.ones((n,), jnp.bool_)
    return _pad_rows(alive, n_pad, False)


def make_sharded_si_round(
        proto: ProtocolConfig, topo: Topology, mesh: Mesh,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False):
    """Build the sharded round step.  Semantically identical to
    models/si.make_si_round; the returned function expects ``state.seen`` of
    shape ``[n_pad, R]`` (see :func:`init_sharded_state`) and may be called
    under an outer ``jax.jit`` / ``lax.while_loop``.

    Returns ``step: SimState -> SimState``; ``tabled=True`` returns
    ``(step, tables)`` with the padded topology arrays as step ARGUMENTS —
    a closed-over 1M+-row table is serialized inline into the XLA compile
    request (models/swim.py doc).  The liveness mask is built in-trace."""
    n, k = topo.n, proto.fanout
    mode = proto.mode
    if mode == C.SWIM:
        raise ValueError("SWIM rounds are built by models/swim.py")
    if mode == C.RUMOR:
        raise ValueError("rumor-mongering rounds are built by "
                         "parallel/sharded_rumor.py (SIR state, not SI)")
    if mode == C.FLOOD and topo.implicit:
        raise ValueError("flood mode needs an explicit neighbor table")
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    drop_prob = 0.0 if fault is None else fault.drop_prob
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)

    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)   # sentinel = n
        deg_pad = _pad_rows(topo.deg, n_pad, 0)

    def local_round(seen_l, round_, base_key, msgs, *table):
        """One round on this shard's rows.  Axis-collective ops: psum_scatter
        (push counts), all_gather (pull/flood digests), psum (counters)."""
        table, sched = NE.split_tables(ch, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        # liveness in-trace (replicated compute, no O(N) inline constant)
        if ch is not None:
            # churn path: per-round liveness / drop prob / cut from the
            # schedule OPERANDS, indexed by the loop counter (ops/nemesis
            # module doc — the compiled loop carries no schedule content)
            base_pad = _pad_rows(
                NE.base_alive_or_ones(fault, n, origin), n_pad, False)
            alive_l = NE.alive_rows(sched, base_pad, round_)[gids]
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
        else:
            alive_l = sharded_alive(fault, n, n_pad, origin)[gids]
            dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        visible = seen_l & alive_l[:, None]
        delta = jnp.zeros_like(seen_l)
        msgs_local = jnp.float32(0.0)
        if have_table:
            nbrs_l, deg_l = table
        else:
            nbrs_l = deg_l = None

        if mode in (C.PUSH, C.PUSH_PULL):
            pkey = jax.random.fold_in(rkey, si_mod.PUSH_TAG)
            targets0 = sample_peers(pkey, gids, topo, k, proto.exclude_self,
                                    local_nbrs=nbrs_l, local_deg=deg_l)
            targets = apply_drop(rkey, si_mod.PUSH_DROP_TAG, gids,
                                 targets0, dp, n, force=ch is not None)
            if ch is not None:
                targets = NE.partition_targets(cut, gids, targets, n)
            sender_active = jnp.any(visible, axis=1)
            valid = (targets < n) & sender_active[:, None]
            # invalid -> n_pad so scatter mode='drop' really drops them
            # (sentinel n would land on a padding row when n < n_pad)
            counts = push_counts(n_pad, jnp.where(valid, targets, n_pad),
                                 visible)
            counts_l = jax.lax.psum_scatter(counts, axis_name,
                                            scatter_dimension=0, tiled=True)
            delta = delta | (counts_l > 0)
            msgs_local = msgs_local + jnp.sum(valid).astype(jnp.float32)
            if ch is not None:
                lost = lost + NE.lost_count(targets0, targets,
                                            sender_active, n)

        if mode in (C.PULL, C.PUSH_PULL, C.ANTI_ENTROPY):
            seen_all = jax.lax.all_gather(visible, axis_name, tiled=True)
            qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
            partners0 = sample_peers(qkey, gids, topo, k, proto.exclude_self,
                                     local_nbrs=nbrs_l, local_deg=deg_l)
            partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, gids,
                                  partners0, dp, n, force=ch is not None)
            if ch is not None:
                partners = NE.partition_targets(cut, gids, partners, n)
            pulled = pull_merge(seen_all, partners, n)
            partners = jnp.where(alive_l[:, None], partners, n)
            n_req = jnp.sum(partners < n).astype(jnp.float32)
            if ch is not None:
                lost_pull = NE.lost_count(partners0, partners, alive_l, n)
                if mode == C.ANTI_ENTROPY and proto.period > 1:
                    # quiescent rounds send nothing, so nothing is lost
                    lost_pull = jnp.where((round_ % proto.period) == 0,
                                          lost_pull, 0.0)
                lost = lost + lost_pull
            if mode == C.ANTI_ENTROPY:
                # bidirectional reconciliation (twin of models/si.py): the
                # initiator's state scatters back into the partner's row
                bt = jnp.where(partners < n, partners, n_pad)

                def reverse_delta(_):
                    bcounts = push_counts(n_pad, bt, visible)
                    return jax.lax.psum_scatter(bcounts, axis_name,
                                                scatter_dimension=0,
                                                tiled=True) > 0

                if proto.period > 1:
                    # lax.cond, not a mask: the psum_scatter must not move
                    # bytes on quiescent rounds (the predicate is replicated,
                    # so every shard takes the same branch)
                    on = (round_ % proto.period) == 0
                    back = jax.lax.cond(
                        on, reverse_delta,
                        lambda _: jnp.zeros_like(pulled), None)
                    pulled = jnp.where(on, pulled, False)
                    n_req = jnp.where(on, n_req, 0.0)
                else:
                    back = reverse_delta(None)
                delta = delta | pulled | back
                msgs_local = msgs_local + 3.0 * n_req
            else:
                delta = delta | pulled
                msgs_local = msgs_local + 2.0 * n_req

        if mode == C.FLOOD:
            seen_all = jax.lax.all_gather(visible, axis_name, tiled=True)
            nbrs_use = nbrs_l
            if ch is not None:
                # churn path: always draw (traced p), then cut the
                # cross-partition edges (models/si.py flood twin)
                dropped = drop_mask(rkey, si_mod.FLOOD_DROP_TAG, gids,
                                    nbrs_use.shape[1], dp)
                nbrs_use = jnp.where(dropped, jnp.int32(n), nbrs_use)
                nbrs_use = NE.partition_targets(cut, gids, nbrs_use, n)
                act_full = jnp.any(seen_all, axis=1)
                edge_live = ((nbrs_l < n)
                             & act_full[jnp.clip(nbrs_l, 0, n - 1)])
                lost = lost + jnp.sum(edge_live & (nbrs_use >= n),
                                      dtype=jnp.float32)
            elif drop_prob > 0.0:
                dropped = drop_mask(rkey, si_mod.FLOOD_DROP_TAG, gids,
                                    nbrs_use.shape[1], drop_prob)
                nbrs_use = jnp.where(dropped, jnp.int32(n), nbrs_use)
            delta = flood_gather(seen_all, nbrs_use, n)
            sender_active = jnp.any(visible, axis=1)
            msgs_local = msgs_local + jnp.sum(
                jnp.where(sender_active, deg_l, 0)).astype(jnp.float32)

        delta = delta & alive_l[:, None]
        msgs_new = msgs + jax.lax.psum(msgs_local, axis_name)
        if ch is not None:
            return (seen_l | delta, msgs_new,
                    jax.lax.psum(lost, axis_name))
        return seen_l | delta, msgs_new

    sh = P(axis_name)          # rows sharded
    sh2 = P(axis_name, None)   # rows sharded, rumor dim replicated
    rep = P()
    in_specs = [sh2, rep, rep, rep]
    tables = ()
    if have_table:
        in_specs += [sh2, sh]
        tables = (nbrs_pad, deg_pad)
    if ch is not None:
        # schedule operands replicated over the mesh (tiny tables; the
        # per-shard slice happens via gids inside local_round)
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))

    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    mapped = shard_map(local_round, mesh=mesh,
                           in_specs=tuple(in_specs),
                           out_specs=out_specs)

    def step_tabled(state: SimState, *tbl):
        out = mapped(state.seen, state.round, state.base_key,
                     state.msgs, *tbl)
        seen, msgs = out[0], out[1]
        new = SimState(seen=seen, round=state.round + 1,
                       base_key=state.base_key, msgs=msgs)
        # churn path returns (state, lost) — the models/si.py contract
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_state(run: RunConfig, proto: ProtocolConfig, topo: Topology,
                       mesh: Mesh, axis_name: str = "nodes") -> SimState:
    """Initial state with ``seen`` padded to the mesh and placed sharded."""
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_state(run, proto, topo.n)
    seen = _pad_rows(st.seen, n_pad, False)
    seen = jax.device_put(seen, NamedSharding(mesh, P(axis_name, None)))
    return SimState(seen=seen, round=st.round, base_key=st.base_key,
                    msgs=st.msgs)


def _dense_round_bytes(proto: ProtocolConfig, n_pad: int, nl: int):
    """``round_ -> f32`` analytic per-device ICI egress of one dense
    round (ops/round_metrics ``bytes`` semantics — the SparseMeta
    per-device convention): the psum_scatter contribution table is
    ``4*n_pad*R`` int32 bytes, the all_gather egress ``nl*R`` bool
    bytes, the msgs psum 4; anti-entropy's reverse psum_scatter moves
    only on exchange rounds, which the returned closure gates in-trace
    on ``round_`` exactly as the kernel's lax.cond does."""
    r = proto.rumors
    mode = proto.mode
    base = 4.0
    if mode in (C.PUSH, C.PUSH_PULL):
        base += 4.0 * n_pad * r
    if mode in (C.PULL, C.PUSH_PULL, C.ANTI_ENTROPY, C.FLOOD):
        base += 1.0 * nl * r

    def per_round(round_):
        from gossip_tpu.ops import round_metrics as RM
        b = jnp.float32(base)
        if mode == C.ANTI_ENTROPY:
            b = b + RM.gate_on_exchange_rounds(4.0 * n_pad * r,
                                               proto.period, round_)
        return b

    return per_round


def _dense_recorder(proto: ProtocolConfig, n_pad: int, n_shards: int):
    """``(m, prev_count, round0, msgs0, s_after, alive) -> (m, count)``
    — the in-loop metrics row for the dense bool-digest drivers
    (ops/round_metrics counter semantics; a pure readout, so
    trajectories are bitwise what they were without it).  The previous
    round's entry count rides the carry as ONE scalar instead of
    re-reading the pre-step table after the step — keeping the old
    digest alive across the round body would force XLA to double-buffer
    (or copy) the state every round, the exact liveness pathology the
    fused engine's donation contract documents."""
    from gossip_tpu.ops import round_metrics as RM
    bytes_of = _dense_round_bytes(proto, n_pad, n_pad // n_shards)
    offered_per_msg = proto.rumors * RM.payload_factor(proto.mode)

    def rec(m, prev_count, round0, msgs0, s1, alive_pad, nem=None):
        count = RM.count_bool(s1.seen, alive_pad)
        newly = count - prev_count
        msgs = s1.msgs - msgs0
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        return RM.record(
            m, newly=newly, msgs=msgs,
            dup=RM.dup_estimate(offered_per_msg * msgs, newly),
            bytes=bytes_of(round0),
            front=RM.front_bool(s1.seen, alive_pad, n_shards), **kw), count

    return rec


def _churn_observables(fault, n: int, n_pad: int, origin: int):
    """``(round0, lost, sched) -> (alive, cut_pairs, dropped)`` for the
    recorders, or None without a churn schedule — the nemesis
    observable row (ops/nemesis.observables + the kernel's exact lost
    count), shared by every sharded driver family.  ``sched`` is the
    TRACED schedule operand the driver peeled off its table tail
    (``NE.split_tables`` / ``NE.sched_of_tables``) — rebuilding it here
    would bake the content back into the loop."""
    from gossip_tpu.ops import nemesis as NE
    if NE.get(fault) is None:
        return None

    def obs(round0, lost, sched):
        base_pad = _pad_rows(NE.base_alive_or_ones(fault, n, origin),
                             n_pad, False)
        alive_now = NE.alive_rows(sched, base_pad, round0)
        a, pairs = NE.observables(sched, alive_now, round0)
        return a, pairs, lost

    return obs


@functools.lru_cache(maxsize=32)
def _cached_dense_loop(kind: str, proto: ProtocolConfig, n: int,
                       have_table: bool, mesh: Mesh,
                       fault_static: FaultConfig, origin: int,
                       axis_name: str, max_rounds: int, target: float,
                       metrics_on: bool):
    """The dense sharded drivers' compiled CHURN loop (``kind``:
    ``curve`` = lax.scan, ``until`` = lax.while_loop), memoized by
    EXACTLY the statics its trace bakes — which, since the schedule
    tables are runtime operands, excludes the schedule CONTENT: K
    nemesis scenarios over one config re-enter ONE compiled loop
    (compile-count-pinned in tests/test_nemesis.py; the sweep memo
    discipline of sweep._cached_pod_sweep_scan).

    Everything scenario-shaped flows through the returned callable as
    ARGUMENTS: ``(state, alive_pad, *tables)`` where ``alive_pad`` is
    the scenario's EVENTUAL alive denominator (ops/nemesis
    .eventual_alive_pad — a function of which churn deaths are
    permanent, i.e. content) and ``tables`` is the factory tail
    (topology pads + schedule operands).  The step itself is built
    against a shape-placeholder topology and a representative one-event
    schedule: the trace reads only ``n``/implicit-vs-table from the
    topology and only SHAPES from the schedule, both part of this key
    (jit's own cache handles canonical-bucket/table-width retraces
    within one entry).  ``fault_static`` must carry ``churn=None`` —
    its static death draw IS baked, which is why it is in the key."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    rep_fault, topo_ph = NE.placeholder_trace_inputs(fault_static, n,
                                                     have_table)
    step, _ = make_sharded_si_round(proto, topo_ph, mesh, rep_fault,
                                    origin, axis_name, tabled=True)
    n_pad = pad_to_mesh(n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    rec = (_dense_recorder(proto, n_pad, n_shards) if metrics_on
           else None)
    obs = (_churn_observables(rep_fault, n, n_pad, origin)
           if metrics_on else None)
    label = ("simulate_curve_sharded" if kind == "curve"
             else "simulate_until_sharded")

    def advance(carry, alive_pad, tbl):
        s0, m, cnt = carry
        round0, msgs0 = s0.round, s0.msgs
        s, lost = step(s0, *tbl)
        if m is not None:
            m, cnt = rec(m, cnt, round0, msgs0, s, alive_pad,
                         nem=obs(round0, lost, NE.sched_of_tables(tbl)))
        return s, m, cnt

    if kind == "curve":
        def scan(state, alive_pad, *tbl):
            m0 = (RM.init(max_rounds, n_shards, label, nemesis=True)
                  if rec else None)
            c0 = RM.count_bool(state.seen, alive_pad) if rec else None

            def body(carry, _):
                s, m, cnt = advance(carry, alive_pad, tbl)
                return (s, m, cnt), (coverage(s.seen, alive_pad),
                                     s.msgs)
            return jax.lax.scan(body, (state, m0, c0), None,
                                length=max_rounds)
        return jax.jit(scan)

    def loop(state, alive_pad, *tbl):
        m0 = (RM.init(max_rounds, n_shards, label, nemesis=True)
              if rec else None)
        c0 = RM.count_bool(state.seen, alive_pad) if rec else None

        def cond(carry):
            s, _, _ = carry
            return ((coverage(s.seen, alive_pad) < jnp.float32(target))
                    & (s.round < max_rounds))

        def body(carry):
            return advance(carry, alive_pad, tbl)
        return jax.lax.while_loop(cond, body, (state, m0, c0))
    return jax.jit(loop)


def _dense_step_tables(topo: Topology, fault, n_pad: int):
    """The dense step's table-argument tail WITHOUT building the step:
    topology pads + schedule operands, in exactly
    make_sharded_si_round's layout (pinned bitwise by the golden
    churn fingerprints) — so the K warm re-entries the memoized loop
    exists for pay only the per-scenario schedule build, not a full
    factory (shard_map plumbing + table re-pad) per call."""
    from gossip_tpu.ops import nemesis as NE
    n = topo.n
    tables = (() if topo.implicit
              else (_pad_rows(topo.nbrs, n_pad, n),
                    _pad_rows(topo.deg, n_pad, 0)))
    return tables + NE.sched_args(NE.build(fault, n, n_pad))


def _dense_churn_call(kind, proto, topo, run, mesh, fault, axis_name):
    """(loop, operands) for the memoized churn path: the shape-keyed
    compiled loop plus this scenario's runtime operands — initial
    state, eventual-alive denominator, topology pads + schedule
    tables (:func:`_dense_step_tables`)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    tables = _dense_step_tables(topo, fault, n_pad)
    # the memo key strips drop_prob too: on the churn path the per-
    # round probability always comes from the drop_tbl OPERAND (the
    # base rate is content), so scenarios differing only in drop_prob
    # must share the one compiled loop
    fn = _cached_dense_loop(
        kind, proto, topo.n, not topo.implicit, mesh,
        dataclasses.replace(fault, churn=None, drop_prob=0.0),
        run.origin, axis_name,
        run.max_rounds, run.target_coverage, RM.wanted())
    init = init_sharded_state(run, proto, topo, mesh, axis_name)
    alive_op = NE.eventual_alive_pad(fault, topo.n, n_pad, run.origin)
    return fn, (init, alive_op) + tuple(tables)


def simulate_curve_sharded(proto: ProtocolConfig, topo: Topology,
                           run: RunConfig, mesh: Mesh,
                           fault: Optional[FaultConfig] = None,
                           axis_name: str = "nodes", timing=None):
    """``lax.scan`` over rounds recording (coverage, msgs) per round, state
    resident sharded.  Sharded twin of runtime/simulator.simulate_curve.
    Returns (coverage[T], msgs[T], final_state) as host arrays/state.
    ``timing``: optional dict filled with the compile/steady AOT split
    (utils/trace.maybe_aot_timed — VERDICT r4 task 5: sharded rows must
    decompose like single-device ones).  With an active run ledger the
    scan carries a round-metrics buffer stack, flushed once by the
    chokepoint (ops/round_metrics)."""
    import numpy as np

    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.ops import nemesis as NE
    if NE.get(fault) is not None:
        # churn path: the shape-keyed memoized loop — schedule content
        # and the eventual-alive denominator ride as operands, so K
        # scenarios compile once (_cached_dense_loop)
        fn, operands = _dense_churn_call("curve", proto, topo, run,
                                         mesh, fault, axis_name)
        (final, _, _), (covs, msgs) = maybe_aot_timed(fn, timing,
                                                      *operands, label="dense")
        return np.asarray(covs), np.asarray(msgs), final
    step, tables = make_sharded_si_round(proto, topo, mesh, fault,
                                         run.origin, axis_name, tabled=True)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    init = init_sharded_state(run, proto, topo, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    rec = _dense_recorder(proto, n_pad, n_shards) if RM.wanted() else None

    @jax.jit
    def scan(state, *tbl):
        alive_pad = sharded_alive(fault, topo.n, n_pad, run.origin)
        m0 = (RM.init(run.max_rounds, n_shards, "simulate_curve_sharded")
              if rec else None)
        c0 = RM.count_bool(state.seen, alive_pad) if rec else None
        def body(carry, _):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            s = step(s0, *tbl)
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_pad)
            return (s, m, cnt), (coverage(s.seen, alive_pad), s.msgs)
        return jax.lax.scan(body, (state, m0, c0), None,
                            length=run.max_rounds)

    (final, _, _), (covs, msgs) = maybe_aot_timed(scan, timing, init,
                                                  *tables, label="dense")
    return np.asarray(covs), np.asarray(msgs), final


def simulate_until_sharded(proto: ProtocolConfig, topo: Topology,
                           run: RunConfig, mesh: Mesh,
                           fault: Optional[FaultConfig] = None,
                           axis_name: str = "nodes", timing=None):
    """``lax.while_loop`` to target coverage, whole loop one XLA program, state
    resident sharded across the mesh.  Returns (rounds, coverage, msgs, state).
    ``timing``: optional compile/steady AOT-split dict (see
    simulate_curve_sharded).  With an active run ledger the loop carries
    a round-metrics buffer stack, flushed once by the chokepoint
    (ops/round_metrics)."""
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.ops import nemesis as NE
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    if NE.get(fault) is not None:
        # churn path: the shape-keyed memoized loop (curve-driver twin)
        fn, operands = _dense_churn_call("until", proto, topo, run,
                                         mesh, fault, axis_name)
        final, _, _ = maybe_aot_timed(fn, timing, *operands, label="dense")
        alive_pad = NE.eventual_alive_pad(fault, topo.n, n_pad,
                                          run.origin)
        return (int(final.round),
                float(coverage(final.seen, alive_pad)),
                float(final.msgs), final)
    step, tables = make_sharded_si_round(proto, topo, mesh, fault,
                                         run.origin, axis_name, tabled=True)
    alive_pad = sharded_alive(fault, topo.n, n_pad, run.origin)
    init = init_sharded_state(run, proto, topo, mesh, axis_name)
    target = jnp.float32(run.target_coverage)
    n_shards = mesh.shape[axis_name]
    rec = _dense_recorder(proto, n_pad, n_shards) if RM.wanted() else None

    @jax.jit
    def loop(state, *tbl):
        alive_t = sharded_alive(fault, topo.n, n_pad, run.origin)
        m0 = (RM.init(run.max_rounds, n_shards, "simulate_until_sharded")
              if rec else None)
        c0 = RM.count_bool(state.seen, alive_t) if rec else None
        def cond(carry):
            s, _, _ = carry
            return ((coverage(s.seen, alive_t) < target)
                    & (s.round < run.max_rounds))
        def body(carry):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            s = step(s0, *tbl)
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_t)
            return s, m, cnt
        return jax.lax.while_loop(cond, body, (state, m0, c0))

    final, _, _ = maybe_aot_timed(loop, timing, init, *tables, label="dense")
    return (int(final.round), float(coverage(final.seen, alive_pad)),
            float(final.msgs), final)
