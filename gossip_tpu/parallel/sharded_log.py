"""Sharded replicated-log pull rounds: ordered per-key offset payloads
on the node-mesh exchange fabric.

Twin of models/log.make_log_round over the node mesh — structurally
parallel/sharded_crdt.make_sharded_crdt_round with the log payload's
max join in place of the counter merge and the send/commit program
applied locally per shard.  The only collective is the all_gather of
the masked state table — ``N x K*(C+1)`` int32 per round — plus the
msgs/lost psums.  Bitwise parity with the single-device round is
pinned in tests/test_logs.py: every random draw is keyed by
(base_key, round, *global* node id), so mesh shape never changes the
trajectory.

Nemesis schedules AND injection programs are runtime operands on the
step's ``tables`` tail (ops/nemesis + ops/logs); convergence is judged
on the eventual-alive set with an integer-exact converged-node count
divided ONCE on the host, and with an active run ledger the drivers
carry a RoundMetrics stack with the ``log_conv`` column.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import (FaultConfig, LogConfig, ProtocolConfig,
                               RunConfig)
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.log import (LogState, _conv_target_count,
                                   check_injections_reachable,
                                   check_log_mode, init_log_state)
from gossip_tpu.models.state import bind_tables
from gossip_tpu.ops import logs as LG
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.parallel.sharded import (_churn_observables, _pad_rows,
                                         pad_to_mesh, sharded_alive)
from gossip_tpu.topology.generators import Topology


def make_sharded_log_round(
        cfg: LogConfig, proto: ProtocolConfig, topo: Topology,
        mesh: Mesh, fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False):
    """``tabled=True`` returns ``(step, tables)`` with padded topology
    + injection (+ schedule) arrays as step ARGUMENTS (no O(N) jit
    closure constants — models/swim.py doc)."""
    check_log_mode(proto)
    n, k = topo.n, proto.fanout
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    drop_prob = 0.0 if fault is None else fault.drop_prob
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)
    # capability row: full schedule feature set on the log fabric
    NE.check_supported(fault, engine="log-pull")

    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
        deg_pad = _pad_rows(topo.deg, n_pad, 0)
    zero = jnp.zeros((), jnp.int32)

    def local_round(val_l, round_, base_key, msgs, *table):
        table, sched = NE.split_tables(ch, table)
        table, inj = LG.split_inject(cfg, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        if ch is not None:
            base_pad = _pad_rows(
                NE.base_alive_or_ones(fault, n, origin), n_pad, False)
            alive_l = NE.alive_rows(sched, base_pad, round_)[gids]
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
        else:
            alive_l = sharded_alive(fault, n, n_pad, origin)[gids]
            dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        # local appends/commits first (models/log.py twin); padding
        # rows (gids >= n) own no send/commit, so inject_rows is zero
        # there by construction
        inj_rows = LG.inject_rows(cfg, inj, gids, round_, n, origin,
                                  fault)
        val_l = LG.merge_max(val_l, inj_rows)
        visible = jnp.where(alive_l[:, None], val_l, zero)
        rows_all = jax.lax.all_gather(visible, axis_name, tiled=True)
        nbrs_l, deg_l = table if have_table else (None, None)

        qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
        partners0 = sample_peers(qkey, gids, topo, k, proto.exclude_self,
                                 local_nbrs=nbrs_l, local_deg=deg_l)
        partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, gids,
                              partners0, dp, n, force=ch is not None)
        if ch is not None:
            partners = NE.partition_targets(cut, gids, partners, n)
        pulled = LG.pull_merge_log(rows_all, partners, n)
        partners = jnp.where(alive_l[:, None], partners, n)
        n_req = jnp.sum(partners < n).astype(jnp.float32)
        if ch is not None:
            lost = lost + NE.lost_count(partners0, partners, alive_l, n)
        pulled = jnp.where(alive_l[:, None], pulled, zero)
        out_val = LG.merge_max(val_l, pulled)
        msgs_new = msgs + jax.lax.psum(2.0 * n_req, axis_name)
        if ch is not None:
            return out_val, msgs_new, jax.lax.psum(lost, axis_name)
        return out_val, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    in_specs = [sh2, rep, rep, rep]
    tables = ()
    if have_table:
        in_specs += [sh2, P(axis_name)]
        tables = (nbrs_pad, deg_pad)
    # injection operands replicated (tiny padded lists; the per-shard
    # ownership slice happens via gids inside local_round)
    inj_ops = LG.inject_args(cfg, n)
    in_specs += [rep] * len(inj_ops)
    tables = tables + inj_ops
    if ch is not None:
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))

    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    mapped = shard_map(local_round, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs)

    def step_tabled(state: LogState, *tbl):
        out = mapped(state.val, state.round, state.base_key,
                     state.msgs, *tbl)
        new = LogState(val=out[0], round=state.round + 1,
                       base_key=state.base_key, msgs=out[1])
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_log_state(run: RunConfig, cfg: LogConfig,
                           topo: Topology, mesh: Mesh,
                           axis_name: str = "nodes") -> LogState:
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_log_state(run, cfg, topo.n)
    val = _pad_rows(st.val, n_pad, 0)
    val = jax.device_put(val, NamedSharding(mesh, P(axis_name, None)))
    return st._replace(val=val)


def _log_recorder(cfg: LogConfig, proto: ProtocolConfig, n: int,
                  n_pad: int, n_shards: int, truth, eventual_pad):
    """In-loop metrics row for the log pull kernels — the
    parallel/sharded_crdt._crdt_recorder twin.  ``newly`` is the
    per-round delta of the merged payload mass (filled slots +
    committed counts — monotone under max, so the delta is exact);
    ``log_conv`` is the converged fraction on the eventual-alive set;
    per-device egress is the state all_gather plus the msgs psum."""
    from gossip_tpu.ops import round_metrics as RM
    s = LG.state_width(cfg)
    nl = n_pad // n_shards
    base = 4.0 + 4.0 * nl * s
    offered_per_msg = s * RM.payload_factor(C.PULL)

    def rec(m, prev_count, round0, msgs0, s1, alive_pad, nem=None):
        count = LG.payload_count(cfg, s1.val, alive_pad)
        newly = count - prev_count
        msgs = s1.msgs - msgs0
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        covered = jnp.any(s1.val != 0, axis=1) & alive_pad
        per = jnp.sum(covered.reshape(n_shards, -1), axis=1,
                      dtype=jnp.float32)
        tot = jnp.sum(alive_pad.reshape(n_shards, -1), axis=1,
                      dtype=jnp.float32)
        return RM.record(
            m, newly=newly, msgs=msgs,
            dup=RM.dup_estimate(offered_per_msg * msgs, newly),
            bytes=jnp.float32(base),
            front=per / jnp.maximum(tot, 1.0),
            log_conv=LG.value_conv_frac(s1.val, truth, eventual_pad),
            **kw), count

    return rec


def _sharded_truth_and_alive(cfg: LogConfig, tbl, ch, fault, n: int,
                             n_pad: int, origin: int):
    """(truth row, eventual-alive over padded rows) — truth from the
    TRACED injection operands on the step's table tail, shared by both
    sharded drivers so the metric and the readout agree."""
    from gossip_tpu.ops import nemesis as NE
    head, _ = NE.split_tables(ch, tbl)
    _, inj = LG.split_inject(cfg, head)
    truth = LG.ground_truth(cfg, inj, fault, n, origin)
    eventual = _pad_rows(LG.eventual_alive_crdt(fault, n, origin),
                         n_pad, False)
    return truth, eventual


def simulate_curve_log_sharded(cfg: LogConfig, proto: ProtocolConfig,
                               topo: Topology, run: RunConfig,
                               mesh: Mesh,
                               fault: Optional[FaultConfig] = None,
                               axis_name: str = "nodes", timing=None):
    """Sharded scan driver: returns ``(log_conv f64[T], msgs f32[T],
    final_state, truth_summary)`` — log_conv from the integer
    converged count divided once on the host (models/log.py
    contract)."""
    import numpy as np

    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    check_injections_reachable(cfg, run)
    step, tables = make_sharded_log_round(cfg, proto, topo, mesh, fault,
                                          run.origin, axis_name,
                                          tabled=True)
    ch = NE.get(fault)
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    init = init_sharded_log_state(run, cfg, topo, mesh, axis_name)
    obs = _churn_observables(fault, n, n_pad, run.origin)

    @jax.jit
    def scan(state, *tbl):
        truth, eventual = _sharded_truth_and_alive(cfg, tbl, ch, fault,
                                                   n, n_pad, run.origin)
        rec = (_log_recorder(cfg, proto, n, n_pad, n_shards, truth,
                             eventual) if RM.wanted() else None)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_curve_log_sharded",
                      nemesis=ch is not None, log=True)
              if rec else None)
        c0 = LG.payload_count(cfg, state.val, eventual) if rec else None

        def body(carry, _):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lo = step(s0, *tbl)
            else:
                s, lo = step(s0, *tbl), None
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, eventual,
                             nem=(obs(round0, lo,
                                      NE.sched_of_tables(tbl))
                                  if obs else None))
            return (s, m, cnt), (
                LG.converged_count(s.val, truth, eventual), s.msgs)

        (final, m, _), ys = jax.lax.scan(body, (state, m0, c0), None,
                                         length=run.max_rounds)
        return (final, m), ys, truth

    # truth comes back from the jitted scan — recomputing it here
    # would re-lower the injection operands un-jitted per call (the
    # sharded_crdt review lesson)
    (final, _), (convs, msgs), truth = maybe_aot_timed(scan, timing,
                                                       init, *tables,
                                                       label="log")
    eventual_np = np.asarray(LG.eventual_alive_crdt(fault, n,
                                                    run.origin))
    denom = max(1, int(eventual_np.sum()))
    return (np.asarray(convs, np.int64) / denom, np.asarray(msgs),
            final, LG.truth_summary(cfg, truth))


def simulate_until_log_sharded(cfg: LogConfig, proto: ProtocolConfig,
                               topo: Topology, run: RunConfig,
                               mesh: Mesh,
                               fault: Optional[FaultConfig] = None,
                               axis_name: str = "nodes", timing=None):
    """Sharded while_loop driver: ``(rounds, log_conv, msgs,
    final_state, truth_summary)`` — the loop cond is the exact integer
    converged-count compare."""
    import numpy as np

    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    check_injections_reachable(cfg, run)
    step, tables = make_sharded_log_round(cfg, proto, topo, mesh, fault,
                                          run.origin, axis_name,
                                          tabled=True)
    ch = NE.get(fault)
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    init = init_sharded_log_state(run, cfg, topo, mesh, axis_name)
    obs = _churn_observables(fault, n, n_pad, run.origin)
    eventual_np = np.asarray(LG.eventual_alive_crdt(fault, n,
                                                    run.origin))
    denom = max(1, int(eventual_np.sum()))
    target = _conv_target_count(run, denom)

    @jax.jit
    def loop(state, *tbl):
        truth, eventual = _sharded_truth_and_alive(cfg, tbl, ch, fault,
                                                   n, n_pad, run.origin)
        rec = (_log_recorder(cfg, proto, n, n_pad, n_shards, truth,
                             eventual) if RM.wanted() else None)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_until_log_sharded",
                      nemesis=ch is not None, log=True)
              if rec else None)
        c0 = LG.payload_count(cfg, state.val, eventual) if rec else None

        def cond(carry):
            s, _, _ = carry
            return ((LG.converged_count(s.val, truth, eventual)
                     < target) & (s.round < run.max_rounds))

        def body(carry):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lo = step(s0, *tbl)
            else:
                s, lo = step(s0, *tbl), None
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, eventual,
                             nem=(obs(round0, lo,
                                      NE.sched_of_tables(tbl))
                                  if obs else None))
            return s, m, cnt

        final, m, _ = jax.lax.while_loop(cond, body, (state, m0, c0))
        return (final, m), truth

    (final, _), truth = maybe_aot_timed(loop, timing, init, *tables,
                                        label="log")
    eventual = _pad_rows(LG.eventual_alive_crdt(fault, n, run.origin),
                         n_pad, False)
    conv = int(LG.converged_count(final.val, truth, eventual)) / denom
    return (int(final.round), conv, float(final.msgs), final,
            LG.truth_summary(cfg, truth))
