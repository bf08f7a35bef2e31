"""Node-dim sharded SIR rumor mongering — the shard_map twin of
models/rumor.py, bitwise-identical to the single-device kernel on any
mesh (same per-node threefry streams keyed by GLOBAL ids, same counter
semantics; tested in tests/test_rumor.py).

Communication per round (dense-exchange family, parallel/sharded.py):
``psum_scatter`` of the push counts (deliveries) and — for the feedback
variant — one ``all_gather`` of the round-start ``seen`` table so each
shard can check whether its push recipients already knew the rumor.
Blind needs NO gather: its counters depend only on local state, so a
blind rumor round moves strictly less ICI than an SI push round at the
same fanout, and the hot set's extinction makes the total traffic
O(N * rumor_k) messages instead of SI's O(N * rounds).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models.rumor import (RUMOR_DROP_TAG, RUMOR_PUSH_TAG,
                                     RumorState, init_rumor_state,
                                     rumor_coverage)
from gossip_tpu.models.state import bind_tables
from gossip_tpu.ops.propagate import push_counts
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.parallel.sharded import (_pad_rows, pad_to_mesh,
                                         sharded_alive)
from gossip_tpu.topology.generators import Topology


def make_sharded_rumor_round(proto: ProtocolConfig, topo: Topology,
                             mesh: Mesh,
                             fault: Optional[FaultConfig] = None,
                             origin: int = 0, axis_name: str = "nodes",
                             tabled: bool = False):
    """Sharded round step; semantics identical to make_rumor_round."""
    if proto.mode != C.RUMOR:
        raise ValueError(f"make_sharded_rumor_round builds mode='rumor' "
                         f"only (got {proto.mode!r})")
    n, k = topo.n, proto.fanout
    kk = proto.rumor_k
    feedback = proto.rumor_variant == "feedback"
    drop_prob = 0.0 if fault is None else fault.drop_prob
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)

    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
        deg_pad = _pad_rows(topo.deg, n_pad, 0)

    def local_round(seen_l, hot_l, cnt_l, round_, base_key, msgs, *table):
        table, sched = NE.split_tables(ch, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        if ch is not None:
            # schedule operands from the table tail (ops/nemesis doc)
            base_pad = _pad_rows(
                NE.base_alive_or_ones(fault, n, origin), n_pad, False)
            alive_l = NE.alive_rows(sched, base_pad, round_)[gids]
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
        else:
            alive_l = sharded_alive(fault, n, n_pad, origin)[gids]
            dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        nbrs_l, deg_l = table if have_table else (None, None)

        payload = hot_l & alive_l[:, None]                     # [nl, R]
        pkey = jax.random.fold_in(rkey, RUMOR_PUSH_TAG)
        targets0 = sample_peers(pkey, gids, topo, k, proto.exclude_self,
                                local_nbrs=nbrs_l, local_deg=deg_l)
        targets = apply_drop(rkey, RUMOR_DROP_TAG, gids, targets0,
                             dp, n, force=ch is not None)      # [nl, k]
        if ch is not None:
            targets = NE.partition_targets(cut, gids, targets, n)
        sender_active = jnp.any(payload, axis=1)
        valid = (targets < n) & sender_active[:, None]

        # Deliveries: scatter counts of the hot payload, reduce-scatter.
        counts = push_counts(n_pad, jnp.where(valid, targets, n_pad),
                             payload)
        counts_l = jax.lax.psum_scatter(counts, axis_name,
                                        scatter_dimension=0, tiled=True)
        delta = (counts_l > 0) & alive_l[:, None]

        # Counters against the ROUND-START global seen (feedback needs the
        # recipients' prior knowledge — one all_gather; blind is local).
        if feedback:
            seen_all = jax.lax.all_gather(seen_l, axis_name, tiled=True)
            safe_t = jnp.where(valid, targets, 0)
            knew = seen_all[safe_t] & valid[:, :, None]        # [nl,k,R]
            hits = jnp.sum(knew, axis=1, dtype=jnp.int32)
        else:
            hits = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None]
        cnt_l = cnt_l + jnp.where(payload, hits, 0)

        new = delta & ~seen_l
        # dead nodes hold no hot bits (extinction-loop liveness; matches
        # the single-device kernel — a dead origin's rumor never spreads)
        hot_l = ((hot_l & (cnt_l < kk)) | new) & alive_l[:, None]
        msgs_new = msgs + jax.lax.psum(
            jnp.sum(valid).astype(jnp.float32), axis_name)
        if ch is not None:
            lost = lost + NE.lost_count(targets0, targets,
                                        sender_active, n)
            return (seen_l | delta, hot_l, cnt_l, msgs_new,
                    jax.lax.psum(lost, axis_name))
        return seen_l | delta, hot_l, cnt_l, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    in_specs = [sh2, sh2, sh2, rep, rep, rep]
    tables = ()
    if have_table:
        in_specs += [sh2, P(axis_name)]
        tables = (nbrs_pad, deg_pad)
    if ch is not None:
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))

    out_specs = ((sh2, sh2, sh2, rep, rep) if ch is not None
                 else (sh2, sh2, sh2, rep))
    mapped = shard_map(local_round, mesh=mesh,
                           in_specs=tuple(in_specs),
                           out_specs=out_specs)

    def step_tabled(state: RumorState, *tbl):
        out = mapped(state.seen, state.hot, state.cnt,
                     state.round, state.base_key, state.msgs, *tbl)
        new = RumorState(seen=out[0], hot=out[1], cnt=out[2],
                         round=state.round + 1,
                         base_key=state.base_key, msgs=out[3])
        # churn path returns (state, lost) — the models/si.py contract
        return (new, out[4]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_rumor_state(run: RunConfig, proto: ProtocolConfig,
                             topo: Topology, mesh: Mesh,
                             axis_name: str = "nodes") -> RumorState:
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_rumor_state(run, proto, topo.n)
    put = lambda x, fill: jax.device_put(               # noqa: E731
        _pad_rows(x, n_pad, fill),
        NamedSharding(mesh, P(axis_name, None)))
    return RumorState(seen=put(st.seen, False), hot=put(st.hot, False),
                      cnt=put(st.cnt, 0), round=st.round,
                      base_key=st.base_key, msgs=st.msgs)


def _rumor_recorder(proto: ProtocolConfig, n_pad: int,
                    n_shards: int):
    """In-loop metrics row for the SIR rumor drivers
    (ops/round_metrics).  The kernel's own hit counters make ``dup``
    EXACT for the feedback variant — ``cnt`` grows by precisely the
    contacts whose recipient already knew — while blind's counter
    counts all contacts, so there the estimator subtracts the round's
    new infections (module-doc upper bound).  The previous round's
    seen/cnt totals ride the carry as two scalars
    (parallel/sharded._dense_recorder liveness rationale)."""
    from gossip_tpu.ops import round_metrics as RM
    feedback = proto.rumor_variant == "feedback"
    r = proto.rumors
    nl = n_pad // n_shards
    # psum_scatter of the int32 counts table every round; feedback adds
    # the round-start seen all_gather (bool egress); plus the msgs psum
    base_bytes = 4.0 * n_pad * r + (1.0 * nl * r if feedback else 0.0) \
        + 4.0

    def rec(m, prev, msgs0, s1, alive, nem=None):
        count = RM.count_bool(s1.seen, alive)
        cntsum = jnp.sum(jnp.where(alive[:, None], s1.cnt, 0),
                         dtype=jnp.float32)
        newly = count - prev[0]
        contacts = cntsum - prev[1]
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        return RM.record(
            m, newly=newly, msgs=s1.msgs - msgs0,
            dup=(contacts if feedback
                 else RM.dup_estimate(contacts, newly)),
            bytes=base_bytes,
            front=RM.front_bool(s1.seen, alive, n_shards), **kw), \
            (count, cntsum)

    def init_prev(state, alive):
        return (RM.count_bool(state.seen, alive),
                jnp.sum(jnp.where(alive[:, None], state.cnt, 0),
                        dtype=jnp.float32))

    return rec, init_prev


def simulate_curve_rumor_sharded(proto: ProtocolConfig, topo: Topology,
                                 run: RunConfig, mesh: Mesh,
                                 fault: Optional[FaultConfig] = None,
                                 axis_name: str = "nodes", timing=None):
    """Fixed-length scan with per-round (coverage, hot_fraction, msgs)
    curves, state resident sharded — the multi-device twin of
    models/rumor.simulate_curve_rumor (same returns; curves weighted by
    the padded alive mask so padding rows deflate nothing).  Closes the
    round-3 carve-out where rumor curve capture was single-device
    only.  ``timing``: optional compile/steady AOT-split dict
    (utils/trace.maybe_aot_timed contract); with an active run ledger
    the scan carries a round-metrics buffer stack (ops/round_metrics)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.parallel.sharded import _churn_observables
    from gossip_tpu.utils.trace import maybe_aot_timed
    step, tables = make_sharded_rumor_round(proto, topo, mesh, fault,
                                            run.origin, axis_name,
                                            tabled=True)
    init = init_sharded_rumor_state(run, proto, topo, mesh, axis_name)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    rec, init_prev = (_rumor_recorder(proto, n_pad, n_shards)
                      if RM.wanted() else (None, None))
    ch = NE.get(fault)
    obs = _churn_observables(fault, topo.n, n_pad, run.origin)

    @jax.jit
    def scan(state, *tbl):
        alive = (NE.eventual_alive_pad(fault, topo.n, n_pad, run.origin)
                 if ch is not None
                 else sharded_alive(fault, topo.n, n_pad, run.origin))
        w = alive.astype(jnp.float32)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_curve_rumor_sharded",
                      nemesis=ch is not None) if rec else None)
        p0 = init_prev(state, alive) if rec else None

        def body(carry, _):
            s0, m, prev = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lost = step(s0, *tbl)
            else:
                s, lost = step(s0, *tbl), None
            if m is not None:
                m, prev = rec(m, prev, msgs0, s, alive,
                              nem=(obs(round0, lost,
                                       NE.sched_of_tables(tbl))
                                   if obs else None))
            hot_any = jnp.any(s.hot, axis=1).astype(jnp.float32)
            hot_frac = jnp.sum(hot_any * w) / jnp.sum(w)
            return ((s, m, prev),
                    (rumor_coverage(s.seen, alive), hot_frac, s.msgs))
        return jax.lax.scan(body, (state, m0, p0), None,
                            length=run.max_rounds)

    (final, _, _), (covs, hots, msgs) = maybe_aot_timed(scan, timing,
                                                        init, *tables,
                                                        label="rumor")
    return covs, hots, msgs, final


def restore_sharded_rumor_state(state: RumorState, mesh: Mesh,
                                axis_name: str = "nodes") -> RumorState:
    """Re-place a host-loaded checkpoint (utils/checkpoint.load_state
    gathers to host) back onto the mesh; rows are already padded (the
    config fingerprint pins the mesh shape)."""
    sharding = NamedSharding(mesh, P(axis_name, None))
    put = lambda x: jax.device_put(jnp.asarray(x), sharding)  # noqa: E731
    return RumorState(seen=put(state.seen), hot=put(state.hot),
                      cnt=put(state.cnt), round=state.round,
                      base_key=state.base_key, msgs=state.msgs)


def simulate_until_rumor_sharded(proto: ProtocolConfig, topo: Topology,
                                 run: RunConfig, mesh: Mesh,
                                 fault: Optional[FaultConfig] = None,
                                 axis_name: str = "nodes", timing=None):
    """Run to extinction or max_rounds, one compiled while_loop, state
    resident sharded.  Same returns as models/rumor.simulate_until_rumor.
    ``timing``: optional compile/steady AOT-split dict; with an active
    run ledger the loop carries a round-metrics buffer stack
    (ops/round_metrics)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.parallel.sharded import _churn_observables
    from gossip_tpu.utils.trace import maybe_aot_timed
    step, tables = make_sharded_rumor_round(proto, topo, mesh, fault,
                                            run.origin, axis_name,
                                            tabled=True)
    init = init_sharded_rumor_state(run, proto, topo, mesh, axis_name)
    n_pad_m = pad_to_mesh(topo.n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    rec, init_prev = (_rumor_recorder(proto, n_pad_m, n_shards)
                      if RM.wanted() else (None, None))
    ch = NE.get(fault)
    obs = _churn_observables(fault, topo.n, n_pad_m, run.origin)

    def alive_of(n_rows):
        if ch is not None:
            return NE.eventual_alive_pad(fault, topo.n, n_rows,
                                         run.origin)
        return sharded_alive(fault, topo.n, n_rows, run.origin)

    @jax.jit
    def loop(state, *tbl):
        alive = alive_of(n_pad_m)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_until_rumor_sharded",
                      nemesis=ch is not None) if rec else None)
        p0 = init_prev(state, alive) if rec else None

        def cond(carry):
            s, _, _ = carry
            return jnp.any(s.hot) & (s.round < run.max_rounds)

        def body(carry):
            s0, m, prev = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lost = step(s0, *tbl)
            else:
                s, lost = step(s0, *tbl), None
            if m is not None:
                m, prev = rec(m, prev, msgs0, s, alive,
                              nem=(obs(round0, lost,
                                       NE.sched_of_tables(tbl))
                                   if obs else None))
            return s, m, prev

        return jax.lax.while_loop(cond, body, (state, m0, p0))

    final, _, _ = maybe_aot_timed(loop, timing, init, *tables, label="rumor")
    # always weight by the padded alive mask: padding rows must not
    # deflate coverage (sharded_alive marks them dead even fault-free)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    alive = alive_of(n_pad)
    cov = float(rumor_coverage(final.seen, alive))
    return (int(final.round), cov, 1.0 - cov, float(final.msgs), final)
