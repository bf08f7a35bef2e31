"""Node-sharded replicated-payload pull rounds: the CRDT, log and
LWW-register payloads on the node-mesh exchange fabric.

models/payload.make_payload_round over the node mesh — structurally
parallel/sharded_packed.make_sharded_packed_round with the payload
row's join in place of the word OR, and the injection program applied
locally per shard.  The only collective is the all_gather of the
masked state table (``N x S`` payload rows; the packed set payload
rides the SAME 32-elements-per-word layout as the rumor planes:
ops/bitpack) plus the msgs/lost psums.  Bitwise parity with the
single-device round is pinned in tests/test_payload.py: every random
draw is keyed by (base_key, round, *global* node id), so mesh shape
never changes the trajectory.

Nemesis schedules, injection programs and liar programs are runtime
operands on the step's ``tables`` tail, so the compiled loops carry
shapes only and one executable serves a whole scenario family.  The
loops are models/payload.drive; with an active run ledger they carry
a RoundMetrics stack with the row's convergence column (plus
``byz_conv`` under a liar program), flushed once by the chokepoint.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.payload import (PayloadState, check_round, drive,
                                       init_payload_state, pull, row_for)
from gossip_tpu.models.state import bind_tables
from gossip_tpu.ops import nemesis as NE
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.parallel.sharded import (_churn_observables, _pad_rows,
                                         pad_to_mesh, shard_liveness)
from gossip_tpu.topology.generators import Topology


def make_sharded_payload_round(
        cfg, proto: ProtocolConfig, topo: Topology, mesh: Mesh,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False,
        defend: bool = False):
    """``tabled=True`` returns ``(step, tables)`` with padded topology
    + injection (+ schedule) (+ byzantine program) arrays as step
    ARGUMENTS (no O(N) jit closure constants — models/swim.py doc).
    ``defend=True`` switches the exchange to the row's defended
    admission (models/payload.check_round)."""
    row = row_for(cfg)
    check_round(row, cfg, proto, fault, True, defend)
    n, k = topo.n, proto.fanout
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    zero = jnp.zeros((), row.dtype(cfg))

    def local_round(val_l, round_, base_key, msgs, *table):
        table, byzt = NE.split_byz(bz, table)
        table, sched = NE.split_tables(ch, table)
        table, inj = row.split_inject(cfg, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        alive_l, dp, cut = shard_liveness(fault, sched, n, n_pad, origin,
                                          round_, axis_name)
        lost = jnp.float32(0.0)
        # local injections first (the single-device order); padding
        # rows (gids >= n) own no injection, so inject_rows is zero
        # there by construction
        val_l = row.apply_inject(cfg, val_l, row.inject_rows(
            cfg, inj, gids, round_, n, origin, fault))
        visible = jnp.where(alive_l[:, None], val_l, zero)
        rows_all = jax.lax.all_gather(visible, axis_name, tiled=True)
        nbrs_l, deg_l = table if table else (None, None)

        qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
        partners0 = sample_peers(qkey, gids, topo, k, proto.exclude_self,
                                 local_nbrs=nbrs_l, local_deg=deg_l)
        partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, gids,
                              partners0, dp, n, force=ch is not None)
        if ch is not None:
            partners = NE.partition_targets(cut, gids, partners, n)
        pulled = pull(row, cfg, rows_all, partners, n, byzt, round_,
                      gids, origin, fault, defend)
        partners = jnp.where(alive_l[:, None], partners, n)
        n_req = jnp.sum(partners < n).astype(jnp.float32)
        if ch is not None:
            lost = lost + NE.lost_count(partners0, partners, alive_l, n)
        pulled = jnp.where(alive_l[:, None], pulled, zero)
        out_val = row.merge(cfg, val_l, pulled)
        msgs_new = msgs + jax.lax.psum(2.0 * n_req, axis_name)
        if ch is not None:
            return out_val, msgs_new, jax.lax.psum(lost, axis_name)
        return out_val, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    in_specs = [sh2, rep, rep, rep]
    tables = ()
    if not topo.implicit:
        in_specs += [sh2, P(axis_name)]
        tables = (_pad_rows(topo.nbrs, n_pad, n),
                  _pad_rows(topo.deg, n_pad, 0))
    # injection operands replicated (tiny padded lists; the per-shard
    # ownership slice happens via gids inside local_round), then the
    # schedule, then the liar program outermost
    inj_ops = row.inject_args(cfg, n)
    in_specs += [rep] * len(inj_ops)
    tables = tables + inj_ops
    if ch is not None:
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))
    if bz is not None:
        in_specs += [rep] * NE.N_BYZ_OPERANDS
        tables = tables + NE.byz_args(NE.build_byz(fault, n, n_pad))

    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    mapped = shard_map(local_round, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs)

    def step_tabled(state: PayloadState, *tbl):
        out = mapped(state.val, state.round, state.base_key,
                     state.msgs, *tbl)
        new = PayloadState(val=out[0], round=state.round + 1,
                           base_key=state.base_key, msgs=out[1])
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_payload_state(run: RunConfig, cfg, topo: Topology,
                               mesh: Mesh,
                               axis_name: str = "nodes") -> PayloadState:
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_payload_state(run, cfg, topo.n)
    val = _pad_rows(st.val, n_pad, 0)
    val = jax.device_put(val, NamedSharding(mesh, P(axis_name, None)))
    return st._replace(val=val)


def _metrics(row, cfg, fault, n: int, n_pad: int, n_shards: int,
             origin: int, max_rounds: int, driver: str):
    """The ``metrics`` hook of models/payload.drive: when a run ledger
    wants one, a RoundMetrics stack named ``driver`` and its in-loop
    row (ops/round_metrics — the parallel/sharded_packed
    ._packed_recorder twin).  ``newly`` is the per-round delta of the
    row's payload mass (monotone under the join, so the delta is
    exact); the row's convergence column is the converged fraction on
    the eventual-alive set; per-device egress is the state all_gather:
    ``nl * S * 4`` bytes plus the msgs psum.  Under a liar program the
    ``byz_conv`` column is honest-node convergence on honest-owned
    components; its honest masks are numpy-built from the static fault
    config (constants in the trace, like the liveness predicates), the
    register key mask from the TRACED write operands."""
    from gossip_tpu.ops import crdt as CR
    from gossip_tpu.ops import round_metrics as RM
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    obs = _churn_observables(fault, n, n_pad, origin)
    s = row.width(cfg, n)
    base = 4.0 + 4.0 * (n_pad // n_shards) * s
    # pull accounting: request + full-state response per exchange; the
    # response carries the payload (the 0.5 pull payload factor)
    offered_per_msg = s * RM.payload_factor(C.PULL)

    def setup(state, tbl, truth, eventual, inj):
        if not RM.wanted():
            return None, None, None
        if bz is not None:
            honest = NE.honest_mask(fault, n)
            mask = row.honest_mask(cfg, inj, fault, n, origin, honest)
            honest_pad = eventual & _pad_rows(honest, n_pad, False)
        sched = (NE.sched_of_tables(NE.split_byz(bz, tbl)[0])
                 if obs else None)

        def record(m, prev_count, s0, s1, lost):
            count = row.payload_count(cfg, s1.val, eventual)
            newly = count - prev_count
            msgs = s1.msgs - s0.msgs
            kw = {}
            if obs:
                alive, cut_pairs, dropped = obs(s0.round, lost, sched)
                kw = dict(alive=alive, cut_pairs=cut_pairs,
                          dropped=dropped)
            covered = jnp.any(s1.val != 0, axis=1) & eventual
            per = jnp.sum(covered.reshape(n_shards, -1), axis=1,
                          dtype=jnp.float32)
            tot = jnp.sum(eventual.reshape(n_shards, -1), axis=1,
                          dtype=jnp.float32)
            if bz is not None:
                kw["byz_conv"] = row.byz_conv_frac(cfg, s1.val, truth,
                                                   honest_pad, mask)
            kw[row.column] = CR.value_conv_frac(s1.val, truth, eventual)
            return RM.record(
                m, newly=newly, msgs=msgs,
                dup=RM.dup_estimate(offered_per_msg * msgs, newly),
                bytes=jnp.float32(base),
                front=per / jnp.maximum(tot, 1.0), **kw), count

        m0 = RM.init(max_rounds, n_shards, driver,
                     nemesis=ch is not None, byz=bz is not None,
                     **{row.label: True})
        return m0, row.payload_count(cfg, state.val, eventual), record

    return setup


def _sharded(kind: str, cfg, proto: ProtocolConfig, topo: Topology,
             run: RunConfig, mesh: Mesh, fault, axis_name: str, timing,
             defend: bool):
    row = row_for(cfg)
    row.check_reachable(cfg, run)
    step, tables = make_sharded_payload_round(cfg, proto, topo, mesh,
                                              fault, run.origin,
                                              axis_name, tabled=True,
                                              defend=defend)
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    metrics = _metrics(row, cfg, fault, n, n_pad, mesh.shape[axis_name],
                       run.origin, run.max_rounds,
                       f"simulate_{kind}_{row.label}_sharded")
    return drive(kind, cfg, run, fault, n, step, tables,
                 init_sharded_payload_state(run, cfg, topo, mesh,
                                            axis_name),
                 row.label, timing, metrics)


def simulate_until_payload_sharded(cfg, proto: ProtocolConfig,
                                   topo: Topology, run: RunConfig,
                                   mesh: Mesh,
                                   fault: Optional[FaultConfig] = None,
                                   axis_name: str = "nodes", timing=None,
                                   defend: bool = False):
    """Sharded until driver: ``(rounds, conv, msgs, final_state,
    truth_summary)`` (models/payload.drive)."""
    return _sharded("until", cfg, proto, topo, run, mesh, fault,
                    axis_name, timing, defend)


def simulate_curve_payload_sharded(cfg, proto: ProtocolConfig,
                                   topo: Topology, run: RunConfig,
                                   mesh: Mesh,
                                   fault: Optional[FaultConfig] = None,
                                   axis_name: str = "nodes", timing=None,
                                   defend: bool = False):
    """Sharded curve driver: ``(conv f64[T], msgs f32[T], final_state,
    truth_summary)`` (models/payload.drive)."""
    return _sharded("curve", cfg, proto, topo, run, mesh, fault,
                    axis_name, timing, defend)
