"""Sharded CRDT pull rounds: the packed exchange fabric with a
commutative-merge payload.

Twin of models/crdt.make_crdt_round over the node mesh — structurally
parallel/sharded_packed.make_sharded_packed_round with the CRDT join
(elementwise max for counter shards, OR for packed set planes) in
place of the word OR, and the injection program applied locally per
shard.  The only collective is the all_gather of the masked state
table — ``N x S`` int32 shards or ``N x 2W`` uint32 set words per
round (the set payload rides the SAME 32-elements-per-word packed
layout as the rumor planes: ops/bitpack) — plus the msgs/lost psums.
Bitwise parity with the single-device round is pinned in
tests/test_crdt.py: every random draw is keyed by (base_key, round,
*global* node id), so mesh shape never changes the trajectory.

Nemesis schedules AND injection programs are runtime operands on the
step's ``tables`` tail (ops/nemesis + ops/crdt), so the compiled loops
carry shapes only and one executable serves a whole scenario family;
value convergence is judged on the eventual-alive set with an
integer-exact converged-node count divided ONCE on the host.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import (CrdtConfig, FaultConfig, ProtocolConfig,
                               RunConfig)
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.crdt import (CrdtState, _conv_target_count,
                                    check_byz_defendable,
                                    check_crdt_mode,
                                    check_injections_reachable,
                                    init_crdt_state, truth_scalar)
from gossip_tpu.models.state import bind_tables
from gossip_tpu.ops import crdt as CR
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.parallel.sharded import (_churn_observables, _pad_rows,
                                         pad_to_mesh, shard_liveness)
from gossip_tpu.topology.generators import Topology


def make_sharded_crdt_round(
        cfg: CrdtConfig, proto: ProtocolConfig, topo: Topology,
        mesh: Mesh, fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False,
        defend: bool = False):
    """``tabled=True`` returns ``(step, tables)`` with padded topology
    + injection (+ schedule) (+ byzantine program) arrays as step
    ARGUMENTS (no O(N) jit closure constants — models/swim.py doc).
    ``defend=True`` switches the exchange to the defended admission
    (ops/crdt byzantine section; models/crdt.py twin)."""
    check_crdt_mode(proto)
    n, k = topo.n, proto.fanout
    if cfg.kind == C.VCLOCK:
        raise ValueError("vclock has no exchange driver (ops/crdt merge "
                         "kernel + tick only)")
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    # capability row: full schedule feature set on the CRDT fabric,
    # plus the byzantine liar program with array-form defenses
    NE.check_supported(fault, engine="crdt-pull", byz=True)
    check_byz_defendable(cfg, fault, k, defend)

    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
        deg_pad = _pad_rows(topo.deg, n_pad, 0)
    counters = cfg.kind in C.CRDT_COUNTER_KINDS
    zero = jnp.zeros((), jnp.int32 if counters else jnp.uint32)

    def local_round(val_l, round_, base_key, msgs, *table):
        table, byzt = NE.split_byz(bz, table)
        table, sched = NE.split_tables(ch, table)
        table, inj = CR.split_inject(cfg, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        alive_fn = CR.alive_at_fn(fault, n, origin)
        eventual = CR.eventual_alive_crdt(fault, n, origin)
        alive_l, dp, cut = shard_liveness(fault, sched, n, n_pad, origin,
                                          round_, axis_name)
        lost = jnp.float32(0.0)
        # local injections first (models/crdt.py twin); padding rows
        # (gids >= n) own no column/element, so inject_rows is zero
        # there by construction
        inj_rows = CR.inject_rows(cfg, inj, gids, round_, n, origin,
                                  alive_fn, eventual)
        val_l = val_l + inj_rows if counters else val_l | inj_rows
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
        if bz is not None:
            pulled = CR.pull_merge_crdt_byz(
                cfg, rows_all, partners, n, byz=byzt, round_=round_,
                gids=gids, n=n, origin=origin, alive_fn=alive_fn,
                defend=defend)
        else:
            pulled = CR.pull_merge_crdt(cfg.kind, rows_all, partners, n)
        partners = jnp.where(alive_l[:, None], partners, n)
        n_req = jnp.sum(partners < n).astype(jnp.float32)
        if ch is not None:
            lost = lost + NE.lost_count(partners0, partners, alive_l, n)
        pulled = jnp.where(alive_l[:, None], pulled, zero)
        out_val = CR.merge(cfg.kind, val_l, pulled)
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
    inj_ops = CR.inject_args(cfg, n)
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

    def step_tabled(state: CrdtState, *tbl):
        out = mapped(state.val, state.round, state.base_key,
                     state.msgs, *tbl)
        new = CrdtState(val=out[0], round=state.round + 1,
                        base_key=state.base_key, msgs=out[1])
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_crdt_state(run: RunConfig, cfg: CrdtConfig,
                            topo: Topology, mesh: Mesh,
                            axis_name: str = "nodes") -> CrdtState:
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_crdt_state(run, cfg, topo.n)
    val = _pad_rows(st.val, n_pad, 0)
    val = jax.device_put(val, NamedSharding(mesh, P(axis_name, None)))
    return st._replace(val=val)


def _crdt_recorder(cfg: CrdtConfig, proto: ProtocolConfig, n: int,
                   n_pad: int, n_shards: int, truth, eventual_pad,
                   byz_extra=None):
    """In-loop metrics row for the CRDT pull kernels (ops/round_metrics
    — the parallel/sharded_packed._packed_recorder twin).  ``newly`` is
    the per-round delta of the merged payload mass (counter mass / set
    bits — monotone under merge, so the delta is exact); ``value_conv``
    is the converged fraction on the eventual-alive set; per-device
    egress is the state all_gather: ``nl * S * 4`` bytes plus the msgs
    psum.  Under a liar program ``byz_extra = (component_mask,
    honest_eventual_pad)`` adds the ``byz_conv`` column — honest-node
    convergence on honest-owned components (ops/crdt byzantine
    section)."""
    from gossip_tpu.ops import round_metrics as RM
    s = CR.state_width(cfg, n)
    nl = n_pad // n_shards
    base = 4.0 + 4.0 * nl * s
    # pull accounting: request + full-state response per exchange; the
    # response carries the payload (the 0.5 pull payload factor)
    offered_per_msg = s * RM.payload_factor(C.PULL)

    def rec(m, prev_count, round0, msgs0, s1, alive_pad, nem=None):
        count = CR.payload_count(cfg, s1.val, alive_pad)
        newly = count - prev_count
        msgs = s1.msgs - msgs0
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        covered = jnp.any(s1.val != 0, axis=1) & alive_pad
        per = jnp.sum(covered.reshape(n_shards, -1), axis=1,
                      dtype=jnp.float32)
        tot = jnp.sum(alive_pad.reshape(n_shards, -1), axis=1,
                      dtype=jnp.float32)
        if byz_extra is not None:
            comp_mask, honest_pad = byz_extra
            kw["byz_conv"] = CR.byz_conv_frac(cfg, s1.val, truth,
                                              honest_pad, comp_mask)
        return RM.record(
            m, newly=newly, msgs=msgs,
            dup=RM.dup_estimate(offered_per_msg * msgs, newly),
            bytes=jnp.float32(base),
            front=per / jnp.maximum(tot, 1.0),
            value_conv=CR.value_conv_frac(s1.val, truth, eventual_pad),
            **kw), count

    return rec


def _sharded_truth_and_alive(cfg: CrdtConfig, tbl, ch, fault, n: int,
                             n_pad: int, origin: int, bz=None):
    """(truth row, eventual-alive over padded rows) — truth from the
    TRACED injection operands on the step's table tail (the compiled
    loop carries injection shapes, never content — models/crdt.py
    discipline), shared by both sharded drivers so the metric and the
    readout agree.  The byz tail (outermost) is peeled first."""
    from gossip_tpu.ops import nemesis as NE
    head, _ = NE.split_byz(bz, tbl)
    head, _ = NE.split_tables(ch, head)
    _, inj = CR.split_inject(cfg, head)
    truth = CR.ground_truth(cfg, inj, fault, n, origin)
    eventual = _pad_rows(CR.eventual_alive_crdt(fault, n, origin),
                         n_pad, False)
    return truth, eventual


def _byz_recorder_extra(cfg, fault, bz, n: int, n_pad: int,
                        origin: int, eventual_pad):
    """``(component_mask, honest_eventual_pad)`` for the recorders'
    ``byz_conv`` column, or None without a liar program — the honest
    masks are numpy-built from the static fault config (constants in
    the trace, like the liveness predicates)."""
    if bz is None:
        return None
    from gossip_tpu.ops import nemesis as NE
    honest = NE.honest_mask(fault, n)
    comp_mask = CR.honest_component_mask(cfg, n, origin, honest)
    honest_pad = eventual_pad & _pad_rows(honest, n_pad, False)
    return comp_mask, honest_pad


def simulate_curve_crdt_sharded(cfg: CrdtConfig, proto: ProtocolConfig,
                                topo: Topology, run: RunConfig,
                                mesh: Mesh,
                                fault: Optional[FaultConfig] = None,
                                axis_name: str = "nodes", timing=None,
                                defend: bool = False):
    """Sharded scan driver: returns ``(value_conv f64[T], msgs f32[T],
    final_state, truth_value)`` — value_conv from the integer converged
    count divided once on the host (models/crdt.py contract).  With an
    active run ledger the scan carries a RoundMetrics stack with the
    ``value_conv`` column (plus ``byz_conv`` under a liar program),
    flushed once by the chokepoint."""
    import numpy as np

    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    check_injections_reachable(cfg, run)
    step, tables = make_sharded_crdt_round(cfg, proto, topo, mesh, fault,
                                           run.origin, axis_name,
                                           tabled=True, defend=defend)
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    init = init_sharded_crdt_state(run, cfg, topo, mesh, axis_name)
    obs = _churn_observables(fault, n, n_pad, run.origin)

    @jax.jit
    def scan(state, *tbl):
        truth, eventual = _sharded_truth_and_alive(cfg, tbl, ch, fault,
                                                   n, n_pad, run.origin,
                                                   bz)
        byz_extra = _byz_recorder_extra(cfg, fault, bz, n, n_pad,
                                        run.origin, eventual)
        rec = (_crdt_recorder(cfg, proto, n, n_pad, n_shards, truth,
                              eventual, byz_extra)
               if RM.wanted() else None)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_curve_crdt_sharded",
                      nemesis=ch is not None, crdt=True,
                      byz=bz is not None)
              if rec else None)
        c0 = CR.payload_count(cfg, state.val, eventual) if rec else None

        def body(carry, _):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lo = step(s0, *tbl)
            else:
                s, lo = step(s0, *tbl), None
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, eventual,
                             nem=(obs(round0, lo, NE.sched_of_tables(
                                      NE.split_byz(bz, tbl)[0]))
                                  if obs else None))
            return (s, m, cnt), (
                CR.converged_count(s.val, truth, eventual), s.msgs)

        (final, m, _), ys = jax.lax.scan(body, (state, m0, c0), None,
                                         length=run.max_rounds)
        return (final, m), ys, truth

    # truth comes back from the jitted scan (the until-driver shape) —
    # recomputing it here would re-lower the injection operands and
    # run the scatter program un-jitted on the host, per call
    (final, _), (convs, msgs), truth = maybe_aot_timed(scan, timing,
                                                       init, *tables,
                                                       label="crdt")
    eventual_np = np.asarray(CR.eventual_alive_crdt(fault, n,
                                                    run.origin))
    denom = max(1, int(eventual_np.sum()))
    return (np.asarray(convs, np.int64) / denom, np.asarray(msgs),
            final, truth_scalar(cfg, truth, n))


def simulate_until_crdt_sharded(cfg: CrdtConfig, proto: ProtocolConfig,
                                topo: Topology, run: RunConfig,
                                mesh: Mesh,
                                fault: Optional[FaultConfig] = None,
                                axis_name: str = "nodes", timing=None,
                                defend: bool = False):
    """Sharded while_loop driver: ``(rounds, value_conv, msgs,
    final_state, truth_value)`` — the loop cond is the exact integer
    converged-count compare (models/crdt._conv_target_count)."""
    import numpy as np

    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    check_injections_reachable(cfg, run)
    step, tables = make_sharded_crdt_round(cfg, proto, topo, mesh, fault,
                                           run.origin, axis_name,
                                           tabled=True, defend=defend)
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    n_shards = mesh.shape[axis_name]
    init = init_sharded_crdt_state(run, cfg, topo, mesh, axis_name)
    obs = _churn_observables(fault, n, n_pad, run.origin)
    eventual_np = np.asarray(CR.eventual_alive_crdt(fault, n,
                                                    run.origin))
    denom = max(1, int(eventual_np.sum()))
    target = _conv_target_count(run, denom)

    @jax.jit
    def loop(state, *tbl):
        truth, eventual = _sharded_truth_and_alive(cfg, tbl, ch, fault,
                                                   n, n_pad, run.origin,
                                                   bz)
        byz_extra = _byz_recorder_extra(cfg, fault, bz, n, n_pad,
                                        run.origin, eventual)
        rec = (_crdt_recorder(cfg, proto, n, n_pad, n_shards, truth,
                              eventual, byz_extra)
               if RM.wanted() else None)
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_until_crdt_sharded",
                      nemesis=ch is not None, crdt=True,
                      byz=bz is not None)
              if rec else None)
        c0 = CR.payload_count(cfg, state.val, eventual) if rec else None

        def cond(carry):
            s, _, _ = carry
            return ((CR.converged_count(s.val, truth, eventual)
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
                             nem=(obs(round0, lo, NE.sched_of_tables(
                                      NE.split_byz(bz, tbl)[0]))
                                  if obs else None))
            return s, m, cnt

        final, m, _ = jax.lax.while_loop(cond, body, (state, m0, c0))
        return (final, m), truth

    (final, _), truth = maybe_aot_timed(loop, timing, init, *tables,
                                        label="crdt")
    eventual = _pad_rows(CR.eventual_alive_crdt(fault, n, run.origin),
                         n_pad, False)
    conv = int(CR.converged_count(final.val, truth, eventual)) / denom
    return (int(final.round), conv, float(final.msgs), final,
            truth_scalar(cfg, truth, n))
