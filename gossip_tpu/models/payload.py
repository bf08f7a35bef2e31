"""Replicated-payload gossip rounds: the exchange fabric with a
mergeable payload in place of the infected bit.

One frame serves the CRDT (ops/crdt), replicated-log (ops/logs) and
LWW-register (ops/registers) payloads: the gossip mechanics — peer
sampling streams, drop coins, partition cuts, churn liveness — are the
EXISTING fabric, untouched (the step below is models/si_packed
.make_packed_round with the payload's join in place of the bool OR),
and everything payload-specific comes from the config's
:class:`~gossip_tpu.ops.payload.PayloadRow`.  The node-mesh frame is
parallel/sharded_payload.py; the until loop and the curve scan below
drive both.  Pull only, by design: state-based dissemination IS the
pull/digest exchange, and the push half would need a scatter-max /
scatter-OR collective XLA does not have (the models/si_packed
precedent).

Semantics under a nemesis schedule (docs/WORKLOADS.md):

  * a churn-down node neither serves pulls, requests, nor receives —
    but its state PERSISTS across downtime (the durable-store
    convention of the rumor kernels' ``seen``), so a recovered node
    re-disseminates everything it ever merged;
  * an injection (add, send/commit, write) fires iff its owner is
    alive at the scripted round and eventually alive (the acked-writes
    semantics — ops/crdt module doc), which makes exact convergence to
    the row's ground truth on the eventual-alive set a guaranteed
    invariant under any fault program;
  * convergence is judged INTEGER-exact: the drivers move a
    converged-node COUNT off device and divide by the eventual-alive
    total once on the host (the bitwise-curve convention).

Schedules, injections and liar programs ride the step's ``tables``
tuple as runtime operands (ops/nemesis + the row's inject_args), so
one compiled loop serves a whole scenario family.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gossip_tpu.config import (CrdtConfig, FaultConfig, LogConfig,
                               ProtocolConfig, RunConfig, TxnConfig)
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.state import alive_mask, bind_tables
from gossip_tpu.ops import crdt as CR
from gossip_tpu.ops import logs as LG
from gossip_tpu.ops import nemesis as NE
from gossip_tpu.ops import registers as RG
from gossip_tpu.ops.payload import PayloadRow
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.topology.generators import Topology

_ROWS = {CrdtConfig: CR.ROW, LogConfig: LG.ROW, TxnConfig: RG.ROW}


def row_for(cfg) -> PayloadRow:
    """The payload row of a CrdtConfig, LogConfig or TxnConfig."""
    return _ROWS[type(cfg)]


class PayloadState(NamedTuple):
    """Carried through ``lax.scan`` / ``lax.while_loop`` rounds — the
    payload twin of models/state.SimState (NamedTuple == registered
    pytree).  ``val`` is the ``[N, S]`` payload row (the row's width
    and dtype)."""

    val: jax.Array
    round: jax.Array
    base_key: jax.Array
    msgs: jax.Array


def init_payload_state(run: RunConfig, cfg, n: int) -> PayloadState:
    """All-zero state: injections apply IN the round loop at their
    scripted rounds (a round-0 write lands in the first step, before
    its exchange), so resume-from-checkpoint and scripted programs
    index the same absolute clock as the nemesis schedule."""
    row = row_for(cfg)
    return PayloadState(
        val=jnp.zeros((n, row.width(cfg, n)), row.dtype(cfg)),
        round=jnp.int32(0),
        base_key=jax.random.key(run.seed),
        msgs=jnp.float32(0.0),
    )


def check_round(row: PayloadRow, cfg, proto: ProtocolConfig, fault,
                sharded: bool, defend: bool) -> None:
    """Every refusal a payload frame raises before it builds a step,
    in one order for both placements.  ``defend=True`` switches the
    exchange to the row's defended admission; ``defend=False`` under a
    liar program is the undefended control arm."""
    row.check_mode(proto)
    row.check_cfg(cfg, sharded)
    # capability row: the pull exchange honors the FULL schedule
    # feature set — events, partition windows, drop ramps — plus the
    # liar program where the row has a defended merge
    # (docs/ROBUSTNESS.md catalog)
    NE.check_supported(fault, engine=row.engine, byz=row.byz)
    CR.check_byz_defendable(cfg, fault, proto.fanout, defend)


def pull(row: PayloadRow, cfg, rows_all, partners, n: int, byzt,
         round_, gids, origin: int, fault, defend: bool):
    """The merge of each requester's sampled partners' rows, through
    the liar program when one is scripted."""
    if byzt is None:
        return row.pull_merge(cfg, rows_all, partners, n)
    return row.pull_merge_byz(cfg, rows_all, partners, n, byzt, round_,
                              gids, origin, fault, defend)


def make_payload_round(cfg, proto: ProtocolConfig, topo: Topology,
                       fault: Optional[FaultConfig] = None,
                       origin: int = 0, tabled: bool = False,
                       defend: bool = False):
    """Single-device payload round step; the node-mesh frame
    (parallel/sharded_payload.py) must stay bitwise identical (pinned
    in tests/test_payload.py).  Returns ``step: PayloadState ->
    PayloadState`` (or ``(state, lost)`` on the churn path — the
    models/si.py contract); ``tabled=True`` returns ``(step, tables)``
    with topology + injection (+ schedule) (+ byzantine program)
    arrays as step ARGUMENTS."""
    row = row_for(cfg)
    check_round(row, cfg, proto, fault, False, defend)
    n, k = topo.n, proto.fanout
    drop_prob = 0.0 if fault is None else fault.drop_prob
    ch = NE.get(fault)
    bz = NE.get_byz(fault)
    # topology, injections, then (on the churn path) the schedule, then
    # the liar program OUTERMOST: all runtime operands on the table
    # tail, shapes-only in the compiled loop
    tables = () if topo.implicit else (topo.nbrs, topo.deg)
    tables = tables + row.inject_args(cfg, n)
    if ch is not None:
        tables = tables + NE.sched_args(NE.build(fault, n))
    if bz is not None:
        tables = tables + NE.byz_args(NE.build_byz(fault, n))
    zero = jnp.zeros((), row.dtype(cfg))

    def step_tabled(state: PayloadState, *tbl):
        tbl, byzt = NE.split_byz(bz, tbl)
        tbl, sched = NE.split_tables(ch, tbl)
        tbl, inj = row.split_inject(cfg, tbl)
        nbrs_t, deg_t = tbl if tbl else (None, None)
        ids = jnp.arange(n, dtype=jnp.int32)
        rkey = jax.random.fold_in(state.base_key, state.round)
        if ch is not None:
            alive = NE.alive_rows(sched, NE.base_alive_or_ones(
                fault, n, origin), state.round)
            dp = NE.drop_at(sched, state.round)
            cut = NE.cut_at(sched, state.round)
        else:
            alive = alive_mask(fault, n, origin)  # None on the hot path
            dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        # local injections land BEFORE the exchange (a write gossips in
        # its own round); the apply mask is the shared alive_at
        # predicate, so the trajectory and ground truth cannot drift
        val = row.apply_inject(cfg, state.val, row.inject_rows(
            cfg, inj, ids, state.round, n, origin, fault))
        visible = val if alive is None else jnp.where(
            alive[:, None], val, zero)
        qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
        partners0 = sample_peers(qkey, ids, topo, k, proto.exclude_self,
                                 local_nbrs=nbrs_t, local_deg=deg_t)
        partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, ids,
                              partners0, dp, n, force=ch is not None)
        if ch is not None:
            partners = NE.partition_targets(cut, ids, partners, n)
        pulled = pull(row, cfg, visible, partners, n, byzt, state.round,
                      ids, origin, fault, defend)
        if alive is not None:
            partners = jnp.where(alive[:, None], partners, n)
        n_req = jnp.sum(partners < n).astype(jnp.float32)
        if ch is not None:
            req_active = (jnp.ones((n,), jnp.bool_) if alive is None
                          else alive)
            lost = lost + NE.lost_count(partners0, partners,
                                        req_active, n)
        if alive is not None:
            pulled = jnp.where(alive[:, None], pulled, zero)
        out = PayloadState(val=row.merge(cfg, val, pulled),
                           round=state.round + 1,
                           base_key=state.base_key,
                           msgs=state.msgs + 2.0 * n_req)
        return (out, lost) if ch is not None else out

    return bind_tables(step_tabled, tables, tabled)


def _conv_target_count(run: RunConfig, eventual_total: int) -> int:
    """The integer while_loop target: converged-node count that meets
    ``run.target_coverage`` of the eventual-alive total — computed ONCE
    on the host so the loop cond is an exact integer compare (no f32
    division anywhere near control flow)."""
    return min(eventual_total,
               math.ceil(run.target_coverage * eventual_total - 1e-9))


def _eventual_rows(fault, n: int, origin: int, rows: int):
    """The eventual-alive mask over a state's ``rows`` (padding rows,
    on the mesh, are never alive)."""
    from gossip_tpu.parallel.sharded import _pad_rows
    return _pad_rows(CR.eventual_alive_crdt(fault, n, origin), rows,
                     False)


def _truth_and_alive(row: PayloadRow, cfg, tbl, fault, n: int,
                     origin: int, rows: int):
    """``(truth row, eventual-alive over the state's rows, injection
    operands)`` — truth from the TRACED operands on a step's table tail
    (the byz tail, outermost, and the schedule are peeled first), so
    the compiled loop carries injection shapes, never content; one
    source for both placements, so the loop cond, the metric and the
    readout agree."""
    head, _ = NE.split_byz(NE.get_byz(fault), tbl)
    head, _ = NE.split_tables(NE.get(fault), head)
    _, inj = row.split_inject(cfg, head)
    return (row.ground_truth(cfg, inj, fault, n, origin),
            _eventual_rows(fault, n, origin, rows), inj)


def drive(kind: str, cfg, run: RunConfig, fault, n: int, step, tables,
          init, label: str, timing=None, metrics=None):
    """THE until loop (``kind="until"``) and curve scan
    (``kind="curve"``) of both placements, over a tabled payload step.

    ``until`` runs ``lax.while_loop`` until the converged-node count
    reaches the integer target (``target_coverage`` of the
    eventual-alive set) and returns ``(rounds, conv, msgs,
    final_state, truth_summary)``; ``curve`` runs ``lax.scan`` over
    ``run.max_rounds`` recording the per-round converged count (int32)
    and msgs and returns ``(conv f64[T], msgs f32[T], final_state,
    truth_summary)``.  Either conv is divided once on the host.

    ``metrics(state, tbl, truth, eventual, inj)`` (the mesh frame's
    recorder, traced inside the loop) returns ``(m0, c0, record)`` —
    a RoundMetrics stack, its running payload count and
    ``record(m, cnt, s0, s1, lost) -> (m, cnt)`` — or Nones."""
    from gossip_tpu.utils.trace import maybe_aot_timed
    row = row_for(cfg)
    ch = NE.get(fault)
    denom = max(1, int(np.asarray(
        CR.eventual_alive_crdt(fault, n, run.origin)).sum()))

    def setup(state, tbl):
        truth, eventual, inj = _truth_and_alive(
            row, cfg, tbl, fault, n, run.origin, state.val.shape[0])
        m0, c0, record = (metrics(state, tbl, truth, eventual, inj)
                          if metrics else (None, None, None))

        def advance(carry):
            s0, m, cnt = carry
            if ch is not None:
                s, lo = step(s0, *tbl)
            else:
                s, lo = step(s0, *tbl), None
            if m is not None:
                m, cnt = record(m, cnt, s0, s, lo)
            return s, m, cnt

        def converged(s):
            return CR.converged_count(s.val, truth, eventual)

        return truth, (state, m0, c0), advance, converged

    if kind == "until":
        target = _conv_target_count(run, denom)

        @jax.jit
        def loop(state, *tbl):
            truth, carry, advance, converged = setup(state, tbl)

            def cond(carry):
                s = carry[0]
                return (converged(s) < target) & (s.round < run.max_rounds)

            final, m, _ = jax.lax.while_loop(cond, advance, carry)
            return (final, m), truth

        (final, _), truth = maybe_aot_timed(loop, timing, init, *tables,
                                            label=label)
        eventual = _eventual_rows(fault, n, run.origin,
                                  final.val.shape[0])
        conv = int(CR.converged_count(final.val, truth, eventual)) / denom
        return (int(final.round), conv, float(final.msgs), final,
                row.truth_summary(cfg, truth, n))

    @jax.jit
    def scan(state, *tbl):
        truth, carry, advance, converged = setup(state, tbl)

        def body(carry, _):
            carry = advance(carry)
            return carry, (converged(carry[0]), carry[0].msgs)

        (final, m, _), ys = jax.lax.scan(body, carry, None,
                                         length=run.max_rounds)
        return (final, m), ys, truth

    # truth comes back from the jitted scan — recomputing it here
    # would re-lower the injection operands and run the scatter
    # program un-jitted on the host, per call
    (final, _), (convs, msgs), truth = maybe_aot_timed(
        scan, timing, init, *tables, label=label)
    return (np.asarray(convs, np.int64) / denom, np.asarray(msgs), final,
            row.truth_summary(cfg, truth, n))


def _solo(kind: str, cfg, proto: ProtocolConfig, topo: Topology,
          run: RunConfig, fault, timing, defend: bool):
    row = row_for(cfg)
    row.check_reachable(cfg, run)
    step, tables = make_payload_round(cfg, proto, topo, fault,
                                      run.origin, tabled=True,
                                      defend=defend)
    return drive(kind, cfg, run, fault, topo.n, step, tables,
                 init_payload_state(run, cfg, topo.n),
                 f"{row.label}_solo", timing)


def simulate_until_payload(cfg, proto: ProtocolConfig, topo: Topology,
                           run: RunConfig,
                           fault: Optional[FaultConfig] = None,
                           timing=None, defend: bool = False):
    """One-device until driver: ``(rounds, conv, msgs, final_state,
    truth_summary)`` (:func:`drive`)."""
    return _solo("until", cfg, proto, topo, run, fault, timing, defend)


def simulate_curve_payload(cfg, proto: ProtocolConfig, topo: Topology,
                           run: RunConfig,
                           fault: Optional[FaultConfig] = None,
                           timing=None, defend: bool = False):
    """One-device curve driver: ``(conv f64[T], msgs f32[T],
    final_state, truth_summary)`` (:func:`drive`)."""
    return _solo("curve", cfg, proto, topo, run, fault, timing, defend)
