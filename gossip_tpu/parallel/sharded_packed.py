"""Sharded bit-packed pull round: 8x less ICI traffic than bool digests.

Twin of models/si_packed.make_packed_round over the node mesh.  The only
collective is the all_gather of the packed visible table — ``N x W`` uint32
words per round (1.25 MB at N=10M, R=1; 10 MB at R=256) instead of the bool
table's ``N x R`` bytes.  Bitwise-parity-tested against the single-device
packed round (and hence against the unpacked pull round) in
tests/test_packed.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.si_packed import init_packed_state, pull_merge_packed
from gossip_tpu.models.state import SimState, bind_tables
from gossip_tpu.ops.bitpack import coverage_packed, pack, unpack
from gossip_tpu.ops.propagate import push_counts
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.parallel.sharded import (_pad_rows, pad_to_mesh,
                                         sharded_alive)
from gossip_tpu.topology.generators import Topology
from gossip_tpu.utils.trace import (BUILD, COVERAGE, EXCHANGE, READBACK,
                                    SAMPLE, span)


def make_sharded_packed_round(
        proto: ProtocolConfig, topo: Topology, mesh: Mesh,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False):
    """``tabled=True`` returns ``(step, tables)`` with the padded topology
    arrays as step ARGUMENTS (no O(N) jit closure constants —
    models/swim.py doc); the liveness mask is built in-trace."""
    n, k = topo.n, proto.fanout
    mode = proto.mode
    if mode not in (C.PULL, C.ANTI_ENTROPY):
        raise ValueError("packed rounds support pull/antientropy only")
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    drop_prob = 0.0 if fault is None else fault.drop_prob
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)

    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
        deg_pad = _pad_rows(topo.deg, n_pad, 0)

    def local_round(packed_l, round_, base_key, msgs, *table):
        table, sched = NE.split_tables(ch, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        # the round's fault masks decide, like the drop coins, which
        # pulls happen: they ride the sample scope.  Liveness in-trace
        # (replicated compute, no O(N) inline constant)
        with jax.named_scope(SAMPLE):
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
        with jax.named_scope(EXCHANGE):
            visible = jnp.where(alive_l[:, None], packed_l, jnp.uint32(0))
            packed_all = jax.lax.all_gather(visible, axis_name, tiled=True)
        nbrs_l, deg_l = table if have_table else (None, None)

        with jax.named_scope(SAMPLE):
            rkey = jax.random.fold_in(base_key, round_)
            qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
            partners0 = sample_peers(qkey, gids, topo, k, proto.exclude_self,
                                     local_nbrs=nbrs_l, local_deg=deg_l)
            partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, gids,
                                  partners0, dp, n, force=ch is not None)
            if ch is not None:
                partners = NE.partition_targets(cut, gids, partners, n)
        with jax.named_scope(EXCHANGE):
            pulled = pull_merge_packed(packed_all, partners, n)
        with jax.named_scope(COVERAGE):
            partners = jnp.where(alive_l[:, None], partners, n)
            n_req = jnp.sum(partners < n).astype(jnp.float32)
            if ch is not None:
                lost_pull = NE.lost_count(partners0, partners, alive_l, n)
                if mode == C.ANTI_ENTROPY and proto.period > 1:
                    # quiescent rounds send nothing, so nothing is lost
                    lost_pull = jnp.where((round_ % proto.period) == 0,
                                          lost_pull, 0.0)
                lost = lost + lost_pull
        with jax.named_scope(EXCHANGE):
            if mode == C.ANTI_ENTROPY:
                # Bidirectional reconciliation (twin of models/si_packed.py):
                # the reverse delta scatters bool contributions and reduces
                # them with psum_scatter (int counts, OR = count > 0), then
                # repacks — the pull direction keeps the packed-word
                # all_gather.  On off-period rounds a lax.cond skips the
                # collective entirely (replicated predicate, uniform branch).
                bt = jnp.where(partners < n, partners, n_pad)

                def reverse_delta(_):
                    bcounts = push_counts(n_pad, bt,
                                          unpack(visible, proto.rumors))
                    return pack(jax.lax.psum_scatter(bcounts, axis_name,
                                                     scatter_dimension=0,
                                                     tiled=True) > 0)

                mfac = 3.0
                if proto.period > 1:
                    on = (round_ % proto.period) == 0
                    back = jax.lax.cond(on, reverse_delta,
                                        lambda _: jnp.zeros_like(pulled), None)
                    pulled = jnp.where(on, pulled, jnp.uint32(0))
                    n_req = jnp.where(on, n_req, 0.0)
                else:
                    back = reverse_delta(None)
                pulled = pulled | back
            else:
                mfac = 2.0
            pulled = jnp.where(alive_l[:, None], pulled, jnp.uint32(0))
            seen_l = packed_l | pulled
        with jax.named_scope(COVERAGE):
            msgs_new = msgs + jax.lax.psum(mfac * n_req, axis_name)
            if ch is not None:
                return seen_l, msgs_new, jax.lax.psum(lost, axis_name)
        return seen_l, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    in_specs = [sh2, rep, rep, rep]
    tables = ()
    if have_table:
        in_specs += [sh2, P(axis_name)]
        tables = (nbrs_pad, deg_pad)
    if ch is not None:
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))

    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    mapped = shard_map(local_round, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs)

    def step_tabled(state: SimState, *tbl):
        out = mapped(state.seen, state.round, state.base_key,
                     state.msgs, *tbl)
        new = SimState(seen=out[0], round=state.round + 1,
                       base_key=state.base_key, msgs=out[1])
        # churn path returns (state, lost) — the models/si.py contract
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_packed_state(run: RunConfig, proto: ProtocolConfig,
                              topo: Topology, mesh: Mesh,
                              axis_name: str = "nodes") -> SimState:
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    st = init_packed_state(run, proto, topo.n)
    seen = _pad_rows(st.seen, n_pad, 0)
    seen = jax.device_put(seen, NamedSharding(mesh, P(axis_name, None)))
    return st._replace(seen=seen)


def sharded_checkpoint_ineligible_reason(proto: ProtocolConfig,
                                         exchange: str):
    """Why a multi-device run cannot use the checkpointed sharded driver,
    or None — the ONE list of preconditions, shared by the CLI and any
    future surface (the fused engine's `_fused_ineligible_reason`
    pattern: two callers can never drift apart)."""
    if exchange != "dense":
        return ("--checkpoint shards via the dense packed engine; "
                f"exchange={exchange!r} has no checkpointed driver")
    if proto.mode not in (C.PULL, C.ANTI_ENTROPY):
        return ("the sharded checkpointed driver runs the packed "
                f"pull/antientropy kernels (got mode {proto.mode!r})")
    return None


def restore_sharded_packed_state(state: SimState, mesh: Mesh,
                                 axis_name: str = "nodes") -> SimState:
    """Re-place a host-loaded checkpoint (utils/checkpoint.load_state)
    onto the mesh: the padded ``seen`` rows go back under the node-axis
    sharding, scalars stay replicated.  The loaded rows are already
    mesh-padded (save gathered the padded global array), so a resume on
    the SAME mesh shape is bitwise exact; a different device count would
    change the padding contract, which the CLI fingerprint refuses."""
    seen = jax.device_put(jnp.asarray(state.seen),
                          NamedSharding(mesh, P(axis_name, None)))
    return state._replace(seen=seen)


def checkpointed_packed_sharded(proto: ProtocolConfig, topo: Topology,
                                run: RunConfig, mesh: Mesh, path: str,
                                every: int = 50,
                                fault: Optional[FaultConfig] = None,
                                resume_state: Optional[SimState] = None,
                                want_curve: bool = False,
                                axis_name: str = "nodes",
                                curve_prefix=(), extra_meta=None,
                                lost_prefix: float = 0.0):
    """Fixed-budget sharded run in compiled segments with atomic npz
    checkpoints — the multi-device twin of the single-device
    ``--checkpoint`` driver (utils/checkpoint.run_with_checkpoints):
    long flagship runs survive preemption (the reference loses
    everything on process death, main.go:22-26) and, with
    ``want_curve``, record their convergence curve at the same time.

    Returns ``(final_state, coverage, curve-or-None)``; bitwise equal to
    an uninterrupted segmented run (tests/test_checkpoint_sharded.py).

    Churn schedules run in the segments exactly as in the straight
    sharded drivers (the step indexes its ABSOLUTE ``state.round``;
    resume == straight run bitwise — utils/checkpoint crash contract);
    the destroyed-message total persists across kills via
    ``track_lost``/``lost_prefix`` and the coverage denominator is the
    EVENTUAL alive set (ops/nemesis.eventual_alive_pad)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.utils.checkpoint import run_with_checkpoints
    ch = NE.get(fault)
    step, tables = make_sharded_packed_round(proto, topo, mesh, fault,
                                             run.origin, axis_name,
                                             tabled=True)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)

    def alive_now():
        # built IN-TRACE when called from curve_fn (no O(N) host
        # constant in the compile request — models/swim.py doc); under
        # churn the eventual set: the heal-convergence denominator
        if ch is not None:
            return NE.eventual_alive_pad(fault, topo.n, n_pad,
                                         run.origin)
        return sharded_alive(fault, topo.n, n_pad, run.origin)

    if resume_state is None:
        state = init_sharded_packed_state(run, proto, topo, mesh, axis_name)
    else:
        state = restore_sharded_packed_state(resume_state, mesh, axis_name)
    r = proto.rumors

    curve_fn = None
    if want_curve:
        def curve_fn(s):
            return coverage_packed(s.seen, r, alive_now())

    remaining = max(0, run.max_rounds - int(state.round))
    out = run_with_checkpoints(step, state, remaining, path, every=every,
                               step_args=tables, curve_fn=curve_fn,
                               curve_prefix=curve_prefix,
                               extra_meta=extra_meta,
                               track_lost=ch is not None,
                               lost_prefix=lost_prefix)
    final, curve = out if want_curve else (out, None)
    cov = float(coverage_packed(final.seen, r, alive_now()))
    return final, cov, curve


def _packed_recorder(proto: ProtocolConfig, n_pad: int, n_shards: int):
    """In-loop metrics row for the packed pull/anti-entropy kernels
    (ops/round_metrics; the dense-driver twin lives in
    parallel/sharded._dense_recorder).  Per-device egress: the packed
    all_gather moves ``nl*W*4`` uint32 bytes every round; anti-entropy's
    reverse psum_scatter contributes ``4*n_pad*R`` int32 bytes (the
    counts table is unpacked) on exchange rounds only."""
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.ops.bitpack import n_words
    r = proto.rumors
    nl = n_pad // n_shards
    base = 4.0 + 4.0 * nl * n_words(r)
    offered_per_msg = r * RM.payload_factor(proto.mode)

    def rec(m, prev_count, round0, msgs0, s1, alive_pad, nem=None):
        count = RM.count_packed(s1.seen, alive_pad)
        newly = count - prev_count
        msgs = s1.msgs - msgs0
        b = jnp.float32(base)
        if proto.mode == C.ANTI_ENTROPY:
            b = b + RM.gate_on_exchange_rounds(4.0 * n_pad * r,
                                               proto.period, round0)
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        return RM.record(
            m, newly=newly, msgs=msgs,
            dup=RM.dup_estimate(offered_per_msg * msgs, newly),
            bytes=b,
            front=RM.front_packed(s1.seen, alive_pad, n_shards),
            **kw), count

    return rec


def simulate_until_packed_sharded(proto: ProtocolConfig, topo: Topology,
                                  run: RunConfig, mesh: Mesh,
                                  fault: Optional[FaultConfig] = None,
                                  axis_name: str = "nodes", timing=None):
    """``timing``: optional compile/steady AOT-split dict
    (parallel/sharded.simulate_until_sharded contract).  With an active
    run ledger the loop carries a round-metrics buffer stack, flushed
    once by the chokepoint (ops/round_metrics)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.parallel.sharded import _churn_observables
    from gossip_tpu.utils.trace import maybe_aot_timed
    with span(BUILD):
        step, tables = make_sharded_packed_round(proto, topo, mesh, fault,
                                                 run.origin, axis_name,
                                                 tabled=True)
        n_pad = pad_to_mesh(topo.n, mesh, axis_name)
        ch = NE.get(fault)
        alive_pad = (NE.eventual_alive_pad(fault, topo.n, n_pad, run.origin)
                     if ch is not None
                     else sharded_alive(fault, topo.n, n_pad, run.origin))
        init = init_sharded_packed_state(run, proto, topo, mesh, axis_name)
        target = jnp.float32(run.target_coverage)
        r = proto.rumors
        n_shards = mesh.shape[axis_name]
        rec = (_packed_recorder(proto, n_pad, n_shards)
               if RM.wanted() else None)
        obs = _churn_observables(fault, topo.n, n_pad, run.origin)

    @jax.jit
    def loop(state, *tbl):
        alive_t = (NE.eventual_alive_pad(fault, topo.n, n_pad,
                                         run.origin) if ch is not None
                   else sharded_alive(fault, topo.n, n_pad, run.origin))
        m0 = (RM.init(run.max_rounds, n_shards,
                      "simulate_until_packed_sharded",
                      nemesis=ch is not None) if rec else None)
        c0 = RM.count_packed(state.seen, alive_t) if rec else None
        def cond(carry):
            s, _, _ = carry
            with jax.named_scope(COVERAGE):
                return ((coverage_packed(s.seen, r, alive_t) < target)
                        & (s.round < run.max_rounds))
        def body(carry):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lost = step(s0, *tbl)
            else:
                s, lost = step(s0, *tbl), None
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_t,
                             nem=(obs(round0, lost,
                                      NE.sched_of_tables(tbl))
                                  if obs else None))
            return s, m, cnt
        return jax.lax.while_loop(cond, body, (state, m0, c0))

    final, _, _ = maybe_aot_timed(loop, timing, init, *tables, label="packed")
    with span(READBACK):
        return (int(final.round),
                float(coverage_packed(final.seen, r, alive_pad)),
                float(final.msgs), final)
