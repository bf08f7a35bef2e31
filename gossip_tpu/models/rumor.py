"""SIR rumor mongering: push gossip that STOPS — the Demers et al. 1987
"rumor mongering" family (§1.4 of the Clearinghouse paper), counter-death
variants.

The SI modes (models/si.py) never stop pushing: an infected node stays
infective forever, so push traffic is Theta(N * fanout) every round even
at full coverage.  Rumor mongering adds the classic third state — each
(node, rumor) is susceptible -> infective ("hot", actively forwarded) ->
REMOVED (known but no longer forwarded) — and nodes lose interest via an
unnecessary-contact counter:

* ``feedback``: a push whose recipient ALREADY knew the rumor increments
  the sender's counter for it; ``rumor_k`` such hits remove it.
* ``blind``: every attempted push increments the counter, whether the
  link or a partition then loses it (the sender cannot tell) — removal
  after exactly ``rumor_k`` pushes.  In the round that would pass
  ``rumor_k`` a pair pushes to its first ``rumor_k - cnt`` targets only.
  This is memberlist's ``TransmitLimitedQueue``: a broadcast leaves the
  queue after its ``retransmitLimit``-th send.

The run self-terminates when the hot set is empty.  The classic quality
metric is the **residue** s(infinity): the fraction of nodes never
informed when gossip dies out (Demers: counter feedback k=2 leaves
~2-6% residue on its own, which is why real systems pair rumor
mongering with periodic anti-entropy — both live in this framework, and
``--mode antientropy`` is the complement).

Reference mapping: the reference's relay (main.go:72-88) is SI flood —
it forwards forever and terminates only because the *dedup set* stops
re-broadcasts (main.go:113).  Rumor mongering is what the reference
would need at scale to stop paying O(degree) per duplicate delivery;
the counter-death semantics here are the batched, round-synchronous
form of that upgrade.

Everything is a pure array update: one round = sample targets for hot
(node, rumor) pairs -> scatter-OR the hot payload -> gather recipients'
prior knowledge for the feedback counters -> threshold against
``rumor_k``.  No data-dependent shapes: dead (node, rumor) pairs simply
push nothing.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models.state import alive_mask, bind_tables
from gossip_tpu.ops.propagate import push_delta
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.topology.generators import Topology
from gossip_tpu.utils.trace import (BUILD, COVERAGE, EXCHANGE, READBACK,
                                    SAMPLE, SIR, span)

RUMOR_PUSH_TAG, RUMOR_DROP_TAG = 11, 12


class RumorState(NamedTuple):
    """SIR per-(node, rumor) state carried through the round loop."""

    seen: jax.Array      # bool[N, R] — informed (infective OR removed)
    hot: jax.Array       # bool[N, R] — infective: still forwarding
    cnt: jax.Array       # int32[N, R] — unnecessary-contact counter
    round: jax.Array     # int32 scalar
    base_key: jax.Array  # PRNG key
    msgs: jax.Array      # float32 scalar — push messages sent


def init_rumor_state(run: RunConfig, proto: ProtocolConfig,
                     n: int) -> RumorState:
    """Rumor r starts hot at node (origin + r) % n (models/state contract)."""
    r = proto.rumors
    origins = (run.origin + jnp.arange(r)) % n
    seen = jnp.zeros((n, r), jnp.bool_).at[origins, jnp.arange(r)].set(True)
    return RumorState(seen=seen, hot=seen, cnt=jnp.zeros((n, r), jnp.int32),
                      round=jnp.int32(0), base_key=jax.random.key(run.seed),
                      msgs=jnp.float32(0.0))


def blind_sends(payload: jax.Array, targets0: jax.Array, cnt: jax.Array,
                rumor_k: int, n: int) -> jax.Array:
    """bool[N, k, R]: the blind counter's pushes this round — pair
    (i, r) sends to its j-th drawn target iff it is hot on an alive
    node, the target exists, and ``j < rumor_k - cnt[i, r]``.
    ``targets0`` are the targets before the drop coin and the cut: a
    send counts whether or not it arrives."""
    slot = jnp.arange(targets0.shape[1], dtype=jnp.int32)[None, :, None]
    return (payload[:, None, :] & (targets0 < n)[:, :, None]
            & (slot < (rumor_k - cnt)[:, None, :]))


def make_rumor_round(proto: ProtocolConfig, topo: Topology,
                     fault: Optional[FaultConfig] = None,
                     origin: int = 0, tabled: bool = False):
    """Build the single-device rumor-mongering round step
    (``RumorState -> RumorState``; ``tabled=True`` as in make_si_round)."""
    if proto.mode != C.RUMOR:
        raise ValueError(f"make_rumor_round builds mode='rumor' only "
                         f"(got {proto.mode!r})")
    n, k = topo.n, proto.fanout
    kk = proto.rumor_k
    feedback = proto.rumor_variant == "feedback"
    drop_prob = 0.0 if fault is None else fault.drop_prob
    tables = () if topo.implicit else (topo.nbrs, topo.deg)
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)
    if ch is not None:
        # schedule as runtime operands on the table tail (models/si.py
        # twin; ops/nemesis module doc)
        tables = tables + NE.sched_args(NE.build(fault, n))

    def step_tabled(state: RumorState, *tbl):
        tbl, sched = NE.split_tables(ch, tbl)
        nbrs_t, deg_t = tbl if tbl else (None, None)
        ids = jnp.arange(n, dtype=jnp.int32)
        seen, hot, cnt = state.seen, state.hot, state.cnt
        # the round's fault masks decide, like the drop coins, which
        # pushes arrive: they ride the sample scope
        with jax.named_scope(SAMPLE):
            rkey = jax.random.fold_in(state.base_key, state.round)
            if ch is not None:
                # churn path: per-round liveness / drop prob / cut from
                # the schedule operands.  A churn-down node loses its hot
                # (forwarding) state like a process crash; its seen set
                # persists (the durable dedup store, main.go:22-26).
                alive = NE.alive_rows(sched, NE.base_alive_or_ones(
                    fault, n, origin), state.round)
                dp = NE.drop_at(sched, state.round)
                cut = NE.cut_at(sched, state.round)
            else:
                alive = alive_mask(fault, n, origin)
                dp, cut = drop_prob, None

            # What this node forwards this round: its hot rumors (dead
            # nodes go dark — neither send nor count).
            payload = hot if alive is None else hot & alive[:, None]

            pkey = jax.random.fold_in(rkey, RUMOR_PUSH_TAG)
            targets0 = sample_peers(pkey, ids, topo, k, proto.exclude_self,
                                    local_nbrs=nbrs_t, local_deg=deg_t)
            targets = apply_drop(rkey, RUMOR_DROP_TAG, ids, targets0,
                                 dp, n, force=ch is not None)     # [N, k]
            if ch is not None:
                targets = NE.partition_targets(cut, ids, targets, n)
            sender_active = jnp.any(payload, axis=1)               # [N]

        # What goes to each target: feedback pushes the whole hot row to
        # every target; blind only within each pair's remaining quota.
        with jax.named_scope(SIR):
            if feedback:
                sends, sent = payload, sender_active[:, None]
            else:
                sends = blind_sends(payload, targets0, cnt, kk, n)
                sent = jnp.any(sends, axis=2)                      # [N, k]
        valid = (targets < n) & sent                               # [N, k]

        with jax.named_scope(EXCHANGE):
            # Deliveries: scatter-OR of the pushed rows into the targets.
            delta = push_delta(n, jnp.where(valid, targets, n), sends)
            if alive is not None:
                delta = delta & alive[:, None]  # dead nodes receive nothing
            if feedback:
                # recipients' ROUND-START knowledge (synchronous
                # semantics: all pushes observe the same snapshot)
                safe_t = jnp.where(valid, targets, 0)
                knew = seen[safe_t] & valid[:, :, None]            # [N,k,R]

        with jax.named_scope(SIR):
            # feedback counts pushes whose recipient already knew the
            # rumor; blind counts every attempted push
            if feedback:
                hits = jnp.sum(knew, axis=1, dtype=jnp.int32)      # [N, R]
                cnt = cnt + jnp.where(payload, hits, 0)
            else:
                cnt = cnt + jnp.sum(sends, axis=1, dtype=jnp.int32)

            # Loss of interest (removal) + fresh infections become hot.
            # Dead nodes can hold no hot bits (a dead multi-rumor origin
            # would otherwise stay "hot" forever with its payload masked,
            # and the extinction loop would never terminate); like SI, a
            # rumor whose origin is dead simply never spreads.
            new = delta & ~seen
            hot = (hot & (cnt < kk)) | new
            if alive is not None:
                hot = hot & alive[:, None]
            seen = seen | delta

        with jax.named_scope(COVERAGE):
            msgs = state.msgs + jnp.sum(valid).astype(jnp.float32)
            if ch is not None:
                lost = NE.lost_count(jnp.where(sent, targets0, n),
                                     jnp.where(sent, targets, n),
                                     sender_active, n)
        out = RumorState(seen=seen, hot=hot, cnt=cnt,
                         round=state.round + 1,
                         base_key=state.base_key, msgs=msgs)
        return (out, lost) if ch is not None else out

    return bind_tables(step_tabled, tables, tabled)


def rumor_coverage(seen: jax.Array,
                   alive: Optional[jax.Array] = None) -> jax.Array:
    """Min-over-rumors informed fraction (same contract as si.coverage)."""
    if alive is None:
        return jnp.min(jnp.mean(seen.astype(jnp.float32), axis=0))
    w = alive.astype(jnp.float32)
    per_rumor = (seen.astype(jnp.float32) * w[:, None]).sum(0) / w.sum()
    return jnp.min(per_rumor)


@jax.jit
def _final_coverage(seen: jax.Array, alive: Optional[jax.Array]):
    with jax.named_scope(COVERAGE):
        return rumor_coverage(seen, alive)


def simulate_until_rumor(proto: ProtocolConfig, topo: Topology,
                         run: RunConfig,
                         fault: Optional[FaultConfig] = None,
                         timing: Optional[dict] = None):
    """Run to extinction (no hot pairs left) or max_rounds, one compiled
    while_loop.  Returns (rounds, coverage, residue, msgs, final_state):
    ``residue`` is the never-informed fraction at termination — the
    rumor-mongering quality metric (worst rumor).  ``timing``: pass a
    dict for the compile/steady AOT split (utils.trace.aot_timed).  The
    seed rides the init state's key, so one executable serves every
    seed of a configuration."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.utils.trace import maybe_aot_timed
    with span(BUILD):
        step, tbl = make_rumor_round(proto, topo, fault, run.origin,
                                     tabled=True)
        step = NE.drop_lost(step, NE.get(fault))
        init = init_rumor_state(run, proto, topo.n)
        # alive_mask, NOT static_death_draw: the kernel pins the origin
        # alive, so the metric weighting must too (matches the sharded
        # twin and every SI curve path); under churn the eventual alive
        # set (ops/nemesis.metric_alive — heal-convergence denominator)
        alive = NE.metric_alive(fault, topo.n, run.origin)

    @jax.jit
    def loop(state, *tables):
        def cond(s):
            with jax.named_scope(COVERAGE):
                return jnp.any(s.hot) & (s.round < run.max_rounds)

        def body(s):
            return step(s, *tables)

        return jax.lax.while_loop(cond, body, state)

    final = maybe_aot_timed(loop, timing, init, *tbl, label="rumor")
    with span(READBACK):
        cov = float(_final_coverage(final.seen, alive))
        return (int(final.round), cov, 1.0 - cov, float(final.msgs), final)


def checkpointed_rumor(proto: ProtocolConfig, topo: Topology,
                       run: RunConfig, path: str, every: int = 50,
                       fault: Optional[FaultConfig] = None, mesh=None,
                       resume_state=None, want_curve: bool = False,
                       curve_prefix=(), extra_meta=None,
                       lost_prefix: float = 0.0):
    """Fixed-budget rumor-mongering run in compiled segments with atomic
    npz checkpoints (utils/checkpoint.run_with_checkpoints) — the SIR
    twin of the SI/SWIM ``--checkpoint`` engines.  Unlike
    :func:`simulate_until_rumor` this does NOT early-exit at extinction
    (segments are fixed-length); the extinct state is absorbing, so the
    trailing rounds are no-ops and the trajectory stays bitwise equal to
    the segmented run it resumes.

    ``want_curve`` records TWO named channels per round — ``coverage``
    (min-over-rumors informed fraction) and ``hot`` (infective
    fraction) — because the extinction round is only recoverable from
    the hot channel (a coverage plateau is NOT extinction: feedback
    pushes keep flowing between informed pairs).  With ``mesh`` the
    node-sharded twin runs.  Returns ``(final_state, coverage,
    residue, curve-dict-or-None)``.

    Under a churn schedule the segments run the fault program exactly
    as the straight drivers do (the step reads its ABSOLUTE
    ``state.round``, which the checkpoint persists — resume == straight
    run bitwise, utils/checkpoint crash contract), the destroyed-
    message total accumulates across kills (``track_lost``; seed a
    resume with the checkpoint's ``extra['dropped']`` via
    ``lost_prefix``), and the metric denominator is the EVENTUAL alive
    set (heal-convergence contract, ops/nemesis.metric_alive).
    """
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.utils.checkpoint import run_with_checkpoints
    ch = NE.get(fault)
    if mesh is None:
        step, tables = make_rumor_round(proto, topo, fault, run.origin,
                                        tabled=True)
        state = (resume_state if resume_state is not None
                 else init_rumor_state(run, proto, topo.n))

        def alive_now():
            # static mask without churn, eventual-alive set under it —
            # metric_alive is the one dispatch
            return NE.metric_alive(fault, topo.n, run.origin)
    else:
        from gossip_tpu.parallel.sharded import pad_to_mesh, sharded_alive
        from gossip_tpu.parallel.sharded_rumor import (
            init_sharded_rumor_state, make_sharded_rumor_round,
            restore_sharded_rumor_state)
        step, tables = make_sharded_rumor_round(proto, topo, mesh, fault,
                                                run.origin, tabled=True)
        state = (restore_sharded_rumor_state(resume_state, mesh)
                 if resume_state is not None
                 else init_sharded_rumor_state(run, proto, topo, mesh))
        n_rows = pad_to_mesh(topo.n, mesh, "nodes")

        def alive_now():
            # padded alive mask: padding rows must not deflate coverage
            if ch is not None:
                return NE.eventual_alive_pad(fault, topo.n, n_rows,
                                             run.origin)
            return sharded_alive(fault, topo.n, n_rows, run.origin)

    curve_fn = None
    if want_curve:
        def curve_fn(s):
            alive = alive_now()
            hot_any = jnp.any(s.hot, axis=1).astype(jnp.float32)
            if alive is None:
                hot_frac = jnp.mean(hot_any)
            else:
                w = alive.astype(jnp.float32)
                hot_frac = jnp.sum(hot_any * w) / jnp.sum(w)
            return {"coverage": rumor_coverage(s.seen, alive),
                    "hot": hot_frac}

    remaining = max(0, run.max_rounds - int(state.round))
    out = run_with_checkpoints(step, state, remaining, path, every=every,
                               step_args=tables, curve_fn=curve_fn,
                               curve_prefix=curve_prefix,
                               extra_meta=extra_meta,
                               track_lost=ch is not None,
                               lost_prefix=lost_prefix)
    final, curve = out if want_curve else (out, None)
    cov = float(rumor_coverage(final.seen, alive_now()))
    return final, cov, 1.0 - cov, curve


def simulate_curve_rumor(proto: ProtocolConfig, topo: Topology,
                         run: RunConfig,
                         fault: Optional[FaultConfig] = None):
    """Fixed-length scan: per-round (coverage, hot_fraction, msgs) curves
    — hot_fraction shows the infective wave rise and die out."""
    from gossip_tpu.ops import nemesis as NE
    step, tbl = make_rumor_round(proto, topo, fault, run.origin, tabled=True)
    step = NE.drop_lost(step, NE.get(fault))
    init = init_rumor_state(run, proto, topo.n)

    @jax.jit
    def scan(state, *tables):
        # alive-weighted coverage, consistent with the until-driver and
        # the SI curve paths (dead nodes are unreachable, not uninformed)
        alive = NE.metric_alive(fault, topo.n, run.origin)
        hot_w = (jnp.float32(1.0) if alive is None
                 else alive.astype(jnp.float32))

        def body(s, _):
            s = step(s, *tables)
            hot_any = jnp.any(s.hot, axis=1).astype(jnp.float32)
            hot_frac = (jnp.mean(hot_any) if alive is None
                        else jnp.sum(hot_any * hot_w) / jnp.sum(hot_w))
            return s, (rumor_coverage(s.seen, alive), hot_frac, s.msgs)
        return jax.lax.scan(body, state, None, length=run.max_rounds)

    final, (covs, hots, msgs) = scan(init, *tbl)
    return covs, hots, msgs, final
