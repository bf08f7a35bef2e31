"""Bit-packed pull / anti-entropy rounds: the gather-only TPU fast path.

The measured cost model on the target TPU (methodology: 20-iteration
``fori_loop`` microbenches at N=10M, see bench.py notes):

  * XLA scatter  ~ 10.6 ns/element  (the push half of push-pull)
  * XLA gather   ~  8.0 ns/element  (bool), ~7.0 ns/element (uint32 word)
  * everything else in a round fuses to ~5 ms at N=10M

so a *pull-only* round costs one gather and nothing else, and pull's
endgame is quadratic (the uninfected fraction squares each round: an
uninfected node stays uninfected only if its sampled partner was also
uninfected), giving ~log2(N) + O(log log N) rounds to 99%.  Measured at
N=10M: pull 27 rounds / 2.30 s vs push-pull 17 rounds / 3.54 s — pull wins
on wall-clock by 1.5x despite more rounds.  Packing (ops/bitpack.py) then
moves 32 rumors per gathered word.

Semantics are EXACTLY models/si.make_si_round's PULL / ANTI_ENTROPY modes —
same RNG tags, same per-global-node-id keying, same message accounting —
verified bitwise in tests/test_packed.py.  Push modes are deliberately
absent: scatter-OR is not an XLA primitive and the scatter is the expensive
half; use models/si.py when push semantics are required.  The one
exception is anti-entropy's reverse delta (the exchange is bidirectional),
which unpacks to bools for the scatter on exchange rounds only.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.state import (SimState, alive_mask, bind_tables,
                                     init_state)
from gossip_tpu.ops.bitpack import coverage_packed, pack, unpack
from gossip_tpu.ops.propagate import push_delta
from gossip_tpu.ops.sampling import apply_drop, sample_peers
from gossip_tpu.topology.generators import Topology
from gossip_tpu.utils.trace import (BUILD, COVERAGE, EXCHANGE, READBACK,
                                    SAMPLE, span)


def init_packed_state(run: RunConfig, proto: ProtocolConfig,
                      n: int) -> SimState:
    """SimState whose ``seen`` is uint32[N, ceil(R/32)] (packed)."""
    st = init_state(run, proto, n)
    return st._replace(seen=pack(st.seen))


def pull_merge_packed(packed_all: jax.Array, partners: jax.Array,
                      sentinel: int) -> jax.Array:
    """OR of k sampled peers' packed digest words -> uint32[N_local, W].

    The packed twin of ops/propagate.pull_merge: one uint32 gather moves 32
    rumor bits."""
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = packed_all[safe]                        # [Nl, k, W] uint32
    got = jnp.where(valid[:, :, None], got, jnp.uint32(0))
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = out | got[:, j, :]
    return out


def make_packed_round(proto: ProtocolConfig, topo: Topology,
                      fault: Optional[FaultConfig] = None,
                      origin: int = 0,
                      sampler: str = "threefry",
                      sampler_seed: int = 0,
                      tabled: bool = False):
    """Packed PULL / ANTI_ENTROPY round step.

    ``sampler="threefry"`` (default) is RNG-identical to
    models/si.make_si_round — same tags, bitwise-equal trajectories.
    ``sampler="pallas"`` draws partners with the TPU hardware PRNG
    (ops/pallas_sampling — different stream, implicit complete graph only,
    the opt-in bench fast path).

    ``tabled=True`` returns ``(step, tables)`` with the topology arrays as
    step ARGUMENTS (no O(N) jit closure constants — models/swim.py doc);
    the liveness mask is built in-trace."""
    n, k = topo.n, proto.fanout
    mode = proto.mode
    if mode not in (C.PULL, C.ANTI_ENTROPY):
        raise ValueError(
            f"packed rounds support pull/antientropy only, got {mode!r} "
            "(push needs scatter-OR, which XLA does not have — see module "
            "doc)")
    if sampler not in ("threefry", "pallas"):
        raise ValueError(f"unknown sampler {sampler!r}")
    if sampler == "pallas" and not topo.implicit:
        raise ValueError("the pallas sampler draws on the implicit "
                         "complete graph only")
    drop_prob = 0.0 if fault is None else fault.drop_prob
    tables = () if topo.implicit else (topo.nbrs, topo.deg)
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)
    if ch is not None:
        # schedule as runtime operands on the table tail (models/si.py
        # twin; ops/nemesis module doc)
        tables = tables + NE.sched_args(NE.build(fault, n))

    def step_tabled(state: SimState, *tbl):
        tbl, sched = NE.split_tables(ch, tbl)
        nbrs_t, deg_t = tbl if tbl else (None, None)
        ids = jnp.arange(n, dtype=jnp.int32)
        packed = state.seen
        # the round's fault masks decide, like the drop coins, which
        # pulls happen: they ride the sample scope
        with jax.named_scope(SAMPLE):
            if ch is not None:
                # churn path: per-round liveness / drop prob / cut from the
                # schedule operands (models/si.py twin)
                alive = NE.alive_rows(sched, NE.base_alive_or_ones(
                    fault, n, origin), state.round)
                dp = NE.drop_at(sched, state.round)
                cut = NE.cut_at(sched, state.round)
            else:
                alive = alive_mask(fault, n, origin)  # in-trace
                dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        with jax.named_scope(SAMPLE):
            rkey = jax.random.fold_in(state.base_key, state.round)
            if sampler == "pallas":
                from gossip_tpu.ops.pallas_sampling import sample_peers_fast
                partners = sample_peers_fast(sampler_seed, state.round, n, n,
                                             k, proto.exclude_self)
            else:
                qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
                partners = sample_peers(qkey, ids, topo, k,
                                        proto.exclude_self,
                                        local_nbrs=nbrs_t, local_deg=deg_t)
            partners0 = partners
            partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, ids,
                                  partners0, dp, n, force=ch is not None)
            if ch is not None:
                partners = NE.partition_targets(cut, ids, partners, n)
        with jax.named_scope(EXCHANGE):
            visible = packed if alive is None else jnp.where(
                alive[:, None], packed, jnp.uint32(0))
            pulled = pull_merge_packed(visible, partners, n)
        with jax.named_scope(COVERAGE):
            if alive is not None:
                partners = jnp.where(alive[:, None], partners, n)
            n_req = jnp.sum(partners < n).astype(jnp.float32)
            if ch is not None:
                lost_pull = NE.lost_count(partners0, partners, alive, n)
                if mode == C.ANTI_ENTROPY and proto.period > 1:
                    # quiescent rounds send nothing, so nothing is lost
                    lost_pull = jnp.where(
                        (state.round % proto.period) == 0, lost_pull, 0.0)
                lost = lost + lost_pull
        with jax.named_scope(EXCHANGE):
            if mode == C.ANTI_ENTROPY:
                # Bidirectional reconciliation (twin of models/si.py): the
                # initiator's digest also scatters back into the partner's row.
                # XLA has no scatter-OR on words, so the push-back unpacks to
                # bools for the scatter and repacks — lax.cond confines that
                # cost to exchange rounds; the pull direction stays a pure
                # word gather.
                def reverse_delta(_):
                    return pack(push_delta(n, partners,
                                           unpack(visible, proto.rumors)))

                mfac = 3.0    # request + digest response + reverse delta
                if proto.period > 1:
                    on = (state.round % proto.period) == 0
                    back = jax.lax.cond(on, reverse_delta,
                                        lambda _: jnp.zeros_like(pulled), None)
                    pulled = jnp.where(on, pulled, jnp.uint32(0))
                    n_req = jnp.where(on, n_req, 0.0)
                else:
                    back = reverse_delta(None)
                pulled = pulled | back
            else:
                mfac = 2.0    # request + digest response
            if alive is not None:
                pulled = jnp.where(alive[:, None], pulled, jnp.uint32(0))
            seen = packed | pulled
        out = SimState(seen=seen, round=state.round + 1,
                       base_key=state.base_key,
                       msgs=state.msgs + mfac * n_req)
        return (out, lost) if ch is not None else out

    return bind_tables(step_tabled, tables, tabled)


def simulate_until_packed(proto: ProtocolConfig, topo: Topology,
                          run: RunConfig,
                          fault: Optional[FaultConfig] = None,
                          timing: Optional[dict] = None):
    """while_loop to target coverage on packed state — the bench fast path.
    Returns (rounds, coverage, msgs, final_state).  ``timing``: pass a
    dict for the compile/steady AOT split (utils.trace.aot_timed)."""
    from gossip_tpu.ops import nemesis as NE
    with span(BUILD):
        step, tables = make_packed_round(proto, topo, fault, run.origin,
                                         tabled=True)
        step = NE.drop_lost(step, NE.get(fault))
        alive = NE.metric_alive(fault, topo.n, run.origin)
        init = init_packed_state(run, proto, topo.n)
        target = jnp.float32(run.target_coverage)
    r = proto.rumors

    @jax.jit
    def loop(state, *tbl):
        alive_t = NE.metric_alive(fault, topo.n, run.origin)
        def cond(s):
            with jax.named_scope(COVERAGE):
                return ((coverage_packed(s.seen, r, alive_t) < target)
                        & (s.round < run.max_rounds))
        def body(s):
            return step(s, *tbl)
        return jax.lax.while_loop(cond, body, state)

    from gossip_tpu.utils.trace import maybe_aot_timed
    final = maybe_aot_timed(loop, timing, init, *tables)
    with span(READBACK):
        return (int(final.round),
                float(coverage_packed(final.seen, r, alive)),
                float(final.msgs), final)


def compiled_until_packed(proto: ProtocolConfig, topo: Topology,
                          run: RunConfig,
                          fault: Optional[FaultConfig] = None,
                          sampler: str = "threefry"):
    """Compiled packed while-loop + fresh init (bench: compile/run split).
    Returns (loop, init, tables); call ``loop(state, *tables)``."""
    from functools import partial

    from gossip_tpu.ops import nemesis as NE
    step, tables = make_packed_round(proto, topo, fault, run.origin,
                                     sampler, run.seed, tabled=True)
    step = NE.drop_lost(step, NE.get(fault))
    init = init_packed_state(run, proto, topo.n)
    target = jnp.float32(run.target_coverage)
    r = proto.rumors

    @partial(jax.jit, donate_argnums=0)
    def loop(state, *tbl):
        alive = NE.metric_alive(fault, topo.n, run.origin)
        def cond(s):
            with jax.named_scope(COVERAGE):
                return ((coverage_packed(s.seen, r, alive) < target)
                        & (s.round < run.max_rounds))
        def body(s):
            return step(s, *tbl)
        return jax.lax.while_loop(cond, body, state)

    return loop, init, tables
