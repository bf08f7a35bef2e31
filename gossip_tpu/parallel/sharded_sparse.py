"""Sparse cross-shard digest exchange: all_to_all request/response rounds.

Every sharded round in parallel/sharded*.py moves O(N) bytes per round over
ICI (`all_gather` of the whole digest table / `psum_scatter` of a full
count table) no matter how many messages the protocol actually sends.  At
10M nodes x 256 rumors that is ~320 MB/round.  This module is the
O(messages) alternative the SURVEY (§2.4, §7 "Cross-shard randomness +
exchange at 10M nodes") and round-1 VERDICT call for: the batched analog of
the reference's *point-to-point* ``SyncRPC`` (/root/reference/main.go:81)
— each pull request travels to exactly one peer shard and comes back as one
digest, instead of every shard broadcasting everything.

How static shapes are squared with sparse traffic
-------------------------------------------------
XLA collectives move fixed-size buffers, so "send only what you sampled"
needs per-(src,dst) message counts known at compile time.  Uniform iid
partner sampling gives Binomial counts — worst case nl*k, which would
erase the savings.  Instead the partner draw is **stratified over shards**:

  * each shard's ``nl*k`` request slots are split round-robin into P
    balanced groups of ``cap = nl*k/P`` (group of local slot ``t`` is
    ``(t + o_r) mod P``, with a fresh random offset ``o_r`` each round);
  * a fresh uniform random permutation ``pi_r`` of the P shards (shared by
    all shards, derived from the round key) maps groups to partner shards;
  * the partner *row within* the shard is drawn uniformly per slot, keyed
    by the slot's global id.

Every slot's partner is therefore EXACTLY uniform over all ``n_pad`` rows
(``pi_r[(t + o_r) mod P]`` is uniform over shards for any fixed ``t``; the
row draw is uniform within the shard), while per-(src,dst) counts are the
constant ``cap`` — the all_to_all buffers are ``[P, cap]`` requests out,
``[P, cap, W]`` digest words back.  What differs from iid sampling is only
the joint distribution (slots of one shard are spread round-robin over
partner shards instead of binomially); the per-node marginal — which
drives the epidemic recurrence — is untouched.  Same design move as the
fused Pallas kernel's lane/row factoring (ops/pallas_round.py).

Traffic accounting (returned as :class:`SparseMeta`): per device per round
the sparse exchange moves ``P*cap*4`` request bytes + ``P*cap*4W`` response
bytes = ``nl*k*(4 + 4W)``, vs ``n_pad*4W`` for the dense all_gather — an
O(N) -> O(messages) drop whenever ``k << P`` rumor words would have been
broadcast wastefully (at N=10M, P=8, W=8, k=1: 45 MB vs 320 MB per round).

Bitwise parity: :func:`sparse_pull_round_reference` computes the identical
trajectory on one device (same RNG keying by global slot id, same pi_r/o_r)
— tests/test_sharded_sparse.py checks equality on the 8-device CPU mesh.
The stratification parameter P is part of the trajectory definition, so the
reference takes it explicitly.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models.state import SimState
from gossip_tpu.ops.bitpack import coverage_packed, n_words, pack, unpack
from gossip_tpu.parallel.sharded import (_pad_rows, local_rows,
                                         pad_to_mesh, sharded_alive)

# RNG tags (disjoint from models/si.py's 1..5)
SPARSE_PERM_TAG = 101
SPARSE_OFFSET_TAG = 102
SPARSE_ROW_TAG = 103
SPARSE_DROP_TAG = 104
TOPO_NBR_TAG = 105


class SparseMeta(NamedTuple):
    """Per-round ICI traffic of the sparse exchange vs the dense path.

    For anti-entropy with period > 1 the kernels cond-skip the ENTIRE
    exchange — request, response, and reverse collectives alike — on
    quiescent rounds, so every byte figure here is per EXCHANGE round
    and the steady per-round average is ``sparse_bytes / period``.
    Pull (and period == 1) exchanges every round, so the figures are
    then plain per-round numbers."""
    p: int                    # shards
    cap: int                  # requests per (src, dst) pair
    request_bytes: int        # per device per EXCHANGE round
    response_bytes: int       # per device per EXCHANGE round
    dense_bytes: int          # per device per round, all_gather equivalent
    # anti-entropy reverse-delta payload (0 = pull)
    reverse_bytes: int = 0

    @property
    def sparse_bytes(self) -> int:
        return self.request_bytes + self.response_bytes + self.reverse_bytes


def sparse_meta(n_pad: int, p: int, k: int, w: int,
                bidirectional: bool = False) -> SparseMeta:
    nl = n_pad // p
    cap = (nl * k) // p
    return SparseMeta(p=p, cap=cap,
                      request_bytes=p * cap * 4,
                      response_bytes=p * cap * 4 * w,
                      dense_bytes=n_pad * 4 * w,
                      reverse_bytes=p * cap * 4 * w if bidirectional else 0)


def _validate(n_pad: int, p: int, k: int) -> int:
    nl = n_pad // p
    if n_pad % p:
        raise ValueError(f"n_pad={n_pad} not divisible by mesh size {p}")
    if (nl * k) % p:
        raise ValueError(
            f"slots per shard ({nl}*{k}) must divide by mesh size {p} for "
            "balanced stratification; pad n or adjust fanout")
    return nl


def _round_draws(rkey: jax.Array, p: int):
    """(pi_r, o_r): the round's shard permutation + group offset.

    Replicated computation — every shard derives the same values."""
    pi = jax.random.permutation(jax.random.fold_in(rkey, SPARSE_PERM_TAG),
                                jnp.arange(p, dtype=jnp.int32))
    o = jax.random.randint(jax.random.fold_in(rkey, SPARSE_OFFSET_TAG),
                           (), 0, p, dtype=jnp.int32)
    return pi, o


def _slot_rows(rkey: jax.Array, slot_gids: jax.Array, nl: int) -> jax.Array:
    """Uniform partner row in [0, nl) per slot, keyed by global slot id."""
    base = jax.random.fold_in(rkey, SPARSE_ROW_TAG)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base, slot_gids)
    return jax.vmap(
        lambda kk: jax.random.randint(kk, (), 0, nl, dtype=jnp.int32))(keys)


def _slot_valid(rkey: jax.Array, slot_gids: jax.Array, drop_prob,
                alive_rows: jax.Array, k: int,
                force: bool = False) -> jax.Array:
    """Which slots issue a request: requester alive and link not dropped.
    ``force=True`` always draws the drop coins so ``drop_prob`` may be a
    TRACED per-round scalar (the ops/nemesis drop-ramp path; a p=0
    round draws all-False, bitwise a no-op on the trajectory)."""
    valid = jnp.repeat(alive_rows, k)
    if force or drop_prob > 0.0:
        base = jax.random.fold_in(rkey, SPARSE_DROP_TAG)
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base,
                                                               slot_gids)
        dropped = jax.vmap(
            lambda kk: jax.random.bernoulli(kk, drop_prob))(keys)
        valid = valid & ~dropped
    return valid


def _or_reduce_k(flat: jax.Array, nl: int, k: int) -> jax.Array:
    """uint32[nl*k, W] -> OR over the k slots of each row -> uint32[nl, W]."""
    g = flat.reshape(nl, k, -1)
    out = g[:, 0, :]
    for j in range(1, k):
        out = out | g[:, j, :]
    return out


def _scatter_merge_digests(ok: jax.Array, recv: jax.Array,
                           recv_d: jax.Array, nl: int, rumors: int,
                           w: int) -> jax.Array:
    """Responder-side anti-entropy reverse merge, the ONE canonical
    implementation both mesh kernels share: OR the received requester
    digests (``recv_d`` [p, cap, W]) into the locally-requested rows
    (``recv`` [p, cap]; invalid slots carry the sentinel and drop)."""
    rows_in = jnp.where(ok, recv, nl).reshape(-1)
    contrib = unpack(recv_d.reshape(-1, w), rumors)
    cnt = jnp.zeros((nl, rumors), jnp.int32).at[rows_in].add(
        contrib.astype(jnp.int32), mode="drop")
    return pack(cnt > 0)


def make_sparse_pull_round(
        proto: ProtocolConfig, n: int, mesh: Mesh,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", tabled: bool = False):
    """Sharded packed pull round with sparse all_to_all digest exchange.

    Implicit complete topology only (the 10M-node scale path — explicit
    neighbor tables keep the dense kernels of parallel/sharded_packed.py).
    State is rumor-packed ``uint32[n_pad, W]`` as in models/si_packed.

    ``proto.exclude_self`` is NOT honored (unlike ops/sampling): the
    stratified draw is uniform over all rows including the requester, so a
    slot self-pulls with probability 1/n_pad — a no-op for SI state, same
    treatment as the fused kernel's phantom pulls (ops/pallas_round.py).
    Exact self-exclusion would make the within-shard row distribution
    non-uniform across shards; not worth the bias for a 1/n effect.

    ``tabled=True`` returns ``(step, tables)`` where ``tables`` is the
    schedule-operand tail (``NE.sched_args``; empty without churn) and
    ``step(state, *tables)`` takes it as ARGUMENTS — the churn drivers
    thread it through their jitted loops so the compiled program holds
    no schedule content (ops/nemesis module doc).  The default closure
    form stays for small callers (content closure-baked, still exact).
    """
    if proto.mode not in (C.PULL, C.ANTI_ENTROPY):
        raise ValueError("sparse exchange is a pull/anti-entropy path; "
                         f"got mode {proto.mode!r}")
    p = mesh.shape[axis_name]
    k = proto.fanout
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = _validate(n_pad, p, k)
    cap = (nl * k) // p
    w = n_words(proto.rumors)
    drop_prob = 0.0 if fault is None else fault.drop_prob
    alive_pad = sharded_alive(fault, n, n_pad, origin)
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)

    def local_round(seen_l, round_, base_key, msgs, alive_l,
                    *sched_tail):
        _, sched = NE.split_tables(ch, sched_tail)
        shard = jax.lax.axis_index(axis_name)
        rkey = jax.random.fold_in(base_key, round_)
        if ch is not None:
            # churn path: the alive operand stays the STATIC mask; the
            # schedule OPERANDS' down-window subtracts per round
            alive_l = NE.alive_rows(
                NE.Schedule(local_rows(sched.die, axis_name, nl),
                            local_rows(sched.rec, axis_name, nl),
                            sched.cut_tbl, sched.drop_tbl),
                alive_l, round_)
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
        else:
            dp, cut = drop_prob, None
        visible = jnp.where(alive_l[:, None], seen_l, jnp.uint32(0))

        def exchange(_):
            """The whole round's sampling + collectives.  For
            anti-entropy with period > 1 a lax.cond skips this ENTIRELY
            on quiescent rounds — forward and reverse bytes both (draws
            are keyed by (round, slot id), so skipped rounds never
            perturb later ones; the reference twin computes-and-zeroes
            to the identical state)."""
            pi, o = _round_draws(rkey, p)
            inv_pi = jnp.argsort(pi).astype(jnp.int32)

            slot_gids = shard * (nl * k) + jnp.arange(nl * k,
                                                      dtype=jnp.int32)
            rows_req = _slot_rows(rkey, slot_gids, nl)        # [nl*k]
            valid = _slot_valid(rkey, slot_gids, dp, alive_l, k,
                                force=ch is not None)
            if ch is not None:
                # cross-cut requests are lost for this round only (the
                # dense kernels' partition_targets semantics, slot form)
                local_slot = jnp.arange(nl * k, dtype=jnp.int32)
                partner_shard = jnp.take(pi, (local_slot + o) % p)
                partner_gid = partner_shard * nl + rows_req
                req_gid = slot_gids // k
                would = jnp.repeat(alive_l, k)
                valid = valid & NE.same_side(cut, req_gid, partner_gid)
                lost = jnp.sum(would & ~valid, dtype=jnp.float32)
            else:
                # must carry the varying-manual-axes type: this is a
                # cond-branch output matched against the quiescent
                # branch's pvary'd zf when period > 1
                lost = jax.lax.pcast(jnp.float32(0.0), (axis_name,), to="varying")
            rows_req = jnp.where(valid, rows_req, jnp.int32(-1))

            # Column c of the [cap, p] slot view holds group (c + o) % p;
            # the shard receiving column c is pi[(c + o) % p].  Reorder
            # columns so send[d] is the block destined to shard d.
            A = rows_req.reshape(cap, p)                      # [cap, p]
            cols_for_dst = (inv_pi - o) % p                   # [p]
            send = jnp.take(A.T, cols_for_dst, axis=0)        # [p, cap]

            recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=False)
            # recv[s, :] = rows requested by shard s from THIS shard.
            ok = recv >= 0
            resp = visible[jnp.clip(recv, 0, nl - 1)]         # [p, cap, W]
            resp = jnp.where(ok[:, :, None], resp, jnp.uint32(0))
            back = jax.lax.all_to_all(resp, axis_name, 0, 0, tiled=False)

            # back[d] answers the column we sent to shard d; undo the
            # reorder.
            dst_for_col = jnp.take(pi, (jnp.arange(p, dtype=jnp.int32)
                                        + o) % p)
            R_cols = jnp.take(back, dst_for_col, axis=0)   # [p(col),cap,W]
            flat = jnp.transpose(R_cols, (1, 0, 2)).reshape(nl * k, w)
            pulled = _or_reduce_k(flat, nl, k)

            if proto.mode == C.ANTI_ENTROPY:
                # Bidirectional reconciliation: the requester's own
                # digest rides ALONG with the request (one extra
                # [p, cap, W] all_to_all) and the responder merges it
                # locally — the partner pair converges to the union in
                # one exchange, still O(messages) traffic
                # (SparseMeta.reverse_bytes).
                req_digest = visible[
                    jnp.arange(nl * k, dtype=jnp.int32) // k]
                req_digest = jnp.where(valid[:, None], req_digest,
                                       jnp.uint32(0))
                D = req_digest.reshape(cap, p, w)             # [cap, p, W]
                send_d = jnp.take(jnp.transpose(D, (1, 0, 2)),
                                  cols_for_dst, axis=0)       # [p, cap, W]
                recv_d = jax.lax.all_to_all(send_d, axis_name, 0, 0,
                                            tiled=False)
                pulled = pulled | _scatter_merge_digests(
                    ok, recv, recv_d, nl, proto.rumors, w)
            return pulled, jnp.sum(valid).astype(jnp.float32), lost

        if proto.mode == C.ANTI_ENTROPY and proto.period > 1:
            on = (round_ % proto.period) == 0
            # the quiescent branch's constants must carry the same
            # varying-manual-axes type as the exchange outputs
            zf = jax.lax.pcast(jnp.float32(0.0), (axis_name,), to="varying")
            quiet = (jnp.zeros_like(seen_l), zf, zf)
            pulled, n_req, lost_r = jax.lax.cond(on, exchange,
                                                 lambda _: quiet, None)
        else:
            pulled, n_req, lost_r = exchange(None)
        mfac = 3.0 if proto.mode == C.ANTI_ENTROPY else 2.0
        pulled = jnp.where(alive_l[:, None], pulled, jnp.uint32(0))
        msgs_new = msgs + jax.lax.psum(mfac * n_req, axis_name)
        if ch is not None:
            return (seen_l | pulled, msgs_new,
                    jax.lax.psum(lost_r, axis_name))
        return seen_l | pulled, msgs_new

    sh, sh2, rep = P(axis_name), P(axis_name, None), P()
    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    in_specs = (sh2, rep, rep, rep, sh)
    tables = ()
    if ch is not None:
        in_specs += (rep,) * NE.N_SCHED_OPERANDS
        tables = NE.sched_args(NE.build(fault, n, n_pad))
    mapped = shard_map(local_round, mesh=mesh,
                           in_specs=in_specs,
                           out_specs=out_specs)

    def step_tabled(state: SimState, *tbl):
        out = mapped(state.seen, state.round, state.base_key,
                     state.msgs, alive_pad, *tbl)
        new = SimState(seen=out[0], round=state.round + 1,
                       base_key=state.base_key, msgs=out[1])
        # churn path returns (state, lost) — the models/si.py contract
        return (new, out[2]) if ch is not None else new

    if tabled:
        return step_tabled, tables

    def step(state: SimState):
        return step_tabled(state, *tables)

    return step


def sparse_pull_round_reference(
        proto: ProtocolConfig, n: int, p: int,
        fault: Optional[FaultConfig] = None,
        origin: int = 0, tabled: bool = False):
    """Single-device twin of :func:`make_sparse_pull_round` — identical
    trajectory for the same stratification parameter ``p`` (the parity
    oracle; collectives only move data).  ``tabled=True`` returns the
    ``(step, schedule-operand-tables)`` pair like the mesh kernel."""
    k = proto.fanout
    n_pad = math.ceil(n / p) * p
    nl = _validate(n_pad, p, k)
    drop_prob = 0.0 if fault is None else fault.drop_prob
    alive_pad = sharded_alive(fault, n, n_pad, origin)
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)
    tables = (() if ch is None
              else NE.sched_args(NE.build(fault, n, n_pad)))

    def step_tabled(state: SimState, *tbl):
        _, sched = NE.split_tables(ch, tbl)
        seen, round_ = state.seen, state.round
        rkey = jax.random.fold_in(state.base_key, round_)
        pi, o = _round_draws(rkey, p)

        slot_gids = jnp.arange(n_pad * k, dtype=jnp.int32)
        local_slot = slot_gids % (nl * k)
        group = (local_slot + o) % p
        partner_shard = jnp.take(pi, group)
        rows = _slot_rows(rkey, slot_gids, nl)
        gids = partner_shard * nl + rows
        if ch is not None:
            alive_now = NE.alive_rows(sched, alive_pad, round_)
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
            valid = _slot_valid(rkey, slot_gids, dp, alive_now, k,
                                force=True)
            valid = valid & NE.same_side(cut, slot_gids // k, gids)
            lost = jnp.sum(jnp.repeat(alive_now, k) & ~valid,
                           dtype=jnp.float32)
        else:
            alive_now = alive_pad
            valid = _slot_valid(rkey, slot_gids, drop_prob, alive_pad, k)
            lost = jnp.float32(0.0)

        visible = jnp.where(alive_now[:, None], seen, jnp.uint32(0))
        got = visible[gids]                                   # [n_pad*k, W]
        got = jnp.where(valid[:, None], got, jnp.uint32(0))
        pulled = _or_reduce_k(got, n_pad, k)

        n_req = jnp.sum(valid).astype(jnp.float32)
        back = None
        if proto.mode == C.ANTI_ENTROPY:
            # reverse delta: the requester's digest merges into the partner
            # (single-device twin of the mesh kernel's piggybacked digest)
            req_digest = visible[slot_gids // k]              # [n_pad*k, W]
            req_digest = jnp.where(valid[:, None], req_digest,
                                   jnp.uint32(0))
            tgt = jnp.where(valid, gids, n_pad)
            cnt = jnp.zeros((n_pad, proto.rumors), jnp.int32
                            ).at[tgt].add(
                unpack(req_digest, proto.rumors).astype(jnp.int32),
                mode="drop")
            back = pack(cnt > 0)
        if proto.mode == C.ANTI_ENTROPY and proto.period > 1:
            on = (round_ % proto.period) == 0
            pulled = jnp.where(on, pulled, jnp.uint32(0))
            back = jnp.where(on, back, jnp.uint32(0))
            n_req = jnp.where(on, n_req, 0.0)
        if proto.period > 1 and proto.mode == C.ANTI_ENTROPY:
            # quiescent rounds send nothing, so nothing is lost (the
            # mesh kernel cond-skips the whole exchange)
            lost = jnp.where((round_ % proto.period) == 0, lost, 0.0)
        if back is not None:
            pulled = pulled | back
        mfac = 3.0 if proto.mode == C.ANTI_ENTROPY else 2.0
        pulled = jnp.where(alive_now[:, None], pulled, jnp.uint32(0))
        new = SimState(seen=seen | pulled, round=round_ + 1,
                       base_key=state.base_key,
                       msgs=state.msgs + mfac * n_req)
        return (new, lost) if ch is not None else new

    if tabled:
        return step_tabled, tables

    def step(state: SimState):
        return step_tabled(state, *tables)

    return step


def init_sparse_state(run: RunConfig, proto: ProtocolConfig, n: int,
                      mesh: Optional[Mesh] = None,
                      axis_name: str = "nodes",
                      p: Optional[int] = None) -> SimState:
    """Packed state padded to the mesh — or, for the single-device parity
    reference, to ``p`` stratification shards — origin rumors seeded as in
    models/state.init_state."""
    from gossip_tpu.models.si_packed import init_packed_state
    if mesh is not None:
        p = mesh.shape[axis_name]
    elif p is None:
        p = 1
    st = init_packed_state(run, proto, n)
    n_pad = math.ceil(n / p) * p
    seen = _pad_rows(st.seen, n_pad, jnp.uint32(0))
    if mesh is not None:
        seen = jax.device_put(seen,
                              NamedSharding(mesh, P(axis_name, None)))
    return SimState(seen=seen, round=st.round, base_key=st.base_key,
                    msgs=st.msgs)


# ---------------------------------------------------------------------------
# Explicit-topology sparse exchange (VERDICT r2 item 5)
#
# The complete-graph kernel above stratifies the partner draw BY
# CONSTRUCTION (round-robin groups -> permuted shards), which is only
# possible because every row is a legal partner.  With an explicit
# neighbor table the partner of a slot is dictated by the graph
# (``nbrs[i, j]`` for a uniform j < deg[i] — the batched analog of the
# reference's per-neighbor RPC, /root/reference/main.go:81), so
# per-(src,dst) counts are data-dependent.  Static shapes come instead
# from CAPACITY-CAPPED buckets: each shard packs its requests into a
# ``[P, cap]`` buffer by destination shard (owner of the partner row
# under the equal row-block partition), in local slot order.  The
# bucket rank is deterministic, so the rare slot that overflows its
# bucket (cap defaults to the TABLE-DERIVED expected max load plus a
# 4-sigma tail — auto_topo_cap) is DROPPED deterministically —
# reproduced bit-for-bit by the single-device reference twin, counted
# per round, and reported as the ``overflow`` output.  An overflowing
# slot is a lost pull request for that round only — at-least-once
# delivery comes from re-sampling every round, exactly like a dropped
# link in FaultConfig.drop_prob.
#
# Traffic: per device per round ``P*cap*(4 + 4W)`` bytes vs the dense
# packed all_gather's ``n_pad*4W`` (parallel/sharded_packed.py).  On
# shard-uniform graphs (ER, shuffled power-law) cap ~ nl*k/P and the
# drop is ~P*4W/(k*(4+4W)) — ~3.6x at P=8, W=1, k=1, linear in mesh
# size and rumor words.  On banded graphs (WS rings) cap honestly grows
# toward nl*k and the meta shows no win — halo exchange territory.


def auto_topo_cap(nbrs, deg, nl: int, k: int, p: int,
                  slack_sigma: float = 4.0, floor: int = 4) -> int:
    """Static per-(src,dst) bucket capacity derived FROM THE TABLE.

    The expected request load on bucket (s, d) is fixed by the graph:
    ``E[s,d] = k * sum_{rows i in s} |nbrs(i) in d| / deg(i)``.  A
    uniform balanced-load cap (2*nl*k/p) is catastrophically wrong for
    banded graphs — on a Watts-Strogatz ring ~80% of every shard's
    requests target the shard's OWN row block, overflowing a uniform
    bucket ~4x over.  Instead the cap is ``max_{s,d} E + slack_sigma *
    sqrt(maxE) + floor`` (the load is a sum of independent per-slot
    Bernoulli draws, so sqrt(E) bounds its std): overflow stays rare on
    ANY topology, and a banded graph honestly drives cap toward the slot
    count ``nl*k`` — where SparseMeta reports no byte win over dense and
    the halo exchange (parallel/halo.py) is the right tool instead.

    ``nbrs``/``deg`` are the REAL (unpadded) host rows — padding rows
    have degree 0 and contribute no load.  One O(N*D) numpy pass at
    build time; no device round-trip of a padded copy."""
    import numpy as np
    nbrs = np.asarray(nbrs)
    deg = np.asarray(deg)
    n_rows, d_max = nbrs.shape
    src = np.repeat(np.arange(n_rows) // nl, d_max)
    valid = np.arange(d_max)[None, :] < deg[:, None]
    dst = np.where(valid, nbrs // nl, 0).reshape(-1)
    wts = np.where(valid, k / np.maximum(deg, 1)[:, None], 0.0).reshape(-1)
    E = np.zeros((p, p))
    np.add.at(E, (src, dst), wts)
    max_e = float(E.max())
    cap = math.ceil(max_e + slack_sigma * math.sqrt(max(max_e, 1.0))
                    + floor)
    return min(nl * k, max(1, cap))


def resolve_topo_cap(topo, p: int, k: int,
                     cap: Optional[int] = None) -> int:
    """The capacity actually used by the topo-sparse kernels: an explicit
    ``cap`` wins; otherwise :func:`auto_topo_cap` on the raw table."""
    if cap is not None:
        return cap
    n_pad = math.ceil(topo.n / p) * p
    return auto_topo_cap(topo.nbrs, topo.deg, n_pad // p, k, p)


def sparse_topo_meta(n_pad: int, p: int, k: int, w: int, cap: int,
                     bidirectional: bool = False) -> SparseMeta:
    """Traffic accounting for the explicit-topology sparse pull (dense
    equivalent: the packed all_gather of parallel/sharded_packed.py).
    ``bidirectional``: anti-entropy's piggybacked requester digest, one
    extra [p, cap, W] all_to_all on exchange rounds."""
    return SparseMeta(p=p, cap=cap,
                      request_bytes=p * cap * 4,
                      response_bytes=p * cap * 4 * w,
                      dense_bytes=n_pad * 4 * w,
                      reverse_bytes=p * cap * 4 * w if bidirectional else 0)


def _slot_nbr_choice(rkey: jax.Array, slot_gids: jax.Array,
                     deg_slot: jax.Array) -> jax.Array:
    """Uniform neighbor INDEX j in [0, deg) per slot, keyed by global
    slot id (mesh-shape invariant).  deg==0 yields j=0; such slots are
    masked invalid by the caller."""
    base = jax.random.fold_in(rkey, TOPO_NBR_TAG)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base, slot_gids)
    u = jax.vmap(lambda kk: jax.random.uniform(kk))(keys)
    return jnp.minimum((u * deg_slot).astype(jnp.int32),
                       jnp.maximum(deg_slot - 1, 0))


def _bucket_rank(dst_eff: jax.Array, p: int) -> jax.Array:
    """Rank of each slot within its destination bucket, in slot order.
    ``dst_eff == p`` marks an invalid slot (consumes no capacity)."""
    occ = dst_eff[:, None] == jnp.arange(p, dtype=jnp.int32)    # [S, p]
    pos = jnp.cumsum(occ.astype(jnp.int32), axis=0) - 1
    return jnp.take_along_axis(
        pos, jnp.clip(dst_eff, 0, p - 1)[:, None], axis=1)[:, 0]


def make_sparse_topo_pull_round(
        proto: ProtocolConfig, topo, mesh: Mesh,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        axis_name: str = "nodes", cap: Optional[int] = None,
        tabled: bool = False):
    """Sharded packed pull / anti-entropy round over an EXPLICIT
    topology with capacity-capped all_to_all request/response exchange
    (see the block comment above).  State is rumor-packed
    ``uint32[n_pad, W]``.

    Anti-entropy piggybacks the requester's digest on the request (one
    extra [p, cap, W] all_to_all, SparseMeta.reverse_bytes) and the
    responder scatter-merges it — the capacity cap bounds the reverse
    side for free, since an overflow-dropped request carries no digest
    either.  ``period > 1`` cond-skips the reverse collective and masks
    the forward merge on quiescent rounds (complete-graph twin,
    :func:`make_sparse_pull_round`).

    Returns ``step(state, overflow, nbrs, deg) -> (state, overflow)``
    plus the padded tables when ``tabled=True`` (the overflow operand is
    a replicated float32 running count of capacity-dropped requests).
    """
    from gossip_tpu.models.state import SimState as _SimState
    if proto.mode not in (C.PULL, C.ANTI_ENTROPY):
        raise ValueError("sparse topology exchange covers pull and "
                         f"anti-entropy (got mode {proto.mode!r}); push/"
                         "flood ride the dense kernels")
    if topo.implicit:
        raise ValueError("implicit complete topology routes to "
                         "make_sparse_pull_round (stratified draw)")
    from gossip_tpu.ops import nemesis as NE
    NE.check_supported(fault, engine="topo-sparse", events=False,
                       partitions=False, ramp=False)
    p = mesh.shape[axis_name]
    k = proto.fanout
    n = topo.n
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // p
    S = nl * k
    w = n_words(proto.rumors)
    cap = resolve_topo_cap(topo, p, k, cap)
    drop_prob = 0.0 if fault is None else fault.drop_prob
    nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)     # sentinel n; deg 0 rows
    deg_pad = _pad_rows(topo.deg, n_pad, 0)

    def local_round(seen_l, round_, base_key, msgs, ovf, nbrs_l, deg_l):
        shard = jax.lax.axis_index(axis_name)
        rkey = jax.random.fold_in(base_key, round_)
        alive_l = local_rows(sharded_alive(fault, n, n_pad, origin),
                             axis_name, nl)
        visible = jnp.where(alive_l[:, None], seen_l, jnp.uint32(0))

        def exchange(_):
            """The whole round's sampling + collectives.  period > 1
            cond-skips this ENTIRELY on quiescent rounds — no forward
            bytes move either (draws are keyed by (round, slot id), so
            skipped rounds never perturb later ones; the reference twin
            computes-and-zeroes to the identical state)."""
            slot_gids = shard * S + jnp.arange(S, dtype=jnp.int32)
            deg_slot = jnp.repeat(deg_l, k)
            j = _slot_nbr_choice(rkey, slot_gids, deg_slot)
            row_of_slot = jnp.arange(S, dtype=jnp.int32) // k
            gid = nbrs_l[row_of_slot, j]                      # [S] global
            valid = (_slot_valid(rkey, slot_gids, drop_prob, alive_l, k)
                     & (deg_slot > 0))
            dst_eff = jnp.where(valid, gid // nl, jnp.int32(p))
            pos = _bucket_rank(dst_eff, p)
            sent = valid & (pos < cap)

            # out-of-range (dst_eff == p: invalid; pos >= cap: overflow)
            # indices are dropped by the scatter, leaving the -1 sentinel
            send_rows = jnp.full((p, cap), -1, jnp.int32
                                 ).at[dst_eff, pos].set(gid % nl,
                                                        mode="drop")
            recv = jax.lax.all_to_all(send_rows, axis_name, 0, 0,
                                      tiled=False)
            ok = recv >= 0
            resp = visible[jnp.clip(recv, 0, nl - 1)]         # [p, cap, W]
            resp = jnp.where(ok[:, :, None], resp, jnp.uint32(0))
            back = jax.lax.all_to_all(resp, axis_name, 0, 0, tiled=False)

            got = back[jnp.clip(dst_eff, 0, p - 1),
                       jnp.clip(pos, 0, cap - 1)]             # [S, W]
            got = jnp.where(sent[:, None], got, jnp.uint32(0))
            pulled = _or_reduce_k(got, nl, k)

            if proto.mode == C.ANTI_ENTROPY:
                # requester digest rides WITH the request in the same
                # (dst, pos) bucket slot; the responder scatter-merges
                # into the requested rows (complete-graph twin layout)
                req_digest = visible[row_of_slot]             # [S, W]
                req_digest = jnp.where(sent[:, None], req_digest,
                                       jnp.uint32(0))
                send_d = jnp.zeros((p, cap, w), jnp.uint32
                                   ).at[dst_eff, pos].set(req_digest,
                                                          mode="drop")
                recv_d = jax.lax.all_to_all(send_d, axis_name, 0, 0,
                                            tiled=False)
                pulled = pulled | _scatter_merge_digests(
                    ok, recv, recv_d, nl, proto.rumors, w)
            return (pulled,
                    jnp.sum(sent).astype(jnp.float32),
                    jnp.sum(valid & ~sent).astype(jnp.float32))

        if proto.mode == C.ANTI_ENTROPY and proto.period > 1:
            on = (round_ % proto.period) == 0
            # the quiescent branch's constants must carry the same
            # varying-manual-axes type as the exchange outputs
            zf = jax.lax.pcast(jnp.float32(0.0), (axis_name,), to="varying")
            quiet = (jnp.zeros_like(seen_l), zf, zf)
            pulled, n_sent, n_over = jax.lax.cond(on, exchange,
                                                  lambda _: quiet, None)
        else:
            pulled, n_sent, n_over = exchange(None)
        mfac = 3.0 if proto.mode == C.ANTI_ENTROPY else 2.0
        pulled = jnp.where(alive_l[:, None], pulled, jnp.uint32(0))
        msgs_new = msgs + jax.lax.psum(mfac * n_sent, axis_name)
        ovf_new = ovf + jax.lax.psum(n_over, axis_name)
        return seen_l | pulled, msgs_new, ovf_new

    sh, sh2, rep = P(axis_name), P(axis_name, None), P()
    mapped = shard_map(local_round, mesh=mesh,
                           in_specs=(sh2, rep, rep, rep, rep, sh2, sh),
                           out_specs=(sh2, rep, rep))

    def step_tabled(state, overflow, nbrs, deg):
        seen, msgs, ovf = mapped(state.seen, state.round, state.base_key,
                                 state.msgs, overflow, nbrs, deg)
        return (_SimState(seen=seen, round=state.round + 1,
                          base_key=state.base_key, msgs=msgs), ovf)

    if tabled:
        return step_tabled, (nbrs_pad, deg_pad)

    def step(state, overflow):
        return step_tabled(state, overflow, nbrs_pad, deg_pad)

    return step


def sparse_topo_pull_round_reference(
        proto: ProtocolConfig, topo, p: int,
        fault: Optional[FaultConfig] = None, origin: int = 0,
        cap: Optional[int] = None):
    """Single-device twin of :func:`make_sparse_topo_pull_round` —
    identical trajectory INCLUDING the deterministic capacity drops
    (bucket ranks recomputed per source-shard block in the same slot
    order) and the anti-entropy reverse merge.  The parity oracle;
    collectives only move data."""
    if proto.mode not in (C.PULL, C.ANTI_ENTROPY):
        raise ValueError("sparse topology exchange covers pull and "
                         f"anti-entropy (got mode {proto.mode!r})")
    from gossip_tpu.ops import nemesis as NE
    NE.check_supported(fault, engine="topo-sparse", events=False,
                       partitions=False, ramp=False)
    k = proto.fanout
    n = topo.n
    n_pad = math.ceil(n / p) * p
    nl = n_pad // p
    S = nl * k
    cap = resolve_topo_cap(topo, p, k, cap)
    drop_prob = 0.0 if fault is None else fault.drop_prob
    nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
    deg_pad = _pad_rows(topo.deg, n_pad, 0)
    alive_pad = sharded_alive(fault, n, n_pad, origin)

    def step(state, overflow):
        seen, round_ = state.seen, state.round
        rkey = jax.random.fold_in(state.base_key, round_)
        slot_gids = jnp.arange(n_pad * k, dtype=jnp.int32)
        deg_slot = jnp.repeat(deg_pad, k)
        j = _slot_nbr_choice(rkey, slot_gids, deg_slot)
        row_of_slot = slot_gids // k
        gid = nbrs_pad[row_of_slot, j]
        valid = (_slot_valid(rkey, slot_gids, drop_prob, alive_pad, k)
                 & (deg_slot > 0))
        dst_eff = jnp.where(valid, gid // nl, jnp.int32(p))
        pos = jax.vmap(_bucket_rank, in_axes=(0, None))(
            dst_eff.reshape(p, S), p).reshape(-1)
        sent = valid & (pos < cap)

        visible = jnp.where(alive_pad[:, None], seen, jnp.uint32(0))
        got = visible[jnp.clip(gid, 0, n_pad - 1)]
        got = jnp.where(sent[:, None], got, jnp.uint32(0))
        pulled = _or_reduce_k(got, n_pad, k)

        n_sent = jnp.sum(sent).astype(jnp.float32)
        n_over = jnp.sum(valid & ~sent).astype(jnp.float32)
        if proto.mode == C.ANTI_ENTROPY:
            # reverse delta: the requester's digest merges into the
            # partner (mesh kernel's piggybacked digest)
            req_digest = visible[row_of_slot]
            req_digest = jnp.where(sent[:, None], req_digest,
                                   jnp.uint32(0))
            tgt = jnp.where(sent, gid, n_pad)
            cnt = jnp.zeros((n_pad, proto.rumors), jnp.int32
                            ).at[tgt].add(
                unpack(req_digest, proto.rumors).astype(jnp.int32),
                mode="drop")
            back = pack(cnt > 0)
            if proto.period > 1:
                on = (round_ % proto.period) == 0
                pulled = jnp.where(on, pulled, jnp.uint32(0))
                back = jnp.where(on, back, jnp.uint32(0))
                n_sent = jnp.where(on, n_sent, 0.0)
                n_over = jnp.where(on, n_over, 0.0)
            pulled = pulled | back
        mfac = 3.0 if proto.mode == C.ANTI_ENTROPY else 2.0
        pulled = jnp.where(alive_pad[:, None], pulled, jnp.uint32(0))

        from gossip_tpu.models.state import SimState as _SimState
        return (_SimState(seen=seen | pulled, round=round_ + 1,
                          base_key=state.base_key,
                          msgs=state.msgs + mfac * n_sent),
                overflow + n_over)

    return step


def _sparse_recorder(proto: ProtocolConfig, n_shards: int,
                     meta: SparseMeta):
    """In-loop metrics row for the sparse exchange drivers
    (ops/round_metrics).  ``bytes`` comes straight from the driver's own
    :class:`SparseMeta` traffic accounting — per device per EXCHANGE
    round — gated in-trace on quiescent anti-entropy rounds exactly as
    the kernels cond-skip the collectives (plus the 4-byte msgs
    psum, which moves every round).  The previous round's entry count
    rides the carry as one scalar (the parallel/sharded._dense_recorder
    liveness rationale)."""
    from gossip_tpu.ops import round_metrics as RM
    offered_per_msg = proto.rumors * RM.payload_factor(proto.mode)
    exchange_b = float(meta.sparse_bytes) + 4.0

    def rec(m, prev_count, round0, msgs0, s1, alive_pad, nem=None):
        count = RM.count_packed(s1.seen, alive_pad)
        newly = count - prev_count
        msgs = s1.msgs - msgs0
        b = jnp.float32(exchange_b)
        if proto.mode == C.ANTI_ENTROPY:
            b = RM.gate_on_exchange_rounds(exchange_b, proto.period,
                                           round0, off=4.0)
        kw = ({} if nem is None
              else dict(alive=nem[0], cut_pairs=nem[1], dropped=nem[2]))
        return RM.record(
            m, newly=newly, msgs=msgs,
            dup=RM.dup_estimate(offered_per_msg * msgs, newly),
            bytes=b,
            front=RM.front_packed(s1.seen, alive_pad, n_shards),
            **kw), count

    return rec


def simulate_curve_topo_sparse(proto: ProtocolConfig, topo, run: RunConfig,
                               mesh: Mesh,
                               fault: Optional[FaultConfig] = None,
                               axis_name: str = "nodes",
                               cap: Optional[int] = None, timing=None):
    """lax.scan over rounds on the explicit-topology sparse pull path.
    Returns (coverage[T], msgs[T], final, SparseMeta, overflow[T]).
    ``timing``: optional compile/steady AOT-split dict
    (parallel/sharded.simulate_curve_sharded contract).  With an active
    run ledger the scan carries a round-metrics buffer stack, flushed
    once by the chokepoint (ops/round_metrics)."""
    import numpy as np

    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    p = mesh.shape[axis_name]
    cap_used = resolve_topo_cap(topo, p, proto.fanout, cap)
    step, tables = make_sparse_topo_pull_round(proto, topo, mesh, fault,
                                               run.origin, axis_name,
                                               cap_used, tabled=True)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    init = init_sparse_state(run, proto, topo.n, mesh, axis_name)
    r = proto.rumors
    meta = sparse_topo_meta(n_pad, p, proto.fanout, n_words(proto.rumors),
                            cap_used,
                            bidirectional=proto.mode == C.ANTI_ENTROPY)
    rec = _sparse_recorder(proto, p, meta) if RM.wanted() else None

    @jax.jit
    def scan(state, *tbl):
        alive_pad = sharded_alive(fault, topo.n, n_pad, run.origin)
        m0 = (RM.init(run.max_rounds, p, "simulate_curve_topo_sparse")
              if rec else None)
        c0 = RM.count_packed(state.seen, alive_pad) if rec else None
        def body(carry, _):
            s0, ovf0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            s, ovf = step(s0, ovf0, *tbl)
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_pad)
            return ((s, ovf, m, cnt),
                    (coverage_packed(s.seen, r, alive_pad), s.msgs, ovf))
        return jax.lax.scan(body, (state, jnp.float32(0.0), m0, c0),
                            None, length=run.max_rounds)

    ((final, _, _, _),
     (covs, msgs, ovfs)) = maybe_aot_timed(scan, timing, init, *tables,
                                           label="sparse")
    return (np.asarray(covs), np.asarray(msgs), final, meta,
            np.asarray(ovfs))


def simulate_until_topo_sparse(proto: ProtocolConfig, topo, run: RunConfig,
                               mesh: Mesh,
                               fault: Optional[FaultConfig] = None,
                               axis_name: str = "nodes",
                               cap: Optional[int] = None, timing=None):
    """while_loop to target coverage on the explicit-topology sparse pull
    path.  Returns (rounds, coverage, msgs, final, SparseMeta, overflow).
    ``timing``: optional compile/steady AOT-split dict.  With an active
    run ledger the loop carries a round-metrics buffer stack
    (ops/round_metrics)."""
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    p = mesh.shape[axis_name]
    cap_used = resolve_topo_cap(topo, p, proto.fanout, cap)
    step, tables = make_sparse_topo_pull_round(proto, topo, mesh, fault,
                                               run.origin, axis_name,
                                               cap_used, tabled=True)
    n_pad = pad_to_mesh(topo.n, mesh, axis_name)
    alive_pad = sharded_alive(fault, topo.n, n_pad, run.origin)
    init = init_sparse_state(run, proto, topo.n, mesh, axis_name)
    target = jnp.float32(run.target_coverage)
    r = proto.rumors
    meta = sparse_topo_meta(n_pad, p, proto.fanout, n_words(proto.rumors),
                            cap_used,
                            bidirectional=proto.mode == C.ANTI_ENTROPY)
    rec = _sparse_recorder(proto, p, meta) if RM.wanted() else None

    @jax.jit
    def loop(state, *tbl):
        # liveness in-trace: no O(N) closed-over constant in the compile
        # request (bind_tables doc)
        alive_t = sharded_alive(fault, topo.n, n_pad, run.origin)
        m0 = (RM.init(run.max_rounds, p, "simulate_until_topo_sparse")
              if rec else None)
        c0 = RM.count_packed(state.seen, alive_t) if rec else None
        def cond(carry):
            s, _, _, _ = carry
            return ((coverage_packed(s.seen, r, alive_t) < target)
                    & (s.round < run.max_rounds))
        def body(carry):
            s0, ovf0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            s, ovf = step(s0, ovf0, *tbl)
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_t)
            return s, ovf, m, cnt
        return jax.lax.while_loop(cond, body,
                                  (state, jnp.float32(0.0), m0, c0))

    final, ovf, _, _ = maybe_aot_timed(loop, timing, init, *tables,
                                       label="sparse")
    return (int(final.round),
            float(coverage_packed(final.seen, r, alive_pad)),
            float(final.msgs), final, meta, float(ovf))


def simulate_curve_sparse(proto: ProtocolConfig, n: int, run: RunConfig,
                          mesh: Mesh, fault: Optional[FaultConfig] = None,
                          axis_name: str = "nodes", timing=None):
    """lax.scan over rounds recording (coverage, msgs) on the sparse
    exchange path.  Returns (coverage[T], msgs[T], final, SparseMeta).
    ``timing``: optional compile/steady AOT-split dict.  With an active
    run ledger the scan carries a round-metrics buffer stack
    (ops/round_metrics)."""
    import numpy as np

    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.parallel.sharded import _churn_observables
    step, tables = make_sparse_pull_round(proto, n, mesh, fault,
                                          run.origin, axis_name,
                                          tabled=True)
    p = mesh.shape[axis_name]
    n_pad = pad_to_mesh(n, mesh, axis_name)
    init = init_sparse_state(run, proto, n, mesh, axis_name)
    r = proto.rumors
    meta = sparse_meta(n_pad, p, proto.fanout, n_words(proto.rumors),
                       bidirectional=proto.mode == C.ANTI_ENTROPY)
    rec = _sparse_recorder(proto, p, meta) if RM.wanted() else None
    ch = NE.get(fault)
    obs = _churn_observables(fault, n, n_pad, run.origin)

    @jax.jit
    def scan(state, *tbl):
        alive_pad = (NE.eventual_alive_pad(fault, n, n_pad, run.origin)
                     if ch is not None
                     else sharded_alive(fault, n, n_pad, run.origin))
        m0 = (RM.init(run.max_rounds, p, "simulate_curve_sparse",
                      nemesis=ch is not None) if rec else None)
        c0 = RM.count_packed(state.seen, alive_pad) if rec else None
        def body(carry, _):
            s0, m, cnt = carry
            round0, msgs0 = s0.round, s0.msgs
            if ch is not None:
                s, lost = step(s0, *tbl)
            else:
                s, lost = step(s0, *tbl), None
            if m is not None:
                m, cnt = rec(m, cnt, round0, msgs0, s, alive_pad,
                             nem=(obs(round0, lost,
                                      NE.sched_of_tables(tbl))
                                  if obs else None))
            return (s, m, cnt), (coverage_packed(s.seen, r, alive_pad),
                                 s.msgs)
        return jax.lax.scan(body, (state, m0, c0), None,
                            length=run.max_rounds)

    (final, _, _), (covs, msgs) = maybe_aot_timed(scan, timing, init,
                                                  *tables, label="sparse")
    return np.asarray(covs), np.asarray(msgs), final, meta


def simulate_until_sparse(proto: ProtocolConfig, n: int, run: RunConfig,
                          mesh: Mesh, fault: Optional[FaultConfig] = None,
                          axis_name: str = "nodes", timing=None):
    """while_loop to target coverage on the sparse exchange path.
    Returns (rounds, coverage, msgs, final_state, SparseMeta).
    ``timing``: optional compile/steady AOT-split dict.  With an active
    run ledger the loop carries a round-metrics buffer stack
    (ops/round_metrics)."""
    from gossip_tpu.ops import round_metrics as RM
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.parallel.sharded import _churn_observables
    step, tables = make_sparse_pull_round(proto, n, mesh, fault,
                                          run.origin, axis_name,
                                          tabled=True)
    p = mesh.shape[axis_name]
    n_pad = pad_to_mesh(n, mesh, axis_name)
    ch = NE.get(fault)
    alive_pad = (NE.eventual_alive_pad(fault, n, n_pad, run.origin)
                 if ch is not None
                 else sharded_alive(fault, n, n_pad, run.origin))
    init = init_sparse_state(run, proto, n, mesh, axis_name)
    target = jnp.float32(run.target_coverage)
    r = proto.rumors
    meta = sparse_meta(n_pad, p, proto.fanout, n_words(proto.rumors),
                       bidirectional=proto.mode == C.ANTI_ENTROPY)
    rec = _sparse_recorder(proto, p, meta) if RM.wanted() else None
    obs = _churn_observables(fault, n, n_pad, run.origin)

    @jax.jit
    def loop(state, *tbl):
        # liveness in-trace: no O(N) closed-over constant (bind_tables
        # doc) — same hardening as simulate_until_topo_sparse
        alive_t = (NE.eventual_alive_pad(fault, n, n_pad, run.origin)
                   if ch is not None
                   else sharded_alive(fault, n, n_pad, run.origin))
        m0 = (RM.init(run.max_rounds, p, "simulate_until_sparse",
                      nemesis=ch is not None) if rec else None)
        c0 = RM.count_packed(state.seen, alive_t) if rec else None
        def cond(carry):
            s, _, _ = carry
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

    final, _, _ = maybe_aot_timed(loop, timing, init, *tables, label="sparse")
    return (int(final.round),
            float(coverage_packed(final.seen, r, alive_pad)),
            float(final.msgs), final, meta)
