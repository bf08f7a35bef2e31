"""CRDT merge kernels: commutative-merge payloads on the gossip fabric.

PAPER.md's reference solves exactly one Maelstrom / Gossip Glomers
workload — broadcast with a dedup set — but the sibling challenges
(grow-only / PN counters, OR-sets) are the *same* epidemic exchange
with a different payload: instead of an infected bit that merges by
OR, each node carries a state whose merge is commutative, associative,
and idempotent (a join-semilattice), so gossip order, duplication, and
loss never corrupt the value — partitions are exactly what CRDTs exist
for (Shapiro et al., "Conflict-free Replicated Data Types", SSS 2011).

Array forms (one row per node, the ``seen[N, R]`` convention):

  * **G-Counter / PN-Counter** — per-node counter shards
    ``int32[N, S]``: column ``j`` is node-``owner(j)``'s contribution;
    only the owner increments its own column, everyone else learns it
    by gossip, and merge is **elementwise max per shard column** (the
    owner's value is monotone, so max is exact).  ``gcounter``: S = n,
    owner(j) = j; ``pncounter``: S = 2n — columns 0..n-1 are the P
    (increment) plane, n..2n-1 the N (decrement) plane, both grow-only,
    value = sum(P) - sum(N).
  * **G-Set / OR-Set** — packed bit-planes ``uint32[N, 2W]``
    (ops/bitpack layout, 32 elements per word): columns 0..W-1 are the
    add plane, W..2W-1 the tombstone plane, merge is **bitwise OR** on
    both, membership = add & ~tombstone.  This is the array form of an
    OR-set where each element carries one unique add tag per run
    (CrdtConfig enforces at-most-one scripted add per element), so
    add-wins and 2P semantics coincide — documented in
    docs/WORKLOADS.md.
  * **Vector clocks** — ``int32[N, n]``: node i's causal clock; the
    owner ticks its own entry per local event, merge is elementwise
    max (the classic vector-clock join).

Injections are runtime OPERANDS, like the nemesis schedule tables
(ops/nemesis module doc): :func:`inject_args` lowers a CrdtConfig to a
tiny tuple of padded arrays the step factories append to their
``tables`` tuple, so the compiled loops carry injection SHAPES but no
CONTENT — two add programs of the same padded arity re-enter one
executable.

Ground truth and the value-convergence metric
---------------------------------------------
An injection is **applied** iff its owner is alive at the injection
round AND eventually alive under the fault program — the batched
analog of the Maelstrom counter checker counting only ACKED adds: a
node destined for permanent death contributes nothing, which is what
makes exact convergence on the eventual-alive set a guaranteed
invariant (every applied contribution's owner eventually recovers and
re-disseminates its full shard).  :func:`ground_truth` computes the
merged truth row from the same operands IN-TRACE (integer-exact — no
float readout anywhere), and :func:`converged_count` counts alive
nodes whose full state row equals it bitwise.  The drivers divide the
integer count by the eventual-alive total ONCE on the host
(value_conv), the repo's bitwise-curve convention.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from gossip_tpu.config import (CRDT_COUNTER_KINDS, CRDT_SET_KINDS,
                               GCOUNTER, GSET, ORSET, PNCOUNTER, PULL,
                               VCLOCK, CrdtConfig, FaultConfig)
from gossip_tpu.ops.bitpack import n_words, pack
from gossip_tpu.ops.payload import PayloadRow

# How many trailing step arguments an injection program occupies on a
# factory's ``tables`` tuple (inject_args / split_inject): counters
# lower to (col, round, amount), sets to (add_elem, add_round,
# rem_elem, rem_round).  Vector clocks inject nothing (self-tick).
N_INJECT_OPERANDS = {GCOUNTER: 3, PNCOUNTER: 3, GSET: 4, ORSET: 4,
                     VCLOCK: 0}

# Minimum padded injection-list length: like the nemesis tables'
# SCHED_T_MIN, a power-of-two bucket makes same-bucket programs
# shape-identical so they share one compiled loop.
INJECT_A_MIN = 8

# Sentinel round for "no injection" on padding rows — far beyond any
# real round (the ChurnConfig horizon cap is 100k), so the in-loop
# ``round == r`` compare never fires for them.
NO_ROUND = 1 << 29


def shard_columns(kind: str, n: int) -> int:
    """S: the state's column count for ``n`` nodes (module doc)."""
    if kind == GCOUNTER or kind == VCLOCK:
        return n
    if kind == PNCOUNTER:
        return 2 * n
    raise ValueError(f"{kind!r} is not a counter-shard kind")


def set_words(cfg: CrdtConfig) -> int:
    """2W: the packed set state's word count (add + tombstone planes)."""
    return 2 * n_words(cfg.elements)


def state_width(cfg: CrdtConfig, n: int) -> int:
    """Columns of the ``val`` row for this config (counter shards or
    packed set words)."""
    if cfg.kind in CRDT_SET_KINDS:
        return set_words(cfg)
    return shard_columns(cfg.kind, n)


def state_dtype(cfg: CrdtConfig):
    return jnp.uint32 if cfg.kind in CRDT_SET_KINDS else jnp.int32


# -- merge kernels (the join-semilattice operations) -------------------

def merge_max(a: jax.Array, b: jax.Array) -> jax.Array:
    """Counter-shard / vector-clock join: elementwise max.  Exact
    because each column is written only by its monotone owner."""
    return jnp.maximum(a, b)


def merge_or(a: jax.Array, b: jax.Array) -> jax.Array:
    """Packed-set join: bitwise OR on the add + tombstone planes."""
    return a | b


def merge(kind: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """The ONE kind dispatcher — every exchange path and every
    algebraic pin goes through it, so a driver can never ship a merge
    the tests did not pin."""
    if kind in CRDT_SET_KINDS:
        return merge_or(a, b)
    return merge_max(a, b)


def pull_merge_crdt(kind: str, rows_all: jax.Array, partners: jax.Array,
                    sentinel: int) -> jax.Array:
    """Merge of k sampled peers' state rows -> ``[N_local, S]``.

    The CRDT twin of ops/propagate.pull_merge / si_packed
    .pull_merge_packed: gather k rows, mask invalid partners to the
    merge identity (0 — the identity of both OR and max-on-nonnegative,
    which the counter planes are by construction), reduce with
    :func:`merge`.  One uint32/int32 gather moves 32 set elements or
    one counter shard per lane.
    """
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = rows_all[safe]                              # [Nl, k, S]
    got = jnp.where(valid[:, :, None], got,
                    jnp.zeros((), rows_all.dtype))
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = merge(kind, out, got[:, j, :])
    return out


# -- byzantine exchange: liar transforms + array-form defenses ---------
#
# The byz half of the nemesis subsystem (ops/nemesis ByzSchedule;
# docs/ROBUSTNESS.md "Byzantine adversaries").  Both halves are
# RECEIVER-side renders of the gathered rows, all jnp.where on the byz
# tables, so the compiled loop carries liar SHAPES but never content:
#
#   * :func:`_byz_serve_counter` / :func:`_byz_serve_set` transform the
#     row an ACTIVE liar partner serves — corrupt (xor), replay (the
#     genesis snapshot: all zeros, maximal staleness), equivocate (a
#     receiver-id-keyed pattern), inflate (raise columns / set bits it
#     does not own).  Every transform touches only NON-OWN components:
#     a liar's own column/element is its own to write (the standard
#     BFT limitation — an own-component lie is indistinguishable from
#     a legitimate write), which is exactly what makes the defended
#     admission below provably reject ALL dishonest content.
#   * with ``defend=True`` the admission is a one-line lattice check
#     per payload: counters admit only the partner's OWN column (the
#     owner-column write guard; the max join is itself the per-column
#     monotonicity clamp), packed sets admit a bit served directly by
#     its owner OR echoed by >= quorum distinct partners this round
#     (the quorum scalar is a traced operand).  Defended exchanges
#     propagate owner-direct (slower — coupon-collector rounds — but
#     EXACT on honest-owned components under any f liars scripted
#     here; quorum additionally tolerates f < q non-colluding forgers
#     on the broadcast planes).

def set_owner_words(elements: int, n: int, origin: int) -> jax.Array:
    """uint32[n, 2W]: the packed element bits node i OWNS (element e's
    owner is ``(origin + e) % n`` — the inject_rows convention), tiled
    over both planes (an add bit and its tombstone share an owner).
    Content-static (iota + pack), shared by the liar transforms, the
    defended admission, and the honest-component convergence mask so
    the three can never disagree on ownership."""
    owners = (origin + jnp.arange(elements, dtype=jnp.int32)) % n
    own = owners[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None]
    w = pack(own)                                      # [n, W]
    return jnp.concatenate([w, w], axis=1)             # [n, 2W]


def _set_universe(elements: int, words2: int) -> jax.Array:
    """uint32[2W]: the element-universe bits of both planes — keeps
    every wire transform off the padding bits past ``elements`` (the
    ops/bitpack never-set contract; a forged padding bit would leak
    into popcount observability)."""
    ones = jnp.ones((1, elements), jnp.bool_)
    w = pack(ones)[0]
    return jnp.concatenate([w, w])[:words2]


def _byz_serve_counter(got, safe, active, gids, byz, n: int):
    """Render what liar partners SERVE (counter shards [Nl, k, S]) —
    module comment catalog; non-own columns only."""
    from gossip_tpu.ops import nemesis as NE
    kindp = byz.kind[safe][:, :, None]                 # [Nl, k, 1]
    argp = byz.arg[safe][:, :, None]
    s = got.shape[-1]
    col_owner = jnp.arange(s, dtype=jnp.int32) % n
    nonown = col_owner[None, None, :] != safe[:, :, None]
    corrupt = jnp.where(nonown, got ^ argp, got)
    inflate = jnp.where(nonown, got + argp, got)
    equiv = jnp.where(nonown,
                      got + argp * (1 + gids[:, None, None]), got)
    out = jnp.where(kindp == NE.BYZ_CODES["corrupt"], corrupt, got)
    out = jnp.where(kindp == NE.BYZ_CODES["replay"],
                    jnp.zeros_like(got), out)
    out = jnp.where(kindp == NE.BYZ_CODES["equivocate"], equiv, out)
    out = jnp.where(kindp == NE.BYZ_CODES["inflate"], inflate, out)
    return jnp.where(active[:, :, None], out, got)


def _byz_serve_set(got, safe, active, gids, byz, own_words, universe):
    """Render what liar partners SERVE (packed set planes
    [Nl, k, 2W]) — non-own bits only, inside the element universe."""
    from gossip_tpu.ops import nemesis as NE
    kindp = byz.kind[safe][:, :, None]
    argp = byz.arg[safe].astype(jnp.uint32)[:, :, None]
    foreign = ~own_words[safe] & universe              # [Nl, k, 2W]
    corrupt = got ^ (argp & foreign)
    inflate = got | foreign
    epat = argp ^ (gids.astype(jnp.uint32)
                   * jnp.uint32(2654435761))[:, None, None]
    equiv = got ^ (epat & foreign)
    out = jnp.where(kindp == NE.BYZ_CODES["corrupt"], corrupt, got)
    out = jnp.where(kindp == NE.BYZ_CODES["replay"],
                    jnp.zeros_like(got), out)
    out = jnp.where(kindp == NE.BYZ_CODES["equivocate"], equiv, out)
    out = jnp.where(kindp == NE.BYZ_CODES["inflate"], inflate, out)
    return jnp.where(active[:, :, None], out, got)


def _unique_valid(safe, valid):
    """bool[Nl, k]: first occurrence of each distinct valid partner —
    the quorum dedupe (a partner sampled twice is ONE independent
    witness, never two)."""
    k = safe.shape[1]
    if k == 1:
        return valid
    eq = safe[:, :, None] == safe[:, None, :]          # [Nl, j, i]
    earlier = jnp.tril(jnp.ones((k, k), jnp.bool_), -1)[None]
    dup = jnp.any(eq & valid[:, None, :] & earlier, axis=2)
    return valid & ~dup


def pull_merge_crdt_byz(cfg: CrdtConfig, rows_all: jax.Array,
                        partners: jax.Array, sentinel: int, *,
                        byz, round_, gids: jax.Array, n: int,
                        origin: int, alive_fn, defend: bool
                        ) -> jax.Array:
    """:func:`pull_merge_crdt` under a byzantine program: gather, mask
    invalid partners to the merge identity, render what each ACTIVE
    liar partner serves (module comment), then either the honest merge
    (``defend=False`` — the control arm, provably divergent under
    forging liars: a forged value above truth sticks under max/OR
    forever) or the defended admission (owner-column guard for counter
    shards; owner-direct OR quorum-echo for packed set bits).  A
    churn-down liar serves nothing — its row is already zeroed by the
    visibility mask and ``alive_fn`` gates the transform too."""
    kind = cfg.kind
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = rows_all[safe]                               # [Nl, k, S]
    got = jnp.where(valid[:, :, None], got,
                    jnp.zeros((), rows_all.dtype))
    from gossip_tpu.ops import nemesis as NE
    active = (valid & NE.byz_active(byz, safe, round_)
              & alive_fn(safe, round_))
    if kind in CRDT_SET_KINDS:
        own_words = set_owner_words(cfg.elements, n, origin)
        universe = _set_universe(cfg.elements, rows_all.shape[-1])
        got = _byz_serve_set(got, safe, active, gids, byz, own_words,
                             universe)
        if not defend:
            out = got[:, 0, :]
            for j in range(1, got.shape[1]):
                out = merge_or(out, got[:, j, :])
            return out
        # defended: owner-direct bits, plus bits echoed by >= quorum
        # distinct partners (carry-save counting chain, depth 3 —
        # ByzConfig caps quorum at 3)
        uniq = _unique_valid(safe, valid)
        once = jnp.zeros_like(got[:, 0, :])
        twice = jnp.zeros_like(once)
        thrice = jnp.zeros_like(once)
        direct = jnp.zeros_like(once)
        for j in range(got.shape[1]):
            b = jnp.where(uniq[:, j, None], got[:, j, :],
                          jnp.zeros((), got.dtype))
            thrice = thrice | (twice & b)
            twice = twice | (once & b)
            once = once | b
            direct = direct | (got[:, j, :] & own_words[safe[:, j]])
        q = byz.quorum
        echoed = jnp.where(q <= 1, once,
                           jnp.where(q == 2, twice, thrice))
        return direct | echoed
    # counter shards / vector clocks
    got = _byz_serve_counter(got, safe, active, gids, byz, n)
    if defend:
        # owner-column write guard: from partner p admit only column
        # p's plane entries (pncounter: both its P and N columns fold
        # through col % n); max IS the monotonicity clamp
        s = got.shape[-1]
        col_owner = jnp.arange(s, dtype=jnp.int32) % n
        admit = ((col_owner[None, None, :] == safe[:, :, None])
                 & valid[:, :, None])
        got = jnp.where(admit, got, jnp.zeros((), got.dtype))
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = merge_max(out, got[:, j, :])
    return out


# -- honest-component convergence (the byz_conv metric) ----------------

def honest_component_mask(cfg: CrdtConfig, n: int, origin: int,
                          honest: jax.Array):
    """The honest-OWNED components of a state row: bool[S] column mask
    for counter shards, uint32[2W] bit mask for packed sets.  A
    liar-owned component is excluded from the ``byz_conv`` equality —
    a liar may withhold (replay) or self-write arbitrarily, both
    undetectable by construction, so honest convergence is only ever
    claimable on honest-owned state (docs/ROBUSTNESS.md)."""
    if cfg.kind in CRDT_SET_KINDS:
        owners = (origin + jnp.arange(cfg.elements,
                                      dtype=jnp.int32)) % n
        w = pack(honest[owners][None, :])[0]
        return jnp.concatenate([w, w])
    s = state_width(cfg, n)
    col_owner = jnp.arange(s, dtype=jnp.int32) % n
    return honest[col_owner]


def byz_converged_count(cfg: CrdtConfig, rows: jax.Array,
                        truth: jax.Array, alive_honest: jax.Array,
                        comp_mask) -> jax.Array:
    """int32 count of honest eventually-alive nodes whose HONEST-owned
    components equal the ground truth bitwise — the ``byz_conv``
    numerator (:func:`converged_count` restricted by
    :func:`honest_component_mask`; divide by the honest eventual-alive
    total once on the host, the bitwise-curve convention)."""
    if cfg.kind in CRDT_SET_KINDS:
        eq = jnp.all((rows & comp_mask[None, :])
                     == (truth & comp_mask)[None, :], axis=-1)
    else:
        eq = jnp.all(jnp.where(comp_mask[None, :],
                               rows == truth[None, :], True), axis=-1)
    return jnp.sum(eq & alive_honest, dtype=jnp.int32)


def byz_conv_frac(cfg: CrdtConfig, rows: jax.Array, truth: jax.Array,
                  alive_honest: jax.Array, comp_mask) -> jax.Array:
    """f32 in-trace byz_conv fraction — RoundMetrics column only (the
    value_conv_frac rule: pinned readouts use the integer count)."""
    c = byz_converged_count(cfg, rows, truth, alive_honest,
                            comp_mask).astype(jnp.float32)
    return c / jnp.maximum(jnp.sum(alive_honest, dtype=jnp.float32),
                           1.0)


# -- injection lowering (runtime operands, the nemesis pattern) --------

def _pad_pow2(length: int) -> int:
    return max(INJECT_A_MIN, 1 << max(0, (length - 1).bit_length()))


def counter_adds(cfg: CrdtConfig, n: int):
    """The effective add list ``[(node, round, amount), ...]`` —
    scripted, or the default program's closed form: node j adds
    ``1 + j % 7`` at round 0, pncounter alternating sign by parity
    (odd nodes decrement).  A formula, not a config table, so no O(N)
    config object is ever materialized (CrdtConfig doc); this is the
    ONE definition of the defaults, shared by the lowering and ground
    truth through :func:`inject_args`."""
    if cfg.adds:
        return list(cfg.adds)
    sign = -1 if cfg.kind == PNCOUNTER else 1
    return [(j, 0, int(1 + j % 7) * (sign if j % 2 else 1))
            for j in range(n)]


def inject_args(cfg: CrdtConfig, n: int) -> tuple:
    """Lower the injection program to its operand tuple (module doc):
    counters -> ``(col int32[A], round int32[A], amount int32[A])``
    with the pncounter N-plane offset already folded into ``col``;
    sets -> ``(add_elem, add_round, rem_elem, rem_round)`` (int32,
    padded with NO_ROUND sentinels).  Padded to a power-of-two bucket
    so same-arity programs are shape-identical."""
    kind = cfg.kind
    if kind == VCLOCK:
        return ()
    if kind in CRDT_COUNTER_KINDS:
        adds = counter_adds(cfg, n)
        bad = [a for a in adds if a[0] >= n]
        if bad:
            raise ValueError(f"counter adds reference node ids >= "
                             f"n={n}: {bad}")
        a_pad = _pad_pow2(len(adds))
        col = [(node if amt >= 0 else n + node)
               if kind == PNCOUNTER else node
               for node, _, amt in adds]
        col += [0] * (a_pad - len(adds))
        rnd = [r for _, r, _ in adds] + [NO_ROUND] * (a_pad - len(adds))
        amt = [abs(a) for _, _, a in adds] + [0] * (a_pad - len(adds))
        return (jnp.asarray(col, jnp.int32), jnp.asarray(rnd, jnp.int32),
                jnp.asarray(amt, jnp.int32))
    # sets: default add program = every element at round 0
    set_adds = (list(cfg.set_adds) if cfg.set_adds
                else [(e, 0) for e in range(cfg.elements)])

    def elem_rounds(pairs):
        a_pad = _pad_pow2(len(pairs)) if pairs else INJECT_A_MIN
        elem = [e for e, _ in pairs] + [0] * (a_pad - len(pairs))
        rnd = ([r for _, r in pairs]
               + [NO_ROUND] * (a_pad - len(pairs)))
        return (jnp.asarray(elem, jnp.int32),
                jnp.asarray(rnd, jnp.int32))

    return elem_rounds(set_adds) + elem_rounds(list(cfg.set_removes))


def split_inject(cfg: CrdtConfig, tbl: tuple):
    """(head_tables, inject_operands): peel the injection operands
    :func:`inject_args` appended back off a step's ``*tables`` tail —
    the ONE inverse (the nemesis split_tables discipline)."""
    k = N_INJECT_OPERANDS[cfg.kind]
    if k == 0:
        return tbl, ()
    return tbl[:-k], tbl[-k:]


def _applied_mask(rounds: jax.Array, owners: jax.Array,
                  alive_at_fn, eventual: jax.Array) -> jax.Array:
    """bool[A]: which injections are APPLIED under the fault program —
    owner alive at the injection round and eventually alive (module
    doc: the acked-adds semantics).  ``alive_at_fn(node, round) ->
    bool`` broadcasts; padding rows carry NO_ROUND and an in-range
    dummy owner, and die out on the alive_at compare below only if the
    schedule said so — so they are excluded explicitly here."""
    real = rounds < NO_ROUND
    return real & alive_at_fn(owners, rounds) & eventual[owners]


def alive_at_fn(fault: Optional[FaultConfig], n: int, origin: int):
    """``(nodes int32[...], rounds int32[...]) -> bool[...]`` liveness
    of ``nodes`` at ``rounds`` under the static mask + churn windows —
    in-trace safe, shared by the step's apply mask and ground truth so
    the two can never disagree on which injections fired."""
    from gossip_tpu.ops import nemesis as NE
    base = NE.base_alive_or_ones(fault, n, origin) \
        if fault is not None else jnp.ones((n,), jnp.bool_)
    ch = NE.get(fault)
    if ch is not None:
        sched_die, sched_rec = NE._event_tables(ch, n)
    else:
        sched_die = jnp.full((n,), NE.NEVER, jnp.int32)
        sched_rec = jnp.full((n,), NE.NEVER, jnp.int32)

    def fn(nodes, rounds):
        nodes = jnp.asarray(nodes, jnp.int32)
        rounds = jnp.asarray(rounds, jnp.int32)
        down = (sched_die[nodes] <= rounds) & (rounds < sched_rec[nodes])
        return base[nodes] & ~down

    return fn


def eventual_alive_crdt(fault: Optional[FaultConfig], n: int,
                        origin: int) -> jax.Array:
    """bool[n] eventual-alive set as a real array (the CRDT value-
    convergence denominator; ops/nemesis.eventual_alive, None-free)."""
    from gossip_tpu.ops import nemesis as NE
    if fault is None:
        return jnp.ones((n,), jnp.bool_)
    return NE.eventual_alive(fault, n, origin)


def inject_rows(cfg: CrdtConfig, inj: tuple, gids: jax.Array, round_,
                n: int, origin: int, alive_fn, eventual: jax.Array
                ) -> jax.Array:
    """The rows each node merges into its OWN state at ``round_`` —
    ``[len(gids), S]`` in the state dtype, zero except where this
    round's applied injections land on a ``gids`` row.  In-trace; the
    injections arrive as the :func:`inject_args` operands."""
    r = jnp.asarray(round_, jnp.int32)
    kind = cfg.kind
    if kind == VCLOCK:
        raise ValueError("vclock rows tick via vclock_tick, not "
                         "injections")
    if kind in CRDT_COUNTER_KINDS:
        col, rnd, amt = inj
        owner = col % n                                   # N-plane folds
        fire = (rnd == r) & _applied_mask(rnd, owner, alive_fn,
                                          eventual)
        s = shard_columns(kind, n)
        row = jnp.zeros((s,), jnp.int32).at[col].add(
            jnp.where(fire, amt, 0), mode="drop")
        col_owner = jnp.arange(s, dtype=jnp.int32) % n
        own = col_owner[None, :] == gids[:, None]         # [Nl, S]
        return jnp.where(own, row[None, :], 0)
    add_elem, add_rnd, rem_elem, rem_rnd = inj
    owners = (origin + jnp.arange(cfg.elements, dtype=jnp.int32)) % n

    def plane(elem, rnd):
        fire = (rnd == r) & _applied_mask(rnd, owners[elem], alive_fn,
                                          eventual)
        bits = jnp.zeros((cfg.elements,), jnp.bool_).at[elem].max(
            fire, mode="drop")
        # element e lands on its owner's row only
        mine = owners[None, :] == gids[:, None]           # [Nl, E]
        return pack(bits[None, :] & mine)                 # [Nl, W]

    return jnp.concatenate([plane(add_elem, add_rnd),
                            plane(rem_elem, rem_rnd)], axis=1)


def vclock_tick(vc: jax.Array, gids: jax.Array, alive: jax.Array,
                n: int) -> jax.Array:
    """One local event per alive node: owner entries increment
    (``vc[i, gids[i]] += alive[i]``) — the classic tick, the only
    write a non-owner never makes."""
    rows = jnp.arange(vc.shape[0], dtype=jnp.int32)
    return vc.at[rows, gids].add(
        jnp.where(alive, 1, 0).astype(vc.dtype), mode="drop")


# -- ground truth + value convergence (integer-exact) ------------------

def ground_truth(cfg: CrdtConfig, inj: tuple, fault, n: int,
                 origin: int) -> jax.Array:
    """The merged row ``[S]`` every eventually-alive node must reach:
    the merge of all APPLIED injections (module doc).  Built from the
    SAME operands and liveness predicate as the in-loop injection, so
    the target and the trajectory cannot drift.  In-trace safe and
    integer-exact."""
    alive_fn = alive_at_fn(fault, n, origin)
    eventual = eventual_alive_crdt(fault, n, origin)
    kind = cfg.kind
    if kind in CRDT_COUNTER_KINDS:
        col, rnd, amt = inj
        fire = _applied_mask(rnd, col % n, alive_fn, eventual)
        s = shard_columns(kind, n)
        return jnp.zeros((s,), jnp.int32).at[col].add(
            jnp.where(fire, amt, 0), mode="drop")
    add_elem, add_rnd, rem_elem, rem_rnd = inj
    owners = (origin + jnp.arange(cfg.elements, dtype=jnp.int32)) % n

    def plane(elem, rnd):
        fire = _applied_mask(rnd, owners[elem], alive_fn, eventual)
        bits = jnp.zeros((cfg.elements,), jnp.bool_).at[elem].max(
            fire, mode="drop")
        return pack(bits[None, :])[0]                     # [W]

    return jnp.concatenate([plane(add_elem, add_rnd),
                            plane(rem_elem, rem_rnd)])


def counter_value(kind: str, rows: jax.Array, n: int) -> jax.Array:
    """int32[...]: the merged counter value of each state row — sum of
    shards (gcounter), sum(P) - sum(N) (pncounter).  Integer-exact."""
    if kind == GCOUNTER:
        return jnp.sum(rows, axis=-1, dtype=jnp.int32)
    if kind == PNCOUNTER:
        return (jnp.sum(rows[..., :n], axis=-1, dtype=jnp.int32)
                - jnp.sum(rows[..., n:], axis=-1, dtype=jnp.int32))
    raise ValueError(f"{kind!r} has no scalar counter value")


def set_members(rows: jax.Array) -> jax.Array:
    """Membership planes of a packed set state: add & ~tombstone
    (``[..., W]`` from the ``[..., 2W]`` planes)."""
    w = rows.shape[-1] // 2
    return rows[..., :w] & ~rows[..., w:]


def converged_count(rows: jax.Array, truth: jax.Array,
                    alive: jax.Array) -> jax.Array:
    """int32 count of alive nodes whose state row equals the ground
    truth BITWISE (full-row equality: for sets that is both planes, so
    a node holding the member set but missing a tombstone has not
    converged — it could still un-remove on a later merge).  Divide by
    the eventual-alive total ONCE on the host for value_conv (module
    doc: integer counts cross the device boundary, never fractions)."""
    eq = jnp.all(rows == truth[None, :], axis=-1)
    return jnp.sum(eq & alive, dtype=jnp.int32)


def value_conv_frac(rows: jax.Array, truth: jax.Array,
                    alive: jax.Array) -> jax.Array:
    """f32 in-trace convergence fraction — for the RoundMetrics
    ``value_conv`` column and while_loop conds ONLY (observability and
    control flow); every pinned readout uses :func:`converged_count`
    and divides on the host."""
    c = converged_count(rows, truth, alive).astype(jnp.float32)
    return c / jnp.maximum(jnp.sum(alive, dtype=jnp.float32), 1.0)


def payload_count(cfg: CrdtConfig, rows: jax.Array,
                  alive: jax.Array) -> jax.Array:
    """f32 total payload mass over alive rows — counter mass (shard
    sums) or set bit count — the CRDT ``newly`` counter's integrand
    (ops/round_metrics: ``newly`` = per-round delta of this, exact
    because both mass measures are monotone under merge)."""
    if cfg.kind in CRDT_SET_KINDS:
        pc = jnp.where(alive[:, None],
                       jax.lax.population_count(rows), 0)
        return jnp.sum(pc, dtype=jnp.float32)
    return jnp.sum(jnp.where(alive[:, None], rows, 0),
                   dtype=jnp.float32)


# -- the exchange drivers' refusals and the payload row -----------------

def check_crdt_mode(proto) -> None:
    """Pull only: state-based CRDT dissemination IS the pull/digest
    exchange (each round a node fetches k peers' full states and joins
    them — Shapiro et al. §3.2), and the push half would need a
    scatter-max/scatter-OR collective XLA does not have — exactly the
    reason models/si_packed.py rejects push modes.  One loud reason,
    shared by every driver and the CLI."""
    if proto.mode != PULL:
        raise ValueError(
            "CRDT rounds run the pull exchange only (state-based merge "
            f"IS the digest pull; got mode {proto.mode!r} — the push "
            "half would need a scatter-max/scatter-OR collective XLA "
            "does not have, the models/si_packed.py precedent)")


def check_injections_reachable(cfg: CrdtConfig, run) -> None:
    """Every scripted injection must fire inside the run: an add at a
    round >= max_rounds would be counted by ground_truth (the owner IS
    alive there) but never applied by the loop, so the run could never
    converge — reported as a quiet converged:false instead of the loud
    error the no-silent-failure policy demands."""
    last = cfg.horizon() - 1
    if last >= run.max_rounds:
        raise ValueError(
            f"injection at round {last} can never fire: the run stops "
            f"after max_rounds={run.max_rounds} rounds, so ground "
            "truth would be unreachable by construction — raise "
            "--max-rounds past the last scripted round")


def check_exchange(cfg: CrdtConfig, sharded: bool) -> None:
    """Vector clocks tick locally and have no exchange driver."""
    if cfg.kind != VCLOCK:
        return
    if sharded:
        raise ValueError("vclock has no exchange driver (ops/crdt merge "
                         "kernel + tick only)")
    raise ValueError("vclock has no exchange driver (merge kernel "
                     "+ tick only — ops/crdt); run gcounter/"
                     "pncounter/gset/orset")


def check_byz_defendable(cfg, fault, fanout: int, defend: bool) -> None:
    """The defend/byz coupling, one loud reason per arm (shared by both
    payload frames and every payload that takes liar programs):
    ``defend=True`` without a liar program is rejected (the defended
    admission CHANGES the exchange — owner-direct propagation — so a
    defended no-liar run is not the control arm of anything), and a
    defended packed-set run needs ``fanout >= quorum`` (a round that
    samples fewer partners than the echo threshold could never admit a
    broadcast bit by quorum)."""
    from gossip_tpu.ops import nemesis as NE
    bz = NE.get_byz(fault)
    if defend and bz is None:
        raise ValueError(
            "defend=True without a byzantine program: the defended "
            "admission changes the exchange (owner-direct "
            "propagation), so there is nothing it would be defending "
            "against — script liars with --byz, or drop --defend")
    if (defend and bz is not None and cfg is not None
            and getattr(cfg, "kind", None) in CRDT_SET_KINDS
            and fanout < fault.byz.quorum):
        raise ValueError(
            f"defended packed-set exchange with fanout={fanout} < "
            f"quorum={fault.byz.quorum}: a bit echoed by fewer "
            "partners than are even sampled per round can never meet "
            "the quorum — raise --fanout or lower ByzConfig.quorum")


def truth_scalar(cfg: CrdtConfig, truth, n: int):
    """The human-readable ground truth: counter value (int) or member
    count (int) — integer-exact, for reports and the CLI."""
    import numpy as np
    truth = np.asarray(truth)
    if cfg.kind in CRDT_COUNTER_KINDS:
        if cfg.kind == PNCOUNTER:
            return int(truth[:n].sum() - truth[n:].sum())
        return int(truth.sum())
    w = truth.shape[0] // 2
    members = truth[:w] & ~truth[w:]
    return int(sum(bin(int(x)).count("1") for x in members))


def _inject_own(cfg, val, rows):
    """Own columns add (increments accumulate), set planes OR."""
    return val + rows if cfg.kind in CRDT_COUNTER_KINDS else val | rows


def _pull_merge_byz(cfg, rows_all, partners, n, byz, round_, gids,
                    origin, fault, defend):
    return pull_merge_crdt_byz(
        cfg, rows_all, partners, n, byz=byz, round_=round_, gids=gids,
        n=n, origin=origin, alive_fn=alive_at_fn(fault, n, origin),
        defend=defend)


ROW = PayloadRow(
    label="crdt", column="value_conv", engine="crdt-pull",
    check_mode=check_crdt_mode,
    check_reachable=check_injections_reachable,
    check_cfg=check_exchange,
    width=state_width, dtype=state_dtype,
    inject_args=inject_args, split_inject=split_inject,
    inject_rows=lambda cfg, inj, gids, r, n, origin, fault: inject_rows(
        cfg, inj, gids, r, n, origin, alive_at_fn(fault, n, origin),
        eventual_alive_crdt(fault, n, origin)),
    apply_inject=_inject_own,
    merge=lambda cfg, a, b: merge(cfg.kind, a, b),
    pull_merge=lambda cfg, rows, partners, n: pull_merge_crdt(
        cfg.kind, rows, partners, n),
    ground_truth=ground_truth, payload_count=payload_count,
    truth_summary=truth_scalar,
    pull_merge_byz=_pull_merge_byz,
    honest_mask=lambda cfg, inj, fault, n, origin, honest: (
        honest_component_mask(cfg, n, origin, honest)),
    byz_conv_frac=byz_conv_frac)
