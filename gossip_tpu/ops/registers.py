"""Last-writer-wins registers: totally-available transaction payloads
on the gossip fabric.

The rung above the Gossip Glomers ladder (ROADMAP item 4): Maelstrom's
``txn-rw-register`` workload — multi-key read/write transactions over
replicated registers — batched into array form.  Where the
counter/set/log payloads (ops/crdt, ops/logs) demand eventual
agreement on a *monotone* value, registers are overwritten: the merge
must pick a WINNER, and the winner must be the same on every replica
no matter the gossip order — which is exactly a lattice join on the
pair ``(timestamp, value)`` ordered lexicographically.

Array form (one row per node, the ops/crdt layout discipline): K
registers flatten to one ``int32[N, 2K]`` row —

  * columns ``0 .. K-1`` — the **value planes**: column k holds the
    currently-winning value of register k (0 = never written;
    TxnConfig requires values >= 1);
  * columns ``K .. 2K-1`` — the **timestamp planes**: column K+k holds
    the winning write's timestamp, the lexicographic ``(round, owner)``
    key packed into one int32 by :func:`pack_ts`
    (``round * n + owner + 1``; 0 = never written).  Packing makes the
    total order ONE integer compare, so the tie-break at equal rounds
    is the owner order by construction — deterministic, test-pinned.

:func:`merge_lww` is the per-key join: take the larger timestamp and
its value.  Because every applied write carries a UNIQUE timestamp
(TxnConfig rejects duplicate ``(key, round, node)`` writes), the pair
order is total on real trajectories; on arbitrary states the
equal-timestamp case resolves to ``max(value)`` so the join stays
commutative, associative, and idempotent unconditionally — the algebra
pins in tests/test_txn.py hold bitwise on random states, not just
reachable ones.

Transactions as programs over rounds
------------------------------------
A transaction's write micro-ops lower to padded runtime operands on
the step's ``tables`` tail (:func:`inject_args` — the nemesis/CRDT/log
pattern: compiled loops carry shapes, never content).  The default
program is a SKEWED traffic generator built by closed forms over the
TxnConfig scalars (:func:`txn_writes`): zipfian key popularity,
optional hot-key storms, uniform or diurnal load curves — no RNG, no
O(T) config object, so a scenario sweep across skews re-enters one
executable per padded arity bucket.

Ground truth and the txn-convergence metric
-------------------------------------------
A write is **applied** iff its owner is alive at the write round AND
eventually alive under the fault program — the acked-adds rule shared
with ops/crdt/ops/logs through the same ``_applied_mask`` /
``alive_at_fn`` predicates, so a node destined for permanent death
wins nothing.  :func:`ground_truth` picks each key's max-timestamp
applied write IN-TRACE from the same operands as the in-loop
injection (target and trajectory cannot drift), and convergence is
judged integer-exact: ``ops/crdt.converged_count`` full-row equality
(value AND timestamp planes — a node holding the right value under
the wrong timestamp could still lose it to a later merge), divided
ONCE on the host.  ``txn_conv`` is the RoundMetrics column.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from gossip_tpu.config import PULL, TxnConfig
# ONE definition each for the padding bucket, the no-injection round
# sentinel, and the shared liveness predicates (ops/crdt): the txn,
# log, and CRDT injection lowerings must agree on all of them by
# construction.
from gossip_tpu.ops.crdt import (NO_ROUND, _applied_mask, _pad_pow2,
                                 alive_at_fn, converged_count,
                                 eventual_alive_crdt, value_conv_frac)
from gossip_tpu.ops.payload import PayloadRow

__all__ = ["N_INJECT_OPERANDS", "ROW", "alive_at_fn", "byz_conv_frac",
           "byz_converged_count", "check_txn_mode",
           "check_writes_reachable", "converged_count",
           "eventual_alive_crdt", "ground_truth", "honest_key_mask",
           "inject_args", "inject_rows", "merge_lww", "pack_ts",
           "payload_count", "pull_merge_reg", "pull_merge_reg_byz",
           "split_inject", "state_width", "truth_summary",
           "txn_writes", "value_conv_frac"]

# Trailing step arguments the write program occupies on a factory's
# ``tables`` tuple: (w_node, w_key, w_round, w_val), each padded
# int32[A].
N_INJECT_OPERANDS = 4


def state_width(cfg: TxnConfig) -> int:
    """2K: value planes then timestamp planes (module doc)."""
    return 2 * cfg.keys


def check_ts_packable(cfg: TxnConfig, n: int) -> None:
    """The packed timestamp ``round * n + owner + 1`` must fit int32 —
    reject the overflow loudly instead of silently wrapping the total
    order (which would fork LWW winners between replicas)."""
    last = cfg.horizon() - 1
    if (last + 1) * n + 1 > 2 ** 31 - 1:
        raise ValueError(
            f"packed (round, owner) timestamp overflows int32 at "
            f"round {last} with n={n} (needs (round+1)*n+1 < 2^31); "
            "shrink the write program's horizon or n")


def pack_ts(rounds: jax.Array, owners: jax.Array, n: int) -> jax.Array:
    """int32 lexicographic ``(round, owner)`` key: ``round * n + owner
    + 1`` (0 = never written, so zeros are the merge identity).  The
    ONE packing, shared by the in-loop injection and the ground truth;
    padding rows carry NO_ROUND and map to 0 here (never a winner)."""
    rounds = jnp.asarray(rounds, jnp.int32)
    owners = jnp.asarray(owners, jnp.int32)
    real = rounds < NO_ROUND
    rc = jnp.where(real, rounds, 0)
    return jnp.where(real, rc * n + owners + 1, 0)


# -- the LWW join ------------------------------------------------------

def merge_lww(a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-key last-writer-wins join of two ``[..., 2K]`` rows: the
    larger timestamp wins and brings its value.  At equal timestamps
    the values are equal on any reachable trajectory (timestamps are
    unique per applied write — TxnConfig); on arbitrary states the tie
    resolves to ``max(value)`` so the join is a total lattice join —
    commutative, associative, idempotent, an upper bound — pinned
    bitwise in tests/test_txn.py."""
    k = a.shape[-1] // 2
    va, ta = a[..., :k], a[..., k:]
    vb, tb = b[..., :k], b[..., k:]
    v = jnp.where(ta > tb, va,
                  jnp.where(tb > ta, vb, jnp.maximum(va, vb)))
    return jnp.concatenate([v, jnp.maximum(ta, tb)], axis=-1)


def pull_merge_reg(rows_all: jax.Array, partners: jax.Array,
                   sentinel: int) -> jax.Array:
    """LWW merge of k sampled peers' register rows -> ``[N_local, 2K]``
    — the ops/logs.pull_merge_log shape with :func:`merge_lww` as the
    join (all-zero rows are the identity: ts 0 never wins)."""
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = rows_all[safe]                              # [Nl, k, 2K]
    got = jnp.where(valid[:, :, None], got,
                    jnp.zeros((), rows_all.dtype))
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = merge_lww(out, got[:, j, :])
    return out


# -- byzantine exchange: liar transforms + the owner/clamp defense -----
#
# Register twin of ops/crdt's byzantine kernels (module comment there;
# docs/ROBUSTNESS.md "Byzantine adversaries").  The packed timestamp
# CARRIES provenance — ``(ts - 1) % n`` is the claimed owner and
# ``(ts - 1) // n`` the claimed round — so the defense is two integer
# compares per key: admit an entry from partner p only when p IS the
# claimed owner (owner-column write guard) and the claimed round is
# not in the future (the monotonicity clamp — a forged
# fresher-than-now timestamp is discarded as forged).  Every scripted
# liar transform forges only entries claimed-owned by OTHER nodes
# (own-entry lies are legitimate writes by definition — the BFT
# limitation), so the defended admission rejects all of it, while the
# undefended ts-max join locks any inflated timestamp in forever.

def _byz_serve_reg(got, safe, active, gids, byz, n: int):
    """Render what liar partners SERVE (register rows [Nl, k, 2K]):
    corrupt = foreign nonzero entries get ts + n (claimed round + 1,
    claimed owner PRESERVED — a plausible forged foreign write) and a
    value xor'd with arg; replay = the genesis snapshot (all zeros —
    pure withholding); equivocate = foreign timestamps inflated by a
    receiver-id-keyed number of rounds; inflate = foreign timestamps
    raised by ``arg * n`` rounds (the value is left alone — the lie is
    freshness, which pins the stale value above all later honest
    writes undefended).  ts == 0 entries are never touched: an
    unwritten key has no claimed owner to preserve, and fabricating
    one would alias node n-1's provenance."""
    from gossip_tpu.ops import nemesis as NE
    k = got.shape[-1] // 2
    v, t = got[..., :k], got[..., k:]
    kindp = byz.kind[safe][:, :, None]                 # [Nl, k, 1]
    argp = byz.arg[safe][:, :, None]
    foreign = (t > 0) & (((t - 1) % n) != safe[:, :, None])
    t_cor = jnp.where(foreign, t + n, t)
    v_cor = jnp.where(foreign, v ^ argp, v)
    t_inf = jnp.where(foreign, t + n * argp, t)
    t_eqv = jnp.where(foreign,
                      t + n * (1 + (gids[:, None, None] & 3)), t)
    vv = jnp.where(kindp == NE.BYZ_CODES["corrupt"], v_cor, v)
    tt = jnp.where(kindp == NE.BYZ_CODES["corrupt"], t_cor, t)
    vv = jnp.where(kindp == NE.BYZ_CODES["replay"], 0, vv)
    tt = jnp.where(kindp == NE.BYZ_CODES["replay"], 0, tt)
    tt = jnp.where(kindp == NE.BYZ_CODES["equivocate"], t_eqv, tt)
    tt = jnp.where(kindp == NE.BYZ_CODES["inflate"], t_inf, tt)
    out = jnp.concatenate([vv, tt], axis=-1)
    return jnp.where(active[:, :, None], out, got)


def pull_merge_reg_byz(rows_all: jax.Array, partners: jax.Array,
                       sentinel: int, *, byz, round_,
                       gids: jax.Array, n: int, alive_fn,
                       defend: bool) -> jax.Array:
    """:func:`pull_merge_reg` under a byzantine program (section
    comment).  Defended admission per key, from partner p at round r:
    ``(ts > 0) & ((ts - 1) % n == p) & ((ts - 1) // n <= r)`` — then
    the LWW join of the admitted entries.  Owner-direct propagation
    only (honest relayed entries are rejected too — slower, still
    exact); the control arm ``defend=False`` merges the rendered rows
    unguarded and provably diverges under any ts-inflating liar."""
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = rows_all[safe]                              # [Nl, k, 2K]
    got = jnp.where(valid[:, :, None], got,
                    jnp.zeros((), rows_all.dtype))
    from gossip_tpu.ops import nemesis as NE
    active = (valid & NE.byz_active(byz, safe, round_)
              & alive_fn(safe, round_))
    got = _byz_serve_reg(got, safe, active, gids, byz, n)
    if defend:
        k = got.shape[-1] // 2
        v, t = got[..., :k], got[..., k:]
        r = jnp.asarray(round_, jnp.int32)
        admit = (valid[:, :, None] & (t > 0)
                 & (((t - 1) % n) == safe[:, :, None])
                 & (((t - 1) // n) <= r))
        got = jnp.concatenate([jnp.where(admit, v, 0),
                               jnp.where(admit, t, 0)], axis=-1)
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = merge_lww(out, got[:, j, :])
    return out


# -- honest-component convergence (the byz_conv metric) ----------------

def honest_key_mask(cfg: TxnConfig, inj: tuple, fault, n: int,
                    origin: int, honest: jax.Array) -> jax.Array:
    """bool[K]: keys whose ground-truth winner is honest-owned (or
    never written).  The byz_conv equality is restricted to these — a
    liar may withhold its own scripted wins (replay) or overwrite its
    own entries arbitrarily, both undetectable by construction, so
    honest convergence is only claimable where truth itself is honest
    (docs/ROBUSTNESS.md).  Built from :func:`_write_plan`'s winning
    timestamps — the same decomposition as the ground truth."""
    _, _, best = _write_plan(cfg, inj, fault, n, origin)
    owner = jnp.where(best > 0, (best - 1) % n, 0)
    return (best == 0) | honest[owner]


def byz_converged_count(cfg: TxnConfig, rows: jax.Array,
                        truth: jax.Array, alive_honest: jax.Array,
                        key_mask: jax.Array) -> jax.Array:
    """int32 count of honest eventually-alive rows equal to truth on
    every honest-won key, BOTH planes (value and timestamp — the
    full-row discipline of ``converged_count``): the byz_conv
    numerator, divided once on the host."""
    m2 = jnp.concatenate([key_mask, key_mask])
    eq = jnp.all(jnp.where(m2[None, :], rows == truth[None, :], True),
                 axis=-1)
    return jnp.sum(eq & alive_honest, dtype=jnp.int32)


def byz_conv_frac(cfg: TxnConfig, rows: jax.Array, truth: jax.Array,
                  alive_honest: jax.Array,
                  key_mask: jax.Array) -> jax.Array:
    """f32 in-trace byz_conv fraction — RoundMetrics column only; the
    pinned readout is the integer count."""
    c = byz_converged_count(cfg, rows, truth, alive_honest,
                            key_mask).astype(jnp.float32)
    return c / jnp.maximum(jnp.sum(alive_honest, dtype=jnp.float32),
                           1.0)


# -- the skewed default traffic program (closed forms, no RNG) ---------

def _hash01(i: int, salt: int = 0) -> float:
    """Deterministic quasi-uniform in [0, 1): Knuth's multiplicative
    hash on the write index — a closed form, not an RNG stream, so the
    program is a pure function of the config scalars."""
    x = ((i * 2654435761) ^ (salt * 40503)) & 0xFFFFFFFF
    x = (x * 2246822519 + 3266489917) & 0xFFFFFFFF
    return x / 2 ** 32


def _zipf_key(u: float, keys: int, alpha: float) -> int:
    """Inverse-CDF zipf(alpha) pick over ``keys`` ranks for quantile
    ``u`` — key 0 is the most popular rank."""
    weights = [1.0 / (r + 1) ** alpha for r in range(keys)]
    total = sum(weights)
    acc = 0.0
    for k, w in enumerate(weights):
        acc += w / total
        if u < acc:
            return k
    return keys - 1


def _load_round(q: float, load: str, spread: int) -> int:
    """Round for program quantile ``q`` in [0, 1) under the load
    curve: ``uniform`` spreads evenly; ``diurnal`` follows the
    inverse CDF of density ``1 + sin`` (one day-shaped peak
    mid-window), computed by bisection on the closed-form CDF."""
    if load == "uniform" or spread == 1:
        return min(spread - 1, int(q * spread))

    def cdf(x):    # integral of (1 + sin(pi * x)) / norm over [0, 1]
        return (x + (1.0 - math.cos(math.pi * x)) / math.pi) / \
            (1.0 + 2.0 / math.pi)

    lo, hi = 0.0, 1.0
    for _ in range(30):
        mid = (lo + hi) / 2
        if cdf(mid) < q:
            lo = mid
        else:
            hi = mid
    return min(spread - 1, int(lo * spread))


def txn_writes(cfg: TxnConfig, n: int):
    """The effective write list ``[(node, key, round, value), ...]`` —
    scripted, or the default SKEWED program's closed form: write i
    picks its key by zipf(``zipf_alpha``) inverse CDF on a hashed
    quantile, redirected to key 0 with probability ``hot_key`` during
    the middle third of the program (the storm window), lands on the
    round given by the ``load`` curve over ``spread_rounds`` (rounds
    are nondecreasing in i by construction), is written by node
    ``(5 * key + c) % n`` where ``c`` counts the EARLIER writes in
    the same (key, round) bucket — distinct writers per bucket
    whenever ``c < n``, so the default program is collision-free by
    construction (the unique-timestamp contract; a bucket needing
    more than n writers is a pigeonhole impossibility — more than n
    same-(key, round) writes cannot carry unique (round, owner)
    timestamps — and errors loudly naming the knobs), with value
    ``1 + (5 * i + 11 * key) % 97``.  A formula, not a config table;
    the ONE definition shared by the lowering and ground truth."""
    if cfg.writes:
        return list(cfg.writes)
    t = cfg.txns
    out = []
    bucket: dict = {}
    for i in range(t):
        q = (i + 0.5) / t
        key = _zipf_key(_hash01(i, 1), cfg.keys, cfg.zipf_alpha)
        if (cfg.hot_key > 0 and t // 3 <= i < (2 * t) // 3
                and _hash01(i, 2) < cfg.hot_key):
            key = 0
        rnd = _load_round(q, cfg.load, cfg.spread_rounds)
        c = bucket.get((key, rnd), 0)
        bucket[(key, rnd)] = c + 1
        if c >= n:
            raise ValueError(
                f"the default txn program places {c + 1} writes on "
                f"key {key} at round {rnd} but only n={n} distinct "
                "writers exist — more than n same-(key, round) "
                "writes cannot carry unique (round, owner) "
                "timestamps; lower --txns, raise --spread (or ease "
                "--hot-key/--zipf-alpha), or raise --n")
        node = (5 * key + c) % n
        out.append((node, key, rnd, 1 + (5 * i + 11 * key) % 97))
    return out


def inject_args(cfg: TxnConfig, n: int) -> tuple:
    """Lower the write program to the 4-operand tuple (module doc),
    padded to a power-of-two bucket so same-arity programs are
    shape-identical and share one compiled loop.  Re-validates the
    unique-(key, round, node) timestamp contract on the EFFECTIVE list
    (a default program is built here, after n is known) and the int32
    packability of every timestamp."""
    check_ts_packable(cfg, n)
    writes = txn_writes(cfg, n)
    bad = [w for w in writes if w[0] >= n]
    if bad:
        raise ValueError(f"txn writes reference node ids >= n={n}: "
                         f"{bad}")
    trips = [(k, r, nd) for nd, k, r, _ in writes]
    if len(set(trips)) != len(trips):
        dup = sorted({t for t in trips if trips.count(t) > 1})
        raise ValueError(
            f"txn write program carries duplicate (key, round, node) "
            f"triples {dup[:4]} — two writes would share one "
            "(round, owner) timestamp and fork the LWW winner; "
            "script distinct writers or rounds")
    a_pad = _pad_pow2(len(writes))
    cols = [[w[j] for w in writes] for j in range(4)]
    cols[0] += [0] * (a_pad - len(writes))            # node
    cols[1] += [0] * (a_pad - len(writes))            # key
    cols[2] += [NO_ROUND] * (a_pad - len(writes))     # round
    cols[3] += [0] * (a_pad - len(writes))            # value
    return tuple(jnp.asarray(c, jnp.int32) for c in cols)


def split_inject(cfg: TxnConfig, tbl: tuple):
    """(head_tables, inject_operands): peel the 4 operands
    :func:`inject_args` appended back off a step's ``*tables`` tail —
    the ONE inverse (the nemesis split_tables discipline)."""
    return tbl[:-N_INJECT_OPERANDS], tbl[-N_INJECT_OPERANDS:]


# -- ground truth + in-loop injection (shared decomposition) -----------

def _write_plan(cfg: TxnConfig, inj: tuple, fault, n: int, origin: int):
    """The shared in-trace decomposition of the 4 operands: the applied
    mask, each write's packed timestamp, and each key's winning
    timestamp — used by BOTH the in-loop injection and the ground
    truth so the two can never drift."""
    w_node, w_key, w_round, _ = inj
    alive_fn = alive_at_fn(fault, n, origin)
    eventual = eventual_alive_crdt(fault, n, origin)
    applied = _applied_mask(w_round, w_node, alive_fn, eventual)
    ts = jnp.where(applied, pack_ts(w_round, w_node, n), 0)
    best = jnp.zeros((cfg.keys,), jnp.int32).at[w_key].max(
        ts, mode="drop")
    return applied, ts, best


def ground_truth(cfg: TxnConfig, inj: tuple, fault, n: int,
                 origin: int) -> jax.Array:
    """The row ``[2K]`` every eventually-alive node must reach: per
    key, the max-timestamp APPLIED write's value and timestamp (module
    doc; unwritten keys stay (0, 0)).  In-trace, integer-exact, built
    from the SAME operands + liveness predicate as
    :func:`inject_rows` — unique timestamps make the winner select
    exact, never a blend."""
    w_key, w_val = inj[1], inj[3]
    applied, ts, best = _write_plan(cfg, inj, fault, n, origin)
    win = applied & (ts > 0) & (ts == best[w_key])
    val = jnp.zeros((cfg.keys,), jnp.int32).at[w_key].max(
        jnp.where(win, w_val, 0), mode="drop")
    return jnp.concatenate([val, best])


def inject_rows(cfg: TxnConfig, inj: tuple, gids: jax.Array, round_,
                n: int, origin: int, fault) -> jax.Array:
    """The rows each node LWW-merges into its OWN state at ``round_``
    — ``int32[len(gids), 2K]``, zero except where this round's applied
    writes land on a ``gids`` row (the writer owns the write — the
    owner-indexed discipline).  A node writes at most one value per
    (key, round) by the unique-timestamp contract, so the per-row
    scatter is collision-free."""
    r = jnp.asarray(round_, jnp.int32)
    w_node, w_key, w_round, w_val = inj
    applied, ts, _ = _write_plan(cfg, inj, fault, n, origin)
    fire = (w_round == r) & applied
    mine = w_node[None, :] == gids[:, None]             # [Nl, A]
    hit = fire[None, :] & mine
    nl = gids.shape[0]
    rows = jnp.zeros((nl, state_width(cfg)), jnp.int32)
    rows = rows.at[:, w_key].max(jnp.where(hit, w_val[None, :], 0),
                                 mode="drop")
    return rows.at[:, cfg.keys + w_key].max(
        jnp.where(hit, ts[None, :], 0), mode="drop")


# -- readouts ----------------------------------------------------------

def payload_count(cfg: TxnConfig, rows: jax.Array,
                  alive: jax.Array) -> jax.Array:
    """f32 total timestamp mass over alive rows — the ``newly``
    integrand (ops/round_metrics): timestamps are monotone under the
    LWW merge (values are not), so the per-round delta is exact.
    Observability-plane f32 only; every pinned readout is the integer
    converged count."""
    ts = rows[..., cfg.keys:]
    return jnp.sum(jnp.where(alive[:, None], ts, 0),
                   dtype=jnp.float32)


def truth_summary(cfg: TxnConfig, truth, n: int) -> dict:
    """Human-readable ground truth for reports and the CLI: per-key
    winning values plus the unpacked (round, owner) of each winner
    (-1 for never-written keys), integer-exact."""
    import numpy as np
    truth = np.asarray(truth)
    vals = truth[:cfg.keys]
    ts = truth[cfg.keys:]
    rounds = [int((t - 1) // n) if t > 0 else -1 for t in ts]
    owners = [int((t - 1) % n) if t > 0 else -1 for t in ts]
    return {"values": [int(v) for v in vals],
            "ts_round": rounds, "ts_owner": owners,
            "written_keys": int((ts > 0).sum())}


# -- the exchange drivers' refusals and the payload row -----------------

def check_txn_mode(proto) -> None:
    """Pull only: state-based dissemination IS the pull/digest
    exchange, and the push half would need a scatter-argmax collective
    XLA does not have (the ops/crdt and ops/logs precedent).  One loud
    reason, shared by every driver and the CLI."""
    if proto.mode != PULL:
        raise ValueError(
            "LWW-register rounds run the pull exchange only "
            "(state-based merge IS the digest pull; got mode "
            f"{proto.mode!r} — the push half would need a "
            "scatter-argmax collective XLA does not have, the "
            "models/crdt and models/log precedent)")


def check_writes_reachable(cfg: TxnConfig, run) -> None:
    """Every scripted write must fire inside the run (the ops/crdt
    rule: an unreachable write makes ground truth unreachable by
    construction — a loud error, never a quiet converged:false)."""
    last = cfg.horizon() - 1
    if last >= run.max_rounds:
        raise ValueError(
            f"txn write at round {last} can never fire: the run "
            f"stops after max_rounds={run.max_rounds} rounds, so "
            "ground truth would be unreachable by construction — "
            "raise --max-rounds past the last scripted round")


def _pull_merge_byz(cfg, rows_all, partners, n, byz, round_, gids,
                    origin, fault, defend):
    return pull_merge_reg_byz(
        rows_all, partners, n, byz=byz, round_=round_, gids=gids, n=n,
        alive_fn=alive_at_fn(fault, n, origin), defend=defend)


# The injection merges via the SAME LWW join as the exchange — an own
# write always wins locally (its timestamp exceeds anything merged in
# earlier rounds) and same-round peers resolve by owner order.
ROW = PayloadRow(
    label="txn", column="txn_conv", engine="txn-pull",
    check_mode=check_txn_mode,
    check_reachable=check_writes_reachable,
    width=lambda cfg, n: state_width(cfg),
    dtype=lambda cfg: jnp.int32,
    inject_args=inject_args, split_inject=split_inject,
    inject_rows=inject_rows,
    apply_inject=lambda cfg, val, rows: merge_lww(val, rows),
    merge=lambda cfg, a, b: merge_lww(a, b),
    pull_merge=lambda cfg, rows, partners, n: pull_merge_reg(
        rows, partners, n),
    ground_truth=ground_truth, payload_count=payload_count,
    truth_summary=truth_summary,
    pull_merge_byz=_pull_merge_byz, honest_mask=honest_key_mask,
    byz_conv_frac=byz_conv_frac)
