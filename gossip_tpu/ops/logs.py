"""Replicated kafka-style logs: ordered per-key offset payloads on the
gossip fabric.

The last Gossip Glomers sibling of the reference's broadcast (after the
PR 8 counters and sets): a **replicated log** — per-key append streams
with offsets, committed offsets, and poll semantics.  What is
qualitatively new is *order*: the counter/set payloads only demand
eventual agreement on an unordered value, while the kafka invariants
demand that acked sends appear **exactly once per key in offset
order**, that committed offsets **never regress**, and that polls from
a committed offset see **no gaps**.

Array form (one row per node, the ops/crdt layout discipline): each
node carries K fixed-capacity per-key ring buffers plus a per-key
committed-offset vector, flattened to one ``int32[N, S]`` row with
``S = K * (C + 1)``:

  * columns ``0 .. K*C-1`` — the **entry planes**: slot ``(k, c)``
    (column ``k*C + c``) holds the value appended at offset ``c`` of
    key ``k`` (0 = empty; LogConfig requires values >= 1).  The slot's
    ring position is ``offset % C``; LogConfig caps sends-per-key at C
    so the ring never wraps onto an unconsumed slot (a wrap would
    silently alias two offsets — rejected loudly instead).
  * columns ``K*C .. K*C+K-1`` — the **committed-offset vector**:
    column ``K*C + k`` holds key ``k``'s committed count (offsets
    below it are committed; 0 = nothing committed).

Why elementwise max is the exact join (the G-Counter column
discipline): every entry slot is written by exactly ONE owner — the
appender of the unique applied send that lands on that offset — and
written once, monotonically 0 -> value; committed counts are monotone
by the kafka contract (commits never regress) and the ground-truth
commit value is the max of all applied commits.  So merge =
elementwise max over the owner-indexed slot planes is commutative,
associative, idempotent, and an upper bound — gossip order,
duplication, and loss can never corrupt the log.

Offset assignment and the acked-appends ground truth
----------------------------------------------------
Sends are a scripted *program over rounds* — ``(node, key, round,
value)`` — lowered to padded runtime operands on the step's ``tables``
tail (:func:`inject_args`, the nemesis/CRDT pattern: compiled loops
carry injection shapes, never content).  A send is **applied** iff its
appender is alive at the send round AND eventually alive under the
fault program (the acked-adds semantics of ops/crdt: a node destined
for permanent death contributes nothing).  A key's applied sends take
offsets ``0 .. m-1`` in script order (:func:`send_offsets` — LogConfig
requires per-key script order to be round-nondecreasing, so offset
order IS time order), compacted over unapplied sends so the acked log
is gap-free by construction.  Commits ``(node, key, round, upto)``
apply under the same liveness predicate and commit
``min(upto, truth_len[key])`` — the clamp to the eventually-acked log
length keeps a committed offset from ever pointing past the final log.

:func:`ground_truth` builds the merged truth row from the SAME
operands and liveness predicate as the in-loop injection, so target
and trajectory cannot drift; convergence is judged integer-exact
(``ops/crdt.converged_count`` full-row equality, divided ONCE on the
host — the ``log_conv`` readout and round-metrics column).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gossip_tpu.config import PULL, LogConfig
# ONE definition each for the padding bucket, the no-injection round
# sentinel, and the shared liveness predicates (ops/crdt): the log and
# CRDT injection lowerings must agree on all of them by construction.
from gossip_tpu.ops.crdt import (NO_ROUND, _applied_mask, _pad_pow2,
                                 alive_at_fn, converged_count,
                                 eventual_alive_crdt, merge_max,
                                 value_conv_frac)
from gossip_tpu.ops.payload import PayloadRow

__all__ = ["N_INJECT_OPERANDS", "ROW", "alive_at_fn",
           "check_injections_reachable", "check_log_mode",
           "converged_count", "eventual_alive_crdt", "ground_truth",
           "inject_args", "inject_rows", "log_commits", "log_len",
           "log_sends", "merge_max", "payload_count", "pull_merge_log",
           "send_offsets", "split_inject", "state_width",
           "truth_summary", "value_conv_frac"]

# Trailing step arguments the injection program occupies on a factory's
# ``tables`` tuple: (s_node, s_key, s_round, s_val) sends +
# (c_node, c_key, c_round, c_upto) commits, each padded int32[A].
N_INJECT_OPERANDS = 8


def state_width(cfg: LogConfig) -> int:
    """S = K*C entry slots + K committed columns (module doc)."""
    return cfg.keys * (cfg.capacity + 1)


def pull_merge_log(rows_all: jax.Array, partners: jax.Array,
                   sentinel: int) -> jax.Array:
    """Merge of k sampled peers' log rows -> ``[N_local, S]`` — the
    ops/crdt.pull_merge_crdt shape with the max join (0 is the merge
    identity: entries and commits are nonnegative by contract)."""
    valid = partners < sentinel
    safe = jnp.minimum(partners, sentinel - 1)
    got = rows_all[safe]                              # [Nl, k, S]
    got = jnp.where(valid[:, :, None], got,
                    jnp.zeros((), rows_all.dtype))
    out = got[:, 0, :]
    for j in range(1, got.shape[1]):
        out = merge_max(out, got[:, j, :])
    return out


# -- injection programs (closed-form defaults, the counter_adds rule) --

def log_sends(cfg: LogConfig, n: int):
    """The effective send list ``[(node, key, round, value), ...]`` —
    scripted, or the default program's closed form: each key k gets 4
    sends, send j appended by node ``(k + 3*j) % n`` at round j with
    value ``1 + (7*k + 3*j) % 23``.  A formula, not a config table
    (no O(N)/O(K) config object); the ONE definition shared by the
    lowering and ground truth through :func:`inject_args`."""
    if cfg.sends:
        return list(cfg.sends)
    return [(int((k + 3 * j) % n), k, j, 1 + (7 * k + 3 * j) % 23)
            for k in range(cfg.keys) for j in range(4)]


def log_commits(cfg: LogConfig, n: int):
    """The effective commit list ``[(node, key, round, upto), ...]`` —
    scripted, or the default: node ``(k + 1) % n`` commits key k up to
    2 entries at round 4 (after the default sends)."""
    if cfg.commits:
        return list(cfg.commits)
    return [(int((k + 1) % n), k, 4, 2) for k in range(cfg.keys)]


def inject_args(cfg: LogConfig, n: int) -> tuple:
    """Lower the send + commit programs to the 8-operand tuple (module
    doc), padded to a power-of-two bucket so same-arity programs are
    shape-identical and share one compiled loop."""
    sends = log_sends(cfg, n)
    commits = log_commits(cfg, n)
    bad = [s for s in sends if s[0] >= n] + \
        [c for c in commits if c[0] >= n]
    if bad:
        raise ValueError(f"log sends/commits reference node ids >= "
                         f"n={n}: {bad}")

    def quad(items):
        a_pad = _pad_pow2(len(items)) if items else _pad_pow2(0)
        cols = [[it[j] for it in items] for j in range(4)]
        cols[0] += [0] * (a_pad - len(items))            # node
        cols[1] += [0] * (a_pad - len(items))            # key
        cols[2] += [NO_ROUND] * (a_pad - len(items))     # round
        cols[3] += [0] * (a_pad - len(items))            # value/upto
        return tuple(jnp.asarray(c, jnp.int32) for c in cols)

    return quad(sends) + quad(commits)


def split_inject(cfg: LogConfig, tbl: tuple):
    """(head_tables, inject_operands): peel the 8 operands
    :func:`inject_args` appended back off a step's ``*tables`` tail —
    the ONE inverse (the nemesis split_tables discipline)."""
    return tbl[:-N_INJECT_OPERANDS], tbl[-N_INJECT_OPERANDS:]


def send_offsets(s_key: jax.Array, applied: jax.Array) -> jax.Array:
    """int32[A]: each send's offset within its key — the count of
    APPLIED sends with the same key at a strictly earlier script index
    (module doc: script order is round-nondecreasing per key by
    LogConfig contract, so offset order is time order; unapplied sends
    are compacted over).  O(A^2) pairwise compare on the tiny padded
    list — in-trace, shapes only."""
    a = s_key.shape[0]
    idx = jnp.arange(a, dtype=jnp.int32)
    earlier = idx[None, :] < idx[:, None]                 # [A, A]
    same_key = s_key[None, :] == s_key[:, None]
    return jnp.sum(earlier & same_key & applied[None, :],
                   axis=1, dtype=jnp.int32)


def _send_plan(cfg: LogConfig, inj: tuple, fault, n: int, origin: int):
    """The shared in-trace decomposition of the 8 operands: applied
    masks, per-send flat slot indices, per-key truth lengths, and
    per-commit clamped values — used by BOTH the in-loop injection and
    the ground truth so the two can never drift."""
    s_node, s_key, s_round, s_val = inj[:4]
    c_node, c_key, c_round, c_upto = inj[4:]
    alive_fn = alive_at_fn(fault, n, origin)
    eventual = eventual_alive_crdt(fault, n, origin)
    applied_s = _applied_mask(s_round, s_node, alive_fn, eventual)
    off = send_offsets(s_key, applied_s)
    slot = s_key * cfg.capacity + off                     # flat entry col
    truth_len = jnp.zeros((cfg.keys,), jnp.int32).at[s_key].add(
        jnp.where(applied_s, 1, 0), mode="drop")
    applied_c = _applied_mask(c_round, c_node, alive_fn, eventual)
    cval = jnp.minimum(c_upto, truth_len[c_key])
    return applied_s, slot, truth_len, applied_c, cval


def ground_truth(cfg: LogConfig, inj: tuple, fault, n: int,
                 origin: int) -> jax.Array:
    """The merged row ``[S]`` every eventually-alive node must reach:
    entry slots from the applied sends at their compacted offsets,
    committed counts = max over applied commits of the clamped value
    (module doc).  In-trace, integer-exact, built from the SAME
    operands + liveness predicate as :func:`inject_rows`."""
    s_val = inj[3]
    c_key = inj[5]
    applied_s, slot, _, applied_c, cval = _send_plan(cfg, inj, fault,
                                                     n, origin)
    ent = jnp.zeros((cfg.keys * cfg.capacity,), jnp.int32).at[slot].max(
        jnp.where(applied_s, s_val, 0), mode="drop")
    com = jnp.zeros((cfg.keys,), jnp.int32).at[c_key].max(
        jnp.where(applied_c, cval, 0), mode="drop")
    return jnp.concatenate([ent, com])


def inject_rows(cfg: LogConfig, inj: tuple, gids: jax.Array, round_,
                n: int, origin: int, fault) -> jax.Array:
    """The rows each node max-merges into its OWN state at ``round_``
    — ``int32[len(gids), S]``, zero except where this round's applied
    sends/commits land on a ``gids`` row (the appender/committer owns
    the write — the owner-indexed slot discipline)."""
    r = jnp.asarray(round_, jnp.int32)
    s_node, _, s_round, s_val = inj[:4]
    c_node, c_key, c_round, _ = inj[4:]
    applied_s, slot, _, applied_c, cval = _send_plan(cfg, inj, fault,
                                                     n, origin)
    nl = gids.shape[0]
    s_width = state_width(cfg)
    fire_s = (s_round == r) & applied_s
    mine_s = s_node[None, :] == gids[:, None]             # [Nl, A]
    ent = jnp.zeros((nl, s_width), jnp.int32).at[:, slot].max(
        jnp.where(fire_s[None, :] & mine_s, s_val[None, :], 0),
        mode="drop")
    fire_c = (c_round == r) & applied_c
    mine_c = c_node[None, :] == gids[:, None]
    com_col = cfg.keys * cfg.capacity + c_key
    return ent.at[:, com_col].max(
        jnp.where(fire_c[None, :] & mine_c, cval[None, :], 0),
        mode="drop")


# -- readouts ----------------------------------------------------------

def log_len(cfg: LogConfig, rows: jax.Array) -> jax.Array:
    """int32[..., K]: contiguous filled-prefix length per key — the
    per-key append cursor derived from the entry planes (a replica may
    transiently hold later slots before earlier ones; polls serve the
    contiguous prefix, the gapless contract)."""
    ent = rows[..., :cfg.keys * cfg.capacity]
    filled = ent.reshape(ent.shape[:-1] + (cfg.keys, cfg.capacity)) != 0
    return jnp.sum(jnp.cumprod(filled.astype(jnp.int32), axis=-1),
                   axis=-1, dtype=jnp.int32)


def committed_of(cfg: LogConfig, rows: jax.Array) -> jax.Array:
    """int32[..., K]: the committed-count vector columns."""
    return rows[..., cfg.keys * cfg.capacity:]


def payload_count(cfg: LogConfig, rows: jax.Array,
                  alive: jax.Array) -> jax.Array:
    """f32 total payload mass over alive rows — filled entry slots +
    committed counts, both monotone under the max merge, so the
    per-round delta (the ``newly`` counter) is exact."""
    ent = rows[..., :cfg.keys * cfg.capacity]
    com = committed_of(cfg, rows)
    filled = jnp.where(alive[:, None], (ent != 0).astype(jnp.int32), 0)
    return (jnp.sum(filled, dtype=jnp.float32)
            + jnp.sum(jnp.where(alive[:, None], com, 0),
                      dtype=jnp.float32))


def truth_summary(cfg: LogConfig, truth) -> dict:
    """Human-readable ground truth for reports and the CLI: per-key
    acked lengths and committed counts, integer-exact."""
    import numpy as np
    truth = np.asarray(truth)
    ent = truth[:cfg.keys * cfg.capacity].reshape(cfg.keys,
                                                  cfg.capacity)
    lens = [int((row != 0).cumprod().sum()) for row in ent]
    committed = [int(c) for c in truth[cfg.keys * cfg.capacity:]]
    return {"lens": lens, "committed": committed,
            "total_entries": int(sum(lens))}


# -- the exchange drivers' refusals and the payload row -----------------

def check_log_mode(proto) -> None:
    """Pull only: state-based dissemination IS the pull/digest
    exchange, and the push half would need a scatter-max collective
    XLA does not have (the ops/crdt precedent).  One loud reason,
    shared by every driver and the CLI."""
    if proto.mode != PULL:
        raise ValueError(
            "replicated-log rounds run the pull exchange only "
            "(state-based merge IS the digest pull; got mode "
            f"{proto.mode!r} — the push half would need a scatter-max "
            "collective XLA does not have, the models/crdt precedent)")


def check_injections_reachable(cfg: LogConfig, run) -> None:
    """Every scripted send/commit must fire inside the run (the
    ops/crdt rule: an unreachable injection makes ground truth
    unreachable by construction — a loud error, never a quiet
    converged:false)."""
    last = cfg.horizon() - 1
    if last >= run.max_rounds:
        raise ValueError(
            f"log send/commit at round {last} can never fire: the run "
            f"stops after max_rounds={run.max_rounds} rounds, so "
            "ground truth would be unreachable by construction — "
            "raise --max-rounds past the last scripted round")


# Writes are max-merges: an entry slot is set once by its owner,
# commits only ever raise.
ROW = PayloadRow(
    label="log", column="log_conv", engine="log-pull",
    check_mode=check_log_mode,
    check_reachable=check_injections_reachable,
    width=lambda cfg, n: state_width(cfg),
    dtype=lambda cfg: jnp.int32,
    inject_args=inject_args, split_inject=split_inject,
    inject_rows=inject_rows,
    apply_inject=lambda cfg, val, rows: merge_max(val, rows),
    merge=lambda cfg, a, b: merge_max(a, b),
    pull_merge=lambda cfg, rows, partners, n: pull_merge_log(
        rows, partners, n),
    ground_truth=ground_truth, payload_count=payload_count,
    truth_summary=lambda cfg, truth, n: truth_summary(cfg, truth))
