"""Plain reference for the rumor-mongering engine (push, blind counter).

The semantics, written out from their definition (Demers et al., PODC
1987, sec. 1.4; memberlist's ``TransmitLimitedQueue``) and not from the
engine: every round each alive node that still holds a hot rumor draws
``fanout`` targets uniformly from the other ``n - 1`` nodes and sends
the rumor to as many of them, in draw order, as its transmit budget has
left: ``rumor_k`` sends in all, each counted whether it arrives or not.
A send is lost to the round's drop coin or to an open partition cut; a
crashed receiver takes nothing.  A node that receives a rumor it had
not seen holds it hot from the next round on; one that has spent its
budget, or is down in this round, holds it no longer.  The run stops at
extinction, when no rumor is hot anywhere, or at ``max_rounds``.

The target and drop streams are part of the semantics: per-node keys are
``fold_in(fold_in(fold_in(key(seed), round), tag), node)`` with tag 11
for targets and 12 for drop coins, drawn with ``jax.random`` directly.
The partition cut and churn come as in ``reference/bit_packed.py``.

The state is dense ``bool``/``int32`` arrays, deliveries are counted by
a scatter-add of ones per target slot, and coverage counts covered
eventually-alive nodes, so nothing of the engine's scatter-max, quota
mask or schedule lowering is reused.
"""

import numpy as np

import jax
import jax.numpy as jnp

PUSH_TAG = 11
DROP_TAG = 12


def _round_tables(fault, rounds):
    """(cut int32[rounds], drop float32[rounds]) of the fault program."""
    cut = np.full((rounds,), -1, np.int32)
    drop = np.full((rounds,), float(fault.get("drop_prob", 0.0)), np.float64)
    for start, end, c in fault.get("partitions", ()):
        cut[start:end] = c
    ramp = fault.get("ramp")
    if ramp is not None:
        start, end, p0, p1 = ramp
        for r in range(start, rounds):
            drop[r] = p0 + (p1 - p0) * min((r - start) / max(end - start, 1),
                                           1.0)
    return cut, drop.astype(np.float32)


def _check_supported(cfg, fault):
    proto = cfg["protocol"]
    if proto["mode"] != "rumor" or proto.get("rumor_variant") != "blind":
        raise ValueError("this reference runs blind-counter rumor mongering "
                         "only")
    if cfg["topology"]["family"] != "complete":
        raise ValueError("this reference runs the implicit complete graph")
    if fault.get("node_death_rate", 0.0):
        raise ValueError("this reference has no static death draw")


def make(cfg, fault):
    """``seed -> answer`` for configuration ``cfg`` under ``fault`` (the
    traffic's resolved fault program).  The answer holds the numbers the
    program reports: the extinction round (-1 if rumors are still hot
    at ``max_rounds``), float32 coverage over the eventually-alive
    nodes (the least over rumors), msgs (sends that passed the drop coin
    and the cut), and the exact covered count with its denominator."""
    _check_supported(cfg, fault)
    n = int(cfg["topology"]["n"])
    proto, run = cfg["protocol"], cfg["run"]
    k, budget = int(proto["fanout"]), int(proto["rumor_k"])
    rumors = int(proto["rumors"])
    max_rounds = int(run["max_rounds"])
    origin = int(run.get("origin", 0))
    events = [tuple(e) for e in fault.get("events", ())]
    cut_tbl, drop_tbl = _round_tables(fault, max_rounds)
    ev_dead = np.asarray([e[0] for e in events if e[2] < 0], np.int32)

    def alive_at(r):
        alive = jnp.ones((n,), bool)
        for node, die, rec in events:
            down = (die <= r) & ((rec < 0) | (r < rec))
            alive = alive.at[node].set(~down)
        return alive

    @jax.jit
    def simulate(key):
        ids = jnp.arange(n, dtype=jnp.int32)
        ev_alive = jnp.ones((n,), bool).at[ev_dead].set(False)
        denom = jnp.sum(ev_alive, dtype=jnp.int32)
        cuts, drops = jnp.asarray(cut_tbl), jnp.asarray(drop_tbl)
        seen = jnp.zeros((n, rumors), bool).at[
            (origin + jnp.arange(rumors)) % n, jnp.arange(rumors)].set(True)
        sent_so_far = jnp.zeros((n, rumors), jnp.int32)

        def cond(c):
            _, hot, _, r, _ = c
            return jnp.any(hot) & (r < max_rounds)

        def body(c):
            seen, hot, sent_so_far, r, msgs = c
            alive = alive_at(r)
            rkey = jax.random.fold_in(key, r)
            tkeys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(rkey, PUSH_TAG), ids)
            draw = jax.vmap(lambda kk: jax.random.randint(
                kk, (k,), 0, n - 1, dtype=jnp.int32))(tkeys)
            target = draw + (draw >= ids[:, None]).astype(jnp.int32)
            dkeys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(rkey, DROP_TAG), ids)
            p = drops[jnp.minimum(r, max_rounds - 1)]
            dropped = jax.vmap(lambda kk: jax.random.bernoulli(
                kk, p, (k,)))(dkeys)
            cut = cuts[jnp.minimum(r, max_rounds - 1)]
            crosses = (cut >= 0) & ((ids[:, None] >= cut) != (target >= cut))
            arrives = ~dropped & ~crosses                       # [n, k]
            holder = hot & alive[:, None]                       # [n, R]
            received = jnp.zeros((n, rumors), jnp.int32)
            msgs_r = jnp.int32(0)
            for j in range(k):
                # the j-th send of a rumor happens while budget is left
                send_j = holder & (sent_so_far + j < budget)    # [n, R]
                ones = (send_j & arrives[:, j, None]).astype(jnp.int32)
                received = received.at[target[:, j]].add(ones)
                msgs_r = msgs_r + jnp.sum(jnp.any(send_j, axis=1)
                                          & arrives[:, j], dtype=jnp.int32)
            sends = jnp.minimum(budget - sent_so_far, k)
            sent_so_far = sent_so_far + jnp.where(holder, sends, 0)
            got = (received > 0) & alive[:, None]
            hot = ((hot & (sent_so_far < budget)) | (got & ~seen)) \
                & alive[:, None]
            return (seen | got, hot, sent_so_far, r + 1,
                    msgs + msgs_r.astype(jnp.float32))

        seen, hot, _, r, msgs = jax.lax.while_loop(
            cond, body, (seen, seen, sent_so_far, jnp.int32(0),
                         jnp.float32(0.0)))
        counts = jnp.sum(seen & ev_alive[:, None], axis=0, dtype=jnp.int32)
        cov = jnp.min(counts.astype(jnp.float32) / denom.astype(jnp.float32))
        return r, jnp.any(hot), cov, msgs, jnp.min(counts), denom

    def answer(seed):
        r, hot, cov, msgs, count, denom = jax.device_get(
            simulate(jax.random.key(seed)))
        return {"rounds": -1 if hot else int(r), "coverage": float(cov),
                "msgs": float(msgs), "count": int(count),
                "denom": int(denom)}

    return answer


def control(cfg, fault, name):
    """The reference with one guarantee of the configuration broken —
    what ``correct`` must refuse (PERF.md, "How correct is decided")."""
    if name == "no_partition":
        return make(cfg, dict(fault, partitions=[]))
    raise ValueError(f"rumor_xla reference has no control {name!r}")
