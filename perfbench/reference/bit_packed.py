"""Plain reference for the bit-packed XLA engines (one chip and node-sharded).

The semantics, written out from their definition and not from the engine:
every round each alive node draws ``fanout`` partners uniformly from the
other ``n - 1`` nodes, loses a pull to the round's drop coin or to an open
partition cut, and ORs in what its alive partners had seen at the start of
the round.  The partner and drop streams are part of the semantics (the
repo pins the packed engines bitwise against the dense ones): per-node keys
are ``fold_in(fold_in(fold_in(key(seed), round), tag), node)`` with tag 2
for partners and 4 for drop coins, drawn with ``jax.random`` directly.

The state here is a dense ``bool[n, rumors]`` and the gather is a plain
index, so nothing of the engine's word packing, sharding or schedule
lowering is reused.  The run stops, as the engine's does, on the first
round whose float32 coverage over the eventually-alive nodes reaches the
target.
"""

import numpy as np

import jax
import jax.numpy as jnp

PULL_TAG = 2
PULL_DROP_TAG = 4


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
    if cfg["protocol"]["mode"] != "pull":
        raise ValueError("this reference runs pull rounds only")
    if cfg["topology"]["family"] != "complete":
        raise ValueError("this reference runs the implicit complete graph")
    if fault.get("node_death_rate", 0.0):
        raise ValueError("this reference has no static death draw")


def make(cfg, fault):
    """``seed -> answer`` for configuration ``cfg`` under ``fault`` (the
    traffic's resolved fault program).  The answer holds the numbers the
    program reports: rounds, float32 coverage, msgs, and the exact count
    of covered eventually-alive nodes with its denominator."""
    _check_supported(cfg, fault)
    n = int(cfg["topology"]["n"])
    proto, run = cfg["protocol"], cfg["run"]
    k, rumors = int(proto["fanout"]), int(proto["rumors"])
    max_rounds = int(run["max_rounds"])
    target = np.float32(run["target_coverage"])
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

    def coverage(seen, ev_alive, denom):
        counts = jnp.sum(seen & ev_alive[:, None], axis=0, dtype=jnp.int32)
        cov = jnp.min(counts.astype(jnp.float32) / denom.astype(jnp.float32))
        return cov, jnp.min(counts)

    @jax.jit
    def simulate(key):
        ids = jnp.arange(n, dtype=jnp.int32)
        ev_alive = jnp.ones((n,), bool).at[ev_dead].set(False)
        denom = jnp.sum(ev_alive, dtype=jnp.int32)
        cuts, drops = jnp.asarray(cut_tbl), jnp.asarray(drop_tbl)
        seen = jnp.zeros((n, rumors), bool).at[
            (origin + jnp.arange(rumors)) % n, jnp.arange(rumors)].set(True)

        def cond(c):
            seen, r, _ = c
            return (coverage(seen, ev_alive, denom)[0] < target) & (
                r < max_rounds)

        def body(c):
            seen, r, msgs = c
            alive = alive_at(r)
            rkey = jax.random.fold_in(key, r)
            pkeys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(rkey, PULL_TAG), ids)
            draw = jax.vmap(lambda kk: jax.random.randint(
                kk, (k,), 0, n - 1, dtype=jnp.int32))(pkeys)
            partner = draw + (draw >= ids[:, None]).astype(jnp.int32)
            dkeys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(rkey, PULL_DROP_TAG), ids)
            p = drops[jnp.minimum(r, max_rounds - 1)]
            dropped = jax.vmap(lambda kk: jax.random.bernoulli(
                kk, p, (k,)))(dkeys)
            cut = cuts[jnp.minimum(r, max_rounds - 1)]
            crosses = (cut >= 0) & ((ids[:, None] >= cut) != (partner >= cut))
            sent = alive[:, None] & ~dropped & ~crosses        # [n, k]
            served = seen & alive[:, None]                     # [n, R]
            got = jnp.zeros_like(seen)
            for j in range(k):
                got = got | (served[partner[:, j]] & sent[:, j, None])
            n_req = jnp.sum(sent, dtype=jnp.int32).astype(jnp.float32)
            return seen | got, r + 1, msgs + jnp.float32(2.0) * n_req

        seen, r, msgs = jax.lax.while_loop(
            cond, body, (seen, jnp.int32(0), jnp.float32(0.0)))
        cov, count = coverage(seen, ev_alive, denom)
        return r, cov, msgs, count, denom

    def answer(seed):
        r, cov, msgs, count, denom = jax.device_get(
            simulate(jax.random.key(seed)))
        return {"rounds": int(r), "coverage": float(cov), "msgs": float(msgs),
                "count": int(count), "denom": int(denom)}

    return answer


def control(cfg, fault, name):
    """The reference with one guarantee of the configuration broken —
    what ``correct`` must refuse (PERF.md, "How correct is decided")."""
    if name == "no_partition":
        return make(cfg, dict(fault, partitions=[]))
    raise ValueError(f"bit_packed reference has no control {name!r}")
