"""Plain reference for the fused multi-rumor engine on one chip, for tables
too large for VMEM (the staged path: every 10M-node cell).

The fused engine defines its partner draw by the chip's layout: node ``v``
sits at row ``i = v // 128``, lane ``j = v % 128`` of a table of ``rows``
rows.  Each round draws 128 per-lane row shifts ``s`` from threefry
(``fold_in(PRNGKey(uint32(seed) * 1000003 + 0x5D0), round)``, first row of
an ``(8, 128)`` draw, modulo ``rows``) and, per node, a lane
``m = b & 127`` and a drop coin ``b >> 12`` from the chip's hardware PRNG,
seeded per block of 1024 rows with ``(int32(seed) * 1000003,
(round ^ 0x5D0) + block * -1640531527)``.  Node ``v`` pulls from node
``((i - s[m]) mod rows) * 128 + m``; a pull from a phantom id (``>= n``)
brings nothing.

The hardware stream cannot be computed off the chip, so the one piece of
Pallas here (:func:`hw_bits`) only seeds the PRNG and writes its bits out.
Everything else is a flat per-node gather of ``uint32`` rumor words, with
no roll decomposition, no in-row gather and no blocking, so it shares
nothing with the engine but the definition.  Off the chip the interpreter
draws zeros, as the engine's own off-chip lowering does.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK = 1024
MIX = 1000003
SALT = 0x5D0
BLOCK_STEP = -1640531527


def on_chip():
    return jax.default_backend() == "tpu"


def table_rows(n):
    r = -(-n // LANES)
    return max(8, -(-r // 8) * 8)


@functools.partial(jax.jit, static_argnames=("rows_pad", "block"))
def hw_bits(seed, round_, rows_pad, block):
    """uint32[rows_pad, 128]: the hardware PRNG's draw for one round."""
    seeds = jnp.stack([jnp.asarray(seed, jnp.int32) * jnp.int32(MIX),
                       jnp.asarray(round_, jnp.int32) ^ jnp.int32(SALT)])

    def kernel(seed_ref, out_ref):
        b = pl.program_id(0)
        pltpu.prng_seed(seed_ref[0], seed_ref[1] + b * jnp.int32(BLOCK_STEP))
        out_ref[:] = pltpu.bitcast(pltpu.prng_random_bits((block, LANES)),
                                   jnp.uint32)

    return pl.pallas_call(
        kernel, grid=(rows_pad // block,),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        interpret=False if on_chip() else pltpu.InterpretParams())(seeds)


def make(cfg, fault, drop_prob=None):
    """``seed -> answer`` (rounds with -1 for a missed target, float32
    min-over-rumors coverage, msgs, and the exact least per-rumor count
    over its denominator ``n``)."""
    if cfg["protocol"]["mode"] != "pull" or int(cfg["protocol"]["fanout"]) != 1:
        raise ValueError("this reference runs fanout-1 pull rounds only")
    if any(fault.get(k) for k in ("events", "partitions", "ramp",
                                  "node_death_rate", "drop_prob")):
        raise ValueError("this reference runs the fault-free engine only")
    n = int(cfg["topology"]["n"])
    rumors = int(cfg["protocol"]["rumors"])
    run = cfg["run"]
    max_rounds = int(run["max_rounds"])
    target = np.float32(run["target_coverage"])
    origin = int(run.get("origin", 0))
    rows = table_rows(n)
    block = min(BLOCK, rows)
    rows_pad = -(-rows // block) * block
    thr = int(round((drop_prob or 0.0) * (1 << 20)))

    def coverage(words):
        bits = (words[:, None] >> jnp.arange(rumors, dtype=jnp.uint32)) & 1
        counts = jnp.sum(bits, axis=0, dtype=jnp.int32)
        least = jnp.min(counts)
        return least.astype(jnp.float32) / jnp.float32(n), least

    def one_round(words, seed, r):
        key = jax.random.PRNGKey(
            jnp.uint32(jnp.asarray(seed, jnp.int32)) * jnp.uint32(MIX)
            + jnp.uint32(SALT))
        s = jax.random.bits(jax.random.fold_in(key, r), (8, LANES),
                            jnp.uint32)[0] % jnp.uint32(rows)
        b = hw_bits(seed, r, rows_pad, block).reshape(-1)[:n]
        v = jnp.arange(n, dtype=jnp.int32)
        m = (b & jnp.uint32(LANES - 1)).astype(jnp.int32)
        src_row = (v // LANES - s[m].astype(jnp.int32)) % rows
        src = src_row * LANES + m
        pulled = jnp.where(src < n, words[jnp.minimum(src, n - 1)],
                           jnp.uint32(0))
        keep = (b >> jnp.uint32(12)) >= jnp.uint32(thr)
        return words | jnp.where(keep, pulled, jnp.uint32(0))

    @jax.jit
    def simulate(seed):
        words = jnp.zeros((n,), jnp.uint32).at[
            (origin + jnp.arange(rumors)) % n].add(
                jnp.uint32(1) << jnp.arange(rumors, dtype=jnp.uint32))

        def cond(c):
            words, r, _ = c
            return (coverage(words)[0] < target) & (r < max_rounds)

        def body(c):
            words, r, msgs = c
            return (one_round(words, seed, r), r + 1,
                    msgs + jnp.float32(2.0 * n))

        words, r, msgs = jax.lax.while_loop(
            cond, body, (words, jnp.int32(0), jnp.float32(0.0)))
        cov, least = coverage(words)
        return r, cov, msgs, least

    def answer(seed):
        r, cov, msgs, least = jax.device_get(
            simulate(jnp.int32(np.int64(seed).astype(np.int32))))
        hit = np.float32(cov) >= target
        return {"rounds": int(r) if hit else -1, "coverage": float(cov),
                "msgs": float(msgs), "count": int(least), "denom": n}

    return answer


def control(cfg, fault, name):
    """The reference with one guarantee of the configuration broken."""
    if name == "lossy_links":
        # the mix promises reliable links; this loses 1% of pulls
        return make(cfg, fault, drop_prob=0.01)
    raise ValueError(f"fused reference has no control {name!r}")
