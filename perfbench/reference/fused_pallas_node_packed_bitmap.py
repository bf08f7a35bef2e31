"""Plain reference for the fused single-rumor engine on one chip (the
node-packed bitmap, which ``bcast10m`` under fault-free traffic runs).

The fused engine defines its partner draw by the chip's layout: node
``v`` is bit ``v % 32`` of word ``w = v // 32``, and word ``w`` sits at
row ``i = w // 128``, lane ``j = w % 128`` of a table of ``rows`` rows.
Each round the kernel seeds the chip's hardware PRNG once with
``(int32(seed) * 1000003, round)`` and draws, in this order, an
``(8, 128)`` block whose first row gives 128 per-lane row shifts
``s = bits % rows``, then one ``(rows, 128)`` block ``b`` per bit
plane ``c0 = 0..31`` (per fanout draw).  The node of word ``(i, j)``
and bit ``c0`` pulls bit ``c = (b >> 7) & 31`` of the word at row
``(i - s[m]) mod rows``, lane ``m = b & 127``, i.e. from node
``(((i - s[m]) mod rows) * 128 + m) * 32 + c``, and keeps it when the
drop coin ``b >> 12`` is at least the 20-bit drop threshold.  A pull
from a phantom id (``>= n``) brings nothing.

The hardware stream cannot be computed off the chip, so the one piece of
Pallas here (:func:`hw_bits`) only seeds the PRNG, draws the same
blocks in the same order and writes them out.  Everything else is a
flat per-node gather of ``bool`` states, with no word packing, roll
decomposition or in-row gather, so it shares nothing with the engine but
the definition.  Off the chip the interpreter draws zeros, as the
engine's own off-chip lowering does.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BITS = 32
NODES_PER_ROW = LANES * BITS
MIX = 1000003
VMEM_LIMIT = 110 * 1024 * 1024


def on_chip():
    return jax.default_backend() == "tpu"


def table_rows(n):
    r = -(-n // NODES_PER_ROW)
    return max(8, -(-r // 8) * 8)


@functools.partial(jax.jit, static_argnames=("rows", "draws"))
def hw_bits(seed, round_, rows, draws):
    """(uint32[8, 128], uint32[draws, rows, 128]): one round's hardware
    PRNG draws, in the kernel's order."""
    seeds = jnp.stack([jnp.asarray(seed, jnp.int32) * jnp.int32(MIX),
                       jnp.asarray(round_, jnp.int32)])

    def kernel(seed_ref, s_ref, b_ref):
        pltpu.prng_seed(seed_ref[0], seed_ref[1])
        s_ref[:] = pltpu.bitcast(pltpu.prng_random_bits((8, LANES)),
                                 jnp.uint32)
        for d in range(draws):
            b_ref[d] = pltpu.bitcast(pltpu.prng_random_bits((rows, LANES)),
                                     jnp.uint32)

    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((8, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((draws, rows, LANES), jnp.uint32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)
        if on_chip() else None,
        interpret=False if on_chip() else pltpu.InterpretParams())(seeds)


def make(cfg, fault, drop_prob=None):
    """``seed -> answer`` (rounds with -1 for a missed target, float32
    coverage, msgs, and the exact informed count over its denominator
    ``n``)."""
    if cfg["protocol"]["mode"] != "pull" or int(cfg["protocol"]["rumors"]) != 1:
        raise ValueError("this reference runs single-rumor pull rounds only")
    if any(fault.get(k) for k in ("events", "partitions", "ramp",
                                  "node_death_rate", "drop_prob")):
        raise ValueError("this reference runs the fault-free engine only")
    n = int(cfg["topology"]["n"])
    fanout = int(cfg["protocol"]["fanout"])
    run = cfg["run"]
    max_rounds = int(run["max_rounds"])
    target = np.float32(run["target_coverage"])
    origin = int(run.get("origin", 0))
    rows = table_rows(n)
    thr = int(round((drop_prob or 0.0) * (1 << 20)))

    def coverage(seen):
        count = jnp.sum(seen, dtype=jnp.int32)
        return count.astype(jnp.float32) / jnp.float32(n), count

    def one_round(seen, seed, r):
        sbits, b = hw_bits(seed, r, rows, BITS * fanout)
        s = (sbits[0] % jnp.uint32(rows)).astype(jnp.int32)        # [128]
        v = jnp.arange(n, dtype=jnp.int32)
        w = v // BITS
        i, c0 = w // LANES, v % BITS
        got = jnp.zeros((n,), bool)
        for f in range(fanout):
            bits = b[c0 * fanout + f, i, w % LANES]                  # [n]
            m = (bits & jnp.uint32(LANES - 1)).astype(jnp.int32)
            c = ((bits >> jnp.uint32(7)) & jnp.uint32(BITS - 1)).astype(
                jnp.int32)
            src = (((i - s[m]) % rows) * LANES + m) * BITS + c
            pulled = jnp.where(src < n, seen[jnp.minimum(src, n - 1)], False)
            keep = (bits >> jnp.uint32(12)) >= jnp.uint32(thr)
            got = got | (pulled & keep)
        return seen | got

    @jax.jit
    def simulate(seed):
        seen = jnp.zeros((n,), bool).at[origin].set(True)

        def cond(c):
            seen, r, _ = c
            return (coverage(seen)[0] < target) & (r < max_rounds)

        def body(c):
            seen, r, msgs = c
            return (one_round(seen, seed, r), r + 1,
                    msgs + jnp.float32(2.0 * fanout * n))

        seen, r, msgs = jax.lax.while_loop(
            cond, body, (seen, jnp.int32(0), jnp.float32(0.0)))
        cov, count = coverage(seen)
        return r, cov, msgs, count

    def answer(seed):
        r, cov, msgs, count = jax.device_get(
            simulate(jnp.int32(np.int64(seed).astype(np.int32))))
        hit = np.float32(cov) >= target
        return {"rounds": int(r) if hit else -1, "coverage": float(cov),
                "msgs": float(msgs), "count": int(count), "denom": n}

    return answer


def control(cfg, fault, name):
    """The reference with one guarantee of the configuration broken."""
    if name == "lossy_links":
        # the mix promises reliable links; this loses 1% of pulls
        return make(cfg, fault, drop_prob=0.01)
    raise ValueError(f"fused reference has no control {name!r}")
