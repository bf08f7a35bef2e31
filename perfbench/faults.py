"""Faults planted in the program under test, to show that ``correct``
catches each (the benchmark's tests, and control.py on the chip).  Each
takes pytest's ``monkeypatch`` (or anything with its ``setattr``) and
breaks the timed path underneath the harness."""

import functools

import jax
import jax.numpy as jnp


def _packed_merge(monkeypatch, wrap):
    from gossip_tpu.models import si_packed
    from gossip_tpu.parallel import sharded_packed
    real = si_packed.pull_merge_packed
    for mod in (si_packed, sharded_packed):
        monkeypatch.setattr(mod, "pull_merge_packed", wrap(real))


def state_unchanged(monkeypatch):
    """Every round returns the state it was given: no pull brings
    anything (packed engines), or the fused round is the identity."""
    from gossip_tpu.ops import pallas_round
    _packed_merge(monkeypatch, lambda real: (
        lambda table, partners, sentinel: jnp.zeros(
            (partners.shape[0], table.shape[1]), table.dtype)))
    monkeypatch.setattr(pallas_round, "fused_multirumor_pull_round",
                        lambda table, *a, **kw: table)


def half_left_out(monkeypatch):
    """The upper half of the nodes never merges what it pulled."""
    from gossip_tpu.ops import pallas_round

    def wrap(real):
        def merge(table, partners, sentinel):
            out = real(table, partners, sentinel)
            half = jnp.arange(out.shape[0]) >= out.shape[0] // 2
            return jnp.where(half[:, None], jnp.uint32(0), out)
        return merge
    _packed_merge(monkeypatch, wrap)
    real_round = pallas_round.fused_multirumor_pull_round

    def fused(table, *a, **kw):
        out = real_round(table, *a, **kw)
        half = jnp.arange(out.shape[0])[:, None] >= out.shape[0] // 2
        return jnp.where(half, table, out)
    monkeypatch.setattr(pallas_round, "fused_multirumor_pull_round", fused)


def exchange_left_out(monkeypatch):
    """The node-sharded exchange gathers only the local shard: each
    device sees its own rows where the others' belong."""
    real = jax.lax.all_gather

    def local_only(x, axis_name, *, tiled=False, **kw):
        if not tiled:
            return real(x, axis_name, tiled=tiled, **kw)
        return jnp.concatenate([x] * jax.lax.axis_size(axis_name), axis=0)

    monkeypatch.setattr(jax.lax, "all_gather", local_only)


def answer_altered(monkeypatch):
    """The report's rounds are one off where run_simulation makes them."""
    from gossip_tpu import backend
    real = backend.run_jax

    @functools.wraps(real)
    def altered(*a, **kw):
        rep = real(*a, **kw)
        rep.rounds = rep.rounds + 1
        return rep
    monkeypatch.setattr(backend, "run_jax", altered)


ALL = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
       "exchange_left_out": exchange_left_out,
       "answer_altered": answer_altered}
