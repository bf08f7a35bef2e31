"""Propagation kernels: one gossip round's data movement.

The reference's propagation is the hot loop at main.go:72-88 — sequential
blocking RPC per neighbor, at-least-once via retry, idempotent receipt via the
dedup set (main.go:113).  Batched on TPU this becomes pure data movement:

  * **push**  — scatter: each active node writes its digest row at k sampled
    target rows.  Idempotence is free (OR/max semantics == the dedup set); the
    TOCTOU duplicate-append race of the reference (SURVEY.md §2.2.5) cannot
    exist because a round is one atomic XLA program.
  * **pull**  — gather: each node reads k sampled peers' digest rows and ORs
    them in.
  * **flood** — gather over the *whole* padded neighbor row (Go-parity mode:
    relay-to-all, main.go:72-75).

Push comes in two flavors: boolean scatter-max for single-device, and int32
scatter-add (``push_counts``) whose output is summable across shards with
``psum_scatter`` — OR is not an XLA collective reduction, + is, and
``count > 0`` == OR.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _flat_payload(targets: jax.Array, payload: jax.Array, dtype) -> tuple:
    """[Nl,k] targets + [Nl,R] (or per-target [Nl,k,R]) payload -> flat
    ([Nl*k], [Nl*k,R]) pairs."""
    nl, k = targets.shape
    r = payload.shape[-1]
    flat_t = targets.reshape(-1)
    if payload.ndim == 2:
        payload = payload[:, None, :]
    flat_p = jnp.broadcast_to(payload, (nl, k, r))
    return flat_t, flat_p.reshape(nl * k, r).astype(dtype)


def push_delta(n: int, targets: jax.Array, payload: jax.Array) -> jax.Array:
    """Single-device push: bool[N,R] delta via scatter-max.

    ``targets`` holds global ids in [0, n) or the sentinel ``n`` (dropped).
    ``payload[i]`` is what node i pushes (its active digest row), or
    ``payload[i, j]`` what it pushes to its j-th target.
    """
    flat_t, flat_p = _flat_payload(targets, payload, jnp.bool_)
    zero = jnp.zeros((n, payload.shape[-1]), jnp.bool_)
    return zero.at[flat_t].max(flat_p, mode="drop")


def push_counts(n: int, targets: jax.Array, payload: jax.Array) -> jax.Array:
    """Sharded push: int32[N,R] receive-counts via scatter-add.

    Summable across shards (``lax.psum_scatter``); ``counts > 0`` is the OR.
    int32 because several pushers may hit the same row in the same round.
    """
    flat_t, flat_p = _flat_payload(targets, payload, jnp.int32)
    zero = jnp.zeros((n, payload.shape[-1]), jnp.int32)
    return zero.at[flat_t].add(flat_p, mode="drop")


def pull_merge(seen_all: jax.Array, partners: jax.Array,
               valid_sentinel: int) -> jax.Array:
    """Pull: OR of k sampled peers' digest rows -> bool[N_local, R].

    ``seen_all`` is the full (or all-gathered) ``bool[N, R]`` digest table;
    ``partners`` is ``int32[N_local, k]`` with sentinel entries masked out.
    """
    valid = partners < valid_sentinel            # [Nl, k]
    safe = jnp.minimum(partners, valid_sentinel - 1)
    got = seen_all[safe]                         # [Nl, k, R]
    got = got & valid[:, :, None]
    return jnp.any(got, axis=1)                  # [Nl, R]


def flood_gather(seen_all: jax.Array, nbrs_local: jax.Array,
                 n: int) -> jax.Array:
    """Flood (Go-parity): OR over the entire neighbor row -> bool[N_local, R].

    With the symmetric topologies Maelstrom hands out, gather-from-all-
    in-neighbors is identical to the reference's push-to-all-out-neighbors
    (main.go:72-75): after round t, coverage is exactly the BFS ball of
    radius t around the origin.  Sender exclusion (main.go:73-75) does not
    change that set — the sender already has the rumor — so the parity mode
    omits it.
    """
    valid = nbrs_local < n
    safe = jnp.minimum(nbrs_local, n - 1)
    got = seen_all[safe] & valid[:, :, None]
    return jnp.any(got, axis=1)
