"""Halo exchange: ``ppermute`` rounds for band-limited topologies.

This is the framework's sequence-parallelism analog (SURVEY.md §5: the
scaled long dimension is *nodes*, not tokens).  The general sharded kernels
(parallel/sharded.py) ``all_gather`` the whole digest table every round —
O(N) ICI traffic, unavoidable for topologies whose edges go anywhere
(complete, ER, power-law).  But **band-limited** graphs (rings, 2-D grids
in row-major order, unrewired Watts–Strogatz lattices — exactly the shapes
Maelstrom hands the reference) have every edge within circular distance B
of its source, so a contiguously-sharded node axis only ever reads rows
within B of its block boundary.  One ``lax.ppermute`` to each mesh neighbor
moves those 2B halo rows — O(B) traffic instead of O(N), the same
neighbor-exchange pattern ring attention uses for sequence blocks.

At the BASELINE scale: a k=6 ring at 10M nodes on 8 shards all-gathers
10 MB/round in the general kernel; the halo kernel moves 2x3 rows = bytes.

Constraints (checked, not assumed): an explicit neighbor table, band(topo)
<= rows-per-shard (halo must come from the *immediate* mesh neighbors),
and n divisible by the mesh size (contiguous blocks, no padding zone in
the circular index math).  Results are bitwise identical to the
single-device kernels — tests/test_halo.py.

CPU-mesh caveat (virtual devices only, not TPU): XLA's in-process CPU
collectives rendezvous across host threads; dispatching hundreds of
ppermute rounds without a host sync can starve one virtual device and
abort the rendezvous.  Python-loop drivers on the CPU mesh should
``block_until_ready`` periodically (a ``lax.while_loop``/``scan`` driver,
the normal production shape, has no such issue).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.models import si as si_mod
from gossip_tpu.models.state import SimState, alive_mask, bind_tables
from gossip_tpu.ops.sampling import apply_drop, drop_mask, sample_peers
from gossip_tpu.topology.generators import Topology


def band_of(topo: Topology) -> int:
    """Max circular edge distance (host-side, one-time).  B such that every
    edge (i, j) has min(|i-j|, n-|i-j|) <= B."""
    if topo.implicit:
        raise ValueError("band is undefined for the implicit complete graph")
    nbrs = np.asarray(topo.nbrs)
    deg = np.asarray(topo.deg)
    n = topo.n
    rows = np.repeat(np.arange(n), nbrs.shape[1])
    flat = nbrs.reshape(-1)
    valid = flat < n
    mask_cols = (np.arange(nbrs.shape[1])[None, :] < deg[:, None]).reshape(-1)
    use = valid & mask_cols
    d = np.abs(flat[use] - rows[use])
    return int(np.minimum(d, n - d).max()) if d.size else 0


def _ring_perms(axis_name: str):
    """(to_right, to_left) ppermute pairs on the mesh ring — the single
    source of the neighbor convention for both the forward halo read and
    the reverse push write-back."""
    p = jax.lax.axis_size(axis_name)
    to_right = [(i, (i + 1) % p) for i in range(p)]
    to_left = [(i, (i - 1) % p) for i in range(p)]
    return to_right, to_left


def _exchange_halos(visible_l: jax.Array, band: int,
                    axis_name: str) -> jax.Array:
    """[nl, R] -> [nl + 2B, R]: prepend the left neighbor's last B rows,
    append the right neighbor's first B rows (both rings of the mesh)."""
    to_right, to_left = _ring_perms(axis_name)
    from_left = jax.lax.ppermute(visible_l[-band:], axis_name, to_right)
    from_right = jax.lax.ppermute(visible_l[:band], axis_name, to_left)
    return jnp.concatenate([from_left, visible_l, from_right], axis=0)


def make_halo_round(proto: ProtocolConfig, topo: Topology, mesh: Mesh,
                    fault: Optional[FaultConfig] = None, origin: int = 0,
                    axis_name: str = "nodes", tabled: bool = False):
    """FLOOD, PULL, PUSH, or PUSH_PULL round with O(band) cross-shard
    traffic.

    Semantically identical to the general sharded kernels and to the
    single-device kernels; only the communication pattern differs.  Push
    scatters into the extended halo buffer and the boundary contributions
    flow BACK to the owning shard with a reverse ``ppermute`` — the push
    twin of the forward halo read.

    ``tabled=True`` returns ``(step, tables)`` with the neighbor arrays as
    step ARGUMENTS (no O(N) jit closure constants — models/swim.py doc);
    the liveness mask is built in-trace."""
    n, k = topo.n, proto.fanout
    mode = proto.mode
    if mode not in (C.FLOOD, C.PULL, C.PUSH, C.PUSH_PULL):
        raise ValueError(
            f"halo rounds support flood/pull/push/pushpull, got {mode!r}")
    if topo.implicit:
        raise ValueError("halo exchange needs an explicit neighbor table")
    p = mesh.shape[axis_name]
    if n % p != 0:
        raise ValueError(f"halo rounds need n % mesh size == 0 "
                         f"(n={n}, mesh={p}); pad the topology instead")
    nl = n // p
    band = band_of(topo)
    if band > nl:
        raise ValueError(
            f"band {band} exceeds rows/shard {nl}: edges span non-adjacent "
            "shards — use the all_gather kernels (parallel/sharded.py)")
    band = max(band, 1)            # ppermute of 0 rows is degenerate
    drop_prob = 0.0 if fault is None else fault.drop_prob
    from gossip_tpu.ops import nemesis as NE
    ch = NE.get(fault)

    def local_round(seen_l, round_, base_key, msgs, nbrs_l, deg_l,
                    *sched_tail):
        _, sched = NE.split_tables(ch, sched_tail)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        # liveness in-trace (replicated compute, no O(N) inline constant)
        if ch is not None:
            # schedule operands from the argument tail (ops/nemesis doc)
            alive_full = NE.alive_rows(
                sched, NE.base_alive_or_ones(fault, n, origin), round_)
            dp = NE.drop_at(sched, round_)
            cut = NE.cut_at(sched, round_)
        else:
            alive = alive_mask(fault, n, origin)
            alive_full = (jnp.ones((n,), jnp.bool_) if alive is None
                          else alive)
            dp, cut = drop_prob, None
        lost = jnp.float32(0.0)
        alive_l = alive_full[gids]
        visible = seen_l & alive_l[:, None]
        ext = _exchange_halos(visible, band, axis_name)   # [nl+2B, R]
        base = shard * nl - band
        msgs_local = jnp.float32(0.0)

        def to_ext(idx):
            # global id -> extended-local row; circular, exact because every
            # needed id is within B of this block (mod n)
            return jnp.mod(idx - base, n)

        delta = jnp.zeros_like(seen_l)
        if mode == C.FLOOD:
            nbrs_use = nbrs_l
            if ch is not None:
                # churn path: always draw (traced p), then cut the
                # cross-partition edges (models/si.py flood twin)
                dropped = drop_mask(rkey, si_mod.FLOOD_DROP_TAG, gids,
                                    nbrs_use.shape[1], dp)
                nbrs_use = jnp.where(dropped, jnp.int32(n), nbrs_use)
                nbrs_use = NE.partition_targets(cut, gids, nbrs_use, n)
                valid0 = nbrs_l < n
                act_ext = jnp.any(ext, axis=1)
                sender_up = act_ext[jnp.where(valid0, to_ext(nbrs_l), 0)]
                lost = lost + jnp.sum(valid0 & sender_up
                                      & (nbrs_use >= n),
                                      dtype=jnp.float32)
            elif drop_prob > 0.0:
                dropped = drop_mask(rkey, si_mod.FLOOD_DROP_TAG, gids,
                                    nbrs_use.shape[1], drop_prob)
                nbrs_use = jnp.where(dropped, jnp.int32(n), nbrs_use)
            valid = nbrs_use < n
            got = ext[jnp.where(valid, to_ext(nbrs_use), 0)]
            delta = jnp.any(got & valid[:, :, None], axis=1)
            sender_active = jnp.any(visible, axis=1)
            msgs_local = jnp.sum(
                jnp.where(sender_active, deg_l, 0)).astype(jnp.float32)

        if mode in (C.PUSH, C.PUSH_PULL):
            # banded push: scatter into the [nl + 2B] extended buffer, then
            # hand the boundary contributions back to their owners with a
            # reverse ppermute (O(band) bytes, the push twin of the halo
            # read)
            pkey = jax.random.fold_in(rkey, si_mod.PUSH_TAG)
            targets0 = sample_peers(pkey, gids, topo, k, proto.exclude_self,
                                    local_nbrs=nbrs_l, local_deg=deg_l)
            targets = apply_drop(rkey, si_mod.PUSH_DROP_TAG, gids,
                                 targets0, dp, n, force=ch is not None)
            if ch is not None:
                targets = NE.partition_targets(cut, gids, targets, n)
            sender_active = jnp.any(visible, axis=1)
            if ch is not None:
                lost = lost + NE.lost_count(targets0, targets,
                                            sender_active, n)
            valid = (targets < n) & sender_active[:, None]
            ext_rows = nl + 2 * band
            tloc = jnp.where(valid, to_ext(targets), ext_rows)  # drop
            flat_t = tloc.reshape(-1)
            flat_p = jnp.broadcast_to(
                visible[:, None, :],
                (nl, k, visible.shape[1])).reshape(-1, visible.shape[1])
            contrib = jnp.zeros((ext_rows, visible.shape[1]), jnp.bool_
                                ).at[flat_t].max(flat_p, mode="drop")
            to_right, to_left = _ring_perms(axis_name)
            # contrib[:B] targets the LEFT neighbor's last B rows;
            # contrib[-B:] targets the RIGHT neighbor's first B rows
            recv_hi = jax.lax.ppermute(contrib[:band], axis_name, to_left)
            recv_lo = jax.lax.ppermute(contrib[band + nl:], axis_name,
                                       to_right)
            pushed = (contrib[band:band + nl]
                      | jnp.pad(recv_lo, ((0, nl - band), (0, 0)))
                      | jnp.pad(recv_hi, ((nl - band, 0), (0, 0))))
            delta = delta | pushed
            msgs_local = msgs_local + jnp.sum(valid).astype(jnp.float32)

        if mode in (C.PULL, C.PUSH_PULL):
            qkey = jax.random.fold_in(rkey, si_mod.PULL_TAG)
            partners0 = sample_peers(qkey, gids, topo, k, proto.exclude_self,
                                     local_nbrs=nbrs_l, local_deg=deg_l)
            partners = apply_drop(rkey, si_mod.PULL_DROP_TAG, gids,
                                  partners0, dp, n, force=ch is not None)
            if ch is not None:
                partners = NE.partition_targets(cut, gids, partners, n)
                lost = lost + NE.lost_count(partners0, partners,
                                            alive_l, n)
            valid = partners < n
            got = ext[jnp.where(valid, to_ext(partners), 0)]
            delta = delta | jnp.any(got & valid[:, :, None], axis=1)
            req = jnp.where(alive_l[:, None], partners, n)
            msgs_local = msgs_local + 2.0 * jnp.sum(
                req < n).astype(jnp.float32)

        delta = delta & alive_l[:, None]
        msgs_new = msgs + jax.lax.psum(msgs_local, axis_name)
        if ch is not None:
            return (seen_l | delta, msgs_new,
                    jax.lax.psum(lost, axis_name))
        return seen_l | delta, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    out_specs = (sh2, rep, rep) if ch is not None else (sh2, rep)
    in_specs = (sh2, rep, rep, rep, sh2, P(axis_name))
    tables = (topo.nbrs, topo.deg)
    if ch is not None:
        in_specs += (rep,) * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n))
    mapped = shard_map(
        local_round, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs)

    def step_tabled(state: SimState, *tbl):
        out = mapped(state.seen, state.round, state.base_key,
                     state.msgs, *tbl)
        new = SimState(seen=out[0], round=state.round + 1,
                       base_key=state.base_key, msgs=out[1])
        # churn path returns (state, lost) — the models/si.py contract
        return (new, out[2]) if ch is not None else new

    return bind_tables(step_tabled, tables, tabled)


def simulate_until_halo(proto: ProtocolConfig, topo: Topology,
                        run: RunConfig, mesh: Mesh,
                        fault: Optional[FaultConfig] = None,
                        axis_name: str = "nodes", timing=None):
    """lax.while_loop to target coverage on the O(band) halo path.
    Returns (rounds, coverage, msgs, final_state, band).
    ``timing``: optional compile/steady AOT-split dict."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.models.si import coverage
    from gossip_tpu.parallel.sharded import init_sharded_state
    step, tables = make_halo_round(proto, topo, mesh, fault, run.origin,
                                   axis_name, tabled=True)
    step = NE.drop_lost(step, NE.get(fault))
    init = init_sharded_state(run, proto, topo, mesh, axis_name)
    target = jnp.float32(run.target_coverage)
    n = topo.n

    @jax.jit
    def loop(state, *tbl):
        alive = NE.metric_alive(fault, n, run.origin)
        def cond(s):
            return ((coverage(s.seen, alive) < target)
                    & (s.round < run.max_rounds))
        def body(s):
            return step(s, *tbl)
        return jax.lax.while_loop(cond, body, state)

    final = maybe_aot_timed(loop, timing, init, *tables, label="halo")
    alive = NE.metric_alive(fault, n, run.origin)
    return (int(final.round), float(coverage(final.seen, alive)),
            float(final.msgs), final, band_of(topo))


def simulate_curve_halo(proto: ProtocolConfig, topo: Topology,
                        run: RunConfig, mesh: Mesh,
                        fault: Optional[FaultConfig] = None,
                        axis_name: str = "nodes", timing=None):
    """lax.scan over rounds recording (coverage, msgs) on the halo path.
    Returns (coverage[T], msgs[T], final_state, band).
    ``timing``: optional compile/steady AOT-split dict."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.utils.trace import maybe_aot_timed
    from gossip_tpu.models.si import coverage
    from gossip_tpu.parallel.sharded import init_sharded_state
    step, tables = make_halo_round(proto, topo, mesh, fault, run.origin,
                                   axis_name, tabled=True)
    step = NE.drop_lost(step, NE.get(fault))
    init = init_sharded_state(run, proto, topo, mesh, axis_name)
    n = topo.n

    @jax.jit
    def scan(state, *tbl):
        alive = NE.metric_alive(fault, n, run.origin)
        def body(s, _):
            s = step(s, *tbl)
            return s, (coverage(s.seen, alive), s.msgs)
        return jax.lax.scan(body, state, None, length=run.max_rounds)

    final, (covs, msgs) = maybe_aot_timed(scan, timing, init, *tables,
                                          label="halo")
    return np.asarray(covs), np.asarray(msgs), final, band_of(topo)
