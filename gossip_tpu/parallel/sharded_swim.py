"""SWIM failure detection sharded over the node mesh.

Twin of :func:`gossip_tpu.models.swim.make_swim_round` (kept semantically
identical — tests/test_swim.py asserts bitwise parity on an 8-device CPU
mesh).  The only structural difference is dissemination: the scatter-max of
wire rows becomes a per-shard scatter-max into an ``int32[n_pad, S]``
contribution table reduced with ``lax.pmax`` over the mesh axis — boolean OR
is not an XLA collective reduction but ``max`` is, and the monotone wire
encoding (models/swim.py module doc) makes max exactly the SWIM merge.

At the BASELINE.json SWIM scale (1M nodes, S=8 subjects) the pmax moves
``1M x 8 x 4 B = 32 MB`` per round over ICI — comfortably under the <1 s
budget; the probe arrays are O(N x K) locals.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from gossip_tpu.config import FaultConfig, ProtocolConfig
from gossip_tpu.models import swim as SW
from gossip_tpu.models.state import bind_tables
from gossip_tpu.models.swim import DEAD_WIRE, SwimState, base_alive
from gossip_tpu.ops.sampling import sample_peers
from gossip_tpu.parallel.sharded import _pad_rows, local_rows, pad_to_mesh
from gossip_tpu.topology.generators import Topology


def make_sharded_swim_round(
        proto: ProtocolConfig, n: int, mesh: Mesh,
        dead_nodes: Tuple[int, ...] = (), fail_round: int = 0,
        fault: Optional[FaultConfig] = None,
        topo: Optional[Topology] = None,
        axis_name: str = "nodes",
        tabled: bool = False,
        max_rounds=None):
    """Returns ``step: SwimState -> SwimState``; ``tabled=True`` returns
    ``(step, tables)`` with the padded topology arrays as step ARGUMENTS
    rather than closure constants — see models/swim.make_swim_round: at
    1M+ nodes a closed-over table inflates the XLA compile request with
    inline constants.  Liveness masks are built in-trace for the same
    reason."""
    s_count = proto.swim_subjects
    if s_count > n:
        raise ValueError(
            f"swim_subjects={s_count} exceeds cluster size n={n}; the "
            "subject window cannot be wider than the membership")
    proxies = proto.swim_proxies
    t_confirm = proto.swim_suspect_rounds
    fanout = proto.fanout
    rotate = proto.swim_rotate
    epoch_rounds = SW.resolve_epoch_rounds(proto, n)
    drop_prob = 0.0 if fault is None else fault.drop_prob
    from gossip_tpu.ops import nemesis as NE
    # events + drop-rate ramps supported (the schedule rides as traced
    # operands — models/swim.py twin); partitions stay rejected
    NE.check_supported(fault, engine="swim", partitions=False)
    ch = NE.get(fault)
    ramped = ch is not None and ch.ramp is not None
    n_pad = pad_to_mesh(n, mesh, axis_name)
    nl = n_pad // mesh.shape[axis_name]
    if topo is None:
        topo = Topology(nbrs=None, deg=None, n=n, family="complete")
    have_table = not topo.implicit
    if have_table:
        nbrs_pad = _pad_rows(topo.nbrs, n_pad, n)
        deg_pad = _pad_rows(topo.deg, n_pad, 0)

    def local_round(wire_l, timer_l, round_, base_key, msgs, *table):
        table, sched = NE.split_tables(ch, table)
        shard = jax.lax.axis_index(axis_name)
        gids = shard * nl + jnp.arange(nl, dtype=jnp.int32)
        rkey = jax.random.fold_in(base_key, round_)
        # O(N) liveness buffers built in-trace (replicated compute, no big
        # inline constants in the compile request — models/swim doc)
        valid = jnp.arange(n_pad) < n             # padding rows: never alive
        alive_base_full = _pad_rows(base_alive(n, dead_nodes, fault),
                                    n_pad, False)
        alive_full = jnp.where(round_ >= fail_round, alive_base_full,
                               True) & valid
        dp = drop_prob
        if ch is not None:
            # scripted crash/recover churn from the schedule OPERANDS
            # (models/swim.py twin; ops/nemesis module doc)
            alive_full = alive_full & ~((sched.die <= round_)
                                        & (round_ < sched.rec))
            if ramped:
                dp = NE.drop_at(sched, round_)
        alive_l = local_rows(alive_full, axis_name, nl)
        subj_gids = SW.subject_window(round_, s_count, n, rotate,
                                      epoch_rounds)
        subj_alive = alive_full[subj_gids]
        if rotate:   # epoch boundary: fresh view state for the new window
            boundary = (round_ > 0) & (round_ % epoch_rounds == 0)
            wire_l = jnp.where(boundary, 0, wire_l)
            timer_l = jnp.where(boundary, 0, timer_l)
        wire0 = wire_l
        nbrs_l, deg_l = table if have_table else (None, None)

        # 1-2: probe + suspect (draws keyed by global id — bitwise == twin)
        if proto.swim_rng == "packed":
            (subj, d_drop, proxy_ids, to_p, p_to_s,
             diss_targets) = SW.packed_round_draws(
                rkey, gids, s_count, n, proxies, fanout, dp,
                nbrs=nbrs_l, deg=deg_l, sentinel=n, force=ramped)
        else:
            subj, d_drop, proxy_ids, to_p, p_to_s = SW.probe_draws(
                rkey, gids, s_count, n, proxies, dp, force=ramped)
            diss_targets = None
        direct_ok = subj_alive[subj] & ~d_drop
        proxy_ok = (alive_full[proxy_ids] & ~to_p & ~p_to_s
                    & subj_alive[subj][:, None])
        indirect_ok = jnp.any(proxy_ok, axis=1)
        fail = alive_l & ~direct_ok & ~indirect_ok
        onehot = jax.nn.one_hot(subj, s_count, dtype=jnp.bool_)
        suspectable = (wire0 < DEAD_WIRE) & onehot & fail[:, None]
        wire1 = jnp.where(suspectable, wire0 | 1, wire0)
        msgs_local = (jnp.sum(alive_l & direct_ok) * 2.0
                      + jnp.sum(alive_l & ~direct_ok)
                      * (1.0 + 4.0 * proxies))

        # 3: dissemination — local scatter-max, pmax over the mesh ---------
        if diss_targets is None:
            dkey = jax.random.fold_in(rkey, SW._DISS_TAG)
            targets = sample_peers(dkey, gids, topo, fanout,
                                   exclude_self=True,
                                   local_nbrs=nbrs_l, local_deg=deg_l)
        else:
            targets = diss_targets
        msgs_local = msgs_local + jnp.sum(
            (targets < n) & alive_l[:, None]).astype(jnp.float32)
        # silent senders (dead/padding) -> n_pad so the scatter drops them
        # (sentinel n would land on a padding row when n < n_pad)
        targets = jnp.where(alive_l[:, None], targets, n_pad)
        contrib = SW.disseminate_max(targets, wire1, n_pad, proto.swim_diss,
                                     max_rounds)
        recv_full = jax.lax.pmax(contrib, axis_name)
        recv_l = local_rows(recv_full, axis_name, nl)
        wire2 = jnp.maximum(wire1, recv_l)

        # 4: refutation (only rows whose gid is an alive subject) ----------
        sel = (gids[:, None] == subj_gids[None, :]) & alive_l[:, None]
        odd = (wire2 % 2 == 1) & (wire2 < DEAD_WIRE)
        wire3 = jnp.where(sel & odd, (wire2 // 2 + 1) * 2, wire2)

        # 5: timers + confirm ---------------------------------------------
        is_susp = (wire3 % 2 == 1) & (wire3 < DEAD_WIRE)
        held = is_susp & (wire3 == wire_l)
        timer = jnp.where(held, timer_l + 1, jnp.where(is_susp, 1, 0))
        confirm = timer >= t_confirm
        wire4 = jnp.where(confirm, DEAD_WIRE, wire3)
        timer = jnp.where(confirm, 0, timer)

        wire_f = jnp.where(alive_l[:, None], wire4, wire0)
        timer_f = jnp.where(alive_l[:, None], timer, timer_l)
        msgs_new = msgs + jax.lax.psum(msgs_local, axis_name)
        return wire_f, timer_f, msgs_new

    sh2 = P(axis_name, None)
    rep = P()
    in_specs = [sh2, sh2, rep, rep, rep]
    tables = (nbrs_pad, deg_pad) if have_table else ()
    if have_table:
        in_specs += [sh2, P(axis_name)]
    if ch is not None:
        in_specs += [rep] * NE.N_SCHED_OPERANDS
        tables = tables + NE.sched_args(NE.build(fault, n, n_pad))

    mapped = shard_map(local_round, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(sh2, sh2, rep))

    def step_tabled(state: SwimState, *tbl) -> SwimState:
        wire, timer, msgs = mapped(state.wire, state.timer, state.round,
                                   state.base_key, state.msgs, *tbl)
        return SwimState(wire=wire, timer=timer, round=state.round + 1,
                         base_key=state.base_key, msgs=msgs)

    return bind_tables(step_tabled, tables, tabled)


def init_sharded_swim_state(n: int, proto: ProtocolConfig, mesh: Mesh,
                            seed: int = 0,
                            axis_name: str = "nodes") -> SwimState:
    n_pad = pad_to_mesh(n, mesh, axis_name)
    st = SW.init_swim_state(n_pad, proto.swim_subjects, seed)
    sharding = NamedSharding(mesh, P(axis_name, None))
    return SwimState(wire=jax.device_put(st.wire, sharding),
                     timer=jax.device_put(st.timer, sharding),
                     round=st.round, base_key=st.base_key, msgs=st.msgs)


def restore_sharded_swim_state(state: SwimState, mesh: Mesh,
                               axis_name: str = "nodes") -> SwimState:
    """Re-place a host-loaded checkpoint (utils/checkpoint.load_state
    gathers to host) back onto the mesh.  The checkpoint already carries
    the padded rows — the config fingerprint pins the mesh shape, so the
    row count matches by construction."""
    sharding = NamedSharding(mesh, P(axis_name, None))
    return SwimState(wire=jax.device_put(jnp.asarray(state.wire), sharding),
                     timer=jax.device_put(jnp.asarray(state.timer),
                                          sharding),
                     round=state.round, base_key=state.base_key,
                     msgs=state.msgs)
