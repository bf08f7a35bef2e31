"""Bit-packed state: pack/unpack round trips and packed==unpacked parity.

The packed pull path is the bench fast path (bench.py), so its contract is
the strongest we have: bitwise-identical trajectories to the unpacked pull
kernel under the same seeds — single-device AND sharded — plus exact
message accounting and coverage agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ChurnConfig, FaultConfig, ProtocolConfig,
                               RunConfig)
from gossip_tpu.models.si import coverage, make_si_round
from gossip_tpu.models.si_packed import (
    init_packed_state, make_packed_round, simulate_until_packed)
from gossip_tpu.models.state import alive_mask, init_state
from gossip_tpu.ops.bitpack import coverage_packed, pack, unpack
from gossip_tpu.ops import nemesis as NE
from gossip_tpu.parallel.sharded import (init_sharded_state, local_rows,
                                         make_mesh, make_sharded_si_round)
from gossip_tpu.parallel.sharded_packed import (
    init_sharded_packed_state, make_sharded_packed_round,
    simulate_until_packed_sharded)
from gossip_tpu.topology import generators as G


@pytest.mark.parametrize("r", [1, 3, 32, 33, 100])
def test_pack_unpack_roundtrip(r):
    key = jax.random.key(r)
    seen = jax.random.bernoulli(key, 0.3, (57, r))
    np.testing.assert_array_equal(np.asarray(unpack(pack(seen), r)),
                                  np.asarray(seen))


@pytest.mark.parametrize("r", [1, 31, 64])
def test_coverage_packed_matches_unpacked(r):
    key = jax.random.key(r + 7)
    seen = jax.random.bernoulli(key, 0.4, (200, r))
    alive = jax.random.bernoulli(jax.random.key(1), 0.9, (200,))
    for a in (None, alive):
        cp = float(coverage_packed(pack(seen), r, a))
        cu = float(coverage(seen, a))
        assert cp == pytest.approx(cu, abs=1e-6)


CASES = [
    ("pull-complete", ProtocolConfig(mode=C.PULL, fanout=2, rumors=40),
     lambda: G.complete(96), None),
    ("pull-er-fault", ProtocolConfig(mode=C.PULL, fanout=1, rumors=5),
     lambda: G.erdos_renyi(96, 0.1, seed=3),
     FaultConfig(node_death_rate=0.1, drop_prob=0.2, seed=7)),
    ("antientropy", ProtocolConfig(mode=C.ANTI_ENTROPY, fanout=1, rumors=2,
                                   period=3),
     lambda: G.watts_strogatz(96, 4, 0.2, seed=1), None),
]


@pytest.mark.parametrize("name,proto,topo_fn,fault", CASES,
                         ids=[c[0] for c in CASES])
def test_packed_bitwise_equals_unpacked(name, proto, topo_fn, fault):
    topo = topo_fn()
    run = RunConfig(seed=11)
    rounds = 6
    ustep = jax.jit(make_si_round(proto, topo, fault, run.origin))
    ust = init_state(run, proto, topo.n)
    pstep = jax.jit(make_packed_round(proto, topo, fault, run.origin))
    pst = init_packed_state(run, proto, topo.n)
    for _ in range(rounds):
        ust = ustep(ust)
        pst = pstep(pst)
    np.testing.assert_array_equal(
        np.asarray(unpack(pst.seen, proto.rumors)), np.asarray(ust.seen))
    assert float(pst.msgs) == pytest.approx(float(ust.msgs))


@pytest.mark.parametrize("name,proto,topo_fn,fault", CASES,
                         ids=[c[0] for c in CASES])
def test_sharded_packed_bitwise_parity(name, proto, topo_fn, fault):
    topo = topo_fn()
    run = RunConfig(seed=11)
    mesh = make_mesh(8)
    rounds = 6
    pstep = jax.jit(make_packed_round(proto, topo, fault, run.origin))
    pst = init_packed_state(run, proto, topo.n)
    sstep = jax.jit(make_sharded_packed_round(proto, topo, mesh, fault,
                                              run.origin))
    sst = init_sharded_packed_state(run, proto, topo, mesh)
    for _ in range(rounds):
        pst = pstep(pst)
        sst = sstep(sst)
    np.testing.assert_array_equal(np.asarray(sst.seen)[:topo.n],
                                  np.asarray(pst.seen))
    assert float(sst.msgs) == pytest.approx(float(pst.msgs))


def test_simulate_until_packed_converges():
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=33)
    rounds, cov, msgs, final = simulate_until_packed(
        proto, G.complete(2000), RunConfig(max_rounds=64))
    assert cov >= 0.99
    assert 0 < rounds < 40
    assert msgs > 0
    # sharded twin reaches the same rounds count
    mesh = make_mesh(8)
    r2, cov2, msgs2, _ = simulate_until_packed_sharded(
        proto, G.complete(2000), RunConfig(max_rounds=64), mesh)
    assert r2 == rounds
    assert cov2 == pytest.approx(cov)   # reduction order differs slightly
    assert msgs2 == pytest.approx(msgs)


def test_packed_rejects_push_modes():
    with pytest.raises(ValueError, match="pull/antientropy"):
        make_packed_round(ProtocolConfig(mode=C.PUSH), G.complete(64))
    with pytest.raises(ValueError, match="pull/antientropy"):
        make_sharded_packed_round(ProtocolConfig(mode=C.PUSH_PULL),
                                  G.complete(64), make_mesh(2))


def _partition_mix(n):
    """The benchmark's partition fault program scaled to ``n``: two
    churn events (one recovers), an N/2 partition window over rounds
    0-5, a drop-rate ramp over rounds 0-3 on a 2% base rate."""
    return FaultConfig(drop_prob=0.02, churn=ChurnConfig(
        events=((1, 1, 4), (2, 2, -1)), partitions=((0, 6, n // 2),),
        ramp=(0, 4, 0.0, 0.1)))


@pytest.mark.parametrize("fault_fn", [_partition_mix, lambda n: None],
                         ids=["churn", "static"])
def test_sharded_packed_round_has_one_gather(fault_fn):
    """A shard reads its own liveness rows by slice (local_rows), so the
    only gather left in the round is the partner lookup of
    pull_merge_packed; an own-block ``x[gids]`` index would lower to a
    second general gather, which pays the random-access rate per row."""
    n = 1024
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    topo = G.complete(n)
    mesh = make_mesh(4)
    step, tables = make_sharded_packed_round(proto, topo, mesh,
                                             fault_fn(n), tabled=True)
    state = init_sharded_packed_state(RunConfig(), proto, topo, mesh)
    text = jax.jit(step).lower(state, *tables).as_text()
    assert text.count('"stablehlo.gather"(') == 1


def _packed_pair(proto, topo, mesh, fault, run):
    single = (make_packed_round(proto, topo, fault, run.origin),
              init_packed_state(run, proto, topo.n))
    sharded = (make_sharded_packed_round(proto, topo, mesh, fault,
                                         run.origin),
               init_sharded_packed_state(run, proto, topo, mesh))
    return single, sharded


def _dense_pair(proto, topo, mesh, fault, run):
    single = (make_si_round(proto, topo, fault, run.origin),
              init_state(run, proto, topo.n))
    sharded = (make_sharded_si_round(proto, topo, mesh, fault, run.origin),
               init_sharded_state(run, proto, topo, mesh))
    return single, sharded


@pytest.mark.parametrize("pair", [_packed_pair, _dense_pair],
                         ids=["packed", "dense"])
def test_sharded_partition_mix_bitwise_parity(pair):
    """Sharded rounds under the partition mix equal the single-device
    rounds bitwise: the churn path's liveness, drop coins and cut all
    run per shard.  n = 1001 leaves three padding rows on the 4-device
    mesh, which must stay dark."""
    n = 1001
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    topo = G.complete(n)
    run = RunConfig(seed=5)
    fault = _partition_mix(n)
    ch = NE.get(fault)
    (one, one_st), (shd, shd_st) = pair(proto, topo, make_mesh(4), fault,
                                        run)
    one = jax.jit(NE.drop_lost(one, ch))
    shd = jax.jit(NE.drop_lost(shd, ch))
    for _ in range(12):
        one_st, shd_st = one(one_st), shd(shd_st)
    seen = np.asarray(shd_st.seen)
    np.testing.assert_array_equal(seen[:n], np.asarray(one_st.seen))
    assert not seen[n:].any()
    assert float(shd_st.msgs) == float(one_st.msgs)


def test_local_rows_reads_each_shards_own_block():
    """local_rows slices rows shard*nl .. shard*nl+nl-1 of a replicated
    array, for vectors and tables alike."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(4)
    nl = 5
    vec = jnp.arange(4 * nl, dtype=jnp.int32)
    tbl = jnp.arange(4 * nl * 3, dtype=jnp.int32).reshape(4 * nl, 3)
    own = shard_map(lambda v, t: (local_rows(v, "nodes", nl),
                                  local_rows(t, "nodes", nl)),
                    mesh=mesh, in_specs=(P(), P()),
                    out_specs=(P("nodes"), P("nodes", None)))
    v, t = own(vec, tbl)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vec))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(tbl))
