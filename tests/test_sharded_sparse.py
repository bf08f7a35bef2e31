"""Tests for the sparse all_to_all exchange (parallel/sharded_sparse.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.ops.bitpack import coverage_packed, n_words
from gossip_tpu.parallel.sharded import make_mesh
from gossip_tpu.parallel.sharded_sparse import (
    SPARSE_ROW_TAG, _round_draws, _slot_rows, init_sparse_state,
    make_sparse_pull_round, make_sparse_topo_pull_round, resolve_topo_cap,
    simulate_curve_topo_sparse, simulate_until_sparse,
    simulate_until_topo_sparse, sparse_meta, sparse_pull_round_reference,
    sparse_topo_pull_round_reference)
from gossip_tpu.topology import generators as G

P8 = 8


def _mesh():
    return make_mesh(P8)


# all params are depth coverage on the slow tier since the
# compile-once PR (the single in-gate param cost 40 s of the 870 s
# tier-1 budget).  The sparse surface keeps two in-gate smokes: the
# dry run executes both sparse families with schema/steady asserts
# every gate run (tests/test_graft_entry.py), and the compile-cache
# driver matrix pins the sparse curve driver's outputs bitwise across
# executable sources (tests/test_compile_cache.py).  Mesh-vs-reference
# BITWISE parity — what only this test proves — runs under `-m slow`.
@pytest.mark.parametrize("mode,fanout,rumors,fault", [
    pytest.param(C.PULL, 1, 1, None, marks=pytest.mark.slow),
    pytest.param(C.PULL, 2, 40, None, marks=pytest.mark.slow),
    pytest.param(C.PULL, 1, 1,
                 FaultConfig(node_death_rate=0.1, drop_prob=0.2, seed=3),
                 marks=pytest.mark.slow),
    pytest.param(C.ANTI_ENTROPY, 1, 5, None, marks=pytest.mark.slow),
])
def test_bitwise_parity_mesh_vs_reference(mode, fanout, rumors, fault):
    """The mesh run and the single-device reference must agree BITWISE for
    several rounds (collectives only move data)."""
    n = 256
    proto = ProtocolConfig(mode=mode, fanout=fanout, rumors=rumors, period=2)
    run = RunConfig(seed=11)
    mesh = _mesh()
    step_m = make_sparse_pull_round(proto, n, mesh, fault, run.origin)
    step_r = sparse_pull_round_reference(proto, n, P8, fault, run.origin)
    st_m = init_sparse_state(run, proto, n, mesh)
    st_r = init_sparse_state(run, proto, n)  # unsharded, same padding (p=1
    # pads to n; mesh pads to n too since 256 % 8 == 0)
    for _ in range(6):
        st_m = step_m(st_m)
        st_r = step_r(st_r)
        np.testing.assert_array_equal(np.asarray(st_m.seen),
                                      np.asarray(st_r.seen))
        assert float(st_m.msgs) == float(st_r.msgs)


def test_partner_marginal_is_uniform():
    """Stratification must leave the per-slot partner marginal uniform over
    all rows: chi-square over many rounds for one fixed slot."""
    p, nl = 8, 32
    n_pad = p * nl
    key = jax.random.key(0)
    slot = jnp.asarray([5], jnp.int32)      # fixed global slot, k=1

    @jax.jit
    @jax.vmap
    def partner_gid(rnd):
        rkey = jax.random.fold_in(key, rnd)
        pi, o = _round_draws(rkey, p)
        shard = pi[(5 + o) % p]
        return shard * nl + _slot_rows(rkey, slot, nl)[0]

    gids = np.asarray(partner_gid(jnp.arange(2000, dtype=jnp.uint32)))
    counts = np.bincount(gids, minlength=n_pad)
    expected = 2000 / n_pad
    chi2 = ((counts - expected) ** 2 / expected).sum()
    # dof = 255; 3-sigma upper bound ~ 255 + 3*sqrt(510) ~ 323
    assert chi2 < 323, chi2


@pytest.mark.slow
def test_converges_and_traffic_accounting():
    n = 1024
    proto = ProtocolConfig(mode=C.PULL, fanout=2, rumors=40)
    run = RunConfig(seed=0, target_coverage=0.99, max_rounds=64)
    rounds, cov, msgs, final, meta = simulate_until_sparse(
        proto, n, run, _mesh())
    assert cov >= 0.99
    assert 5 <= rounds <= 30
    w = n_words(40)
    nl = n // P8
    assert meta.cap == (nl * 2) // P8
    assert meta.request_bytes == P8 * meta.cap * 4
    assert meta.response_bytes == P8 * meta.cap * 4 * w
    assert meta.dense_bytes == n * 4 * w
    # the whole point: sparse moves less than dense when k < shards*W/(W+1)
    assert meta.sparse_bytes < meta.dense_bytes
    # msgs: 2 per valid request, all nodes alive -> 2*k*n per active round
    assert float(msgs) == pytest.approx(2.0 * 2 * n * rounds)


@pytest.mark.slow
def test_sparse_matches_dense_pull_statistically():
    """Same protocol, different exchange: rounds-to-99% must agree within
    +/-2 rounds of the dense packed pull path."""
    from gossip_tpu.models.si_packed import simulate_until_packed
    from gossip_tpu.topology import generators as G
    n = 2048
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    run = RunConfig(seed=5, target_coverage=0.99, max_rounds=64)
    r_sparse, cov_s, _, _, _ = simulate_until_sparse(proto, n, run, _mesh())
    r_dense, cov_d, _, _ = simulate_until_packed(proto, G.complete(n), run)
    assert cov_s >= 0.99 and cov_d >= 0.99
    assert abs(r_sparse - r_dense) <= 2, (r_sparse, r_dense)


def test_rejects_push_and_unbalanced():
    mesh = _mesh()
    with pytest.raises(ValueError, match="pull"):
        make_sparse_pull_round(ProtocolConfig(mode=C.PUSH), 256, mesh)
    with pytest.raises(ValueError, match="divide"):
        # nl*k = 4 slots per shard, not divisible by 8 shards
        make_sparse_pull_round(
            ProtocolConfig(mode=C.PULL, fanout=1), 32, mesh)


# ---------------------------------------------------------------------
# Explicit-topology sparse exchange (VERDICT r2 item 5)


@pytest.mark.parametrize("family,mode,fanout,rumors,fault", [
    pytest.param("erdos_renyi", C.PULL, 1, 1, None,
                 marks=pytest.mark.slow),
    pytest.param("erdos_renyi", C.PULL, 2, 40, None,
                 marks=pytest.mark.slow),
    pytest.param("watts_strogatz", C.PULL, 1, 5,
                 FaultConfig(node_death_rate=0.1, drop_prob=0.2, seed=3),
                 marks=pytest.mark.slow),
    pytest.param("power_law", C.PULL, 1, 1, None,
                 marks=pytest.mark.slow),
    pytest.param("erdos_renyi", C.ANTI_ENTROPY, 1, 5, None,
                 marks=pytest.mark.slow),
    pytest.param("watts_strogatz", C.ANTI_ENTROPY, 2, 3,
                 FaultConfig(drop_prob=0.15, seed=5),
                 marks=pytest.mark.slow),
])
def test_topo_bitwise_parity_mesh_vs_reference(family, mode, fanout,
                                               rumors, fault):
    """Mesh run == single-device reference BITWISE, including the
    deterministic capacity drops and the anti-entropy reverse merge, on
    explicit topologies (anti-entropy uses period=2: the cond-gated
    reverse collective and the quiescent-round masking both covered)."""
    n = 256
    topo = {"erdos_renyi": lambda: G.erdos_renyi(n, 0.05, seed=7),
            "watts_strogatz": lambda: G.watts_strogatz(n, 6, 0.1, seed=7),
            "power_law": lambda: G.power_law(n, 3, seed=7)}[family]()
    proto = ProtocolConfig(mode=mode, fanout=fanout, rumors=rumors,
                           period=2 if mode == C.ANTI_ENTROPY else 1)
    run = RunConfig(seed=11)
    mesh = _mesh()
    step_m = make_sparse_topo_pull_round(proto, topo, mesh, fault,
                                         run.origin)
    step_r = sparse_topo_pull_round_reference(proto, topo, P8, fault,
                                              run.origin)
    st_m = init_sparse_state(run, proto, n, mesh)
    st_r = init_sparse_state(run, proto, n, p=P8)
    ovf_m = ovf_r = jnp.float32(0.0)
    for _ in range(6):
        st_m, ovf_m = step_m(st_m, ovf_m)
        st_r, ovf_r = step_r(st_r, ovf_r)
        np.testing.assert_array_equal(np.asarray(st_m.seen),
                                      np.asarray(st_r.seen))
        assert float(st_m.msgs) == float(st_r.msgs)
        assert float(ovf_m) == float(ovf_r)


@pytest.mark.slow
def test_topo_overflow_is_deterministic_and_counted():
    """With a tiny forced cap, overflow drops happen, are counted, and
    stay bitwise-identical between mesh and reference."""
    n = 256
    topo = G.erdos_renyi(n, 0.08, seed=2)
    proto = ProtocolConfig(mode=C.PULL, fanout=2, rumors=1)
    run = RunConfig(seed=4)
    mesh = _mesh()
    cap = 2               # way below the balanced load 256/8*2/8 = 8
    step_m = make_sparse_topo_pull_round(proto, topo, mesh, None,
                                         run.origin, cap=cap)
    step_r = sparse_topo_pull_round_reference(proto, topo, P8, None,
                                              run.origin, cap=cap)
    st_m = init_sparse_state(run, proto, n, mesh)
    st_r = init_sparse_state(run, proto, n, p=P8)
    ovf_m = ovf_r = jnp.float32(0.0)
    for _ in range(5):
        st_m, ovf_m = step_m(st_m, ovf_m)
        st_r, ovf_r = step_r(st_r, ovf_r)
    np.testing.assert_array_equal(np.asarray(st_m.seen),
                                  np.asarray(st_r.seen))
    assert float(ovf_m) == float(ovf_r) > 0
    # overflow drops cost coverage progress, not correctness: every pull
    # that WAS delivered still lands on a legal neighbor, so msgs counts
    # only the delivered ones (2 per request)
    assert float(st_m.msgs) < 2.0 * 2 * n * 5


@pytest.mark.slow
def test_topo_byte_accounting_er_100k():
    """The VERDICT item's 'done' criterion: on a 100k-node ER graph the
    sparse exchange moves O(messages), not O(N) — the per-round ICI
    bytes drop vs the dense packed all_gather by ~p*4W/(k*(4+4W)), and
    the epidemic still converges."""
    n = 100_000
    topo = G.erdos_renyi(n, 10.0 / n, seed=1)    # mean degree ~10
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    run = RunConfig(seed=0, target_coverage=0.99, max_rounds=64)
    rounds, cov, msgs, _, meta, ovf = simulate_until_topo_sparse(
        proto, topo, run, _mesh())
    assert cov >= 0.99
    assert rounds < 64
    # O(messages): request+response bytes vs the dense packed gather.
    # ER is shard-uniform, so cap ~ balanced load + 4-sigma slack and
    # the drop at p=8, W=1, k=1 is ~3.6x; it grows linearly with mesh
    # size and rumor words.
    assert meta.sparse_bytes * 3 <= meta.dense_bytes, (
        meta.sparse_bytes, meta.dense_bytes)
    # table-derived cap (auto_topo_cap) -> overflow is rare on ER
    assert ovf < 0.01 * msgs
    # traffic formula documented in sparse_topo_meta
    nl = (n + P8 - 1) // P8
    n_pad = nl * P8
    assert meta.cap == resolve_topo_cap(topo, P8, 1)
    assert meta.request_bytes == P8 * meta.cap * 4
    assert meta.dense_bytes == n_pad * 4


@pytest.mark.slow
def test_topo_sparse_matches_dense_statistically():
    """Same ER pull protocol through the sparse exchange and the dense
    sharded path: rounds-to-99% must agree within a seed-stream-aware
    margin (the two engines draw from DIFFERENT RNG streams, so the
    agreement is statistical, not bitwise).  This only guards against
    gross divergence like a lost round of mixing; the bitwise-parity
    tests above are the correctness gate."""
    from gossip_tpu.parallel.sharded import simulate_until_sharded
    n = 2048
    topo = G.erdos_renyi(n, 12.0 / n, seed=9)
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    run = RunConfig(seed=5, target_coverage=0.99, max_rounds=64)
    r_s, cov_s, _, _, _, _ = simulate_until_topo_sparse(
        proto, topo, run, _mesh())
    r_d, cov_d, _, _ = simulate_until_sharded(proto, topo, run, _mesh())
    assert cov_s >= 0.99 and cov_d >= 0.99
    assert abs(r_s - r_d) <= 2, (r_s, r_d)


@pytest.mark.slow
def test_topo_curve_driver_and_overflow_series():
    n = 1024
    topo = G.watts_strogatz(n, 8, 0.2, seed=3)
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=3)
    run = RunConfig(seed=1, max_rounds=24)
    covs, msgs, final, meta, ovfs = simulate_curve_topo_sparse(
        proto, topo, run, _mesh())
    assert covs.shape == (24,) and ovfs.shape == (24,)
    assert (np.diff(covs) >= -1e-6).all(), "coverage must be monotone"
    assert covs[-1] > 0.99
    assert (np.diff(ovfs) >= 0).all(), "overflow count is cumulative"


def test_topo_rejections():
    mesh = _mesh()
    topo = G.erdos_renyi(256, 0.05, seed=0)
    with pytest.raises(ValueError, match="pull and anti-entropy"):
        make_sparse_topo_pull_round(ProtocolConfig(mode=C.PUSH), topo, mesh)
    with pytest.raises(ValueError, match="pull and anti-entropy"):
        make_sparse_topo_pull_round(ProtocolConfig(mode=C.FLOOD), topo,
                                    mesh)
    with pytest.raises(ValueError, match="implicit"):
        make_sparse_topo_pull_round(
            ProtocolConfig(mode=C.PULL), G.complete(256), mesh)


@pytest.mark.slow
def test_topo_antientropy_converges_and_reverse_accounting():
    """Anti-entropy through the topo exchange: faster convergence than
    pure pull (bidirectional merge), reverse bytes in the meta, msgs
    factor 3 on exchange rounds only."""
    n = 2048
    topo = G.erdos_renyi(n, 12.0 / n, seed=4)
    run = RunConfig(seed=2, target_coverage=0.99, max_rounds=64)
    r_ae, cov_ae, msgs_ae, _, meta_ae, _ = simulate_until_topo_sparse(
        ProtocolConfig(mode=C.ANTI_ENTROPY, fanout=1, rumors=1), topo,
        run, _mesh())
    r_pl, cov_pl, _, _, meta_pl, _ = simulate_until_topo_sparse(
        ProtocolConfig(mode=C.PULL, fanout=1, rumors=1), topo, run,
        _mesh())
    assert cov_ae >= 0.99 and cov_pl >= 0.99
    assert r_ae <= r_pl
    assert meta_ae.reverse_bytes == meta_ae.response_bytes > 0
    assert meta_pl.reverse_bytes == 0
    # 3 messages per delivered request (request + digest + reverse)
    assert msgs_ae == pytest.approx(3.0 * n * r_ae, rel=0.05)


@pytest.mark.slow
def test_topo_dead_nodes_stay_dark():
    n = 256
    fault = FaultConfig(node_death_rate=0.3, seed=9)
    topo = G.erdos_renyi(n, 0.08, seed=5)
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    run = RunConfig(seed=2, max_rounds=40)
    mesh = _mesh()
    step = make_sparse_topo_pull_round(proto, topo, mesh, fault, run.origin)
    st = init_sparse_state(run, proto, n, mesh)
    ovf = jnp.float32(0.0)
    from gossip_tpu.models.state import alive_mask
    alive = np.asarray(alive_mask(fault, n, run.origin))
    for _ in range(16):
        st, ovf = step(st, ovf)
    seen = np.asarray(st.seen)[:n, 0]
    assert not (seen[~alive] != 0).any(), "dead nodes must stay dark"
    assert (seen[alive] != 0).mean() > 0.8


@pytest.mark.slow
def test_backend_routes_explicit_family_to_topo_sparse():
    """run_simulation(exchange='sparse') on an explicit family must take
    the capacity-capped topology path and report its traffic meta."""
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.config import MeshConfig, TopologyConfig
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    tc = TopologyConfig(family="erdos_renyi", n=1024, p=0.01, seed=3)
    run = RunConfig(seed=0, target_coverage=0.99, max_rounds=64)
    rep = run_simulation("jax-tpu", proto, tc, run, None,
                         MeshConfig(n_devices=P8, exchange="sparse"))
    assert rep.coverage >= 0.99
    assert rep.meta["exchange"] == "sparse"
    assert "overflow_dropped_requests" in rep.meta
    assert rep.meta["ici_bytes_per_round"]["sparse"] <= \
        rep.meta["ici_bytes_per_round"]["dense_equivalent"]
    # anti-entropy routes through the same path (round 3); push is
    # rejected loudly, never silently densified
    rep_ae = run_simulation("jax-tpu",
                            ProtocolConfig(mode=C.ANTI_ENTROPY, period=2),
                            tc, run, None,
                            MeshConfig(n_devices=P8, exchange="sparse"))
    assert rep_ae.meta["exchange"] == "sparse"
    assert rep_ae.coverage >= 0.99
    with pytest.raises(ValueError, match="pull and anti-entropy"):
        run_simulation("jax-tpu", ProtocolConfig(mode=C.PUSH),
                       tc, run, None,
                       MeshConfig(n_devices=P8, exchange="sparse"))


@pytest.mark.slow
def test_dead_nodes_never_infected_or_requesting():
    n = 256
    fault = FaultConfig(node_death_rate=0.3, seed=9)
    proto = ProtocolConfig(mode=C.PULL, fanout=1, rumors=1)
    run = RunConfig(seed=2, max_rounds=40)
    mesh = _mesh()
    step = make_sparse_pull_round(proto, n, mesh, fault, run.origin)
    st = init_sparse_state(run, proto, n, mesh)
    from gossip_tpu.models.state import alive_mask
    alive = np.asarray(alive_mask(fault, n, run.origin))
    for _ in range(12):
        st = step(st)
    seen = np.asarray(st.seen)[:n, 0]
    assert not (seen[~alive] != 0).any(), "dead nodes must stay dark"
    assert (seen[alive] != 0).mean() > 0.9
