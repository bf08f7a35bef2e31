"""SIR rumor-mongering tests (models/rumor.py).

The exact 2-node scenarios are fully deterministic — with exclude_self
on a 2-node complete graph there is only one possible partner — so they
pin the counter semantics (feedback vs blind) without touching RNG.
"""

import numpy as np
import pytest

from gossip_tpu.backend import run_simulation
from gossip_tpu.config import (FaultConfig, MeshConfig, ProtocolConfig,
                               RunConfig, TopologyConfig)
from gossip_tpu.models.rumor import (init_rumor_state, make_rumor_round,
                                     simulate_curve_rumor,
                                     simulate_until_rumor)
from gossip_tpu.topology import generators as G


def _run(n=2048, variant="feedback", k=2, fanout=1, max_rounds=256,
         fault=None, family="complete", seed=0):
    proto = ProtocolConfig(mode="rumor", fanout=fanout, rumor_k=k,
                           rumor_variant=variant)
    topo = (G.complete(n) if family == "complete"
            else G.build(TopologyConfig(family=family, n=n, k=6, p=0.1)))
    run = RunConfig(max_rounds=max_rounds, seed=seed)
    return simulate_until_rumor(proto, topo, run, fault)


def test_two_node_feedback_exact():
    # r1: 0 pushes to 1 (1 didn't know: no hit). r2: both push (both knew:
    # cnt=1 each). r3: both push again (cnt=2 -> removed). 5 msgs total.
    rounds, cov, residue, msgs, final = _run(n=2, variant="feedback", k=2)
    assert (rounds, msgs) == (3, 5.0)
    assert cov == 1.0 and residue == 0.0
    assert not bool(np.asarray(final.hot).any())


def test_two_node_blind_exact():
    # r1: 0 pushes (cnt0=1), 1 infected. r2: both push (cnt0=2 -> removed,
    # cnt1=1). r3: 1 pushes (cnt1=2 -> removed). 4 msgs total.
    rounds, cov, residue, msgs, final = _run(n=2, variant="blind", k=2)
    assert (rounds, msgs) == (3, 4.0)
    assert cov == 1.0


def test_terminates_with_low_residue_feedback():
    rounds, cov, residue, msgs, final = _run(n=2048, variant="feedback", k=3)
    assert not bool(np.asarray(final.hot).any())      # self-terminated
    assert rounds < 256
    assert cov > 0.9                                   # Demers ballpark
    assert residue == pytest.approx(1.0 - cov)


def test_blind_message_bound_and_more_residue():
    # Blind counter k: every (node, rumor) pushes exactly k times (the
    # last round's fanout is cut to the quota) — a hard traffic bound SI
    # push has no analog of.
    n, k, fanout = 4096, 2, 2
    rounds, cov_b, residue_b, msgs, _ = _run(n=n, variant="blind", k=k,
                                             fanout=fanout)
    assert msgs <= n * k
    # feedback at the same k informs at least as many nodes (it only
    # stops on evidence of redundancy, blind stops unconditionally)
    _, cov_f, _, _, _ = _run(n=n, variant="feedback", k=k, fanout=fanout)
    assert cov_f >= cov_b


@pytest.mark.parametrize("drop_prob", [0.0, 0.5])
def test_blind_counter_sends_exactly_rumor_k(drop_prob):
    """memberlist's retransmit limit: with fanout 3 and rumor_k 5 every
    informed pair spends exactly 5 sends — 3 then 2, never 6 — and a
    send lost to the link counts like one that arrives.  Fault-free,
    every send is a message."""
    fault = FaultConfig(drop_prob=drop_prob) if drop_prob else None
    rounds, cov, _, msgs, final = _run(n=1500, variant="blind", k=5,
                                       fanout=3, fault=fault, seed=3)
    seen, cnt = np.asarray(final.seen), np.asarray(final.cnt)
    assert not np.asarray(final.hot).any() and rounds > 0
    assert (cnt[seen] == 5).all() and (cnt[~seen] == 0).all()
    if drop_prob:
        assert msgs < 5 * seen.sum()
    else:
        assert msgs == 5.0 * seen.sum()


def test_sharded_blind_counter_bitwise_parity():
    """The sharded twin cuts the last round's fanout to the same quota
    and counts the same lost sends, bitwise, under a partition
    nemesis with drops, on a padded 8-device mesh."""
    from gossip_tpu.config import ChurnConfig
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_rumor import (
        init_sharded_rumor_state, make_sharded_rumor_round)

    n = 500                        # NOT divisible by 8: padding exercised
    proto = ProtocolConfig(mode="rumor", fanout=3, rumor_k=4,
                           rumor_variant="blind")
    fault = FaultConfig(drop_prob=0.3, churn=ChurnConfig(
        events=((1, 1, 4), (2, 2, -1)), partitions=((0, 3, n // 2),)))
    topo = G.complete(n)
    run = RunConfig(seed=11, max_rounds=32)
    step_1, t1 = make_rumor_round(proto, topo, fault, tabled=True)
    st1 = init_rumor_state(run, proto, n)
    step_8, t8 = make_sharded_rumor_round(proto, topo, make_mesh(8), fault,
                                          tabled=True)
    st8 = init_sharded_rumor_state(run, proto, topo, make_mesh(8))
    lost_1 = lost_8 = 0.0
    for _ in range(6):
        st1, l1 = step_1(st1, *t1)
        st8, l8 = step_8(st8, *t8)
        lost_1, lost_8 = lost_1 + float(l1), lost_8 + float(l8)
    for field in ("seen", "hot", "cnt"):
        np.testing.assert_array_equal(np.asarray(getattr(st1, field)),
                                      np.asarray(getattr(st8, field))[:n],
                                      err_msg=field)
    assert float(st1.msgs) == float(st8.msgs)
    assert lost_1 == lost_8 > 0.0
    assert np.asarray(st1.cnt).max() == 4


def test_monotone_seen_and_curve_matches_until():
    proto = ProtocolConfig(mode="rumor", fanout=1, rumor_k=2)
    topo = G.complete(1024)
    run = RunConfig(max_rounds=128, seed=7)
    covs, hots, msgs, final = simulate_curve_rumor(proto, topo, run)
    covs = np.asarray(covs)
    assert (np.diff(covs) >= -1e-7).all()              # monotone coverage
    # the infective wave rises then dies out
    assert float(hots[-1]) == 0.0
    assert hots.max() > 0.1
    rounds, cov, _, msgs_u, _ = simulate_until_rumor(proto, topo, run)
    assert cov == pytest.approx(float(covs[-1]))
    assert msgs_u == pytest.approx(float(msgs[-1]))


# ~8 s (txn-PR rebalance): the static-death rumor surface stays
# smoked in-gate by the nemesis rumor-churn ensemble parity
# (tests/test_nemesis.py) and the rumor_sir dry-run family; this
# 256-round depth re-proves under -m slow
@pytest.mark.slow
def test_dead_nodes_stay_dark():
    fault = FaultConfig(node_death_rate=0.2, seed=3)
    proto = ProtocolConfig(mode="rumor", fanout=2, rumor_k=3)
    topo = G.complete(512)
    run = RunConfig(max_rounds=256, seed=1)
    rounds, cov, residue, msgs, final = simulate_until_rumor(
        proto, topo, run, fault)
    from gossip_tpu.models.state import alive_mask
    alive = np.asarray(alive_mask(fault, 512, 0))      # origin pinned alive,
    seen = np.asarray(final.seen)                      # like the kernel
    hot = np.asarray(final.hot)
    assert not seen[~alive].any()                      # dead never informed
    assert not hot[~alive].any()
    assert cov > 0.9                                   # alive population
    # the curve driver weights by the SAME mask: with every alive node
    # informed, coverage reads ~1.0 (dead nodes are unreachable, not
    # uninformed) and the backend's rounds are extinction rounds in both
    # driver shapes
    curve_rep = run_simulation("jax-tpu", proto,
                               TopologyConfig(family="complete", n=512),
                               run, fault=fault, want_curve=True)
    until_rep = run_simulation("jax-tpu", proto,
                               TopologyConfig(family="complete", n=512),
                               run, fault=fault)
    assert curve_rep.coverage == pytest.approx(cov, abs=1e-6)
    assert curve_rep.meta["rounds_semantics"] == "extinction"
    assert curve_rep.rounds == until_rep.rounds == rounds


def test_backend_routing_and_rejections():
    rep = run_simulation("jax-tpu",
                         ProtocolConfig(mode="rumor", rumor_k=2),
                         TopologyConfig(family="complete", n=1024),
                         RunConfig(max_rounds=128))
    assert rep.mode == "rumor"
    assert rep.meta["variant"] == "feedback"
    assert rep.meta["terminated"] is True
    assert rep.meta["residue"] == pytest.approx(1.0 - rep.coverage, abs=1e-6)
    assert rep.rounds > 0
    with pytest.raises(ValueError, match="pull rounds only"):
        run_simulation("jax-tpu", ProtocolConfig(mode="rumor"),
                       TopologyConfig(family="complete", n=1024),
                       RunConfig(engine="fused"))
    with pytest.raises(ValueError, match="rumor_k"):
        ProtocolConfig(mode="rumor", rumor_k=0)
    with pytest.raises(ValueError, match="rumor_variant"):
        ProtocolConfig(mode="rumor", rumor_variant="telepathy")
    # the SI builders refuse SIR mode loudly (no silent no-op rounds)
    from gossip_tpu.models.si import make_si_round
    from gossip_tpu.parallel.sharded import make_mesh, make_sharded_si_round
    with pytest.raises(ValueError, match="rumor"):
        make_si_round(ProtocolConfig(mode="rumor"), G.complete(64))
    with pytest.raises(ValueError, match="rumor"):
        make_sharded_si_round(ProtocolConfig(mode="rumor"), G.complete(64),
                              make_mesh(8))


def test_works_on_explicit_tables():
    rounds, cov, residue, msgs, _ = _run(n=2048, family="watts_strogatz",
                                         k=3, fanout=2)
    assert cov > 0.8


@pytest.mark.parametrize("variant", [
    pytest.param("feedback", marks=pytest.mark.slow),
    pytest.param("blind", marks=pytest.mark.slow)])
def test_sharded_rumor_bitwise_parity(variant):
    """The shard_map twin is bitwise-identical to the single-device
    kernel — same per-node threefry streams (keyed by global id), same
    counters — on the 8-device CPU mesh, padding included."""
    import jax

    from gossip_tpu.models.rumor import make_rumor_round
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_rumor import (
        init_sharded_rumor_state, make_sharded_rumor_round)

    n = 1000                       # NOT divisible by 8: padding exercised
    proto = ProtocolConfig(mode="rumor", fanout=2, rumor_k=2,
                           rumor_variant=variant, rumors=3)
    topo = G.complete(n)
    run = RunConfig(seed=11, max_rounds=32)
    mesh = make_mesh(8)

    step_1 = make_rumor_round(proto, topo)
    st1 = init_rumor_state(run, proto, n)
    step_8, tables = make_sharded_rumor_round(proto, topo, mesh, tabled=True)
    st8 = init_sharded_rumor_state(run, proto, topo, mesh)
    for _ in range(10):
        st1 = step_1(st1)
        st8 = step_8(st8, *tables)
    for field in ("seen", "hot", "cnt"):
        a = np.asarray(getattr(st1, field))
        b = np.asarray(getattr(st8, field))[:n]
        np.testing.assert_array_equal(a, b, err_msg=field)
    assert float(st1.msgs) == float(st8.msgs)


@pytest.mark.slow
def test_sharded_rumor_until_matches_single():
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_rumor import (
        simulate_until_rumor_sharded)

    proto = ProtocolConfig(mode="rumor", fanout=1, rumor_k=2)
    topo = G.complete(2048)
    run = RunConfig(seed=4, max_rounds=256)
    single = simulate_until_rumor(proto, topo, run)
    sharded = simulate_until_rumor_sharded(proto, topo, run, make_mesh(8))
    assert single[:4] == sharded[:4]       # rounds, cov, residue, msgs
    # ... and through the backend seam
    rep = run_simulation("jax-tpu", proto,
                         TopologyConfig(family="complete", n=2048),
                         run, mesh_cfg=MeshConfig(n_devices=8))
    assert rep.meta["devices"] == 8
    assert rep.meta["terminated"] is True
    assert rep.rounds == single[0]


# ~5 s (txn-PR rebalance): the rumor ensemble's churn twin
# (test_ensemble_rumor_churn_matches_solo, tests/test_nemesis.py)
# keeps the vmapped-SIR solo-parity surface in-gate; the fault-free
# depth re-proves under -m slow
@pytest.mark.slow
def test_rumor_seed_ensemble_matches_solo_trajectories():
    """One vmapped XLA program = |seeds| SIR trajectories, each bitwise
    equal to its solo scan; residue/extinction stats come out."""
    from gossip_tpu.parallel.sweep import ensemble_rumor_curves
    proto = ProtocolConfig(mode="rumor", fanout=1, rumor_k=2)
    topo = G.complete(1024)
    run = RunConfig(max_rounds=96, seed=3)
    seeds = [3, 4, 5, 6]
    ens = ensemble_rumor_curves(proto, topo, run, seeds)
    assert ens.curves.shape == (4, 96)
    s = ens.summary()
    assert s["terminated"] == 4
    assert 0.0 <= s["residue_p95"] <= 1.0
    assert s["extinction_rounds_mean"] > 0
    # row 1 (seed 4) must equal the solo curve driver with seed 4
    solo_covs, solo_hots, solo_msgs, _ = simulate_curve_rumor(
        proto, topo, RunConfig(max_rounds=96, seed=4))
    np.testing.assert_array_equal(ens.curves[1], np.asarray(solo_covs))
    np.testing.assert_array_equal(ens.hot[1], np.asarray(solo_hots))
    np.testing.assert_array_equal(ens.msgs[1], np.asarray(solo_msgs))
    # extinction round of row 1 agrees with the solo hot curve
    idx = np.nonzero(np.asarray(solo_hots) == 0.0)[0]
    assert ens.extinction_rounds[1] == idx[0] + 1


@pytest.mark.slow
def test_sharded_rumor_curve_matches_single():
    """Round-4: sharded rumor CURVE capture (the last rumor carve-out).
    Both channels — coverage and hot fraction — match the single-device
    scan point for point on a padded mesh, and the backend routes
    want_curve + devices>1 to it instead of refusing."""
    from gossip_tpu.models.rumor import simulate_curve_rumor
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_rumor import (
        simulate_curve_rumor_sharded)

    n = 300                        # not divisible by 8: padding exercised
    proto = ProtocolConfig(mode="rumor", fanout=1, rumor_k=2, rumors=2)
    topo = G.erdos_renyi(n, 0.03, seed=5)
    run = RunConfig(seed=7, max_rounds=20)
    covs1, hots1, msgs1, fin1 = simulate_curve_rumor(proto, topo, run)
    covs8, hots8, msgs8, fin8 = simulate_curve_rumor_sharded(
        proto, topo, run, make_mesh(8))
    np.testing.assert_allclose(np.asarray(covs8), np.asarray(covs1),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(hots8), np.asarray(hots1),
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(msgs8), np.asarray(msgs1))
    np.testing.assert_array_equal(np.asarray(fin8.seen)[:n],
                                  np.asarray(fin1.seen))

    from gossip_tpu.backend import run_jax
    from gossip_tpu.config import MeshConfig, TopologyConfig
    rep = run_jax(proto, TopologyConfig(family="erdos_renyi", n=n,
                                        p=0.03, seed=5),
                  RunConfig(seed=7, max_rounds=20), None,
                  MeshConfig(n_devices=8), want_curve=True)
    np.testing.assert_allclose(rep.curve, np.asarray(covs1), rtol=0,
                               atol=1e-6)
