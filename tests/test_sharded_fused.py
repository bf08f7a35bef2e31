"""Rumor-plane sharding of the fused kernel (parallel/sharded_fused.py).

The inject path makes the sharded round bitwise-checkable on the virtual
8-device CPU mesh: every plane must equal the single-device multi-rumor
kernel applied to that plane with the same bits — the shared partner
stream IS the semantic (one partner per node per round, whole digest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_tpu.config import RunConfig
from gossip_tpu.ops.pallas_round import (
    BITS, LANES, fused_multirumor_pull_round, mr_rows, word_pack,
    word_unpack)
from gossip_tpu.parallel.sharded_fused import (
    assert_prng_invariant, coverage_planes, init_plane_state,
    make_plane_mesh, make_sharded_fused_round, plane_count,
    simulate_until_sharded_fused)

ON_TPU = jax.default_backend() == "tpu"
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the virtual multi-device mesh")


def _bits(rng, rows, fanout=1):
    return (rng.integers(0, 2**32, (fanout, 8, LANES), dtype=np.uint32),
            rng.integers(0, 2**32, (fanout, rows, LANES), dtype=np.uint32))


def test_plane_count_and_init_padding():
    mesh = make_plane_mesh(4)
    assert plane_count(1, 4) == 4            # padded up to the mesh
    assert plane_count(33, 4) == 4
    assert plane_count(129, 4) == 8
    n, rumors = 500, 40                      # plane 1 has 8 real rumors
    planes = init_plane_state(n, rumors, mesh)
    assert planes.shape[0] == 4
    got = np.asarray(word_unpack(planes[1], n, BITS))
    # real rumor columns: exactly one origin each; padding columns all-True
    assert got[:, :8].sum() == 8
    assert got[:, 8:].all()
    # whole padding planes are all-ones for real nodes
    assert np.asarray(word_unpack(planes[2], n, BITS)).all()
    assert float(coverage_planes(planes, n)) == pytest.approx(1.0 / n)


def test_sharded_round_matches_single_device_per_plane():
    n, rumors, n_dev = 128 * 16, 256, 4      # 8 planes over 4 devices
    mesh = make_plane_mesh(n_dev)
    rows = mr_rows(n)
    rng = np.random.default_rng(17)
    planes = init_plane_state(n, rumors, mesh)
    # seed some extra infection so the round moves real data
    seen = rng.random((n, BITS)) < 0.1
    planes = planes.at[3].set(planes[3] | word_pack(jnp.asarray(seen)))
    bits = _bits(rng, rows)
    step = make_sharded_fused_round(n, mesh, interpret=not ON_TPU,
                                    inject_bits=bits)
    out = np.asarray(step(planes, 0, 0))
    for p in range(planes.shape[0]):
        # materialize the shard slice so the single-device reference call
        # is not itself partitioned over the mesh
        plane_p = jnp.asarray(np.asarray(planes[p]))
        want = fused_multirumor_pull_round(
            plane_p, 0, 0, n, 1, interpret=not ON_TPU, inject_bits=bits)
        np.testing.assert_array_equal(out[p], np.asarray(want),
                                      err_msg=f"plane {p}")


def test_whole_digest_rides_one_partner_across_planes():
    """Nodes holding ALL 256 rumors must transfer all-or-nothing: the
    partner draw is shared across every plane."""
    n, rumors, n_dev = 128 * 16, 256, 4
    mesh = make_plane_mesh(n_dev)
    rows = mr_rows(n)
    rng = np.random.default_rng(23)
    holders = rng.random(n) < 0.1
    seen = jnp.repeat(jnp.asarray(holders)[:, None], BITS, axis=1)
    one = word_pack(seen)
    planes = jax.device_put(
        jnp.stack([one] * plane_count(rumors, n_dev)),
        jax.sharding.NamedSharding(mesh,
                                   jax.sharding.PartitionSpec("planes",
                                                              None, None)))
    step = make_sharded_fused_round(n, mesh, interpret=not ON_TPU,
                                    inject_bits=_bits(rng, rows))
    out = np.asarray(step(planes, 0, 0))
    got = np.stack([np.asarray(word_unpack(jnp.asarray(out[p]), n, BITS))
                    for p in range(out.shape[0])])   # [W, n, 32]
    flat = got.transpose(1, 0, 2).reshape(n, -1)     # [n, W*32]
    assert (flat.all(axis=1) | (~flat.any(axis=1))).all()


def test_simulate_until_converges_with_degenerate_prng():
    """CPU interpreter stubs the hw PRNG with zeros: every node pulls the
    same fixed partner each round.  Not an epidemic — but the driver must
    still run the full sharded while_loop and terminate at max_rounds."""
    n, rumors = 128 * 8, 64
    mesh = make_plane_mesh(4)
    rounds, cov, msgs, final = simulate_until_sharded_fused(
        n, rumors, RunConfig(max_rounds=3), mesh, interpret=True)
    assert rounds == 3                       # degenerate PRNG never hits 99%
    assert msgs == 2.0 * n * 3
    assert final.shape[0] == plane_count(rumors, 4)
    assert 0.0 < cov < 0.99


def test_prng_same_stream_invariant_digests():
    """The zero-ICI claim as an executed assertion (VERDICT r2 item 4):
    every device's identically-seeded round digests identically.  On TPU
    (GOSSIP_TPU_TEST_PLATFORM=tpu tier) this checks the HARDWARE PRNG
    stream; on the CPU interpreter the stubbed PRNG makes equality
    trivial but the digest/all_gather program is the real one."""
    mesh = make_plane_mesh(4)
    d = np.asarray(assert_prng_invariant(128 * 16, mesh,
                                         interpret=not ON_TPU))
    assert d.shape == (4, 2)
    assert (d == d[0]).all()
    assert int(d[0, 0]) > 0      # non-degenerate: bits actually flowed


def test_sharded_round_fault_masks_match_single_device():
    """Round-4 fault masks on the plane-sharded engine: every plane must
    equal the single-device MR kernel run with the SAME masks and bits
    (the masks are replicated over the node dim, rebuilt in-trace on
    each device)."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import fault_masks_word
    n, rumors, n_dev = 128 * 16, 128, 4      # 4 planes over 4 devices
    mesh = make_plane_mesh(n_dev)
    rows = mr_rows(n)
    rng = np.random.default_rng(23)
    planes = init_plane_state(n, rumors, mesh)
    seen = rng.random((n, BITS)) < 0.1
    planes = planes.at[1].set(planes[1] | word_pack(jnp.asarray(seen)))
    bits = _bits(rng, rows)
    fault = FaultConfig(drop_prob=0.3, node_death_rate=0.2, seed=12)
    alive_words, thresh = fault_masks_word(fault, n, 0)
    step = make_sharded_fused_round(n, mesh, interpret=not ON_TPU,
                                    inject_bits=bits, fault=fault)
    out = np.asarray(step(planes, 0, 0))
    for p in range(planes.shape[0]):
        plane_p = jnp.asarray(np.asarray(planes[p]))
        want = fused_multirumor_pull_round(
            plane_p, 0, 0, n, 1, interpret=not ON_TPU, inject_bits=bits,
            drop_threshold=thresh, alive_words=alive_words)
        np.testing.assert_array_equal(out[p], np.asarray(want),
                                      err_msg=f"plane {p}")


def test_fused_planes_cov_fn_alive_weighting():
    """The alive-weighted plane coverage: padding rumors stay 1.0 (alive
    nodes hold their all-ones bits), real rumors weight by the alive
    population only."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.models.state import alive_mask
    from gossip_tpu.parallel.sharded_fused import fused_planes_cov_fn
    n, rumors, n_dev = 600, 40, 4            # 2 real planes + 2 padding
    mesh = make_plane_mesh(n_dev)
    rng = np.random.default_rng(4)
    fault = FaultConfig(node_death_rate=0.3, seed=9)
    alive = np.asarray(alive_mask(fault, n, 0))
    seen = rng.random((n, rumors)) < 0.6
    planes = init_plane_state(n, rumors, mesh)
    for p in range(2):
        lo = p * BITS
        real = min(rumors - lo, BITS)
        chunk = np.zeros((n, BITS), bool)
        chunk[:, :real] = seen[:, lo:lo + real]
        chunk[:, real:] = True
        planes = planes.at[p].set(planes[p]
                                  | word_pack(jnp.asarray(chunk)))
    got = float(fused_planes_cov_fn(n, fault)(planes))
    # min over REAL rumors of the alive-weighted fraction (origins of
    # the real rumors are seeded, so union with the init state)
    seen_init = np.zeros_like(seen)
    seen_init[(np.arange(rumors)) % n, np.arange(rumors)] = True
    want = ((seen | seen_init)[alive].mean(axis=0)).min()
    assert got == pytest.approx(want, abs=1e-6)
    # and the unweighted chooser is untouched by a drop-only fault
    drop_only = FaultConfig(drop_prob=0.5, seed=1)
    got2 = float(fused_planes_cov_fn(n, drop_only)(planes))
    assert got2 == pytest.approx(float(coverage_planes(planes, n)),
                                 abs=1e-7)


# fault-variant params are slow-tier since the fused-operand-PR
# rebalance (~3.3 s flight data): the fault-operand binding of the
# memoized loops is now additionally pinned in-gate by
# test_sharded_round_full_schedule_matches_single_device and
# test_fused_churn_sweep_matches_solo_and_validates (which walk the
# same step/mask plumbing under a FULL mixed schedule); the static-
# fault depth twins re-prove under -m slow
@pytest.mark.parametrize(
    "fanout,with_fault",
    [(1, False), (2, False),
     pytest.param(1, True, marks=pytest.mark.slow),
     pytest.param(2, True, marks=pytest.mark.slow)])
def test_device_resident_loop_matches_per_round_driver(fanout, with_fault):
    """The memoized device-resident drivers (curve scan + until loop,
    on-device convergence, cached jitted init, alive mask as operand)
    reproduce the per-round driver EXACTLY: same coverage curve, same
    final planes — CPU, fanout 1 and 2, with and without FaultConfig.
    This is the byte-identity contract behind the dry-run steady-state
    speedup: faster, not different."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.parallel.sharded_fused import (
        fused_planes_cov_fn, simulate_curve_sharded_fused)
    n, rumors, n_dev, rounds = 128 * 8, 96, 4, 3
    mesh = make_plane_mesh(n_dev)
    fault = (FaultConfig(node_death_rate=0.2, drop_prob=0.3, seed=7)
             if with_fault else None)
    run = RunConfig(seed=0, max_rounds=rounds)
    covs, final = simulate_curve_sharded_fused(
        n, rumors, run, mesh, fanout=fanout, interpret=not ON_TPU,
        fault=fault)
    # the per-round driver: step eagerly, coverage recorded per round
    step = make_sharded_fused_round(n, mesh, fanout=fanout,
                                    interpret=not ON_TPU, fault=fault)
    planes = init_plane_state(n, rumors, mesh, 0)
    cov_fn = fused_planes_cov_fn(n, fault)
    for t in range(rounds):
        planes = step(planes, 0, t)
        assert float(covs[t]) == float(cov_fn(planes)), t
    np.testing.assert_array_equal(np.asarray(final), np.asarray(planes))
    # the until twin walks the same trajectory (the degenerate stubbed
    # PRNG never reaches target, so it runs the full budget) and must
    # land on the same planes and report coverage through the same
    # chooser
    rounds_u, cov_u, msgs_u, final_u = simulate_until_sharded_fused(
        n, rumors, run, mesh, fanout=fanout, interpret=not ON_TPU,
        fault=fault)
    assert rounds_u == rounds
    assert msgs_u == 2.0 * fanout * n * rounds
    np.testing.assert_array_equal(np.asarray(final_u), np.asarray(planes))
    assert float(cov_u) == float(cov_fn(planes))


def test_fault_loop_shares_executable_across_death_draws():
    """The fault-curve driver must NOT recompile per fault point: two
    configs differing only in death rate/seed (same drop_prob) hit the
    SAME memoized compiled loop — the alive mask is a runtime operand
    (sharded_fused._cached_curve_scan key contract)."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.parallel.sharded_fused import (
        _cached_curve_scan, drop_threshold_for,
        simulate_curve_sharded_fused)
    n, rumors, n_dev = 128 * 8, 64, 4
    mesh = make_plane_mesh(n_dev)
    run = RunConfig(seed=0, max_rounds=2)
    f1 = FaultConfig(node_death_rate=0.1, drop_prob=0.2, seed=3)
    f2 = FaultConfig(node_death_rate=0.3, drop_prob=0.2, seed=11)
    assert drop_threshold_for(f1) == drop_threshold_for(f2)
    covs1, _ = simulate_curve_sharded_fused(n, rumors, run, mesh,
                                            interpret=not ON_TPU, fault=f1)
    info_before = _cached_curve_scan.cache_info()
    covs2, _ = simulate_curve_sharded_fused(n, rumors, run, mesh,
                                            interpret=not ON_TPU, fault=f2)
    info_after = _cached_curve_scan.cache_info()
    assert info_after.misses == info_before.misses   # shared loop builder
    assert info_after.hits == info_before.hits + 1
    # ... and the shared executable still separates the trajectories
    # (different death draws weight coverage differently)
    assert covs1.shape == covs2.shape == (2,)


def test_simulate_curve_sharded_fused_matches_stepwise():
    """The plane-sharded curve scan equals stepping the sharded round by
    hand (stubbed interpreter PRNG), coverage recorded per round."""
    from gossip_tpu.parallel.sharded_fused import (
        fused_planes_cov_fn, simulate_curve_sharded_fused)
    n, rumors, n_dev, rounds = 128 * 16, 128, 4, 3
    mesh = make_plane_mesh(n_dev)
    run = RunConfig(seed=0, max_rounds=rounds)
    covs, final = simulate_curve_sharded_fused(n, rumors, run, mesh,
                                               interpret=not ON_TPU)
    assert covs.shape == (rounds,)
    step = make_sharded_fused_round(n, mesh, interpret=not ON_TPU)
    planes = init_plane_state(n, rumors, mesh, 0)
    cov_fn = fused_planes_cov_fn(n)
    for t in range(rounds):
        planes = step(planes, 0, t)
        assert float(covs[t]) == float(cov_fn(planes)), t
    np.testing.assert_array_equal(np.asarray(final), np.asarray(planes))


# ---------------------------------------------------------------------
# The fused-operand PR: fault content as runtime KERNEL operands — the
# 20-bit drop threshold as an SMEM scalar indexed from the nemesis
# threshold table, partition windows as per-round side-word cut masks
# (render_cut_words), churn events as per-round alive words.  The
# tests below pin (a) the schedule-to-operand lowering against the XLA
# engines' semantics, (b) the sharded round's full-schedule binding
# against the single-device kernel, (c) the partition stall + heal
# bound on the fused path, and (d) the compile-amortization claim: K
# mixed scenarios through ONE executable, salted re-entry compiling
# ZERO.
# ---------------------------------------------------------------------

def _mixed_fault():
    from gossip_tpu.config import ChurnConfig, FaultConfig
    return FaultConfig(seed=1, drop_prob=0.1, churn=ChurnConfig(
        events=((3, 1, 3), (7, 2, -1)),
        partitions=((1, 3, 600),),
        ramp=(0, 4, 0.05, 0.4)))


def test_fused_sched_tables_match_xla_schedule_semantics():
    """The fused engines' schedule operands (ops/nemesis
    .fused_sched_tables) are the SAME timelines the XLA engines consume
    — one _cut_drop_rows construction — and the threshold lowering is
    value-preserving: a flat drop schedule's per-round thresholds equal
    the static path's drop_threshold_for bit for bit (why the fused
    ckpt-static fingerprints stay green), and the side-mask compare
    reproduces ops/nemesis.same_side exactly."""
    from gossip_tpu.config import ChurnConfig, FaultConfig
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops.pallas_round import (drop_threshold_for,
                                             render_cut_words)
    n = 128 * 8
    fault = _mixed_fault()
    sched = NE.build(fault, n)
    cut_np, thr_np = NE.fused_sched_tables(fault, n)
    np.testing.assert_array_equal(cut_np, np.asarray(sched.cut_tbl))
    want_thr = [int(round(float(p) * (1 << 20)))
                for p in np.asarray(sched.drop_tbl, np.float64)]
    np.testing.assert_array_equal(thr_np, want_thr)
    # flat schedule: every row IS the static threshold
    flat = FaultConfig(seed=1, drop_prob=0.1,
                       churn=ChurnConfig(events=((3, 1, 3),)))
    _, thr_flat = NE.fused_sched_tables(flat, n)
    assert (thr_flat == drop_threshold_for(flat)).all()
    # the side-word mask reproduces same_side for every (cut, pair)
    for cut in (-1, 0, 600, n):
        words = np.asarray(render_cut_words(cut, n)).reshape(-1)
        side = words[:n] != 0
        for a, b in ((0, 1), (0, 599), (599, 600), (600, n - 1),
                     (0, n - 1)):
            assert (side[a] == side[b]) == bool(
                NE.same_side(cut, jnp.int32(a), jnp.int32(b))), (cut, a,
                                                                 b)


def test_sharded_round_full_schedule_matches_single_device():
    """The fault-binding wrapper under a MIXED program (event +
    partition window + drop ramp): every plane of the sharded round at
    round r equals the single-device MR kernel run with the explicitly
    assembled operands — alive words at r, the clamped threshold-table
    row, and the rendered cut mask (the operands the compiled loops
    index in-trace)."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops.pallas_round import render_cut_words
    n, rumors, n_dev = 128 * 8, 128, 4
    mesh = make_plane_mesh(n_dev)
    rows = mr_rows(n)
    rng = np.random.default_rng(29)
    fault = _mixed_fault()
    planes = init_plane_state(n, rumors, mesh)
    seen = rng.random((n, BITS)) < 0.1
    planes = planes.at[1].set(planes[1] | word_pack(jnp.asarray(seen)))
    bits = _bits(rng, rows)
    step = make_sharded_fused_round(n, mesh, interpret=not ON_TPU,
                                    inject_bits=bits, fault=fault)
    base = NE.fused_base_words(fault, n, 0)
    die_w, rec_w = NE.fused_word_tables(fault, n)
    cut_np, thr_np = NE.fused_sched_tables(fault, n)
    for r in (0, 2, 5):
        out = np.asarray(step(planes, 0, r))
        idx = min(max(r, 0), len(cut_np) - 1)
        aw = NE.fused_alive_words_at(base, die_w, rec_w, r)
        cw = render_cut_words(int(cut_np[idx]), n)
        for p in (0, 1):
            plane_p = jnp.asarray(np.asarray(planes[p]))
            want = fused_multirumor_pull_round(
                plane_p, 0, r, n, 1, interpret=not ON_TPU,
                inject_bits=bits, drop_threshold=int(thr_np[idx]),
                alive_words=aw, cut_words=cw)
            np.testing.assert_array_equal(out[p], np.asarray(want),
                                          err_msg=f"round {r} plane {p}")


def test_fused_partition_stall_and_heal():
    """Partition semantics on the fused kernel, with REAL injected
    randomness: an open cut isolating the origin side stalls the far
    side at zero for the whole window (cross-cut pulls destroyed both
    directions — lost, not deferred), and after the window closes the
    epidemic crosses and completes — the same stall + heal contract
    the XLA engines pin in test_nemesis."""
    from gossip_tpu.config import ChurnConfig, FaultConfig
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops.pallas_round import render_cut_words, word_unpack
    n, rumors, heal = 1024, 4, 5
    rows = mr_rows(n)
    cut = n // 2
    fault = FaultConfig(seed=0, churn=ChurnConfig(
        partitions=((0, heal, cut),)))
    cut_np, _ = NE.fused_sched_tables(fault, n)
    rng = np.random.default_rng(31)
    seen0 = np.zeros((n, rumors), bool)
    seen0[:4, :] = True                     # origins below the cut
    table = word_pack(jnp.asarray(seen0))
    fanout = 2
    for r in range(16):
        idx = min(r, len(cut_np) - 1)
        cw = render_cut_words(int(cut_np[idx]), n)
        table = fused_multirumor_pull_round(
            table, 0, r, n, fanout, interpret=not ON_TPU,
            inject_bits=_bits(rng, rows, fanout), cut_words=cw)
        got = np.asarray(word_unpack(table, n, rumors))
        if r < heal - 1:
            assert not got[cut:].any(), (
                f"round {r}: infection crossed an OPEN partition")
    assert got.all(), "epidemic did not complete after the heal"


def test_fused_churn_sweep_matches_solo_and_validates():
    """parallel/sweep.fused_churn_sweep_curves: per-scenario curves are
    BITWISE the solo fused curve driver's (the sweep is executable
    reuse over the same driver — pinned against drift), and the
    validation matrix rejects schedule-free faults and mixed static
    structure loudly."""
    from gossip_tpu.config import ChurnConfig, FaultConfig
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.parallel.sharded_fused import (
        simulate_curve_sharded_fused)
    from gossip_tpu.parallel.sweep import fused_churn_sweep_curves
    n, rumors, n_dev = 128 * 8, 64, 4
    mesh = make_plane_mesh(n_dev)
    run = RunConfig(seed=0, max_rounds=3)
    faults = NE.mixed_scenarios(4, n, drop_prob=0.05, seed=2)
    res = fused_churn_sweep_curves(n, rumors, run, faults, mesh,
                                   interpret=not ON_TPU)
    assert res.curves.shape == (4, 3)
    for i, f in enumerate(faults):
        covs, _ = simulate_curve_sharded_fused(
            n, rumors, run, mesh, fault=f, interpret=not ON_TPU)
        np.testing.assert_array_equal(res.curves[i], np.asarray(covs))
    assert (res.msgs[:, -1] == 2.0 * n * 3).all()
    with pytest.raises(ValueError, match="churn schedule"):
        fused_churn_sweep_curves(
            n, rumors, run, faults + [FaultConfig(drop_prob=0.5)],
            mesh, interpret=not ON_TPU)
    with pytest.raises(ValueError, match="STATIC fault structure"):
        fused_churn_sweep_curves(
            n, rumors, run,
            faults + [FaultConfig(node_death_rate=0.2, seed=9,
                                  churn=ChurnConfig(
                                      events=((3, 1, 2),)))],
            mesh, interpret=not ON_TPU)


def test_fused_k_scenarios_compile_once(assert_compiles):
    """THE fused amortization acceptance (the tentpole's headline): K=8
    mixed nemesis scenarios — events, partition windows, drop ramps —
    through the plane-sharded fused engine compile EXACTLY once.  The
    memoized curve scan keys WITHOUT the fault config (alive words,
    cut table, threshold table all operands), so scenarios 2..8 are
    pure executable reuses, and a SALTED re-entry (new content, same
    shapes — ops/nemesis.mixed_scenarios' contract) through the sweep
    driver compiles ZERO."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.parallel import sharded_fused as SF
    from gossip_tpu.parallel.sweep import fused_churn_sweep_curves
    n, rumors, n_dev = 128 * 8, 64, 4
    mesh = make_plane_mesh(n_dev)
    run = RunConfig(seed=0, max_rounds=2)
    SF._cached_curve_scan.cache_clear()
    SF._cached_churn_masks.cache_clear()
    faults = NE.mixed_scenarios(8, n, salt=0, drop_prob=0.05, seed=2)
    covs0, _ = SF.simulate_curve_sharded_fused(
        n, rumors, run, mesh, fault=faults[0],
        interpret=not ON_TPU)                  # the only compile
    assert covs0.shape == (2,)
    with assert_compiles(0):
        for f in faults[1:]:
            covs, _ = SF.simulate_curve_sharded_fused(
                n, rumors, run, mesh, fault=f, interpret=not ON_TPU)
            assert covs.shape == (2,)
    # salted re-entry through the sweep driver: same shapes, new
    # schedule content — zero compiles end to end
    with assert_compiles(0):
        res = fused_churn_sweep_curves(
            n, rumors, run,
            NE.mixed_scenarios(8, n, salt=3, drop_prob=0.05, seed=2),
            mesh, interpret=not ON_TPU)
        assert res.curves.shape == (8, 2)


def test_committed_fused_sweep_record():
    """The committed fused amortization artifact
    (artifacts/ledger_fused_sweep_r17.jsonl, tools/fused_sweep_capture
    .py): provenance-carrying; the K>=8-scenario plane-sharded fused
    warm path beat K solo (fresh-compile) reruns by >= 3x — the
    pre-operand cost model, where the drop threshold was a kernel
    compile-time static — and a salted scenario family re-entered the
    executable without a fresh compile leg."""
    import os
    from gossip_tpu.utils import telemetry
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts",
        "ledger_fused_sweep_r17.jsonl")
    evs = telemetry.load_ledger(path, run="last")
    assert evs[0]["ev"] == "provenance"
    assert len(evs[0]["git_commit"]) == 40
    rec = [e for e in evs if e.get("ev") == "fused_sweep_record"][-1]
    assert rec["k"] >= 8 and rec["driver"] == "fused_planes"
    assert rec["accept_3x"] is True
    assert rec["solo_total_ms"] >= 3 * rec["warm_total_ms"]
    assert rec["speedup"] >= 3
    # the salted re-entry (fresh content, same shapes) cost steady
    # walls, not another compile leg
    assert 0 < rec["salted_reentry_ms"] < rec["solo_total_ms"] / 3
    scen = [e for e in evs if e.get("ev") == "fused_sweep_scenario"]
    assert len(scen) == rec["k"]
    # the family mixes all three schedule classes on the FUSED engine
    assert any(s["scenario"]["partitions"] for s in scen)
    assert any(s["scenario"]["ramp"] for s in scen)
    assert any(s["scenario"]["events"] for s in scen)
