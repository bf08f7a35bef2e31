"""Backend seam, CLI, and gRPC sidecar tests (SURVEY.md §7 layers 5-6)."""

import json
import os
import subprocess
import sys

import pytest

from gossip_tpu.backend import (RunReport, request_to_args, run_simulation)
from gossip_tpu.config import (MeshConfig, ProtocolConfig, RunConfig,
                               TopologyConfig)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_backend_parity_race_free_ring():
    # On the k=2 ring the event sim's hop clock equals the kernel's round
    # clock exactly (gonative parity contract), so the two backends must
    # report identical rounds-to-target through the seam.
    tc = TopologyConfig(family="ring", n=256, k=2)
    run = RunConfig(target_coverage=1.0, max_rounds=200)
    jax_r = run_simulation("jax-tpu", ProtocolConfig(mode="flood"), tc, run)
    go_r = run_simulation("go-native", ProtocolConfig(mode="flood"), tc, run)
    assert jax_r.coverage == go_r.coverage == 1.0
    assert jax_r.rounds == go_r.rounds == 128
    assert go_r.meta["clock"] == "hop-depth"


def test_backend_swim_report():
    proto = ProtocolConfig(mode="swim", fanout=2, swim_subjects=4,
                           swim_proxies=2, swim_suspect_rounds=4)
    r = run_simulation("jax-tpu", proto,
                       TopologyConfig(family="complete", n=128),
                       RunConfig(max_rounds=40))
    assert r.mode == "swim"
    assert r.coverage > 0.97          # detection fraction
    assert 0 < r.rounds < 40


def test_backend_swim_scenario_from_fault():
    # VERDICT r1: the failure scenario is config, not a hardcode — which
    # nodes die, and when, comes from the FaultConfig / RPC request.
    from gossip_tpu.config import FaultConfig
    proto = ProtocolConfig(mode="swim", fanout=2, swim_subjects=6,
                           swim_proxies=2, swim_suspect_rounds=4)
    fault = FaultConfig(dead_nodes=(0, 3, 5), fail_round=4)
    r = run_simulation("jax-tpu", proto,
                       TopologyConfig(family="complete", n=128),
                       RunConfig(max_rounds=48), fault=fault)
    assert r.meta["dead_subjects"] == [0, 3, 5]
    assert r.meta["fail_round"] == 4
    assert r.meta["default_scenario"] is False
    assert r.coverage > 0.97
    # out-of-window dead id without rotation is a config error
    with pytest.raises(ValueError, match="swim-rotate"):
        run_simulation("jax-tpu", proto,
                       TopologyConfig(family="complete", n=128),
                       RunConfig(max_rounds=8),
                       fault=FaultConfig(dead_nodes=(100,)))
    # ... and with rotation it is detected (meta records the window mode)
    proto_rot = ProtocolConfig(mode="swim", fanout=2, swim_subjects=8,
                               swim_proxies=2, swim_suspect_rounds=4,
                               swim_rotate=True)
    r = run_simulation("jax-tpu", proto_rot,
                       TopologyConfig(family="complete", n=96),
                       RunConfig(max_rounds=250),
                       fault=FaultConfig(dead_nodes=(57,), fail_round=0))
    assert r.meta["subject_window"] == "rotating"
    assert r.meta["peak_detection"] > 0.97


def test_rpc_request_carries_swim_scenario():
    args = request_to_args({"proto": {"mode": "swim", "swim_rotate": True},
                            "fault": {"dead_nodes": [4, 9],
                                      "fail_round": 3}})
    assert args["fault"].dead_nodes == (4, 9)    # list -> hashable tuple
    assert args["fault"].fail_round == 3
    assert args["proto"].swim_rotate is True
    assert hash(args["fault"]) is not None


def test_backend_sharded_path():
    r = run_simulation("jax-tpu", ProtocolConfig(mode="pushpull"),
                       TopologyConfig(family="complete", n=512),
                       RunConfig(max_rounds=64),
                       mesh_cfg=MeshConfig(n_devices=8), want_curve=True)
    assert r.meta["devices"] == 8
    assert r.coverage >= 0.99
    assert len(r.curve) == 64


def test_wall_reconciliation_contract():
    """VERDICT r4 task 5: every reported wall decomposes in the report
    itself — wall == compile_s + steady_wall_s + driver_overhead_s, the
    topology build is attributed separately, and the split exists on
    SHARDED engines too (round 4 left them as one fused wall)."""
    proto = ProtocolConfig(mode="pull", fanout=1)
    tc = TopologyConfig(family="erdos_renyi", n=1024, p=0.02)
    run = RunConfig(max_rounds=64)
    for mesh_cfg in (None, MeshConfig(n_devices=8)):
        r = run_simulation("jax-tpu", proto, tc, run, mesh_cfg=mesh_cfg)
        m = r.meta
        assert m["topo_build_s"] >= 0.0
        parts = (m["compile_s"] + m["steady_wall_s"]
                 + m["driver_overhead_s"])
        # == up to the 4-decimal rounding of the three parts
        assert r.wall_s == pytest.approx(parts, abs=2e-3)


def test_backend_packed_routing_matches_bool_path():
    # pull/anti-entropy route through the bit-packed engine; trajectories
    # are bitwise-identical to the bool path, so rounds-to-target and final
    # coverage must agree exactly with the curve (bool) run.
    proto = ProtocolConfig(mode="pull", fanout=1, rumors=3)
    tc = TopologyConfig(family="erdos_renyi", n=1024, p=0.02)
    run = RunConfig(max_rounds=64)
    fast = run_simulation("jax-tpu", proto, tc, run)
    assert fast.meta["engine"] == "bit-packed"
    slow = run_simulation("jax-tpu", proto, tc, run, want_curve=True)
    assert "engine" not in slow.meta          # curve keeps the bool path
    # identical trajectory => same rounds-to-target (the while-loop run
    # stops there; the curve run continues to max_rounds, so final
    # coverage/msgs are not comparable between the two driver shapes)
    assert fast.rounds == slow.rounds
    assert fast.coverage >= run.target_coverage
    # sharded twin routes too and agrees exactly
    sh = run_simulation("jax-tpu", proto, tc, run,
                        mesh_cfg=MeshConfig(n_devices=8))
    assert sh.meta["engine"] == "bit-packed"
    assert sh.rounds == fast.rounds
    assert sh.msgs == pytest.approx(fast.msgs)


# ~8 s (flight data, the log-PR rebalance): the sparse exchange keeps
# three in-gate smokes — the dry run's two sparse families and the
# compile-cache sparse driver leg (the PR 3 rationale) — and full
# mesh-vs-reference parity already runs under -m slow; this
# backend-routing depth joins it
@pytest.mark.slow
def test_backend_sparse_exchange():
    # the O(messages) all_to_all path as a product surface (--exchange)
    r = run_simulation("jax-tpu", ProtocolConfig(mode="pull", fanout=1),
                       TopologyConfig(family="complete", n=2048),
                       RunConfig(max_rounds=64),
                       mesh_cfg=MeshConfig(n_devices=8, exchange="sparse"))
    assert r.meta["exchange"] == "sparse"
    assert r.coverage >= 0.99
    b = r.meta["ici_bytes_per_round"]
    assert b["sparse"] < b["dense_equivalent"]
    # explicit families route to the capacity-capped topology path
    # (round 3; was a ValueError before) — full coverage in
    # tests/test_sharded_sparse.py
    r2 = run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                        TopologyConfig(family="ring", n=512, k=4),
                        RunConfig(max_rounds=200),
                        mesh_cfg=MeshConfig(n_devices=8, exchange="sparse"))
    assert r2.meta["exchange"] == "sparse"
    assert "overflow_dropped_requests" in r2.meta


def test_backend_halo_exchange():
    # the O(band) ppermute path as a product surface, with curve
    r = run_simulation("jax-tpu", ProtocolConfig(mode="pushpull", fanout=2),
                       TopologyConfig(family="ring", n=512, k=6),
                       RunConfig(max_rounds=128, target_coverage=0.9),
                       mesh_cfg=MeshConfig(n_devices=8, exchange="halo"),
                       want_curve=True)
    assert r.meta["exchange"] == "halo"
    assert r.meta["band"] == 3
    assert r.coverage >= 0.9
    assert len(r.curve) == 128
    with pytest.raises(ValueError, match="unknown exchange"):
        MeshConfig(n_devices=8, exchange="carrier-pigeon")
    # a requested non-dense exchange is never silently substituted
    with pytest.raises(ValueError, match="n_devices > 1"):
        run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                       TopologyConfig(family="complete", n=256), RunConfig(),
                       mesh_cfg=MeshConfig(n_devices=1, exchange="sparse"))
    with pytest.raises(ValueError, match="swim"):
        run_simulation("jax-tpu", ProtocolConfig(mode="swim"),
                       TopologyConfig(family="ring", n=256, k=4),
                       RunConfig(),
                       mesh_cfg=MeshConfig(n_devices=8, exchange="halo"))


def test_backend_rejections():
    with pytest.raises(ValueError, match="unknown backend"):
        run_simulation("torch", ProtocolConfig(), TopologyConfig(),
                       RunConfig())
    with pytest.raises(ValueError, match="no Go equivalent"):
        run_simulation("go-native", ProtocolConfig(mode="pushpull"),
                       TopologyConfig(family="ring", n=64), RunConfig())
    with pytest.raises(ValueError, match="capped"):
        run_simulation("go-native", ProtocolConfig(mode="flood"),
                       TopologyConfig(family="ring", n=50_000), RunConfig())
    from gossip_tpu.config import FaultConfig
    with pytest.raises(ValueError, match="no FaultConfig"):
        run_simulation("go-native", ProtocolConfig(mode="flood"),
                       TopologyConfig(family="ring", n=64), RunConfig(),
                       fault=FaultConfig(drop_prob=0.1))


def test_engine_fused_routing_and_rejections():
    import jax

    with pytest.raises(ValueError, match="unknown engine"):
        RunConfig(engine="warp")
    fused = RunConfig(engine="fused", max_rounds=64)
    # config errors surface identically on any backend (platform check last)
    with pytest.raises(ValueError, match="pull rounds only"):
        run_simulation("jax-tpu", ProtocolConfig(mode="push"),
                       TopologyConfig(n=4096), fused)
    with pytest.raises(ValueError, match="complete"):
        run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                       TopologyConfig(family="ring", n=4096, k=2), fused)
    from gossip_tpu.config import FaultConfig
    # round 4: static fault masks (drop_prob / node_death_rate) are
    # in-kernel on every fused layout — only SCRIPTED deaths reject
    with pytest.raises(ValueError, match="dead_nodes"):
        run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                       TopologyConfig(n=4096), fused,
                       fault=FaultConfig(dead_nodes=(3,), fail_round=2))
    # >32 rumors needs the plane-sharded multi-device path
    with pytest.raises(ValueError, match="shard rumor planes"):
        run_simulation("jax-tpu", ProtocolConfig(mode="pull", rumors=33),
                       TopologyConfig(n=4096), fused)
    # multi-rumor past the VMEM envelope: ANY fanout routes through the
    # staged big-table path since round 5 (multi-pass accumulation) —
    # no upper bound on n
    from gossip_tpu.ops.pallas_round import check_fused_fits
    assert check_fused_fits(50_000_000, 8, 1) > 0
    assert check_fused_fits(50_000_000, 8, 2) > 0
    # the single-rumor node-packed layout has no staged twin, so a
    # table past the envelope still raises the friendly error
    with pytest.raises(ValueError, match="VMEM budget"):
        check_fused_fits(2_000_000_000, 1)
    with pytest.raises(ValueError, match="jax-tpu kernel"):
        run_simulation("go-native", ProtocolConfig(mode="flood"),
                       TopologyConfig(family="ring", n=64, k=2), fused)
    # the RPC schema reaches the engine knob through the run object
    args = request_to_args({"run": {"engine": "fused"}})
    assert args["run"].engine == "fused"

    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="needs a TPU"):
            run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                           TopologyConfig(n=4096), fused)
        # round 4: want_curve is fused-eligible (scan twins), so off-TPU
        # the platform probe is the error that surfaces — not a config
        # rejection (on TPU this combination simply runs)
        with pytest.raises(ValueError, match="needs a TPU"):
            run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                           TopologyConfig(n=4096), fused, want_curve=True)
        # multi-device (rumor-plane sharded) path gates on TPU the same way
        with pytest.raises(ValueError, match="needs a TPU"):
            run_simulation("jax-tpu", ProtocolConfig(mode="pull", rumors=256),
                           TopologyConfig(n=4096), fused,
                           mesh_cfg=MeshConfig(n_devices=8))
    else:
        for rumors in (1, 8):
            rep = run_simulation("jax-tpu",
                                 ProtocolConfig(mode="pull", rumors=rumors),
                                 TopologyConfig(n=1 << 16), fused)
            assert rep.meta["engine"] == "fused-pallas"
            assert rep.coverage >= 0.99 and rep.rounds > 0
            assert rep.msgs == 2.0 * (1 << 16) * rep.rounds

    # a requested sparse/halo exchange is never silently dropped
    with pytest.raises(ValueError, match="no exchange"):
        run_simulation("jax-tpu", ProtocolConfig(mode="pull", rumors=256),
                       TopologyConfig(n=4096), fused,
                       mesh_cfg=MeshConfig(n_devices=8, exchange="sparse"))


def test_request_to_args_strict():
    args = request_to_args({"backend": "jax-tpu",
                            "proto": {"mode": "push", "fanout": 2},
                            "topology": {"family": "ring", "n": 64, "k": 2}})
    assert args["proto"].fanout == 2
    assert args["tc"].family == "ring"
    with pytest.raises(ValueError, match="unknown proto fields"):
        request_to_args({"proto": {"fanoot": 2}})


def test_rpc_sidecar_round_trip():
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    server, port = serve(port=0, max_workers=2)
    try:
        client = SidecarClient(f"127.0.0.1:{port}")
        h = client.health()
        assert h["ok"] and h["devices"] >= 1
        rep = client.run(
            backend="jax-tpu",
            proto={"mode": "pushpull", "fanout": 1},
            topology={"family": "erdos_renyi", "n": 500, "p": 0.02},
            run={"max_rounds": 64}, curve=True)
        assert rep["coverage"] >= 0.99
        assert rep["backend"] == "jax-tpu"
        assert len(rep["curve"]) == 64
        # same request direct == same result (the shim adds nothing)
        direct = run_simulation(
            "jax-tpu", ProtocolConfig(mode="pushpull", fanout=1),
            TopologyConfig(family="erdos_renyi", n=500, p=0.02),
            RunConfig(max_rounds=64), want_curve=True)
        assert rep["rounds"] == direct.rounds
        assert rep["msgs"] == direct.msgs
        # bad requests become INVALID_ARGUMENT, not server crashes
        import grpc as g
        with pytest.raises(g.RpcError) as ei:
            client.run(backend="torch")
        assert ei.value.code() == g.StatusCode.INVALID_ARGUMENT
        client.close()
    finally:
        server.stop(grace=None)


# Children inherit the session-scoped compile cache dir conftest put
# in GOSSIP_COMPILE_CACHE (a fresh temp dir — never the developer's
# persistent ~/.cache, which the old "" pin guarded against): CLI
# re-execs sharing a shape start warm.  An explicit --compile-cache /
# --no-compile-cache flag in a test still overrides the env default.
CLI_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": _REPO}


def _cli(*argv):
    return subprocess.run([sys.executable, "-m", "gossip_tpu", *argv],
                          capture_output=True, text=True, cwd=_REPO,
                          env=CLI_ENV, timeout=240)


def test_cli_run_json():
    p = _cli("run", "--backend", "go-native", "--mode", "flood",
             "--family", "ring", "--n", "128", "--k", "2",
             "--target", "1.0", "--max-rounds", "100")
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    assert rep["rounds"] == 64 and rep["coverage"] == 1.0


def test_cli_run_jax_and_error_paths():
    p = _cli("run", "--mode", "pushpull", "--n", "300",
             "--family", "erdos_renyi", "--p", "0.03", "--curve")
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    assert rep["coverage"] >= 0.99 and rep["curve"]
    p = _cli("run", "--backend", "go-native", "--mode", "pushpull",
             "--family", "ring", "--n", "64")
    assert p.returncode == 2
    assert "no Go equivalent" in p.stderr
    p = _cli("run", "--mode", "pull", "--n", "256", "--engine", "fused",
             "--ensemble", "4")
    assert p.returncode == 2
    assert "single-run only" in p.stderr


# depth tier (tier-1 wall budget, CRDT-PR rebalance): 2 CLI children;
# the compile-cache contracts keep in-gate coverage via
# tests/test_compile_cache.py (cross-process populate-then-hit +
# per-driver warm-vs-cold), and every CLI child in the gate already
# runs through _enable_compile_cache with the session cache dir
@pytest.mark.slow
def test_cli_compile_cache_flags(tmp_path):
    """--compile-cache creates the cache dir and the run still works
    (whether entries land depends on the 2 s min-compile threshold);
    --no-compile-cache runs without touching the path."""
    cache = tmp_path / "xla-cache"
    p = _cli("run", "--mode", "pushpull", "--n", "256",
             "--family", "erdos_renyi", "--p", "0.05",
             "--compile-cache", str(cache))
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout)["coverage"] >= 0.9
    assert cache.is_dir()
    off = tmp_path / "never-created"
    p = _cli("run", "--mode", "pushpull", "--n", "256",
             "--family", "erdos_renyi", "--p", "0.05",
             "--compile-cache", str(off), "--no-compile-cache")
    assert p.returncode == 0, p.stderr
    assert not off.exists()


def test_cli_grid_ns_one_program():
    # the n axis of the structural sweep, batched (VERDICT r3 item 6):
    # two sizes of one family in one compiled program, per-point n/family
    # reported; deeper bitwise coverage in tests/test_config_sweep.py
    p = _cli("grid", "--modes", "push", "pull", "--fanouts", "1",
             "--family", "erdos_renyi", "--ns", "300", "600",
             "--p", "0.02", "--max-rounds", "24")
    assert p.returncode == 0, p.stderr
    rows = [json.loads(line) for line in p.stdout.splitlines()]
    assert sorted({r["n"] for r in rows}) == [300, 600]
    assert all(r["family"] == "erdos_renyi" and r["converged"]
               for r in rows)


@pytest.mark.slow
def test_cli_sweep_smoke():
    p = _cli("sweep", "--scale", "0.002", "--devices", "4",
             "--only", "push-complete-64-goref", "pushpull-er-10k",
             "multirumor-10m-sharded")
    assert p.returncode == 0, p.stderr
    lines = [json.loads(line) for line in p.stdout.splitlines()]
    assert len(lines) == 3
    byname = {line["config"]: line for line in lines}
    assert byname["push-complete-64-goref"]["gonative_ref"]["coverage"] == 1.0
    assert byname["multirumor-10m-sharded"]["meta"]["devices"] == 4
    assert all(line["coverage"] >= 0.99 for line in lines)
    # row-level reconciliation (VERDICT r4 task 5): the row wall covers
    # engine wall + topo build + the go-native ref + named residual
    for line in lines:
        parts = (line["wall_s"]
                 + (line.get("meta") or {}).get("topo_build_s", 0.0)
                 + (line.get("gonative_ref") or {}).get("wall_s", 0.0)
                 + line["row_overhead_s"])
        assert line["row_wall_s"] >= line["wall_s"]
        assert abs(line["row_wall_s"] - parts) < 0.05


# slow tier (tier-1 wall budget): the diss-override CLI leg;
# sweep-CLI stays gated via test_cli_grid_ns_one_program
@pytest.mark.slow
def test_cli_sweep_swim_diss_override():
    """`sweep --swim-diss` re-measures the SWIM row under an A/B-
    arbitrated lowering without a code change (hw_refresh contract);
    trajectories must be identical across lowerings and the effective
    lowering must be visible in the row's meta."""
    rows = {}
    for impl in ("sort", "pack"):
        p = _cli("sweep", "--scale", "0.002", "--only", "swim-powerlaw-1m",
                 "--swim-diss", impl)
        assert p.returncode == 0, p.stderr
        rows[impl] = json.loads(p.stdout.splitlines()[0])
        assert rows[impl]["meta"]["swim_diss_effective"] == impl
    a, b = rows["sort"], rows["pack"]
    assert (a["rounds"], a["coverage"], a["msgs"]) == \
        (b["rounds"], b["coverage"], b["msgs"])


def test_fused_auto_routing_decision():
    """engine='auto' picks the fused engine exactly when a single-device
    run satisfies every _run_fused precondition (quietly)."""
    import jax

    from gossip_tpu.backend import _fused_auto_ok
    from gossip_tpu.config import FaultConfig

    pull = ProtocolConfig(mode="pull")
    comp = TopologyConfig(family="complete", n=100_000)

    # on CPU the fused engine is never auto-picked (hardware PRNG)
    if jax.default_backend() != "tpu":
        assert not _fused_auto_ok(pull, comp, None)

    # decision logic independent of platform, via a patched backend probe
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        assert _fused_auto_ok(pull, comp, None)
        assert _fused_auto_ok(ProtocolConfig(mode="pull", rumors=32),
                              comp, None)
        # the flagship: 10M x 32 rumors fanout 1 -> staged big path
        assert _fused_auto_ok(
            ProtocolConfig(mode="pull", rumors=32),
            TopologyConfig(family="complete", n=10_000_000), None)
        # fanout 2 past the VMEM envelope: the staged path multi-pass
        # accumulates since round 5 -> eligible
        assert _fused_auto_ok(
            ProtocolConfig(mode="pull", rumors=32, fanout=2),
            TopologyConfig(family="complete", n=10_000_000), None)
        assert not _fused_auto_ok(ProtocolConfig(mode="pushpull"),
                                  comp, None)
        assert not _fused_auto_ok(
            pull, TopologyConfig(family="ring", n=4096, k=2), None)
        # round 4: static fault masks are fused-eligible (in-kernel) —
        # auto may pick it; scripted deaths remain ineligible
        assert _fused_auto_ok(pull, comp, FaultConfig(drop_prob=0.1))
        assert not _fused_auto_ok(
            pull, comp, FaultConfig(dead_nodes=(5,), fail_round=1))
        assert not _fused_auto_ok(ProtocolConfig(mode="pull", rumors=33),
                                  comp, None)
    finally:
        jax.default_backend = real


def test_auto_stays_on_xla_path_off_tpu():
    """On CPU, engine='auto' must keep the bit-packed XLA path (and not
    record an auto fused pick)."""
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("CPU-only routing assertion")
    rep = run_simulation("jax-tpu", ProtocolConfig(mode="pull"),
                         TopologyConfig(family="complete", n=4096),
                         RunConfig(max_rounds=64))
    assert rep.meta.get("engine") == "bit-packed"
    assert "engine_auto" not in rep.meta
    assert rep.coverage >= 0.99


def test_engine_xla_is_the_auto_fused_opt_out():
    """engine='xla' forces the XLA kernels (identical to auto's XLA
    route), never the fused engine — the opt-out that keeps the
    single-device <-> sharded bitwise cross-validation reachable on TPU."""
    proto = ProtocolConfig(mode="pull")
    tc = TopologyConfig(family="complete", n=2048)
    run_auto = RunConfig(max_rounds=64)
    run_xla = RunConfig(max_rounds=64, engine="xla")
    a = run_simulation("jax-tpu", proto, tc, run_auto)
    x = run_simulation("jax-tpu", proto, tc, run_xla)
    assert x.meta["engine"] == "bit-packed"
    assert "engine_auto" not in x.meta
    # same threefry stream when auto also lands on XLA (always on CPU)
    if "engine_auto" not in a.meta:
        assert (a.rounds, a.coverage, a.msgs) == (x.rounds, x.coverage,
                                                  x.msgs)
    args = request_to_args({"run": {"engine": "xla"}})
    assert args["run"].engine == "xla"


@pytest.mark.slow
def test_cli_checkpoint_resume_and_profile(tmp_path):
    ck = str(tmp_path / "run.npz")
    prof = str(tmp_path / "prof")
    # 12 rounds, checkpoint every 5 -> file exists, rounds == 12
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--max-rounds",
             "12", "--checkpoint", ck, "--checkpoint-every", "5")
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    assert rep["rounds"] == 12 and os.path.exists(ck)
    # resume continues to 20 TOTAL rounds and must match an
    # uninterrupted 20-round checkpointed run bitwise (same seed)
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--max-rounds",
             "20", "--checkpoint", ck, "--resume")
    assert p.returncode == 0, p.stderr
    resumed = json.loads(p.stdout)
    assert resumed["rounds"] == 20 and resumed["resumed"] is True
    ck2 = str(tmp_path / "solo.npz")
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--max-rounds",
             "20", "--checkpoint", ck2)
    solo = json.loads(p.stdout)
    assert (resumed["coverage"], resumed["msgs"]) == (solo["coverage"],
                                                      solo["msgs"])
    # round-4: swim checkpointing is a supported engine now (the full
    # resume contract lives in test_checkpoint_sharded.py); the guard
    # that remains is the backend gate
    p = _cli("run", "--mode", "swim", "--n", "256", "--max-rounds", "6",
             "--checkpoint", str(tmp_path / "sw.npz"))
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout)["engine"] == "swim-xla"
    p = _cli("run", "--backend", "go-native", "--n", "64",
             "--checkpoint", str(tmp_path / "gn.npz"))
    assert p.returncode == 2 and "jax-tpu engines" in p.stderr
    # resume with different flags refuses (config fingerprint mismatch)
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--max-rounds",
             "30", "--seed", "9", "--checkpoint", ck, "--resume")
    assert p.returncode == 2 and "config mismatch" in p.stderr
    assert "seed" in p.stderr
    # --resume without --checkpoint errors instead of silently restarting
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--resume")
    assert p.returncode == 2 and "--checkpoint" in p.stderr
    # round 4: --curve composes with the segment driver (scan segments;
    # deeper coverage in tests/test_checkpoint_sharded.py)
    p = _cli("run", "--mode", "pushpull", "--n", "512", "--max-rounds",
             "6", "--checkpoint", str(tmp_path / "curve.npz"), "--curve")
    assert p.returncode == 0, p.stderr
    assert len(json.loads(p.stdout)["curve"]) == 6
    # --profile wraps the run and writes a trace directory
    p = _cli("run", "--mode", "pull", "--n", "256", "--max-rounds", "16",
             "--profile", prof)
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    assert rep["profile_logdir"] == prof
    assert os.path.isdir(prof) and any(os.scandir(prof))


def test_rpc_sidecar_runs_rumor_mode():
    """The new SIR family is reachable through the service seam with its
    extinction metadata intact."""
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    server, port = serve(port=0, max_workers=2)
    try:
        client = SidecarClient(f"127.0.0.1:{port}")
        rep = client.run(proto={"mode": "rumor", "rumor_k": 2,
                                "rumor_variant": "blind"},
                         topology={"family": "complete", "n": 1024},
                         run={"max_rounds": 128})
        assert rep["mode"] == "rumor"
        assert rep["meta"]["terminated"] is True
        assert rep["meta"]["variant"] == "blind"
        assert 0 < rep["coverage"] <= 1.0
    finally:
        server.stop(0)


# ---------------------------------------------------------------------
# engine='native' above the go-native cap + --parity-check (VERDICT r2
# item 8).


def test_gonative_native_engine_raises_cap():
    """engine='native' forces the C++ core and lifts the 20k ceiling;
    engine='auto' above the ceiling stays a loud error."""
    import dataclasses as _dc
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.runtime.native_sim import native_available
    proto = ProtocolConfig(mode="flood")
    tc = TopologyConfig(family="erdos_renyi", n=25_000, p=0.0004, seed=1)
    run = RunConfig(max_rounds=24)
    with pytest.raises(ValueError, match="native"):
        run_simulation("go-native", proto, tc, run)
    if not native_available():
        pytest.skip("no C++ compiler")
    rep = run_simulation("go-native", proto, tc,
                         _dc.replace(run, engine="native"))
    assert rep.meta["engine"] == "NativeGoSim"
    assert rep.coverage > 0.95
    # jax-tpu must reject the go-native engine selection loudly
    with pytest.raises(ValueError, match="go-native"):
        run_simulation("jax-tpu", proto, tc,
                       _dc.replace(run, engine="native"))
    # and xla/fused are jax selections the event backend rejects
    with pytest.raises(ValueError, match="jax-tpu"):
        run_simulation("go-native", proto, tc,
                       _dc.replace(run, engine="xla"))


def test_cli_parity_check_race_free_ring():
    """The CLI parity artifact: on the race-free k=2 ring the two
    backends agree EXACTLY on the hop clock."""
    p = _cli("run", "--mode", "flood", "--family", "ring", "--n", "256",
             "--k", "2", "--max-rounds", "140", "--target", "1.0",
             "--parity-check")
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    assert rep["curve_gap"] == 0.0
    assert rep["hop_bound_violation"] == 0.0
    assert rep["fixed_point_gap"] == 0.0


def test_cli_parity_check_rejects_non_flood():
    p = _cli("run", "--mode", "push", "--family", "ring", "--n", "64",
             "--parity-check")
    assert p.returncode == 2
    assert "flood" in p.stderr


# depth tier (tier-1 wall budget, CRDT-PR rebalance): 3 CLI children
# of pure flag-validation; the parity-check surface keeps its in-gate
# smokes via test_cli_parity_check_race_free_ring (happy path) and
# test_cli_parity_check_rejects_non_flood (rejection path)
@pytest.mark.slow
def test_cli_parity_check_flag_conflicts_and_truncation():
    # insufficient --max-rounds must error, not report a bogus gap
    p = _cli("run", "--mode", "flood", "--family", "ring", "--n", "256",
             "--k", "2", "--max-rounds", "20", "--target", "1.0",
             "--parity-check")
    assert p.returncode == 2 and "max-rounds" in p.stderr
    # conflicting run shapes are rejected, never silently dropped
    p = _cli("run", "--mode", "flood", "--family", "ring", "--n", "128",
             "--k", "2", "--parity-check", "--ensemble", "4")
    assert p.returncode == 2 and "parity" in p.stderr
    p = _cli("run", "--mode", "flood", "--family", "ring", "--n", "128",
             "--k", "2", "--parity-check", "--curve")
    assert p.returncode == 2 and "self-contained" in p.stderr


def test_until_reports_split_compile_and_steady_wall():
    """Hardware-table contract (round-2 verdict): non-curve runs report
    compile_s and steady_wall_s separately so tables stop mixing one-off
    compile cost with steady-state throughput."""
    for proto in (ProtocolConfig(mode="pushpull"),        # bool until
                  ProtocolConfig(mode="pull")):           # bit-packed
        r = run_simulation("jax-tpu", proto,
                           TopologyConfig(family="complete", n=256),
                           RunConfig(max_rounds=32))
        assert r.meta["compile_s"] > 0
        assert r.meta["steady_wall_s"] > 0
        assert r.meta["compile_s"] + r.meta["steady_wall_s"] \
            <= r.wall_s + 0.05
    # swim early-exit driver too
    r = run_simulation("jax-tpu",
                       ProtocolConfig(mode="swim", fanout=2,
                                      swim_subjects=4, swim_proxies=2,
                                      swim_suspect_rounds=4),
                       TopologyConfig(family="complete", n=128),
                       RunConfig(max_rounds=40))
    assert r.meta["compile_s"] > 0 and r.meta["steady_wall_s"] > 0
    # curve runs keep the fused wall (no AOT split there)
    r = run_simulation("jax-tpu", ProtocolConfig(mode="pushpull"),
                       TopologyConfig(family="complete", n=256),
                       RunConfig(max_rounds=16), want_curve=True)
    assert "compile_s" not in r.meta


# depth tier (tier-1 wall budget, CRDT-PR rebalance): the sidecar
# surface keeps test_rpc_sidecar_round_trip in-gate, and the shared
# ensemble dispatch (backend.run_ensemble) stays gated via
# tests/test_sweep.py's ensemble pins — this RPC-transport twin of the
# same dispatch runs under -m slow
@pytest.mark.slow
def test_rpc_sidecar_ensemble():
    """Round 4: the Ensemble RPC — seed-ensemble statistics in one
    coarse call, mode-dispatched through backend.run_ensemble (shared
    with the CLI so the two surfaces cannot drift)."""
    import grpc

    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    server, port = serve(0, 2)
    c = SidecarClient(f"localhost:{port}")
    try:
        r = c.ensemble(proto={"mode": "pushpull"},
                       topology={"family": "complete", "n": 256},
                       run={"max_rounds": 24}, ensemble=4)
        assert r["ensemble"]["seeds"] == 4
        assert r["ensemble"]["converged"] == 4
        r = c.ensemble(proto={"mode": "swim", "fanout": 2,
                              "swim_subjects": 4, "swim_proxies": 2,
                              "swim_suspect_rounds": 4},
                       topology={"family": "complete", "n": 128},
                       run={"max_rounds": 40}, seeds=[5, 6, 7])
        assert r["metric"] == "detection_fraction"
        assert r["ensemble"]["converged"] == 3
        # strict schema: flood, both/neither seed forms, unknown fields
        for bad in (dict(proto={"mode": "flood"}, topology={"n": 64},
                         run={}, ensemble=2),
                    dict(proto={"mode": "push"}, topology={"n": 64},
                         run={}),
                    dict(proto={"mode": "push"}, topology={"n": 64},
                         run={}, ensemble=2, seeds=[1]),
                    dict(proto={"mode": "push"}, topology={"n": 64},
                         run={}, ensemble=2, bogus=1)):
            with pytest.raises(grpc.RpcError) as exc:
                c.ensemble(**bad)
            assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        c.close()
        server.stop(0)
