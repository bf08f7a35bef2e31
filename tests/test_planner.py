"""Scale planner: plan algebra + streamed bit-plane tiling contracts.

The budget model (gossip_tpu/planner/budget) is pure host arithmetic,
so its pins are free; the streaming pins (gossip_tpu/planner/stream)
share ONE plan shape across tests so the tile-loop executable is
compiled once per session (the module-level step cache + jit shape
cache — exactly the reuse the subsystem exists to certify).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import ChurnConfig, FaultConfig
from gossip_tpu.planner import budget as PB
from gossip_tpu.planner import stream as PS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MIXED = FaultConfig(drop_prob=0.05, seed=2, churn=ChurnConfig(
    events=((3, 1, 4), (9, 2, -1)),       # crash/recover + permanent
    partitions=((1, 4, 256),),            # open window
    ramp=(0, 3, 0.0, 0.15)))              # drop ramp


def _forced_plan(n=512, rumors=128, tiles=2, max_rounds=6, seed=0,
                 fault=MIXED, devices=1):
    """A plan whose artificial HBM budget forces exactly the requested
    tile count — via the ONE shared construction
    (budget.forced_device_for_tiles); every streaming test shares the
    default shape so the tile-loop executable compiles once per
    session."""
    dev = PB.forced_device_for_tiles(
        n, rumors=rumors, fanout=2, max_rounds=max_rounds,
        fault=fault, tiles_at_least=tiles, devices=devices,
        host_ram_bytes=1 << 30)
    return PB.plan_scale(n, rumors=rumors, device=dev, fanout=2,
                         max_rounds=max_rounds, fault=fault,
                         segment_every=3, seed=seed)


# -------------------------------------------------------------- algebra


def test_jax_free_twins_cannot_drift():
    """budget.py never imports jax, so its word-count and canonical-
    horizon forms are duplicated — this pin is what makes the
    duplication safe."""
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops.bitpack import n_words
    for r in (1, 31, 32, 33, 64, 255, 256, 1000):
        assert PB.n_words(r) == n_words(r)
    for ch in (ChurnConfig(events=((0, 1, 2),)),
               ChurnConfig(partitions=((0, 40, 8),)),
               ChurnConfig(ramp=(0, 100, 0.0, 0.5)),
               MIXED.churn):
        f = FaultConfig(churn=ch)
        assert PB.sched_t_pad(f) == NE.canonical_horizon(ch), ch
    assert PB.sched_t_pad(None) == NE.SCHED_T_MIN
    # and the module really is jax-free (the no-chip contract, the
    # analysis/ rationale)
    import ast
    src = os.path.join(_REPO, "gossip_tpu", "planner", "budget.py")
    tree = ast.parse(open(src).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            assert not any(a.name.split(".")[0] == "jax"
                           for a in node.names)
        if isinstance(node, ast.ImportFrom):
            assert (node.module or "").split(".")[0] != "jax"


@pytest.mark.parametrize("engine", PB.ENGINES)
def test_budget_monotone_in_n(engine):
    """Per-device peak bytes are nondecreasing in N at fixed tile
    width — the property that makes 'largest feasible N' well-defined
    and feasibility monotone (a smaller N always fits a budget a
    bigger one fit)."""
    last = 0
    for n in (1000, 4096, 10**5, 10**6, 10**7, 10**8):
        p = sum(PB.engine_components(
            engine, n=n, rumors=64, fanout=2, tile_words=1, devices=4,
            fault=MIXED, max_rounds=64).values())
        assert p >= last, (engine, n)
        last = p


def test_bucket_stability_and_determinism():
    """Growing N under a FIXED budget never widens the tile bucket
    (pow2 buckets shrink monotonically), and planning is a pure
    function of its inputs."""
    # 64 MB: fits n=10**6 at the 1-word tile even with the pipeline's
    # fetch_buffer term in the peak (planner/budget engine_components)
    dev = PB.DeviceSpec(chips=1, hbm_bytes_per_chip=64 * 1024**2,
                        host_ram_bytes=1 << 34)
    last_bucket = None
    for n in (10**4, 10**5, 3 * 10**5, 10**6):
        plan = PB.plan_scale(n, rumors=256, device=dev, fanout=1,
                             max_rounds=32)
        assert (plan.bucket_words & (plan.bucket_words - 1)) == 0
        assert plan.tiles * plan.bucket_words >= plan.total_words
        if last_bucket is not None:
            assert plan.bucket_words <= last_bucket, n
        last_bucket = plan.bucket_words
        again = PB.plan_scale(n, rumors=256, device=dev, fanout=1,
                              max_rounds=32)
        assert again.to_dict() == plan.to_dict()


def test_infeasible_refusals_name_the_binding_constraint():
    # HBM wall: even the 1-word tile cannot fit — constraint named in
    # the message AND machine-readable on the exception
    with pytest.raises(PB.InfeasiblePlanError) as ei:
        PB.plan_scale(10**8, rumors=64,
                      device=PB.DeviceSpec(chips=1,
                                           hbm_bytes_per_chip=10**6,
                                           host_ram_bytes=1 << 40),
                      fanout=2, max_rounds=64)
    assert ei.value.binding in dict(
        PB.engine_components("packed", n=10**8, rumors=64, fanout=2,
                             tile_words=1, devices=1, fault=None,
                             max_rounds=64))
    assert ei.value.binding in str(ei.value)
    assert "1-word tile" in str(ei.value)
    # host-RAM wall: streaming cannot help a host that cannot hold the
    # packed state
    with pytest.raises(PB.InfeasiblePlanError) as ei:
        PB.plan_scale(10**8, rumors=1024,
                      device=PB.DeviceSpec(chips=256,
                                           hbm_bytes_per_chip=1 << 34,
                                           host_ram_bytes=10**9))
    assert ei.value.binding == "host_state"
    assert "host RAM" in str(ei.value)
    # int32 node-id space
    with pytest.raises(PB.InfeasiblePlanError) as ei:
        PB.plan_scale(2**31, device=PB.DeviceSpec())
    assert ei.value.binding == "node_id_dtype"
    # non-tileable mode refused at PLAN time
    with pytest.raises(ValueError, match="reverse delta"):
        PB.plan_scale(1000, mode=C.ANTI_ENTROPY)
    with pytest.raises(ValueError, match="unknown engine"):
        PB.plan_scale(1000, engine="warp")


def test_plan_json_round_trip_and_validation():
    plan = _forced_plan()
    doc = json.loads(plan.to_json())
    again = PB.plan_from_dict(doc)
    assert again.to_dict() == plan.to_dict()
    assert again.fault == plan.fault      # churn tuples survive JSON
    # structural validation names the offending field
    bad = json.loads(plan.to_json())
    bad["tiling"]["bucket_words"] = 3
    with pytest.raises(ValueError, match="power of two"):
        PB.validate_plan(bad)
    bad = json.loads(plan.to_json())
    del bad["segments"]
    with pytest.raises(ValueError, match="segments"):
        PB.validate_plan(bad)
    bad = json.loads(plan.to_json())
    bad["version"] = 99
    with pytest.raises(ValueError, match="version"):
        PB.validate_plan(bad)
    # a hand-edited tiling that no longer matches the model is refused
    bad = json.loads(plan.to_json())
    bad["tiling"]["tiles"] = plan.tiles * 2
    bad["tiling"]["bucket_words"] = plan.bucket_words
    with pytest.raises(ValueError, match="tiling"):
        PB.plan_from_dict(bad)
    # a wrong-TYPED section refuses the same one-line way (never a
    # TypeError/AttributeError traceback)
    for sec in ("target", "tiling", "segments", "budget", "device"):
        bad = json.loads(plan.to_json())
        bad[sec] = 7
        with pytest.raises(ValueError, match=sec):
            PB.validate_plan(bad)
    # a truncated budget/foreign device section is a one-line
    # ValueError naming the section, never a KeyError/TypeError
    # traceback (the CLI refusal contract)
    bad = json.loads(plan.to_json())
    del bad["budget"]["reserve_frac"]
    with pytest.raises(ValueError, match="reserve_frac"):
        PB.plan_from_dict(bad)
    bad = json.loads(plan.to_json())
    bad["device"]["warp_drives"] = 1
    with pytest.raises(ValueError, match="device"):
        PB.plan_from_dict(bad)
    # fingerprints: content-sensitive, order-insensitive
    fp = PB.plan_fingerprint(doc)
    assert fp == PB.plan_fingerprint(json.loads(plan.to_json()))
    other = _forced_plan(seed=1)
    assert fp != PB.plan_fingerprint(other.to_dict())


def test_forced_device_verifies_the_tile_count():
    """forced_device_for_tiles must DELIVER >= the requested tiles (it
    plans against its own budget and shrinks the candidate width), and
    refuse loudly when fixed-size components make the request
    unforceable — never silently under-deliver."""
    for tiles in (2, 4):
        dev = PB.forced_device_for_tiles(
            512, rumors=128, fanout=2, max_rounds=6, fault=MIXED,
            tiles_at_least=tiles)
        plan = PB.plan_scale(512, rumors=128, device=dev, fanout=2,
                             max_rounds=6, fault=MIXED)
        assert plan.tiles >= tiles
    # degenerate shape: n so tiny the alignment/sched floors dominate
    # every tile width — a loud refusal, not a 1-tile "forced" plan
    with pytest.raises(ValueError, match="cannot force"):
        PB.forced_device_for_tiles(4, rumors=256, fanout=1,
                                   max_rounds=4, fault=None,
                                   tiles_at_least=4)
    # more tiles than word planes is word-granularly impossible
    with pytest.raises(ValueError, match="word"):
        PB.forced_device_for_tiles(512, rumors=32, fanout=1,
                                   max_rounds=4, fault=None,
                                   tiles_at_least=2)


def test_host_init_packed_matches_jax_init():
    from gossip_tpu.config import ProtocolConfig, RunConfig
    from gossip_tpu.models.si_packed import init_packed_state
    for n, r, o in ((64, 40, 3), (17, 5, 0), (128, 64, 7)):
        st = init_packed_state(RunConfig(seed=0, origin=o),
                               ProtocolConfig(mode=C.PULL, fanout=1,
                                              rumors=r), n)
        assert np.array_equal(np.asarray(st.seen),
                              PS.host_init_packed(n, r, o)), (n, r, o)


# ------------------------------------------------------------ streaming


def test_streamed_bitwise_under_mixed_fault_program():
    """THE tentpole gate: the T-tile streamed trajectory — final
    state, msgs, and the exact ``dropped`` total — is BITWISE the
    untiled in-memory run, under the full mixed program (event +
    permanent crash + open partition window + drop ramp)."""
    plan = _forced_plan()
    assert plan.tiles == 2
    res = PS.run_at_scale(plan, check_bitwise=True)
    assert res.bitwise_equal is True
    assert res.dropped > 0          # the program actually destroyed
    assert res.rounds == plan.max_rounds


def test_pipelined_four_tiles_bitwise_vs_no_overlap_and_untiled(
        tmp_path):
    """The pipeline gate: a forced >=4-tile run with the three-stage
    fetch overlap is BITWISE the serial --no-overlap leg AND the
    untiled reference (state, msgs, exact dropped) under the mixed
    fault program; its tile_stream ledger events carry every tile's
    four walls and the run reports a sane overlap_efficiency."""
    from gossip_tpu.utils import telemetry
    plan = _forced_plan(tiles=4)
    assert plan.tiles >= 4
    path = str(tmp_path / "tile_stream.jsonl")
    led = telemetry.Ledger(path)
    prev = telemetry.activate(led)
    try:
        piped = PS.run_at_scale(plan, check_bitwise=True,
                                keep_state=True)
    finally:
        telemetry.activate(prev)
        led.close()
    assert piped.overlap and piped.bitwise_equal is True
    assert 0.0 <= piped.overlap_efficiency <= 1.0
    serial = PS.run_at_scale(plan, overlap=False, keep_state=True)
    assert not serial.overlap
    assert np.array_equal(piped.final_state, serial.final_state)
    assert (piped.msgs, piped.dropped) == (serial.msgs, serial.dropped)
    evs = [e for e in telemetry.load_ledger(path)
           if e.get("ev") == "tile_stream"]
    # one event per tile per segment, each with the four pipeline walls
    assert len(evs) == plan.tiles * plan.segment_count, evs
    for e in evs:
        for k in ("put_ms", "dispatch_ms", "wait_ms", "copy_ms"):
            assert e[k] >= 0.0, e
    assert {e["tile"] for e in evs} == set(range(plan.tiles))
    run_ev = [e for e in telemetry.load_ledger(path)
              if e.get("ev") == "scale_run"][-1]
    assert run_ev["overlap"] is True
    assert 0.0 <= run_ev["overlap_efficiency"] <= 1.0


def test_two_slice_hybrid_bitwise_vs_single_slice():
    """The multislice gate: a dcn_slices=2 plan EXECUTES (the refusal
    is lifted) on the simulated hybrid mesh — conftest forces 8 CPU
    devices — and its trajectory is bitwise the single-slice run's:
    tiles fan out round-robin with zero cross-slice bytes, so the
    slice count is invisible to the result."""
    plan1 = _forced_plan(tiles=4)
    dev2 = PB.DeviceSpec(
        chips=2, slices=2,
        hbm_bytes_per_chip=plan1.device.hbm_bytes_per_chip,
        host_ram_bytes=plan1.device.host_ram_bytes)
    plan2 = PB.plan_scale(plan1.n, rumors=plan1.rumors, device=dev2,
                          fanout=plan1.fanout,
                          max_rounds=plan1.max_rounds,
                          fault=plan1.fault,
                          segment_every=plan1.segment_every)
    assert plan2.mesh_kind == "hybrid" and plan2.dcn_slices == 2
    assert plan2.tiles == plan1.tiles >= 4
    r1 = PS.run_at_scale(plan1, keep_state=True)
    r2 = PS.run_at_scale(plan2, check_bitwise=True, keep_state=True)
    assert r2.dcn_slices == 2
    assert r2.bitwise_equal is True     # vs its own untiled reference
    assert np.array_equal(r1.final_state, r2.final_state)
    assert (r1.msgs, r1.dropped) == (r2.msgs, r2.dropped)


def test_two_slice_mid_pipeline_resume_bitwise(tmp_path):
    """Crash safety through the fan-out: halt a 2-slice pipelined run
    after one published segment, resume, land bitwise on the
    uninterrupted run — all slices drain into the ONE host cursor
    before the publish, so the resume contract is slice-count
    independent."""
    plan1 = _forced_plan(tiles=4)
    dev2 = PB.DeviceSpec(
        chips=2, slices=2,
        hbm_bytes_per_chip=plan1.device.hbm_bytes_per_chip,
        host_ram_bytes=plan1.device.host_ram_bytes)
    plan = PB.plan_scale(plan1.n, rumors=plan1.rumors, device=dev2,
                         fanout=plan1.fanout,
                         max_rounds=plan1.max_rounds,
                         fault=plan1.fault,
                         segment_every=plan1.segment_every)
    straight = PS.run_at_scale(plan, keep_state=True)
    ck = str(tmp_path / "slice_ck.npz")
    r1 = PS.run_at_scale(plan, checkpoint_path=ck,
                         halt_after_segments=1)
    assert r1.halted
    r2 = PS.run_at_scale(plan, checkpoint_path=ck, resume=True,
                         keep_state=True)
    assert r2.resumed and r2.rounds == plan.max_rounds
    assert np.array_equal(r2.final_state, straight.final_state)
    assert r2.msgs == straight.msgs
    assert r2.dropped == straight.dropped


def test_tiles_compile_once_per_bucket_and_salted_reentry_zero(
        assert_compiles):
    """K tiles share ONE executable per pow2 shape bucket, and a
    SALTED plan (new schedule content + seed, same shapes) re-enters
    with ZERO compiles — tile content and schedules are operands,
    never memo keys."""
    PS.run_at_scale(_forced_plan(seed=3))     # bucket executable built
    salted = FaultConfig(drop_prob=0.05, seed=2, churn=ChurnConfig(
        events=((7, 1, 4), (15, 2, -1)),
        partitions=((1, 4, 100),),
        ramp=(0, 3, 0.0, 0.1)))
    with assert_compiles(0):
        res = PS.run_at_scale(_forced_plan(seed=4, fault=salted))
    assert res.tiles == 2


def test_streamed_resume_bitwise_and_fingerprint_refusals(tmp_path):
    """Crash safety through the streamed driver: halt after the first
    published segment, resume, land bitwise on the uninterrupted run;
    a checkpoint from a DIFFERENT plan (or fault program) is refused
    loudly."""
    plan = _forced_plan()
    straight = PS.run_at_scale(plan, keep_state=True)
    ck = str(tmp_path / "scale_ck.npz")
    r1 = PS.run_at_scale(plan, checkpoint_path=ck,
                         halt_after_segments=1)
    assert r1.halted and r1.rounds == plan.segment_every
    r2 = PS.run_at_scale(plan, checkpoint_path=ck, resume=True,
                         keep_state=True)
    assert r2.resumed and r2.rounds == plan.max_rounds
    assert np.array_equal(r2.final_state, straight.final_state)
    assert r2.msgs == straight.msgs
    assert r2.dropped == straight.dropped
    # a different plan's checkpoint is refused by fingerprint
    with pytest.raises(ValueError, match="different scale plan"):
        PS.run_at_scale(_forced_plan(seed=9), checkpoint_path=ck,
                        resume=True)
    # the fault-program backstop: same plan fingerprint stamped, but a
    # checkpoint whose fault_program entry disagrees (a foreign or
    # pre-planner checkpoint) must not be continued
    import jax
    import jax.numpy as jnp
    from gossip_tpu.models.state import SimState
    from gossip_tpu.utils.checkpoint import save_state
    save_state(ck, SimState(seen=straight.final_state,
                            round=jnp.int32(3),
                            base_key=jax.random.key(0),
                            msgs=jnp.float32(0.0)),
               extra_meta={"round": 3,
                           "scale_plan": PB.plan_fingerprint(
                               plan.to_dict()),
                           "fault_program": "not-the-real-digest"})
    with pytest.raises(ValueError, match="fault program"):
        PS.run_at_scale(plan, checkpoint_path=ck, resume=True)


def test_stream_refusals_are_loud():
    plan = _forced_plan()
    broken = dataclasses.replace(plan, engine="dense")
    with pytest.raises(ValueError, match="packed engine only"):
        PS.run_at_scale(broken)
    # dcn_slices > 1 EXECUTES now (the multislice fan-out), but a plan
    # wanting more slices than the platform reports still refuses
    # loudly (multislice._hybrid_device_grid), never silently shrinks
    overdrawn = dataclasses.replace(plan, dcn_slices=999)
    with pytest.raises(ValueError, match="devices"):
        PS.run_at_scale(overdrawn)
    # a caller-supplied mesh whose grid disagrees with the plan's
    # slicing refuses too — a silently re-gridded run would make the
    # per-slice accounting unattributable
    two_slice = dataclasses.replace(plan, dcn_slices=2)
    from gossip_tpu.parallel.sharded import make_mesh
    with pytest.raises(ValueError, match="hybrid"):
        PS.run_at_scale(two_slice, mesh=make_mesh(1, axis_name="nodes"))
    with pytest.raises(ValueError, match="checkpoint_path"):
        PS.run_at_scale(plan, resume=True)


@pytest.mark.slow
def test_streamed_bitwise_on_node_mesh():
    """The sharded leg: streamed-vs-untiled bitwise on a 4-device node
    mesh.  Slow-tier depth: the dry-run ``scale_plan`` family runs
    this exact mesh program (with the bitwise assert inside) in every
    tier-1 session via the dryrun_pair fixture."""
    from gossip_tpu.parallel.sharded import make_mesh
    plan = _forced_plan(n=1024, devices=4)
    res = PS.run_at_scale(plan, check_bitwise=True,
                          mesh=make_mesh(4, axis_name="nodes"))
    assert res.bitwise_equal is True
    assert res.tiles == 2


def test_memory_prediction_bounds_measurement():
    """The budget model's honesty gate: the tile loop's AOT memory
    analysis (args + outputs + temps) lands INSIDE the predicted peak.
    (Tightness on real HBM is the hw_refresh scale_plan step's job —
    CPU XLA fuses temps, so only the bound direction is portable.)"""
    plan = _forced_plan(seed=5)
    res = PS.run_at_scale(plan, measure_memory=True)
    assert res.measured_loop_bytes is not None
    assert res.measured_loop_bytes <= res.predicted_peak_device_bytes


# --------------------------------------------------- committed evidence


def test_committed_scale_record_verdict():
    """The committed artifacts/ledger_scale_r23.jsonl cannot rot:
    provenance-stamped, N = 2^20 forced to >= 4 streamed tiles through
    the three-stage pipeline, final state bitwise the untiled run AND
    the --no-overlap serial run, a sane overlap_efficiency, the
    simulated 2-slice hybrid leg executing bitwise (the dcn_slices
    refusal is lifted), coverage 1.0 on the eventual-alive set,
    measured allocation inside the predicted peak, resume bitwise."""
    from gossip_tpu.utils import telemetry
    path = os.path.join(_REPO, "artifacts", "ledger_scale_r23.jsonl")
    events = telemetry.load_ledger(path, run="last")
    assert events[0]["ev"] == "provenance"
    assert len(events[0]["git_commit"]) == 40
    rec = [e for e in events if e["ev"] == "scale_record"][-1]
    assert rec["ok"] is True
    assert rec["n"] == 2**20
    assert rec["tiles"] >= 4
    assert rec["bitwise_equal"] is True
    assert rec["no_overlap_bitwise"] is True
    assert 0.0 <= rec["overlap_efficiency"] <= 1.0
    assert rec["two_slice_bitwise"] is True
    assert rec["two_slice_dcn_slices"] == 2
    assert rec["coverage"] == 1.0
    assert rec["resume_bitwise"] is True
    assert rec["measured_loop_bytes"] <= \
        rec["predicted_peak_device_bytes"]
    assert rec["dropped"] > 0        # the mixed program really ran
    # per-tile pipeline walls landed in the same run (sync=False
    # emission from inside the timed segment loop)
    ts = [e for e in events if e["ev"] == "tile_stream"]
    assert len(ts) >= rec["tiles"]
    assert all(k in ts[0]
               for k in ("put_ms", "dispatch_ms", "wait_ms",
                         "copy_ms"))
    # the smoke rehearsal parses with the same shape (hw_refresh
    # convention)
    smoke = telemetry.load_ledger(
        os.path.join(_REPO, "artifacts",
                     "ledger_scale_r23.smoke.jsonl"), run="last")
    srec = [e for e in smoke if e["ev"] == "scale_record"][-1]
    assert srec["ok"] is True and srec["smoke"] is True


# ------------------------------------------------------------------ CLI


def test_cli_plan_validate_and_infeasible(tmp_path, capsys):
    from gossip_tpu import cli
    out = str(tmp_path / "plan.json")
    rc = cli.main(["plan", "--n", "4096", "--rumors", "256", "--chips",
                   "1", "--hbm-gb", "0.001", "--host-ram-gb", "1",
                   "--max-rounds", "6", "--segment-every", "3",
                   "--drop", "0.05",
                   "--scenario", "event=1:1:3;partition=1:3:32;"
                                 "ramp=0:2:0.0:0.2",
                   "--out", out])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip())
    assert line["tiles"] >= 2 and line["plan_written"] == out
    rc = cli.main(["plan", "--validate", out])
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip())["plan_valid"]
    # infeasible: exit 2, one line, constraint named
    rc = cli.main(["plan", "--n", str(10**8), "--chips", "1",
                   "--hbm-gb", "0.001"])
    captured = capsys.readouterr()
    assert rc == 2
    assert "binding constraint" in captured.err
    assert captured.out == ""
    # a corrupted plan file is refused with the field named
    doc = json.load(open(out))
    doc["tiling"]["tiles"] = doc["tiling"]["tiles"] * 2
    bad = str(tmp_path / "bad.json")
    json.dump(doc, open(bad, "w"))
    rc = cli.main(["plan", "--validate", bad])
    assert rc == 2
    assert "tiling" in capsys.readouterr().err


def test_cli_scale_run_executes_plan(tmp_path, capsys):
    """scale-run end to end on the shared small shape: bitwise gate on,
    checkpoint published, then run --plan resumes it (the two CLI
    surfaces share _run_plan_file)."""
    from gossip_tpu import cli
    plan = _forced_plan()
    pf = str(tmp_path / "plan.json")
    with open(pf, "w") as f:
        f.write(plan.to_json())
    ck = str(tmp_path / "ck.npz")
    rc = cli.main(["scale-run", "--plan", pf, "--checkpoint", ck,
                   "--check-bitwise"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert out["bitwise_equal"] is True and out["tiles"] == 2
    assert os.path.exists(ck)
    rc = cli.main(["run", "--plan", pf, "--checkpoint", ck, "--resume"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert out["resumed"] is True
    # no-silent-drop: flags the plan path would discard are refused —
    # both the output-shape flags and any run-shape flag changed from
    # its parser default (the guard reads the LIVE parser defaults)
    rc = cli.main(["run", "--plan", pf, "--curve"])
    assert rc == 2
    assert "drop --ensemble" in capsys.readouterr().err
    rc = cli.main(["run", "--plan", pf, "--n", "9999", "--drop", "0.5"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--n" in err and "--drop" in err
    # the guarded set is derived from the parser, so engine-specific
    # flags (swim, rumor, topology) are covered without enumeration
    rc = cli.main(["run", "--plan", pf, "--swim-subjects", "16"])
    assert rc == 2
    assert "--swim-subjects" in capsys.readouterr().err
