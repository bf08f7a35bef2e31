#!/usr/bin/env python
"""Capture the streamed bit-plane scale record (the scale-planner PR's
acceptance artifact).

The CPU-feasible STRUCTURAL record: N = 2^20 nodes x 256 rumors
(8 word planes) planned against an artificially tiny HBM budget that
forces >= 4-tile streaming, run through the full streamed executor
(planner/stream.run_at_scale) — the THREE-STAGE PIPELINE: tile k
computes while k+1's words transfer in and k-1's result drains out —
under a MIXED fault program (crash/recover event + permanent crash +
open partition window + drop-rate ramp), with these gates:

  * ``tiles >= 4``                — the plan actually streamed;
  * ``bitwise_equal``             — the T-tile streamed trajectory is
    byte-identical to the untiled in-memory run (final state, msgs,
    AND the exact ``dropped`` total);
  * ``no_overlap_bitwise``        — the A/B leg: the same plan re-run
    with ``overlap=False`` (immediate per-tile drain, no pipeline)
    lands bitwise on the pipelined run — overlap moves WALLS, never
    bytes;
  * ``efficiency_sane``           — the pipelined run reports an
    ``overlap_efficiency`` in [0, 1] (fraction of the segment wall
    NOT spent blocked in the drain stage);
  * ``two_slice_bitwise``         — the multislice leg: the plan
    re-planned for DeviceSpec(chips=2, slices=2) EXECUTES on the
    simulated hybrid mesh (the old ``dcn_slices > 1`` refusal is
    lifted; tiles fan out round-robin across slices with zero DCN
    bytes) and is bitwise the single-slice run;
  * ``coverage == 1.0``           — on the EVENTUAL-alive set (the
    churn convergence denominator, ops/nemesis.metric_alive);
  * ``measured <= predicted``     — the tile loop's AOT memory
    analysis lands inside the planner's predicted peak device bytes
    (the budget model's honesty gate, now including the third
    fetch-out staging buffer);

plus a crash-safety leg: the run is repeated with a halt after its
first checkpoint segment and resumed, and the resumed final state must
equal the uninterrupted one bitwise (the utils/checkpoint cursor
discipline through the streamed driver).

Everything lands in ONE run ledger (utils/telemetry — provenance first
line), so the committed artifact passes tools/validate_artifacts.py's
scale/plan/budget provenance gate.

    python tools/scale_capture.py [OUT.jsonl]    # default
        artifacts/ledger_scale_r23.jsonl
    python tools/scale_capture.py --smoke        # CPU rehearsal at
        2^14 nodes, .smoke-infixed artifact (hw_refresh convention)
    python tools/scale_capture.py --full-scale   # the 100M-node leg:
        plans against the DETECTED device topology and executes, into
        its own artifact (ledger_scale_full.jsonl — the structural
        record's run="last" readers must keep seeing a scale_record);
        refuses rc 1 off-TPU (real HBM only — ROADMAP item 3's
        hardware-capture remainder, run by the hw_refresh scale_plan
        step on the chip)
    python tools/scale_capture.py --multislice   # the DETECTED-
        topology multislice executor leg: plans N = 2^20 against the
        real chip/HBM/slice topology and fans the tile stream across
        the reported DCN slices, into its own artifact
        (ledger_scale_multislice.jsonl).  Refuses rc 1 off-TPU or when
        detect_slices() < 2 — run by the hw_refresh scale_plan step
        when the structural record reports slices > 1.

Platform: ambient (the hw_refresh convention) — the committed record
on this container is the CPU structural proof; the same tool at a TPU
window measures real HBM numbers.
"""

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = 2**20
RUMORS = 256            # 8 word planes -> 4 tiles at the forced budget
FANOUT = 2
MAX_ROUNDS = 40
SEGMENT_EVERY = 10
SMOKE_N = 2**14
SMOKE_ROUNDS = 24
FULL_SCALE_N = 100_000_000


def mixed_fault(n):
    """The crashloop-style mixed program: crash/recover + permanent
    crash + open partition window + drop ramp, sized so coverage 1.0
    on the eventual-alive set is reachable inside MAX_ROUNDS."""
    from gossip_tpu.config import ChurnConfig, FaultConfig
    return FaultConfig(drop_prob=0.02, seed=2, churn=ChurnConfig(
        events=((3, 2, 8), (11, 3, -1)),
        partitions=((4, 10, n // 2),),
        ramp=(0, 6, 0.0, 0.15)))


def forced_plan(n, rounds, *, tiles_at_least=4):
    """Plan ``n`` against an HBM budget that forces >=
    ``tiles_at_least`` streamed tiles (the ONE shared construction,
    planner/budget.forced_device_for_tiles — the budget is recorded
    in the artifact; nothing about the trajectory depends on it)."""
    from gossip_tpu.planner import budget as PB
    fault = mixed_fault(n)
    dev = PB.forced_device_for_tiles(
        n, rumors=RUMORS, fanout=FANOUT, max_rounds=rounds,
        fault=fault, tiles_at_least=tiles_at_least)
    return PB.plan_scale(n, rumors=RUMORS, device=dev, fanout=FANOUT,
                         max_rounds=rounds, fault=fault,
                         segment_every=SEGMENT_EVERY)


def full_scale(led) -> int:
    """The 100M-node hardware leg: plan against the DETECTED topology
    and execute.  Gated on real TPU HBM — on any other backend this is
    a structural no-op refused rc 1 (the hw_refresh step only passes
    --full-scale on the chip)."""
    import jax
    from gossip_tpu.planner import budget as PB
    from gossip_tpu.planner.stream import run_at_scale
    if jax.default_backend() != "tpu":
        # off-TPU --full-scale is an operator error
        print(json.dumps({"error": "full-scale needs real TPU HBM",
                          "backend": jax.default_backend()}))
        return 1
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    hbm = int(stats.get("bytes_limit", 16 * 1024**3))
    from gossip_tpu.parallel.multislice import detect_slices
    dev = PB.DeviceSpec(chips=len(devs), hbm_bytes_per_chip=hbm,
                        slices=detect_slices(devs))
    plan = PB.plan_scale(FULL_SCALE_N, rumors=64, device=dev,
                         fanout=FANOUT, max_rounds=64,
                         fault=mixed_fault(FULL_SCALE_N))
    led.event("scale_full_plan", **{
        "n": plan.n, "tiles": plan.tiles,
        "bucket_words": plan.bucket_words,
        "chips": dev.chips, "hbm_bytes_per_chip": hbm,
        "slices": dev.slices,
        "predicted_peak_device_bytes":
            plan.predicted_peak_device_bytes})
    res = run_at_scale(plan, measure_memory=True)
    led.event("scale_full_run", rounds=res.rounds,
              coverage=res.coverage, tiles=res.tiles,
              measured_loop_bytes=res.measured_loop_bytes)
    print(json.dumps({"full_scale": res.to_dict()}))
    return 0 if res.coverage == 1.0 else 1


def multislice_leg(led) -> int:
    """The detected-topology multislice executor leg: plan the
    structural N against the REAL chip/HBM/slice topology and fan the
    tile stream across the reported DCN slices (per-slice segments
    merging into the one host cursor, zero cross-slice bytes).  Gated
    on a real TPU backend reporting >= 2 slices — anywhere else this
    is an operator error refused rc 1 (the hw_refresh step only passes
    --multislice when the structural record reports slices > 1)."""
    import jax
    from gossip_tpu.planner import budget as PB
    from gossip_tpu.planner.stream import run_at_scale
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "multislice leg needs real DCN "
                                   "slices",
                          "backend": jax.default_backend()}))
        return 1
    from gossip_tpu.parallel.multislice import detect_slices
    devs = jax.devices()
    slices = detect_slices(devs)
    if slices < 2:
        print(json.dumps({"error": "multislice leg needs >= 2 "
                                   "detected slices",
                          "slices": slices}))
        return 1
    stats = devs[0].memory_stats() or {}
    hbm = int(stats.get("bytes_limit", 16 * 1024**3))
    dev = PB.DeviceSpec(chips=len(devs), hbm_bytes_per_chip=hbm,
                        slices=slices)
    plan = PB.plan_scale(N, rumors=RUMORS, device=dev, fanout=FANOUT,
                         max_rounds=MAX_ROUNDS, fault=mixed_fault(N),
                         segment_every=SEGMENT_EVERY)
    res = run_at_scale(plan, check_bitwise=True, measure_memory=True)
    gates = {
        "executed_across_slices": res.dcn_slices == slices >= 2,
        "bitwise_equal": res.bitwise_equal is True,
        "coverage_1": res.coverage == 1.0,
    }
    ok = all(gates.values())
    led.event("scale_multislice_run", n=plan.n, tiles=res.tiles,
              chips=dev.chips, dcn_slices=res.dcn_slices,
              rounds=res.rounds, coverage=res.coverage,
              overlap_efficiency=res.overlap_efficiency,
              measured_loop_bytes=res.measured_loop_bytes,
              ok=ok, **gates)
    print(json.dumps({"multislice": res.to_dict(), "ok": ok,
                      "gates": gates}))
    return 0 if ok else 1


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    full = "--full-scale" in argv
    multislice = "--multislice" in argv
    argv = [a for a in argv
            if a not in ("--smoke", "--full-scale", "--multislice")]
    infix = ".smoke" if smoke else ""
    # the full-scale and multislice legs get their OWN artifacts:
    # appending a run with no scale_record event to the structural
    # record would break its run="last" readers
    # (bench.last_scale_record, the tier-1 pin)
    if full:
        default_name = f"ledger_scale_full{infix}.jsonl"
    elif multislice:
        default_name = f"ledger_scale_multislice{infix}.jsonl"
    else:
        default_name = f"ledger_scale_r23{infix}.jsonl"
    out_path = (argv[0] if argv else
                os.path.join(REPO, "artifacts", default_name))
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not (full or multislice):
        # the structural record's two-slice leg needs >= 2 devices on
        # the default backend; off-TPU that means forcing the host
        # platform's device count BEFORE the first jax import (the
        # flag only touches the cpu platform, so it is inert at a real
        # TPU window)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
    n = SMOKE_N if smoke else N
    rounds = SMOKE_ROUNDS if smoke else MAX_ROUNDS

    import numpy as np

    import jax
    from gossip_tpu.parallel.multislice import detect_slices
    from gossip_tpu.planner import budget as PB
    from gossip_tpu.planner.stream import run_at_scale
    from gossip_tpu.utils import telemetry

    led = telemetry.Ledger(out_path)
    prev = telemetry.activate(led)
    try:
        led.record_runtime()
        if full:
            return full_scale(led)
        if multislice:
            return multislice_leg(led)
        plan = forced_plan(n, rounds)
        t0 = time.perf_counter()
        res = run_at_scale(plan, check_bitwise=True,
                           measure_memory=True, keep_state=True)
        streamed_ms = (time.perf_counter() - t0) * 1e3

        # A/B leg: the same plan with the pipeline OFF — every tile
        # drained the moment it is dispatched.  Overlap moves walls,
        # never bytes, so this must land bitwise on the pipelined run.
        t0 = time.perf_counter()
        r_ser = run_at_scale(plan, overlap=False, keep_state=True)
        serial_ms = (time.perf_counter() - t0) * 1e3
        no_overlap_bitwise = (
            np.array_equal(r_ser.final_state, res.final_state)
            and r_ser.msgs == res.msgs
            and r_ser.dropped == res.dropped)

        # multislice leg: re-plan the SAME trajectory for a simulated
        # 2-slice hybrid topology (chips=2, slices=2 — per_slice=1, so
        # each mesh row is one pinned device) and execute across it.
        # Tiles fan out round-robin with zero cross-slice bytes; the
        # slice count must be invisible to the result.
        dev2 = PB.DeviceSpec(
            chips=2, slices=2,
            hbm_bytes_per_chip=plan.device.hbm_bytes_per_chip,
            host_ram_bytes=plan.device.host_ram_bytes)
        plan2 = PB.plan_scale(plan.n, rumors=plan.rumors, device=dev2,
                              fanout=plan.fanout,
                              max_rounds=plan.max_rounds,
                              fault=plan.fault,
                              segment_every=plan.segment_every)
        r_2s = run_at_scale(plan2, keep_state=True)
        two_slice_bitwise = (
            plan2.mesh_kind == "hybrid" and r_2s.dcn_slices == 2
            and np.array_equal(r_2s.final_state, res.final_state)
            and r_2s.msgs == res.msgs
            and r_2s.dropped == res.dropped)

        # crash-safety leg: halt after the first published segment,
        # resume, and land bitwise on the uninterrupted run
        with tempfile.TemporaryDirectory() as td:
            ck = os.path.join(td, "scale_ck.npz")
            run_at_scale(plan, checkpoint_path=ck,
                         halt_after_segments=1)
            r2 = run_at_scale(plan, checkpoint_path=ck, resume=True,
                              keep_state=True)
        resume_bitwise = (np.array_equal(r2.final_state,
                                         res.final_state)
                          and r2.dropped == res.dropped
                          and r2.msgs == res.msgs)

        eff = res.overlap_efficiency
        gates = {
            "tiles_ge_4": res.tiles >= 4,
            "bitwise_equal": res.bitwise_equal is True,
            "no_overlap_bitwise": no_overlap_bitwise,
            "efficiency_sane": (eff is not None
                                and 0.0 <= eff <= 1.0),
            "two_slice_bitwise": two_slice_bitwise,
            "coverage_1": res.coverage == 1.0,
            "memory_within_prediction":
                res.measured_loop_bytes is not None
                and res.measured_loop_bytes
                <= res.predicted_peak_device_bytes,
            "resume_bitwise": resume_bitwise,
        }
        ok = all(gates.values())
        led.event("scale_record",
                  n=n, rumors=RUMORS, fanout=FANOUT, rounds=res.rounds,
                  tiles=res.tiles, bucket_words=res.bucket_words,
                  total_words=plan.total_words,
                  segments=res.segments_run,
                  backend=jax.default_backend(), smoke=smoke,
                  hbm_budget_bytes=plan.hbm_budget_bytes,
                  predicted_peak_device_bytes=
                  res.predicted_peak_device_bytes,
                  measured_loop_bytes=res.measured_loop_bytes,
                  coverage=res.coverage, msgs=res.msgs,
                  dropped=res.dropped,
                  streamed_wall_ms=round(streamed_ms, 1),
                  serial_wall_ms=round(serial_ms, 1),
                  overlap_efficiency=eff,
                  two_slice_tiles=r_2s.tiles,
                  two_slice_dcn_slices=r_2s.dcn_slices,
                  binding=plan.binding, ok=ok, **gates)
        print(json.dumps({"n": n, "tiles": res.tiles,
                          "coverage": res.coverage,
                          "measured_loop_bytes": res.measured_loop_bytes,
                          "predicted_peak_device_bytes":
                          res.predicted_peak_device_bytes,
                          "overlap_efficiency": eff,
                          "backend": jax.default_backend(),
                          "slices": detect_slices(),
                          "ok": ok, "gates": gates,
                          "ledger": out_path}))
        return 0 if ok else 1
    finally:
        telemetry.activate(prev)
        led.close()


if __name__ == "__main__":
    sys.exit(main())
