#!/usr/bin/env python
"""One-shot hardware refresh: every measurement the rounds owe the chip.

Run on a machine with the chip.  Every step is its own subprocess (a
chip belongs to one process, so this parent never touches jax).  The
outer timeout must cover the sum of ALL per-step subprocess timeouts at
their worst; ``worst_case_budget_s()`` below computes it from the same
constants the steps use:

    python tools/hw_refresh.py                    # every pending step
    python tools/hw_refresh.py --smoke            # CPU-scale rehearsal

``--smoke`` runs the SAME pipeline at CPU scale (CPU platform, 8
virtual devices, interpreter-mode kernels, sweep --scale 0.002; the
bench step needs a chip and is skipped) writing ``.smoke``-infixed
artifacts — a rehearsal of every subprocess, timeout, merge, and
artifact path, so chip time is never burned by a plumbing bug.

Steps (each prints a tagged JSON line; failures don't stop later steps;
ordered by VERDICT r4 priority so a short window lands the most
important captures first):
  1. SWIM dissemination A/B (sort vs pack) on the BASELINE-1M shape
     -> artifacts/swim_diss_ab_r05.json  (VERDICT r4 task 1a)
  2. bench.py headline
  3. PERF.md interactive-provenance kernel numbers re-measured
     -> artifacts/kernel_numbers_r05.json  (task 1b)
  4. staged big-table MR kernel validation at 10M x 32 rumors
     (post-padding variant) + per-round timing
  5. hardware-PRNG digest of the plane-sharded fused round
  5b. fused churn sweep: K mixed fault scenarios through ONE fused
     executable, solo-recompile vs warm ratio on real Mosaic kernels
     -> artifacts/ledger_fused_sweep_r17.jsonl (fused-operand PR)
  5c. scale planner: the streamed bit-plane tiling record (N = 2^20
     forced to >= 4 tiles, bitwise + coverage + memory-prediction
     gates), and on a real TPU backend the 100M-node --full-scale leg
     planned against the DETECTED chip/HBM/slice topology
     -> artifacts/ledger_scale_r20.jsonl (scale-planner PR)
  6. roofline: utilization vs first-principles floors, both fused
     layouts -> artifacts/roofline_r05.json  (task 3)
  7. the five BASELINE configs at full scale, SWIM row under the
     arbitrated A/B winner -> artifacts/baseline_sweep_r05.jsonl
  8. SWIM steady-state ms/round decomposition by component stubbing
     -> artifacts/swim_steady_ablation_r05.json  (task 4)
  9. ensemble surface on hardware via the public CLI
     -> artifacts/ensembles_r05.json  (task 6)
 10. TPU-only pallas statistics tests
     -> artifacts/tpu_pallas_tests_r05.txt

All step lines are also collected into artifacts/hw_refresh_r05.json.
Afterwards update README.md's hardware table (tools/readme_table.py)
and docs/PERF.md's pending numbers from the recorded lines.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MR_TIMEOUT_S = 1200
PRNG_TIMEOUT_S = 900
FUSED_SWEEP_TIMEOUT_S = 1200
SWEEP_TIMEOUT_S = 2400
TESTS_TIMEOUT_S = 2400
BENCH_TIMEOUT_S = 1800


def swim_ab_budget_s():
    """swim_diss_ab.py's self-computed worst case plus slack — derived
    from the child's own constants so this budget can't drift below
    what the child needs to run its own group-kill (killing it early
    would orphan a live TPU client that holds the chip)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import swim_diss_ab
    finally:
        sys.path.pop(0)
    return swim_diss_ab.worst_case_budget_s() + 120

# --smoke: the full pipeline at CPU scale — a REHEARSAL of every
# subprocess/plumbing/artifact path, so chip time is never burned by a
# plumbing bug (round 2's capture failed exactly that way).  Smoke artifacts carry a .smoke
# infix and never touch the real r05 names.
SMOKE = False


def _art(name):
    if SMOKE:
        stem, dot, ext = name.rpartition(".")
        name = f"{stem}.smoke.{ext}" if dot else name + ".smoke"
    return os.path.join(REPO, "artifacts", name)


def summary_path():
    return _art("hw_refresh_r05.json")


_LEDGER = None


def _ledger():
    """The refresh run's flight recorder (utils/telemetry), opened
    lazily AFTER --smoke has been parsed (the path is smoke-infixed).
    Step subprocesses inherit the same file via GOSSIP_TELEMETRY
    (_body_env), so a window that closes mid-step still leaves one
    mechanically readable timeline: provenance, per-step spans (start
    fsynced before the subprocess launches), step verdict events, and
    whatever the children recorded before the kill."""
    global _LEDGER
    if _LEDGER is None:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            from _telemetry import open_ledger
        finally:
            sys.path.pop(0)
        _LEDGER = open_ledger(_art("ledger_hw_refresh.jsonl"))
    return _LEDGER


def worst_case_budget_s():
    """Sum of every per-step subprocess timeout, so the recommended outer
    ``timeout`` can't silently drift below what a fully timed-out run
    needs."""
    return (swim_ab_budget_s() + KERNEL_NUMBERS_TIMEOUT_S + MR_TIMEOUT_S
            + PRNG_TIMEOUT_S + FUSED_SWEEP_TIMEOUT_S
            + SCALE_TIMEOUT_S + FULL_SCALE_TIMEOUT_S + COST_TIMEOUT_S
            + FLEET_TIMEOUT_S + ROOFLINE_TIMEOUT_S + SWEEP_TIMEOUT_S
            + SWIM_ABLATION_TIMEOUT_S + ENSEMBLES_TIMEOUT_S
            + BENCH_TIMEOUT_S + TESTS_TIMEOUT_S)


def load_summary():
    """Prior runs' step lines, keyed by step name — a retry must MERGE
    with these, never clobber a green result captured in an earlier
    healthy window."""
    try:
        with open(summary_path()) as f:
            return {r["step"]: r for r in json.load(f)}
    except (OSError, ValueError, KeyError, TypeError):
        return {}


_SUMMARY = load_summary()


def step(tag, fn):
    """Run one step; record its line in the merged summary.  Returns
    ``True`` (green), ``False`` (failed), or ``"timeout"`` — the
    subprocess-overran-its-budget case: the caller stops instead of
    spending the remaining steps' budgets."""
    led = _ledger()     # lazy init (file open + git rev-parse) must not
    t0 = time.time()    # bill its cost to the first step's wall_s
    try:
        with led.span(tag, step=tag):
            out = fn()
        line = {"step": tag, "ok": True,
                "wall_s": round(time.time() - t0, 1), "result": out}
    except subprocess.TimeoutExpired as e:
        line = {"step": tag, "ok": False, "timed_out": True,
                "wall_s": round(time.time() - t0, 1),
                "error": f"TimeoutExpired: {e}"[:500]}
    except Exception as e:  # keep going; later steps still run
        line = {"step": tag, "ok": False,
                "wall_s": round(time.time() - t0, 1),
                "error": f"{type(e).__name__}: {e}"[:500]}
    print(json.dumps(line), flush=True)
    led.event("step", **line)
    # persist after EVERY step so an outer-timeout kill still leaves the
    # completed steps on disk as a committable artifact; a failed write
    # must not abort the remaining steps (stdout still carries the line)
    _SUMMARY[tag] = line
    try:
        with open(summary_path(), "w") as f:
            json.dump(list(_SUMMARY.values()), f, indent=1)
    except OSError as e:
        print(f"hw_refresh: summary write failed: {e}", file=sys.stderr)
    if line.get("timed_out"):
        return "timeout"
    return line["ok"]


def _mr_staged_body():
    """Runs in a SUBPROCESS: a chip belongs to one process, so the
    parent must never hold it while later steps spawn their own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gossip_tpu.ops.pallas_round import (fused_multirumor_pull_round,
                                             init_multirumor_state)
    # smoke: tiny n on the CPU interpreter (stubbed PRNG — plumbing
    # rehearsal, not statistics; all_rumors_growing is reported, not
    # asserted, and is expected False under the degenerate stub)
    n = 128 * 8 if SMOKE else 10_000_000
    rounds = 4 if SMOKE else 20
    st = init_multirumor_state(n, 32)
    jax.block_until_ready(st.table)
    t0 = time.perf_counter()
    out = fused_multirumor_pull_round(st.table, jnp.int32(0), jnp.int32(1),
                                      n, 1, interpret=SMOKE)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in range(2, rounds + 2):
        out = fused_multirumor_pull_round(out, jnp.int32(0), jnp.int32(r),
                                          n, 1, interpret=SMOKE)
    jax.block_until_ready(out)
    per_round_ms = (time.perf_counter() - t0) / rounds * 1e3
    flat = np.asarray(out).reshape(-1)[:n]
    counts = [int(((flat >> k) & np.uint32(1)).sum()) for k in range(32)]
    print(json.dumps({"compile_s": round(compile_s, 2),
                      "per_round_ms": round(per_round_ms, 3),
                      "rounds_run": rounds + 1,
                      f"mean_count_after_{rounds + 1}": sum(counts) / 32,
                      "all_rumors_growing": all(c > 64 for c in counts),
                      "smoke": SMOKE}))
    return 0


def _prng_body():
    """Subprocess: hardware-PRNG digest of the plane-sharded fused round
    (sharded_fused.assert_prng_invariant).  On one chip the all-equal
    assertion is trivial (one device) but the digest
    itself is the real hardware PRNG artifact; a multi-chip pod runs
    the same step and checks the zero-ICI same-stream invariant for
    real."""
    import jax
    import numpy as np

    from gossip_tpu.parallel.sharded_fused import (assert_prng_invariant,
                                                   make_plane_mesh)
    n_dev = len(jax.devices())
    mesh = make_plane_mesh(n_dev)
    d = assert_prng_invariant(128 * 8 if SMOKE else 128 * 64, mesh,
                              interpret=SMOKE)
    print(json.dumps({"devices": n_dev,
                      "digests": np.asarray(d).tolist(),
                      "smoke": SMOKE}))
    return 0


def _body_env():
    """Env for the step subprocesses: the repo on PYTHONPATH for
    run-by-path imports; real runs keep the ambient TPU platform, the
    smoke rehearsal pins the CPU with an 8-device virtual mesh and the
    compile cache off."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if not SMOKE:
        return _share_ledger(env)
    env.update(JAX_PLATFORMS="cpu", GOSSIP_COMPILE_CACHE="",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    # conftest honors this var over JAX_PLATFORMS — an operator who has
    # it exported for hardware runs must not leak it into the rehearsal
    env.pop("GOSSIP_TPU_TEST_PLATFORM", None)
    return _share_ledger(env)


def _share_ledger(env):
    """Children append to the refresh ledger (one timeline per window;
    their own provenance lines carry distinct run ids)."""
    path = _ledger().path
    if path:
        env.setdefault("GOSSIP_TELEMETRY", path)
    return env


def _smoke_argv():
    return ["--smoke"] if SMOKE else []


def swim_diss_ab():
    """Arbitrate the SWIM dissemination lowerings (sort control vs pack
    candidate) on the chip — VERDICT r4 task 1a.  Delegates to
    tools/swim_diss_ab.py (per-impl fresh compile cache, group-kill on
    timeout)."""
    p = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "swim_diss_ab.py"),
                        *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=swim_ab_budget_s(), cwd=REPO,
                       env=_body_env())
    if p.returncode != 0:
        raise RuntimeError(f"rc {p.returncode}\n"
                           + (p.stderr or p.stdout)[-400:])
    with open(_art("swim_diss_ab_r05.json")) as f:
        doc = json.load(f)
    return {"verdict": doc.get("verdict"),
            "trajectories_identical": doc.get("trajectories_identical"),
            "rows": [{k: r.get(k) for k in ("swim_diss", "wall_s",
                                            "compile_s", "steady_wall_s")}
                     for r in doc.get("rows", [])]}


def swim_diss_winner():
    """The arbitrated dissemination lowering from this round's committed
    A/B artifact (its explicit ``winner`` field — ONE definition, owned
    by swim_diss_ab.py), or None (CLI default) when no clean verdict
    exists — the sweep recapture below passes it through so the SWIM
    row is re-measured under the winner in the SAME window (VERDICT r4
    1a)."""
    try:
        with open(_art("swim_diss_ab_r05.json")) as f:
            doc = json.load(f)
        if not doc.get("trajectories_identical"):
            return None
        return doc.get("winner")
    except (OSError, ValueError):
        return None


STATICCHECK_TIMEOUT_S = 120    # pure-stdlib AST passes: seconds, no jax
KERNEL_NUMBERS_TIMEOUT_S = 1500
ROOFLINE_TIMEOUT_S = 1200
ENSEMBLES_TIMEOUT_S = 2700     # covers both sub-captures' own budgets
SWIM_ABLATION_TIMEOUT_S = 1800  # ~6 variants x ~130 s compile + timing


def _run_tool(script: str, timeout_s: int):
    """Run a capture tool (tools/<script>) and return ITS last stdout
    JSON line — the tool owns its artifact, smoke infixing, and summary
    keys (one definition, one file; hw_refresh never re-derives them)."""
    p = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", script),
                        *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=timeout_s, cwd=REPO, env=_body_env())
    if p.returncode != 0:
        raise RuntimeError(f"rc {p.returncode}\n"
                           + (p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def kernel_numbers():
    """Re-measure docs/PERF.md's interactive-provenance kernel numbers
    (VERDICT r4 task 1b) — single-rumor ms/round, VMEM OOM ladder,
    topology build, fault-mask on-cost."""
    return _run_tool("kernel_numbers.py", KERNEL_NUMBERS_TIMEOUT_S)


def roofline():
    """Utilization vs first-principles floors for both fused layouts
    (VERDICT r4 task 3)."""
    return _run_tool("roofline.py", ROOFLINE_TIMEOUT_S)


def fused_churn_sweep():
    """K mixed nemesis scenarios — events, partition windows, drop
    ramps — through the plane-sharded fused engine ON THE CHIP: solo
    (per-scenario Mosaic kernel recompile, the pre-operand cost model)
    vs warm (one executable, schedule content as runtime operands) —
    tools/fused_sweep_capture.py.  This is the fused family's first
    real-hardware fault-scenario measurement; the committed r17 record
    is the CPU reference-lowering structure proof, and this leg
    refreshes the stale r06 CPU-fallback headline with Mosaic
    numbers."""
    return _run_tool("fused_sweep_capture.py", FUSED_SWEEP_TIMEOUT_S)


def staticcheck():
    """The AST invariant analyzer over the tree this capture runs from
    (tools/staticcheck.py): recompile-hazard lint, rpc lock
    discipline, convention gates — pure stdlib, CPU-only, seconds.
    Runs FIRST so a capture window never spends its budget measuring a
    tree whose serving invariants already regressed (no jax import)."""
    return _run_tool("staticcheck.py", STATICCHECK_TIMEOUT_S)


def _scale_leg(flag, timeout_s):
    """One gated scale_capture re-run (--full-scale / --multislice)
    into its own artifact, returning the leg's last stdout JSON line;
    a non-zero rc is the leg's own gate failing."""
    p = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools",
                                     "scale_capture.py"),
                        flag, *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=timeout_s, cwd=REPO, env=_body_env())
    if p.returncode != 0:
        raise RuntimeError(f"scale_capture {flag} rc {p.returncode}\n"
                           + (p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def scale_plan():
    """The scale planner's streamed-tiling record on this host
    (tools/scale_capture.py): N = 2^20 forced to >= 4 streamed word-
    plane tiles through the three-stage pipeline, bitwise-vs-untiled +
    no-overlap-A/B + simulated-2-slice + coverage-1.0 +
    memory-prediction gates — the structural proof refreshed at the
    capture window.  On a real TPU backend the tool is then re-run
    with ``--full-scale`` (the 100M-node leg against the DETECTED
    chip/HBM/slice topology — gated on real HBM only, which is why the
    committed record stays the CPU structural proof until a window
    lands, ROADMAP item 3), and when the structural record reports
    more than one DCN slice, with ``--multislice`` too: the executor
    leg that fans the tile stream across the REAL slices."""
    line = _run_tool("scale_capture.py", SCALE_TIMEOUT_S)
    if line.get("backend") == "tpu":
        line["full_scale"] = _scale_leg("--full-scale",
                                        FULL_SCALE_TIMEOUT_S)
        if line.get("slices", 1) > 1:
            line["multislice"] = _scale_leg("--multislice",
                                            FULL_SCALE_TIMEOUT_S)
    return line


def cost_attribution():
    """The XLA cost & memory attribution record on this host
    (tools/cost_capture.py, docs/OBSERVABILITY.md "XLA cost & memory
    attribution"): one forced-miss compile per engine through the ONE
    chokepoint, every ``xla_compile`` event labeled + verdict-carrying
    with cost/memory fields populated-or-null, the cross-closure warm
    re-entry coming back a store HIT, and the packed budget
    cross-check green (measured peak bytes <= the planner's closed
    form at a forced >=4-tile plan).  On a TPU window the same tool
    attributes real HBM executables — the cost table the capacity
    plans cite then names hardware numbers, not the CPU structural
    proof."""
    return _run_tool("cost_capture.py", COST_TIMEOUT_S)


def byzantine_conv():
    """The byzantine-adversary convergence record on this host
    (tools/byzantine_capture.py, docs/ROBUSTNESS.md "Byzantine
    adversaries"): the mixed fail-stop + scripted-liar scenario with
    the defended arm converging EXACTLY on the honest eventual-alive
    set (integer count == denominator) while the undefended control
    arm provably diverges, plus bitwise 1-vs-4-device mesh parity.
    Integer arithmetic on honest-owned components, not a chip rate —
    but re-proven on whatever host the hardware captures run on."""
    return _run_tool("byzantine_capture.py", BYZ_TIMEOUT_S)


def fleet_failover():
    """The replicated serving fleet's crashloop on this host
    (tools/fleet_crashloop.py): the load mix through the fronting
    router, seeded mid-load replica SIGKILLs, zero acked-request loss
    + bitwise failover parity + recovery gates, refreshing the
    committed fleet record.  Replica children pin JAX_PLATFORMS=cpu by
    design — N replica processes cannot share one TPU, and the fleet
    contract is a bitwise-trajectory structure, not a chip rate — so
    this step certifies the serving layer survives its nemesis on the
    same host the hardware captures run on."""
    return _run_tool("fleet_crashloop.py", FLEET_TIMEOUT_S)


def request_trace():
    """The request-tracing record on this host
    (tools/trace_capture.py, docs/OBSERVABILITY.md "Request tracing &
    live metrics"): the traced load mix through the router, one seeded
    mid-load SIGKILL, every acked request joining to a COMPLETE
    waterfall (failover-replayed included), fleet-status seeing the
    kill and the recovery, and the post-recovery steady window gated
    zero-compile + zero-fsync via the Metrics counters.  The summary
    line is re-joined here to refresh the ATTRIBUTED slow-request
    exemplars (wall + dominant leg) alongside the committed ledger."""
    out = _run_tool("trace_capture.py", TRACE_TIMEOUT_S)
    import trace_report
    rows = trace_report.waterfalls(
        trace_report.load_events([out["ledger"]]))
    out["exemplars"] = trace_report.exemplars(rows, k=3)
    return out


def mesh_serving():
    """The mesh-sharded serving capture on this host
    (tools/load_harness.py --mesh-devices, docs/SERVING.md
    "Mesh-sharded replicas"): fixed-concurrency legs per
    devices-per-replica width, gated on bitwise reply parity and
    steady-all-warm.  On hosts with enough schedulable cores the
    >= --mesh-min-ratio device-scaling gate arms itself
    (``scaling_resolved`` in the gate event) — THIS step is where the
    committed meshserve record's scaling leg gets its real
    multi-core/multi-chip recapture; on a serial host the capture
    still certifies parity + warmth and ledgers the scaling leg as
    unresolved."""
    p = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "load_harness.py"),
                        "--mesh-devices", "1,4",
                        "--out", _art("ledger_meshserve_r21.jsonl"),
                        *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=MESH_SERVING_TIMEOUT_S, cwd=REPO,
                       env=_body_env())
    if p.returncode != 0:
        raise RuntimeError(f"rc {p.returncode}\n"
                           + (p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def ensembles():
    """The round-4 ensemble surface on hardware via the public CLI
    (VERDICT r4 task 6).  The tool merges sub-captures incrementally;
    a failed sub-capture keeps this step pending for a retry."""
    return _run_tool("ensemble_capture.py", ENSEMBLES_TIMEOUT_S)


def swim_steady_ablation():
    """Steady-state ms/round decomposition of the BASELINE SWIM shape
    (VERDICT r4 task 4: name the residual 374 ms/round's owner or the
    floor).  Merges variant rows across retries."""
    return _run_tool("swim_steady_ablation.py", SWIM_ABLATION_TIMEOUT_S)


def prng_invariant():
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--prng-body", *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=PRNG_TIMEOUT_S, cwd=REPO, env=_body_env())
    if p.returncode != 0:
        raise RuntimeError((p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def mr_staged_10m():
    # run-by-path puts tools/ (not the repo root) on the child's
    # sys.path; gossip_tpu needs an explicit PYTHONPATH entry
    # (_body_env provides it both modes)
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--mr-body", *_smoke_argv()],
                       capture_output=True, text=True,
                       timeout=MR_TIMEOUT_S, cwd=REPO, env=_body_env())
    if p.returncode != 0:
        raise RuntimeError((p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def _write_sweep_artifact(stdout):
    """Persist whatever config lines the sweep produced — a crash or
    timeout on config 5 must not discard 4 completed full-scale
    hardware measurements from a scarce healthy window.  MERGES with an
    existing artifact by config name (new rows win) so a retry that got
    less far can never clobber rows a fuller earlier attempt captured."""
    art = _art("baseline_sweep_r05.jsonl")
    if isinstance(stdout, bytes):
        stdout = stdout.decode(errors="replace")
    stdout = stdout or ""

    def rows_by_config(text):
        rows = {}
        for line in text.splitlines():
            try:
                r = json.loads(line)
                rows[r["config"]] = line
            except (ValueError, KeyError, TypeError):
                continue
        return rows

    new = rows_by_config(stdout)
    if new:
        merged = {}
        try:
            with open(art) as f:
                merged = rows_by_config(f.read())
        except OSError:
            pass
        merged.update(new)
        with open(art, "w") as f:
            f.write("\n".join(merged.values()) + "\n")
    return stdout


def baseline_sweep():
    try:
        # -u: the per-config JSONL lines must not die in the child's
        # block buffer when a timeout SIGKILLs it mid-sweep
        scale = "0.002" if SMOKE else "1.0"
        extra = ["--devices", "4"] if SMOKE else []
        # --no-compile-cache: the captured compile_s IS the canonical
        # cold number; the (default-on) persistent cache would silently
        # substitute a ~3 s warm compile on any host that ever built
        # these shapes before
        winner = swim_diss_winner()
        if winner:
            extra += ["--swim-diss", winner]
        elif not os.path.exists(_art("swim_diss_ab_r05.json")):
            # the SWIM row's whole point this round is re-measurement
            # under the ARBITRATED lowering (VERDICT r4 1a).  If the A/B
            # hasn't produced an artifact yet (step pending/transient),
            # a sweep run now would go green under the CLI default and
            # never be re-captured on retry (pending_steps skips green
            # steps) — so stay pending until the A/B lands.  A written
            # artifact with no winner (trajectory mismatch) is a real
            # verdict, and so is a recorded DETERMINISTIC A/B failure
            # (e.g. the candidate lowering crashing on the chip — rc 1,
            # no artifact): both proceed under the default rather than
            # blocking the five-config capture forever.
            ab = load_summary().get("swim_diss_ab", {})
            deterministic_ab_failure = (
                ab and not ab.get("ok") and not ab.get("timed_out"))
            if not deterministic_ab_failure:
                raise RuntimeError(
                    "blocked: swim_diss_ab has no artifact yet; the "
                    "SWIM row must be captured under the arbitrated "
                    "lowering")
        p = subprocess.run([sys.executable, "-u", "-m", "gossip_tpu",
                            "sweep", "--scale", scale,
                            "--no-compile-cache", *extra],
                           capture_output=True, text=True,
                           timeout=SWEEP_TIMEOUT_S, cwd=REPO,
                           env=_body_env())
    except subprocess.TimeoutExpired as e:
        _write_sweep_artifact(e.stdout)
        raise
    out = _write_sweep_artifact(p.stdout)
    if p.returncode != 0:
        raise RuntimeError(p.stderr[-400:])
    rows = [json.loads(line) for line in out.splitlines() if line.strip()]
    return [{"config": r["config"], "rounds": r["rounds"],
             "coverage": round(r["coverage"], 4), "wall_s": r["wall_s"],
             "compile_s": r.get("meta", {}).get("compile_s"),
             "steady_wall_s": r.get("meta", {}).get("steady_wall_s"),
             "engine": r.get("meta", {}).get("engine")}
            for r in rows]


def bench():
    """bench.py's headline line.  The bench has no CPU path, so the
    smoke rehearsal records the step as skipped."""
    if SMOKE:
        return {"skipped": "bench.py needs a TPU"}
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True,
                       timeout=BENCH_TIMEOUT_S, cwd=REPO,
                       env=_share_ledger(dict(os.environ)))
    if p.returncode != 0:
        raise RuntimeError((p.stderr or p.stdout)[-400:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def tpu_pallas_tests():
    art = _art("tpu_pallas_tests_r05.txt")
    # conftest pins tests to CPU unless this var points at the chip;
    # smoke keeps CPU (the TPU-only classes skip — the rehearsal proves
    # the pytest/artifact plumbing, the chip proves the statistics)
    env = (_body_env() if SMOKE
           else {**os.environ, "GOSSIP_TPU_TEST_PLATFORM": "tpu"})

    def _text(x):
        return ("" if x is None else
                x if isinstance(x, str) else x.decode(errors="replace"))

    try:
        # -u for the same reason as the sweep: per-test progress must
        # survive a timeout SIGKILL for the partial artifact to exist
        p = subprocess.run([sys.executable, "-u", "-m", "pytest",
                            "tests/test_pallas.py",
                            "tests/test_pallas_round.py", "-q"],
                           capture_output=True, text=True,
                           timeout=TESTS_TIMEOUT_S, cwd=REPO, env=env)
    except subprocess.TimeoutExpired as e:
        with open(art, "w") as f:
            f.write(_text(e.stdout) + "\n--- TIMED OUT after "
                    f"{TESTS_TIMEOUT_S} s ---\n--- stderr ---\n"
                    + _text(e.stderr)[-2000:])
        raise
    with open(art, "w") as f:
        f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-2000:])
    tail = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    if p.returncode != 0:
        raise RuntimeError(tail)
    return tail


# Priority order = VERDICT r4 task 1: the A/B arbitration first (it
# unblocks the SWIM default flip and the sweep recapture), then the
# scoreboard headline, then the cheap kernel validations, then the
# five-config sweep (which picks up the A/B winner), then the test tier.
# A run cut short lands the most important steps first; retries are
# incremental (pending steps only).
FLEET_TIMEOUT_S = 1200
TRACE_TIMEOUT_S = 1200          # traced crashloop + steady window
MESH_SERVING_TIMEOUT_S = 1200   # thousands of connections x 2 legs
SCALE_TIMEOUT_S = 1200          # structural record: ~2 min on CPU
FULL_SCALE_TIMEOUT_S = 3600     # the 100M leg owns a real window slot
COST_TIMEOUT_S = 900            # 7 tiny compiles + one forced-tile run
BYZ_TIMEOUT_S = 900             # 2 payload classes x 2 arms + parity

STEPS = [("staticcheck", staticcheck),
         ("swim_diss_ab", swim_diss_ab),
         ("bench", bench),
         ("kernel_numbers", kernel_numbers),
         ("mr_staged_10m", mr_staged_10m),
         ("prng_invariant", prng_invariant),
         ("fused_churn_sweep", fused_churn_sweep),
         ("byzantine_conv", byzantine_conv),
         ("scale_plan", scale_plan),
         ("cost_attribution", cost_attribution),
         ("fleet_failover", fleet_failover),
         ("request_trace", request_trace),
         ("mesh_serving", mesh_serving),
         ("roofline", roofline),
         ("baseline_sweep", baseline_sweep),
         ("swim_steady_ablation", swim_steady_ablation),
         ("ensembles", ensembles),
         ("tpu_pallas_tests", tpu_pallas_tests)]


def pending_steps():
    """Step names without a green line in the merged summary — what a
    retry should run instead of re-burning already-captured steps."""
    done = load_summary()
    return [t for t, _ in STEPS if not done.get(t, {}).get("ok")]


def main(only=None):
    """Exit code reports overall outcome: 0 = every requested step ok,
    1 = partial (some landed), 2 = nothing succeeded.  ``only`` (or
    --steps a,b on the CLI) restricts to the named steps; a step
    TIMEOUT aborts the rest rather than spending their budgets."""
    if only is not None and not list(only):
        print(json.dumps({"nothing_pending": True}), flush=True)
        return 0
    _ledger().event("refresh_start", smoke=SMOKE,
                    steps=[t for t, _ in STEPS
                           if only is None or t in only])
    results = []
    for tag, fn in STEPS:
        if only is not None and tag not in only:
            continue
        r = step(tag, fn)
        results.append(r)
        if r == "timeout":
            print(json.dumps({"aborted_after": tag,
                              "reason": "step timeout; not spending the "
                                        "remaining budgets"}),
                  flush=True)
            _ledger().event("refresh_abort", after=tag,
                            reason="step timeout")
            break
    oks = [r is True for r in results]
    return 0 if oks and all(oks) else (1 if any(oks) else 2)


if __name__ == "__main__":
    # Hand-rolled args (argparse would fight the --steps comma contract
    # callers already depend on), so REJECT anything unrecognized: a
    # typo'd or guessed flag (--help, --dry-run, ...) must print usage,
    # not silently launch a full hardware-refresh attempt.
    _known = {"--smoke", "--mr-body", "--prng-body", "--steps"}
    _args = sys.argv[1:]
    _bad = [a for i, a in enumerate(_args)
            if a not in _known and not (i > 0 and _args[i - 1] == "--steps")]
    if _bad:
        print(f"unrecognized args: {_bad}\n"
              "usage: hw_refresh.py [--smoke] [--steps a,b,...] "
              "[--mr-body|--prng-body]\n"
              "NO ARGS runs every pending hardware step",
              file=sys.stderr)
        sys.exit(2)
    if "--smoke" in sys.argv:
        SMOKE = True
        _SUMMARY = load_summary()   # re-key to the smoke summary path
    if "--mr-body" in sys.argv:
        sys.exit(_mr_staged_body())
    if "--prng-body" in sys.argv:
        sys.exit(_prng_body())
    only = None
    if "--steps" in sys.argv:
        idx = sys.argv.index("--steps") + 1
        if idx >= len(sys.argv):
            print("--steps needs a comma-separated value, e.g. "
                  "--steps bench,tpu_pallas_tests", file=sys.stderr)
            sys.exit(2)
        names = sys.argv[idx].split(",")
        known = {t for t, _ in STEPS}
        bad = [n for n in names if n and n not in known]
        if bad:
            print(f"unknown steps: {bad}; known: {sorted(known)}",
                  file=sys.stderr)
            sys.exit(2)
        only = [n for n in names if n]
    sys.exit(main(only))
