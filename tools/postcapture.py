#!/usr/bin/env python
"""Render every r05 hardware artifact into doc-ready markdown.

After a hardware refresh lands (artifacts/*_r05.json),
the numbers must flow into README.md's hardware table and docs/PERF.md
— during what may be a short window of human attention.  This tool
collapses that to one read: it prints, for every r05 artifact that
exists, a markdown-ready block plus the decisions the numbers imply
(e.g. the swim_diss default flip if pack won).  Read-only; prints
"missing" for artifacts not yet captured, so it also serves as a
capture-progress report.

    python tools/postcapture.py
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = "--smoke" in sys.argv[1:]     # rehearse on the .smoke artifacts


def _art_name(name):
    if SMOKE:
        stem, dot, ext = name.rpartition(".")
        name = f"{stem}.smoke.{ext}" if dot else name
    return name


def load(name):
    try:
        with open(os.path.join(REPO, "artifacts", _art_name(name))) as f:
            return json.load(f)
    except OSError:
        return None


def section(title):
    print(f"\n## {title}\n")


def main():
    any_found = False

    doc = load("hw_refresh_r05.json")
    section("Capture status (hw_refresh_r05.json)")
    if doc is None:
        print("missing — no refresh attempt has landed yet")
    else:
        any_found = True
        for r in doc:
            mark = "ok" if r.get("ok") else (
                "TIMEOUT" if r.get("timed_out") else "FAILED")
            print(f"- {r['step']}: {mark} ({r.get('wall_s')} s)"
                  + ("" if r.get("ok") else
                     f" — {r.get('error', '')[:120]}"))

    ab = load("swim_diss_ab_r05.json")
    section("SWIM dissemination A/B (swim_diss_ab_r05.json)")
    if ab is None:
        print("missing")
    else:
        any_found = True
        for r in ab.get("rows", []):
            print(f"- {r['swim_diss']}: wall {r['wall_s']:.1f} s = "
                  f"compile {r['compile_s']:.1f} + steady "
                  f"{r['steady_wall_s']:.1f} s "
                  f"({r['rounds']} rounds, cov {r['coverage']:.4f})")
        print(f"- trajectories identical: "
              f"{ab.get('trajectories_identical')}")
        print(f"- verdict: {ab.get('verdict')}")
        if ab.get("winner") == "pack":
            print("- ACTION: flip ProtocolConfig.swim_diss default to "
                  "'pack' (config.py + CLI default + docstrings; "
                  "trajectories bitwise-identical so tests stay green)")
        elif ab.get("winner"):
            print(f"- ACTION: none — '{ab['winner']}' confirmed as "
                  "default")

    sweep = None
    path = os.path.join(REPO, "artifacts",
                        _art_name("baseline_sweep_r05.jsonl"))
    if os.path.exists(path):
        with open(path) as f:
            sweep = [json.loads(x) for x in f if x.strip()]
    section("Five-config sweep (baseline_sweep_r05.jsonl)")
    if not sweep:
        print("missing")
    else:
        any_found = True
        print("README 'BASELINE configs measured on hardware' table "
              "(tools/readme_table.py rendering):\n")
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            import readme_table
            readme_table.main(path)
        finally:
            sys.path.pop(0)
        for r in sweep:
            m = r.get("meta") or {}
            if m.get("swim_diss_effective"):
                print(f"\nSWIM row ran swim_diss="
                      f"{m['swim_diss_effective']}, swim_rng="
                      f"{m.get('swim_rng')}")

    kn = load("kernel_numbers_r05.json")
    section("Kernel provenance re-measurement (kernel_numbers_r05.json)")
    if kn is None:
        print("missing")
    else:
        any_found = True
        sr = kn["single_rumor"]
        print(f"- fused single-rumor at N={sr['n']}: "
              f"{sr['ms_per_round']} ms/round "
              f"({sr['node_rounds_per_s']:.3g} node-rounds/s)")
        f2 = kn.get("mr_staged_fanout2")
        if f2:
            print(f"- staged big-MR fanout 2 at N={f2['n']}x"
                  f"{f2['rumors']}: {f2['ms_per_round']} ms/round")
        oom = kn["vmem_oom_ladder"]
        if oom.get("value_kernel_compiles"):
            print("- VMEM ladder: value kernel unexpectedly compiled "
                  "(re-check _VMEM_LIMIT_BYTES vs chip)")
        else:
            print(f"- VMEM ladder: value kernel at {oom['table_mib']} "
                  f"MiB table OOMs as designed; XLA message captured")
        tb = kn["topology_build"]
        print(f"- {tb['n']}-node power-law build: {tb['build_s']} s")
        fm = kn["fault_mask"]
        print(f"- fault masks at N={fm['n']}: off "
              f"{fm['masks_off_ms_per_round']} ms -> on "
              f"{fm['masks_on_ms_per_round']} ms/round "
              f"({fm['on_cost_pct']:+.1f}%)")

    rf = load("roofline_r05.json")
    section("Roofline (roofline_r05.json)")
    if rf is None:
        print("missing")
    else:
        any_found = True
        s = rf["single_rumor"]
        print(f"- single-rumor: {s['actual_ms_per_round']} ms/round vs "
              f"floors serial {s['floor_serial_ms']} / overlap "
              f"{s['floor_overlap_ms']} ms -> utilization "
              f"{s['utilization_vs_serial']:.0%} (serial) / "
              f"{s['utilization_vs_overlap']:.0%} (overlap)")
        fc = s["floor_components_ms"]
        print(f"  components: prng {fc['prng']} ms, gather "
              f"{fc['gather']} ms, vpu {fc['vpu']} ms")
        if not s.get("gather_floor_resolved", True):
            print("  WARNING: gather rate unresolved (differential "
                  "below noise) — the floors are lower bounds missing "
                  "the gather term; re-run before quoting utilization")
        dom = max(fc, key=fc.get)
        print(f"  dominant primitive: {dom} — the harvest target if "
              "utilization is high and actual >> floor")
        s2 = s.get("actual_ms_plane_sharing2")
        if s2 is not None:
            verdict = ("WINS — consider shipping as the bench variant"
                       if s2 < s["actual_ms_per_round"] * 0.95
                       else "no win")
            print(f"  plane_sharing=2 (half the PRNG words): {s2} "
                  f"ms/round -> {verdict}")
        m = rf["mr_staged"]
        print(f"- staged MR: {m['actual_ms_per_round']} ms/round vs HBM "
              f"floor {m['floor_ms_fused_rotation']} ms (fused rot) / "
              f"{m['floor_ms_materialized_rotation']} ms (materialized)"
              f" -> {m['utilization_vs_fused_floor']:.0%} of the fused-"
              f"rotation floor; rotation fuses: {m['rotation_fuses']}")

    ab2 = load("swim_steady_ablation_r05.json")
    section("SWIM steady decomposition (swim_steady_ablation_r05.json)")
    if ab2 is None:
        print("missing")
    else:
        any_found = True
        for r in ab2.get("rows", []):
            print(f"- {r['variant']}: {r['ms_per_round']} ms/round "
                  f"(delta vs full {r.get('delta_vs_full_ms', '?')})")

    ens = load("ensembles_r05.json")
    section("Hardware ensembles (ensembles_r05.json)")
    if ens is None:
        print("missing")
    else:
        any_found = True
        for name, sub in ens.items():
            if not isinstance(sub, dict):
                continue
            if not sub.get("ok"):
                print(f"- {name}: FAILED — {sub.get('error', '')[:120]}")
                continue
            e = (sub.get("report") or {}).get("ensemble") or {}
            print(f"- {name}: seeds {e.get('seeds')}, converged "
                  f"{e.get('converged')}, rounds p50 {e.get('rounds_p50')}"
                  f" p95 {e.get('rounds_p95')}")

    if not any_found:
        print("\n(no r05 hardware artifacts yet — run "
              "tools/hw_refresh.py on the chip)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
