#!/usr/bin/env python
"""Run the round-4 ensemble surface on hardware (VERDICT r4 task 6).

The seed-axis ensembles (SWIM detection-latency distribution, SI
rounds-to-target quantiles) shipped in round 4 CPU-tested only.  This
tool drives the SAME public CLI path a user would
(``run --ensemble S``) on the chip, for:

  1. the BASELINE SWIM-1M shape, 16 seeds — detection-latency
     distribution of the failure detector, and
  2. the flagship SI pull shape at bench scale (10M nodes, XLA threefry
     engine — ensembles are contractually threefry: backend.run_ensemble
     rejects engine='fused'), 8 seeds — rounds-to-target quantiles.

Each sub-capture is its own CLI subprocess (own process group,
group-kill on timeout: a half-killed process would keep the chip), and
the artifact is written after EVERY sub-capture, so a run cut short
keeps the completed half.  artifacts/ensembles_r05.json.

``--smoke`` rehearses both sub-captures at CPU scale.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sub_captures(smoke: bool):
    """(name, cli_args, timeout_s) per sub-capture, priority order."""
    if smoke:
        swim_n, si_n, swim_seeds, si_seeds = 20_000, 100_000, 4, 4
    else:
        swim_n, si_n, swim_seeds, si_seeds = 1_000_000, 10_000_000, 16, 8
    return [
        ("swim_1m_detection", [
            "run", "--mode", "swim", "--n", str(swim_n),
            "--family", "power_law", "--k", "3", "--degree-cap", "256",
            "--fanout", "2", "--swim-subjects", "8", "--swim-proxies", "3",
            "--swim-suspect-rounds", "24", "--max-rounds", "80",
            "--ensemble", str(swim_seeds)], 1500),
        ("si_pull_bench_scale", [
            "run", "--mode", "pull", "--n", str(si_n), "--fanout", "1",
            "--max-rounds", "40", "--ensemble", str(si_seeds)], 900),
    ]


def run_capture(args, timeout_s: int, smoke: bool) -> dict:
    cmd = [sys.executable, "-u", "-m", "gossip_tpu", *args]
    env = (dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                GOSSIP_COMPILE_CACHE="") if smoke else dict(os.environ))
    t0 = time.time()
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, cwd=REPO,
                         env=env, start_new_session=True)
    try:
        stdout, stderr = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        p.communicate()
        raise
    if p.returncode != 0:
        raise RuntimeError(f"CLI rc={p.returncode}\n{stderr[-1500:]}")
    out = None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "ensemble" in cand:
                out = cand
    if out is None:
        raise RuntimeError(f"no ensemble JSON on stdout\n{stdout[-1500:]}")
    out["subprocess_wall_s"] = round(time.time() - t0, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of sub-capture names")
    a = ap.parse_args()
    infix = ".smoke" if a.smoke else ""
    art = os.path.join(REPO, "artifacts", f"ensembles_r05{infix}.json")
    try:
        with open(art) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"what": ("hardware capture of the seed-axis ensemble "
                        "surface via the public run --ensemble CLI "
                        "(VERDICT r4 task 6); sub-captures merge "
                        "incrementally — reruns only fill gaps")}

    timeouts = hard_failures = 0
    for name, args, timeout_s in sub_captures(a.smoke):
        if a.only is not None and name not in a.only:
            continue
        if doc.get(name, {}).get("ok"):
            continue                     # landed in an earlier window
        try:
            res = run_capture(args, timeout_s, a.smoke)
            doc[name] = {"ok": True, "command": " ".join(args),
                         "report": res}
        except subprocess.TimeoutExpired:
            timeouts += 1
            doc[name] = {"ok": False,
                         "error": f"timeout after {timeout_s} s"}
        except Exception as e:
            hard_failures += 1
            doc[name] = {"ok": False,
                         "error": f"{type(e).__name__}: {e}"[:800]}
        # stamped per write: the merged artifact's attribution is the
        # run that last touched it (the one artifact schema —
        # tools/validate_artifacts.py / staticcheck writer gate)
        from _telemetry import telemetry
        doc["provenance"] = telemetry().provenance()
        with open(art, "w") as f:
            json.dump(doc, f, indent=1)
    # final summary line = the callers' machine-readable result
    # (tools/hw_refresh.py parses the LAST stdout JSON line)
    print(json.dumps({k: v.get("ok") for k, v in doc.items()
                      if isinstance(v, dict)}), flush=True)
    print(f"wrote {art}", file=sys.stderr)
    # exit codes: 2 = a sub-capture timed out (a rerun fills the gap),
    # 1 = a sub-capture failed
    if timeouts:
        return 2
    return 0 if hard_failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
