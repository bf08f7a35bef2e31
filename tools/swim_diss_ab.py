#!/usr/bin/env python
"""A/B the SWIM dissemination lowerings on the real chip.

docs/PERF.md "SWIM-1M cost budget" leaves steady state (~374 ms/round
at 1M nodes) as the remaining lever, and the repo cost model prices its
dominant HBM term — the sorted row gather — at ~7 ns/word x M*S words.
``swim_diss='pack'`` (models/swim.disseminate_max) gathers 8/16-bit
packed transport codes instead, 4x/2x fewer words, bitwise-identical
trajectories (tests/test_swim.py pins the equivalence).  This tool
arbitrates on hardware, exactly like the r04 sort-vs-scatter A/B
(artifacts/swim_ab_r04.json) whose verdict made sort the default:

  - runs the exact BASELINE SWIM-1M shape through the run CLI once per
    impl (fresh per-impl compile-cache dir: compile_s stays honest),
  - asserts the trajectories match (rounds / coverage / msgs equal —
    anything else means the lowering is NOT pure and must not ship),
  - writes artifacts/swim_diss_ab_r05.json with walls, steady split,
    and a verdict line.

Run on the chip.  ``--smoke`` rehearses the plumbing at CPU scale (n=20k, no
TPU) writing a ``.smoke``-infixed artifact, repo convention.

    python tools/swim_diss_ab.py                 # sort (control) vs pack
    python tools/swim_diss_ab.py --impls scatter sort pack
    python tools/swim_diss_ab.py --smoke
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_RUN_TIMEOUT_S = 900


def worst_case_budget_s(n_impls: int = 2,
                        run_timeout_s: int = DEFAULT_RUN_TIMEOUT_S) -> int:
    """Upper bound on a full A/B run (every run at its full timeout),
    exported so tools/hw_refresh.py derives its step budget from the
    same constants this file's loops use — a parent timeout below this
    can kill us before our own group-kill fires, orphaning a live TPU
    client."""
    return n_impls * run_timeout_s


class RunFailed(RuntimeError):
    """A run timed out or the run CLI exited nonzero."""


BASE_ARGS = ["--mode", "swim", "--family", "power_law", "--k", "3",
             "--degree-cap", "256", "--fanout", "2", "--swim-subjects", "8",
             "--swim-proxies", "3", "--swim-suspect-rounds", "24",
             "--max-rounds", "80"]


def run_one(impl: str, n: int, timeout_s: int, smoke: bool) -> dict:
    cmd = [sys.executable, "-m", "gossip_tpu", "run", "--n", str(n),
           *BASE_ARGS, "--swim-diss", impl]
    env = (dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
           if smoke else dict(os.environ))
    with tempfile.TemporaryDirectory(prefix=f"swimab-{impl}-") as cache:
        cmd += ["--compile-cache", cache]   # per-impl dir: cold, honest
        t0 = time.time()
        # own process group + group kill on timeout: a half-killed TPU
        # client would keep holding the chip
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
            raise RunFailed(f"{impl}: run timed out after {timeout_s} s")
    if p.returncode != 0:
        raise RunFailed(f"{impl}: run CLI failed rc={p.returncode}\n"
                        f"{stderr[-2000:]}")
    out = None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "wall_s" in cand:
                out = cand
    if out is None:
        raise RuntimeError(f"{impl}: no result JSON on stdout\n"
                           f"{stdout[-2000:]}")
    meta = out.get("meta") or {}
    return {"swim_diss": impl,
            "wall_s": out["wall_s"],
            "compile_s": meta.get("compile_s"),
            "steady_wall_s": meta.get("steady_wall_s"),
            "rounds": out["rounds"],
            "coverage": out["coverage"],
            "msgs": out["msgs"],
            "subprocess_wall_s": round(time.time() - t0, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impls", nargs="+", default=["sort", "pack"])
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--timeout", type=int, default=DEFAULT_RUN_TIMEOUT_S,
                    help="per-run subprocess timeout (s)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-scale rehearsal (n=20k, JAX_PLATFORMS=cpu)")
    a = ap.parse_args()
    n = 20_000 if a.smoke else a.n
    infix = ".smoke" if a.smoke else ""
    art = os.path.join(REPO, "artifacts", f"swim_diss_ab_r05{infix}.json")

    rows = []
    for impl in a.impls:
        try:
            row = run_one(impl, n, a.timeout, a.smoke)
        except RunFailed as e:
            print(str(e), file=sys.stderr)
            return 1
        print(json.dumps(row), flush=True)
        rows.append(row)

    traj = {(r["rounds"], r["coverage"], r["msgs"]) for r in rows}
    identical = len(traj) == 1
    verdict = winner = None
    if identical and len(rows) >= 2:
        # winner = min steady over ALL rows (control included): a
        # candidate that regresses must lose to the control, and the
        # artifact's field is THE arbitration consumers read
        # (hw_refresh.swim_diss_winner) — one definition, one file
        ctl, best = rows[0], min(rows, key=lambda r: r["steady_wall_s"])
        winner = best["swim_diss"]
        verdict = (f"winner {winner}: steady {ctl['steady_wall_s']:.1f}"
                   f" -> {best['steady_wall_s']:.1f} s, compile "
                   f"{ctl['compile_s']:.1f} -> {best['compile_s']:.1f} s "
                   f"vs {ctl['swim_diss']} control")
    from _telemetry import telemetry
    doc = {
        # the one artifact schema (run_id/git_commit/captured —
        # tools/validate_artifacts.py): regenerations must be
        # attributable even though the committed file is
        # legacy-allowlisted by name (staticcheck writer gate)
        "provenance": telemetry().provenance(),
        "what": ("A/B of ProtocolConfig.swim_diss lowerings on the "
                 "BASELINE SWIM-1M shape; identical trajectories required "
                 "(rounds/coverage/msgs) per models/swim.disseminate_max"),
        "command": ("python -m gossip_tpu run --n %d %s "
                    "--swim-diss {%s} --compile-cache FRESH_DIR"
                    % (n, " ".join(BASE_ARGS), "|".join(a.impls))),
        "rows": rows,
        "trajectories_identical": identical,
        "winner": winner,
        "verdict": verdict,
    }
    with open(art, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {art}", file=sys.stderr)
    if not identical:
        print("TRAJECTORY MISMATCH — the candidate lowering is not pure; "
              "do not change the default", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
