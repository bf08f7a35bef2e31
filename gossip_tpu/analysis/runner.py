"""Orchestrate the checker families over the live tree and emit the
provenance-stamped findings ledger.

``run_tree()`` is the one entry every consumer shares — CLI
``gossip_tpu staticcheck``, tools/staticcheck.py (CI / hw_refresh
step), the dry-run staticcheck step, and tests/test_staticcheck.py's
clean-tree gate — so the scope tables and baseline application cannot
drift between them.  Pure stdlib: importing this module never imports
jax (the analyzer must run on a box with no chip).

Ledger schema (docs/OBSERVABILITY.md):

  * the usual ``provenance`` first line (telemetry.artifact_ledger);
  * one ``checker`` event per family: ``{checker, findings,
    suppressed}`` counts;
  * one ``finding`` event per live finding (rule/path/line/symbol/
    message) — dirty runs leave mechanically checkable evidence;
  * a final ``staticcheck`` verdict event: ``{verdict: clean|dirty,
    findings, suppressed, baseline_entries, files_scanned}`` — the
    committed artifacts/ledger_staticcheck_r19.jsonl pins it tier-1.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from gossip_tpu.analysis import conventions, locks, recompile
from gossip_tpu.analysis.core import (BASELINE_PATH, REPO, Finding,
                                      apply_baseline, iter_py_files,
                                      load_baseline, load_modules)

FAMILIES = ("recompile", "locks", "conventions", "baseline")


@dataclasses.dataclass
class Report:
    findings: List[Finding]          # live (unsuppressed) findings
    suppressed: List[Finding]        # baselined, rationale on file
    baseline_entries: int
    files_scanned: int

    @property
    def clean(self) -> bool:
        return not self.findings

    def counts(self) -> Dict[str, Dict[str, int]]:
        out = {fam: {"findings": 0, "suppressed": 0}
               for fam in FAMILIES}
        for f in self.findings:
            out.setdefault(f.checker,
                           {"findings": 0, "suppressed": 0})[
                "findings"] += 1
        for f in self.suppressed:
            out.setdefault(f.checker,
                           {"findings": 0, "suppressed": 0})[
                "suppressed"] += 1
        return out


def run_tree(root: str = REPO,
             baseline_path: Optional[str] = None) -> Report:
    """All four checker families over the tree at ``root`` with the
    committed suppression baseline applied.  ``baseline_path=None``
    uses tools/staticcheck_baseline.json under ``root``; pass "" to
    run baseline-free (the raw-findings view)."""
    if baseline_path is None:
        baseline_path = os.path.join(root, BASELINE_PATH)

    # parse every in-scope file exactly ONCE and hand the checkers
    # filtered views — the scopes overlap heavily (rpc/ and sweep are
    # inside both the serving and memo/event sets), and Module's
    # parent-map construction is the analyzer's dominant cost
    memo_files = list(iter_py_files(root, ("gossip_tpu",)))
    event_files = list(iter_py_files(root, conventions.EVENT_SCOPE_DIRS))
    tool_files = list(iter_py_files(root, (conventions.TOOLS_DIR,)))
    all_mods = load_modules(
        root, sorted(set(memo_files) | set(event_files)
                     | set(tool_files) | set(recompile.SCOPE)
                     | set(recompile.STREAM_SCOPE)
                     | set(locks.SCOPE)))

    def view(paths):
        return {p: all_mods[p] for p in paths if p in all_mods}

    serving = view(recompile.SCOPE)
    stream = view(recompile.STREAM_SCOPE)
    memo = view(memo_files)
    rpc = view(locks.SCOPE)
    event_mods = view(event_files)
    tool_mods = view(tool_files)

    findings: List[Finding] = []
    findings += recompile.check(serving, memo)
    findings += recompile.check_stream_fetch(stream)
    findings += locks.check(rpc)
    findings += conventions.check_event_kind(event_mods)
    findings += conventions.check_sync_emit(event_mods)
    findings += conventions.check_artifact_provenance(tool_mods)
    findings += conventions.check_dryrun_budgets(root)
    findings += conventions.check_capability_strings(memo)
    findings += conventions.check_unattributed_compile(memo)

    entries, problems = (load_baseline(baseline_path)
                         if baseline_path else ([], []))
    live, suppressed, stale = apply_baseline(findings, entries)
    live = sorted(live + problems + stale,
                  key=lambda f: (f.path, f.line, f.rule))
    scanned = set(memo_files) | set(event_files) | set(tool_files) \
        | set(serving) | set(rpc)
    return Report(findings=live, suppressed=suppressed,
                  baseline_entries=len(entries),
                  files_scanned=len(scanned))


def write_ledger(report: Report, path: str) -> None:
    """The findings ledger (module doc schema) through the one shared
    provenance-stamping helper — the same writer discipline as
    tests/conftest.py's duration ledger, by construction."""
    from gossip_tpu.utils import telemetry
    with telemetry.artifact_ledger(path) as led:
        for fam, cnt in sorted(report.counts().items()):
            led.event("checker", checker=fam, **cnt)
        for f in report.findings:
            led.event("finding", checker=f.checker, rule=f.rule,
                      path=f.path, line=f.line, symbol=f.symbol,
                      message=f.message)
        led.event("staticcheck",
                  verdict="clean" if report.clean else "dirty",
                  findings=len(report.findings),
                  suppressed=len(report.suppressed),
                  baseline_entries=report.baseline_entries,
                  files_scanned=report.files_scanned)


def main(argv: Optional[List[str]] = None) -> int:
    """CLI body shared by ``gossip_tpu staticcheck`` and
    tools/staticcheck.py: print findings (one line each), optionally
    write the ledger, exit 0 iff clean."""
    import argparse
    import json as _json

    ap = argparse.ArgumentParser(
        prog="gossip_tpu staticcheck",
        description="AST invariant analyzer: recompile-hazard lint, "
                    "rpc lock discipline, convention gates "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--root", default=REPO,
                    help="tree to analyze (default: this repo)")
    ap.add_argument("--baseline", default=None, metavar="JSON",
                    help="suppression baseline (default: tools/"
                         "staticcheck_baseline.json under --root; "
                         "'' disables)")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="write the provenance-stamped findings "
                         "ledger here")
    ap.add_argument("--json", action="store_true",
                    help="emit one summary JSON line instead of "
                         "per-finding text")
    a = ap.parse_args(argv)
    report = run_tree(a.root, a.baseline)
    if a.ledger:
        write_ledger(report, a.ledger)
    counts = report.counts()
    if a.json:
        print(_json.dumps({
            "verdict": "clean" if report.clean else "dirty",
            "findings": len(report.findings),
            "suppressed": len(report.suppressed),
            "baseline_entries": report.baseline_entries,
            "files_scanned": report.files_scanned,
            "counts": counts,
            **({"ledger": a.ledger} if a.ledger else {})}))
    else:
        for f in report.findings:
            print(f.render())
        print(f"staticcheck: {len(report.findings)} finding(s), "
              f"{len(report.suppressed)} baselined "
              f"(rationales on file), {report.files_scanned} files — "
              + ("clean" if report.clean else "DIRTY"))
    return 0 if report.clean else 1
