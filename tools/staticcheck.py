#!/usr/bin/env python
"""CI/capture entry for the AST invariant analyzer (``gossip_tpu
staticcheck``): run all four checker families over the live tree,
write the provenance-stamped findings ledger, and print one summary
JSON line (the hw_refresh last-stdout-line contract).

    python tools/staticcheck.py                # artifacts/ledger_staticcheck_r19.jsonl
    python tools/staticcheck.py --smoke        # .smoke infixed artifact
    python tools/staticcheck.py --no-ledger    # console-only (pre-commit)

Pure stdlib + the repo's own analysis package — never imports jax, so
this step runs identically on a laptop, a saturated CI host, and a TPU
box (the one hw_refresh step that needs no chip).  Exit 0 iff the tree is clean against the
suppression baseline (tools/staticcheck_baseline.json); findings print
one per line before the summary.  Gated in tier-1 by
tests/test_staticcheck.py (clean-tree gate + committed-artifact pin).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARTIFACT_STEM = "ledger_staticcheck_r19"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="rehearsal mode: same full analysis (AST "
                         "passes are already single-digit seconds), "
                         ".smoke-infixed artifact")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="findings-ledger path (default: artifacts/"
                         f"{ARTIFACT_STEM}[.smoke].jsonl)")
    ap.add_argument("--no-ledger", action="store_true",
                    help="console-only run, write nothing")
    a = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    try:
        from gossip_tpu.analysis import runner
    finally:
        sys.path.pop(0)

    report = runner.run_tree()
    ledger = None
    if not a.no_ledger:
        infix = ".smoke" if a.smoke else ""
        ledger = a.ledger or os.path.join(
            REPO, "artifacts", f"{ARTIFACT_STEM}{infix}.jsonl")
        runner.write_ledger(report, ledger)
    for f in report.findings:
        print(f.render(), file=sys.stderr)
    counts = report.counts()
    print(json.dumps({
        "verdict": "clean" if report.clean else "dirty",
        "findings": len(report.findings),
        "suppressed": len(report.suppressed),
        "baseline_entries": report.baseline_entries,
        "files_scanned": report.files_scanned,
        "counts": counts,
        **({"ledger": ledger} if ledger else {})}))
    return 0 if report.clean else 1


if __name__ == "__main__":
    sys.exit(main())
