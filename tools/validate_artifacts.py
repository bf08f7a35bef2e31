#!/usr/bin/env python
"""CI gate: every committed artifact parses, every new-format artifact
carries provenance.

The round-ledger contract (round 7, docs/OBSERVABILITY.md): an
artifact whose numbers are meant to be believed must say which commit,
toolchain, and run produced them — the provenance keys ``run_id``,
``git_commit``, ``captured`` (utils/telemetry.provenance).  Ledger
JSONLs carry them on their first ``provenance`` event line; plain-JSON
artifacts embed the dict under a ``"provenance"`` key (or the three
keys at top level).

Artifacts that predate the ledger are ALLOWLISTED BY NAME below — an
explicit, reviewable list, not a silent grandfather clause: adding a
new artifact without provenance fails loudly, and retiring a legacy
file shrinks the list.  Every file, legacy or not, must still parse
(torn jsonl lines — a killed writer's fragment, tail or mid-file in
shared flight-recorder files — are dropped by the crash contract; the
surviving lines must satisfy the schema).

    python tools/validate_artifacts.py            # repo artifacts/
    python tools/validate_artifacts.py DIR        # any directory

Exit 0 all green; exit 1 with one line per failure.  Run in tier-1 by
tests/test_validate_artifacts.py.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROVENANCE_KEYS = ("run_id", "git_commit", "captured")

# Pre-ledger artifacts, frozen by name.  Do NOT add new files here —
# new artifacts must carry provenance (utils/telemetry.provenance);
# this list only shrinks.
LEGACY = frozenset({
    "baseline_sweep_r02.jsonl",
    "baseline_sweep_r04.jsonl",
    "baseline_sweep_r04.smoke.jsonl",
    "baseline_sweep_r04b.jsonl",
    "baseline_sweep_r05.smoke.jsonl",
    "dryrun_steady_budget_r06.json",
    "ensembles_r05.smoke.json",
    "hw_refresh_r04.json",
    "hw_refresh_r04.smoke.json",
    "hw_refresh_r05.smoke.json",
    "kernel_numbers_r05.smoke.json",
    "maelstrom_batching_r04.json",
    "maelstrom_batching_r05.json",
    "parity_r03.json",
    "parity_r04.json",
    "parity_r05.json",
    "roofline_r05.smoke.json",
    "swim_ab_r04.json",
    "swim_cache_r04.json",
    "swim_compile_ablation_r04.json",
    "swim_diss_ab_r04.smoke.json",
    "swim_diss_ab_r05.smoke.json",
    # swim_steady_ablation_r05.smoke.json left this list in the
    # observability PR: the tool now embeds provenance and the
    # committed smoke artifact was regenerated with it
})


def _parse_jsonl(path):
    """Parsed lines via the ONE crash-contract parser
    (utils/telemetry.load_ledger: torn lines dropped — tail for
    single-writer ledgers, mid-file for shared flight-recorder files)
    — the contract must not fork between the writer and this gate."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from _telemetry import telemetry
    finally:
        sys.path.pop(0)
    return telemetry().load_ledger(path)


def _has_provenance_keys(obj) -> bool:
    if not isinstance(obj, dict):
        return False
    if all(k in obj for k in PROVENANCE_KEYS):
        return True
    prov = obj.get("provenance")
    return isinstance(prov, dict) and all(k in prov
                                          for k in PROVENANCE_KEYS)


def _is_nemesis_name(name: str) -> bool:
    """Churn/nemesis/crashloop/CRDT scenario artifacts by name —
    robustness evidence (heal convergence, fault observables,
    SIGKILL/resume records, value-convergence verdicts) must always be
    attributable; the legacy allowlist can never grandfather one in
    (the whole nemesis layer, the crashloop harness, and the CRDT
    subsystem all post-date the provenance schema)."""
    return ("churn" in name or "nemesis" in name
            or "crashloop" in name or "crdt" in name)


def _is_byz_name(name: str) -> bool:
    """Byzantine-adversary artifacts by name — the liar-scenario
    evidence (defended honest-set convergence vs the undefended
    control arm, quorum parameters, mesh-parity verdicts —
    ops/nemesis byz programs via tools/byzantine_capture) must always
    be attributable; the legacy allowlist can never grandfather one
    in (the whole byzantine layer post-dates the provenance schema).
    An unattributed adversary record is the exact claim the defense
    lattice exists to reject: state nobody can trace to a writer."""
    return ("byz" in name or "byzantine" in name
            or "adversary" in name)


def _is_log_name(name: str) -> bool:
    """Replicated-log ("kafka") artifacts by name — log-convergence
    verdicts and workload invariant records (the ordered
    eventual-consistency evidence, ops/logs + the KafkaServer
    workload) must always be attributable; the legacy allowlist can
    never grandfather one in (the whole log subsystem post-dates the
    provenance schema)."""
    return "kafka" in name or "replog" in name


def _is_txn_name(name: str) -> bool:
    """Txn/register artifacts by name — isolation-anomaly verdicts and
    LWW convergence records (the totally-available-transactions
    evidence, ops/registers + the TxnServer workload +
    runtime/txn_checker) must always be attributable; the legacy
    allowlist can never grandfather one in (the whole register
    subsystem post-dates the provenance schema)."""
    return "txn" in name or "register" in name


def _is_fused_sweep_name(name: str) -> bool:
    """Fused-sweep artifacts by name — the fused engine's
    compile-amortization evidence (K scenarios through one executable,
    warm-vs-solo-recompile ratios — tools/fused_sweep_capture) must
    always be attributable; the legacy allowlist can never grandfather
    one in (the fused-operand layer post-dates the provenance
    schema)."""
    return "fused_sweep" in name


def _is_staticcheck_name(name: str) -> bool:
    """Staticcheck/lint artifacts by name — the invariant analyzer's
    own verdict ledgers (clean-tree claims, per-checker finding
    counts — gossip_tpu/analysis + tools/staticcheck.py) must always
    be attributable; the legacy allowlist can never grandfather one
    in (the analyzer post-dates the provenance schema by fifteen
    rounds, and a lint verdict nobody can attribute to a commit
    certifies nothing)."""
    return "staticcheck" in name or "lint" in name


def _is_scale_name(name: str) -> bool:
    """Scale-planner artifacts by name — capacity plans, HBM budget
    verdicts, and streamed-tiling records (gossip_tpu/planner +
    tools/scale_capture) must always be attributable; the legacy
    allowlist can never grandfather one in (the whole planner
    subsystem post-dates the provenance schema).  The ONE name-space
    collision is carved out explicitly rather than allowlisted:
    dryrun_steady_budget_r06.json is the round-6 dry-run STEADY-WALL
    budget snapshot (docs/PERF.md cites it as before/after evidence),
    not a scale-planner budget — it predates the subsystem by
    fourteen rounds and stays on the ordinary legacy list above."""
    if name == "dryrun_steady_budget_r06.json":
        return False
    return "scale" in name or "plan" in name or "budget" in name


def _is_fleet_name(name: str) -> bool:
    """Fleet/router/failover artifacts by name — the replicated-
    serving evidence (SIGKILLed replicas with zero acked-request loss,
    bitwise failover replay parity, recovery to full capacity —
    rpc/router + tools/fleet_crashloop) must always be attributable;
    the legacy allowlist can never grandfather one in (the whole fleet
    layer post-dates the provenance schema)."""
    return ("fleet" in name or "router" in name
            or "failover" in name)


def _is_serving_name(name: str) -> bool:
    """Serving/load/meshserve artifacts by name — throughput and
    latency gates (the admission-batching layer's committed evidence:
    requests/sec, p50/p95/p99, bitwise-equality verdicts —
    tools/load_harness, including the mesh-sharded device-scaling
    captures) must always be attributable; the legacy allowlist can
    never grandfather one in (the whole serving layer post-dates the
    provenance schema)."""
    return "serving" in name or "load" in name or "meshserve" in name


def _is_cost_name(name: str) -> bool:
    """Cost/xprof/attribution artifacts by name — the XLA cost &
    memory attribution evidence (per-executable flops/bytes, cache
    verdicts, the packed budget_xcheck measured≤predicted pair —
    utils/compile_cache's xla_compile events via tools/cost_capture)
    must always be attributable; the legacy allowlist can never
    grandfather one in (the whole attribution plane post-dates the
    provenance schema).  An unattributed cost table is the exact
    failure the plane exists to prevent: numbers nobody can pin to a
    commit or a compile."""
    return ("cost" in name or "xprof" in name
            or "attribution" in name)


def _is_trace_name(name: str) -> bool:
    """Trace/fleet-status artifacts by name — the request-tracing and
    live-metrics evidence (per-request waterfalls joined by trace_id,
    fleet health snapshots — tools/trace_report, tools/trace_capture,
    `gossip_tpu fleet-status --out`) must always be attributable; the
    legacy allowlist can never grandfather one in (the whole tracing
    plane post-dates the provenance schema).  An unattributed
    waterfall is worse than none: it LOOKS like per-request evidence
    while naming no commit anyone can reproduce it against."""
    return "trace" in name or "fleet_status" in name


def validate_file(path):
    """[] when valid, else a list of human-readable problems."""
    name = os.path.basename(path)
    problems = []
    try:
        if name.endswith(".jsonl"):
            rows = _parse_jsonl(path)
            with open(path) as f:
                nonblank = sum(1 for ln in f if ln.strip())
            if nonblank and not rows:
                # torn-line tolerance must not bless a file with NO
                # surviving lines — that is destruction, not a crash
                problems.append("does not parse: no parseable lines "
                                f"among {nonblank}")
            has_prov = any(_has_provenance_keys(r) for r in rows
                           if isinstance(r, dict))
            if name not in LEGACY and not has_prov:
                problems.append(
                    "new-format jsonl without a provenance line "
                    f"carrying {PROVENANCE_KEYS} "
                    "(utils/telemetry.provenance)")
            # round-metric series are protocol-semantics evidence
            # (ops/round_metrics) and post-date the ledger by two
            # rounds: an artifact carrying them MUST be attributable,
            # allowlist or not — the legacy list can never grandfather
            # a metrics-bearing file in
            if not has_prov and any(
                    isinstance(r, dict)
                    and r.get("ev") == "round_metrics" for r in rows):
                problems.append(
                    "carries round_metrics events but no provenance "
                    "line — round-metric artifacts must be "
                    "attributable (utils/telemetry.provenance)")
            if not has_prov and _is_nemesis_name(name):
                problems.append(
                    "nemesis/churn artifact without a provenance line "
                    "— robustness evidence must be attributable, "
                    "allowlist or not (utils/telemetry.provenance)")
            if not has_prov and _is_serving_name(name):
                problems.append(
                    "serving/load artifact without a provenance line "
                    "— throughput/latency gates must be attributable, "
                    "allowlist or not (utils/telemetry.provenance)")
            if not has_prov and _is_fleet_name(name):
                problems.append(
                    "fleet/router/failover artifact without a "
                    "provenance line — replicated-serving evidence "
                    "must be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_log_name(name):
                problems.append(
                    "replicated-log/kafka artifact without a "
                    "provenance line — log-convergence evidence must "
                    "be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_txn_name(name):
                problems.append(
                    "txn/register artifact without a provenance line "
                    "— isolation-anomaly and LWW-convergence "
                    "evidence must be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_byz_name(name):
                problems.append(
                    "byzantine/adversary artifact without a "
                    "provenance line — liar-scenario evidence must "
                    "be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_fused_sweep_name(name):
                problems.append(
                    "fused-sweep artifact without a provenance line — "
                    "compile-amortization evidence must be "
                    "attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_staticcheck_name(name):
                problems.append(
                    "staticcheck/lint artifact without a provenance "
                    "line — an invariant-analyzer verdict must be "
                    "attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_scale_name(name):
                problems.append(
                    "scale/plan/budget artifact without a provenance "
                    "line — capacity plans and streamed-tiling "
                    "records must be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_trace_name(name):
                problems.append(
                    "trace/fleet_status artifact without a provenance "
                    "line — per-request waterfalls and fleet health "
                    "snapshots must be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
            if not has_prov and _is_cost_name(name):
                problems.append(
                    "cost/xprof/attribution artifact without a "
                    "provenance line — XLA cost & memory attribution "
                    "evidence must be attributable, allowlist or not "
                    "(utils/telemetry.provenance)")
        else:
            with open(path) as f:
                doc = json.load(f)
            if _is_nemesis_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "nemesis/churn artifact without provenance keys "
                    f"{PROVENANCE_KEYS} — robustness evidence must be "
                    "attributable, allowlist or not")
            elif _is_serving_name(name) \
                    and not _has_provenance_keys(doc):
                problems.append(
                    "serving/load artifact without provenance keys "
                    f"{PROVENANCE_KEYS} — throughput/latency gates "
                    "must be attributable, allowlist or not")
            elif _is_fleet_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "fleet/router/failover artifact without "
                    f"provenance keys {PROVENANCE_KEYS} — replicated-"
                    "serving evidence must be attributable, allowlist "
                    "or not")
            elif _is_log_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "replicated-log/kafka artifact without provenance "
                    f"keys {PROVENANCE_KEYS} — log-convergence "
                    "evidence must be attributable, allowlist or not")
            elif _is_txn_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "txn/register artifact without provenance keys "
                    f"{PROVENANCE_KEYS} — isolation-anomaly and "
                    "LWW-convergence evidence must be attributable, "
                    "allowlist or not")
            elif _is_byz_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "byzantine/adversary artifact without provenance "
                    f"keys {PROVENANCE_KEYS} — liar-scenario evidence "
                    "must be attributable, allowlist or not")
            elif _is_fused_sweep_name(name) \
                    and not _has_provenance_keys(doc):
                problems.append(
                    "fused-sweep artifact without provenance keys "
                    f"{PROVENANCE_KEYS} — compile-amortization "
                    "evidence must be attributable, allowlist or not")
            elif _is_staticcheck_name(name) \
                    and not _has_provenance_keys(doc):
                problems.append(
                    "staticcheck/lint artifact without provenance "
                    f"keys {PROVENANCE_KEYS} — an invariant-analyzer "
                    "verdict must be attributable, allowlist or not")
            elif _is_scale_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "scale/plan/budget artifact without provenance "
                    f"keys {PROVENANCE_KEYS} — capacity plans and "
                    "streamed-tiling records must be attributable, "
                    "allowlist or not")
            elif _is_trace_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "trace/fleet_status artifact without provenance "
                    f"keys {PROVENANCE_KEYS} — per-request waterfalls "
                    "and fleet health snapshots must be attributable, "
                    "allowlist or not")
            elif _is_cost_name(name) and not _has_provenance_keys(doc):
                problems.append(
                    "cost/xprof/attribution artifact without "
                    f"provenance keys {PROVENANCE_KEYS} — XLA cost & "
                    "memory attribution evidence must be attributable, "
                    "allowlist or not")
            elif name not in LEGACY and not _has_provenance_keys(doc):
                problems.append(
                    "new-format json without provenance keys "
                    f"{PROVENANCE_KEYS} (embed utils/telemetry."
                    "provenance() under a 'provenance' key)")
    except ValueError as e:
        problems.append(f"does not parse: {e}")
    except OSError as e:
        problems.append(f"unreadable: {e}")
    return problems


def validate_dir(art_dir):
    """{filename: [problems]} for every *.json / *.jsonl in the dir
    (empty dict == all green).  Non-JSON artifacts (.txt/.log capture
    transcripts) are out of scope."""
    failures = {}
    for name in sorted(os.listdir(art_dir)):
        if not name.endswith((".json", ".jsonl")):
            continue
        problems = validate_file(os.path.join(art_dir, name))
        if problems:
            failures[name] = problems
    return failures


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    art_dir = argv[0] if argv else os.path.join(REPO, "artifacts")
    if not os.path.isdir(art_dir):
        print(f"no such directory: {art_dir}", file=sys.stderr)
        return 2
    failures = validate_dir(art_dir)
    checked = [n for n in sorted(os.listdir(art_dir))
               if n.endswith((".json", ".jsonl"))]
    for name, problems in failures.items():
        for p in problems:
            print(f"FAIL {name}: {p}")
    print(f"{len(checked) - len(failures)}/{len(checked)} artifacts "
          f"valid in {art_dir}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
