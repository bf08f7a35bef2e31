#!/usr/bin/env python
"""Render a run ledger (utils/telemetry JSONL) into doc-ready markdown.

The one place artifacts get their numbers from (round 7): the dry-run
per-family table, the budget deltas against tools/dryrun_budgets.json,
the probe timeline of a capture window, and device-memory high-water
all come straight out of the ledger — no re-parsing of stdout, no
bespoke per-tool JSON.

    python tools/telemetry_report.py ARTIFACT.jsonl            # last run
    python tools/telemetry_report.py ARTIFACT.jsonl --run RUNID
    python tools/telemetry_report.py ARTIFACT.jsonl --all-runs
    python tools/telemetry_report.py ... -o report.md

A ledger written by a run that was SIGKILLed mid-flight still renders:
unclosed spans are reported as such (the flight-recorder read-out the
dark rounds needed), and a torn final line is dropped by the loader's
documented crash contract.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS_PATH = os.path.join(REPO, "tools", "dryrun_budgets.json")


def _telemetry():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from _telemetry import telemetry
    finally:
        sys.path.pop(0)
    return telemetry()


def load_ledger(path, run=None):
    return _telemetry().load_ledger(path, run=run)


def runs(events):
    """Run ids in file order (provenance lines define runs; lines from
    an unknown run — a truncated provenance — still count)."""
    seen = []
    for e in events:
        r = e.get("run")
        if r is not None and r not in seen:
            seen.append(r)
    return seen


def span_tree(events):
    """[(depth, node)] in start order; ``node`` has name/wall_ms/ok and
    ``unclosed=True`` when the run died before span_end (SIGKILL, outer
    timeout) — the span_start is durable by the fsync contract, so the
    tree still shows WHERE it died."""
    nodes = {}
    order = []
    for e in events:
        if e.get("ev") == "span_start":
            nodes[e["span"]] = {"span": e["span"], "parent": e.get("parent"),
                                "name": e.get("name"), "ts": e.get("ts"),
                                "unclosed": True,
                                "attrs": {k: v for k, v in e.items()
                                          if k not in ("ev", "ts", "run",
                                                       "span", "parent",
                                                       "name")}}
            order.append(e["span"])
        elif e.get("ev") == "span_end" and e.get("span") in nodes:
            n = nodes[e["span"]]
            n["unclosed"] = False
            n["wall_ms"] = e.get("wall_ms")
            n["ok"] = e.get("ok", True)
            n["attrs"].update({k: v for k, v in e.items()
                               if k not in ("ev", "ts", "run", "span",
                                            "parent", "name", "wall_ms",
                                            "ok")})

    def depth(sid):
        d = 0
        p = nodes[sid]["parent"]
        while p is not None and p in nodes:
            d += 1
            p = nodes[p]["parent"]
        return d

    return [(depth(s), nodes[s]) for s in order]


def family_table(events):
    """{family: row} from the dry run's ``family`` events — the exact
    per-family ms table the body printed on stdout, recovered from
    ledger data alone (first/steady plus the wall decomposition on the
    fused rows)."""
    table = {}
    for e in events:
        if e.get("ev") == "family":
            row = {k: v for k, v in e.items()
                   if k not in ("ev", "ts", "run", "family")}
            table[e["family"]] = row
    return table


def memory_high_water(events):
    """Max bytes_in_use / peak_bytes_in_use over every memory snapshot
    (span_end ``memory`` fields and standalone ``memory`` events), or
    None when the backend reported no stats (CPU)."""
    peak = {}
    for e in events:
        rows = []
        if e.get("ev") == "memory":
            rows = e.get("devices") or []
        elif e.get("ev") == "span_end" and e.get("memory"):
            rows = e["memory"]
        for r in rows:
            for k in ("bytes_in_use", "peak_bytes_in_use"):
                if isinstance(r.get(k), (int, float)):
                    peak[k] = max(peak.get(k, 0), r[k])
    return peak or None


def probe_timeline(events):
    """The capture-window read-out: every probe/fallback/measurement
    event with a time offset from the run's first event — 78 timed-out
    probes render as 78 rows with walls, not a lost stderr stream."""
    t0 = events[0]["ts"] if events else 0.0
    rows = []
    for e in events:
        if e.get("ev") in ("probe", "fallback", "measurement",
                           "measurement_failed", "body_abnormal_exit",
                           "refresh_start", "refresh_abort", "step",
                           "budget_guard"):
            rows.append({"t_offset_s": round(e["ts"] - t0, 1),
                         "ev": e["ev"],
                         **{k: v for k, v in e.items()
                            if k not in ("ev", "ts", "run")}})
    return rows


def load_budgets(path=BUDGETS_PATH, table="steady_ms"):
    """One per-family budget table (default the steady one).  The
    budgets file became two-table in the compile-once PR
    (``{"steady_ms": ..., "first_warm_ms": ...}``); a flat legacy file
    is read as the steady table so old records keep rendering."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if isinstance(doc.get("steady_ms"), dict):
        return doc.get(table) or {}
    return doc if table == "steady_ms" else {}


def compile_cache_table(events):
    """The compile-once read-out: ``{"status", "rows", "totals"}`` from
    a run's cache events.  ``status`` is the last ``compile_cache``
    enable event (dir/persistent); ``rows`` is one entry per
    compile — the dry-run body's per-family ``compile`` events and the
    chokepoint's ``compile`` span_ends (utils/compile_cache) — each
    carrying ``cache: hit|miss|disabled``; ``totals`` counts rows by
    verdict.  Empty rows/None status on pre-compile-cache ledgers."""
    status = None
    rows = []
    totals = {}
    for e in events:
        row = None
        if e.get("ev") == "compile_cache":
            status = {k: v for k, v in e.items()
                      if k not in ("ev", "ts", "run")}
        elif e.get("ev") == "compile":
            row = {"where": e.get("family") or e.get("fn"),
                   "phase": e.get("phase"), "cache": e.get("cache"),
                   "ms": e.get("measured_ms"),
                   "hits": e.get("hits"), "misses": e.get("misses")}
        elif e.get("ev") == "span_end" and e.get("name") == "compile":
            row = {"where": e.get("fn"), "phase": "aot",
                   "cache": e.get("cache"), "ms": e.get("wall_ms")}
        if row is not None:
            rows.append(row)
            totals[row["cache"]] = totals.get(row["cache"], 0) + 1
    return {"status": status, "rows": rows, "totals": totals}


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.1f}"
    return str(v)


def _protocol_metrics_section(events):
    """The "Protocol metrics" lines, rendered by the diff tool's ONE
    implementation (tools/ledger_diff.render_protocol_metrics) so the
    report and the cross-run gate can never disagree about what a
    ``round_metrics`` event means."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from ledger_diff import render_protocol_metrics
    finally:
        sys.path.pop(0)
    return render_protocol_metrics(events)


def _serving_section(events):
    """The "Serving batches" lines, rendered by the batching tool's ONE
    implementation (tools/batching_report.render_serving_section — the
    rpc/batcher ``batch`` event schema has exactly one reader).  Empty
    for runs with no serving telemetry."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from batching_report import render_serving_section
    finally:
        sys.path.pop(0)
    return render_serving_section(events)


def _trace_section(events):
    """The "Request traces" lines, rendered by the trace tool's ONE
    implementation (tools/trace_report.render_trace_section — the
    ``request_trace`` waterfall join has exactly one reader).  Empty
    for runs with no trace-bearing events."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from trace_report import render_trace_section
    finally:
        sys.path.pop(0)
    return render_trace_section(events)


def _cost_section(events):
    """The "Executable costs" lines, rendered by the cost tool's ONE
    implementation (tools/cost_report.render_cost_section — the
    ``xla_compile``/``budget_xcheck`` attribution schema has exactly
    one reader).  Empty for runs with no attribution events."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from cost_report import render_cost_section
    finally:
        sys.path.pop(0)
    return render_cost_section(events)


def check_health(events):
    """Ledger-health problems for the ``--check`` CI gate: a run whose
    evidence cannot be trusted mechanically.  Flags (a) a missing
    provenance line — numbers with no commit/toolchain attribution —
    and (b) unclosed spans: the writer died or wedged inside them
    (exactly what the flight recorder exists to show, and exactly what
    a green CI artifact must not contain)."""
    problems = []
    if not any(e.get("ev") == "provenance" for e in events):
        problems.append("no provenance line (run_id/git_commit/"
                        "captured) — pre-ledger file or torn before "
                        "first fsync")
    unclosed = [n["name"] for _, n in span_tree(events) if n["unclosed"]]
    for name in unclosed:
        problems.append(f"unclosed span {name!r} — the run was killed "
                        "or wedged inside it")
    return problems


def render_markdown(events, budgets=None, title=None,
                    trace_events=None):
    """``trace_events`` overrides the event set the "Request traces"
    section joins over: the waterfall halves are written by DIFFERENT
    processes (router run + replica runs in one multi-writer file), so
    a run-filtered view would render every trace incomplete — main()
    passes the whole file.  None = join the same events as the rest of
    the report (single-writer ledgers)."""
    budgets = load_budgets() if budgets is None else budgets
    out = []
    prov = next((e for e in events if e.get("ev") == "provenance"), None)
    rt = next((e for e in events if e.get("ev") == "runtime"), None)
    out.append(f"# {title or 'Run ledger report'}")
    out.append("")
    if prov:
        out.append(f"- run `{prov.get('run_id')}` captured "
                   f"{prov.get('captured')} at commit "
                   f"`{(prov.get('git_commit') or 'unknown')[:12]}` "
                   f"(jax {prov.get('jax_version')}, "
                   f"python {prov.get('python')})")
        out.append(f"- argv: `{' '.join(prov.get('argv', []))}`")
    else:
        out.append("- **no provenance line** (pre-ledger file or torn "
                   "before first fsync)")
    if rt:
        out.append(f"- backend `{rt.get('backend')}`, "
                   f"{rt.get('device_count')} device(s) "
                   f"({rt.get('device_kind')})")
    out.append("")

    fams = family_table(events)
    if fams:
        out.append("## Per-family dry-run walls (ms)")
        out.append("")
        decomp = any("steady_exec_ms" in r for r in fams.values())
        hdr = ["family", "first_ms", "steady_ms", "budget_ms",
               "headroom_ms"]
        if decomp:
            hdr += ["steady_exec_ms", "init_build_ms",
                    "driver_overhead_ms"]
        out.append("| " + " | ".join(hdr) + " |")
        out.append("|" + "---|" * len(hdr))
        for fam, row in fams.items():
            budget = budgets.get(fam)
            cells = [fam, _fmt(row.get("first_ms", "")),
                     _fmt(row.get("steady_ms", "")),
                     _fmt(budget) if budget is not None else "-",
                     _fmt(budget - row["steady_ms"])
                     if budget is not None and "steady_ms" in row else "-"]
            if decomp:
                cells += [_fmt(row[k]) if k in row else "-"
                          for k in ("steady_exec_ms", "init_build_ms",
                                    "driver_overhead_ms")]
            out.append("| " + " | ".join(cells) + " |")
        out.append("")
        guard = [e for e in events if e.get("ev") == "budget_guard"]
        if guard:
            g = guard[-1]
            verdict = ("**green**" if g.get("ok") else
                       f"**TRIPPED**: {g.get('over') or g.get('missing')}")
            out.append(f"Budget guard (tools/dryrun_budgets.json): "
                       f"{verdict}.")
            out.append("")

    cache = compile_cache_table(events)
    if cache["status"] or cache["rows"]:
        out.append("## Compile cache")
        out.append("")
        st = cache["status"]
        if st:
            out.append(f"- cache dir `{st.get('dir')}` "
                       f"(persistent={st.get('persistent')})")
        if cache["totals"]:
            out.append("- compiles by verdict: " + ", ".join(
                f"{k}={v}" for k, v in sorted(cache["totals"].items(),
                                              key=lambda kv: str(kv[0]))))
        if cache["rows"]:
            out.append("")
            out.append("| where | phase | cache | ms |")
            out.append("|---|---|---|---|")
            for r in cache["rows"]:
                out.append(f"| {r['where']} | {r.get('phase') or '-'} "
                           f"| {r['cache']} "
                           f"| {_fmt(r['ms']) if r.get('ms') is not None else '-'} |")
        out.append("")

    out.extend(_protocol_metrics_section(events))
    out.extend(_serving_section(events))
    out.extend(_trace_section(events if trace_events is None
                              else trace_events))
    out.extend(_cost_section(events))

    tree = span_tree(events)
    if tree:
        out.append("## Span tree")
        out.append("")
        for depth, n in tree:
            pad = "  " * depth
            if n["unclosed"]:
                out.append(f"{pad}- `{n['name']}` — **unclosed** (run "
                           "killed/wedged inside this span)")
            else:
                flag = "" if n.get("ok", True) else " **[raised]**"
                out.append(f"{pad}- `{n['name']}` — "
                           f"{n['wall_ms']:.1f} ms{flag}")
        out.append("")

    mem = memory_high_water(events)
    out.append("## Device memory high-water")
    out.append("")
    if mem:
        for k, v in sorted(mem.items()):
            out.append(f"- {k}: {v:,} bytes")
    else:
        out.append("- no device memory snapshots in this run (CPU "
                   "backends report none)")
    out.append("")

    probes = probe_timeline(events)
    if probes:
        out.append("## Event timeline")
        out.append("")
        out.append("| t+s | event | detail |")
        out.append("|---|---|---|")
        for r in probes:
            detail = ", ".join(f"{k}={_fmt(v) if isinstance(v, float) else v}"
                               for k, v in r.items()
                               if k not in ("t_offset_s", "ev")
                               and not isinstance(v, (dict, list)))
            out.append(f"| {r['t_offset_s']} | {r['ev']} | {detail} |")
        out.append("")

    counters = {}
    for e in events:
        if e.get("ev") == "counter":
            counters[e["name"]] = e.get("total")
    if counters:
        out.append("## Counters (final totals)")
        out.append("")
        for k, v in sorted(counters.items()):
            out.append(f"- {k}: {v}")
        out.append("")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("ledger", help="path to a telemetry JSONL ledger")
    ap.add_argument("--run", default="last",
                    help="run id to render (default: the newest run in "
                         "the file)")
    ap.add_argument("--all-runs", action="store_true",
                    help="render every run in the file, newest last")
    ap.add_argument("--budgets", default=BUDGETS_PATH,
                    help="per-family steady budget JSON for the delta "
                         "column (default: tools/dryrun_budgets.json)")
    ap.add_argument("-o", "--out", default=None,
                    help="write markdown here instead of stdout")
    ap.add_argument("--check", action="store_true",
                    help="ledger-health gate: exit 1 (no render) on "
                         "unclosed spans or missing provenance — for "
                         "CI (checks every run with --all-runs, else "
                         "the selected one)")
    args = ap.parse_args(argv)

    all_events = load_ledger(args.ledger)

    def run_events(r):
        return [e for e in all_events if e.get("run") == r]

    def selected_run(rs):
        """args.run resolved against the one parse (the load_ledger
        run= semantics, without a second full read of the file) via
        the diff tool's ONE resolver, so an unknown explicit id is an
        ERROR here too — never an empty selection that --check would
        misdiagnose as a torn/pre-ledger file."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            from ledger_diff import resolve_run_id
        finally:
            sys.path.pop(0)
        return resolve_run_id(rs, args.run, args.ledger,
                              tool="telemetry_report")

    if args.check:
        problems = []
        rs = runs(all_events)
        if not rs:
            problems += check_health(all_events)
        elif args.all_runs:
            for r in rs:
                problems += [f"run {r}: {p}"
                             for p in check_health(run_events(r))]
        else:
            problems = check_health(run_events(selected_run(rs)))
        name = os.path.basename(args.ledger)
        if problems:
            for p in problems:
                print(f"FAIL {name}: {p}", file=sys.stderr)
            return 1
        print(f"{name}: ledger health OK")
        return 0
    budgets = load_budgets(args.budgets)
    name = os.path.basename(args.ledger)
    if args.all_runs:
        # per-run parts suppress the trace section (trace_events=[]):
        # the halves of one waterfall live in different writers' runs,
        # so the join is rendered ONCE over the whole file instead
        parts = [render_markdown(
            [e for e in all_events if e.get("run") == r], budgets,
            title=f"{name} — run {r}", trace_events=[])
            for r in runs(all_events)]
        traces = _trace_section(all_events)
        if traces:
            parts.append("\n".join(
                [f"# {name} — cross-run trace join", ""] + traces))
        doc = "\n\n".join(parts)
    else:
        rs = runs(all_events)
        events = run_events(selected_run(rs)) if rs else all_events
        if not events:
            print(f"no events for run {args.run!r} in {args.ledger}",
                  file=sys.stderr)
            return 1
        doc = render_markdown(events, budgets, title=name,
                              trace_events=all_events)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")
    else:
        print(doc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
