"""Per simulation, the program's gossip.lower spans: tracing and
lowering the compiled loop at the AOT chokepoint, which every call pays
(program span, on the profiler's clock)."""

import program_trace


def read(run):
    keys = program_trace.of(run)
    if not keys or "gossip.lower" not in keys["span_s"] or not run.sims:
        return None
    return 1e3 * keys["span_s"]["gossip.lower"] / len(run.sims)
