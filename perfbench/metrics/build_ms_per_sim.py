"""Per simulation, the program's gossip.build spans: topology, step and
table construction, schedule tables, initial state and masks (program
span, on the profiler's clock)."""

import program_trace


def read(run):
    keys = program_trace.of(run)
    if not keys or "gossip.build" not in keys["span_s"] or not run.sims:
        return None
    return 1e3 * keys["span_s"]["gossip.build"] / len(run.sims)
