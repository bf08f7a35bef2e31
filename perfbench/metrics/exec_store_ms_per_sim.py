"""Per simulation, the AOT executable store's share of an acquisition:
the gossip.cache_key (HLO text and its hash), gossip.exec_load (read and
deserialize a stored executable), gossip.exec_store (serialize, verify,
write) and gossip.attribution (cost and memory analysis) spans (program
span, on the profiler's clock)."""

import program_trace

SPANS = ("gossip.cache_key", "gossip.exec_load", "gossip.exec_store",
         "gossip.attribution")


def read(run):
    keys = program_trace.of(run)
    if not keys or not run.sims:
        return None
    found = [keys["span_s"][s] for s in SPANS if s in keys["span_s"]]
    return 1e3 * sum(found) / len(run.sims) if found else None
