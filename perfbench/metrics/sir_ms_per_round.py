"""Device time per round executed of the ops under the gossip.sir scope:
the rumor-mongering counters, the removal test and the hot and seen
update, averaged over the chips (device trace)."""

import program_trace

SCOPE = "gossip.sir"


def read(run):
    keys = program_trace.of(run)
    if not keys or SCOPE not in keys["scope_s"]:
        return None
    rounds = sum(run.rounds_executed(s) for s in run.sims
                 if s.report is not None)
    return 1e3 * keys["scope_s"][SCOPE] / rounds if rounds else None
