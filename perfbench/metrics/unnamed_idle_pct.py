"""Share of the traced window in which the device was idle and no
gossip.* program span was open on the host, averaged over the chips:
the idle time the program's spans do not name (device trace)."""

import program_trace


def read(run):
    keys = program_trace.of(run)
    if (not keys or not keys["span_s"] or not run.trace["busy_s"]
            or not run.trace["window_s"]):
        return None
    return 100.0 * keys["unnamed_idle_s"] / run.trace["window_s"]
