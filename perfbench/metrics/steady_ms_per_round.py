"""Sum of the reports' meta.steady_wall_s over the rounds they executed:
the compiled round loop alone, host clock with block_until_ready."""


def read(run):
    done = [s for s in run.sims if s.report is not None
            and "steady_wall_s" in s.report["meta"]]
    rounds = sum(run.rounds_executed(s) for s in done)
    if not rounds:
        return None
    return 1e3 * sum(s.report["meta"]["steady_wall_s"] for s in done) / rounds
