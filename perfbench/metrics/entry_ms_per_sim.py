"""Per simulation, the harness's wall around run_simulation less the
report's own wall_s: topology and state build, schedule lowering,
readback and the report, outside the engine's timed driver call."""


def read(run):
    done = [s for s in run.sims if s.report is not None]
    if not done:
        return None
    return 1e3 * sum(s.wall_s - s.report["wall_s"] for s in done) / len(done)
