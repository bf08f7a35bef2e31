"""Mean meta.compile_s of the window's reports: the lower and the compile
or cache load each run_simulation call pays (utils/trace.aot_timed)."""


def read(run):
    vals = [s.report["meta"]["compile_s"] for s in run.sims
            if s.report is not None and "compile_s" in s.report["meta"]]
    return 1e3 * sum(vals) / len(vals) if vals else None
