"""Share of the window's reports whose executable the compile layer's
in-process memo served (the report's meta.exec_memo, a program counter
of utils/compile_cache); nothing where no report carries the field."""


def read(run):
    flags = [s.report["meta"]["exec_memo"] for s in run.sims
             if s.report is not None
             and "exec_memo" in s.report.get("meta", {})]
    return sum(map(bool, flags)) / len(flags) if flags else None
