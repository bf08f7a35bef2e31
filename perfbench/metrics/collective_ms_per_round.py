"""Device time of the collective operations per round executed, averaged
over the chips: the node-sharded exchange of packed planes and the
coverage and message reductions over ICI.  The TPU compiler lowers the
exchange's all_gather to an all-reduce, so every collective kind counts."""

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace["op_s"].items()
            if any(kind in k for kind in KINDS))
    rounds = sum(run.rounds_executed(s) for s in run.sims
                 if s.report is not None)
    if not t or not rounds:
        return None
    return 1e3 * t / rounds
