"""Sum of n x rounds-to-target over the simulations that reached their
target, over the same time as sims_per_s: the north-star rate, summed
over the cell's chips (host clock)."""


def read(run):
    end = max((s.t1 for s in run.sims), default=0.0)
    work = sum(run.cell.n * s.report["rounds"] for s in run.ok)
    return work / end if end > 0 else None
