"""Simulations that reached their target, over the time from the window's
start to the last completion (host clock)."""


def read(run):
    end = max((s.t1 for s in run.sims), default=0.0)
    return len(run.ok) / end if end > 0 else None
