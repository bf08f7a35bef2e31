"""Closed loop: one client runs simulations back to back, as a seed study
does.  The next simulation starts when the previous one returns; none
starts once the window's seconds are spent."""


def run_window(one, seconds):
    """``one(i)`` runs the window's i-th simulation and returns its record
    (``t1``: seconds from the window's start to its completion)."""
    sims = []
    while True:
        sims.append(one(len(sims)))
        if sims[-1].t1 >= seconds:
            return sims
