"""Share of the round's HBM floor in the device's busy time per round.

The floor counts the algorithm's bytes, not an implementation's: each
round reads every node's own state, reads fanout partners' state and
writes the state back, (2 + fanout) * n * rumors bits, over the chips'
HBM bandwidth (peaks.json).  The busy time is the union of device
operations in the traced window, averaged over the chips, per round the
window's simulations executed.  Bound by HBM bytes; it reads the same
whatever implements the round."""


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    bw = run.peaks.get("hbm_bytes_per_s")
    rounds = sum(run.rounds_executed(s) for s in run.sims
                 if s.report is not None)
    if not bw or not rounds:
        return None
    p = run.cell.cfg["protocol"]
    floor_bytes = (2 + p["fanout"]) * run.cell.n * p["rumors"] / 8
    floor_s = floor_bytes / (run.cell.chips * bw)
    return 100.0 * floor_s / (run.trace["busy_s"] / rounds)
