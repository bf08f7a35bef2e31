"""Share of the push rounds' HBM floor in the device's busy time.

The floor counts the algorithm's bytes, not an implementation's.  Each
(node, rumor) pair holds b = 2 + ceil(log2(rumor_k + 1)) bits: seen,
hot and the transmit counter.  Every executed round reads and writes
each pair's state, and each push that passed the drop coin and the cut
(the report's msgs) reads and writes one receiver seen bit per rumor:

    rounds x 2 x n x R x b / 8  +  msgs x R x 2 / 8  bytes,

summed over the window's simulations, over the chips' HBM bandwidth
(peaks.json).  The busy time is the union of device operations in the
traced window, averaged over the chips.  Bound by HBM bytes; it reads
the same whatever implements the round."""

import math


def floor_bytes(n, rumors, rumor_k, rounds, msgs):
    """HBM bytes the push rounds of one simulation need at least."""
    b = 2 + math.ceil(math.log2(rumor_k + 1))
    return rounds * 2 * n * rumors * b / 8 + msgs * rumors * 2 / 8


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    bw = run.peaks.get("hbm_bytes_per_s")
    p = run.cell.cfg["protocol"]
    if not bw or "rumor_k" not in p:
        return None
    done = [s for s in run.sims if s.report is not None]
    if not done:
        return None
    total = sum(floor_bytes(run.cell.n, p["rumors"], p["rumor_k"],
                            run.rounds_executed(s), s.report["msgs"])
                for s in done)
    floor_s = total / (run.cell.chips * bw)
    return 100.0 * floor_s / run.trace["busy_s"]
