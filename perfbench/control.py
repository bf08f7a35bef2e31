"""The readings the correctness limits are set from (PERF.md), on the chip.

    python3 perfbench/control.py --workload NAME [--seeds 12]
        [--control-seeds 3] [--faults]

In one process: set-up as a benchmark run, then ``--seeds`` simulations
through run_simulation back to back (the window's own path and sizes),
each checked against the plain reference: the largest gap over them is a
number's lower reading.  Then the traffic's control (the reference with
one guarantee broken) on the first ``--control-seeds`` of them: the
smallest gap it gives is the upper reading.  With ``--faults``, each
planted fault of faults.py that the cell can have, over two simulations
each: the check must come out not correct.  One JSON line per reading;
the benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time


def emit(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=424242)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", action="store_true")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness
    cell = harness.Cell(a.workload)
    backend = harness.import_program()
    harness.require_chips(cell.chips)
    harness.configure_cache()
    t0 = time.perf_counter()
    warm, err = harness.simulate(backend, cell, harness.WARM_SEED)
    if warm is None:
        raise RuntimeError(err)
    emit(phase="setup", s=time.perf_counter() - t0,
         engine=warm["meta"].get("engine"))

    sims = []
    for i in range(a.seeds):
        s = harness.sim_seed(a.base_seed, i)
        t1 = time.perf_counter()
        rep, err = harness.simulate(backend, cell, s)
        sims.append(harness.Sim(i, s, 0.0, time.perf_counter() - t1, rep,
                                err))
        emit(phase="program", seed=s, wall_s=sims[-1].t1, error=err,
             **({k: rep[k] for k in ("rounds", "coverage", "msgs")}
                if rep else {}),
             engine=rep["meta"].get("engine") if rep else None)
    done = [s for s in sims if s.report is not None]
    mod = harness.reference_for(done[0].report)
    ref = mod.make(cell.cfg, cell.fault)
    lower = {}
    for s in done:
        t1 = time.perf_counter()
        gaps = harness.compare([s], ref)
        emit(phase="reference", seed=s.seed, s=time.perf_counter() - t1,
             **gaps)
        for k, v in gaps.items():
            lower[k] = max(lower.get(k, 0.0), v)
    emit(phase="lower", **lower)

    ctl = mod.control(cell.cfg, cell.fault, cell.traffic["control"])
    upper = {}
    for s in done[:a.control_seeds]:
        gaps = harness.compare([s], ctl)
        emit(phase="control", seed=s.seed, **gaps)
        for k, v in gaps.items():
            upper[k] = min(upper.get(k, float("inf")), v)
    emit(phase="upper", control=cell.traffic["control"], **upper)

    if a.faults:
        import pytest
        import faults
        for name, plant in faults.ALL.items():
            if name == "exchange_left_out" and cell.chips == 1:
                continue
            mp = pytest.MonkeyPatch()
            try:
                plant(mp)
                fs = []
                for i in range(2):
                    s = harness.sim_seed(a.base_seed + 1, i)
                    rep, err = harness.simulate(backend, cell, s)
                    fs.append(harness.Sim(i, s, 0.0, 0.0, rep, err))
            finally:
                mp.undo()
            correct, checks = harness.check(cell, fs, a.base_seed,
                                            wanted_sample=[
                                                f for f in fs
                                                if f.report is not None])
            emit(phase="fault", fault=name, correct=correct,
                 checks={k: v["value"] for k, v in checks.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
