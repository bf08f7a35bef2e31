"""Faults planted in the rumor-mongering engine under test, to show that
``correct`` catches each on the ``serf10m`` cells (the benchmark's tests,
and this module's command on the chip).  Each takes pytest's
``monkeypatch`` (or anything with its ``setattr``) and breaks the timed
path underneath the harness.

    python3 perfbench/faults_rumor.py --workload serf10m.partition

runs, in one process, set-up as a benchmark run and then two simulations
under each fault, checked as a run checks them; one JSON line per fault.
"""

import argparse
import json
import sys

import jax.numpy as jnp

import faults


def _wrap_push(monkeypatch, wrap):
    from gossip_tpu.models import rumor
    monkeypatch.setattr(rumor, "push_delta", wrap(rumor.push_delta))


def push_lost(monkeypatch):
    """Every push is sent and counted, and none is delivered."""
    _wrap_push(monkeypatch, lambda real: (
        lambda n, targets, payload: jnp.zeros_like(real(n, targets,
                                                        payload))))


def half_left_out(monkeypatch):
    """The upper half of the nodes drops what is pushed to it."""
    def wrap(real):
        def push(n, targets, payload):
            out = real(n, targets, payload)
            half = jnp.arange(out.shape[0]) >= out.shape[0] // 2
            return jnp.where(half[:, None], jnp.zeros_like(out), out)
        return push
    _wrap_push(monkeypatch, wrap)


ALL = {"push_lost": push_lost, "half_left_out": half_left_out,
       "answer_altered": faults.answer_altered}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=424243)
    a = ap.parse_args(argv)
    import pytest
    import harness
    cell = harness.Cell(a.workload)
    backend = harness.import_program()
    harness.require_chips(cell.chips)
    harness.configure_cache()
    warm, err = harness.simulate(backend, cell, harness.WARM_SEED)
    if warm is None:
        raise RuntimeError(err)
    for name, plant in ALL.items():
        mp = pytest.MonkeyPatch()
        try:
            plant(mp)
            fs = []
            for i in range(2):
                s = harness.sim_seed(a.base_seed, i)
                rep, err = harness.simulate(backend, cell, s)
                fs.append(harness.Sim(i, s, 0.0, 0.0, rep, err))
        finally:
            mp.undo()
        correct, checks = harness.check(
            cell, fs, a.base_seed,
            wanted_sample=[f for f in fs if f.report is not None])
        print(json.dumps({"fault": name, "correct": correct,
                          "checks": {k: v["value"]
                                     for k, v in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
