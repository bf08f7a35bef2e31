"""The plain references against run_simulation at small sizes on the CPU:
the answers the benchmark compares (rounds, msgs, coverage) agree exactly,
and each control differs from them."""

import pytest

import harness


def answers(cell, seeds):
    backend = harness.import_program()
    out = []
    for s in seeds:
        rep, err = harness.simulate(backend, cell, s)
        assert err is None, err
        assert harness.engine_mismatch(cell, rep) is None
        out.append(rep)
    return out


@pytest.mark.parametrize("name", ["bcast10m.partition",
                                  "bcast10m.partition4"])
def test_packed_reference_matches_run_simulation(name):
    cell = harness.Cell(name, n=6000)
    seeds = [harness.sim_seed(7, i) for i in range(3)]
    reps = answers(cell, seeds)
    ref = harness.reference_for(reps[0]).make(cell.cfg, cell.fault)
    for s, rep in zip(seeds, reps):
        a = ref(s)
        assert (a["rounds"], a["msgs"]) == (rep["rounds"], rep["msgs"])
        # float32 coverage: the engine divides as XLA fuses it, the
        # reference as it writes it; they may part by an ulp or two
        assert abs(a["coverage"] - rep["coverage"]) * a["denom"] < 0.01
        assert a["count"] == round(rep["coverage"] * a["denom"])


def test_partition_control_differs():
    cell = harness.Cell("bcast10m.partition", n=6000)
    seeds = [harness.sim_seed(8, i) for i in range(3)]
    reps = answers(cell, seeds)
    mod = harness.reference_for(reps[0])
    ctl = mod.control(cell.cfg, cell.fault, cell.traffic["control"])
    for s, rep in zip(seeds, reps):
        a = ctl(s)
        assert a["msgs"] != rep["msgs"]


def test_fused_reference_matches_run_simulation(monkeypatch):
    import fused_on_cpu
    fused_on_cpu.patch(monkeypatch)
    cell = harness.Cell("mr10m.seeds", n=5000)
    seeds = [harness.sim_seed(9, i) for i in range(2)]
    reps = answers(cell, seeds)
    mod = harness.reference_for(reps[0])
    ref = mod.make(cell.cfg, cell.fault)
    for s, rep in zip(seeds, reps):
        a = ref(s)
        assert (a["rounds"], a["msgs"]) == (rep["rounds"], rep["msgs"])
        assert abs(a["coverage"] - rep["coverage"]) * a["denom"] < 0.01
