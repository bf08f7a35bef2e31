"""The serf10m.partition cell, and bcast10m under the seeds traffic
through the single-rumor fused kernel, on the CPU at small sizes: whole
harness runs check out correct, the plain references agree with
run_simulation exactly, the control differs, and each planted fault of
faults_rumor.py comes out not correct."""

import time

import pytest

import faults_rumor
import harness

SMALL = {"serf10m.partition": 6000, "bcast10m.seeds": 6000}


def cell_of(name, n):
    """``bcast10m.seeds`` is no benchmark cell (PERF.md §7 #2: one fused
    compile per seed leaves too few simulations a window for a steady
    rate), so its reference is checked on a workload made here."""
    if name != "bcast10m.seeds":
        return harness.Cell(name, n=n)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["workloads"].append({"name": name, "config": "bcast10m",
                               "traffic": "seeds", "chips": 1})
    return harness.Cell(name, bench=bench, n=n)


def run(name, monkeypatch, seed=11):
    if name == "bcast10m.seeds":
        import fused_bitmap_on_cpu
        fused_bitmap_on_cpu.patch(monkeypatch)
    cell = cell_of(name, SMALL[name])
    return harness.run_cell(cell, seed, 0.3, False, time.perf_counter(),
                            allow_cpu=True)


def answers(cell, seeds):
    backend = harness.import_program()
    out = []
    for s in seeds:
        rep, err = harness.simulate(backend, cell, s)
        assert err is None, err
        assert harness.engine_mismatch(cell, rep) is None
        out.append(rep)
    return out


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_and_checks_out(name, monkeypatch):
    res = run(name, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"sims_per_s", "node_rounds_per_s",
                                   "setup_s"}


def test_rumor_reference_matches_run_simulation():
    cell = harness.Cell("serf10m.partition", n=6000)
    seeds = [harness.sim_seed(7, i) for i in range(3)]
    reps = answers(cell, seeds)
    assert reps[0]["meta"]["engine"] == "rumor-xla"
    mod = harness.reference_for(reps[0])
    ref = mod.make(cell.cfg, cell.fault)
    ctl = mod.control(cell.cfg, cell.fault, cell.traffic["control"])
    for s, rep in zip(seeds, reps):
        a = ref(s)
        assert rep["rounds"] > 0
        assert (a["rounds"], a["msgs"]) == (rep["rounds"], rep["msgs"])
        assert abs(a["coverage"] - rep["coverage"]) * a["denom"] < 0.01
        assert a["count"] == round(rep["coverage"] * a["denom"])
        assert ctl(s)["msgs"] != rep["msgs"]


@pytest.mark.parametrize("fault", sorted(faults_rumor.ALL))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    faults_rumor.ALL[fault](monkeypatch)
    res = run("serf10m.partition", monkeypatch, seed=12)
    assert res["correct"] is False, res["checks"]


def test_node_packed_reference_matches_run_simulation(monkeypatch):
    import fused_bitmap_on_cpu
    fused_bitmap_on_cpu.patch(monkeypatch)
    cell = cell_of("bcast10m.seeds", 6000)
    seeds = [harness.sim_seed(9, i) for i in range(2)]
    reps = answers(cell, seeds)
    assert reps[0]["meta"]["layout"] == "node-packed bitmap"
    mod = harness.reference_for(reps[0])
    ref = mod.make(cell.cfg, cell.fault)
    ctl = mod.control(cell.cfg, cell.fault, cell.traffic["control"])
    for s, rep in zip(seeds, reps):
        a = ref(s)
        assert (a["rounds"], a["msgs"]) == (rep["rounds"], rep["msgs"])
        assert abs(a["coverage"] - rep["coverage"]) * a["denom"] < 0.01
        assert ctl(s)["coverage"] != rep["coverage"]
