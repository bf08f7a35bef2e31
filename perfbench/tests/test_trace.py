"""The trace reduction and the metric readers: on a small trace recorded
on the CPU (the XLA CPU client's thread stands in for a device plane),
on hand-made intervals whose answers are known, and on a fake report."""

import os

import pytest

import harness

tr = harness.load_module("trace.py")
DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace")


def cpu_ops(planes):
    """The XLA CPU client thread's operations, as device 0's."""
    out = []
    for plane in planes:
        if plane["name"] == "/host:CPU":
            for line in plane["lines"]:
                if line["name"].startswith("tf_XLAPjRtCpuClient"):
                    out += [e for e in line["events"] if e[2] > e[1]
                            and not e[0].startswith(("Threadpool", "end:"))]
    return {0: out}


def test_recorded_cpu_trace_reduces():
    planes = tr.load(DATA)
    red = tr.reduce(planes, devices=[0], ops_of=cpu_ops,
                    async_of=lambda p: {})
    ops = cpu_ops(planes)[0]
    assert ops, "the recorded trace holds the CPU client's operations"
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] <= sum(e - s for _, s, e in ops) * 1e-9
    assert {n for n, _ in red["device_ops"]} <= {n for n, _, _ in ops}
    assert red["idle_gaps"] and all(g[1] > 0 for g in red["idle_gaps"])
    # between the operations the host was inside the harness's spans
    names = {g[0] for g in red["idle_gaps"]}
    assert names & {"sim", "window"} or any("Pjit" in n or "Execute" in n
                                            for n in names)
    idle = red["window_s"] - red["busy_s"]
    assert sum(g[1] for g in red["idle_gaps"]) <= idle + 1e-12


def test_union_and_holes_on_known_intervals():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert tr.union(evs) == [(0, 15), (20, 30)]
    assert tr.holes(tr.union(evs), -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("perfbench.window", 0, 100), ("perfbench.sim", 0, 50),
            ("perfbench.sim", 50, 100), ("backend_compile", 55, 95)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("%while.3 = (u32[8]) while(%x)", 0, 50),
                ("%fusion = u32[8] fusion(%all-reduce.1)", 0, 40),
                ("%all-gather = u32[8] all-gather(%y)", 30, 50)]},
            {"name": "Async XLA Ops", "events": [
                ("%all-reduce-start.2 = u32[8] all-reduce-start(%z)", 10,
                 30)]},
            {"name": "XLA Modules", "events": [("jit_loop", 0, 50)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [("fusion", 0, 20)]}]}]
    red = tr.reduce(planes, devices=[0, 1])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((50 + 20) / 2 * 1e-9)
    ops = dict(red["device_ops"])
    assert ops["fusion"] == pytest.approx(30e-9)
    assert ops["all-gather"] == pytest.approx(10e-9)
    assert "while.3" not in ops
    assert red["async_op_s"] == {"all-reduce-start.2": pytest.approx(10e-9)}
    assert red["idle_gaps"] == [["backend_compile", pytest.approx(50e-9)]]


class FakeCell:
    n = 1000
    chips = 1
    cfg = {"protocol": {"fanout": 1, "rumors": 8},
           "run": {"target_coverage": 0.99, "max_rounds": 128}}


def fake_run(trace):
    sims = [harness.Sim(i, 100 + i, 2.0 * i, 2.0 * i + 2.0,
                        {"rounds": 30, "coverage": 0.995, "msgs": 6e4,
                         "wall_s": 1.5,
                         "meta": {"compile_s": 0.5, "steady_wall_s": 0.9}},
                        None) for i in range(3)]
    return harness.Run(FakeCell(), sims, 12.0, 3, trace,
                       {"hbm_bytes_per_s": 1e9})


def read(name, run):
    return harness.load_module("metrics", name + ".py").read(run)


def test_readers_on_a_fake_report():
    run = fake_run({"busy_s": 0.9, "window_s": 6.0, "op_s": {
        "all-gather.3": 0.09, "fusion": 0.8}})
    assert read("sims_per_s", run) == pytest.approx(3 / 6.0)
    assert read("node_rounds_per_s", run) == pytest.approx(3 * 30 * 1000 / 6)
    assert read("setup_s", run) == 12.0
    assert read("entry_ms_per_sim", run) == pytest.approx(500.0)
    assert read("compile_ms_per_sim", run) == pytest.approx(500.0)
    assert read("backend_compiles_per_sim", run) == pytest.approx(1.0)
    assert read("steady_ms_per_round", run) == pytest.approx(2700 / 90)
    # floor: 3 * 1000 * 8 / 8 bytes = 3000 B at 1e9 B/s = 3 us per round;
    # busy per round 0.9 s / 90 rounds = 10 ms
    assert read("round_roofline", run) == pytest.approx(100 * 3e-6 / 1e-2)
    assert read("device_idle_pct", run) == pytest.approx(85.0)
    assert read("collective_ms_per_round", run) == pytest.approx(1.0)


def test_readers_find_nothing_without_a_trace():
    run = fake_run(None)
    for name in ("round_roofline", "device_idle_pct",
                 "collective_ms_per_round"):
        assert read(name, run) is None


def test_backend_compiles_are_counted():
    import jax
    import numpy as np
    counter = harness.CompileCounter()
    before = counter.count
    for k in (3, 5):
        jax.jit(lambda x: x * k + 1)(np.ones((7, k))).block_until_ready()
    assert counter.count - before == 2
