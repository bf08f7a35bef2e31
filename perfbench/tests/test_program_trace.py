"""The program's spans and device scopes in a trace (program_trace.py)
and the per-layer readers of them: on a small trace recorded on the CPU
(the XLA CPU client's threads stand in for a device plane), on a
hand-built layout whose answers are known, and on a synthetic run."""

import os

import pytest

import harness
import program_trace as pt

tr = pt.tr
DATA = os.path.join(os.path.dirname(__file__), "data")
SPANS = os.path.join(DATA, "cpu_trace_spans")
OLD = os.path.join(DATA, "cpu_trace")
NEW_METRICS = ("build_ms_per_sim", "lower_ms_per_sim",
               "exec_store_ms_per_sim", "unnamed_idle_pct",
               "sample_ms_per_round", "exchange_ms_per_round",
               "coverage_ms_per_round")


def _cpu_lines(planes):
    for plane in planes:
        if plane["name"] == "/host:CPU":
            for line in plane["lines"]:
                if line["name"].startswith("tf_XLA"):
                    yield line


def _is_op(name, s, e):
    return e > s and not name.startswith(("Threadpool", "end:"))


def cpu_ops(planes):
    """The XLA CPU client's operations, every thread's, as device 0's."""
    return {0: [ev for line in _cpu_lines(planes)
                for ev in line["events"] if _is_op(*ev)]}


def cpu_scoped_ops(planes):
    return {0: [(n, scope, s, e) for line in _cpu_lines(planes)
                for (n, s, e), scope in zip(line["events"], line["scopes"])
                if _is_op(n, s, e)]}


def test_recorded_spans_and_scopes_reduce():
    planes = pt.load(SPANS)
    red = pt.reduce(planes, devices=[0], ops_of=cpu_ops,
                    scoped_of=cpu_scoped_ops)
    base = tr.reduce(planes, devices=[0], ops_of=cpu_ops,
                     async_of=lambda p: {})
    # the window held a build span nested in another, a lower span and
    # an execute span
    assert {"gossip.build", "gossip.lower", "gossip.execute"} <= set(
        red["span_s"])
    builds = [(s, e) for n, s, e in tr.host_events(planes)
              if n == "gossip.build"]
    assert len(builds) == 2
    outer = max(builds, key=lambda b: b[1] - b[0])
    assert red["span_s"]["gossip.build"] == pytest.approx(
        (outer[1] - outer[0]) * 1e-9)
    # every op of the loop is under one of the three scopes, and the
    # scopes share out exactly the op totals
    assert {"gossip.sample", "gossip.exchange", "gossip.coverage"} <= set(
        red["scope_s"])
    assert sum(red["scope_s"].values()) == pytest.approx(
        sum(base["op_s"].values()))
    # a 10 ms sleep inside the window but outside every program span
    idle = base["window_s"] - base["busy_s"]
    assert 0.009 < red["unnamed_idle_s"] <= idle


def test_existing_reduce_keys_unchanged_on_the_old_fixture():
    from test_trace import cpu_ops as old_cpu_ops
    kw = dict(devices=[0], ops_of=old_cpu_ops, async_of=lambda p: {})
    assert (tr.reduce(pt.load(OLD), **kw)
            == tr.reduce(tr.load(OLD), **kw))


def test_hlo_protos_name_each_instruction_scope():
    import glob
    [path] = glob.glob(os.path.join(SPANS, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    protos = pt.hlo_protos(path)
    loops = [m for m in protos if m.startswith("jit_loop(")]
    assert loops
    scopes = set(pt.instruction_scopes(protos[loops[0]]).values())
    assert {"gossip.sample", "gossip.exchange",
            "gossip.coverage"} <= scopes
    assert pt.innermost_scope(
        "jit(loop)/while/body/gossip.exchange/jit(f)/gossip.sample/add"
    ) == "gossip.sample"
    assert pt.innermost_scope("jit(loop)/while/body/add") is None


def test_hand_built_layout():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("perfbench.window", 0, 100), ("perfbench.sim", 0, 100),
            ("gossip.build", 0, 30), ("gossip.build", 10, 20),
            ("gossip.lower", 40, 50), ("gossip.execute", 95, 120)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [("%while.1 = u32[8] while(%x)", 30, 40),
                        ("%fusion.2 = u32[8] fusion(%y)", 30, 40),
                        ("%fusion.3 = u32[8] fusion(%y)", 50, 60),
                        ("%add.4 = u32[8] add(%y, %y)", 90, 100)],
             "scopes": [None, "gossip.exchange", "gossip.sample", None]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [("fusion.2", 0, 100)],
             "scopes": ["gossip.exchange"]}]}]
    red = pt.reduce(planes, devices=[0, 1])
    # nested build counted once; the execute span clipped to the window
    assert red["span_s"] == {"gossip.build": pytest.approx(30e-9),
                             "gossip.lower": pytest.approx(10e-9),
                             "gossip.execute": pytest.approx(5e-9)}
    # device 0 idles 0-30 (build), 40-50 (lower), 60-90 (no span):
    # 30 unnamed; device 1 never idles
    assert red["unnamed_idle_s"] == pytest.approx(30 / 2 * 1e-9)
    # the while container counts in neither op_s nor scope_s
    assert red["scope_s"] == {"gossip.exchange": pytest.approx(55e-9),
                              "gossip.sample": pytest.approx(5e-9),
                              "unscoped": pytest.approx(5e-9)}
    assert pt.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


class FakeCell:
    n = 1000
    chips = 1
    cfg = {"protocol": {"fanout": 1, "rumors": 8},
           "run": {"target_coverage": 0.99, "max_rounds": 128}}


def fake_run(trace):
    sims = [harness.Sim(i, 100 + i, 2.0 * i, 2.0 * i + 2.0,
                        {"rounds": 30, "coverage": 0.995, "msgs": 6e4,
                         "wall_s": 1.5, "meta": {}}, None)
            for i in range(3)]
    return harness.Run(FakeCell(), sims, 12.0, 3, trace, {})


def read(name, run):
    return harness.load_module("metrics", name + ".py").read(run)


def test_new_readers_on_a_synthetic_run():
    run = fake_run({"busy_s": 0.9, "window_s": 6.0, "span_s": {
        "gossip.build": 0.3, "gossip.lower": 0.6, "gossip.cache_key": 0.03,
        "gossip.exec_load": 0.9, "gossip.attribution": 0.006,
        "gossip.compile": 1.6}, "scope_s": {
        "gossip.sample": 0.09, "gossip.exchange": 0.72,
        "gossip.coverage": 0.045, "unscoped": 0.045},
        "unnamed_idle_s": 0.12})
    assert read("build_ms_per_sim", run) == pytest.approx(100.0)
    assert read("lower_ms_per_sim", run) == pytest.approx(200.0)
    assert read("exec_store_ms_per_sim", run) == pytest.approx(312.0)
    assert read("unnamed_idle_pct", run) == pytest.approx(2.0)
    # 3 simulations x 30 rounds
    assert read("sample_ms_per_round", run) == pytest.approx(1.0)
    assert read("exchange_ms_per_round", run) == pytest.approx(8.0)
    assert read("coverage_ms_per_round", run) == pytest.approx(0.5)


DEVICE_METRICS = NEW_METRICS[3:]


@pytest.mark.parametrize("trace,silent", [
    (None, NEW_METRICS),
    # a program without spans or scopes (as before they existed)
    ({"busy_s": 0.9, "window_s": 6.0, "span_s": {},
      "scope_s": {"unscoped": 0.9}, "unnamed_idle_s": 5.1}, NEW_METRICS),
    # off the chip: the host spans, but no device planes
    ({"busy_s": 0.0, "window_s": 6.0, "span_s": {"gossip.build": 0.3},
      "scope_s": {}, "unnamed_idle_s": 5.7}, DEVICE_METRICS)])
def test_new_readers_find_nothing_to_read(trace, silent):
    run = fake_run(trace)
    for name in silent:
        assert read(name, run) is None, name


def test_a_trace_that_cannot_be_read_reads_as_nothing(monkeypatch,
                                                       tmp_path):
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))   # no trace there
    run = fake_run({"busy_s": 0.9, "window_s": 6.0})
    assert pt.of(run) is None
    for name in NEW_METRICS:
        assert read(name, run) is None, name
