"""Whole runs of the harness on the CPU at small sizes: past its look for
a chip, every cell's run checks out correct, and with the timed path
broken underneath (faults.py) the same run comes out not correct."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import faults
import harness

SMALL = {"bcast10m.partition": 6000, "bcast10m.partition4": 6000,
         "mr10m.seeds": 5000}


def run(name, seed=11, trace=False, monkeypatch=None):
    if name == "mr10m.seeds":
        import fused_on_cpu
        fused_on_cpu.patch(monkeypatch)
    cell = harness.Cell(name, n=SMALL[name])
    return harness.run_cell(cell, seed, 0.3, trace, time.perf_counter(),
                            allow_cpu=True)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_and_checks_out(name, monkeypatch):
    res = run(name, monkeypatch=monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"sims_per_s", "node_rounds_per_s",
                                   "setup_s"}
    assert res["device"]["count"] >= 1


def test_traced_run_reads_the_host_layers(monkeypatch):
    res = run("bcast10m.partition", trace=True, monkeypatch=monkeypatch)
    assert res["correct"] is True
    # off the chip there are no device planes: the device readers find
    # nothing and say nothing, the host-side readers read
    assert {"entry_ms_per_sim", "compile_ms_per_sim",
            "backend_compiles_per_sim",
            "steady_ms_per_round"} <= set(res["metrics"])
    assert "round_roofline" not in res["metrics"]
    assert res["device"]["busy_s"] == 0.0
    assert res["device"]["window_s"] > 0.0


CASES = [("bcast10m.partition", "state_unchanged"),
         ("bcast10m.partition", "half_left_out"),
         ("bcast10m.partition", "answer_altered"),
         ("bcast10m.partition4", "state_unchanged"),
         ("bcast10m.partition4", "half_left_out"),
         ("bcast10m.partition4", "exchange_left_out"),
         ("bcast10m.partition4", "answer_altered")]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    faults.ALL[fault](monkeypatch)
    res = run(name, seed=12, monkeypatch=monkeypatch)
    assert res["correct"] is False, res["checks"]


def command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "bcast10m.partition", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = command(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = command(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_benchmark_file_keeps_the_contract():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.expect["engine"]
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
