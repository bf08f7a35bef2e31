"""bench.py scoreboard-line contract (VERDICT r2 item 9).

The bench measures on a TPU or not at all: with no chip it exits
nonzero and prints no line, so no CPU number can ever be read under the
device metric.  ``vs_baseline`` is only ever computed for a TPU line.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# repo-root module, not a package member: load by path so collection
# works from any cwd (same pattern as test_backend_cli_rpc.py)
_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(_REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_tpu_measurement_carries_vs_baseline():
    line = bench.measurement_line(
        rate=3.2e9, backend="tpu", n=10_000_000,
        variant="fused-pallas pull SI", rounds=26, dt=0.077)
    assert line["backend"] == "tpu"
    assert line["vs_baseline"] == round(
        3.2e9 / bench.BASELINE_NODE_ROUNDS_PER_SEC_PER_CHIP, 4)
    assert line["metric"] == "node_rounds_per_sec_per_chip"


def test_line_is_json_serializable_and_flat():
    line = bench.measurement_line(1.0, "tpu", 10, "x", 1, 1.0)
    parsed = json.loads(json.dumps(line))
    assert set(parsed) == {"metric", "value", "unit", "vs_baseline",
                           "backend"}


def test_line_carries_compile_s():
    """The flagship loop's compile wall rides the line as set-up time,
    beside the rate and never inside it; absent when not measured."""
    line = bench.measurement_line(1.0, "tpu", 10, "x", 1, 1.0,
                                  compile_s=9.31)
    assert json.loads(json.dumps(line))["compile_s"] == 9.31
    assert "compile_s" not in bench.measurement_line(
        1.0, "tpu", 10, "x", 1, 1.0)


def test_compile_timed_measures_a_real_compile():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(256, dtype=jnp.float32)
    compiled, compile_s = bench._compile_timed(
        jax.jit(lambda v: jnp.cumsum(v * 2.0)), x)
    assert compile_s > 0
    assert float(compiled(x)[-1]) > 0


def test_line_carries_churn_families():
    """Traced-operand PR: the nemesis families (churn_heal +
    churn_sweep with its first/warm amortization split) ride the
    scoreboard line as an optional ``families`` object and survive the
    JSON trip; absent when the body did not measure them."""
    fam = {"churn_heal": {"n": 100_000, "rounds": 23,
                          "wall_ms": 4200.0,
                          "node_rounds_per_sec": 5.4e5},
           "churn_sweep": {"k": 8, "n": 8192, "first_ms": 3000.0,
                           "warm_ms": 500.0, "amortization": 6.0,
                           "converged": 8}}
    line = bench.measurement_line(1.0, "tpu", 10, "x", 1, 1.0,
                                  families=fam)
    assert json.loads(json.dumps(line))["families"] == fam
    assert "families" not in bench.measurement_line(
        1.0, "tpu", 10, "x", 1, 1.0)


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_refuses_without_a_tpu(script):
    """No CPU path: under JAX_PLATFORMS=cpu both on-chip entry points
    exit nonzero and print nothing on stdout — no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(_REPO, script)],
                       capture_output=True, text=True, env=env,
                       timeout=300, cwd=_REPO)
    assert p.returncode != 0, p.stdout
    assert p.stdout.strip() == "", p.stdout
    assert "needs a TPU" in p.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo
    must fail too (the driver's sealed-copy check), whatever the
    platform."""
    import shutil
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, env=env,
                       timeout=300, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
