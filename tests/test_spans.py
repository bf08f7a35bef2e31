"""Program spans and device scopes (utils/trace.span, jax.named_scope).

* ``span`` puts a ``gossip.*`` event on the profiler's host plane and,
  under an active ledger, the ledger span of the same name; it never
  imports jax itself.
* the AOT chokepoint names each step of an acquisition: ``lower``,
  ``cache_key`` and ``exec_load`` on a hit, ``backend_compile`` and
  ``exec_store`` on a miss; attribution runs only under an active
  ledger or on the first ``last_compile()`` read.
* the packed, sharded-packed and fused loops carry the ``gossip.sample``,
  ``gossip.exchange`` and ``gossip.coverage`` scopes in the op-name
  metadata of their compiled HLO, which is what the profiler reports
  for each device op.
"""

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from gossip_tpu.config import (ChurnConfig, FaultConfig, ProtocolConfig,
                               RunConfig)
from gossip_tpu.topology import generators as G
from gossip_tpu.utils import compile_cache, telemetry
from gossip_tpu.utils import trace as tr

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = (tr.SAMPLE, tr.EXCHANGE, tr.COVERAGE)


@pytest.fixture
def own_ledger(tmp_path):
    led = telemetry.Ledger(str(tmp_path / "led.jsonl"))
    prev = telemetry.activate(led)
    yield led
    telemetry.activate(prev)
    led.close()


def _span_names(led):
    return [e["name"] for e in telemetry.load_ledger(led.path)
            if e["ev"] == "span_end"]


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    logdir = str(tmp_path / "prof")
    jax.profiler.start_trace(logdir)
    try:
        with tr.span("probe") as ext:
            jax.block_until_ready(jnp.arange(8) * 2)
    finally:
        jax.profiler.stop_trace()
    assert ext == {}                 # no active ledger: a throwaway dict
    [path] = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert "gossip.probe" in names


def test_span_opens_the_ledger_span_of_the_same_name(own_ledger):
    with tr.span("probe") as ext:
        ext["k"] = 1
    [end] = [e for e in telemetry.load_ledger(own_ledger.path)
             if e["ev"] == "span_end"]
    assert end["name"] == "probe" and end["k"] == 1 and end["ok"]


def test_span_imports_no_jax():
    code = ("import sys\n"
            "from gossip_tpu.utils import trace\n"
            "with trace.span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'span imported jax'\n")
    subprocess.run([sys.executable, "-c", code], cwd=_REPO, check=True,
                   timeout=60)


def test_chokepoint_spans_on_miss_then_hit(own_ledger, tmp_path,
                                           monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    x = jnp.arange(16.0)
    _, s1 = compile_cache.load_or_compile(jax.jit(lambda v: v * 5), x)
    miss = _span_names(own_ledger)
    _, s2 = compile_cache.load_or_compile(jax.jit(lambda v: v * 5), x)
    hit = _span_names(own_ledger)[len(miss):]
    assert (s1, s2) == ("miss", "hit")
    assert miss == [tr.LOWER, tr.CACHE_KEY, tr.EXEC_LOAD,
                    tr.BACKEND_COMPILE, tr.EXEC_STORE, tr.COMPILE,
                    tr.ATTRIBUTION]
    assert hit == [tr.LOWER, tr.CACHE_KEY, tr.EXEC_LOAD, tr.COMPILE,
                   tr.ATTRIBUTION]


def test_attribution_waits_for_its_reader_under_the_null_ledger(
        monkeypatch):
    assert not telemetry.current().active
    monkeypatch.setenv(compile_cache.ENV_VAR, "")
    calls = []
    real = compile_cache.xla_attribution

    def counted(compiled):
        calls.append(compiled)
        return real(compiled)

    monkeypatch.setattr(compile_cache, "xla_attribution", counted)
    compile_cache.load_or_compile(jax.jit(lambda v: v - 3), jnp.arange(4))
    assert calls == []               # nothing on the acquisition path
    last = compile_cache.last_compile()
    assert len(calls) == 1
    for field in compile_cache.ATTRIBUTION_FIELDS:
        assert field in last, field
    assert last["peak_bytes"] is not None     # CPU reports both analyses
    assert compile_cache.last_compile() is last and len(calls) == 1


def _compiled_loop_text(monkeypatch, drive):
    """The compiled HLO of the loop ``drive`` hands to the chokepoint."""
    seen = {}
    real = tr.maybe_aot_timed

    def capture(jitted, timing, *args, **kw):
        seen["text"] = jitted.lower(*args).compile().as_text()
        return real(jitted, timing, *args, **kw)

    monkeypatch.setattr(tr, "maybe_aot_timed", capture)
    drive()
    return seen["text"]


def _partition_fault(n):
    return FaultConfig(drop_prob=0.02, churn=ChurnConfig(
        events=((1, 1, 4), (2, 2, -1)), partitions=((0, 6, n // 2),),
        ramp=(0, 4, 0.0, 0.1)))


@pytest.mark.parametrize("devices", [1, 4])
def test_packed_loops_carry_the_device_scopes(monkeypatch, devices):
    n = 512
    proto = ProtocolConfig(mode="pull", fanout=1)
    topo = G.complete(n)
    run = RunConfig(seed=7, max_rounds=32)
    if devices == 1:
        from gossip_tpu.models.si_packed import simulate_until_packed

        def drive():
            simulate_until_packed(proto, topo, run, _partition_fault(n),
                                  timing={})
    else:
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_packed import (
            simulate_until_packed_sharded)

        def drive():
            simulate_until_packed_sharded(proto, topo, run,
                                          make_mesh(devices),
                                          _partition_fault(n), timing={})
    text = _compiled_loop_text(monkeypatch, drive)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


def test_fused_staged_reference_loop_carries_the_device_scopes(
        monkeypatch):
    from gossip_tpu.ops import pallas_round as PR
    # the staged big-table path (the 10M x 8-rumor route) at a small n
    monkeypatch.setattr(PR, "_mr_wants_big", lambda table_bytes, f: True)
    loop, init = PR.compiled_until_fused_multirumor(
        4096, 8, seed=11, max_rounds=8, interpret=True)
    text = loop.lower(init).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
