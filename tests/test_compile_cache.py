"""Compile-once runtime contract (utils/compile_cache).

Three layers of proof:

  1. **Store mechanics** — a miss writes a serialized executable, the
     next identical lookup hits it, corrupt entries degrade to misses,
     and GOSSIP_COMPILE_CACHE="" disables cleanly.  The in-process memo
     in front of the store serves a repeated key without a deserialize
     only while the entry's file is unchanged, and keeps a bounded
     number of executables.
  2. **Warm-vs-cold bitwise equality, per driver** — every sharded
     driver whose ``timing=`` path goes through the
     ``utils/trace.aot_timed`` chokepoint (sharded / sharded_sparse /
     sharded_fused / the 2-D pod sweep) must produce IDENTICAL outputs
     whether its executable was compiled cold, compiled into the store
     (miss), or deserialized from it (hit) — an executable round-trip
     that changed results would silently corrupt every warm process.
  3. **Cross-process** — process A populates the store, process B must
     hit it and reproduce A's trajectory bitwise (the dry-run contract
     test additionally proves the same for the persistent XLA cache
     layer on whole processes).
"""

import functools
import json
import os
import subprocess
import sys
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu.topology import generators as G
from gossip_tpu.utils import compile_cache, telemetry
from gossip_tpu.utils.trace import maybe_aot_timed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def no_persistent_cache():
    """Suspend the session-scoped XLA persistent cache (conftest) for
    tests asserting the AOT store's miss/hit choreography: with it
    active the "cold" compile can be served warm by the OTHER layer —
    and a persistent-cache-loaded executable cannot enter the store at
    all (the round-trip verify in compile_cache._try_store)."""
    prev = getattr(jax.config, "jax_compilation_cache_dir", None)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture
def own_cache(tmp_path, monkeypatch, no_persistent_cache):
    """A fresh store dir, made the ambient one (overriding the
    session-scoped conftest dir so hit/miss assertions see only this
    test's traffic)."""
    d = str(tmp_path / "cc")
    monkeypatch.setenv(compile_cache.ENV_VAR, d)
    return d


@pytest.fixture
def fresh_memo(monkeypatch):
    """An empty in-process memo, so no earlier test's executables are
    in it."""
    monkeypatch.setattr(compile_cache, "_MEMO", OrderedDict())


@pytest.fixture
def no_memo(monkeypatch, fresh_memo):
    """A memo that keeps nothing: every store hit deserializes, as a
    fresh process does."""
    monkeypatch.setattr(compile_cache, "MEMO_ENTRIES", 0)


@pytest.fixture
def load_calls(monkeypatch):
    """Every compile_cache._load call (a deserialize onto the devices:
    a store hit's load, or the store's verify), counted."""
    calls = []
    real = compile_cache._load

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(compile_cache, "_load", counted)
    return calls


def test_store_miss_then_hit_bitwise(own_cache, no_memo):
    f = jax.jit(lambda x: jnp.cumsum(jnp.sin(x) * 3.0))
    x = jnp.arange(64, dtype=jnp.float32)
    c1, s1 = compile_cache.load_or_compile(f, x)
    assert s1 == "miss"
    assert compile_cache.entry_count(own_cache) == 1
    c2, s2 = compile_cache.load_or_compile(f, x)
    assert s2 == "hit"
    np.testing.assert_array_equal(np.asarray(c1(x)), np.asarray(c2(x)))


def test_store_disabled_by_empty_env(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "")
    f = jax.jit(lambda x: x * 2)
    _, status = compile_cache.load_or_compile(f, jnp.arange(4))
    assert status == "disabled"
    assert compile_cache.entry_count(str(tmp_path)) == 0


def test_corrupt_entry_degrades_to_miss(own_cache):
    f = jax.jit(lambda x: x + 1)
    x = jnp.arange(8)
    compiled, s1 = compile_cache.load_or_compile(f, x)
    assert s1 == "miss"
    aot = os.path.join(own_cache, "aot")
    (entry,) = os.listdir(aot)
    with open(os.path.join(aot, entry), "wb") as fh:
        fh.write(b"not a pickled executable")
    c2, s2 = compile_cache.load_or_compile(f, x)
    assert s2 == "miss"            # dropped + recompiled, never raised
    np.testing.assert_array_equal(np.asarray(c2(x)), np.arange(8) + 1)


def test_distinct_programs_get_distinct_entries(own_cache):
    x = jnp.arange(8, dtype=jnp.float32)
    _, s1 = compile_cache.load_or_compile(jax.jit(lambda v: v * 2), x)
    _, s2 = compile_cache.load_or_compile(jax.jit(lambda v: v * 3), x)
    # different closed-over constants -> different HLO -> both miss
    assert (s1, s2) == ("miss", "miss")
    assert compile_cache.entry_count(own_cache) == 2
    # shape is part of the key too
    _, s3 = compile_cache.load_or_compile(
        jax.jit(lambda v: v * 2), jnp.arange(16, dtype=jnp.float32))
    assert s3 == "miss"


def test_compile_span_and_counters_reach_ledger(own_cache, tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    prev = telemetry.activate(led)
    try:
        f = jax.jit(lambda x: x - 7)
        timing = {}
        out = maybe_aot_timed(f, timing, jnp.arange(4))
        assert timing["compile_cache"] == "miss"
        timing2 = {}
        maybe_aot_timed(f, timing2, jnp.arange(4))
        assert timing2["compile_cache"] == "hit"
        assert int(out[0]) == -7
    finally:
        telemetry.activate(prev)
        led.close()
    events = telemetry.load_ledger(p)
    spans = [e for e in events if e["ev"] == "span_end"
             and e["name"] == "compile"]
    assert [e["cache"] for e in spans] == ["miss", "hit"]
    assert all("key" in e for e in spans)
    counters = {e["name"]: e["total"] for e in events
                if e["ev"] == "counter"}
    assert counters["compile_cache_miss"] == 1
    assert counters["compile_cache_hit"] == 1
    # the driver_timing event carries the verdict alongside the walls
    dts = [e for e in events if e["ev"] == "driver_timing"]
    assert [e["cache"] for e in dts] == ["miss", "hit"]


# -- the in-process memo in front of the store -------------------------

def _entry(cache_dir):
    aot = os.path.join(cache_dir, "aot")
    (name,) = os.listdir(aot)
    return os.path.join(aot, name)


@pytest.mark.parametrize("filled_on", ["miss", "hit"])
def test_memo_serves_a_repeated_acquisition(filled_on, own_cache,
                                            fresh_memo, load_calls,
                                            monkeypatch):
    """The second acquisition of a key in one process deserializes
    nothing: the memo hands back the executable the first one compiled
    (filled on a miss) or loaded from the store (filled on a hit)."""
    f = jax.jit(lambda x: jnp.cumsum(jnp.cos(x) * 5.0))
    x = jnp.arange(64, dtype=jnp.float32)
    if filled_on == "hit":
        # a store written by an earlier process: nothing memoized yet
        bound = compile_cache.MEMO_ENTRIES
        monkeypatch.setattr(compile_cache, "MEMO_ENTRIES", 0)
        compile_cache.load_or_compile(f, x)
        monkeypatch.setattr(compile_cache, "MEMO_ENTRIES", bound)
    c1, s1 = compile_cache.load_or_compile(f, x)
    assert (s1, compile_cache.last_memo()) == (filled_on, False)
    before = len(load_calls)
    c2, s2 = compile_cache.load_or_compile(f, x)
    assert len(load_calls) == before          # no _load at all
    assert (s2, compile_cache.last_memo()) == ("hit", True)
    assert c2 is c1
    np.testing.assert_array_equal(np.asarray(c1(x)), np.asarray(c2(x)))


def _rewrite(path):
    """The same bytes in a new file renamed over the entry."""
    with open(path, "rb") as fh:
        data = fh.read()
    with open(path + ".new", "wb") as fh:
        fh.write(data)
    os.replace(path + ".new", path)


def _corrupt(path):
    with open(path, "wb") as fh:
        fh.write(b"not a pickled executable")


@pytest.mark.parametrize("change,status,loaded,dropped", [
    (_rewrite, "hit", True, False),      # reloaded from the disk
    (_corrupt, "miss", False, True),     # dropped, recompiled, stored
    (os.remove, "miss", False, False)])  # recompiled, stored
def test_changed_entry_falls_through_to_the_disk(change, status, loaded,
                                                 dropped, own_cache,
                                                 fresh_memo, load_calls,
                                                 capsys):
    """A memo entry serves only the file it came from: after the entry
    is rewritten, corrupted or deleted, the lookup takes the store's
    own path, and the memo then holds what that path acquired."""
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(8)
    c1, _ = compile_cache.load_or_compile(f, x)
    change(_entry(own_cache))
    capsys.readouterr()
    before = len(load_calls)
    c2, s2 = compile_cache.load_or_compile(f, x)
    # a reload deserializes the entry; a miss deserializes to verify
    # the entry it stores
    assert len(load_calls) == before + 1
    assert (s2, compile_cache.last_memo()) == (status, False)
    assert (c2 is not c1) and os.path.exists(_entry(own_cache))
    assert ("dropping corrupt" in capsys.readouterr().err) == dropped
    assert (status == "hit") == loaded
    np.testing.assert_array_equal(np.asarray(c2(x)), np.arange(8) * 3 + 1)
    c3, s3 = compile_cache.load_or_compile(f, x)
    assert c3 is c2 and (s3, compile_cache.last_memo()) == ("hit", True)


def test_memo_bound_evicts_the_oldest_key(own_cache, fresh_memo,
                                          load_calls, monkeypatch):
    monkeypatch.setattr(compile_cache, "MEMO_ENTRIES", 2)
    x = jnp.arange(8, dtype=jnp.float32)
    fns = [jax.jit(lambda v, k=k: v * k) for k in (2.0, 3.0, 5.0)]
    for fn in fns:
        assert compile_cache.load_or_compile(fn, x)[1] == "miss"
    assert len(compile_cache._MEMO) == 2
    before = len(load_calls)
    _, s = compile_cache.load_or_compile(fns[0], x)   # evicted: disk
    assert (s, compile_cache.last_memo()) == ("hit", False)
    assert len(load_calls) == before + 1
    _, s = compile_cache.load_or_compile(fns[0], x)   # memoized again
    assert (s, compile_cache.last_memo()) == ("hit", True)
    assert len(load_calls) == before + 1


def _refuse_to_store(monkeypatch):
    def no_round_trip(compiled):
        raise RuntimeError("no round trip")
    monkeypatch.setattr(compile_cache, "_execution_device_ids",
                        no_round_trip)


@pytest.mark.parametrize("store,status", [("disabled", "disabled"),
                                          ("refused", "miss")])
def test_nothing_memoized_without_a_written_entry(store, status, own_cache,
                                                  fresh_memo, monkeypatch):
    """A disabled store keeps meaning honest cold compiles, and a miss
    whose entry the store would not write is not memoized either."""
    if store == "disabled":
        monkeypatch.setenv(compile_cache.ENV_VAR, "")
    else:
        _refuse_to_store(monkeypatch)
    f = jax.jit(lambda x: x * 11)
    for _ in range(2):
        _, s = compile_cache.load_or_compile(f, jnp.arange(4))
        assert (s, compile_cache.last_memo()) == (status, False)
    assert len(compile_cache._MEMO) == 0


def test_memo_reaches_ledger_and_timing(own_cache, fresh_memo, tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    prev = telemetry.activate(led)
    try:
        f = jax.jit(lambda x: x - 9)
        timings = [{}, {}]
        for timing in timings:
            maybe_aot_timed(f, timing, jnp.arange(4))
    finally:
        telemetry.activate(prev)
        led.close()
    assert [t["exec_memo"] for t in timings] == [False, True]
    assert [t["compile_cache"] for t in timings] == ["miss", "hit"]
    events = telemetry.load_ledger(p)
    spans = [e for e in events if e["ev"] == "span_end"
             and e["name"] == "compile"]
    assert [e["memo"] for e in spans] == [False, True]
    records = [e for e in events if e["ev"] == "xla_compile"]
    assert [e["memo"] for e in records] == [False, True]
    counters = {e["name"]: e["total"] for e in events
                if e["ev"] == "counter"}
    assert counters["compile_cache_memo"] == 1
    assert counters["compile_cache_hit"] == 1


def test_report_meta_says_the_memo_served_a_fresh_seed(own_cache,
                                                       fresh_memo):
    """The packed loop does not depend on the seed: a second
    simulation on another seed is served by the memo, and the report's
    meta says so (the benchmark's exec_memo_share reads it)."""
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.config import TopologyConfig
    proto = ProtocolConfig(mode="pull", fanout=1)
    tc = TopologyConfig(family="complete", n=4096)
    metas = [run_simulation("jax-tpu", proto, tc,
                            RunConfig(seed=seed, max_rounds=64)).meta
             for seed in (5, 6)]
    assert metas[0]["engine"] == metas[1]["engine"] == "bit-packed"
    assert [m["exec_memo"] for m in metas] == [False, True]


def test_rumor_report_meta_says_the_memo_served_a_fresh_seed(own_cache,
                                                             fresh_memo):
    """The rumor loop keeps the seed in its init state's key, so the
    push engine's seed study is served by the memo too, under a
    nemesis schedule, with the compile/steady split in the report."""
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.config import ChurnConfig, TopologyConfig
    proto = ProtocolConfig(mode="rumor", rumor_variant="blind", fanout=3,
                           rumor_k=8)
    tc = TopologyConfig(family="complete", n=2048)
    fault = FaultConfig(drop_prob=0.02, churn=ChurnConfig(
        events=((1, 1, 4), (2, 2, -1)), partitions=((0, 6, 1024),),
        ramp=(0, 4, 0.0, 0.1)))
    metas = [run_simulation("jax-tpu", proto, tc,
                            RunConfig(seed=seed, max_rounds=64), fault).meta
             for seed in (5, 6)]
    assert metas[0]["engine"] == metas[1]["engine"] == "rumor-xla"
    assert [m["exec_memo"] for m in metas] == [False, True]
    for m in metas:
        assert m["compile_s"] >= 0.0 and m["steady_wall_s"] > 0.0


def test_fused_report_meta_says_the_memo_served_a_fresh_seed(
        own_cache, fresh_memo, monkeypatch):
    """The fused multi-rumor loop takes the seed as an operand: on its
    staged big-table path (the 10M x 8 route, here at a small n with
    the kernels in interpret mode), a second simulation on a fresh seed
    is served by the memo from the store entry the first one wrote."""
    from gossip_tpu import backend
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.config import TopologyConfig
    from gossip_tpu.ops import pallas_round as PR
    real_reason = backend._fused_ineligible_reason

    def reason(*a, **kw):
        r = real_reason(*a, **kw)
        return None if r and "needs a TPU" in r else r

    monkeypatch.setattr(backend, "_fused_ineligible_reason", reason)
    monkeypatch.setattr(PR, "_mr_wants_big", lambda *a: True)
    monkeypatch.setattr(PR, "compiled_until_fused_multirumor",
                        functools.partial(PR.compiled_until_fused_multirumor,
                                          interpret=True))
    PR._fused_mr_round_jit.clear_cache()
    proto = ProtocolConfig(mode="pull", fanout=1, rumors=8)
    tc = TopologyConfig(family="complete", n=4096)
    try:
        reps, caches = [], []
        for seed in (1_234_567_891, 987_654_321):
            reps.append(run_simulation("jax-tpu", proto, tc,
                                       RunConfig(seed=seed, max_rounds=8)))
            caches.append(compile_cache.last_compile()["cache"])
    finally:
        PR._fused_mr_round_jit.clear_cache()
    assert [r.meta["engine"] for r in reps] == ["fused-pallas"] * 2
    assert [r.meta["exec_memo"] for r in reps] == [False, True]
    assert caches == ["miss", "hit"]


# -- warm-vs-cold bitwise equality, driver by driver -------------------

def _mesh(n_devices=4):
    from gossip_tpu.parallel.sharded import make_mesh
    return make_mesh(n_devices)


def _drive_sharded(timing):
    from gossip_tpu.parallel.sharded import simulate_curve_sharded
    covs, msgs, final = simulate_curve_sharded(
        ProtocolConfig(mode=C.PUSH_PULL, fanout=2, rumors=2),
        G.erdos_renyi(64, p=0.2, seed=0), RunConfig(seed=0, max_rounds=4),
        _mesh(), fault=FaultConfig(node_death_rate=0.05, drop_prob=0.1,
                                   seed=1), timing=timing)
    return np.asarray(covs), np.asarray(msgs), np.asarray(final.seen)


def _drive_sparse(timing):
    from gossip_tpu.parallel.sharded_sparse import simulate_curve_sparse
    covs, msgs, final, _meta = simulate_curve_sparse(
        ProtocolConfig(mode=C.ANTI_ENTROPY, fanout=2, rumors=5, period=2),
        128, RunConfig(seed=0, max_rounds=4), _mesh(), timing=timing)
    return np.asarray(covs), np.asarray(msgs), np.asarray(final.seen)


def _drive_fused(timing):
    from gossip_tpu.parallel.sharded_fused import (
        make_plane_mesh, simulate_curve_sharded_fused)
    covs, final = simulate_curve_sharded_fused(
        128, 40, RunConfig(seed=0, max_rounds=3), make_plane_mesh(4),
        interpret=True, timing=timing)
    return np.asarray(covs), np.asarray(final)


def _drive_sweep(timing):
    from gossip_tpu.parallel.multislice import make_hybrid_mesh
    from gossip_tpu.parallel.sweep import (SweepPoint,
                                           config_sweep_curves_2d)
    pts = [SweepPoint(mode=m, fanout=f, drop_prob=0.0, period=1, seed=0)
           for m in (C.PUSH, C.PULL) for f in (1, 2)]
    res = config_sweep_curves_2d(
        pts, G.ring(64, k=4), RunConfig(seed=0, max_rounds=3),
        make_hybrid_mesh(2, 2, axis_names=("sweep", "nodes")),
        timing=timing)
    return np.asarray(res.curves), np.asarray(res.msgs)


DRIVERS = {"sharded": _drive_sharded, "sharded_sparse": _drive_sparse,
           "sharded_fused": _drive_fused, "pod_sweep_2d": _drive_sweep}


# pod_sweep_2d rides the slow tier since the log-PR rebalance (~6 s
# flight data): the warm-vs-cold mechanism is driver-generic (the ONE
# trace.aot_timed chokepoint) and stays pinned in-gate by the
# sharded/sparse/fused params; the pod-sweep SURFACE keeps its in-gate
# smokes via the hybrid_2d_sweep dry-run family and the 2-D pod sweep
# parity test (tests/test_config_sweep.py)
@pytest.mark.parametrize(
    "name", [pytest.param(n, marks=pytest.mark.slow)
             if n == "pod_sweep_2d" else n for n in sorted(DRIVERS)])
def test_driver_warm_vs_cold_bitwise(name, tmp_path, monkeypatch,
                                     no_persistent_cache, no_memo):
    """Cold (store-miss: a real XLA compile) and warm (store-hit: the
    deserialized executable) executions of the same driver call must
    agree BITWISE on every output — the warm path can change walls,
    never values.  (A disabled-cache leg would be the identical
    compile path as the miss leg minus the store write, so it buys no
    extra coverage for a third driver compile.)"""
    drive = DRIVERS[name]
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    t_miss = {}
    cold = drive(t_miss)
    assert t_miss["compile_cache"] == "miss"
    t_hit = {}
    warm = drive(t_hit)
    assert t_hit["compile_cache"] == "hit"
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)


_CHILD = r"""
import json, sys
import numpy as np
import jax
sys.path.insert(0, {repo!r})
from gossip_tpu.config import FaultConfig, ProtocolConfig, RunConfig
from gossip_tpu import config as C
from gossip_tpu.topology import generators as G
from gossip_tpu.parallel.sharded import make_mesh, simulate_curve_sharded
timing = {{}}
covs, msgs, final = simulate_curve_sharded(
    ProtocolConfig(mode=C.PUSH_PULL, fanout=2, rumors=2),
    G.erdos_renyi(64, p=0.2, seed=0), RunConfig(seed=0, max_rounds=4),
    make_mesh(4), fault=FaultConfig(node_death_rate=0.05, drop_prob=0.1,
                                    seed=1), timing=timing)
print(json.dumps({{"cache": timing["compile_cache"],
                   "covs": np.asarray(covs).tolist(),
                   "digest": int(np.asarray(final.seen).sum())}}))
"""


# ~11 s (txn-PR rebalance): the cross-process reuse claim is proven
# in-gate every session by the dryrun_pair fixture (cold process
# populates, warm process must be ALL-HIT — asserted on the compile
# verdicts in tests/test_graft_entry.py); this store-level twin
# re-proves under -m slow
@pytest.mark.slow
def test_cross_process_populate_then_hit(tmp_path):
    """Process A populates the AOT store; process B — a fresh
    interpreter, same program — must HIT it and reproduce A's
    trajectory bitwise.  The compile-once claim is exactly this
    cross-process reuse; same-process hits (above) would also be
    served by jax's in-memory caches."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["GOSSIP_COMPILE_CACHE"] = str(tmp_path / "cc")
    env["GOSSIP_TELEMETRY"] = ""

    def run():
        p = subprocess.run([sys.executable, "-c",
                            _CHILD.format(repo=_REPO)],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.splitlines()[-1])

    a = run()
    b = run()
    assert a["cache"] == "miss"
    assert b["cache"] == "hit"
    assert a["covs"] == b["covs"]
    assert a["digest"] == b["digest"] > 0


# -- sweep cache telemetry (satellite) ---------------------------------

def test_pod_sweep_cache_stats_eviction_predicate():
    from collections import namedtuple

    from gossip_tpu.parallel.sweep import _pod_sweep_cache_stats
    Info = namedtuple("CacheInfo", "hits misses maxsize currsize")
    g, ev = _pod_sweep_cache_stats(Info(5, 3, 16, 3), Info(5, 2, 16, 2))
    assert not ev and g["pod_sweep_scan_cache_hits"] == 5
    # a miss while the memo was full: lru evicted to admit this scan
    _, ev = _pod_sweep_cache_stats(Info(0, 17, 16, 16),
                                   Info(0, 16, 16, 16))
    assert ev
    # over-subscribed HISTORY but this call was a memo hit: no warning
    # (the cumulative-totals predicate would cry wolf forever here)
    _, ev = _pod_sweep_cache_stats(Info(9, 17, 16, 16),
                                   Info(8, 17, 16, 16))
    assert not ev
    # a miss while the memo still had room: growth, not eviction
    _, ev = _pod_sweep_cache_stats(Info(0, 4, 16, 4), Info(0, 3, 16, 3))
    assert not ev


# ~7 s (txn-PR rebalance): the eviction-warning predicate stays
# unit-tested above and the 2-D sweep surface stays in-gate via the
# hybrid_2d_sweep dry-run family; the live gauge emission re-proves
# under -m slow
@pytest.mark.slow
def test_pod_sweep_emits_cache_gauges(tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    prev = telemetry.activate(led)
    try:
        _drive_sweep(None)
    finally:
        telemetry.activate(prev)
        led.close()
    gauges = {e["name"]: e["value"]
              for e in telemetry.load_ledger(p) if e["ev"] == "gauge"}
    assert "pod_sweep_scan_cache_hits" in gauges
    assert "pod_sweep_scan_cache_misses" in gauges
    assert gauges["pod_sweep_scan_cache_maxsize"] == 16
    assert gauges["pod_sweep_scan_cache_size"] >= 1


# ---------------------------------------------------------------------
# placement: $JAX_COMPILATION_CACHE_DIR, else one fixed in-checkout dir
# ---------------------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Every jax.config.update the code under test makes, recorded
    instead of applied (the process's own cache config stays put)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_honours_jax_compilation_cache_dir(tmp_path, monkeypatch,
                                                 config_updates):
    """Placed from outside: the env dir wins over the caller's choice,
    and no jax_compilation_cache_dir update is made (JAX reads the
    variable itself)."""
    jcc, other = tmp_path / "jcc", tmp_path / "other"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(jcc))
    status = compile_cache.enable_persistent(str(other))
    assert status == {"dir": str(jcc), "persistent": True}
    assert jcc.is_dir() and not other.exists()
    assert [k for k, _ in config_updates
            if k == "jax_compilation_cache_dir"] == []
    assert ("jax_enable_compilation_cache", True) in config_updates
    # an explicit disable still wins over the variable (cold compiles)
    config_updates.clear()
    assert not compile_cache.enable_persistent(None)["persistent"]
    assert config_updates == [("jax_enable_compilation_cache", False)]


def test_cache_default_is_the_fixed_in_checkout_dir(monkeypatch,
                                                    config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.DEFAULT_DIR == os.path.join(_REPO, ".jax_cache")
    status = compile_cache.enable_persistent(compile_cache.DEFAULT_DIR)
    assert status["dir"] == compile_cache.DEFAULT_DIR
    assert ("jax_compilation_cache_dir",
            compile_cache.DEFAULT_DIR) in config_updates
    # and the CLI's default is that same dir (no home dir, no temp name)
    env = {k: v for k, v in os.environ.items()
           if k not in ("GOSSIP_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    p = subprocess.run(
        [sys.executable, "-c",
         "from gossip_tpu import cli; print(cli._CACHE_DEFAULT)"],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=120)
    assert p.stdout.strip() == compile_cache.DEFAULT_DIR, p.stderr


def test_cli_run_keeps_both_layers_in_jax_compilation_cache_dir(tmp_path):
    """End to end: a CLI run under $JAX_COMPILATION_CACHE_DIR stamps
    that dir and writes its AOT store there."""
    jcc = tmp_path / "jcc"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(jcc),
               GOSSIP_TELEMETRY="")
    env.pop("GOSSIP_COMPILE_CACHE", None)
    p = subprocess.run(
        [sys.executable, "-m", "gossip_tpu", "run", "--mode", "pull",
         "--n", "512"], capture_output=True, text=True, env=env,
        cwd=_REPO, timeout=300)
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    assert rep["compile_cache"] == str(jcc)
    assert (jcc / "aot").is_dir()
