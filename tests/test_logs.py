"""Replicated-log subsystem (ops/logs; its driver contract — heal,
mesh parity, until target, refusals, the log_conv round-metrics
column, CLI — is the log row of tests/test_payload.py): config
validation, offset-assignment + acked-appends ground truth, RPC
fall-through + Maelstrom kafka workload surfaces, the committed
artifact verdict pin, and the ``*kafka*``/``*replog*`` provenance
rule."""

import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ChurnConfig, FaultConfig, LogConfig,
                               ProtocolConfig, RunConfig)
from gossip_tpu.topology import generators as G

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- config validation -------------------------------------------------

def test_log_config_validation():
    LogConfig(keys=2, capacity=4,
              sends=((0, 0, 0, 5), (1, 0, 2, 7), (2, 1, 0, 1)),
              commits=((0, 0, 3, 2),))
    with pytest.raises(ValueError, match="keys must be"):
        LogConfig(keys=0)
    with pytest.raises(ValueError, match="values must be >= 1"):
        LogConfig(sends=((0, 0, 0, 0),))
    with pytest.raises(ValueError, match="outside"):
        LogConfig(keys=2, sends=((0, 5, 0, 1),))
    with pytest.raises(ValueError, match="horizon cap"):
        LogConfig(sends=((0, 0, 10 ** 9, 1),))
    # the ring never wraps: more sends than capacity is a loud error
    with pytest.raises(ValueError, match="wrap"):
        LogConfig(keys=1, capacity=2,
                  sends=((0, 0, 0, 1), (1, 0, 1, 2), (2, 0, 2, 3)))
    # offset order IS time order: per-key script order must be
    # round-nondecreasing
    with pytest.raises(ValueError, match="nondecreasing"):
        LogConfig(sends=((0, 0, 5, 1), (1, 0, 2, 2)))
    with pytest.raises(ValueError, match="upto must be"):
        LogConfig(commits=((0, 0, 2, 0),))
    # the DEFAULT send program (4 per key) obeys the same no-wrap
    # contract a scripted one does — a tiny unscripted capacity must
    # error loudly, never alias slots silently (review finding)
    with pytest.raises(ValueError, match="default send program"):
        LogConfig(keys=4, capacity=2)
    LogConfig(keys=4, capacity=2, sends=((0, 0, 0, 1), (1, 1, 0, 1)))
    # horizon: last scripted round + 1; defaults end at round 4
    assert LogConfig(sends=((0, 0, 7, 1),)).horizon() == 8
    assert LogConfig().horizon() == 5


# -- offset assignment + acked-appends ground truth --------------------

def test_ground_truth_acked_append_semantics():
    """A send is applied iff its appender is alive at the send round
    AND eventually alive; unapplied sends are compacted over (the
    acked log is gap-free), and commits clamp to the eventually-acked
    length."""
    from gossip_tpu.ops import logs as LG
    n = 8
    cfg = LogConfig(keys=2, capacity=8,
                    sends=((0, 0, 0, 10),   # healthy: offset 0
                           (7, 0, 1, 20),   # dies forever at 1: out
                           (1, 0, 2, 30),   # down [1, 4): missed
                           (2, 0, 5, 40)),  # healthy: offset 1
                    commits=((4, 0, 6, 3),  # clamps to acked len 2
                             (5, 1, 6, 1)))  # key 1 empty: commits 0
    f = FaultConfig(churn=ChurnConfig(events=((7, 1, -1), (1, 1, 4))))
    inj = LG.inject_args(cfg, n)
    truth = np.asarray(LG.ground_truth(cfg, inj, f, n, 0))
    assert truth[:8].tolist() == [10, 40, 0, 0, 0, 0, 0, 0]
    assert truth[8:16].tolist() == [0] * 8          # key 1 empty
    assert truth[16:].tolist() == [2, 0]            # commit clamped
    # fault-free: everything applies, offsets in script order
    truth0 = np.asarray(LG.ground_truth(cfg, inj, None, n, 0))
    assert truth0[:8].tolist() == [10, 20, 30, 40, 0, 0, 0, 0]
    assert truth0[16:].tolist() == [3, 0]
    # out-of-range appender ids are a loud error, not a silent no-op
    with pytest.raises(ValueError, match="node ids"):
        LG.inject_args(LogConfig(sends=((99, 0, 0, 1),)), n)
    # the derived append cursor reads the contiguous prefix
    lens = np.asarray(LG.log_len(cfg, truth[None, :]))[0]
    assert lens.tolist() == [2, 0]


# -- RPC: the admission-batcher fall-through contract ------------------

def test_log_request_falls_through_batcher_labeled():
    """A log-workload Run request is NOT a megabatch lane shape: it
    must fall through the admission batcher to the solo path with a
    NAMED ``meta.batch.reason`` (the PR 9 fall-through contract — a
    labeled solo reply, never INTERNAL), and the solo path must
    actually run it."""
    from gossip_tpu.backend import request_to_args, run_simulation
    from gossip_tpu.rpc.batcher import classify_run
    base = {"backend": "jax-tpu",
            "proto": {"mode": "pull", "fanout": 2},
            "topology": {"family": "complete", "n": 32},
            "run": {"max_rounds": 16, "target_coverage": 1.0},
            "log": {"keys": 2, "capacity": 8}}
    args = request_to_args(dict(base))
    key, reason, _ = classify_run(args)
    assert key is None and "log workload" in reason
    # the solo path the fallthrough lands on runs the workload
    rep = run_simulation(**args).to_dict()
    assert rep["mode"] == "log" and rep["coverage"] == 1.0
    assert rep["meta"]["truth"]["total_entries"] > 0
    # without the log field the same request batches normally
    plain = {k: v for k, v in base.items() if k != "log"}
    key2, _, _ = classify_run(request_to_args(plain))
    assert key2 is not None


def test_sidecar_log_request_solo_reply_labeled():
    """Live batching sidecar: the log request's reply carries the loud
    ``batched: false`` label + reason (and the Ensemble RPC rejects
    log requests with INVALID_ARGUMENT, not INTERNAL)."""
    grpc = pytest.importorskip("grpc")
    from gossip_tpu.config import ServingConfig
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    server, port = serve(port=0, max_workers=4,
                         batching=ServingConfig(tick_ms=50,
                                                max_batch=8))
    try:
        c = SidecarClient(f"127.0.0.1:{port}")
        out = c.run(backend="jax-tpu",
                    proto={"mode": "pull", "fanout": 2},
                    topology={"family": "complete", "n": 32},
                    run={"max_rounds": 16, "target_coverage": 1.0},
                    log={"keys": 2, "capacity": 8})
        assert out["coverage"] == 1.0
        assert out["meta"]["batch"]["batched"] is False
        assert "log workload" in out["meta"]["batch"]["reason"]
        with pytest.raises(grpc.RpcError) as ei:
            c.ensemble(backend="jax-tpu",
                       proto={"mode": "pull", "fanout": 2},
                       topology={"family": "complete", "n": 32},
                       log={"keys": 2}, ensemble=2)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        c.close()
    finally:
        server.gossip_batcher.close()
        server.stop(0)


# -- Maelstrom kafka workload (the Gossip Glomers invariants) ----------

# ~4 s: the in-gate acceptance surface is the maelstrom-check CLI run
# below (the SAME run_kafka_workload through the same partition;
# invariant_ok already ANDs the monotone + gapless flags); this
# direct-API depth — per-flag granularity, committed-map coverage —
# runs under -m slow
@pytest.mark.slow
def test_kafka_workload_invariants_through_partition():
    """run_kafka_workload: acked sends appear exactly once per key in
    offset order, committed offsets never regress, and polls are
    gapless — through a harness-injected mid-cluster partition (the
    fault-tolerance variant of the Gossip Glomers kafka challenge).
    ops=12/seed=0 exercises commits on multiple keys (committed map
    non-empty)."""
    import asyncio

    from gossip_tpu.runtime.maelstrom_harness import run_kafka_workload
    stats = asyncio.run(run_kafka_workload(
        4, ops=12, rate=25.0, latency=0.001, partition_mid=True,
        seed=0))
    assert stats["invariant_ok"] is True
    assert stats["partitioned"] is True
    assert stats["monotone_ok"] is True and stats["gapless_ok"] is True
    assert sum(stats["acked"].values()) > 0
    assert stats["committed"]            # commits actually exercised
    # sends/polls/commits are client ops via the shared accounting
    assert stats["ops"] > 12 and stats["broadcast_ops"] == 0


def test_cli_maelstrom_check_kafka_in_gate(capsys):
    """The acceptance surface: ``maelstrom-check --workload kafka``
    passes all three kafka invariants through a mid-run partition."""
    from gossip_tpu import cli
    rc = cli.main(["maelstrom-check", "--workload", "kafka", "--n", "4",
                   "--ops", "12", "--rate", "25", "--latency", "0.001",
                   "--partition"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["workload"] == "kafka"
    assert out["invariant_ok"] is True and out["partitioned"] is True
    # invariant_ok ANDs all three kafka checks; assert the per-flag
    # verdicts + commit coverage too (ops=12/seed=0 commits >= 2 keys)
    assert out["monotone_ok"] is True and out["gapless_ok"] is True
    assert out["committed"] and sum(out["acked"].values()) > 0
    # the native router speaks the broadcast envelope set only
    rc = cli.main(["maelstrom-check", "--workload", "kafka",
                   "--router", "native"])
    assert rc == 2
    assert "python router" in capsys.readouterr().err


def test_kafka_workload_timeout_send_is_indeterminate_not_crash():
    """A client RPC timing out (a long partition outlasting the 15 s
    budget while the node's forward retries keep going) must record
    the send INDETERMINATE — it may later appear in polls via the
    owner's at-least-once forward — never crash run_kafka_workload
    (review finding: the uncaught TimeoutError path)."""
    import asyncio

    from gossip_tpu.runtime import maelstrom_harness as MH

    orig = MH.MaelstromHarness.kafka_send
    state = {"fired": False}

    async def flaky(self, node, key, msg):
        if not state["fired"]:
            state["fired"] = True
            raise asyncio.TimeoutError()
        return await orig(self, node, key, msg)

    MH.MaelstromHarness.kafka_send = flaky
    try:
        stats = asyncio.run(MH.run_kafka_workload(
            3, ops=6, rate=50.0, latency=0.001, partition_mid=False,
            seed=1))
    finally:
        MH.MaelstromHarness.kafka_send = orig
    assert state["fired"]
    # the timed-out send is indeterminate, the rest acked; the
    # invariants still hold (an indeterminate value may appear in
    # polls, at most once)
    assert stats["invariant_ok"] is True
    assert sum(stats["indeterminate"].values()) == 1
    assert sum(stats["acked"].values()) == 5


# -- committed artifact + provenance gate ------------------------------

def test_committed_kafka_artifact_verdict():
    """The committed replicated-log convergence record
    (artifacts/ledger_kafka_r15.jsonl, tools/kafka_capture.py):
    provenance-carrying; log_conv reached 1.0 on the eventual-alive
    set under the mixed fault program with the partition stall visible
    and bitwise 1-vs-4-device parity; the drivers' round_metrics
    events carry the log_conv column — re-asserted here so the
    verdict can never rot."""
    from gossip_tpu.utils import telemetry
    path = os.path.join(_REPO, "artifacts", "ledger_kafka_r15.jsonl")
    evs = telemetry.load_ledger(path, run="last")
    assert evs[0]["ev"] == "provenance"
    assert len(evs[0]["git_commit"]) == 40
    fp = [e for e in evs if e.get("ev") == "kafka_fault_program"][-1]
    assert fp["partitions"] and fp["ramp"] and len(fp["events"]) == 2
    scen = [e for e in evs if e.get("ev") == "kafka_scenario"][-1]
    assert scen["log_conv_final"] == 1.0
    assert scen["mesh_parity_bitwise"] is True
    assert scen["partition_stalled"] is True
    # convergence STALLED while the committed window was open
    stall = scen["partition_stall_rounds"]
    assert all(c < 1.0 for c in scen["log_conv_curve"][:stall])
    assert scen["ok"] is True
    assert [e for e in evs if e.get("ev") == "kafka_verdict"][-1]["ok"] \
        is True
    rms = [e for e in evs if e.get("ev") == "round_metrics"]
    assert rms and all("log_conv" in e for e in rms)
    assert all(e["totals"]["log_conv_final"] == 1.0 for e in rms)


def test_validate_artifacts_requires_provenance_on_kafka(tmp_path):
    """``*kafka*``/``*replog*`` artifacts can never be grandfathered
    in without provenance (the nemesis/crdt/serving rule, extended)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "validate_artifacts",
        os.path.join(_REPO, "tools", "validate_artifacts.py"))
    va = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(va)
    bad = tmp_path / "kafka_convergence_rXX.jsonl"
    bad.write_text(json.dumps({"ev": "kafka_scenario"}) + "\n")
    problems = va.validate_file(str(bad))
    assert problems and any("attributable" in p for p in problems)
    badj = tmp_path / "replog_sweep.json"
    badj.write_text(json.dumps({"log_conv": 1.0}))
    assert va.validate_file(str(badj))
