"""LWW-register transaction subsystem (ops/registers,
runtime/txn_checker; the driver contract — heal, mesh parity, until
target, refusals, the txn_conv round-metrics column, CLI — is the txn
row of tests/test_payload.py): config validation, the LWW join algebra
+ owner-order tie-break, acked-writes ground truth, RPC fall-through +
Maelstrom txn-rw-register workload surfaces, the weak-isolation
checker (which MUST flag planted G0/G1a anomalies), the committed
artifact verdict pin, and the ``*txn*``/``*register*`` provenance
rule."""

import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ChurnConfig, FaultConfig,
                               ProtocolConfig, RunConfig, TxnConfig)
from gossip_tpu.topology import generators as G

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROTO = ProtocolConfig(mode=C.PULL, fanout=2)


# -- config validation -------------------------------------------------

def test_txn_config_validation():
    TxnConfig(keys=2, writes=((0, 0, 0, 5), (1, 0, 2, 7),
                              (2, 1, 0, 1)))
    with pytest.raises(ValueError, match="keys must be"):
        TxnConfig(keys=0)
    with pytest.raises(ValueError, match="values must be >= 1"):
        TxnConfig(writes=((0, 0, 0, 0),))
    with pytest.raises(ValueError, match="outside"):
        TxnConfig(keys=2, writes=((0, 5, 0, 1),))
    with pytest.raises(ValueError, match="horizon cap"):
        TxnConfig(writes=((0, 0, 10 ** 9, 1),))
    # the unique-timestamp contract: two writes sharing one
    # (key, round, node) would fork the LWW winner — a loud error
    with pytest.raises(ValueError, match="duplicate"):
        TxnConfig(writes=((0, 0, 1, 5), (0, 0, 1, 6)))
    with pytest.raises(ValueError, match="zipf_alpha"):
        TxnConfig(zipf_alpha=0.0)
    with pytest.raises(ValueError, match="hot_key"):
        TxnConfig(hot_key=1.5)
    with pytest.raises(ValueError, match="unknown load"):
        TxnConfig(load="lunar")
    # horizon: last scripted round + 1; the default spans spread_rounds
    assert TxnConfig(writes=((0, 0, 7, 1),)).horizon() == 8
    assert TxnConfig(spread_rounds=6).horizon() == 6


def test_skewed_default_program_is_closed_form_and_skewed():
    """The default traffic generator is a pure function of the config
    scalars (same config -> identical program), zipf-skews key
    popularity, honors the hot-key storm window, and spreads diurnal
    load toward the window's middle."""
    from gossip_tpu.ops import registers as RG
    n = 64
    cfg = TxnConfig(keys=8, txns=200, zipf_alpha=1.5)
    ws = RG.txn_writes(cfg, n)
    assert ws == RG.txn_writes(cfg, n)           # deterministic
    counts = [0] * 8
    for _, k, _, _ in ws:
        counts[k] += 1
    assert counts[0] > counts[4]                 # zipf head > tail
    # hot-key storm: the middle third concentrates onto key 0
    hot = TxnConfig(keys=8, txns=200, zipf_alpha=1.5, hot_key=1.0)
    hws = RG.txn_writes(hot, n)
    mid = [k for i, (_, k, _, _) in enumerate(hws)
           if 200 // 3 <= i < 400 // 3]
    assert mid and all(k == 0 for k in mid)
    # diurnal load: density peaks mid-window vs the uniform spread
    di = TxnConfig(keys=8, txns=200, load="diurnal", spread_rounds=10)
    rounds = [r for _, _, r, _ in RG.txn_writes(di, n)]
    mid_mass = sum(1 for r in rounds if 3 <= r <= 6)
    edge_mass = sum(1 for r in rounds if r <= 1 or r >= 8)
    assert mid_mass > edge_mass
    # every program obeys the unique-timestamp contract at lowering
    RG.inject_args(di, n)
    # collision-free BY CONSTRUCTION even where the old writer formula
    # collided (review finding: tiny n, many writes per (key, round)
    # bucket) — and the pigeonhole impossibility errors loudly naming
    # the knobs instead of a "script distinct writers" message for a
    # program the user never scripted
    RG.inject_args(TxnConfig(keys=2, txns=32, hot_key=1.0), 4)
    with pytest.raises(ValueError, match="lower --txns"):
        RG.txn_writes(TxnConfig(keys=1, txns=32, spread_rounds=1), 4)


# -- the LWW join algebra (the acceptance pins) ------------------------

def _rand_states(rng, shape, keys):
    """Random register rows: arbitrary value/ts planes (the algebra
    must hold on ALL states, not just reachable ones)."""
    vals = rng.integers(0, 50, size=shape).astype(np.int32)
    ts = rng.integers(0, 40, size=shape).astype(np.int32)
    return np.concatenate([vals, ts], axis=-1)


def test_lww_merge_algebra_bitwise():
    """Commutativity, associativity, idempotence, upper bound — the
    lattice-join laws, BITWISE on random states (including equal-ts
    ties, which the max(value) rule keeps total)."""
    from gossip_tpu.ops.registers import merge_lww
    rng = np.random.default_rng(7)
    for _ in range(10):
        a = _rand_states(rng, (6, 4), 4)
        b = _rand_states(rng, (6, 4), 4)
        c = _rand_states(rng, (6, 4), 4)
        ab = np.asarray(merge_lww(a, b))
        ba = np.asarray(merge_lww(b, a))
        assert (ab == ba).all()                      # commutative
        abc1 = np.asarray(merge_lww(merge_lww(a, b), c))
        abc2 = np.asarray(merge_lww(a, merge_lww(b, c)))
        assert (abc1 == abc2).all()                  # associative
        aa = np.asarray(merge_lww(a, a))
        assert (aa == a).all()                       # idempotent
        assert (ab[..., 4:] >= a[..., 4:]).all()     # ts upper bound
        again = np.asarray(merge_lww(ab, a))
        assert (again == ab).all()                   # absorbs operands


def test_tie_break_at_equal_round_is_owner_order():
    """Two writes to one key at the SAME round: the higher owner id
    wins — deterministic by the packed (round, owner) timestamp, on
    the ground truth AND on a full simulated trajectory."""
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.ops import registers as RG
    n = 16
    cfg = TxnConfig(keys=2, writes=((3, 0, 1, 9), (5, 0, 1, 7),
                                    (1, 1, 0, 4)))
    inj = RG.inject_args(cfg, n)
    truth = np.asarray(RG.ground_truth(cfg, inj, None, n, 0))
    assert truth[0] == 7                     # owner 5 > owner 3
    assert RG.truth_summary(cfg, truth, n)["ts_owner"][0] == 5
    # and the trajectory converges to that winner everywhere
    run = RunConfig(seed=0, max_rounds=12, target_coverage=1.0)
    conv, _, final, ts = simulate_curve_payload(cfg, _PROTO,
                                                G.complete(n), run)
    assert conv[-1] == 1.0
    assert ts["values"][0] == 7 and ts["ts_owner"][0] == 5
    # a LATER round beats any same-round owner: round order dominates
    cfg2 = TxnConfig(keys=2, writes=((15, 0, 1, 9), (0, 0, 2, 7)))
    t2 = np.asarray(RG.ground_truth(cfg2, RG.inject_args(cfg2, n),
                                    None, n, 0))
    assert t2[0] == 7


def test_ground_truth_acked_write_semantics():
    """A write is applied iff its owner is alive at the write round
    AND eventually alive (the acked-writes rule); the LWW winner is
    picked among APPLIED writes only, and the packed-ts overflow is a
    loud error."""
    from gossip_tpu.ops import registers as RG
    n = 8
    cfg = TxnConfig(keys=2, writes=((0, 0, 0, 10),   # healthy
                                    (7, 0, 3, 20),   # dies forever at 1
                                    (1, 0, 2, 30),   # down [1, 4)
                                    (2, 1, 1, 40)))  # healthy
    f = FaultConfig(churn=ChurnConfig(events=((7, 1, -1), (1, 1, 4))))
    inj = RG.inject_args(cfg, n)
    truth = np.asarray(RG.ground_truth(cfg, inj, f, n, 0))
    # 20 (dead owner) and 30 (down at round 2) never apply: 10 wins
    assert truth[0] == 10 and truth[1] == 40
    # fault-free, the round-3 write wins key 0
    truth0 = np.asarray(RG.ground_truth(cfg, inj, None, n, 0))
    assert truth0[0] == 20
    with pytest.raises(ValueError, match="node ids"):
        RG.inject_args(TxnConfig(writes=((99, 0, 0, 1),)), n)
    with pytest.raises(ValueError, match="overflows int32"):
        RG.check_ts_packable(TxnConfig(writes=((0, 0, 90_000, 1),)),
                             50_000)


# -- the weak-isolation checker (it MUST flag planted anomalies) -------

def _committed(tid, writes=(), reads=()):
    return {"id": tid, "status": "committed",
            "reads": list(reads),
            "writes": [{"key": k, "value": v, "ts": list(ts)}
                       for k, v, ts in writes]}


def test_checker_flags_planted_g0_dirty_write():
    """A synthetic ww cycle — T1's write precedes T2's on key x while
    T2's precedes T1's on key y — MUST be classified G0 (a checker
    that cannot fail is not a checker); the same trace with consistent
    per-txn timestamps is clean."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    planted = [
        _committed(1, writes=[("x", 10, (1, 0)), ("y", 11, (4, 0))]),
        _committed(2, writes=[("x", 20, (2, 1)), ("y", 21, (3, 1))]),
    ]
    out = check_txn_trace(planted)
    assert out["g0"] and not out["ok"]
    assert set(out["g0"][0]["cycle"]) >= {1, 2}
    assert set(out["g0"][0]["keys"]) == {"x", "y"}
    # one timestamp per txn (the server's commit discipline): clean
    clean = [
        _committed(1, writes=[("x", 10, (1, 0)), ("y", 11, (1, 0))]),
        _committed(2, writes=[("x", 20, (2, 1)), ("y", 21, (2, 1))]),
    ]
    out2 = check_txn_trace(clean)
    assert not out2["g0"] and out2["ok"]


def test_checker_flags_planted_g1a_aborted_read():
    """A committed read of a value written by an ABORTED transaction
    MUST be classified G1a; an indeterminate writer's value is
    admissible (the Maelstrom info-timeout convention)."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    planted = [
        {"id": 1, "status": "aborted", "reads": [],
         "writes": [{"key": "x", "value": 99, "ts": [1, 0]}]},
        _committed(2, reads=[["x", 99]]),
    ]
    out = check_txn_trace(planted)
    assert out["g1a"] == [{"reader": 2, "key": "x", "value": 99,
                           "writer": 1}]
    assert not out["ok"]
    # the LIVE trace shape: an aborted txn's writes carry NO server
    # timestamp (the error reply has none) — G1a attribution must
    # still fire on them (review finding: stripping ts-less aborted
    # writes made live G1a detection vacuous)
    live = [
        {"id": 1, "status": "aborted", "reads": [],
         "writes": [{"key": "x", "value": 99, "ts": None}]},
        _committed(2, reads=[["x", 99]]),
    ]
    out_live = check_txn_trace(live)
    assert out_live["g1a"] and not out_live["ok"]
    # the same read of an INDETERMINATE writer is legitimate
    indet = [
        {"id": 1, "status": "indeterminate", "reads": [],
         "writes": [{"key": "x", "value": 99, "ts": [1, 0]}]},
        _committed(2, reads=[["x", 99]]),
    ]
    assert check_txn_trace(indet)["ok"]


def test_checker_defects_and_convergence_cross_check():
    """Trace-integrity defects (duplicate write values, same-key ts
    collisions) and the final-state LWW cross-check fail the verdict
    — a broken harness can never masquerade as a clean isolation
    run."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    dup = [_committed(1, writes=[("x", 5, (1, 0))]),
           _committed(2, writes=[("y", 5, (2, 1))])]
    assert not check_txn_trace(dup)["ok"]
    coll = [_committed(1, writes=[("x", 5, (1, 0))]),
            _committed(2, writes=[("x", 6, (1, 0))])]
    out = check_txn_trace(coll)
    assert out["defects"] and not out["ok"]
    # convergence: final reads must agree AND match the max-ts winner
    txns = [_committed(1, writes=[("x", 5, (1, 0))]),
            _committed(2, writes=[("x", 7, (2, 1))])]
    good = {"n0": {"x": 7}, "n1": {"x": 7}}
    assert check_txn_trace(txns, final_reads=good)["ok"]
    stale = {"n0": {"x": 5}, "n1": {"x": 5}}
    out2 = check_txn_trace(txns, final_reads=stale)
    assert out2["converged"] is False and not out2["ok"]
    split = {"n0": {"x": 7}, "n1": {"x": 5}}
    assert check_txn_trace(txns, final_reads=split)["converged"] \
        is False
    # a timed-out txn's write MAY have applied and won (the Maelstrom
    # info-timeout convention): an agreed final state holding it is
    # converged, not a false alarm
    with_indet = txns + [{"id": 3, "status": "indeterminate",
                          "reads": [],
                          "writes": [{"key": "x", "value": 9,
                                      "ts": None}]}]
    won = {"n0": {"x": 9}, "n1": {"x": 9}}
    assert check_txn_trace(with_indet, final_reads=won)["converged"] \
        is True
    # an ABORTED write leaking into the final state fails the verdict
    # even on a key no committed txn ever wrote (review finding: `best`
    # never covers such a key, so the leak needs its own scan)
    leak = [_committed(1, writes=[("x", 5, (1, 0))]),
            {"id": 2, "status": "aborted", "reads": [],
             "writes": [{"key": "y", "value": 99, "ts": None}]}]
    leaked = {"n0": {"x": 5, "y": 99}, "n1": {"x": 5, "y": 99}}
    out3 = check_txn_trace(leak, final_reads=leaked)
    assert out3["converged"] is False and not out3["ok"]
    clean_final = {"n0": {"x": 5, "y": None}, "n1": {"x": 5, "y": None}}
    assert check_txn_trace(leak, final_reads=clean_final)["ok"]


def test_checker_flags_planted_g1b_intermediate_read():
    """A committed FOREIGN read of a value its writer itself
    overwrote on the same key MUST be classified G1b (intermediate
    read); reading the writer's FINAL value of the key is legitimate,
    and a txn re-reading its own intermediate write is not G1b (the
    read-your-writes path, not an isolation leak)."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    planted = [
        _committed(1, writes=[("x", 10, (1, 0)), ("x", 12, (2, 0))]),
        _committed(2, reads=[["x", 10]]),
    ]
    out = check_txn_trace(planted)
    assert out["g1b"] == [{"reader": 2, "writer": 1, "key": "x",
                           "value": 10, "final": 12}]
    assert not out["ok"]
    # negative twin: the reader saw the writer's FINAL value — clean
    final_read = [
        _committed(1, writes=[("x", 10, (1, 0)), ("x", 12, (2, 0))]),
        _committed(2, reads=[["x", 12]]),
    ]
    out2 = check_txn_trace(final_read)
    assert not out2["g1b"] and out2["ok"]
    # negative twin: SELF-read of an intermediate value — clean
    self_read = [
        _committed(1, writes=[("x", 10, (1, 0)), ("x", 12, (2, 0))],
                   reads=[["x", 10]]),
    ]
    out3 = check_txn_trace(self_read)
    assert not out3["g1b"] and out3["ok"]


def test_checker_flags_planted_g1c_circular_information_flow():
    """A ww u wr cycle closed by a wr edge MUST be classified G1c
    (circular information flow): T2's y-write precedes T1's (ww
    T2 -> T1) while T2 reads T1's x-write (wr T1 -> T2) — no ww-only
    cycle, so G0 stays empty and the wr edge is what closes the
    loop.  Shifting T2's y-write after T1's breaks the cycle."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    planted = [
        _committed(1, writes=[("x", 10, (1, 0)), ("y", 11, (2, 0))]),
        _committed(2, writes=[("y", 21, (1, 1))], reads=[["x", 10]]),
    ]
    out = check_txn_trace(planted)
    assert not out["g0"]
    assert out["g1c"] and not out["ok"]
    cyc = out["g1c"][0]
    assert cyc["cycle"][0] == cyc["cycle"][-1]
    assert set(cyc["cycle"]) == {1, 2}
    assert cyc["wr_edge"] == [1, 2, "x"]
    # negative twin: same reads, T2's y-write AFTER T1's — both edges
    # now point T1 -> T2, no cycle, clean
    ordered = [
        _committed(1, writes=[("x", 10, (1, 0)), ("y", 11, (2, 0))]),
        _committed(2, writes=[("y", 21, (3, 1))], reads=[["x", 10]]),
    ]
    out2 = check_txn_trace(ordered)
    assert not out2["g1c"] and out2["ok"]


def test_checker_reports_lost_update_without_failing_verdict():
    """Two committed txns that both read the same (key, pre-value)
    snapshot and both wrote the key MUST be reported as a lost update
    — but the verdict stays ok: LWW read-committed registers lose
    concurrent updates BY DESIGN (a live partitioned run can
    legitimately produce one), so the checker reports the anomaly for
    the harness counts without branding the trace a violation."""
    from gossip_tpu.runtime.txn_checker import check_txn_trace
    planted = [
        _committed(1, writes=[("x", 5, (1, 0))], reads=[["x", None]]),
        _committed(2, writes=[("x", 7, (2, 1))], reads=[["x", None]]),
    ]
    out = check_txn_trace(planted)
    assert out["lost_update"] == [{"key": "x", "pre": None,
                                   "txns": [1, 2]}]
    assert out["ok"]  # reported, NOT folded into the verdict
    # non-None pre-value: two RMWs atop the same committed version
    stacked = [
        _committed(1, writes=[("x", 5, (1, 0))]),
        _committed(2, writes=[("x", 6, (2, 1))], reads=[["x", 5]]),
        _committed(3, writes=[("x", 7, (3, 2))], reads=[["x", 5]]),
    ]
    out2 = check_txn_trace(stacked)
    assert out2["lost_update"] == [{"key": "x", "pre": 5,
                                    "txns": [2, 3]}]
    assert out2["ok"]
    # negative twin: SERIALIZED read-modify-writes — each read sees
    # the prior write, no shared snapshot, nothing lost
    serial = [
        _committed(1, writes=[("x", 5, (1, 0))], reads=[["x", None]]),
        _committed(2, writes=[("x", 7, (2, 1))], reads=[["x", 5]]),
    ]
    out3 = check_txn_trace(serial)
    assert not out3["lost_update"] and out3["ok"]


# -- RPC: the admission-batcher fall-through contract ------------------

def test_txn_request_falls_through_batcher_labeled():
    """A txn-workload Run request is NOT a megabatch lane shape: it
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
            "txn": {"keys": 4, "txns": 8}}
    args = request_to_args(dict(base))
    key, reason, _ = classify_run(args)
    assert key is None and "txn workload" in reason
    # the solo path the fallthrough lands on runs the workload
    rep = run_simulation(**args).to_dict()
    assert rep["mode"] == "txn" and rep["coverage"] == 1.0
    assert rep["meta"]["truth"]["written_keys"] > 0
    # without the txn field the same request batches normally
    plain = {k: v for k, v in base.items() if k != "txn"}
    key2, _, _ = classify_run(request_to_args(plain))
    assert key2 is not None
    # at most one payload workload per request — a loud error
    both = dict(base)
    both["log"] = {"keys": 2, "capacity": 8}
    with pytest.raises(ValueError, match="at most one payload"):
        run_simulation(**request_to_args(both))


def test_sidecar_txn_request_solo_reply_labeled():
    """Live batching sidecar: the txn request's reply carries the loud
    ``batched: false`` label + reason (and the Ensemble RPC rejects
    txn requests with INVALID_ARGUMENT, not INTERNAL)."""
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
                    txn={"keys": 4, "txns": 8})
        assert out["coverage"] == 1.0
        assert out["meta"]["batch"]["batched"] is False
        assert "txn workload" in out["meta"]["batch"]["reason"]
        with pytest.raises(grpc.RpcError) as ei:
            c.ensemble(backend="jax-tpu",
                       proto={"mode": "pull", "fanout": 2},
                       topology={"family": "complete", "n": 32},
                       txn={"keys": 4}, ensemble=2)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        c.close()
    finally:
        server.gossip_batcher.close()
        server.stop(0)


# -- Maelstrom txn-rw-register workload --------------------------------

# ~5 s: the in-gate acceptance surface is the maelstrom-check CLI run
# below (the SAME run_txn_workload through the same partition;
# invariant_ok already ANDs the g0/g1a/convergence flags); this
# direct-API depth — per-flag granularity, abort accounting — runs
# under -m slow
@pytest.mark.slow
def test_txn_workload_through_partition_direct_api():
    """run_txn_workload: no G0/G1a anomalies, no trace defects, and
    cross-node LWW convergence — through a harness-injected
    mid-cluster partition (total availability, checked)."""
    import asyncio

    from gossip_tpu.runtime.maelstrom_harness import run_txn_workload
    stats = asyncio.run(run_txn_workload(
        4, ops=12, rate=25.0, latency=0.001, partition_mid=True,
        seed=0))
    assert stats["invariant_ok"] is True
    assert stats["partitioned"] is True
    assert stats["g0_ok"] is True and stats["g1a_ok"] is True
    assert stats["converged"] is True
    assert stats["anomalies"] == {"g0": 0, "g1a": 0, "g1b": 0,
                                  "g1c": 0, "lost_update": 0,
                                  "defects": 0}
    assert stats["committed"] > 0
    # txns + final read-alls are client ops via the shared accounting
    assert stats["ops"] > 12 and stats["broadcast_ops"] == 0


def test_cli_maelstrom_check_txn_in_gate(capsys):
    """The acceptance surface: ``maelstrom-check --workload txn``
    passes through a mid-run partition — no G0, no G1a, LWW
    convergence across nodes."""
    from gossip_tpu import cli
    rc = cli.main(["maelstrom-check", "--workload", "txn", "--n", "4",
                   "--ops", "12", "--rate", "25", "--latency", "0.001",
                   "--partition"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["workload"] == "txn"
    assert out["invariant_ok"] is True and out["partitioned"] is True
    assert out["g0_ok"] is True and out["g1a_ok"] is True
    assert out["converged"] is True
    assert out["anomalies"] == {"g0": 0, "g1a": 0, "g1b": 0, "g1c": 0,
                                "lost_update": 0, "defects": 0}
    assert out["committed"] > 0
    # the native router speaks the broadcast envelope set only
    rc = cli.main(["maelstrom-check", "--workload", "txn",
                   "--router", "native"])
    assert rc == 2
    assert "python router" in capsys.readouterr().err


def test_txn_node_malformed_txn_is_definite_abort():
    """TxnServer validates the WHOLE micro-op list before applying
    anything: a malformed txn draws an error reply AND installs no
    writes (the definite-abort contract G1a checking rests on)."""
    import asyncio

    from gossip_tpu.runtime.maelstrom_harness import MaelstromHarness

    async def run():
        import sys as _sys
        h = MaelstromHarness(2, latency=0.001, argv=[
            _sys.executable, "-u", "-m",
            "gossip_tpu.runtime.maelstrom_node", "--workload", "txn"])
        await h.start()
        try:
            await h.set_topology({"n0": ["n1"], "n1": ["n0"]})
            # malformed: a write with a null value, after a valid write
            r = await h.txn("n0", [["w", "x", 5], ["w", "y", None]])
            assert r["body"]["type"] == "error"
            # NOTHING applied — x is still unwritten
            r2 = await h.txn("n0", [["r", "x", None],
                                    ["r", "y", None]])
            assert r2["body"]["type"] == "txn_ok"
            assert r2["body"]["txn"] == [["r", "x", None],
                                         ["r", "y", None]]
            # a committed txn reads its own earlier writes
            r3 = await h.txn("n0", [["w", "x", 9], ["r", "x", None]])
            assert r3["body"]["txn"] == [["w", "x", 9], ["r", "x", 9]]
            assert r3["body"]["ts"][1] == 0        # owner index rides
            # a txn's SECOND write to one key wins in program order
            # (both share the txn timestamp — review finding: a
            # strict ts compare silently dropped it while acking it)
            r4 = await h.txn("n0", [["w", "z", 1], ["w", "z", 2],
                                    ["r", "z", None]])
            assert r4["body"]["txn"] == [["w", "z", 1], ["w", "z", 2],
                                         ["r", "z", 2]]
            r5 = await h.txn("n0", [["r", "z", None]])
            assert r5["body"]["txn"] == [["r", "z", 2]]
        finally:
            await h.stop()

    asyncio.run(run())


# -- committed artifact + provenance gate ------------------------------

def test_committed_txn_artifact_verdict():
    """The committed txn-register record
    (artifacts/ledger_txn_r16.jsonl, tools/txn_capture.py):
    provenance-carrying; txn_conv reached 1.0 on the eventual-alive
    set under the mixed fault program with the partition stall visible
    and bitwise 1-vs-4-device parity; the Maelstrom workload leg shows
    ZERO G0/G1a anomalies through its partition with cross-node LWW
    convergence; the drivers' round_metrics events carry the txn_conv
    column — re-asserted here so the verdict can never rot."""
    from gossip_tpu.utils import telemetry
    path = os.path.join(_REPO, "artifacts", "ledger_txn_r16.jsonl")
    evs = telemetry.load_ledger(path, run="last")
    assert evs[0]["ev"] == "provenance"
    assert len(evs[0]["git_commit"]) == 40
    fp = [e for e in evs if e.get("ev") == "txn_fault_program"][-1]
    assert fp["partitions"] and fp["ramp"] and len(fp["events"]) == 2
    scen = [e for e in evs if e.get("ev") == "txn_scenario"][-1]
    assert scen["txn_conv_final"] == 1.0
    assert scen["mesh_parity_bitwise"] is True
    assert scen["partition_stalled"] is True
    # convergence STALLED while the committed window was open
    stall = scen["partition_stall_rounds"]
    assert all(c < 1.0 for c in scen["txn_conv_curve"][:stall])
    assert scen["ok"] is True
    wl = [e for e in evs if e.get("ev") == "txn_workload"][-1]
    assert wl["g0"] == 0 and wl["g1a"] == 0 and wl["defects"] == 0
    assert wl["converged"] is True and wl["partitioned"] is True
    assert wl["committed"] > 0 and wl["ok"] is True
    assert [e for e in evs if e.get("ev") == "txn_verdict"][-1]["ok"] \
        is True
    rms = [e for e in evs if e.get("ev") == "round_metrics"]
    assert rms and all("txn_conv" in e for e in rms)
    assert all(e["totals"]["txn_conv_final"] == 1.0 for e in rms)


def test_validate_artifacts_requires_provenance_on_txn(tmp_path):
    """``*txn*``/``*register*`` artifacts can never be grandfathered
    in without provenance (the nemesis/crdt/serving/kafka rule,
    extended)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "validate_artifacts",
        os.path.join(_REPO, "tools", "validate_artifacts.py"))
    va = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(va)
    bad = tmp_path / "txn_anomalies_rXX.jsonl"
    bad.write_text(json.dumps({"ev": "txn_scenario"}) + "\n")
    problems = va.validate_file(str(bad))
    assert problems and any("attributable" in p for p in problems)
    badj = tmp_path / "register_sweep.json"
    badj.write_text(json.dumps({"txn_conv": 1.0}))
    assert va.validate_file(str(badj))
