"""The replicated-payload drivers (models/payload, parallel/
sharded_payload) over their three rows — crdt, log, txn: one round
frame per placement, one until loop and one curve scan.  Each test is
parametrised over the rows, so every payload is held to the same
contract: the partition-stall/exact-heal acceptance, 1-vs-4-device
bitwise parity under the full mixed fault program, the integer until
target, loud refusals, the round-metrics convergence column, the CLI
surface, and one operand-tail layout shared by both frames."""

import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ByzConfig, ChurnConfig, CrdtConfig,
                               FaultConfig, LogConfig, ProtocolConfig,
                               RunConfig, TopologyConfig, TxnConfig)
from gossip_tpu.topology import generators as G

_PROTO = ProtocolConfig(mode=C.PULL, fanout=2)
# the full mixed fault program every parity/heal surface runs:
# crash/recover, permanent crash, open partition window, drop ramp
_CFAULT = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
    events=((3, 2, 5), (7, 1, -1)), partitions=((0, 6, 16),),
    ramp=(1, 4, 0.0, 0.3)))

# the configs the until/metrics/parity surfaces drive, one per row
_CFGS = {"crdt": CrdtConfig(kind="gcounter"),
         "log": LogConfig(keys=4, capacity=8),
         "txn": TxnConfig(keys=8, txns=16)}
_ROWS = ["crdt", "log", "txn"]


def _mesh(k=4):
    from gossip_tpu.parallel.sharded import make_mesh
    return make_mesh(k)


# -- partition-heal convergence (the acceptance gate) ------------------

_HEAL_N = 64
_HEAL_END = 8    # long enough for each side to saturate its own split


def _heal_bound(fanout):
    # ~2 epidemic legs + slack after the window closes (the
    # docs/ROBUSTNESS.md bound the broadcast heal tests use)
    import math
    leg = math.ceil(math.log(_HEAL_N) / math.log(1 + fanout))
    return _HEAL_END + 2 * leg + 4


def _heal_crdt():
    """While the window is open, value convergence provably stalls at
    the partition value split — each side's merged value is exactly its
    OWN side's truth sum, nobody holds the global truth — and after
    heal every node reaches the exact integer ground truth within the
    documented bound."""
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.ops import crdt as CR
    cut = 48
    cfg = CrdtConfig(kind="gcounter")
    fault = FaultConfig(seed=0, churn=ChurnConfig(
        partitions=((0, _HEAL_END, cut),)))
    run = RunConfig(seed=0, max_rounds=24, target_coverage=1.0)
    topo = G.complete(_HEAL_N)
    conv, msgs, final, truth_val = simulate_curve_payload(
        cfg, _PROTO, topo, run, fault)
    # stalled: nobody converges to the GLOBAL truth while the cut is
    # open (both sides hold strictly partial sums)
    assert all(c == 0.0 for c in conv[:_HEAL_END]), list(conv)
    # ... and the stall sits exactly at the partition value SPLIT: by
    # round _HEAL_END every node holds its own side's full sum — run
    # the open-window prefix and check the integer split
    prefix = RunConfig(seed=0, max_rounds=_HEAL_END - 1,
                       target_coverage=1.0)
    _, _, mid, _ = simulate_curve_payload(cfg, _PROTO, topo, prefix,
                                          fault)
    truth = np.asarray(CR.ground_truth(
        cfg, CR.inject_args(cfg, _HEAL_N), fault, _HEAL_N, 0))
    lo_sum, hi_sum = int(truth[:cut].sum()), int(truth[cut:].sum())
    vals = np.asarray(mid.val).sum(axis=1)
    assert vals.max() <= lo_sum + hi_sum
    assert (vals[:cut] <= lo_sum).all() and (vals[cut:] <= hi_sum).all()
    assert vals[:cut].max() == lo_sum       # near side saturated its split
    # healed: EXACT ground truth everywhere within the bound
    hit = np.nonzero(np.asarray(conv) >= 1.0)[0]
    assert len(hit), f"never healed: {list(conv)}"
    assert int(hit[0]) + 1 <= _heal_bound(_PROTO.fanout), list(conv)
    assert (np.asarray(final.val)
            == truth[None, :]).all()        # integer-exact, every node
    assert truth_val == lo_sum + hi_sum


def _heal_under_full_program(cfg, ops):
    """Curve under the full mixed fault program: stalled while the
    committed window [0, 6) is open, then every eventual-alive node
    holds the exact integer truth row; returns the truth summary."""
    from gossip_tpu.models.payload import simulate_curve_payload
    run = RunConfig(seed=0, max_rounds=24, target_coverage=1.0)
    n = 32
    conv, _, final, truth = simulate_curve_payload(cfg, _PROTO,
                                                   G.complete(n), run,
                                                   _CFAULT)
    assert all(c < 1.0 for c in conv[:6]), list(conv)
    assert conv[-1] == 1.0, list(conv)
    inj = ops.inject_args(cfg, n)
    truth_row = np.asarray(ops.ground_truth(cfg, inj, _CFAULT, n, 0))
    eventual = np.asarray(ops.eventual_alive_crdt(_CFAULT, n, 0))
    vals = np.asarray(final.val)
    assert (vals[eventual] == truth_row[None, :]).all()
    return truth


def _heal_log():
    """While the window is open, log convergence provably stalls (no
    node holds the global acked log + committed offsets) and after
    heal every eventual-alive node reaches the exact integer ground
    truth — the ordered eventual-consistency invariant under the full
    mixed fault program."""
    from gossip_tpu.ops import logs as LG
    truth = _heal_under_full_program(LogConfig(keys=4, capacity=8), LG)
    # the permanently-dead appender's sends are compacted out of truth
    assert truth["total_entries"] < 16


def _heal_txn():
    """While the window is open, txn convergence provably stalls (no
    node holds the global LWW winners) and after heal every
    eventual-alive node reaches the exact integer ground truth —
    value AND timestamp planes — under the full mixed fault
    program."""
    from gossip_tpu.ops import registers as RG
    truth = _heal_under_full_program(
        TxnConfig(keys=8, txns=24, zipf_alpha=1.2, hot_key=0.3), RG)
    # the permanently-dead writer's writes won nothing
    assert 7 not in truth["ts_owner"]


@pytest.mark.parametrize("heal", [_heal_crdt, _heal_log, _heal_txn],
                         ids=_ROWS)
def test_partition_stall_and_exact_heal(heal):
    heal()


# -- mesh parity: schedules + injections as operands -------------------

@pytest.mark.parametrize("cfg", [
    CrdtConfig(kind="gcounter"),
    CrdtConfig(kind="orset", elements=48,
               set_removes=((5, 3), (11, 8))),
    pytest.param(CrdtConfig(kind="pncounter"),
                 marks=pytest.mark.slow),
    LogConfig(keys=4, capacity=8),
    TxnConfig(keys=8, txns=16, zipf_alpha=1.2, hot_key=0.3),
], ids=["crdt-gcounter", "crdt-orset_packed", "crdt-pncounter", "log",
        "txn"])
def test_mesh_parity_bitwise_full_fault_program(cfg):
    """1-device vs 4-device trajectories BITWISE identical under the
    full mixed fault program (event + permanent crash + open partition
    window + ramp) — counter shards on the dense int32 exchange, the
    packed-plane set payload (uint32 words, 32 elements per lane — the
    ops/bitpack layout), log slots and LWW registers — plus exact
    convergence on the eventual-alive set."""
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.parallel.sharded_payload import (
        simulate_curve_payload_sharded)
    run = RunConfig(seed=0, max_rounds=16, target_coverage=1.0)
    topo = G.complete(32)
    c1, m1, f1, t1 = simulate_curve_payload(cfg, _PROTO, topo, run,
                                            _CFAULT)
    c4, m4, f4, t4 = simulate_curve_payload_sharded(cfg, _PROTO, topo,
                                                    run, _mesh(), _CFAULT)
    assert (np.asarray(c1) == np.asarray(c4)).all()
    assert (np.asarray(f1.val) == np.asarray(f4.val)[:32]).all()
    assert float(f1.msgs) == float(f4.msgs)
    assert t1 == t4
    if getattr(cfg, "kind", None) != C.PNCOUNTER:
        assert c4[-1] == 1.0


# ~5 s for crdt (flight data, the log-PR rebalance): the integer-target
# until cond stays pinned in-gate by the log and txn cases (the same
# converged-count compare) and the CLI crdt run (no --curve) smokes the
# single-device until driver; the crdt single-vs-sharded depth runs
# under -m slow
@pytest.mark.parametrize("name", [
    pytest.param("crdt", marks=pytest.mark.slow), "log", "txn"])
def test_until_driver_integer_target(name):
    """The while_loop driver's cond is an exact integer converged-count
    compare; single and sharded agree on rounds and the final value."""
    from gossip_tpu.models.payload import simulate_until_payload
    from gossip_tpu.parallel.sharded_payload import (
        simulate_until_payload_sharded)
    run = RunConfig(seed=0, max_rounds=24, target_coverage=1.0)
    topo = G.complete(32)
    cfg = _CFGS[name]
    r1, c1, m1, f1, t1 = simulate_until_payload(cfg, _PROTO, topo, run,
                                                _CFAULT)
    r4, c4, m4, f4, t4 = simulate_until_payload_sharded(
        cfg, _PROTO, topo, run, _mesh(), _CFAULT)
    assert (r1, c1, t1) == (r4, c4, t4)
    assert c1 == 1.0 and r1 < 24


# -- loud refusals -----------------------------------------------------

@pytest.mark.parametrize("cfg,late", [
    (CrdtConfig(kind="gcounter"),
     CrdtConfig(kind="gcounter", adds=((0, 100, 5),))),
    (LogConfig(), LogConfig(sends=((0, 0, 100, 1),))),
    (TxnConfig(), TxnConfig(writes=((0, 0, 100, 1),))),
], ids=_ROWS)
def test_rejections_are_loud(cfg, late):
    from gossip_tpu.models.payload import (make_payload_round,
                                           simulate_until_payload)
    with pytest.raises(ValueError, match="pull exchange only"):
        make_payload_round(cfg, ProtocolConfig(mode=C.PUSH),
                           G.complete(8))
    if isinstance(cfg, CrdtConfig):
        with pytest.raises(ValueError, match="no exchange driver"):
            make_payload_round(CrdtConfig(kind="vclock"),
                               ProtocolConfig(mode=C.PULL),
                               G.complete(8))
    # an injection the loop can never fire makes ground truth
    # unreachable by construction — drivers reject it loudly instead
    # of quietly reporting converged:false
    with pytest.raises(ValueError, match="can never fire"):
        simulate_until_payload(late, _PROTO, G.complete(8),
                               RunConfig(seed=0, max_rounds=8))


# -- the round-metrics convergence column ------------------------------

# ~6 s for crdt (flight data, the log-PR rebalance): the payload-column
# recorder mechanism (RM.init flag -> record -> emit, zero-impact
# bitwise) is pinned in-gate by the log and txn cases, and the
# value_conv column itself stays asserted in-gate on the committed
# record (test_committed_crdt_artifact_verdict); the live crdt
# emission depth runs under -m slow
@pytest.mark.parametrize("name,column", [
    pytest.param("crdt", "value_conv", marks=pytest.mark.slow),
    ("log", "log_conv"), ("txn", "txn_conv")], ids=_ROWS)
def test_round_metrics_emitted_and_bitwise_free(tmp_path, name, column):
    """With an active run ledger the sharded drivers flush a
    round_metrics stack carrying the row's convergence column (+ the
    nemesis columns under churn); recording must not move the
    trajectory bitwise (the ops/round_metrics zero-impact contract)."""
    from gossip_tpu.parallel.sharded_payload import (
        simulate_curve_payload_sharded)
    from gossip_tpu.utils import telemetry
    run = RunConfig(seed=0, max_rounds=12, target_coverage=1.0)
    topo = G.complete(32)
    cfg = _CFGS[name]
    # metrics-off reference
    c0, _, f0, _ = simulate_curve_payload_sharded(cfg, _PROTO, topo, run,
                                                  _mesh(), _CFAULT)
    path = str(tmp_path / f"{name}_metrics.jsonl")
    led = telemetry.Ledger(path)
    prev = telemetry.activate(led)
    try:
        c1, _, f1, _ = simulate_curve_payload_sharded(
            cfg, _PROTO, topo, run, _mesh(), _CFAULT)
    finally:
        telemetry.activate(prev)
        led.close()
    assert (np.asarray(c0) == np.asarray(c1)).all()
    assert (np.asarray(f0.val) == np.asarray(f1.val)).all()
    evs = telemetry.load_ledger(path)
    rms = [e for e in evs if e.get("ev") == "round_metrics"]
    assert rms
    e = rms[-1]
    assert e["driver"] == f"simulate_curve_{name}_sharded"
    assert len(e[column]) == e["rounds"] == 12
    assert e["totals"][f"{column}_final"] == pytest.approx(
        float(c1[-1]), abs=1e-4)
    # nemesis columns ride the same stack under the fault program
    assert e["totals"]["dropped"] > 0
    assert any(p > 0 for p in e["cut_pairs"])


# -- one operand tail, both frames -------------------------------------

@pytest.mark.parametrize("cfg", [
    CrdtConfig(kind="orset", elements=40),
    LogConfig(keys=4, capacity=8),
    TxnConfig(keys=8, txns=16)], ids=_ROWS)
def test_rows_share_one_operand_tail_on_both_frames(cfg):
    """Every row builds its tabled step through the SAME frame on one
    device and on a 4-device mesh: topology tables, then the row's
    injection operands, then the schedule, then the liar program
    outermost — the layout the drivers peel back off — and one step
    from round 0 lands the same rows on both placements."""
    import jax

    from gossip_tpu.models.payload import (init_payload_state,
                                           make_payload_round, row_for)
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.parallel.sharded_payload import (
        init_sharded_payload_state, make_sharded_payload_round)
    row = row_for(cfg)
    n = 30                                   # pads to 32 on the mesh
    topo = G.build(TopologyConfig(family="erdos_renyi", n=n, p=0.3,
                                  seed=3))
    fault = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
        events=((3, 2, 5),), partitions=((0, 4, 16),)),
        byz=(ByzConfig(liars=((3, 0, "inflate", 5),), quorum=2)
             if row.byz else None))
    proto = ProtocolConfig(mode=C.PULL, fanout=3)
    run = RunConfig(seed=0, max_rounds=8)
    mesh = _mesh()
    step1, t1 = make_payload_round(cfg, proto, topo, fault, tabled=True)
    step4, t4 = make_sharded_payload_round(cfg, proto, topo, mesh, fault,
                                           tabled=True)
    inj = row.inject_args(cfg, n)
    n_byz = NE.N_BYZ_OPERANDS if row.byz else 0
    assert len(t1) == len(t4) == (2 + len(inj) + NE.N_SCHED_OPERANDS
                                  + n_byz)
    # topology first: the mesh pads rows, never reorders them
    for a, b in zip(t1[:2], t4[:2]):
        assert (np.asarray(a) == np.asarray(b)[:n]).all()
    # then the injection operands, bitwise the same on both
    for a, b, c in zip(t1[2:2 + len(inj)], t4[2:2 + len(inj)], inj):
        assert (np.asarray(a) == np.asarray(c)).all()
        assert (np.asarray(b) == np.asarray(c)).all()
    # the byz tail is outermost and peels off first, then the schedule
    head1, byz1 = NE.split_byz(NE.get_byz(fault), t1)
    head4, byz4 = NE.split_byz(NE.get_byz(fault), t4)
    assert (byz1 is None) == (byz4 is None) == (not row.byz)
    assert len(NE.split_tables(NE.get(fault), head1)[0]) == 2 + len(inj)
    assert len(NE.split_tables(NE.get(fault), head4)[0]) == 2 + len(inj)
    s1, _ = jax.jit(step1)(init_payload_state(run, cfg, n), *t1)
    s4, _ = jax.jit(step4)(
        init_sharded_payload_state(run, cfg, topo, mesh), *t4)
    assert (np.asarray(s1.val) == np.asarray(s4.val)[:n]).all()
    assert float(s1.msgs) == float(s4.msgs)


# -- CLI ---------------------------------------------------------------

def _cli_crdt(cli, capsys):
    rc = cli.main(["crdt", "--type", "gcounter", "--n", "32",
                   "--max-rounds", "24", "--partition", "0:4:16",
                   "--churn-event", "3:2:5", "--drop-ramp",
                   "1:3:0.0:0.2", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["mode"] == "crdt" and out["type"] == "gcounter"
    assert out["converged"] is True and out["value_conv"] == 1.0
    assert out["truth_value"] > 0 and out["fault_program"] is True
    # scripted adds + curve
    rc = cli.main(["crdt", "--type", "pncounter", "--n", "16",
                   "--add", "0:0:9", "--add", "1:1:-4", "--curve",
                   "--max-rounds", "12", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["truth_value"] == 5
    assert out["curve"][-1] == 1.0
    # validation surfaces as a clean CLI error, never a traceback
    rc = cli.main(["crdt", "--type", "gcounter", "--add", "0:0:-1",
                   "--no-compile-cache"])
    assert rc == 2
    assert "positive" in capsys.readouterr().err


def _cli_log(cli, capsys):
    rc = cli.main(["log", "--n", "32", "--max-rounds", "24",
                   "--partition", "0:4:16", "--churn-event", "3:2:5",
                   "--drop-ramp", "1:3:0.0:0.2", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["mode"] == "log"
    assert out["converged"] is True and out["log_conv"] == 1.0
    assert out["truth"]["total_entries"] > 0
    assert out["fault_program"] is True
    # scripted sends/commits + curve
    rc = cli.main(["log", "--n", "16", "--keys", "2",
                   "--send", "0:0:0:9", "--send", "1:0:1:4",
                   "--commit", "2:0:3:1", "--curve",
                   "--max-rounds", "12", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["truth"] == {"lens": [2, 0], "committed": [1, 0],
                            "total_entries": 2}
    assert out["curve"][-1] == 1.0
    # validation surfaces as a clean CLI error, never a traceback
    rc = cli.main(["log", "--send", "0:0:0:0", "--no-compile-cache"])
    assert rc == 2
    assert "values must be >= 1" in capsys.readouterr().err


def _cli_txn(cli, capsys):
    rc = cli.main(["txn", "--n", "32", "--max-rounds", "24",
                   "--partition", "0:4:16", "--churn-event", "3:2:5",
                   "--drop-ramp", "1:3:0.0:0.2", "--zipf-alpha", "1.3",
                   "--hot-key", "0.4", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["mode"] == "txn"
    assert out["converged"] is True and out["txn_conv"] == 1.0
    assert out["truth"]["written_keys"] > 0
    assert out["fault_program"] is True
    assert out["zipf_alpha"] == 1.3 and out["hot_key"] == 0.4
    # scripted writes + curve: the owner tie-break is visible in truth
    rc = cli.main(["txn", "--n", "16", "--keys", "2",
                   "--write", "3:0:1:9", "--write", "5:0:1:7",
                   "--write", "1:1:0:4", "--curve",
                   "--max-rounds", "12", "--no-compile-cache"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["truth"]["values"] == [7, 4]
    assert out["truth"]["ts_owner"] == [5, 1]
    assert out["curve"][-1] == 1.0
    # validation surfaces as a clean CLI error, never a traceback
    rc = cli.main(["txn", "--write", "0:0:0:0", "--no-compile-cache"])
    assert rc == 2
    assert "values must be >= 1" in capsys.readouterr().err


@pytest.mark.parametrize("drive", [_cli_crdt, _cli_log, _cli_txn],
                         ids=_ROWS)
def test_cli_run_and_error_paths(capsys, monkeypatch, drive):
    from gossip_tpu import cli

    # in-process cli.main: --no-compile-cache writes
    # GOSSIP_COMPILE_CACHE="" into THIS process's env — monkeypatch
    # re-pins the session cache dir for the tests that follow
    monkeypatch.setenv("GOSSIP_COMPILE_CACHE",
                       os.environ.get("GOSSIP_COMPILE_CACHE", ""))
    drive(cli, capsys)
