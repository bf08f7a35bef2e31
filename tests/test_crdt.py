"""CRDT gossip subsystem (ops/crdt; its driver contract — the
partition-heal acceptance, mesh parity, until target, refusals, the
value_conv round-metrics column, CLI — is the crdt row of
tests/test_payload.py): algebraic merge pins (commutativity /
associativity / idempotence, BITWISE), injection lowering + acked-adds
ground truth, heal under the full fault program for every kind,
Maelstrom counter-workload surfaces, the committed artifact verdict
pin, and the no-CRDT regression guard (existing fabric trajectories
bitwise unchanged)."""

import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ChurnConfig, CrdtConfig, FaultConfig,
                               ProtocolConfig, RunConfig)
from gossip_tpu.topology import generators as G

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROTO = ProtocolConfig(mode=C.PULL, fanout=2)
# the full mixed fault program every parity/heal surface runs:
# crash/recover, permanent crash, open partition window, drop ramp
_CFAULT = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
    events=((3, 2, 5), (7, 1, -1)), partitions=((0, 6, 16),),
    ramp=(1, 4, 0.0, 0.3)))


# -- config validation -------------------------------------------------

def test_crdt_config_validation():
    CrdtConfig(kind="gcounter", adds=((0, 0, 5), (3, 2, 1)))
    CrdtConfig(kind="pncounter", adds=((0, 0, -5),))
    CrdtConfig(kind="orset", elements=40, set_adds=((0, 0), (39, 2)),
               set_removes=((0, 3),))
    with pytest.raises(ValueError, match="unknown CRDT kind"):
        CrdtConfig(kind="lww")
    with pytest.raises(ValueError, match="positive"):
        CrdtConfig(kind="gcounter", adds=((0, 0, -1),))
    with pytest.raises(ValueError, match="nonzero"):
        CrdtConfig(kind="pncounter", adds=((0, 0, 0),))
    with pytest.raises(ValueError, match="universe"):
        CrdtConfig(kind="gset", elements=8, set_adds=((8, 0),))
    with pytest.raises(ValueError, match="grow-only"):
        CrdtConfig(kind="gset", set_adds=((0, 0),),
                   set_removes=((0, 1),))
    with pytest.raises(ValueError, match="at most once"):
        CrdtConfig(kind="orset", set_adds=((2, 0), (2, 1)))
    with pytest.raises(ValueError, match="counter adds"):
        CrdtConfig(kind="orset", adds=((0, 0, 1),))
    with pytest.raises(ValueError, match="set_adds"):
        CrdtConfig(kind="gcounter", set_adds=((0, 0),))
    with pytest.raises(ValueError, match="horizon cap"):
        CrdtConfig(kind="gcounter", adds=((0, 10 ** 9, 1),))
    # vclock carries no injection program — a scripted one must be a
    # loud error, never a silent no-op
    with pytest.raises(ValueError, match="no injection program"):
        CrdtConfig(kind="vclock", adds=((0, 0, 5),))
    # a remove at-or-before its element's add would silently fork
    # add-wins into remove-wins — rejected (happens-after contract)
    with pytest.raises(ValueError, match="happen-after"):
        CrdtConfig(kind="orset", elements=8, set_adds=((5, 4),),
                   set_removes=((5, 2),))
    with pytest.raises(ValueError, match="happen-after"):
        CrdtConfig(kind="orset", set_removes=((5, 0),))  # default add @0
    # a remove of a never-added element is a harmless no-op: allowed
    CrdtConfig(kind="orset", elements=8, set_adds=((1, 0),),
               set_removes=((5, 0),))
    # horizon: last injection round + 1
    assert CrdtConfig(kind="gcounter", adds=((0, 7, 1),)).horizon() == 8


# -- algebraic pins: the join-semilattice laws, bitwise ----------------

def _random_state(kind, n, elements, rng):
    from gossip_tpu.ops import crdt as CR
    if kind in C.CRDT_SET_KINDS:
        w = 2 * ((elements + 31) // 32)
        return rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    s = CR.shard_columns(kind, n)
    return rng.integers(0, 1000, size=(n, s), dtype=np.int32)


def _assert_merge_laws(kind, seeds, n=16, elements=40):
    import jax.numpy as jnp

    from gossip_tpu.ops import crdt as CR
    for seed in seeds:
        rng = np.random.default_rng(seed)
        a, b, c = (jnp.asarray(_random_state(kind, n, elements, rng))
                   for _ in range(3))
        ab = np.asarray(CR.merge(kind, a, b))
        ba = np.asarray(CR.merge(kind, b, a))
        assert (ab == ba).all(), f"{kind}: merge not commutative"
        abc1 = np.asarray(CR.merge(kind, CR.merge(kind, a, b), c))
        abc2 = np.asarray(CR.merge(kind, a, CR.merge(kind, b, c)))
        assert (abc1 == abc2).all(), f"{kind}: merge not associative"
        aa = np.asarray(CR.merge(kind, a, a))
        assert (aa == np.asarray(a)).all(), f"{kind}: not idempotent"
        # merge is an upper bound of both operands (join-semilattice)
        assert (np.asarray(CR.merge(kind, jnp.asarray(ab), a))
                == ab).all(), f"{kind}: merge not an upper bound"


def test_merge_algebra_bitwise_smoke():
    """Commutativity / associativity / idempotence on random states,
    BITWISE, for every kind (the in-gate smoke; depth under -m slow)."""
    for kind in C.CRDT_KINDS:
        _assert_merge_laws(kind, seeds=range(3))


@pytest.mark.slow
def test_merge_algebra_bitwise_depth():
    for kind in C.CRDT_KINDS:
        _assert_merge_laws(kind, seeds=range(50), n=33, elements=97)


def test_vclock_tick_and_merge():
    """The vector-clock kernel: owner-only ticks, merge = elementwise
    max dominates both histories."""
    import jax.numpy as jnp

    from gossip_tpu.ops import crdt as CR
    n = 4
    vc = jnp.zeros((n, n), jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)
    alive = jnp.asarray([True, True, False, True])
    vc = CR.vclock_tick(vc, ids, alive, n)
    assert np.asarray(vc).diagonal().tolist() == [1, 1, 0, 1]
    other = jnp.zeros((n, n), jnp.int32).at[:, 2].set(7)
    merged = np.asarray(CR.merge(C.VCLOCK, vc, other))
    assert (merged[:, 2] == 7).all()
    # max dominates both histories: node 2's own entry takes the
    # larger observed clock, everyone else keeps their tick
    assert merged.diagonal().tolist() == [1, 1, 7, 1]


# -- injection lowering + acked-adds ground truth ----------------------

def test_ground_truth_acked_adds_semantics():
    """An injection is applied iff its owner is alive at the injection
    round AND eventually alive — a permanently-dead owner contributes
    nothing, an owner down at the round misses its one-shot add, a
    temporarily-down-later owner's add stays in (it recovers and must
    re-disseminate)."""
    from gossip_tpu.ops import crdt as CR
    n = 8
    cfg = CrdtConfig(kind="gcounter",
                     adds=((0, 0, 10),   # healthy: applied
                           (1, 2, 20),   # owner down [1, 4): missed
                           (2, 0, 30),   # owner dies forever at 3: out
                           (3, 5, 40)))  # owner down [1, 4), adds at 5
    f = FaultConfig(churn=ChurnConfig(events=((1, 1, 4), (2, 3, -1),
                                              (3, 1, 4))))
    truth = np.asarray(CR.ground_truth(cfg, CR.inject_args(cfg, n), f,
                                       n, 0))
    assert truth.tolist() == [10, 0, 0, 40, 0, 0, 0, 0]
    # fault-free: everything applies
    truth0 = np.asarray(CR.ground_truth(cfg, CR.inject_args(cfg, n),
                                        None, n, 0))
    assert truth0.tolist() == [10, 20, 30, 40, 0, 0, 0, 0]
    # the default program's closed form: node j adds 1 + j%7 at round 0
    d = CrdtConfig(kind="gcounter")
    td = np.asarray(CR.ground_truth(d, CR.inject_args(d, n), None, n, 0))
    assert td.tolist() == [1 + j % 7 for j in range(n)]
    # out-of-range scripted ids are a loud error, not a silent no-op
    with pytest.raises(ValueError, match="node ids"):
        CR.inject_args(CrdtConfig(kind="gcounter", adds=((99, 0, 1),)),
                       n)


def test_set_injection_owner_rotation_and_tombstones():
    from gossip_tpu.ops import crdt as CR
    n = 8
    cfg = CrdtConfig(kind="orset", elements=40, set_removes=((5, 3),))
    truth = np.asarray(CR.ground_truth(cfg, CR.inject_args(cfg, n),
                                       None, n, 0))
    members = np.asarray(CR.set_members(truth[None, :]))[0]
    bits = sum(bin(int(x)).count("1") for x in members)
    assert bits == 39                       # 40 added, element 5 removed
    # a permanent death excludes every element that node owns
    f = FaultConfig(churn=ChurnConfig(events=((7, 1, -1),)))
    trc = np.asarray(CR.ground_truth(cfg, CR.inject_args(cfg, n), f,
                                     n, 0))
    mc = np.asarray(CR.set_members(trc[None, :]))[0]
    bits_c = sum(bin(int(x)).count("1") for x in mc)
    # elements 7, 15, 23, 31, 39 owned by node 7 -> 5 adds excluded
    # (element 5's remove still applies: owner node 5 is alive)
    assert bits_c == 40 - 5 - 1


# -- partition-heal value convergence (the acceptance gate) ------------

def test_heal_under_full_fault_program_pncounter():
    """The PN-counter reaches exact ground truth on the eventual-alive
    set under the full mixed fault program (event + permanent crash +
    window + ramp) — the integer-exact eventual-consistency invariant.
    (In-gate this covers the one kind the parity tests in
    tests/test_payload.py do not already drive to 1.0 under _CFAULT;
    the all-kinds sweep runs in the slow tier — tier-1 wall budget.)"""
    from gossip_tpu.models.payload import simulate_curve_payload
    run = RunConfig(seed=0, max_rounds=24, target_coverage=1.0)
    conv, _, final, _ = simulate_curve_payload(
        CrdtConfig(kind="pncounter"), _PROTO, G.complete(32), run,
        _CFAULT)
    assert conv[-1] == 1.0, list(conv)


@pytest.mark.slow
def test_heal_under_full_fault_program_all_kinds():
    from gossip_tpu.models.payload import simulate_curve_payload
    run = RunConfig(seed=0, max_rounds=24, target_coverage=1.0)
    topo = G.complete(32)
    for cfg in (CrdtConfig(kind="gcounter"),
                CrdtConfig(kind="pncounter"),
                CrdtConfig(kind="orset", elements=40,
                           set_removes=((5, 3),)),
                CrdtConfig(kind="gset", elements=40)):
        conv, _, final, _ = simulate_curve_payload(cfg, _PROTO, topo,
                                                   run, _CFAULT)
        assert conv[-1] == 1.0, (cfg.kind, list(conv))


# -- Maelstrom counter workload (the Gossip Glomers invariant) ---------

def test_workload_startup_failure_stops_spawned_nodes():
    """A topology failure inside the shared _start_workload scaffolding
    must kill the already-spawned node processes, not strand them
    stdin-blocked (the callers' try/finally only guards after it
    returns)."""
    import asyncio

    from gossip_tpu.runtime import maelstrom_harness as MH

    async def main():
        seen = {}
        orig = MH.MaelstromHarness.set_topology

        async def boom(self, topo):
            seen["h"] = self
            raise RuntimeError("no topology_ok")

        MH.MaelstromHarness.set_topology = boom
        try:
            with pytest.raises(RuntimeError, match="no topology_ok"):
                await MH._start_workload(2, ops=4, rate=50.0,
                                         latency=0.001,
                                         topology="line",
                                         partition_mid=False, argv=None)
        finally:
            MH.MaelstromHarness.set_topology = orig
        h = seen["h"]
        assert h.procs
        for nid, proc in h.procs.items():
            assert proc.returncode is not None, (
                f"node {nid} leaked after startup failure")

    asyncio.run(main())


def test_counter_workload_invariant_through_partition():
    """run_counter_workload: every node's final read equals the sum of
    acked adds — EXACT integer equality — with a harness-injected
    partition cutting a mid-cluster link mid-run (the fault-tolerance
    variant of Gossip Glomers challenge #4)."""
    import asyncio

    from gossip_tpu.runtime.maelstrom_harness import run_counter_workload
    stats = asyncio.run(run_counter_workload(
        4, ops=8, rate=25.0, latency=0.001, partition_mid=True, seed=3))
    assert stats["invariant_ok"] is True
    assert stats["partitioned"] is True
    assert stats["final_values"] == [stats["expected"]] * 4
    # per-workload stats surface (the shared accounting): adds are
    # client ops, msgs_per_op counts them
    assert stats["ops"] == 8 and stats["broadcast_ops"] == 0
    assert stats["msgs_per_op"] > 0
    assert stats["op_latency_ms"]["p99"] >= stats["op_latency_ms"]["p50"]


# -- committed artifact + provenance gate ------------------------------

def test_committed_crdt_artifact_verdict():
    """The committed CRDT convergence record
    (artifacts/ledger_crdt_r13.jsonl, tools/crdt_capture.py):
    provenance-carrying; G-Counter, PN-Counter AND OR-Set each reached
    value_conv == 1.0 under the mixed fault program with bitwise
    1-vs-4-device parity; the drivers' round_metrics events carry the
    value_conv column — re-asserted here so the verdict can never
    rot."""
    from gossip_tpu.utils import telemetry
    path = os.path.join(_REPO, "artifacts", "ledger_crdt_r13.jsonl")
    evs = telemetry.load_ledger(path, run="last")
    assert evs[0]["ev"] == "provenance"
    assert len(evs[0]["git_commit"]) == 40
    fp = [e for e in evs if e.get("ev") == "crdt_fault_program"][-1]
    assert fp["partitions"] and fp["ramp"] and len(fp["events"]) == 2
    scen = {e["crdt"]: e for e in evs
            if e.get("ev") == "crdt_scenario"}
    assert set(scen) == {"gcounter", "pncounter", "orset"}
    for name, e in scen.items():
        assert e["value_conv_final"] == 1.0, name
        assert e["mesh_parity_bitwise"] is True, name
        assert e["ok"] is True, name
        # convergence STALLED while the committed window was open
        assert all(c < 1.0
                   for c in e["value_conv_curve"][:6]), name
    assert [e for e in evs if e.get("ev") == "crdt_verdict"][-1]["ok"] \
        is True
    rms = [e for e in evs if e.get("ev") == "round_metrics"]
    assert rms and all("value_conv" in e for e in rms)
    assert all(e["totals"]["value_conv_final"] == 1.0 for e in rms)


def test_validate_artifacts_requires_provenance_on_crdt(tmp_path):
    """``*crdt*`` artifacts can never be grandfathered in without
    provenance (the nemesis/crashloop rule, extended)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "validate_artifacts",
        os.path.join(_REPO, "tools", "validate_artifacts.py"))
    va = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(va)
    bad = tmp_path / "crdt_convergence_rXX.jsonl"
    bad.write_text(json.dumps({"ev": "crdt_scenario"}) + "\n")
    problems = va.validate_file(str(bad))
    assert problems and any("attributable" in p for p in problems)
    badj = tmp_path / "ledger_crdt_sweep.json"
    badj.write_text(json.dumps({"value_conv": 1.0}))
    assert va.validate_file(str(badj))


# -- no-CRDT regression guard ------------------------------------------

def _assert_fingerprints(names):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import _churn_surfaces as CS
    finally:
        sys.path.pop(0)
    with open(CS.DATA) as f:
        golden = json.load(f)["digests"]
    for name in names:
        runner, fault_of = CS.SURFACES[name]
        assert runner(fault_of()) == golden[f"churn:{name}"], (
            f"churn:{name} moved under the CRDT PR")
        assert runner(CS._static_fault()) == golden[f"static:{name}"], (
            f"static:{name} moved under the CRDT PR")


# depth tier since the fleet-PR rebalance (tier-1 wall budget, ~8 s):
# the packed-sharded trajectory stays pinned in-gate by the per-mode
# packed sharded-vs-unpacked parity params (test_packed) and the
# nemesis dense digest (test_nemesis's in-gate subset), and the CRDT
# payload parities (gcounter dense + orset packed mesh parity, both
# in-gate) would surface any fabric move through the payload
# trajectories; this guard's golden-digest re-proof runs with the
# full matrix under -m slow
@pytest.mark.slow
def test_no_crdt_fabric_fingerprints_unchanged():
    """The CRDT subsystem rides the fabric without moving it: the
    packed-sharded broadcast trajectory — churn AND static — is
    BITWISE the golden digest captured before this PR
    (tests/data/churn_fingerprints_r06.json).  Packed sharded is the
    pick because the CRDT payload shares ITS exchange shape
    (all_gather of word rows); dense_sharded is already re-verified
    in-gate by test_nemesis, and the rumor/SWIM surfaces run in the
    slow twin below + test_nemesis's full matrix."""
    _assert_fingerprints(["packed_sharded"])


@pytest.mark.slow
def test_no_crdt_fabric_fingerprints_unchanged_depth():
    _assert_fingerprints(["rumor_single", "packed_single"])
