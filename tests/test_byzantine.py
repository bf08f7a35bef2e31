"""Byzantine nemesis (ops/nemesis liar programs + the array-form
defenses): config validation, the defense-bypass pin (defend=True
converges EXACTLY where the undefended control arm provably diverges),
the pure-operand compile pin (K mixed byz programs through ONE
executable, salted re-entry compiles nothing), the no-byz fingerprint
guard (an empty or inactive liar table leaves existing trajectories
bitwise unchanged), capability rejections (engines without liar
transforms reject ``fault.byz`` loudly), and the committed artifact +
provenance gates (tools/byzantine_capture / validate_artifacts)."""

import json
import os

import numpy as np
import pytest

from gossip_tpu import config as C
from gossip_tpu.config import (ByzConfig, ChurnConfig, CrdtConfig,
                               FaultConfig, ProtocolConfig, RunConfig)
from gossip_tpu.topology import generators as G

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the committed scenario (tools/byzantine_capture): a 16-node pull
# fabric, one fail-stop churn event riding WITH the liar program —
# node 3 inflates foreign components from round 2, node 11 corrupts
# them with a high-bit xor from round 0
_N = 16
_BPROTO = ProtocolConfig(mode=C.PULL, fanout=3)
_BRUN = RunConfig(seed=7, max_rounds=100, target_coverage=1.0)
_LIARS = ((3, 2, "inflate", 5), (11, 0, "corrupt", 1 << 20))
_BFAULT = FaultConfig(churn=ChurnConfig(events=((4, 6, 12),)),
                      byz=ByzConfig(liars=_LIARS, quorum=2))


def _mesh(k=4):
    from gossip_tpu.parallel.sharded import make_mesh
    return make_mesh(k)


# -- config validation -------------------------------------------------

def test_byz_config_validation():
    ByzConfig(liars=((0, 0, "inflate", 1), (5, 3, "corrupt", 7)))
    ByzConfig(liars=(), quorum=3)  # the empty program is legal
    with pytest.raises(ValueError, match="unknown byz kind"):
        ByzConfig(liars=((0, 0, "lie", 1),))
    with pytest.raises(ValueError, match="at most once"):
        ByzConfig(liars=((0, 0, "inflate", 1), (0, 2, "corrupt", 2)))
    with pytest.raises(ValueError, match="quorum=0"):
        ByzConfig(liars=((0, 0, "inflate", 1),), quorum=0)
    with pytest.raises(ValueError, match="carry-save chain"):
        ByzConfig(liars=((0, 0, "inflate", 1),), quorum=9)
    with pytest.raises(ValueError, match=">= 0"):
        ByzConfig(liars=((-1, 0, "inflate", 1),))
    # FaultConfig carries the program next to the churn schedule
    f = FaultConfig(byz=ByzConfig(liars=_LIARS))
    assert f.byz.liars == _LIARS


# -- the defense-bypass pin --------------------------------------------

def test_defended_exact_where_undefended_control_diverges():
    """THE acceptance shape: under the mixed fail-stop + liar program
    the DEFENDED honest eventual-alive set converges EXACTLY
    (byz_conv == denominator/denominator, integer count) while the
    UNDEFENDED control arm — the same executable shape, defenses
    off — provably diverges.  A defense whose absence changes nothing
    defends nothing."""
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.ops import crdt as CR
    from gossip_tpu.ops import nemesis as NE
    topo = G.complete(_N)
    cfg = CrdtConfig(kind="gcounter")
    _, _, fin_u, _ = simulate_curve_payload(cfg, _BPROTO, topo, _BRUN,
                                            _BFAULT, defend=False)
    conv_d, _, fin_d, _ = simulate_curve_payload(cfg, _BPROTO, topo,
                                                 _BRUN, _BFAULT,
                                                 defend=True)
    inj = CR.inject_args(cfg, _N)
    truth = CR.ground_truth(cfg, inj, _BFAULT, _N, 0)
    honest = NE.honest_mask(_BFAULT, _N)
    alive_h = CR.eventual_alive_crdt(_BFAULT, _N, 0) & honest
    comp = CR.honest_component_mask(cfg, _N, 0, honest)
    denom = int(alive_h.sum())
    assert denom == _N - len(_LIARS)  # churned node 4 recovers
    cnt_d = int(CR.byz_converged_count(cfg, fin_d.val, truth,
                                       alive_h, comp))
    cnt_u = int(CR.byz_converged_count(cfg, fin_u.val, truth,
                                       alive_h, comp))
    assert cnt_d == denom            # defended: exact, all honest
    assert cnt_u < denom             # undefended: provably diverged
    assert conv_d[-1] == 1.0         # the curve agrees with the count
    # the liars' rows are NOT in the denominator: honest-set metric
    assert not bool(alive_h[3]) and not bool(alive_h[11])


# -- pure-operand proof: K programs, one executable --------------------

def test_byz_programs_compile_once_and_salted_reentry_is_free(
        assert_compiles):
    """The liar program is DATA, not code: K mixed byz scenarios —
    different liars, rounds, kinds, args AND quorum — run through ONE
    jitted sharded step (tabled=True puts the byz arrays on the
    argument tail), so after the first call every salted re-entry
    compiles NOTHING."""
    import jax
    from gossip_tpu.parallel.sharded_payload import (
        init_sharded_payload_state, make_sharded_payload_round)
    topo = G.complete(32)
    cfg = CrdtConfig(kind="gcounter")
    run = RunConfig(seed=0, max_rounds=8, target_coverage=1.0)
    mesh = _mesh()
    base = FaultConfig(drop_prob=0.05, seed=2,
                       churn=ChurnConfig(events=((3, 2, 5),)),
                       byz=ByzConfig(liars=((3, 1, "inflate", 5),),
                                     quorum=2))
    step, tables = make_sharded_payload_round(cfg, _BPROTO, topo, mesh,
                                           base, 0, tabled=True,
                                           defend=True)
    step = jax.jit(step)
    state = init_sharded_payload_state(run, cfg, topo, mesh)
    with assert_compiles(4, at_most=True):  # first call + auxiliaries
        jax.block_until_ready(step(state, *tables))
    salts = [
        ByzConfig(liars=((5, 2, "equivocate", 9), (11, 1, "replay", 0),
                         (13, 0, "inflate", 3)), quorum=3),
        ByzConfig(liars=((7, 0, "corrupt", 1 << 18),), quorum=1),
        ByzConfig(liars=((1, 3, "replay", 2), (30, 0, "equivocate", 4)),
                  quorum=2),
    ]
    with assert_compiles(0):
        for bz in salts:
            salted = FaultConfig(drop_prob=0.05, seed=2,
                                 churn=ChurnConfig(events=((3, 2, 5),)),
                                 byz=bz)
            _, tk = make_sharded_payload_round(cfg, _BPROTO, topo, mesh,
                                            salted, 0, tabled=True,
                                            defend=True)
            jax.block_until_ready(step(state, *tk))


# -- no-byz fingerprint guard ------------------------------------------

def test_inactive_liar_table_leaves_trajectory_bitwise_unchanged():
    """Threading the byz operands through the kernels must cost the
    existing fabric NOTHING semantically: a fault program with an
    EMPTY liar table, or one whose liars only start past the horizon,
    reproduces the no-byz trajectory BITWISE (curve, final state,
    message count) — on both arms of the defend gate's control side."""
    from gossip_tpu.models.payload import simulate_curve_payload
    topo = G.complete(_N)
    cfg = CrdtConfig(kind="gcounter")
    run = RunConfig(seed=3, max_rounds=16, target_coverage=1.0)
    churn = ChurnConfig(events=((3, 2, 5),))
    plain = FaultConfig(drop_prob=0.05, seed=1, churn=churn)
    empty = FaultConfig(drop_prob=0.05, seed=1, churn=churn,
                        byz=ByzConfig(liars=(), quorum=2))
    dormant = FaultConfig(drop_prob=0.05, seed=1, churn=churn,
                          byz=ByzConfig(liars=((3, 900, "inflate", 5),
                                               (7, 900, "corrupt", 1)),
                                        quorum=2))
    c0, _, f0, t0 = simulate_curve_payload(cfg, _BPROTO, topo, run,
                                           plain)
    for fault in (empty, dormant):
        c1, _, f1, t1 = simulate_curve_payload(cfg, _BPROTO, topo, run,
                                               fault)
        assert (np.asarray(c0) == np.asarray(c1)).all()
        assert (np.asarray(f0.val) == np.asarray(f1.val)).all()
        assert float(f0.msgs) == float(f1.msgs)
        assert t0 == t1


# -- capability rows: loud rejections ----------------------------------

def test_engines_without_liar_transforms_reject_byz_loudly():
    """Only the crdt-pull and register-pull exchanges render liar
    transforms and carry the defenses — every other engine must
    reject ``fault.byz`` loudly (the no-silent-substitution policy),
    even when the program carries no churn schedule at all."""
    from gossip_tpu.config import LogConfig
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.ops import nemesis as NE
    byz_only = FaultConfig(byz=ByzConfig(liars=_LIARS, quorum=2))
    with pytest.raises(ValueError, match="byz"):
        NE.check_supported(byz_only, engine="swim-probe")
    with pytest.raises(ValueError, match="byz"):
        simulate_curve_payload(LogConfig(), _BPROTO, G.complete(_N),
                               _BRUN, byz_only)


# -- committed artifact + provenance gate ------------------------------

def test_committed_byz_artifact_verdict():
    """The committed byzantine convergence record
    (artifacts/ledger_byz_r25.jsonl, tools/byzantine_capture.py):
    provenance-carrying; the defended honest eventual-alive set
    converged EXACTLY (count == denominator) under the mixed
    fail-stop + liar program for BOTH the gcounter and LWW-register
    payloads while the undefended control arm diverged, with bitwise
    1-vs-4-device mesh parity; the sharded runs' round_metrics events
    carry the byz_conv column at 1.0 — re-asserted here so the
    verdict can never rot."""
    from gossip_tpu.utils import telemetry
    path = os.path.join(_REPO, "artifacts", "ledger_byz_r25.jsonl")
    evs = telemetry.load_ledger(path, run="last")
    assert evs[0]["ev"] == "provenance"
    assert len(evs[0]["git_commit"]) == 40
    fp = [e for e in evs if e.get("ev") == "byz_fault_program"][-1]
    assert fp["quorum"] == 2 and len(fp["liars"]) == 2
    assert fp["churn_events"]  # MIXED: fail-stop rides with the liars
    scen = [e for e in evs if e.get("ev") == "byz_scenario"][-1]
    assert scen["payload"] == "gcounter"
    assert scen["defended_exact"] is True
    assert scen["defended_count"] == scen["denominator"] > 0
    assert scen["undefended_diverged"] is True
    assert scen["undefended_count"] < scen["denominator"]
    assert scen["mesh_parity_bitwise"] is True and scen["ok"] is True
    assert scen["defended_curve"][-1] == 1.0
    assert scen["undefended_curve"][-1] < 1.0
    tscen = [e for e in evs if e.get("ev") == "byz_txn_scenario"][-1]
    assert tscen["defended_exact"] is True
    assert tscen["undefended_diverged"] is True
    assert tscen["mesh_parity_bitwise"] is True and tscen["ok"] is True
    assert [e for e in evs if e.get("ev") == "byz_verdict"][-1]["ok"] \
        is True
    rms = [e for e in evs if e.get("ev") == "round_metrics"]
    assert rms and all("byz_conv" in e for e in rms)
    assert all(e["totals"]["byz_conv_final"] == 1.0 for e in rms)
    # the hw_refresh smoke twin exists and carries the same verdict
    smoke = os.path.join(_REPO, "artifacts",
                         "ledger_byz_r25.smoke.jsonl")
    sevs = telemetry.load_ledger(smoke, run="last")
    assert sevs[0]["ev"] == "provenance"
    sv = [e for e in sevs if e.get("ev") == "byz_verdict"][-1]
    assert sv["ok"] is True and sv["smoke"] is True


def test_validate_artifacts_requires_provenance_on_byz(tmp_path):
    """``*byz*``/``*byzantine*``/``*adversary*`` artifacts can never
    be grandfathered in without provenance (the nemesis/crashloop
    rule, extended): an unattributed adversary record is the exact
    claim the defense lattice exists to reject."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "validate_artifacts",
        os.path.join(_REPO, "tools", "validate_artifacts.py"))
    va = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(va)
    bad = tmp_path / "ledger_byz_rXX.jsonl"
    bad.write_text(json.dumps({"ev": "byz_scenario"}) + "\n")
    problems = va.validate_file(str(bad))
    assert problems and any("attributable" in p for p in problems)
    badj = tmp_path / "adversary_sweep.json"
    badj.write_text(json.dumps({"byz_conv": 1.0}))
    assert va.validate_file(str(badj))
    badb = tmp_path / "byzantine_record.jsonl"
    badb.write_text(json.dumps({"ev": "byz_verdict", "ok": True})
                    + "\n")
    assert va.validate_file(str(badb))
