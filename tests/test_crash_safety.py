"""Crash-tolerant fault programs (the utils/checkpoint crash contract).

Three layers under test:

* **Corrupt/partial checkpoints** — a truncated or foreign file raises
  ``ValueError`` NAMING the file (never a raw ``zipfile``/``KeyError``
  traceback), the CLI ``--resume`` refuses it with a one-line error,
  and a stale ``path + ".tmp"`` stranded by a kill between the tmp
  write and ``os.replace`` is cleaned on the next save and never read.
* **Resume == straight run under an ACTIVE fault program**, bitwise,
  for every checkpointed driver that came off the nemesis rejection
  list (SI single-device, sharded packed, rumor, SWIM, fused planes) —
  including a resume landing INSIDE an open partition window and
  mid-ramp, and the exact destroyed-message total carried across the
  kill (``extra['dropped']`` -> ``lost_prefix``).
* **No-churn checkpointed trajectories are unchanged**: the
  ``ckpt-static:*`` fingerprints in tests/data/churn_fingerprints_r06
  .json were captured from the PRE-lift tree (PR 6, git 2f4d850) and
  re-pinned on jax 0.9.0 (PR 21, after checking that the SI and
  fused-planes states equal their un-checkpointed runs and resume ==
  straight bitwise); the drivers must reproduce them bitwise.

The live SIGKILL harness is tools/crashloop.py (single-kill smoke at
the bottom; the committed 3-kill record is
artifacts/ledger_crashloop_r12.jsonl).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from gossip_tpu.config import (ChurnConfig, FaultConfig, ProtocolConfig,
                               RunConfig)
from gossip_tpu.topology import generators as G
from gossip_tpu.utils.checkpoint import (load_meta, load_state,
                                         run_with_checkpoints,
                                         save_state)

import _churn_surfaces as CS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": _REPO}

# events + partition window + drop ramp: every schedule feature the SI
# engines honor.  The partition window [2, 6) and ramp [1, 4) straddle
# the resume points below BY DESIGN: the kill lands inside an open
# window and mid-ramp.
_FAULT = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
    events=((3, 2, 5), (7, 1, -1)),
    partitions=((2, 6, 32),),
    ramp=(1, 4, 0.0, 0.3)))
_N = 64


def _cli(*argv):
    return subprocess.run([sys.executable, "-m", "gossip_tpu", *argv],
                          capture_output=True, text=True, cwd=_REPO,
                          env=CLI_ENV, timeout=240)


# ---------------------------------------------------------------------
# corrupt / partial checkpoints
# ---------------------------------------------------------------------

def _valid_checkpoint(tmp_path, name="ok.npz"):
    from gossip_tpu.models.state import init_state
    p = str(tmp_path / name)
    proto = ProtocolConfig(mode="pushpull", fanout=1)
    save_state(p, init_state(RunConfig(seed=0), proto, 16),
               extra_meta={"k": 1})
    return p


def test_load_corrupt_names_file(tmp_path):
    # truncated npz: a real checkpoint cut mid-archive
    p = _valid_checkpoint(tmp_path)
    raw = open(p, "rb").read()
    trunc = str(tmp_path / "trunc.npz")
    with open(trunc, "wb") as f:
        f.write(raw[:len(raw) // 3])
    for loader in (load_meta, load_state):
        with pytest.raises(ValueError, match="trunc.npz"):
            loader(trunc)
    # non-npz imposter
    imp = str(tmp_path / "imposter.npz")
    with open(imp, "wb") as f:
        f.write(b"not a zip archive at all")
    with pytest.raises(ValueError, match="imposter.npz"):
        load_meta(imp)
    # a missing file stays FileNotFoundError (absent != corrupt)
    with pytest.raises(FileNotFoundError):
        load_meta(str(tmp_path / "nope.npz"))


def test_load_foreign_npz_and_unknown_class(tmp_path):
    # a VALID npz that is not a gossip_tpu checkpoint: no __meta__
    foreign = str(tmp_path / "foreign.npz")
    np.savez(foreign, a=np.arange(3))
    with pytest.raises(ValueError, match="foreign.npz"):
        load_meta(foreign)
    # unknown state class / missing array entry named by the metadata
    bogus = str(tmp_path / "bogus.npz")
    np.savez(bogus, __meta__=json.dumps(
        {"cls": "NoSuchState", "fields": ["x"], "key_field": None}))
    with pytest.raises(ValueError, match="NoSuchState"):
        load_state(bogus)
    torn = str(tmp_path / "torn.npz")
    np.savez(torn, __meta__=json.dumps(
        {"cls": "SimState", "fields": ["seen"], "key_field": None}))
    with pytest.raises(ValueError, match="torn.npz"):
        load_state(torn)
    # incomplete metadata (keyed state, no key_impl): its OWN diagnosis,
    # never misreported as a truncated array write
    incomp = str(tmp_path / "incomplete.npz")
    np.savez(incomp, __meta__=json.dumps(
        {"cls": "SimState", "fields": ["seen", "base_key"],
         "key_field": "base_key"}), seen=np.zeros((4, 1), bool),
        base_key=np.zeros((2,), np.uint32))
    with pytest.raises(ValueError, match="incomplete"):
        load_state(incomp)


def test_load_mid_archive_corruption_names_file(tmp_path):
    """Corruption that leaves the zip central directory (at EOF)
    intact: np.load opens fine and __meta__ parses, then a MEMBER read
    fails its CRC — still the crash contract's ValueError naming the
    file, never a raw zipfile/zlib traceback."""
    p = _valid_checkpoint(tmp_path, "midrot.npz")
    raw = bytearray(open(p, "rb").read())
    # flip bytes inside the member data region (past the first local
    # headers, well before the central directory at EOF)
    mid = len(raw) // 2
    for i in range(mid, mid + 16):
        raw[i] ^= 0xFF
    with open(p, "wb") as f:
        f.write(raw)
    with pytest.raises(ValueError, match="midrot.npz"):
        load_state(p)
    p = _valid_checkpoint(tmp_path)
    good = load_meta(p)
    # a kill between the tmp write and os.replace strands the sibling
    with open(p + ".tmp", "wb") as f:
        f.write(b"partial garbage from a killed writer")
    # loads never look at it
    assert load_meta(p) == good
    # the next save removes the stranded partial before writing
    from gossip_tpu.models.state import init_state
    save_state(p, init_state(RunConfig(seed=1),
                             ProtocolConfig(mode="pushpull", fanout=1),
                             16), extra_meta={"k": 2})
    assert not os.path.exists(p + ".tmp")
    assert load_meta(p)["extra"] == {"k": 2}


def test_cli_resume_corrupt_checkpoint_clean_error(tmp_path):
    bad = str(tmp_path / "corrupt.npz")
    with open(bad, "wb") as f:
        f.write(b"PK\x03\x04 torn by a filesystem crash")
    r = _cli("run", "--mode", "pushpull", "--n", "64",
             "--max-rounds", "4", "--checkpoint", bad, "--resume")
    assert r.returncode == 2
    assert "error:" in r.stderr and "corrupt.npz" in r.stderr
    assert "Traceback" not in r.stderr


# ---------------------------------------------------------------------
# resume == straight run under an active fault program, bitwise
# ---------------------------------------------------------------------

def _si_leg(tmp_path, name, rounds, resume_state=None, lost_prefix=0.0):
    from gossip_tpu.models.si import make_si_round
    from gossip_tpu.models.state import init_state
    proto = ProtocolConfig(mode="pushpull", fanout=2, rumors=2)
    step, tables = make_si_round(proto, G.complete(_N), _FAULT, 0,
                                 tabled=True)
    state = (resume_state if resume_state is not None
             else init_state(RunConfig(seed=0), proto, _N))
    p = str(tmp_path / name)
    fin = run_with_checkpoints(step, state,
                               rounds - int(state.round), p, every=3,
                               step_args=tables, track_lost=True,
                               lost_prefix=lost_prefix)
    return fin, p


@pytest.mark.parametrize(
    "kill_at",
    [pytest.param(3, id="inside-partition-window-and-mid-ramp"),
     # the boundary variant is depth, not a distinct mechanism — slow
     # tier (tier-1 wall budget, ROADMAP gate)
     pytest.param(6, id="at-window-close", marks=pytest.mark.slow)])
def test_si_resume_under_fault_bitwise(tmp_path, kill_at):
    # kill_at=3 lands INSIDE the open partition window [2, 6) and past
    # the ramp start (mid-ramp); kill_at=6 resumes exactly at the heal
    full, pf = _si_leg(tmp_path, "full.npz", 10)
    half, ph = _si_leg(tmp_path, "half.npz", kill_at)
    lp = load_meta(ph)["extra"]["dropped"]
    res, _ = _si_leg(tmp_path, "half.npz", 10,
                     resume_state=load_state(ph), lost_prefix=lp)
    np.testing.assert_array_equal(np.asarray(full.seen),
                                  np.asarray(res.seen))
    assert float(full.msgs) == float(res.msgs)
    assert int(res.round) == 10
    # the destroyed-message total carries across the kill EXACTLY
    assert (load_meta(pf)["extra"]["dropped"]
            == load_meta(ph)["extra"]["dropped"])
    assert load_meta(pf)["extra"]["round"] == 10


# depth tier: see test_swim_resume_under_churn_bitwise's rationale
@pytest.mark.slow
def test_rumor_resume_under_fault_bitwise(tmp_path):
    from gossip_tpu.models.rumor import checkpointed_rumor
    proto = ProtocolConfig(mode="rumor", fanout=2, rumors=2, rumor_k=3)
    topo = G.complete(_N)

    def leg(name, rounds, resume_state=None, lost_prefix=0.0):
        return checkpointed_rumor(
            proto, topo, RunConfig(seed=0, max_rounds=rounds),
            str(tmp_path / name), every=3, fault=_FAULT,
            resume_state=resume_state, lost_prefix=lost_prefix)

    full, cov_f, _, _ = leg("full.npz", 10)
    leg("half.npz", 4)        # inside the partition window, mid-ramp
    lp = load_meta(str(tmp_path / "half.npz"))["extra"]["dropped"]
    res, cov_r, _, _ = leg("half.npz", 10,
                           resume_state=load_state(
                               str(tmp_path / "half.npz")),
                           lost_prefix=lp)
    for f in ("seen", "hot", "cnt"):
        np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                      np.asarray(getattr(res, f)))
    assert cov_f == cov_r
    assert (load_meta(str(tmp_path / "full.npz"))["extra"]["dropped"]
            == load_meta(str(tmp_path / "half.npz"))["extra"]["dropped"])


# ~11 s (txn-PR rebalance): the shared churn-resume mechanism —
# absolute round cursor + dropped carry + schedule fingerprint — stays
# pinned in-gate by the SI resume params and the fused-planes resume;
# this packed-sharded twin re-proves under -m slow
@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs the virtual multi-device mesh")
def test_packed_sharded_resume_under_fault_bitwise(tmp_path):
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_packed import (
        checkpointed_packed_sharded)
    proto = ProtocolConfig(mode="pull", fanout=1, rumors=3)
    topo = G.erdos_renyi(200, 0.06, seed=4)
    fault = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
        events=((3, 2, 5), (7, 1, -1)), partitions=((2, 6, 100),),
        ramp=(1, 4, 0.0, 0.3)))
    mesh = make_mesh(4)

    def leg(name, rounds, resume_state=None, lost_prefix=0.0):
        return checkpointed_packed_sharded(
            proto, topo, RunConfig(seed=11, max_rounds=rounds), mesh,
            str(tmp_path / name), every=3, fault=fault,
            resume_state=resume_state, lost_prefix=lost_prefix)

    full, cov_f, _ = leg("full.npz", 8)
    leg("half.npz", 4)        # inside the partition window, mid-ramp
    lp = load_meta(str(tmp_path / "half.npz"))["extra"]["dropped"]
    res, cov_r, _ = leg("half.npz", 8,
                        resume_state=load_state(
                            str(tmp_path / "half.npz")),
                        lost_prefix=lp)
    np.testing.assert_array_equal(np.asarray(full.seen),
                                  np.asarray(res.seen))
    assert cov_f == cov_r and float(full.msgs) == float(res.msgs)
    assert (load_meta(str(tmp_path / "full.npz"))["extra"]["dropped"]
            == load_meta(str(tmp_path / "half.npz"))["extra"]["dropped"])


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs the virtual multi-device mesh")
def test_fused_planes_resume_under_churn_events_bitwise(tmp_path):
    from gossip_tpu.parallel.sharded_fused import (
        checkpointed_fused_planes, make_plane_mesh)
    fault = FaultConfig(seed=1, churn=ChurnConfig(
        events=((3, 2, 5), (7, 1, -1))))
    mesh = make_plane_mesh(2)

    def leg(name, rounds, resume_state=None):
        return checkpointed_fused_planes(
            _N, 2, RunConfig(seed=0, max_rounds=rounds), mesh,
            str(tmp_path / name), every=3, interpret=True, fault=fault,
            resume_state=resume_state)

    full, cov_f, _ = leg("full.npz", 8)
    leg("half.npz", 4)
    res, cov_r, _ = leg("half.npz", 8,
                        resume_state=load_state(
                            str(tmp_path / "half.npz")))
    np.testing.assert_array_equal(np.asarray(full.table),
                                  np.asarray(res.table))
    assert cov_f == cov_r

    # partitions and ramps run on this engine since the fused-operand
    # PR (per-round cut masks + the threshold table behind the SMEM
    # scalar) — the checkpointed segments index them by the ABSOLUTE
    # round cursor, so resume under the FULL schedule is bitwise too
    full_fault = FaultConfig(seed=1, drop_prob=0.05, churn=ChurnConfig(
        events=((3, 2, 5),), partitions=((1, 6, 32),),
        ramp=(0, 4, 0.0, 0.3)))

    def fleg(name, rounds, resume_state=None):
        return checkpointed_fused_planes(
            _N, 2, RunConfig(seed=0, max_rounds=rounds), mesh,
            str(tmp_path / name), every=3, interpret=True,
            fault=full_fault, resume_state=resume_state)

    ffull, fcov, _ = fleg("pfull.npz", 8)
    fleg("phalf.npz", 4)
    fres, fcov_r, _ = fleg("phalf.npz", 8,
                           resume_state=load_state(
                               str(tmp_path / "phalf.npz")))
    np.testing.assert_array_equal(np.asarray(ffull.table),
                                  np.asarray(fres.table))
    assert fcov == fcov_r


# depth tier (tier-1 wall budget, serving-PR rebalance): the churn-
# resume mechanism (absolute state.round schedule indexing + the lost
# carry through run_with_checkpoints) is shared by every surface and
# stays pinned in-gate by the SI, packed-sharded, and fused-planes
# resumes + the crashloop smoke; the SWIM and rumor per-surface depth
# re-proves under -m slow
@pytest.mark.slow
def test_swim_resume_under_churn_bitwise(tmp_path):
    from gossip_tpu.runtime.simulator import checkpointed_swim
    # events (a permanent crash to detect + a recovering node) + ramp;
    # partitions are rejected by the SWIM factory (membership overlay)
    fault = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
        events=((5, 2, -1), (3, 4, 6)), ramp=(1, 4, 0.0, 0.2)))
    proto = ProtocolConfig(mode="swim", fanout=2, swim_subjects=8,
                           swim_proxies=3, swim_suspect_rounds=6)

    def leg(name, rounds, resume_state=None):
        return checkpointed_swim(
            proto, _N, RunConfig(seed=0, max_rounds=rounds),
            str(tmp_path / name), every=5, dead_nodes=(), fail_round=0,
            fault=fault, resume_state=resume_state)

    full, det_f, _ = leg("full.npz", 12)
    leg("half.npz", 6)        # mid-ramp, while node 3 is churn-down
    res, det_r, _ = leg("half.npz", 12,
                        resume_state=load_state(
                            str(tmp_path / "half.npz")))
    np.testing.assert_array_equal(np.asarray(full.wire),
                                  np.asarray(res.wire))
    np.testing.assert_array_equal(np.asarray(full.timer),
                                  np.asarray(res.timer))
    assert det_f == det_r == 1.0  # the scheduled crash is detected


def test_base_round_mismatch_refused():
    # a driver that rebuilt its state with a re-zeroed round counter
    # would silently restart the fault program from round 0 — refused
    from gossip_tpu.models.si import make_si_round
    from gossip_tpu.models.state import init_state
    proto = ProtocolConfig(mode="pushpull", fanout=1)
    step, tables = make_si_round(proto, G.complete(16), None, 0,
                                 tabled=True)
    st = init_state(RunConfig(seed=0), proto, 16)
    with pytest.raises(ValueError, match="base_round"):
        run_with_checkpoints(step, st, 2, "/dev/null.npz",
                             base_round=7, step_args=tables)


def test_schedule_fingerprint_semantics():
    from gossip_tpu.ops import nemesis as NE
    assert NE.schedule_fingerprint(None, _N) is None
    assert NE.schedule_fingerprint(
        FaultConfig(drop_prob=0.1, seed=0), _N) is None
    fp = NE.schedule_fingerprint(_FAULT, _N)
    assert isinstance(fp, str) and len(fp) == 64
    # deterministic; sensitive to the program AND the denominator
    assert fp == NE.schedule_fingerprint(_FAULT, _N)
    other = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
        events=((4, 2, 5), (7, 1, -1)),
        partitions=((2, 6, 32),), ramp=(1, 4, 0.0, 0.3)))
    assert fp != NE.schedule_fingerprint(other, _N)
    assert fp != NE.schedule_fingerprint(_FAULT, _N * 2)


# ---------------------------------------------------------------------
# CLI: the fault-program fingerprint refusal matrix
# ---------------------------------------------------------------------

_CHURN_FLAGS = ("--churn-event", "3:2:5", "--churn-event", "7:1",
                "--partition", "2:6:32", "--drop-ramp", "1:4:0.0:0.3")


@pytest.mark.slow
def test_cli_resume_fingerprint_refusals(tmp_path):
    """A checkpoint written WITHOUT the fault-program fingerprint (a
    pre-crash-safety build) refuses a churn resume; dropping the churn
    flags on resume refuses too (config fingerprint); and the happy
    path — same program — resumes to the bitwise straight-run state
    with the exact dropped total in the report."""
    ck = str(tmp_path / "c.npz")
    r = _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
             "--max-rounds", "4", "--checkpoint", ck,
             "--checkpoint-every", "3", "--seed", "1", *_CHURN_FLAGS)
    assert r.returncode == 0, r.stderr
    # strip the fingerprint the way a pre-crash-safety build would
    # have: same arrays, same config fingerprint, no fault_program key
    with np.load(ck, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    assert meta["extra"].pop("fault_program")
    np.savez(ck, __meta__=json.dumps(meta), **arrays)
    r = _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
             "--max-rounds", "8", "--checkpoint", ck, "--resume",
             "--checkpoint-every", "3", "--seed", "1", *_CHURN_FLAGS)
    assert r.returncode == 2
    assert "no fault-program fingerprint" in r.stderr
    # dropping the churn flags is a config mismatch (refused before the
    # schedule-specific guards)
    r = _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
             "--max-rounds", "8", "--checkpoint", ck, "--resume",
             "--checkpoint-every", "3", "--seed", "1")
    assert r.returncode == 2 and "config mismatch" in r.stderr

    # happy path: rewrite the run from scratch, kill at 4, resume; the
    # final report matches an uninterrupted run exactly (incl. dropped)
    full_ck = str(tmp_path / "f.npz")
    rf = _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
              "--max-rounds", "8", "--checkpoint", full_ck,
              "--checkpoint-every", "3", "--seed", "1", *_CHURN_FLAGS)
    os.remove(ck)
    _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
         "--max-rounds", "4", "--checkpoint", ck,
         "--checkpoint-every", "3", "--seed", "1", *_CHURN_FLAGS)
    rr = _cli("run", "--mode", "pushpull", "--n", "64", "--fanout", "2",
              "--max-rounds", "8", "--checkpoint", ck, "--resume",
              "--checkpoint-every", "3", "--seed", "1", *_CHURN_FLAGS)
    assert rr.returncode == 0, rr.stderr
    full, res = json.loads(rf.stdout), json.loads(rr.stdout)
    for key in ("coverage", "msgs", "dropped", "fault_program",
                "rounds"):
        assert full[key] == res[key], key
    with np.load(full_ck) as a, np.load(ck) as b:
        np.testing.assert_array_equal(a["seen"], b["seen"])


# ---------------------------------------------------------------------
# no-churn checkpointed trajectories: provably unchanged
# ---------------------------------------------------------------------

def _pinned():
    with open(CS.DATA) as f:
        return json.load(f)["digests"]


@pytest.mark.parametrize("name", ["ckpt_si", "ckpt_fused"])
def test_checkpointed_static_fingerprints_fast(name):
    """In-gate subset: the single-device SI surface smokes the
    re-plumbed run_with_checkpoints against its pre-lift digest, and
    the fused-planes surface guards the STATIC fused trajectory
    (drop_prob=0.05 — the drop threshold rides the SMEM scalar operand
    since the fused-operand PR, and this digest proves the promotion
    is value-preserving bit for bit).  The full five-surface matrix
    runs under -m slow below."""
    runner = CS.CHECKPOINTED_SURFACES[name]
    assert runner(CS._static_fault()) == _pinned()[f"ckpt-static:{name}"]


@pytest.mark.slow
@pytest.mark.parametrize("name", ["ckpt_packed", "ckpt_rumor",
                                  "ckpt_swim", "ckpt_fused"])
def test_checkpointed_static_fingerprints_full(name):
    runner = CS.CHECKPOINTED_SURFACES[name]
    assert runner(CS._static_fault()) == _pinned()[f"ckpt-static:{name}"]


# ---------------------------------------------------------------------
# the live SIGKILL harness (single-kill smoke; committed 3-kill record
# is artifacts/ledger_crashloop_r12.jsonl)
# ---------------------------------------------------------------------

# ~18 s (txn-PR tier-1 rebalance, flight data in
# artifacts/ledger_tests.jsonl): the crash-safety surface stays
# in-gate via the committed 3-kill record pin below plus the SI and
# fused-planes churn resumes; the live SIGKILL loop re-proves under
# -m slow
@pytest.mark.slow
def test_crashloop_single_kill_smoke(tmp_path):
    out = str(tmp_path / "ledger_crashloop_smoke.jsonl")
    # n=4096 + a 2 ms poll: each 4-round segment walls ~15 ms on this
    # CPU tier, so the poller reliably observes an INTERMEDIATE durable
    # cursor and the kill lands mid-run (a tiny n publishes its final
    # checkpoint between polls and the tool refuses the vacuous kill)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "crashloop.py"),
         "--n", "4096", "--max-rounds", "12", "--every", "4",
         "--kills", "1", "--poll-ms", "2",
         "--workdir", str(tmp_path / "wk"), "--out", out],
        capture_output=True, text=True, cwd=_REPO, env=CLI_ENV,
        timeout=420)
    assert r.returncode == 0, r.stderr + r.stdout
    verdict = json.loads(r.stdout)
    assert verdict["ok"] and verdict["kills"] == 1
    assert verdict["coverage"] == 1.0
    # the ledger parses per the flight-recorder contract and carries
    # provenance + one kill event with the durable round cursor
    from gossip_tpu.utils.telemetry import load_ledger
    rows = load_ledger(out)
    kinds = [row.get("ev") for row in rows]
    assert kinds[0] == "provenance"
    assert "kill" in kinds and "verdict" in kinds
    kill = next(row for row in rows if row.get("ev") == "kill")
    assert kill["run_id"]
    # the kill interrupted REAL work: at least one durable segment
    # existed, and the final checkpoint did not (the tool refuses to
    # count a kill that postdates the last durable round)
    assert 4 <= kill["durable_round"] < 12


def test_committed_crashloop_record_is_green():
    """The standing proof: >= 3 SIGKILL/resume cycles, bitwise-equal
    final state, convergence to 1.0 on the eventual-alive set, and a
    kill INSIDE the scheduled partition window — all asserted on the
    committed artifact, so the record can never rot silently."""
    from gossip_tpu.utils.telemetry import load_ledger
    rows = load_ledger(os.path.join(_REPO, "artifacts",
                                    "ledger_crashloop_r12.jsonl"))
    assert rows[0].get("ev") == "provenance"
    cfg = next(r for r in rows if r.get("ev") == "config")
    kills = [r for r in rows if r.get("ev") == "kill"]
    verdict = next(r for r in rows if r.get("ev") == "verdict")
    assert len(kills) >= 3 and verdict["kills"] >= 3
    assert verdict["ok"] and verdict["bitwise_equal"]
    assert verdict["coverage"] == 1.0 and verdict["dropped"] > 0
    # every kill is attributable, durable-round-stamped, and landed
    # BEFORE the final checkpoint (it interrupted real work)
    for k in kills:
        assert k["run_id"]
        assert 0 <= k["durable_round"] < cfg["max_rounds"]
    # at least one kill landed inside the scheduled partition window
    part = cfg["churn"][cfg["churn"].index("--partition") + 1]
    start, end, _cut = (int(x) for x in part.split(":"))
    assert any(start <= k["durable_round"] < end for k in kills), (
        "no kill landed inside the partition window "
        f"[{start}, {end}): {[k['durable_round'] for k in kills]}")
