#!/usr/bin/env python
"""Crashloop: the paper's nemesis, pointed at the simulator itself.

The source harness crashes and partitions its *nodes* and checks that
gossip still converges (PAPER.md; Maelstrom's whole method).  This tool
applies the same discipline to OUR process: it launches a checkpointed
CLI run under a mixed fault program (crash/recover churn + a permanent
crash + a partition window + a drop ramp), SIGKILLs the process at K
randomized mid-segment points, resumes after each kill, and gates the
crash contract (utils/checkpoint module doc):

  * the final state is BITWISE equal to an uninterrupted run of the
    same config — every array, the message accounting, the absolute
    round cursor, and the exact destroyed-message total, no matter
    where the kills landed (inside an open partition window, mid-ramp);
  * coverage converges to 1.0 on the EVENTUAL alive set (the paper's
    convergence check, under our own process churn on top of the
    scheduled node churn);
  * the run ledger (utils/telemetry — provenance first line, one
    ``kill``/``resume`` event pair per cycle with the durable round
    cursor observed at the kill) parses per the flight-recorder
    contract; tools/validate_artifacts.py refuses any ``*crashloop*``
    artifact without provenance, so the committed record
    (artifacts/ledger_crashloop_r12.jsonl) can never be grandfathered.

Kill points are *round thresholds*: the harness polls the checkpoint's
durable round cursor and SIGKILLs the instant it crosses the next
threshold — i.e. while the NEXT compiled segment is in flight, so the
kill lands mid-segment by construction (a stranded ``path + ".tmp"``
partial, when the timing produces one, is recorded per kill and must be
cleaned by the next save).  Thresholds are drawn from ``--kill-seed``,
so a failing sequence replays exactly.

    python tools/crashloop.py                       # committed-record
        # config: n=16384 pushpull, 60 rounds, every=5, 3 kills ->
        # artifacts/ledger_crashloop_r12.jsonl
    python tools/crashloop.py --n 4096 --max-rounds 12 --every 4 \
        --kills 1 --poll-ms 2 --out /tmp/smoke.jsonl  # the tier-1 smoke

Runs on the hermetic CPU tier by design: the crash contract is a
bitwise-trajectory structure, not a chip rate.
"""

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_OUT = os.path.join(REPO, "artifacts",
                           "ledger_crashloop_r12.jsonl")

# hard deadline per child leg: a hung child must fail the harness
# loudly, never hang it
LEG_TIMEOUT_S = 600


def churn_flags(n: int, rounds: int):
    """The mixed fault program, scaled to the run: a crash/recover
    event, a permanent crash, a partition window long enough that a
    kill can land INSIDE it, and a drop ramp across the early segments
    — every schedule feature the SI engines honor, in one program."""
    heal = max(4, rounds // 2)
    return [
        "--churn-event", f"3:2:{heal}",
        "--churn-event", "7:3",                      # forever
        "--partition", f"{max(2, rounds // 6)}:{heal}:{n // 2}",
        "--drop-ramp", f"1:{max(3, rounds // 3)}:0.0:0.15",
    ]


def cli_argv(a, ckpt: str, resume: bool):
    argv = [sys.executable, "-m", "gossip_tpu", "run",
            "--mode", a.mode, "--n", str(a.n), "--fanout", "2",
            "--max-rounds", str(a.max_rounds), "--seed", str(a.seed),
            "--checkpoint", ckpt,
            "--checkpoint-every", str(a.every)]
    if a.devices > 1:
        argv += ["--devices", str(a.devices)]
    argv += churn_flags(a.n, a.max_rounds)
    if resume:
        argv.append("--resume")
    return argv


def durable_round(ckpt: str):
    """The checkpoint's absolute round cursor, or -1 before the first
    durable segment.  Atomic os.replace means a concurrent writer can
    never hand us a torn file.  Deliberately jax-free (np.load + json
    only): the poller's first call must not pay a multi-second jax
    import while the child is publishing segments."""
    try:
        with np.load(ckpt, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
        return int(meta.get("extra", {}).get("round", -1))
    except FileNotFoundError:
        return -1
    except Exception:
        return -1          # unreadable == no durable round yet


def run_to_completion(argv, env):
    p = subprocess.run(argv, capture_output=True, text=True, env=env,
                       timeout=LEG_TIMEOUT_S)
    if p.returncode != 0:
        raise RuntimeError(f"leg failed rc={p.returncode}:\n{p.stderr}")
    return json.loads(p.stdout)


def kill_at_round(argv, env, ckpt, threshold, max_rounds, log_prefix,
                  poll_s=0.01):
    """Launch the leg and SIGKILL it once the durable round cursor
    crosses ``threshold``.  Returns (killed: bool, observed_round,
    stale_tmp: bool, wall_s); killed=False means the leg completed —
    or published its FINAL checkpoint — before the threshold could be
    observed mid-run.  The final-cursor case matters: a SIGKILL after
    round ``max_rounds`` is durable would interrupt nothing, and a
    harness that counted it would certify crash recovery it never
    exercised (raise --n so segments outlast the poller instead).

    Child output goes to ``log_prefix``.out/.err FILES, not pipes — a
    chatty child filling an undrained pipe buffer would block mid-write
    and deadlock the poll loop."""
    t0 = time.perf_counter()
    with open(log_prefix + ".out", "wb") as fo, \
            open(log_prefix + ".err", "wb") as fe:
        proc = subprocess.Popen(argv, stdout=fo, stderr=fe, env=env)
        try:
            while True:
                rc = proc.poll()
                r = durable_round(ckpt)
                if rc is not None:
                    if rc != 0:
                        err = open(log_prefix + ".err",
                                   errors="replace").read()
                        raise RuntimeError(
                            f"leg died on its own rc={rc}:\n{err}")
                    return False, r, False, time.perf_counter() - t0
                if time.perf_counter() - t0 > LEG_TIMEOUT_S:
                    raise RuntimeError(
                        f"leg exceeded {LEG_TIMEOUT_S}s without "
                        f"reaching round {threshold} (wedged child?)")
                if r >= max_rounds:
                    # all work is already durable: a kill now is
                    # vacuous — let the leg finish and report
                    # completed_before_kill
                    proc.wait()
                    return False, r, False, time.perf_counter() - t0
                if r >= threshold:
                    proc.send_signal(signal.SIGKILL)
                    proc.wait()
                    stale = os.path.exists(ckpt + ".tmp")
                    return True, r, stale, time.perf_counter() - t0
                time.sleep(poll_s)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def assert_bitwise_equal(ref_ckpt: str, crash_ckpt: str):
    """Every array and the whole metadata entry (config fingerprint,
    absolute round, exact dropped total) must match bitwise."""
    problems = []
    with np.load(ref_ckpt, allow_pickle=False) as a, \
            np.load(crash_ckpt, allow_pickle=False) as b:
        if sorted(a.files) != sorted(b.files):
            return [f"entry sets differ: {sorted(a.files)} vs "
                    f"{sorted(b.files)}"]
        for name in a.files:
            if name == "__meta__":
                ma, mb = (json.loads(str(a[name])),
                          json.loads(str(b[name])))
                if ma != mb:
                    problems.append(f"metadata differs: {ma} vs {mb}")
            elif not np.array_equal(np.asarray(a[name]),
                                    np.asarray(b[name])):
                problems.append(f"array {name!r} differs")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=16384,
                    help="node count; the default is big enough that a "
                         "segment outlasts the kill poller on CPU — a "
                         "tiny n can outrun it and complete early")
    ap.add_argument("--mode", default="pushpull")
    ap.add_argument("--max-rounds", type=int, default=60)
    ap.add_argument("--every", type=int, default=5)
    ap.add_argument("--kills", type=int, default=3)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--kill-seed", type=int, default=12,
                    help="seeds the randomized kill thresholds (a "
                         "failing sequence replays exactly)")
    ap.add_argument("--poll-ms", type=float, default=10.0,
                    help="cursor poll interval; must be well under the "
                         "per-segment wall or the child publishes its "
                         "final checkpoint between polls and the kill "
                         "is refused as vacuous (smoke configs: ~4k "
                         "nodes with --poll-ms 2)")
    ap.add_argument("--workdir", default=None,
                    help="checkpoint scratch dir (default: a fresh "
                         "temp dir)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    a = ap.parse_args(argv)

    if a.workdir is None:
        import tempfile
        a.workdir = tempfile.mkdtemp(prefix="crashloop_")
    os.makedirs(a.workdir, exist_ok=True)
    ref_ckpt = os.path.join(a.workdir, "reference.npz")
    crash_ckpt = os.path.join(a.workdir, "crashloop.npz")
    for p in (ref_ckpt, crash_ckpt, crash_ckpt + ".tmp"):
        if os.path.exists(p):
            os.remove(p)

    # children inherit the caller's platform pins (the tier-1 smoke
    # passes JAX_PLATFORMS=cpu + the session compile cache); the
    # harness itself never imports jax — np.load + json reads only
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # children run `-m gossip_tpu`; make the repo importable no matter
    # where the harness was launched from
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    from gossip_tpu.utils import telemetry
    led = telemetry.Ledger(a.out)
    prov = {"run_id": led.run_id}
    rng = random.Random(a.kill_seed)
    # thresholds stay below the LAST segment's start: a threshold past
    # max_rounds - every could only fire on the final checkpoint, when
    # there is no mid-segment work left to kill
    lo, hi = a.every, max(a.every + 1, a.max_rounds - a.every)
    # one randomized threshold per equal slice of the round budget:
    # kills SPREAD across the run (early segment, inside the partition
    # window, late) instead of clustering wherever one draw lands
    pool = []
    for i in range(a.kills):
        s0 = lo + (hi - lo) * i // a.kills
        s1 = max(s0 + 1, lo + (hi - lo) * (i + 1) // a.kills)
        pool.append(rng.randrange(s0, s1))
    pool.sort()
    led.event("config", n=a.n, mode=a.mode, max_rounds=a.max_rounds,
              every=a.every, kills=a.kills, devices=a.devices,
              seed=a.seed, kill_seed=a.kill_seed,
              kill_thresholds=pool,
              churn=churn_flags(a.n, a.max_rounds))

    # ---- reference leg: the uninterrupted run -----------------------
    t0 = time.perf_counter()
    ref = run_to_completion(cli_argv(a, ref_ckpt, resume=False), env)
    led.event("reference_done", wall_s=round(time.perf_counter() - t0, 3),
              coverage=ref["coverage"], rounds=ref["rounds"],
              dropped=ref.get("dropped"),
              fault_program=ref.get("fault_program"))

    # ---- crash leg: run / SIGKILL / resume, K times -----------------
    kills_done = 0
    kill_rounds = []
    final = None
    resume = False
    for threshold in pool:
        # each leg must publish at least one NEW durable segment before
        # its kill — a threshold the cursor already crossed would kill
        # the resume before it did any work, proving nothing
        threshold = max(threshold, durable_round(crash_ckpt) + 1)
        killed, at, stale, wall = kill_at_round(
            cli_argv(a, crash_ckpt, resume=resume), env, crash_ckpt,
            threshold, a.max_rounds,
            os.path.join(a.workdir, f"leg{kills_done + 1}"),
            poll_s=a.poll_ms / 1000.0)
        if not killed:
            # the leg outran the poller and completed; the remaining
            # kills have nothing to kill — record honestly and stop
            led.event("completed_before_kill", threshold=threshold,
                      durable_round=at, wall_s=round(wall, 3))
            break
        kills_done += 1
        kill_rounds.append(at)
        # provenance AT the kill point: the durable cursor the next
        # resume will continue from, stamped with this run's identity
        led.event("kill", seq=kills_done, threshold=threshold,
                  durable_round=at, stale_tmp=stale,
                  wall_s=round(wall, 3), **prov)
        resume = True
    if resume:
        t0 = time.perf_counter()
        final = run_to_completion(cli_argv(a, crash_ckpt, resume=True),
                                  env)
        led.event("resume_done", resumed_from=durable_round(crash_ckpt),
                  wall_s=round(time.perf_counter() - t0, 3),
                  coverage=final["coverage"], dropped=final.get("dropped"))
    else:
        final = run_to_completion(cli_argv(a, crash_ckpt, resume=False),
                                  env)

    # ---- verdict ----------------------------------------------------
    problems = assert_bitwise_equal(ref_ckpt, crash_ckpt)
    if kills_done < a.kills:
        problems.append(f"only {kills_done}/{a.kills} kills landed "
                        "(raise --max-rounds or lower --every)")
    if any(k >= a.max_rounds for k in kill_rounds):
        # belt-and-braces twin of the kill_at_round guard: no recorded
        # kill may postdate the final durable state
        problems.append("a kill landed after the final checkpoint "
                        f"(durable rounds {kill_rounds}) — it "
                        "interrupted nothing")
    if final["coverage"] != 1.0:
        problems.append("crashloop leg did not converge on the "
                        f"eventual-alive set: coverage={final['coverage']}")
    if ref["coverage"] != 1.0:
        problems.append("reference leg did not converge: "
                        f"coverage={ref['coverage']}")
    for key in ("coverage", "msgs", "rounds", "dropped",
                "fault_program"):
        if ref.get(key) != final.get(key):
            problems.append(f"report {key!r} differs: {ref.get(key)} "
                            f"vs {final.get(key)}")
    led.event("verdict", ok=not problems, kills=kills_done,
              bitwise_equal=not [p for p in problems if "differ" in p],
              coverage=final["coverage"], dropped=final.get("dropped"),
              problems=problems)
    led.close()
    if problems:
        for p in problems:
            print(f"CRASHLOOP FAIL: {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "kills": kills_done,
                      "coverage": final["coverage"],
                      "dropped": final.get("dropped"),
                      "ledger": a.out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
