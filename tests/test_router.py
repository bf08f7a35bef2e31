"""Replicated sidecar serving (rpc/router + tools/fleet_crashloop):
health-gated failover dispatch, flap hysteresis, the ops/logs control
plane, shed/deadline semantics, the SidecarClient retry budget, the
batcher drain ordering, and the committed fleet-crashloop record's
gates."""

import importlib.util
import json
import os
import sys
import time

import pytest

from gossip_tpu.config import FleetConfig, ServingConfig
from gossip_tpu.utils import telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLEET_RECORD = os.path.join(_REPO, "artifacts",
                            "ledger_fleet_r18.jsonl")
TRACE_RECORD = os.path.join(_REPO, "artifacts",
                            "ledger_trace_r22.jsonl")


# -- control plane (ops/logs dogfood) ---------------------------------

def test_control_plane_log_epochs_and_catchup():
    """The fleet's admission state IS a replicated log (ops/logs):
    per-replica owner keys, committed offset = config epoch, views
    merged by the log join — and a wiped (rejoined) view catches the
    whole fleet state up from any survivor's gossip, never from
    operator state."""
    from gossip_tpu.rpc.router import (STATE_DOWN, STATE_UP,
                                       ControlPlane)
    cp = ControlPlane(3, 8)
    assert cp.append(0, STATE_UP) == 1
    assert cp.append(1, STATE_UP) == 1
    assert cp.append(0, STATE_DOWN) == 2
    # transitions live only in the owners' views until gossip carries
    # them (replica 2 has not yet heard of replica 0's transitions);
    # rotating-partner pulls converge the fleet within n-1 ticks
    assert int(cp.views[2].sum()) == 0
    for _ in range(3):
        cp.gossip_tick()
    assert cp.epochs() == [2, 1, 0]
    assert (cp.views[0] == cp.views[2]).all()      # fully converged
    assert cp.state_of(0) == "down" and cp.state_of(1) == "up"
    # rejoin: replica 0's view dies with its process; catchup rebuilds
    # epoch AND state purely by merging survivors
    cp.wipe(0)
    assert cp.epoch(0) == 0
    assert cp.catchup(0) == 2
    assert cp.state_of(0) == "down"
    assert cp.append(0, STATE_UP) == 3     # epochs never alias
    # a full ring refuses loudly instead of aliasing epochs on a wrap
    cp2 = ControlPlane(1, 4)
    for state in (STATE_UP, STATE_DOWN, STATE_UP, STATE_DOWN):
        cp2.append(0, state)
    with pytest.raises(ValueError, match="ring wrap"):
        cp2.append(0, STATE_UP)
    # flush-before-wipe: an owner-only entry pushed to peers survives
    # the owner's death (the replace_replica ordering)
    cp3 = ControlPlane(2, 8)
    cp3.append(0, STATE_UP)
    cp3.flush(0)
    cp3.wipe(0)
    assert cp3.catchup(0) == 1


# -- hysteresis (satellite: probe flapping) ---------------------------

def test_probe_flapping_respects_readmission_hysteresis():
    """Satellite pin: a replica alternating healthy/unhealthy must NOT
    oscillate in and out of rotation — after a down, re-admission
    takes ``up_after`` CONSECUTIVE healthy probes, so a scripted
    flap sequence keeps it out until a genuinely stable stretch."""
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from gossip_tpu.rpc.router import Router
    router = Router(["127.0.0.1:1", "127.0.0.1:2"],
                    FleetConfig(down_after=2, up_after=3,
                                probe_interval_ms=10_000))
    r = router.replicas[0]
    try:
        # initial admission: one healthy probe (nothing was lost yet)
        router.observe_probe(r, True)
        assert r.healthy
        # down takes down_after consecutive failures, not one blip
        router.observe_probe(r, False)
        assert r.healthy
        router.observe_probe(r, False)
        assert not r.healthy
        # the flap: ok/fail alternation never re-admits (consec_ok
        # resets every failure, so it never reaches up_after=3)
        for _ in range(6):
            router.observe_probe(r, True)
            assert not r.healthy, "flapping replica re-entered " \
                "rotation before the hysteresis threshold"
            router.observe_probe(r, False)
        # a stable healthy stretch re-admits at exactly up_after
        router.observe_probe(r, True)
        router.observe_probe(r, True)
        assert not r.healthy
        router.observe_probe(r, True)
        assert r.healthy
        # the control-plane log recorded the admission history
        assert router.control.epoch(0) == 3          # up, down, up
        assert router.control.state_of(0) == "up"
    finally:
        router.close()


# -- dispatch unit semantics (shed / deadline) ------------------------

class _Aborted(Exception):
    pass


class _Ctx:
    """Minimal gRPC server-context stand-in for dispatch unit tests."""

    def __init__(self, remaining=None):
        self._remaining = remaining
        self.code = self.details = None

    def time_remaining(self):
        return self._remaining

    def abort(self, code, details):
        self.code, self.details = code, details
        raise _Aborted(details)


def test_router_sheds_and_honors_abandoned_deadlines(tmp_path):
    """Shed, never queue: with no healthy replica the router rejects
    RESOURCE_EXHAUSTED and ledgers a ``shed`` event.  Deadlines
    propagate end-to-end: a request whose client deadline already
    passed is rejected DEADLINE_EXCEEDED without ever dispatching — a
    failover retry can never run a request its client abandoned."""
    grpc = pytest.importorskip("grpc")
    from gossip_tpu.rpc.router import Router
    led_path = str(tmp_path / "router.jsonl")
    led = telemetry.Ledger(led_path)
    prev = telemetry.activate(led)
    router = Router(["127.0.0.1:1"],
                    FleetConfig(probe_interval_ms=10_000))
    try:
        # nothing admitted yet -> shed
        ctx = _Ctx()
        with pytest.raises(_Aborted, match="shed"):
            router.dispatch("run", b"{}", ctx)
        assert ctx.code == grpc.StatusCode.RESOURCE_EXHAUSTED
        # a healthy replica but an expired client deadline -> terminal
        # DEADLINE_EXCEEDED, zero dispatch attempts (the stub would
        # raise UNAVAILABLE and the counters would show a failover)
        router.observe_probe(router.replicas[0], True)
        ctx = _Ctx(remaining=-0.01)
        with pytest.raises(_Aborted, match="deadline"):
            router.dispatch("run", b"{}", ctx)
        assert ctx.code == grpc.StatusCode.DEADLINE_EXCEEDED
        assert router.counters["failovers"] == 0
        assert router.counters["deadline_rejects"] == 1
        # saturation: every healthy replica at the in-flight cap
        router.replicas[0].inflight = router.cfg.max_inflight
        ctx = _Ctx()
        with pytest.raises(_Aborted, match="shed"):
            router.dispatch("run", b"{}", ctx)
        assert ctx.code == grpc.StatusCode.RESOURCE_EXHAUSTED
    finally:
        router.close()
        telemetry.activate(prev)
        led.close()
    events = telemetry.load_ledger(led_path)
    sheds = [e for e in events if e.get("ev") == "shed"]
    assert len(sheds) == 2
    assert sheds[0]["reason"] == "no healthy replica"
    assert "cap" in sheds[1]["reason"]
    assert [e for e in events if e.get("ev") == "deadline_exceeded"
            and e.get("source") == "router"]


# -- live failover (in-gate: one compile, two replicas) ---------------

def test_router_failover_redispatches_inflight_bitwise(tmp_path):
    """THE fleet tentpole, live and in-process: two batching sidecar
    replicas behind the router; a request runs, replica 0 dies hard,
    the next dispatch fails over to the survivor and the reply is
    BITWISE the same as replaying the identical payload (requests are
    pure functions of their payload — the re-dispatch safety
    contract), with the down/failover flight-record and the
    control-plane epochs advancing."""
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from gossip_tpu.rpc import router as RT
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    led_path = str(tmp_path / "fleet.jsonl")
    led = telemetry.Ledger(led_path)
    prev = telemetry.activate(led)
    servers = [serve(port=0, max_workers=4,
                     batching=ServingConfig(tick_ms=25))
               for _ in range(2)]
    # start_probes=False: admission driven by probe_once below, so a
    # background probe can never race the hard stop and steal the
    # failover (the dispatch must find the corpse first)
    rserver, rport, router = RT.serve_router(
        [f"127.0.0.1:{p}" for _, p in servers],
        cfg=FleetConfig(probe_interval_ms=10_000, down_after=1,
                        up_after=2), start_probes=False)
    client = SidecarClient(f"127.0.0.1:{rport}", max_attempts=1)

    def req(seed):
        return dict(backend="jax-tpu",
                    proto={"mode": "pushpull", "fanout": 2},
                    topology={"family": "complete", "n": 64},
                    run={"max_rounds": 4, "engine": "xla",
                         "seed": seed}, curve=True)
    try:
        router.probe_once()
        assert router.healthy_count() == 2
        a = client.run(timeout=120, **req(0))
        assert a["meta"]["batch"]["batched"] is True
        # hard failure: the serial least-inflight policy had routed to
        # replica 0, so the next dispatch lands on the corpse first
        servers[0][0].gossip_batcher.close()
        servers[0][0].stop(grace=None)
        b = client.run(timeout=120, **req(1))
        assert b["coverage"] > 0
        s = router.stats()
        assert s["failovers"] >= 1 and s["healthy"] == 1
        assert s["states"][0] == "down" and s["states"][1] == "up"
        assert s["epochs"][0] >= 2          # up, then down
        # bitwise replay parity: the surviving replica re-serves the
        # SAME payload to the same bytes — what makes failover
        # re-dispatch safe
        a2 = client.run(timeout=120, **req(0))
        for field in ("curve", "msgs", "coverage", "rounds"):
            assert a2[field] == a[field], field
        # the router's health reply carries the fleet summary
        h = client.health()
        assert h["router"] is True and h["healthy"] == 1
    finally:
        client.close()
        rserver.stop(grace=None)
        router.close()
        servers[1][0].gossip_batcher.close()
        servers[1][0].stop(grace=None)
        telemetry.activate(prev)
        led.close()
    events = telemetry.load_ledger(led_path)
    kinds = {e.get("ev") for e in events}
    assert {"replica_down", "failover", "replica_up"} <= kinds


def test_trace_propagates_through_failover_redispatch(tmp_path):
    """Satellite pin: ONE minted trace_id survives a mid-flight
    failover re-dispatch.  The replayed attempt carries the SAME
    trace_id with a NEW ``dispatch_attempt`` span on the survivor, the
    ``failover`` span carries it too, the router's terminal
    ``request_trace`` waterfall counts the retry, and the trace_id
    join (tools/trace_report) yields one COMPLETE waterfall — the
    end-to-end tracing contract under the fleet's hardest path."""
    pytest.importorskip("grpc")
    from gossip_tpu.rpc import router as RT
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    led_path = str(tmp_path / "trace_failover.jsonl")
    led = telemetry.Ledger(led_path)
    prev = telemetry.activate(led)
    servers = [serve(port=0, max_workers=4,
                     batching=ServingConfig(tick_ms=25))
               for _ in range(2)]
    rserver, rport, router = RT.serve_router(
        [f"127.0.0.1:{p}" for _, p in servers],
        cfg=FleetConfig(probe_interval_ms=10_000, down_after=1,
                        up_after=2), start_probes=False)
    client = SidecarClient(f"127.0.0.1:{rport}", max_attempts=1)

    def req(seed):
        return dict(backend="jax-tpu",
                    proto={"mode": "push", "fanout": 2},
                    topology={"family": "complete", "n": 64},
                    run={"max_rounds": 4, "engine": "xla",
                         "seed": seed}, curve=True)
    tid = "feedfacecafe0001"
    try:
        router.probe_once()
        assert router.healthy_count() == 2
        client.run(timeout=120, **req(0))    # routes to replica 0
        # kill replica 0 hard: the serial least-inflight policy sends
        # the NEXT dispatch to the corpse first, forcing the failover
        servers[0][0].gossip_batcher.close()
        servers[0][0].stop(grace=None)
        out = client.run(timeout=120, trace_id=tid, **req(1))
        assert out["coverage"] > 0
        assert router.stats()["failovers"] >= 1
    finally:
        client.close()
        rserver.stop(grace=None)
        router.close()
        servers[1][0].gossip_batcher.close()
        servers[1][0].stop(grace=None)
        telemetry.activate(prev)
        led.close()
    # the trace_id= filter isolates the one request's span set
    tev = telemetry.load_ledger(led_path, trace_id=tid)
    attempts = [e for e in tev if e.get("ev") == "dispatch_attempt"]
    assert [a["attempt"] for a in attempts] == [1, 2]
    assert attempts[0]["replica"] == 0          # the corpse
    assert attempts[1]["replica"] == 1          # the survivor
    assert any(e.get("ev") == "failover" for e in tev)
    rt = [e for e in tev if e.get("ev") == "request_trace"]
    router_half = [e for e in rt if e.get("source") == "router"]
    replica_half = [e for e in rt if e.get("source") == "replica"]
    assert len(router_half) == 1
    assert router_half[0]["retries"] == 1       # the replay counted
    assert router_half[0]["replica"] == 1
    assert replica_half, rt                     # survivor's half joins
    # and the one join implementation agrees: a complete waterfall
    # with the failover attributed
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    wf = trace_report.waterfall(
        trace_report.join_traces(telemetry.load_ledger(led_path))[tid])
    assert wf["complete"] and wf["attempts"] == 2
    assert wf["failovers"] >= 1 and wf["retries"] == 1


# -- SidecarClient retry budget (satellite) ---------------------------

def test_client_retry_budget_clamps_attempt_deadlines():
    """Satellite pin: the caller's timeout is the TOTAL retry budget —
    each attempt's deadline is clamped to the remaining budget (the
    last attempt gets exactly what is left), and a budget exhausted
    between attempts re-raises instead of dispatching again.  Without
    this a dying replica stretches one call to attempts x timeout."""
    grpc = pytest.importorskip("grpc")
    from gossip_tpu.rpc.sidecar import SidecarClient

    class Unavailable(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

        def details(self):
            return "fake transport failure"

    client = SidecarClient("127.0.0.1:1", max_attempts=4,
                           backoff_base=0.03, backoff_cap=0.05)
    calls = []

    def fake(payload, timeout=None, metadata=None):
        calls.append((timeout, time.monotonic()))
        raise Unavailable()
    t0 = time.monotonic()
    budget = 0.5
    with pytest.raises(grpc.RpcError):
        client._call_with_retry(fake, b"{}", budget, "run")
    wall = time.monotonic() - t0
    assert len(calls) == 4              # budget covered all attempts
    deadline = t0 + budget
    timeouts = [c[0] for c in calls]
    # strictly shrinking deadlines, each equal to the REMAINING budget
    assert all(a > b for a, b in zip(timeouts, timeouts[1:]))
    for tmo, at in calls:
        assert abs(tmo - (deadline - at)) < 0.05, (tmo, deadline - at)
    assert timeouts[-1] < budget        # the clamp actually engaged
    assert wall < budget + 0.2
    # budget exhausted mid-backoff: NO further attempt is dispatched
    client2 = SidecarClient("127.0.0.1:1", max_attempts=4,
                            backoff_base=0.2, backoff_cap=0.4)
    calls.clear()
    t0 = time.monotonic()
    with pytest.raises(grpc.RpcError):
        client2._call_with_retry(fake, b"{}", 0.05, "run")
    assert len(calls) < 4, "an attempt ran after the budget expired"
    assert time.monotonic() - t0 < 0.5
    client.close()
    client2.close()


# -- batcher drain ordering (satellite) -------------------------------

def test_batcher_drain_rejects_new_admissions_before_flushing():
    """Satellite pin: a draining batcher refuses new admissions with
    Closed (-> UNAVAILABLE) BEFORE flushing queued work — the stop
    flag is checked inside the queue lock, so no admission can land in
    a queue after its final drain and strand its handler forever."""
    from gossip_tpu.backend import request_to_args
    from gossip_tpu.rpc import batcher as B
    args = request_to_args({
        "backend": "jax-tpu", "proto": {"mode": "pull", "fanout": 1},
        "topology": {"family": "complete", "n": 8},
        "run": {"max_rounds": 2}})
    b = B.Batcher(ServingConfig(tick_ms=10_000, max_batch=8,
                                max_queue=8))
    # park the collector so the drain points are OURS alone (the
    # white-box way to pin an ordering that is otherwise a race)
    b._stop.set()
    b._thread.join(timeout=10)
    b._stop.clear()
    pending, note = b.submit_run(args, time.monotonic() - 0.01)
    assert pending is not None and note is None
    # the drain begins: stop flag FIRST...
    b._stop.set()
    with pytest.raises(B.Closed, match="shut down"):
        b.submit_run(args, None)
    # ...and the queued request is still pending (not yet flushed):
    # rejection precedes flush, so nothing can slip in between
    assert not pending.event.is_set()
    # ...flush SECOND: close() answers the queued request (expired
    # here, so it errors rather than runs) — never strands it
    b.close()
    with pytest.raises(B.Expired, match="deadline expired"):
        pending.wait()
    assert b._queue == []


# -- CLI ---------------------------------------------------------------

def test_cli_route_validates_flags(capsys):
    from gossip_tpu.cli import main as cli_main
    assert cli_main(["route", "--replicas", "0"]) == 2
    assert "replicas" in capsys.readouterr().err
    # mesh-sharded replicas need the admission batcher: refusing the
    # contradiction beats spawning a fleet whose mesh can never run
    assert cli_main(["route", "--devices-per-replica", "4",
                     "--no-batching"]) == 2
    assert "devices-per-replica" in capsys.readouterr().err
    # devices per replica must be a pow2 (FleetConfig validation):
    # lane buckets divide the mesh or the executable cache fragments
    assert cli_main(["route", "--devices-per-replica", "3"]) == 2
    assert "power of two" in capsys.readouterr().err


# -- devices-per-replica gate (the mesh-sharded serving PR) -----------

def test_fleet_env_threads_host_device_count(monkeypatch):
    """A replica child pinned to CPU has exactly ONE XLA device unless
    fleet_env threads the host-device-count flag — the silent-mesh-
    degradation bug this PR's satellite closes.  An ambient pin is
    respected, never duplicated."""
    from gossip_tpu.rpc.router import fleet_env
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    env = fleet_env(platform="cpu", devices=4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"
    # devices=1 (or None) adds nothing: the solo replica path
    assert "XLA_FLAGS" not in fleet_env(devices=1)
    # an ambient count is the caller's pin — left alone
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    assert fleet_env(devices=4)["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=8"
    # other ambient flags survive the append
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/x")
    assert fleet_env(devices=4)["XLA_FLAGS"] == \
        "--xla_dump_to=/tmp/x --xla_force_host_platform_device_count=4"


def test_fleet_env_inherits_the_ambient_platform(monkeypatch):
    """No CPU default: the replicas run where the caller's JAX would
    (a pin only when asked for one)."""
    from gossip_tpu.rpc.router import fleet_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert fleet_env()["JAX_PLATFORMS"] == "tpu"
    assert fleet_env(platform="cpu")["JAX_PLATFORMS"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in fleet_env()


@pytest.mark.parametrize("platform,replicas,ok", [
    ("tpu", 2, False), ("", 2, False), ("tpu,cpu", 3, False),
    ("cpu", 2, True), ("tpu", 1, True), ("", 1, True)])
def test_replicas_never_share_a_chip(platform, replicas, ok):
    from gossip_tpu.rpc.router import check_replica_platform
    env = {"JAX_PLATFORMS": platform} if platform else {}
    if ok:
        check_replica_platform(env, replicas)
    else:
        with pytest.raises(ValueError, match="share one chip"):
            check_replica_platform(env, replicas)


def test_route_refuses_replicas_on_one_chip(capsys):
    """`route` refuses before it spawns anything when its replicas
    would share the chip."""
    from gossip_tpu import cli
    rc = cli.main(["route", "--replicas", "2", "--port", "0",
                   "--replica-platform", "tpu"])
    assert rc == 2
    assert "share one chip" in capsys.readouterr().err


def test_replica_device_verification_refuses_degraded_mesh():
    """Planted degradation: a live replica serving WITHOUT a mesh
    (exactly what a child missing the host-device-count env degrades
    to) reports serving_devices=1 in its health reply, and the fleet's
    spawn-time gate refuses it loudly instead of running a healthy-
    looking 1-device fleet — a gate that cannot fail is not a gate."""
    from gossip_tpu.rpc.router import _verify_replica_devices
    from gossip_tpu.rpc.sidecar import serve
    server, port = serve(port=0, max_workers=2,
                         batching=ServingConfig(tick_ms=25.0))
    try:
        addr = f"127.0.0.1:{port}"
        _verify_replica_devices(addr, "r0_g0", 1)        # solo: fine
        with pytest.raises(RuntimeError) as ei:
            _verify_replica_devices(addr, "r0_g0", 2)
        msg = str(ei.value)
        assert "serving_devices=1" in msg
        assert "devices_per_replica=2" in msg
    finally:
        server.gossip_batcher.close()
        server.stop(grace=None)


# -- committed record + live smoke ------------------------------------

def test_committed_fleet_crashloop_record_gates_hold():
    """The committed fleet nemesis record
    (artifacts/ledger_fleet_r18.jsonl) re-asserted so it can never
    rot: provenance present, K >= 2 seeded SIGKILLs that all landed
    MID-load, zero acked-request loss, per-request bitwise reply
    parity vs solo dispatch, failover-visible flight-record
    (replica_down / failover / replica_up / control_catchup), and
    recovery to full healthy capacity."""
    events = telemetry.load_ledger(FLEET_RECORD, run="last")
    prov = events[0]
    assert prov["ev"] == "provenance"
    assert len(prov["git_commit"]) == 40
    cfgs = [e for e in events if e.get("ev") == "config"]
    assert cfgs and cfgs[0]["replicas"] >= 3
    verdict = [e for e in events if e.get("ev") == "verdict"][-1]
    assert verdict["ok"] is True
    assert verdict["kills"] >= 2
    assert verdict["zero_acked_loss"] is True
    assert verdict["errors"] == 0
    assert verdict["acked"] == verdict["requests"]
    assert verdict["bitwise_equal"] is True
    assert verdict["mismatches"] == 0
    assert verdict["failovers"] >= 1
    assert verdict["recovered_full_capacity"] is True
    assert verdict["healthy"] == cfgs[0]["replicas"]
    # every kill landed strictly mid-load
    kills = [e for e in events if e.get("ev") == "kill"]
    assert len(kills) == verdict["kills"]
    for k in kills:
        assert 0 < k["acked"] < verdict["requests"]
    # the failover flight-record is complete: downs, re-dispatches,
    # re-admissions, and the gossip catchup of every respawn
    kinds = {e.get("ev") for e in events}
    assert {"replica_down", "failover", "replica_up",
            "control_catchup", "respawn", "recovered"} <= kinds
    catchups = [e for e in events if e.get("ev") == "control_catchup"]
    assert len(catchups) >= verdict["kills"]
    for e in catchups:
        assert e["epoch"] >= 2          # up + down survived the wipe


def test_committed_trace_capture_record_gates_hold():
    """The committed request-tracing record
    (artifacts/ledger_trace_r22.jsonl, tools/trace_capture.py)
    re-asserted so it can never rot: provenance present, a 3-replica
    K=1 SIGKILL crashloop with zero acked loss, EVERY trace joined to
    a complete waterfall (failover-replayed included — re-joined live
    here via tools/trace_report.py, not just trusted from the
    verdict), fleet-status seeing the kill and the recovery, and the
    zero-steady-state-cost claim (zero compiles + zero fsyncs at the
    Metrics window edges)."""
    # the trace ledger is MULTI-writer (router + replica children):
    # no run filter — the join is exactly the cross-run contract
    events = telemetry.load_ledger(TRACE_RECORD)
    prov = events[0]
    assert prov["ev"] == "provenance"
    assert len(prov["git_commit"]) == 40
    cfgs = [e for e in events if e.get("ev") == "config"]
    assert cfgs and cfgs[0]["replicas"] >= 3
    verdict = [e for e in events if e.get("ev") == "verdict"][-1]
    assert verdict["ok"] is True
    assert verdict["problems"] == []
    assert verdict["kills"] >= 1
    assert verdict["errors"] == 0
    assert verdict["acked"] == verdict["requests"]
    assert verdict["complete"] == verdict["traces"]
    assert verdict["replayed"] >= 1
    assert verdict["replayed_complete"] >= 1
    assert verdict["fleet_status_saw_kill"] is True
    assert verdict["fleet_status_saw_recovery"] is True
    assert verdict["recovered_full_capacity"] is True
    assert verdict["healthy"] == cfgs[0]["replicas"]
    for k in [e for e in events if e.get("ev") == "kill"]:
        assert 0 < k["acked"] < verdict["requests"]
    # fleet-status's own flight-record: degraded after the kill,
    # healthy again after the probe hysteresis re-admits the respawn
    fs = [e for e in events if e.get("ev") == "fleet_status"]
    assert any(e["degraded"] and e["tag"].startswith("after_kill")
               for e in fs)
    assert any(not e["degraded"] and e["tag"] == "after_recovery"
               for e in fs)
    # the zero-cost window, from the recorded Metrics edge deltas
    cost = [e for e in events if e.get("ev") == "steady_cost"][-1]
    assert cost["ok"] is True
    assert cost["router_fsyncs_delta"] == 0
    assert cost["replicas"]
    for row in cost["replicas"].values():
        assert row["compiles_delta"] in (0, None)
        assert row["fsyncs_delta"] == 0
    # re-join the artifact live: every traced request must close
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_REPO, "tools",
                                     "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    joined = tr.join_traces(events)
    # router-dispatched traces (the capture's measured + steady mix);
    # direct-to-replica warmup calls structurally have no router half
    terminal_tids = {e["trace_id"] for e in events
                     if e.get("ev") == "request_trace"
                     and e.get("source") == "router"}
    assert len(terminal_tids) == verdict["traces"]
    complete = [t for t in terminal_tids
                if tr.waterfall(joined[t])["complete"]]
    assert len(complete) == len(terminal_tids)
    replayed = [t for t in terminal_tids
                if joined[t]["attempts"] > 1]
    assert replayed and all(
        tr.waterfall(joined[t])["complete"] for t in replayed)


# depth tier (tier-1 wall budget): the live fleet smoke spawns 2 jax
# replica subprocesses + a respawn (~2 min); the in-gate fleet surface
# keeps the live in-process failover test above + the committed-record
# pin, and the dry-run fleet_failover family runs a live fleet every
# session
@pytest.mark.slow
def test_fleet_crashloop_smoke_live(tmp_path):
    """tools/fleet_crashloop --smoke end to end: a real subprocess
    fleet, one seeded mid-load SIGKILL, every gate enforced."""
    spec = importlib.util.spec_from_file_location(
        "fleet_crashloop", os.path.join(_REPO, "tools",
                                        "fleet_crashloop.py"))
    fc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fc)
    out = str(tmp_path / "fleet_smoke.jsonl")
    assert fc.main(["--smoke", "--out", out]) == 0
    events = telemetry.load_ledger(out, run="last")
    verdict = [e for e in events if e.get("ev") == "verdict"][-1]
    assert verdict["ok"] is True and verdict["kills"] == 1
