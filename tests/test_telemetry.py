"""Run-ledger telemetry (utils/telemetry): schema, crash contract, and
the flight-recorder proof — a SIGKILLed dry run leaves a parseable
ledger with provenance and every span up to the kill point."""

import os
import signal
import subprocess
import sys
import time

import pytest

from gossip_tpu.utils import telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ledger_schema_spans_counters_gauges(tmp_path):
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p, argv=["prog", "--x"]) as led:
        with led.span("outer", tag="t") as ext:
            with led.span("inner"):
                pass
            ext["rows"] = 3
        led.counter("timeouts")
        led.counter("timeouts", 2)
        led.gauge("coverage", 0.5)
        led.event("probe", outcome="ok")
    events = telemetry.load_ledger(p)
    # provenance first, with the one artifact schema's keys
    prov = events[0]
    assert prov["ev"] == "provenance"
    for key in ("run_id", "git_commit", "captured", "argv", "jax_version",
                "schema"):
        assert key in prov, key
    assert prov["argv"] == ["prog", "--x"]
    # every line is run-scoped and timestamped
    assert all(e["run"] == prov["run_id"] and "ts" in e for e in events)
    # span nesting via parent ids; walls recorded on end
    starts = {e["name"]: e for e in events if e["ev"] == "span_start"}
    ends = {e["name"]: e for e in events if e["ev"] == "span_end"}
    assert starts["inner"]["parent"] == starts["outer"]["span"]
    assert ends["outer"]["wall_ms"] >= ends["inner"]["wall_ms"] >= 0
    assert ends["outer"]["ok"] and ends["outer"]["rows"] == 3
    assert starts["outer"]["tag"] == "t"
    # counters carry a running total so partial ledgers read high-water
    totals = [e["total"] for e in events if e["ev"] == "counter"]
    assert totals == [1, 3]


def test_span_records_failure_and_start_precedes_work(tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    with pytest.raises(RuntimeError):
        with led.span("doomed"):
            raise RuntimeError("boom")
    led.close()
    events = telemetry.load_ledger(p)
    end = next(e for e in events if e["ev"] == "span_end")
    assert end["ok"] is False
    # span_start is durable BEFORE the block body runs — the kill-proof
    # property (the start line was already fsynced when the body raised)
    assert [e["ev"] for e in events] == ["provenance", "span_start",
                                        "span_end"]


def test_from_env_null_and_activate(tmp_path, monkeypatch):
    monkeypatch.delenv(telemetry.ENV_VAR, raising=False)
    led = telemetry.from_env()
    assert isinstance(led, telemetry.NullLedger)
    with led.span("x") as ext:       # the no-op twin still yields a dict
        ext["k"] = 1
    led.event("y")
    led.counter("z")
    # explicit empty disables even over a default path
    monkeypatch.setenv(telemetry.ENV_VAR, "")
    assert isinstance(
        telemetry.from_env(str(tmp_path / "d.jsonl")),
        telemetry.NullLedger)
    # env var wins; activate() installs/restores the ambient ledger
    p = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(telemetry.ENV_VAR, p)
    real = telemetry.from_env()
    assert real.path == os.path.abspath(p)
    prev = telemetry.activate(real)
    try:
        assert telemetry.current() is real
    finally:
        telemetry.activate(prev)
    real.close()
    assert telemetry.load_ledger(p)[0]["ev"] == "provenance"


def test_torn_lines_dropped_and_strict_mode(tmp_path):
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p) as led:
        led.event("a")
        led.event("b")
    n = len(telemetry.load_ledger(p))
    # a kill between write and fsync tears at most one line per writer
    with open(p, "a") as f:
        f.write('{"ev": "torn_mid_wri')
    assert len(telemetry.load_ledger(p)) == n
    # mid-file tears happen in SHARED files (a killed step subprocess,
    # then the parent appends) — the post-mortem read-out must survive
    # them, so the default drops; strict mode (single-writer) raises
    lines = [ln for ln in open(p).read().splitlines() if ln.strip()]
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(lines[0] + "\nGARBAGE\n" + lines[1] + "\n")
    assert len(telemetry.load_ledger(bad)) == 2
    with pytest.raises(ValueError, match="corrupt"):
        telemetry.load_ledger(bad, strict=True)


def test_new_writer_heals_torn_tail_of_shared_file(tmp_path):
    """A writer opening a file whose last line is torn (killed previous
    writer) must newline-separate before its provenance line — the
    fragment stays its own (dropped) line instead of corrupting the
    new run's first event."""
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p) as led:
        led.event("a")
    with open(p, "a") as f:
        f.write('{"ev": "killed_mid_wri')       # no newline
    with telemetry.Ledger(p) as led2:
        led2.event("b")
    events = telemetry.load_ledger(p)
    assert any(e["ev"] == "provenance" and e["run"] == led2.run_id
               for e in events)
    assert any(e["ev"] == "b" for e in events)


def test_load_ledger_run_filter(tmp_path):
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p) as a:
        a.event("first_run_event")
    with telemetry.Ledger(p) as b:
        b.event("second_run_event")
    assert a.run_id != b.run_id
    last = telemetry.load_ledger(p, run="last")
    assert {e["run"] for e in last} == {b.run_id}
    assert any(e["ev"] == "second_run_event" for e in last)
    only_a = telemetry.load_ledger(p, run=a.run_id)
    assert any(e["ev"] == "first_run_event" for e in only_a)
    assert not any(e["ev"] == "second_run_event" for e in only_a)


def test_maybe_aot_timed_emits_driver_timing(tmp_path):
    """Every sharded driver's wall decomposition reaches the ambient
    ledger through the ONE timing chokepoint (utils/trace) — no
    per-driver plumbing."""
    import jax.numpy as jnp

    import jax
    from gossip_tpu.utils.trace import maybe_aot_timed
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    prev = telemetry.activate(led)
    try:
        timing = {"init_build_s": 0.001}
        out = maybe_aot_timed(jax.jit(lambda x: x * 2), timing,
                              jnp.arange(4))
        assert int(out[1]) == 2
        # no ledger event without a timing dict (the plain-call path)
        maybe_aot_timed(jax.jit(lambda x: x * 2), None, jnp.arange(4))
    finally:
        telemetry.activate(prev)
        led.close()
    events = [e for e in telemetry.load_ledger(p)
              if e["ev"] == "driver_timing"]
    assert len(events) == 1
    assert events[0]["compile_s"] >= 0
    assert events[0]["steady_s"] > 0
    assert events[0]["init_build_s"] == 0.001


@pytest.mark.skipif(not hasattr(signal, "SIGKILL"),
                    reason="needs POSIX SIGKILL")
def test_flight_recorder_survives_sigkill_mid_dryrun(tmp_path):
    """THE flight-recorder proof (ISSUE 2 acceptance): SIGKILL a
    dry-run family mid-round and the ledger on disk still parses,
    containing provenance plus every span up to the kill point.

    The child runs the real ``_dryrun_multichip_body`` on a 2-device
    CPU mesh; the parent polls the ledger and pulls the
    trigger as soon as the first FAMILY span has started (i.e. mid
    compile/round of dense_pushpull) — exactly the dark-round shape:
    a killed capture with work in flight."""
    ledger = str(tmp_path / "killed.jsonl")
    env = dict(os.environ)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    env["PYTHONPATH"] = _REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["GOSSIP_TELEMETRY"] = ledger
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from __graft_entry__ import _dryrun_multichip_body; "
         "_dryrun_multichip_body(2)"],
        env=env, cwd=_REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 180
        killed_during = None
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("dry run finished before the kill — poll "
                            "window missed (raise the family count?)")
            if os.path.exists(ledger):
                try:
                    events = telemetry.load_ledger(ledger)
                except ValueError:
                    events = []
                fam_spans = [e for e in events
                             if e.get("ev") == "span_start"
                             and ":" in (e.get("name") or "")]
                if fam_spans:
                    killed_during = fam_spans[0]["name"]
                    proc.send_signal(signal.SIGKILL)
                    break
            time.sleep(0.05)
        else:
            pytest.fail("no family span appeared within 180 s")
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # the ledger parses IN FULL (fsync-per-event contract: at most a
    # torn final line, which the loader drops by contract)
    events = telemetry.load_ledger(ledger)
    assert events[0]["ev"] == "provenance"
    assert events[0]["git_commit"] is None or len(
        events[0]["git_commit"]) == 40
    # runtime context captured before any family ran
    assert any(e["ev"] == "runtime" for e in events)
    # every span up to the kill point is present; the family the run
    # died inside shows an un-ended span — the "why was it dark" answer
    names = [e["name"] for e in events if e["ev"] == "span_start"]
    assert "dryrun_multichip" in names
    assert killed_during in names
    ended = {e["span"] for e in events if e["ev"] == "span_end"}
    started = {e["span"]: e["name"] for e in events
               if e["ev"] == "span_start"}
    unclosed = [started[s] for s in started if s not in ended]
    assert killed_during in unclosed
    # and the report tool renders the partial ledger without error,
    # naming the span the run died in
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    md = telemetry_report.render_markdown(events)
    assert "unclosed" in md
    assert killed_during.split(":")[0] in md


@pytest.mark.skipif(not hasattr(signal, "SIGKILL"),
                    reason="needs POSIX SIGKILL")
def test_flight_recorder_survives_sigkill_on_trace_ledger(tmp_path):
    """Satellite pin: the flight-recorder contract extends to a
    trace-BEARING ledger.  SIGKILL a serving process mid-traffic: the
    ledger still parses (at most a torn line, dropped by contract),
    every ``request_trace`` written before the kill survives with a
    usable 16-hex trace_id, and ``load_ledger(trace_id=...)``
    round-trips on the partial file — a crash must not cost the
    waterfalls of the requests it already acked."""
    ledger = str(tmp_path / "killed_trace.jsonl")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["GOSSIP_TELEMETRY"] = ledger
    child = (
        "from gossip_tpu.utils import telemetry\n"
        "telemetry.activate(telemetry.from_env("
        "argv=['trace_kill_child']))\n"
        "from gossip_tpu.config import ServingConfig\n"
        "from gossip_tpu.rpc.sidecar import SidecarClient, serve\n"
        "server, port = serve(port=0, batching=ServingConfig("
        "tick_ms=10, max_batch=8))\n"
        "client = SidecarClient(f'127.0.0.1:{port}')\n"
        "i = 0\n"
        "while True:\n"
        "    client.run(backend='jax-tpu',\n"
        "               proto={'mode': 'push', 'fanout': 2},\n"
        "               topology={'family': 'complete', 'n': 32},\n"
        "               run={'max_rounds': 3, 'engine': 'xla',\n"
        "                    'seed': i}, curve=True)\n"
        "    i += 1\n")
    proc = subprocess.Popen([sys.executable, "-c", child], env=env,
                            cwd=_REPO, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("serving child exited before the kill")
            if os.path.exists(ledger):
                try:
                    events = telemetry.load_ledger(ledger)
                except ValueError:
                    events = []
                if any(e.get("ev") == "request_trace"
                       for e in events):
                    proc.send_signal(signal.SIGKILL)
                    break
            time.sleep(0.05)
        else:
            pytest.fail("no request_trace appeared within 180 s")
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    events = telemetry.load_ledger(ledger)
    assert events[0]["ev"] == "provenance"
    traced = [e for e in events if e.get("ev") == "request_trace"]
    assert traced
    tid = traced[0]["trace_id"]
    assert len(tid) == 16
    assert all(c in "0123456789abcdef" for c in tid)
    sub = telemetry.load_ledger(ledger, trace_id=tid)
    assert sub and all(e.get("trace_id") == tid for e in sub)
    assert any(e.get("ev") == "request_trace" for e in sub)


def test_reserved_keys_never_collide(tmp_path):
    """A caller-supplied run/ts/ev field (the pre-ledger log
    format carried its own 'ts') must not corrupt run filtering — it
    is prefixed, never overwriting."""
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p) as led:
        led.event("probe", ts="2026-01-01T00:00:00", run="bogus", ev="x")
    events = telemetry.load_ledger(p, run="last")
    probe = next(e for e in events if e["ev"] == "probe")
    assert probe["run"] == events[0]["run_id"]       # filtering intact
    assert probe["x_ts"] == "2026-01-01T00:00:00"
    assert probe["x_run"] == "bogus" and probe["x_ev"] == "x"


def test_disabled_file_keeps_echo_diagnostics(tmp_path, monkeypatch,
                                              capsys):
    """GOSSIP_TELEMETRY='' disables the FILE, but an echo-requesting
    surface (bench.py) still gets stderr diagnostics — disabling the
    recorder must never recreate the silent dark window."""
    monkeypatch.setenv(telemetry.ENV_VAR, "")
    led = telemetry.from_env(str(tmp_path / "d.jsonl"), echo=True)
    assert isinstance(led, telemetry.EchoLedger)
    assert led.path is None
    led.event("probe", outcome="timeout")
    led.counter("probe_timeouts")
    err = capsys.readouterr().err
    assert '"probe"' in err and "timeout" in err
    assert not os.path.exists(tmp_path / "d.jsonl")


def test_sync_false_event_still_lands(tmp_path):
    """sync=False (the in-window driver_timing path) skips only the
    fsync; the flushed line is still on disk immediately after."""
    p = str(tmp_path / "led.jsonl")
    led = telemetry.Ledger(p)
    led.event("driver_timing", sync=False, steady_s=0.1)
    events = telemetry.load_ledger(p)     # ledger still open
    led.close()
    assert any(e["ev"] == "driver_timing" and e["steady_s"] == 0.1
               for e in events)


def test_device_memory_stats_shape():
    """CPU devices report no memory_stats: the helper returns None (and
    memory_snapshot emits nothing) rather than fabricating zeros."""
    stats = telemetry.device_memory_stats()
    assert stats is None or (isinstance(stats, list) and stats
                             and "device" in stats[0])


def test_shared_writer_midfile_tear_is_dropped_not_fatal(tmp_path):
    """The SHARED-file crash shape end to end: writer A is killed
    mid-write (its fragment has no newline), writer B then appends a
    whole run.  B's leading-newline self-heal keeps the fragment its
    own line; the default loader drops exactly that line and keeps
    EVERY event on both sides of it — a mid-file tear, unlike the
    single-writer tail tear, so strict mode refuses the file."""
    p = str(tmp_path / "shared.jsonl")
    with telemetry.Ledger(p) as a:
        a.event("step", n=1)
    with open(p, "a") as f:
        f.write('{"ev": "step", "n": 2, "half_writ')   # killed writer
    with telemetry.Ledger(p) as b:
        b.event("step", n=3)
        b.event("step", n=4)
    events = telemetry.load_ledger(p)
    assert [e["n"] for e in events if e["ev"] == "step"] == [1, 3, 4]
    # both runs' provenance survived around the tear
    assert [e["ev"] for e in events].count("provenance") == 2
    with pytest.raises(ValueError, match="corrupt"):
        telemetry.load_ledger(p, strict=True)


def test_non_finite_values_stay_strict_json(tmp_path):
    """A poisoned gauge/counter value (nan/inf — a diverged measurement
    upstream) must record the poisoning WITHOUT breaking the file for
    strict-JSON consumers: Python's json would happily write NaN
    literals that jq and every non-Python reader reject."""
    import json as _json
    import math
    p = str(tmp_path / "led.jsonl")
    with telemetry.Ledger(p) as led:
        led.gauge("bad_rate", float("nan"))
        led.gauge("worse_rate", float("inf"))
        led.event("probe", wall_s=float("-inf"),
                  nested={"deep": float("nan"), "fine": 1.5})
        led.gauge("fine", 0.25)
    # every line parses under STRICT json (NaN/Infinity literals raise)
    def no_constants(s):
        raise ValueError(f"non-strict JSON constant {s!r}")
    with open(p) as f:
        rows = [_json.loads(ln, parse_constant=no_constants)
                for ln in f if ln.strip()]
    gauges = {r["name"]: r["value"] for r in rows if r["ev"] == "gauge"}
    assert gauges == {"bad_rate": "nan", "worse_rate": "inf",
                      "fine": 0.25}
    probe = next(r for r in rows if r["ev"] == "probe")
    assert probe["wall_s"] == "-inf"
    assert probe["nested"] == {"deep": "nan", "fine": 1.5}
    # and the crash-contract loader reads them back the same way
    evs = telemetry.load_ledger(p)
    assert any(e.get("value") == "nan" for e in evs)
    assert not any(isinstance(e.get("value"), float)
                   and math.isnan(e["value"]) for e in evs)


def _load_report_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(_REPO, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_report_check_gate_health(tmp_path, capsys):
    """telemetry_report --check: exit 0 on a healthy ledger, exit 1
    naming the problem on an unclosed span or a missing provenance
    line — the CI hook for ledger health."""
    report = _load_report_tool()
    good = str(tmp_path / "good.jsonl")
    with telemetry.Ledger(good) as led:
        with led.span("fine"):
            pass
    assert report.main([good, "--check"]) == 0

    # a run killed inside a span: span_start durable, no span_end
    wedged = str(tmp_path / "wedged.jsonl")
    led = telemetry.Ledger(wedged)
    cm = led.span("doomed_family")
    cm.__enter__()                         # never exited: the kill
    led.close()
    assert report.main([wedged, "--check"]) == 1
    err = capsys.readouterr().err
    assert "unclosed span" in err and "doomed_family" in err

    # an unknown explicit --run id is an ERROR, not an empty selection
    # misdiagnosed as "no provenance" (the ledger_diff convention)
    with pytest.raises(SystemExit, match="not in"):
        report.main([good, "--check", "--run", "no_such_run"])

    # no provenance at all (hand-rolled pre-ledger file)
    bare = str(tmp_path / "bare.jsonl")
    with open(bare, "w") as f:
        f.write('{"ev": "probe", "outcome": "ok"}\n')
    assert report.main([bare, "--check"]) == 1
    assert "no provenance" in capsys.readouterr().err

    # --all-runs checks every run in a shared file
    shared = str(tmp_path / "shared.jsonl")
    with telemetry.Ledger(shared) as led:
        with led.span("ok_span"):
            pass
    led2 = telemetry.Ledger(shared)
    cm = led2.span("dead_run_span")
    cm.__enter__()
    led2.close()
    assert report.main([shared, "--all-runs", "--check"]) == 1
    assert "dead_run_span" in capsys.readouterr().err
