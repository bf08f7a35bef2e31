"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-device behavior is tested without TPU hardware the same way the
reference tested multi-node without a cluster — the reference ran N OS
processes on one machine under Maelstrom (SURVEY.md §4); we run 8 virtual XLA
host devices in one process.

The platform goes through ``jax.config.update`` (a caller may have imported
jax before conftest runs); XLA_FLAGS is still read at backend init, which
has not happened yet at conftest import time.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Force CPU: unit tests must be fast and hermetic, and test-spawned
# children inherit the pin.  GOSSIP_TPU_TEST_PLATFORM=tpu runs the
# suite on a chip instead (one process: a child cannot reach a chip its
# parent holds).
_platform = os.environ.get("GOSSIP_TPU_TEST_PLATFORM", "cpu")
jax.config.update("jax_platforms", _platform)
os.environ["JAX_PLATFORMS"] = _platform

# Compile-once session cache (utils/compile_cache), EVERY tier: one
# cache dir shared by every test-spawned CHILD, so the suite's
# subprocess-heavy tests (CLI re-execs, checkpoint resumes, the
# dry-run contract's cold+warm pair) compile each program once per
# SESSION instead of once per child — what un-slowed the compile-heavy
# resume tests back into tier-1.  It also keeps CLI children off the
# checkout's own .jax_cache/ (cli.py's default), which the operator's
# runs share.  Tests that must measure cold compiles pin "" (or
# pass explicit --compile-cache flags) in their own child envs, which
# override this.  The PERSISTENT XLA layer is deliberately NOT
# enabled in the pytest process itself (no jax.config update here):
# the AOT-store tests must observe real miss->hit round-trips
# in-process, not persistent-cache hits.  The AOT STORE, by contrast, is ambient in-process via
# this env var (trace.aot_timed reads it) and safely so: store hits
# are bitwise-identical executables by contract, and tests that
# assert store choreography pin their own dir over this one.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_pinned_cache = os.environ.get("GOSSIP_TPU_TEST_COMPILE_CACHE")
if _pinned_cache:
    # caller-owned dir for cross-session reuse during local iteration
    os.environ["GOSSIP_COMPILE_CACHE"] = _pinned_cache
else:
    import atexit
    import shutil
    import tempfile
    _session_cache = tempfile.mkdtemp(prefix="gossip_test_compile_cache_")
    os.environ["GOSSIP_COMPILE_CACHE"] = _session_cache
    # a session's cache holds the whole suite's XLA entries + AOT
    # executables (multi-MB) — reap it ourselves rather than betting
    # on /tmp aging
    atexit.register(shutil.rmtree, _session_cache, ignore_errors=True)


# ---------------------------------------------------------------------
# Per-test duration ledger + tier-1 wall headroom warning.
#
# The tier-1 gate is a hard 870 s timeout (ROADMAP.md) that the suite
# approaches silently: every PR adds a test or two, nothing tracks the
# total, and the PR that finally crosses the line fails with an opaque
# `timeout` instead of a named culprit.  So the session records its own
# flight data — one `test` event per test with its wall, a `session`
# summary with the slowest offenders — through the same run-ledger
# layer everything else uses (utils/telemetry), and WARNS at 90% of the
# gate so the rebalance happens one PR early, not one PR late.

import sys  # noqa: E402
import time as _time  # noqa: E402

import pytest  # noqa: E402  (imported after the platform pinning above)

TIER1_GATE_S = 870.0
TIER1_WARN_FRACTION = 0.9

_session_t0 = _time.perf_counter()
_test_walls: dict = {}


def tier1_wall_warning(total_s: float, gate_s: float = TIER1_GATE_S,
                       frac: float = TIER1_WARN_FRACTION):
    """The warning line when a session's wall crosses ``frac`` of the
    tier-1 gate, else None — a plain predicate so the threshold
    arithmetic is unit-testable without running an 800 s session
    (the sweep_cache_eviction pattern)."""
    if total_s <= frac * gate_s:
        return None
    return (f"WARNING: test session wall {total_s:.0f} s exceeds "
            f"{frac:.0%} of the {gate_s:.0f} s tier-1 gate — rebalance "
            "now (mark redundant depth tests `slow`, keep one smoke "
            "per surface) instead of letting the NEXT PR trip the "
            "timeout; per-test walls are in the session ledger "
            "($GOSSIP_TEST_LEDGER, default artifacts/"
            "ledger_tests.jsonl)")


def pytest_runtest_logreport(report):
    # setup + call + teardown all count toward the wall the gate sees
    _test_walls[report.nodeid] = (_test_walls.get(report.nodeid, 0.0)
                                  + report.duration)


def pytest_sessionfinish(session, exitstatus):
    total = _time.perf_counter() - _session_t0
    path = os.environ.get("GOSSIP_TEST_LEDGER")
    explicit = path is not None
    if path is None:
        path = os.path.join(_REPO, "artifacts", "ledger_tests.jsonl")
    if not path:            # explicit "" disables (the GOSSIP_TELEMETRY
        return              # convention)
    try:
        from gossip_tpu.utils import telemetry
        # the ONE provenance-stamping artifact-ledger helper
        # (telemetry.artifact_ledger), shared with the staticcheck
        # findings writer so the choreography cannot drift.  The
        # default path is per-session flight data, rewritten every
        # session (the .gitignore contract) — only an explicit
        # $GOSSIP_TEST_LEDGER appends, so a caller can aggregate
        # several sessions into one shared ledger.  fsync=False
        # (helper default): flush-only is plenty for test flight
        # data, and ~300 per-event fsyncs would tax the very wall
        # being measured.
        with telemetry.artifact_ledger(path,
                                       rewrite=not explicit) as led:
            for nodeid, wall in sorted(_test_walls.items(),
                                       key=lambda kv: -kv[1]):
                led.event("test", nodeid=nodeid,
                          wall_s=round(wall, 3))
            led.event("session", exitstatus=int(exitstatus),
                      tests=len(_test_walls),
                      wall_s=round(total, 1),
                      gate_s=TIER1_GATE_S,
                      over_warn_threshold=bool(
                          tier1_wall_warning(total)))
    except Exception as e:      # the recorder must never fail the suite
        sys.stderr.write(f"conftest: test ledger disabled ({e})\n")


def pytest_terminal_summary(terminalreporter):
    msg = tier1_wall_warning(_time.perf_counter() - _session_t0)
    if msg:
        terminalreporter.write_line(msg, yellow=True, bold=True)


# ---------------------------------------------------------------------
# The 4-device cold+warm dry-run pair, session-scoped: ONE pair serves
# every consumer — the dry-run contract tests (tests/test_graft_entry)
# and the ledger_diff regression gate (tests/test_ledger_diff) — so
# tier-1 pays the two ~30 s runs exactly once.

def _load_graft_entry():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(_REPO, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# Compile-count delta probe (the traced-operand PR): PR 3's
# JitCompileMonitor, wrapped as a reusable context so tests can pin
# "K scenarios, ONE compile" without copy-pasting monitoring plumbing.

@pytest.fixture(scope="session")
def _compile_monitor():
    # one per process: jax's listener registration is permanent
    from gossip_tpu.utils.compile_cache import JitCompileMonitor
    return JitCompileMonitor()


@pytest.fixture
def assert_compiles(_compile_monitor):
    """``with assert_compiles(n):`` — assert the block triggered exactly
    ``n`` REAL XLA backend compiles (jax.monitoring's per-compile
    duration event; in-memory executable reuse triggers none).  Pass
    ``at_most=True`` for an upper bound — the right form for "the
    first call may compile auxiliaries, later calls must compile
    NOTHING" pins.  Skips when this jax cannot report backend-compile
    events (the monitor's degrade path)."""
    import contextlib

    mon = _compile_monitor
    if not mon.durations_available:
        pytest.skip("jax.monitoring has no duration listener on this "
                    "toolchain; compile-count pins unavailable")

    @contextlib.contextmanager
    def _ctx(expected: int, at_most: bool = False):
        before = mon.backend_compiles
        yield
        got = mon.backend_compiles - before
        if at_most:
            assert got <= expected, (
                f"block compiled {got} XLA programs, expected at most "
                f"{expected} — a memoized loop lost its cache hit "
                "(schedule content leaked back into a trace?)")
        else:
            assert got == expected, (
                f"block compiled {got} XLA programs, expected exactly "
                f"{expected}")
    return _ctx


@pytest.fixture(scope="session")
def dryrun_pair(tmp_path_factory):
    """(cold, warm) 4-device dry runs sharing ONE fresh compile-cache
    dir — the cross-process warm-start proof: process A populates the
    cache, process B (expect_warm=True: the body ENFORCES the
    first_warm_ms budgets) must hit it.  4 devices for tier-1 wall
    budget; the full 8-device shape with the >= 3x acceptance ratio is
    pinned on the committed records (tests/test_graft_entry).  Each run
    keeps its own ledger; both carry round-metrics events for the
    driver-level families (ops/round_metrics — the dry-run ledger is
    always on)."""
    graft_entry = _load_graft_entry()
    tmp = tmp_path_factory.mktemp("dryrun_cc")
    cache = str(tmp / "compile_cache")
    cold_ledger = str(tmp / "cold_ledger.jsonl")
    warm_ledger = str(tmp / "warm_ledger.jsonl")
    cold = graft_entry.dryrun_multichip(4, ledger_path=cold_ledger,
                                        compile_cache_dir=cache)
    warm = graft_entry.dryrun_multichip(4, ledger_path=warm_ledger,
                                        compile_cache_dir=cache,
                                        expect_warm=True)
    return {"cold": cold, "warm": warm, "cache": cache}
