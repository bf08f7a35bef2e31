"""Run-ledger telemetry: the crash-safe flight recorder every surface
writes through (docs/OBSERVABILITY.md holds the full schema).

The reference node has zero instrumentation — every number came from
the external Maelstrom checker (SURVEY.md §5) — and this repo's own
timing story was fragmented ad-hoc dicts until round 7: ``timing=``
splits in utils/trace, per-family keys in the dry run, bespoke JSON in
tools/hw_refresh.py, diagnostics printed to stderr and lost.  A round
whose only evidence was a hand-rolled log is the motivating failure:
the capture path must leave mechanically checkable evidence even when
the process is SIGKILLed mid-round.

This module is that one layer:

  * a :class:`Ledger` is a run-scoped, append-only JSONL file opened
    once per run with a **provenance** first line (run id, git commit,
    jax version, argv, timestamps);
  * a nested **span** API (``with ledger.span("compile"): ...``)
    recording monotonic walls and optional device ``memory_stats()``
    snapshots;
  * **counters/gauges** for discrete occurrences (probe timeouts,
    fallbacks);
  * **crash-safe flushing**: every event is written as one line and
    fsynced before control returns — a SIGKILLed or hung run leaves
    a parseable partial ledger (at most one torn line per writer,
    which :func:`load_ledger` drops by contract; a new writer
    newline-heals a shared file's torn tail on open).

Zero steady-state cost: nothing here runs inside a compiled loop.
Spans wrap whole driver calls on the host; per-round coverage/msgs
stay on device (carried in the scan/while_loop, exported once), so
telemetry adds no host callbacks to steady state — the dry-run budget
guard (tools/dryrun_budgets.json) runs with telemetry enabled and
stays green.

jax is only imported lazily (``record_runtime`` / memory snapshots):
bench.py's parent process deliberately never initializes a backend —
probing happens in subprocesses — and the go-native paths must stay
runnable without jax (the utils/trace deferred-import pattern).

``GOSSIP_TELEMETRY=<path>`` is the ambient switch: :func:`from_env`
opens a ledger there (appending — multiple runs share one flight
recorder file, distinguished by the per-line ``run`` id), or returns
the no-op :class:`NullLedger` when unset and no default is given.
Render a ledger with tools/telemetry_report.py.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import subprocess
import sys
import threading
import time
import uuid
from typing import IO, Iterator, Optional

SCHEMA_VERSION = 1
ENV_VAR = "GOSSIP_TELEMETRY"

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _git_commit() -> Optional[str]:
    """HEAD of the repo this module ships in, or None (source exports
    without .git, or no git binary — provenance tolerates absence, the
    validator only requires the KEY to be present)."""
    try:
        p = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_REPO,
                           capture_output=True, text=True, timeout=30)
        out = p.stdout.strip()
        return out if p.returncode == 0 and len(out) == 40 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _jax_version() -> Optional[str]:
    """jax's version WITHOUT importing (and thereby initializing) it:
    importlib.metadata reads dist-info only.  Already-imported jax is
    read directly (cheaper, and correct even for editable installs)."""
    mod = sys.modules.get("jax")
    if mod is not None:
        return getattr(mod, "__version__", None)
    try:
        import importlib.metadata
        return importlib.metadata.version("jax")
    except Exception:
        return None


def provenance(argv=None) -> dict:
    """The one provenance schema every new-format artifact carries
    (tools/validate_artifacts.py contract): ``run_id``, ``git_commit``,
    ``captured`` plus toolchain/process context.  Embed this dict under
    a ``"provenance"`` key in plain-JSON artifacts; ledgers carry it as
    their first event line."""
    return {
        "run_id": uuid.uuid4().hex[:12],
        "schema": SCHEMA_VERSION,
        "git_commit": _git_commit(),
        "captured": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "argv": list(sys.argv) if argv is None else list(argv),
        "jax_version": _jax_version(),
        "python": sys.version.split()[0],
        "platform": sys.platform,
        "pid": os.getpid(),
    }


def new_trace_id() -> str:
    """A fresh request correlation id (16 hex chars) — minted ONCE per
    logical request by the outermost client (rpc/sidecar.SidecarClient)
    and carried verbatim through gRPC metadata across router dispatch,
    failover re-dispatch, and batcher admission, so every ledger event
    a request touches joins on the same id (tools/trace_report.py).
    uuid4-derived: no coordination, no clock, collision odds at any
    realistic request volume are negligible (64 bits)."""
    return uuid.uuid4().hex[:16]


def _finite(x):
    """Non-finite floats replaced by their reprs ('nan'/'inf'/'-inf'),
    recursively — the ledger must stay STRICT JSON (jq and every
    non-Python consumer reject the NaN/Infinity literals Python's json
    would otherwise emit), and a poisoned gauge must record the fact of
    the poisoning, not corrupt the file."""
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def _dumps(obj) -> str:
    """json.dumps that never emits non-strict NaN/Infinity literals:
    the cheap strict attempt first, the :func:`_finite` rewrite only
    when a non-finite value is actually present.  ``default=str``
    catches numpy scalars — a numpy nan stringifies to "nan" there,
    consistent with the rewrite."""
    try:
        return json.dumps(obj, default=str, allow_nan=False)
    except ValueError:
        return json.dumps(_finite(obj), default=str, allow_nan=False)


class Ledger:
    """Append-only JSONL flight recorder; one instance per run.

    Every emit is one ``f.write(line)`` + flush + fsync, so a SIGKILL
    at any point leaves every prior event durable and at most the
    final line torn (:func:`load_ledger` drops a torn tail).  Lines
    all carry ``ev`` (event kind), ``ts`` (wall-clock seconds) and
    ``run`` (this run's id) — multiple runs can append to one file and
    stay separable.

    ``echo`` mirrors each line to stderr (bench.py's probe events stay
    operator-visible without a second ad-hoc print path).  ``fsync``
    can be disabled for high-rate callers that only need flush
    semantics; the default is the flight-recorder contract.
    """

    # a recording ledger: surfaces that would pay real work to PREPARE
    # an emission (round-metric device transfers — ops/round_metrics)
    # check this instead of emitting into a void
    active = True

    def __init__(self, path: str, argv=None, echo: bool = False,
                 fsync: bool = True):
        self.path = os.path.abspath(path)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._f: Optional[IO[str]] = open(self.path, "a")
        self._echo = echo
        self._fsync = fsync
        # fsyncs actually issued: the zero-new-fsyncs-in-the-timed-path
        # claim (request tracing, docs/OBSERVABILITY.md) is verified by
        # reading this counter across a measured window, not by trust
        self.fsyncs = 0
        self._span_stack: list = []
        self._next_span = 1
        self._counters: dict = {}
        prov = provenance(argv)
        self.run_id = prov["run_id"]
        self._emit("provenance", prov)

    # -- core ----------------------------------------------------------

    def _emit(self, ev: str, fields: dict, sync: bool = True):
        if self._f is None:
            return
        obj = {"ev": ev, "ts": round(time.time(), 3), "run": self.run_id}
        # reserved keys never collide silently — a caller-supplied
        # "run"/"ts"/"ev" would break run filtering and the report's
        # timeline, so they are prefixed instead of overwriting (the
        # pre-ledger formats carried their own "ts")
        fields = dict(fields)
        for k in ("ev", "ts", "run"):
            if k in fields:
                fields[f"x_{k}"] = fields.pop(k)
        obj.update(fields)
        line = _dumps(obj)
        try:
            # leading newline: every write SELF-HEALS a torn tail left
            # by any sibling writer killed mid-write on a shared file
            # (an already-open append handle would otherwise merge its
            # next event into the fragment).  Costs an occasional blank
            # line, which every reader here skips.
            self._f.write("\n" + line + "\n")
            self._f.flush()
            if self._fsync and sync:
                os.fsync(self._f.fileno())
                self.fsyncs += 1
        except OSError as e:
            # the flight recorder must never be what kills the flight
            # (disk full mid-run): warn once, stop recording
            sys.stderr.write(f"telemetry: ledger write failed, "
                             f"disabling recorder: {e}\n")
            try:
                self._f.close()
            except OSError:
                pass
            self._f = None
            return
        if self._echo:
            sys.stderr.write(line + "\n")

    def event(self, kind: str, sync: bool = True, **fields):
        """A free-form event line (``probe``, ``family``, ``step`` ...);
        reserved kinds (``provenance``, ``span_start``, ``span_end``,
        ``counter``, ``gauge``) have dedicated emitters.

        ``sync=False`` skips the per-event fsync (flush only) — for
        emitters that run INSIDE a caller's timed window, where fsync
        latency would leak into the wall being measured
        (utils/trace.maybe_aot_timed).  Durability then arrives with
        the next fsynced event; the flushed line still survives any
        crash that isn't a whole-OS power loss."""
        self._emit(kind, fields, sync=sync)

    def counter(self, name: str, inc: int = 1):
        """Monotonic occurrence count; each update is durable, and the
        running total rides along so a partial ledger still reads the
        high-water without re-summing."""
        total = self._counters.get(name, 0) + inc
        self._counters[name] = total
        self._emit("counter", {"name": name, "inc": inc, "total": total})

    def gauge(self, name: str, value, sync: bool = True):
        """``sync=False`` is for gauges emitted from INSIDE a caller's
        timed window (the sweep's cache stats — the sweep call itself
        is what the dry run measures): flush-only, same contract as
        ``event(..., sync=False)``."""
        self._emit("gauge", {"name": name, "value": value}, sync=sync)

    # -- spans ---------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, memory: bool = False,
             **attrs) -> Iterator[dict]:
        """Nested wall-clock span.  Emits ``span_start`` immediately
        (durable before the work begins — a killed run still shows the
        span was entered) and ``span_end`` with the monotonic wall on
        exit; ``ok`` records whether the block raised.  Yields a dict
        the block can stuff extra fields into; they land on the end
        event.  ``memory=True`` snapshots device ``memory_stats()`` at
        exit (TPU backends report bytes_in_use/peak_bytes_in_use; CPU
        devices have none and the field is omitted).

        The ledger writes bracket the timed region — span walls never
        include the fsync cost of their own events."""
        span_id = self._next_span
        self._next_span += 1
        parent = self._span_stack[-1] if self._span_stack else None
        # structural keys win over caller attrs of the same name
        self._emit("span_start", {**attrs, "span": span_id,
                                  "parent": parent, "name": name})
        self._span_stack.append(span_id)
        extra: dict = {}
        t0 = time.perf_counter()
        ok = True
        try:
            yield extra
        except BaseException:
            ok = False
            raise
        finally:
            wall_ms = (time.perf_counter() - t0) * 1e3
            self._span_stack.pop()
            if memory:
                mem = device_memory_stats()
                if mem is not None:
                    extra.setdefault("memory", mem)
            self._emit("span_end", {**extra, "span": span_id,
                                    "parent": parent, "name": name,
                                    "wall_ms": round(wall_ms, 3),
                                    "ok": ok})

    # -- runtime context ----------------------------------------------

    def record_runtime(self):
        """Backend/platform/device-count provenance from a process that
        has already initialized jax (the dry-run body, capture tools).
        Separate from __init__ because opening a ledger must never be
        the thing that initializes a backend (a chip belongs to one
        process; the ledger's opener may not be it)."""
        try:
            import jax
            devs = jax.devices()
            self._emit("runtime", {
                "backend": jax.default_backend(),
                "device_count": len(devs),
                "device_kind": (getattr(devs[0], "device_kind", None)
                                if devs else None),
                "jax_version": jax.__version__})
        except Exception as e:
            self._emit("runtime",
                       {"error": f"{type(e).__name__}: {e}"[:300]})

    def memory_snapshot(self, tag: str = ""):
        """One ``memory`` event with per-device memory_stats (no-op
        fields on backends that expose none)."""
        mem = device_memory_stats()
        if mem is not None:
            self._emit("memory", {"tag": tag, "devices": mem})

    def close(self):
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class NullLedger:
    """No-op twin so hot surfaces can call unconditionally; the active
    ledger is a pure config choice (GOSSIP_TELEMETRY), never an
    if-tree at every call site."""

    path = None
    run_id = None
    active = False
    fsyncs = 0

    def event(self, kind, sync=True, **fields):
        pass

    def counter(self, name, inc=1):
        pass

    def gauge(self, name, value, sync=True):
        pass

    @contextlib.contextmanager
    def span(self, name, memory=False, **attrs):
        yield {}

    def record_runtime(self):
        pass

    def memory_snapshot(self, tag=""):
        pass

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class EchoLedger(NullLedger):
    """File-less ledger that still echoes events to stderr — what an
    echo-requesting surface (bench.py) gets when the operator disabled
    the file with GOSSIP_TELEMETRY="": the flight-recorder FILE is
    off, but diagnostics must never go silent (the lesson this layer
    exists for)."""

    active = True

    def event(self, kind, sync=True, **fields):
        obj = {"ev": kind, "ts": round(time.time(), 3)}
        obj.update(fields)
        sys.stderr.write(json.dumps(obj, default=str) + "\n")

    def counter(self, name, inc=1):
        self.event("counter", name=name, inc=inc)

    def gauge(self, name, value, sync=True):
        self.event("gauge", name=name, value=value)


def device_memory_stats():
    """[{device, **memory_stats}] for devices that report stats, or
    None (jax absent / not initialized / CPU-only — never imports jax
    into a process that hasn't already paid for it)."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        rows = []
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", lambda: None)()
            if stats:
                rows.append({"device": str(d),
                             **{k: stats[k] for k in
                                ("bytes_in_use", "peak_bytes_in_use",
                                 "bytes_limit") if k in stats}})
        return rows or None
    except Exception:
        return None


# -- ambient ledger ---------------------------------------------------

_CURRENT: object = NullLedger()


def current():
    """The process-ambient ledger (NullLedger unless activated) —
    utils/trace.maybe_aot_timed emits driver timing through this, so
    every sharded driver's wall decomposition reaches the flight
    recorder without threading a ledger argument through the world."""
    return _CURRENT


def activate(ledger):
    """Install ``ledger`` as the ambient one; returns the previous
    (restore it in a finally for scoped use)."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = ledger
    return prev


def from_env(default_path: Optional[str] = None, argv=None,
             echo: bool = False):
    """Ledger at $GOSSIP_TELEMETRY, else at ``default_path``, else the
    NullLedger.  GOSSIP_TELEMETRY="" explicitly disables the FILE
    (matches the GOSSIP_COMPILE_CACHE convention); an ``echo``-
    requesting caller still gets stderr diagnostics via EchoLedger —
    disabling the recorder must never silence failure evidence."""
    path = os.environ.get(ENV_VAR)
    if path is None:
        path = default_path
    if not path:
        return EchoLedger() if echo else NullLedger()
    try:
        return Ledger(path, argv=argv, echo=echo)
    except OSError as e:
        # an unwritable ledger path must degrade, not abort the run it
        # was meant to record (bench's one-JSON-line contract survives
        # a read-only checkout)
        sys.stderr.write(f"telemetry: cannot open ledger {path!r} "
                         f"({e}); recording disabled\n")
        return EchoLedger() if echo else NullLedger()


def artifact_ledger(path: str, rewrite: bool = True,
                    fsync: bool = False, argv=None):
    """Provenance-stamped ARTIFACT ledger — the ONE stamping helper
    every committed-jsonl writer shares: tests/conftest.py's per-test
    duration ledger and gossip_tpu/analysis's staticcheck findings
    ledger both open through here, so a future writer cannot re-roll
    (and drift) the remove-then-stamp choreography.

    Differences from :func:`from_env`, which serves RUN flight
    recorders: ``rewrite=True`` truncates an existing file first — a
    committed artifact is THIS run's evidence, not an append log
    (pass False for the explicit-path append convention, e.g. a
    caller aggregating several test sessions) — and ``fsync`` defaults
    off (artifact writers run outside any crash window worth an fsync
    per line; the provenance first line still lands via Ledger's
    normal emit path).  An unwritable path degrades to the NullLedger
    with a stderr warning — a recorder must never fail the run it
    records (the from_env contract)."""
    if rewrite:
        try:
            os.remove(path)
        except FileNotFoundError:
            pass
        except OSError as e:
            sys.stderr.write(f"telemetry: cannot rewrite artifact "
                             f"ledger {path!r} ({e}); recording "
                             "disabled\n")
            return NullLedger()
    try:
        return Ledger(path, argv=argv, fsync=fsync)
    except OSError as e:
        sys.stderr.write(f"telemetry: cannot open artifact ledger "
                         f"{path!r} ({e}); recording disabled\n")
        return NullLedger()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]) of a value sequence, 0.0
    with no samples — the ONE latency-quantile definition the serving
    layer shares: the admission batcher's per-tick ``batch`` events
    (rpc/batcher wait walls) and the load harness's p50/p95/p99 gates
    (tools/load_harness) must mean the same thing by construction.
    Same nearest-rank convention as utils/trace.RoundTimer."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q={q} outside [0, 1]")
    vals = sorted(values)
    if not vals:
        return 0.0
    # epsilon guards float artifacts like 0.95*20 -> 19.000000000000004
    rank = math.ceil(q * len(vals) - 1e-9)
    return float(vals[min(len(vals) - 1, max(0, rank - 1))])


class MetricsWindow:
    """Thread-safe rolling metrics window for the live fleet plane
    (the ``Metrics`` RPC on gossip.Simulator — rpc/sidecar serves one
    per replica, rpc/router keeps its own for dispatch latencies).

    Holds (monotonic_ts, latency_ms) samples pruned to the trailing
    ``window_s`` seconds plus named monotonic counters (sheds,
    failovers, ...).  ``snapshot()`` is the one read path: rps over
    the window, sample count, p50/p95/p99 via :func:`percentile` (the
    shared nearest-rank definition), and the counter totals.  Pure
    host-side bookkeeping — a record() is an append + occasional
    popleft under a lock, never an fsync, never a device transfer —
    so the zero-steady-state-cost contract of this module holds.
    """

    def __init__(self, window_s: float = 60.0):
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._samples: collections.deque = collections.deque()
        self._counters: dict = {}

    def record(self, latency_ms: float, now: Optional[float] = None):
        now = time.monotonic() if now is None else now
        with self._lock:
            self._samples.append((now, float(latency_ms)))
            self._prune_locked(now)

    def bump(self, name: str, inc: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + inc

    def _prune_locked(self, now: float):
        cutoff = now - self.window_s
        while self._samples and self._samples[0][0] < cutoff:
            self._samples.popleft()

    def snapshot(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        with self._lock:
            self._prune_locked(now)
            lats = [ms for _, ms in self._samples]
            oldest = self._samples[0][0] if self._samples else now
            counters = dict(self._counters)
        # rps over the ACTUAL span covered, not the nominal window:
        # a 3-second-old process with 30 samples reports ~10 rps, not
        # the misleading 0.5 a fixed 60 s denominator would give
        if lats:
            span = min(max(now - oldest, 1e-9), self.window_s)
            rps = len(lats) / span
        else:
            rps = 0.0
        return {
            "window_s": self.window_s,
            "n": len(lats),
            "rps": round(rps, 3),
            "p50_ms": round(percentile(lats, 0.50), 3),
            "p95_ms": round(percentile(lats, 0.95), 3),
            "p99_ms": round(percentile(lats, 0.99), 3),
            **counters,
        }


# -- reading ----------------------------------------------------------

def parse_dryrun_table(text: str):
    """The last ``{"dryrun_family_ms": ...}`` JSON object line in
    ``text``, or None — the ONE parser of the dry-run stdout contract
    (teardown noise after the table never discards it).  Lives here,
    dependency-free, so tools/readme_table.py can render a MULTICHIP
    record's tail without importing anything jax-bearing;
    __graft_entry__.dryrun_multichip uses the same function on its
    subprocess stdout."""
    for line in reversed(text.splitlines()):
        if not line.strip():
            continue
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and "dryrun_family_ms" in parsed:
            return parsed
    return None


def load_ledger(path: str, run: Optional[str] = None,
                strict: bool = False,
                trace_id: Optional[str] = None):
    """Parse a ledger back into a list of event dicts.

    Crash contract: every fsynced line is durable, and a kill between
    write and fsync can tear at most one line per WRITER.  A
    single-writer ledger therefore tears only at the tail; a shared
    file (hw_refresh + its step subprocesses) can carry a torn line
    mid-file when a killed child's fragment is followed by the
    parent's appends (the writer heals the newline, so the fragment
    stays its own line).  The flight-recorder read-out must survive
    exactly that post-mortem, so unparseable lines are DROPPED by
    default; ``strict=True`` (single-writer files, tests) raises
    ValueError on any torn line that is not the final one.
    ``run`` filters to one run id; ``run="last"`` selects the newest
    provenance line's run.  ``trace_id`` filters to the events of one
    request trace (events carrying that ``trace_id`` field) — the
    single-trace read path tools/trace_report.py's exemplar drill-down
    and the failover-propagation tests share."""
    events = []
    with open(path) as f:
        lines = f.read().splitlines()
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            events.append(json.loads(line))
        except json.JSONDecodeError:
            if strict and i != len(lines) - 1:
                raise ValueError(
                    f"{path}:{i + 1}: corrupt ledger line (not a torn "
                    f"tail): {line[:120]!r}")
            continue                       # torn line: documented drop
    if run == "last":
        provs = [e for e in events if e.get("ev") == "provenance"]
        run = provs[-1]["run"] if provs else None
    if run is not None:
        events = [e for e in events if e.get("run") == run]
    if trace_id is not None:
        events = [e for e in events if e.get("trace_id") == trace_id]
    return events
