"""Compile-once runtime: persistent XLA cache + AOT executable store.

A serving system cannot pay full XLA compilation on every process
start, so this module makes the SECOND process (and every later one)
reuse executables instead of recompiling.  Two layers, one directory:

  1. **JAX's persistent compilation cache** (``enable_persistent``):
     every plain ``jit`` first call consults the on-disk cache before
     invoking XLA.

  2. **An own-layer AOT store** (``load_or_compile``): explicit
     ``lower().compile()`` callers — every driver's ``timing=`` path,
     through the ONE chokepoint in ``utils/trace.aot_timed`` —
     serialize the compiled executable
     (``jax.experimental.serialize_executable``) into
     ``<dir>/aot/<key>``.  A later process lowers, matches the key,
     and DESERIALIZES onto the same devices instead of compiling:
     warm cost is trace+lower+load, and trace+lower for a key this
     process already acquired (the memo below).  The key is the
     sha256 of the **lowered HLO text** plus jax version / backend /
     the process's device ids (each entry records the devices it
     executes on) — shapes, dtypes, mesh/axis specs, donation, and
     closed-over constants are all part of the HLO by construction,
     so a hit can never pair a stale executable with changed program
     semantics (warm-vs-cold bitwise equality is pinned in
     tests/test_compile_cache.py).

     In front of the store sits an in-process **memo** of acquired
     executables: a read-through of the store entry, not a cache of
     its own.  Each memo entry records the ``(st_ino, st_size,
     st_mtime_ns)`` of the ``.xbin`` it was loaded from or stored to;
     a lookup serves it only while one ``os.stat`` of the entry still
     matches, so a rewritten, corrupt or deleted entry falls through to
     the disk path exactly as without the memo.  It is filled on a
     store hit (the loaded executable) and on a miss whose entry was
     written (the compiled one), holds :data:`MEMO_ENTRIES` executables
     (least recently used goes first), and is off with the store.

Where the directory is (:func:`resolve_dir`): ``$JAX_COMPILATION_CACHE_DIR``
when set — JAX reads it itself and this module never overrides it —
else the caller's explicit dir (``--compile-cache`` /
``$GOSSIP_COMPILE_CACHE``), else the one fixed in-checkout path
:data:`DEFAULT_DIR`.  The path is part of the cache's key, so it
never moves: no home dir, temp name, pid or timestamp.
``GOSSIP_COMPILE_CACHE=""`` explicitly disables both layers (cold
compiles; the same convention as GOSSIP_TELEMETRY).  Every compile
through the chokepoint emits a telemetry ``compile`` span with
``cache: hit|miss|disabled`` and ``memo: true|false`` and bumps a
``compile_cache_<status>`` counter (and ``compile_cache_memo`` when
the memo served it), so a run ledger shows exactly which process paid
which compile (tools/telemetry_report.py renders the table).

Trust note: the AOT store deserializes pickled executables from the
cache directory — the same trust domain as the persistent XLA cache
directory and the checkpoint files (a hostile cache dir is a hostile
filesystem).  Corrupt, stale or unloadable entries are treated as
misses, never raised to the driver.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import sys
import threading
import time
from collections import OrderedDict
from typing import Optional, Tuple

from gossip_tpu.utils.trace import (ATTRIBUTION, BACKEND_COMPILE, CACHE_KEY,
                                    COMPILE, EXEC_LOAD, EXEC_STORE, LOWER,
                                    span)

ENV_VAR = "GOSSIP_COMPILE_CACHE"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the one default cache directory: fixed, inside the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_AOT_SUBDIR = "aot"
# bumped when the store's on-disk format changes; part of every key so
# old entries become misses instead of unpickle errors (v2: entries
# carry their execution device ids)
_STORE_VERSION = 2


def cache_dir_from_env(default_path: Optional[str] = None) -> Optional[str]:
    """The active AOT-store directory: $GOSSIP_COMPILE_CACHE, else
    ``default_path``, else None.  An empty-string env var explicitly
    DISABLES the cache (overriding any default) — the GOSSIP_TELEMETRY
    convention."""
    path = os.environ.get(ENV_VAR)
    if path is None:
        path = default_path
    return path or None


def resolve_dir(path: Optional[str]) -> Optional[str]:
    """The directory a cache enabled at ``path`` really uses:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (placed from outside —
    it wins over any in-code choice), else ``path``.  None/"" means
    disabled and stays so."""
    if not path:
        return None
    return os.path.abspath(os.environ.get(JAX_ENV_VAR) or path)


def enable_persistent(path: Optional[str],
                      min_compile_time_secs: float = 0.0,
                      min_entry_size_bytes: int = -1) -> dict:
    """Turn jax's persistent compilation cache on at
    :func:`resolve_dir` of ``path`` (None/"" turns it off — an
    explicit disable must mean honestly-cold compiles, whatever
    $JAX_COMPILATION_CACHE_DIR says).  Returns a status dict —
    ``{"dir", "persistent"}`` — that callers ledger verbatim, so every
    artifact says whether its compiles could have been warm.

    ``jax_compilation_cache_dir`` is set here only when
    $JAX_COMPILATION_CACHE_DIR is not: JAX reads that variable itself.
    ``min_compile_time_secs=0.0`` caches everything by default: the
    dry-run families compile in 0.5-5 s each and the disk round-trip
    is microseconds by comparison; the CLI keeps its own 2 s
    threshold."""
    import jax
    status = {"dir": None, "persistent": False}
    path = resolve_dir(path)
    if not path:
        jax.config.update("jax_enable_compilation_cache", False)
        return status
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        # read-only checkout / sandbox: run uncached, never abort the
        # run the cache was meant to speed up
        sys.stderr.write(f"compile_cache: cannot create {path!r} ({e}); "
                         "persistent cache disabled\n")
        jax.config.update("jax_enable_compilation_cache", False)
        return status
    if not os.environ.get(JAX_ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      min_entry_size_bytes)
    status["dir"] = path
    status["persistent"] = True
    return status


def enable_from_env(default_path: Optional[str] = None,
                    min_compile_time_secs: float = 0.0) -> dict:
    """``enable_persistent`` at the ambient dir (env over default) —
    the one call a process makes at startup to become warm-startable.
    The returned status should be ledgered (the dry-run body does)."""
    return enable_persistent(cache_dir_from_env(default_path),
                             min_compile_time_secs=min_compile_time_secs)


# -- the AOT executable store -----------------------------------------

def _fingerprint(hlo_text: str) -> str:
    """Store key: lowered-HLO hash + toolchain/device context.  The
    HLO carries shapes, dtypes, sharding/mesh specs (the partition
    count among them), donation and every closed-over constant;
    version/backend/device ids guard the executable format and the
    devices it can be assigned to (a serialized CPU executable must
    never load into a TPU process or onto another device set).  The
    entry itself records the exact devices it executes on
    (:func:`_execution_device_ids`)."""
    import jax
    h = hashlib.sha256()
    h.update(hlo_text.encode())
    h.update(f"|v{_STORE_VERSION}|{jax.__version__}"
             f"|{jax.default_backend()}"
             f"|{[d.id for d in jax.devices()]}".encode())
    return h.hexdigest()[:40]


def _execution_device_ids(compiled) -> Tuple[int, ...]:
    """Ids of the devices a compiled executable runs on (its argument
    and result shardings' device set, else the default device) — what
    ``deserialize_and_load`` must be handed back as
    ``execution_devices``, or a sharded executable loads onto every
    device of the process and refuses its arguments."""
    import jax
    ids = set()
    for s in jax.tree_util.tree_leaves((compiled.input_shardings,
                                        compiled.output_shardings)):
        ids.update(d.id for d in s.device_set)
    return tuple(sorted(ids)) or (jax.devices()[0].id,)


def _entry_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, _AOT_SUBDIR, key + ".xbin")


def _load(payload, in_tree, out_tree, device_ids):
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def _try_load(path: str):
    """Deserialized executable, or None — a miss, by contract, never an
    error.  A pickle-corrupt file (torn write from a pre-atomic-rename
    crash, disk damage) is deleted; an entry that unpickles but will
    not LOAD here is KEPT — the writer verified it once
    (:func:`_try_store`), so deleting would let one odd process evict
    everyone's warm start."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        # missing entry, or a TRANSIENT read failure (EMFILE, EIO,
        # permissions): a miss either way, and never grounds to evict
        # an entry other processes may be warm-starting from
        return None
    try:
        payload, in_tree, out_tree, device_ids = pickle.loads(data)
    except Exception as e:
        sys.stderr.write(f"compile_cache: dropping corrupt AOT entry "
                         f"{os.path.basename(path)} "
                         f"({type(e).__name__}: {e})\n")
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    try:
        return _load(payload, in_tree, out_tree, device_ids)
    except Exception as e:
        sys.stderr.write(f"compile_cache: AOT entry "
                         f"{os.path.basename(path)} did not load in "
                         f"this process ({type(e).__name__}); "
                         "recompiling\n")
        return None


def _try_store(path: str, compiled) -> Optional[tuple]:
    """Serialize ``compiled`` to ``path`` atomically (tmp + rename, so
    a killed writer can never leave a torn entry a sibling process
    would then deserialize).  The blob is VERIFIED by deserializing it
    before the rename: the store must never publish an entry its own
    writer cannot read back.  Returns the written entry's
    :func:`_identity` (the rename keeps it), or None when nothing was
    written: failures degrade to "not cached" (the persistent-cache
    layer still serves the program)."""
    from jax.experimental.serialize_executable import serialize
    try:
        device_ids = _execution_device_ids(compiled)
        payload, in_tree, out_tree = serialize(compiled)
        _load(payload, in_tree, out_tree, device_ids)   # verify
    except Exception as e:
        sys.stderr.write(f"compile_cache: executable does not "
                         f"round-trip ({type(e).__name__}); not "
                         "storing\n")
        return None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump((payload, in_tree, out_tree, device_ids), f)
        ident = _identity(tmp)
        os.replace(tmp, path)
        return ident
    except Exception as e:
        sys.stderr.write(f"compile_cache: could not store AOT entry "
                         f"({type(e).__name__}: {e})\n")
        return None


# -- the in-process memo of acquired executables ----------------------

# executables kept per process: a few loops a workload alternates
# between, and a bound for seed studies that compile a program per seed
MEMO_ENTRIES = 8
# entry path -> (identity of the .xbin when acquired, executable)
_MEMO: "OrderedDict[str, tuple]" = OrderedDict()
_MEMO_LOCK = threading.Lock()
_THREAD = threading.local()      # this thread's last chokepoint record


def _identity(path: str) -> Optional[tuple]:
    """``(st_ino, st_size, st_mtime_ns)`` of a store entry, or None
    when it cannot be stat'ed."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (st.st_ino, st.st_size, st.st_mtime_ns)


def _memo_get(path: str):
    """(executable or None, the entry's identity now).  The memo serves
    an executable only while the entry it came from is the same file;
    an entry rewritten, replaced or deleted since drops its memo."""
    ident = _identity(path)
    with _MEMO_LOCK:
        held = _MEMO.get(path)
        if held is None:
            return None, ident
        if ident is None or held[0] != ident:
            del _MEMO[path]
            return None, ident
        _MEMO.move_to_end(path)
        return held[1], ident


def _memo_put(path: str, ident: Optional[tuple], compiled) -> None:
    if ident is None:
        return
    with _MEMO_LOCK:
        _MEMO[path] = (ident, compiled)
        _MEMO.move_to_end(path)
        while len(_MEMO) > MEMO_ENTRIES:
            _MEMO.popitem(last=False)


def last_memo() -> bool:
    """Whether the calling thread's most recent chokepoint acquisition
    was served by the memo (``memo`` of its ``xla_compile`` record)."""
    record = getattr(_THREAD, "record", None)
    return bool(record and record["memo"])


# -- XLA cost & memory attribution (the observability PR) -------------

# every xla_compile event carries ALL of these keys, populated or
# explicit-null (record-never-gate): a consumer joins on schema, not on
# backend luck.  peak_bytes is argument+output+temp — the same closed
# form the PR 15 scale gate measured against budget.py's prediction.
ATTRIBUTION_FIELDS = ("flops", "bytes_accessed", "argument_bytes",
                      "output_bytes", "temp_bytes", "peak_bytes")

_LAST_COMPILE: Optional[dict] = None
# (record, executable) whose attribution fields last_compile() fills on
# its first read: an acquisition outside an active ledger pays no
# cost/memory analysis unless someone asks for it
_PENDING_ATTRIBUTION: Optional[tuple] = None
_ATTRIBUTION_LOCK = threading.Lock()


def last_compile() -> Optional[dict]:
    """The most recent chokepoint compile's attribution record (the
    ``xla_compile`` event fields), or None when this process has not
    compiled through the chokepoint yet — the sidecar's ``Metrics``
    reply reads this so a steady-state fleet that compiles shows WHAT
    compiled (absent-not-wrong: no compile means no field, never a
    fabricated one).  The attribution fields of an acquisition made
    outside an active ledger are computed here, on the first read."""
    global _PENDING_ATTRIBUTION
    with _ATTRIBUTION_LOCK:
        pending = _PENDING_ATTRIBUTION
        if pending is not None and pending[0] is _LAST_COMPILE:
            with span(ATTRIBUTION):
                _LAST_COMPILE.update(xla_attribution(pending[1]))
            _PENDING_ATTRIBUTION = None
        return _LAST_COMPILE


def xla_attribution(compiled) -> dict:
    """``cost_analysis()`` flops/bytes-accessed and
    ``memory_analysis()`` argument/output/temp/peak bytes of a compiled
    executable — every field explicit None when the backend/object
    cannot report it (older jax lines return no analyses; interpret
    stubs have neither method).  Never raises: attribution is evidence
    about the run, not a gate on it."""
    out = {k: None for k in ATTRIBUTION_FIELDS}
    try:
        cost = compiled.cost_analysis()
        # this jax line returns [per-computation dict]; others a dict
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost.get("flops") is not None:
            out["flops"] = float(cost["flops"])
        if cost.get("bytes accessed") is not None:
            out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        for field, attr in (("argument_bytes", "argument_size_in_bytes"),
                            ("output_bytes", "output_size_in_bytes"),
                            ("temp_bytes", "temp_size_in_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[field] = int(v)
        if None not in (out["argument_bytes"], out["output_bytes"],
                        out["temp_bytes"]):
            out["peak_bytes"] = (out["argument_bytes"]
                                 + out["output_bytes"]
                                 + out["temp_bytes"])
    except Exception:
        pass
    return out


def _default_label(jitted, name: str) -> str:
    """Fallback driver label when the caller supplies none: the wrapped
    function's defining module tail (``parallel.sharded`` → the engine
    family), else the function name — so even an unlabeled compile is
    attributable to SOME surface."""
    mod = getattr(jitted, "__module__", None)
    if mod and mod.startswith("gossip_tpu."):
        return mod[len("gossip_tpu."):]
    return mod or name


def load_or_compile(jitted, *args, cache_dir: Optional[str] = None,
                    label: Optional[str] = None) -> Tuple[object, str]:
    """(compiled, status): the AOT chokepoint.  Lower ``jitted`` for
    ``args``, then either deserialize a stored executable (``"hit"``)
    or compile and store it (``"miss"``); ``"disabled"`` when no cache
    dir is active.  The whole operation is one ``compile`` span
    (utils/trace.span: a ``gossip.compile`` profiler event, and a
    ledger span carrying ``cache``/``fn``/``key`` when the ambient
    ledger is active), with one span per step inside it — ``lower``,
    ``cache_key``, ``exec_load``, ``backend_compile``, ``exec_store``,
    ``attribution`` — so a run killed mid-compile shows WHERE, and a
    profiler trace names each step's share of the acquisition
    (utils/trace.aot_timed is the one caller the sharded drivers go
    through).

    The lowering runs unconditionally: it IS the key (module doc), so
    a warm process still pays trace+lower — that residual is exactly
    what the dry run's ``first_warm_ms`` budgets bound.  A key this
    process acquired before costs only that: the memo (module doc)
    hands back the same executable while one ``os.stat`` finds its
    entry unchanged, and ``exec_load`` then spans the stat and the
    lookup.  A memo hit is still ``"hit"`` (a hit on the store entry),
    with ``memo: true`` on the ``compile`` span's end event and the
    ``xla_compile`` record and a ``compile_cache_memo`` counter;
    every other acquisition reads ``memo: false``.

    Every acquisition here additionally emits one ``xla_compile``
    event (sync=False — this runs inside callers' timed windows) with
    the caller's driver ``label``, the store ``key``, the acquire
    wall, the cache verdict, and the executable's own cost/memory
    attribution (:func:`xla_attribution`, explicit nulls on backends
    without the analyses) — the self-attribution plane
    docs/OBSERVABILITY.md "XLA cost & memory attribution" documents.
    The attribution is computed only under an active ledger;
    otherwise :func:`last_compile`, which keeps the most recent record
    for the live Metrics surface, computes it on its first read."""
    global _LAST_COMPILE, _PENDING_ATTRIBUTION
    from gossip_tpu.utils import telemetry
    if cache_dir is None:
        cache_dir = cache_dir_from_env()
    led = telemetry.current()
    name = getattr(jitted, "__name__", None) or type(jitted).__name__
    key = None
    memo = False
    t0 = time.perf_counter()
    with span(COMPILE) as ext:
        # on the END event: the report's cache table reads rows from
        # span_end lines
        ext["fn"] = name
        with span(LOWER):
            lowered = jitted.lower(*args)
        if not cache_dir:
            with span(BACKEND_COMPILE):
                compiled = lowered.compile()
            status = "disabled"
        else:
            with span(CACHE_KEY):
                key = _fingerprint(lowered.as_text())
            path = _entry_path(cache_dir, key)
            with span(EXEC_LOAD):
                compiled, ident = _memo_get(path)
                memo = compiled is not None
                if not memo:
                    # the identity stat'ed BEFORE the read: an entry
                    # replaced in between fails the next lookup's stat
                    compiled = _try_load(path)
                    if compiled is not None:
                        _memo_put(path, ident, compiled)
            if compiled is not None:
                status = "hit"
            else:
                with span(BACKEND_COMPILE):
                    compiled = lowered.compile()
                with span(EXEC_STORE):
                    _memo_put(path, _try_store(path, compiled), compiled)
                status = "miss"
            ext["key"] = key
        ext["cache"] = status
        ext["memo"] = memo
    wall_ms = (time.perf_counter() - t0) * 1e3
    led.counter(f"compile_cache_{status}")
    if memo:
        led.counter("compile_cache_memo")
    record = {"label": label or _default_label(jitted, name),
              "fn": name, "key": key, "cache": status, "memo": memo,
              "compile_ms": round(wall_ms, 3)}
    _THREAD.record = record
    active = getattr(led, "active", False)
    with _ATTRIBUTION_LOCK:
        if active:
            with span(ATTRIBUTION):
                record.update(xla_attribution(compiled))
        _LAST_COMPILE = dict(record)
        _PENDING_ATTRIBUTION = None if active else (_LAST_COMPILE, compiled)
    led.event("xla_compile", sync=False, **record)
    return compiled, status


# -- plain-jit compile accounting -------------------------------------

class JitCompileMonitor:
    """Counts XLA persistent-cache hits/misses for PLAIN jit calls —
    the compiles that never pass through :func:`load_or_compile`
    because nothing lowers them explicitly (the dry-run families'
    first calls).  jax.monitoring emits one event per compile request;
    deltas around a timed window classify it as warm or cold, so the
    dry run can ledger a ``compile`` event per family with the same
    ``cache: hit|miss|disabled`` vocabulary as the chokepoint.

    Since the traced-operand PR the monitor also counts REAL backend
    compiles (``backend_compiles``: jax's per-compile
    ``/jax/core/compile/backend_compile_duration`` event, which fires
    whether or not a persistent cache is configured) — the delta probe
    behind the ``assert_compiles`` test fixture (tests/conftest.py):
    "K nemesis scenarios, ONE compile" is an assertion on this counter.

    Listener registration is process-global and permanent (jax offers
    no unregister on this line) — instantiate once per process, as the
    dry-run body does."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.backend_compiles = 0
        self.available = False
        self.durations_available = False
        try:
            from jax import monitoring
            monitoring.register_event_listener(self._on_event)
            self.available = True
        except Exception as e:
            sys.stderr.write("compile_cache: jax.monitoring unavailable "
                             f"({type(e).__name__}: {e}); plain-jit "
                             "cache accounting disabled\n")
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            self.durations_available = True
        except Exception:
            pass        # older jax: backend-compile counting degrades

    def _on_event(self, name, **kw):
        if name == self.HIT:
            self.hits += 1
        elif name == self.MISS:
            self.misses += 1

    def _on_duration(self, name, dur, **kw):
        if name == self.BACKEND:
            self.backend_compiles += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.hits, self.misses

    def classify(self, before: Tuple[int, int],
                 cache_enabled: bool) -> dict:
        """{cache, hits, misses} for the window since ``before``.
        ``miss`` wins when a window holds both (ONE cold sub-compile
        means the process paid a real compile)."""
        dh, dm = self.hits - before[0], self.misses - before[1]
        if not cache_enabled or not self.available:
            cache = "disabled"
        elif dm > 0:
            cache = "miss"
        elif dh > 0:
            cache = "hit"
        else:
            # no persistent-cache traffic at all: an in-memory
            # executable reuse (steady calls) — not a compile event
            cache = "none"
        return {"cache": cache, "hits": dh, "misses": dm}


def entry_count(cache_dir: Optional[str]) -> Optional[int]:
    """Number of files in the cache dir tree (both layers), or None
    when disabled/absent — a cheap cross-check the dry run ledgers
    alongside the monitor's counters."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return None
    total = 0
    for _, _, files in os.walk(cache_dir):
        total += len(files)
    return total
