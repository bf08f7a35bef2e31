"""Profiling hooks: the program's spans and device scopes, and
jax.profiler captures.

The reference has no tracing at all (SURVEY.md §5).  These helpers wrap
``jax.profiler`` so any driver can capture an XLA trace viewable in
TensorBoard / Perfetto (`trace(...)`) and mark host-side phases with
:func:`span`, the program's one host span: a ``gossip.<name>``
``TraceAnnotation`` on the profiler's clock (the clock the device
planes share) and, when the ambient run ledger is active, the ledger
span of the same name.  Device work is named with ``jax.named_scope``
under the scope names below; docs/OBSERVABILITY.md "Program spans and
device scopes" has the vocabulary.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Iterator, Optional

# jax is imported lazily inside the helpers: cli.cmd_run imports this
# module unconditionally, and the go-native/native-router paths must
# stay runnable without ever touching jax (deferred-import pattern of
# backend.py/cli.py).

PROFILE_ENV = "GOSSIP_PROFILE"

# -- span and scope names: the one vocabulary -------------------------
# Host spans (span(NAME) -> profiler event "gossip.NAME", ledger span NAME).
SPAN_PREFIX = "gossip."
BUILD = "build"                    # topology, step/tables, init state
COMPILE = "compile"                # the whole AOT acquisition
LOWER = "lower"                    # jitted.lower(*args)
CACHE_KEY = "cache_key"            # HLO text + store fingerprint
EXEC_LOAD = "exec_load"            # memo lookup, else read + deserialize
BACKEND_COMPILE = "backend_compile"  # lowered.compile()
EXEC_STORE = "exec_store"          # serialize, verify, write
ATTRIBUTION = "attribution"        # cost_analysis + memory_analysis
EXECUTE = "execute"                # compiled(*args) + block_until_ready
READBACK = "readback"              # coverage and host conversions
# Device scopes (jax.named_scope), around XLA ops and pallas_call only.
SAMPLE = "gossip.sample"           # partner draws, drop coins, fault masks
EXCHANGE = "gossip.exchange"       # gather/all_gather, merge, fused call
COVERAGE = "gossip.coverage"       # loop-condition coverage, msgs sums
SIR = "gossip.sir"                 # rumor counters, removal, hot/seen update


@contextlib.contextmanager
def span(name: str) -> Iterator[dict]:
    """The program's one host span.  Opens the profiler annotation
    ``gossip.<name>`` whenever jax is already imported (it never
    imports jax itself), and, when the ambient ledger is active, the
    ledger span ``name``; yields that span's end-event dict (a
    throwaway dict otherwise).  Name only: a ``TraceAnnotation`` with
    keyword arguments appends ``#k=v#`` to its name, and trace
    reductions match names."""
    from gossip_tpu.utils import telemetry
    jax = sys.modules.get("jax")
    led = telemetry.current()
    with (jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
          if jax is not None else contextlib.nullcontext()):
        if getattr(led, "active", False):
            with led.span(name) as ext:
                yield ext
        else:
            yield {}


def profile_dir() -> Optional[str]:
    """$GOSSIP_PROFILE — the ambient profiler capture directory, or
    None (unset/empty = profiling off, the GOSSIP_TELEMETRY
    convention)."""
    return os.environ.get(PROFILE_ENV) or None


@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed block into ``logdir``
    (TensorBoard's profile plugin / Perfetto read it).  ``None``/empty
    is a no-op (matching callers' ``if args.profile`` truthiness gates),
    so callers can wrap unconditionally: ``with trace(args.profile):``."""
    if not logdir:
        yield
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def profile(tag: Optional[str] = None) -> Iterator[None]:
    """The $GOSSIP_PROFILE hook: capture a jax.profiler trace of the
    enclosed block into the ambient directory, with an optional
    :func:`span` around the whole block.  A no-op (zero jax import)
    when GOSSIP_PROFILE is unset — the profiled surfaces (dry-run
    families, bench legs) wrap unconditionally.

    One capture per ``profile()`` block: jax traces do not nest, so the
    callers wrap the OUTER program (the dry-run body, one bench leg)
    and mark inner phases with :func:`span`."""
    logdir = profile_dir()
    if not logdir:
        yield
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        with span(tag) if tag else contextlib.nullcontext():
            yield
    finally:
        jax.profiler.stop_trace()


def aot_timed(jitted, *args, label=None):
    """(out, compile_s, steady_s, cache): obtain the executable for
    these arguments ahead of time, then time the execution alone.
    ``label`` is the caller's driver label for the chokepoint's
    ``xla_compile`` attribution event (utils/compile_cache) — the
    per-engine name a cost report groups by.

    The hardware-table contract (round-2 verdict): reported walls must
    not mix one-off compile cost with steady-state throughput — the
    64-node sweep row's "11.6 s" was ~all compile.  ``compile_s``
    covers trace+lower+ACQUIRE; since the compile-once PR, acquisition
    goes through the ONE chokepoint ``utils/compile_cache
    .load_or_compile`` — a real XLA compile on a cache miss (or with
    the cache disabled: bitwise the old behavior), a deserialization
    of the stored executable on a hit (or, for a key this process
    acquired before, the same executable again) — and ``cache`` says which
    (``hit|miss|disabled``), so a warm compile_s can never masquerade
    as a cold one in an artifact.  ``steady_s`` is the device
    execution of one call, identical either way (warm-vs-cold output
    equality is pinned in tests/test_compile_cache.py)."""
    import jax

    from gossip_tpu.utils import compile_cache
    t0 = time.perf_counter()
    compiled, cache = compile_cache.load_or_compile(jitted, *args,
                                                    label=label)
    compile_s = time.perf_counter() - t0
    with span(EXECUTE):
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        steady_s = time.perf_counter() - t0
    return out, compile_s, steady_s, cache


def steady_timed(jitted, *args):
    """(out, steady_s): time ONE plain call of an already-jitted
    callable — an executable-cache hit when the caller warmed it, so
    the number is steady-state execution, not compile.  The cached-loop
    twin of :func:`aot_timed` (whose lower+compile deliberately
    bypasses the executable cache to measure a real compile)."""
    import jax
    with span(EXECUTE):
        t0 = time.perf_counter()
        out = jitted(*args)
        jax.block_until_ready(out)
        steady_s = time.perf_counter() - t0
    return out, steady_s


def maybe_aot_timed(jitted, timing, *args, label=None):
    """:func:`aot_timed` when the caller passed a ``timing`` dict (fills
    ``compile_s``/``steady_s``), a plain call otherwise — the one place
    the drivers' optional-timing branch and its key names live.
    ``label`` names the calling driver for compile attribution
    (:func:`aot_timed`); it also rides the ``driver_timing`` event so
    walls and costs join on the same engine name.

    ``timing={"aot": False}`` opts into :func:`steady_timed` instead:
    ``steady_s`` is the cached-executable execution and ``compile_s``
    reports 0.0 (nothing compiled) — for callers probing a memoized
    driver's steady state, where an AOT lower+compile would measure a
    recompile the real re-entry never pays.

    On the AOT path ``timing["compile_cache"]`` records the executable
    store's verdict (``hit|miss|disabled`` — utils/compile_cache) and
    ``timing["exec_memo"]`` whether the in-process memo served it (the
    chokepoint record's ``memo``): this is the chokepoint every sharded
    driver's compile goes through, so enabling GOSSIP_COMPILE_CACHE
    warms them all with no per-driver plumbing."""
    fn_name = getattr(jitted, "__name__", None) or type(jitted).__name__
    if timing is None:
        out = jitted(*args)
        _emit_round_metrics(out, fn_name)
        return out
    if timing.get("aot", True) is False:
        out, timing["steady_s"] = steady_timed(jitted, *args)
        timing.setdefault("compile_s", 0.0)
    else:
        from gossip_tpu.utils import compile_cache
        (out, timing["compile_s"], timing["steady_s"],
         timing["compile_cache"]) = aot_timed(jitted, *args, label=label)
        timing["exec_memo"] = compile_cache.last_memo()
    # every driver's wall decomposition reaches the ambient run ledger
    # (utils/telemetry) with no per-driver plumbing; a NullLedger makes
    # this a no-op.  The emit happens AFTER this call's own timed
    # region, but the CALLER may be timing us (the dry run's family
    # windows) — so sync=False: flush-only, no fsync latency inside
    # anyone's measured wall
    from gossip_tpu.utils import telemetry
    telemetry.current().event(
        "driver_timing", sync=False,
        fn=fn_name,
        label=label,
        cache=timing.get("compile_cache"),
        # walls only: the bool "aot" control flag is an int subclass
        # and must not masquerade as a timing field
        **{k: v for k, v in timing.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)})
    _emit_round_metrics(out, fn_name)
    return out


def _emit_round_metrics(out, fn_name: str):
    """The round-metrics flush half of the chokepoint: any
    :class:`~gossip_tpu.ops.round_metrics.RoundMetrics` stacks an
    instrumented driver carried through its loop are transferred to the
    host ONCE here — after the timed region, outside the compiled
    program — and ledgered as ``round_metrics`` events.  Gated on an
    ACTIVE ambient ledger so un-ledgered callers pay neither the
    device-to-host copy nor the ops import (and the go-native paths
    never touch jax)."""
    from gossip_tpu.utils import telemetry
    led = telemetry.current()
    if not getattr(led, "active", False):
        return
    from gossip_tpu.ops import round_metrics
    round_metrics.emit(out, led, fn=fn_name)

