"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

Input is the ``.xplane.pb`` the profiler writes, read with
``jax.profiler.ProfileData``.  Device operations are the events of each
device plane's ``XLA Ops`` line; host spans are the harness's own
``TraceAnnotation`` events (names starting ``perfbench.``) on the host
plane.  All times are nanoseconds on the profiler's shared clock.

* busy: the union of a device's operation intervals inside the window
  span, so overlapping operations count once;
* idle gaps: the holes in that union, each named by the innermost host
  event that covers most of it (a harness span, or an event JAX itself
  recorded inside it, such as a compile);
* operation totals: summed device durations by operation name.
"""

import glob
import os
import re

WINDOW_SPAN = "perfbench.window"
# control-flow ops enclose the ops they run: in the union, not the totals
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "perfbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINE = "XLA Ops"


def load(trace_dir):
    """Planes of the newest trace under ``trace_dir`` as plain data:
    ``[{"name", "lines": [{"name", "events": [(name, start, end)]}]}]``."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns))
                   for e in line.events]
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(text):
    """The HLO instruction name of a device event (``fusion.38``), which
    the profiler gives with the whole instruction text."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def tpu_ops(planes, line_name=_OP_LINE):
    """{device index: [(op, start, end)]} from the device planes."""
    out = {}
    for plane in planes:
        m = _DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == line_name:
                out.setdefault(int(m.group(1)), []).extend(
                    (op_name(n), s, e) for n, s, e in line["events"])
    return out


def tpu_async_ops(planes):
    return tpu_ops(planes, "Async XLA Ops")


def host_events(planes):
    """Every event of the host planes, harness spans and JAX's own."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                out.extend(e for e in line["events"] if e[2] > e[1])
    return out


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of ``intervals``."""
    merged = []
    for s, e in sorted((s, e) for _, s, e in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def holes(merged, lo, hi):
    """The gaps of a merged union inside ``[lo, hi]``."""
    out, cur = [], lo
    for s, e in clip(merged, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def name_gap(gap, events):
    """The innermost host event covering most of ``gap``: among events
    that cover at least half of it, the shortest.  Harness spans are
    named without their prefix; JAX's own events keep their names."""
    s, e = gap
    need = 0.5 * (e - s)
    best = None
    for name, es, ee in events:
        cover = min(e, ee) - max(s, es)
        if cover >= need and (best is None or ee - es < best[2] - best[1]):
            best = (name, es, ee)
    if best is None:
        return "no host event"
    name = best[0]
    return name[len(SPAN_PREFIX):] if name.startswith(SPAN_PREFIX) else name


def window_of(events):
    spans = [(s, e) for name, s, e in events if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return spans[-1]


def reduce(planes, devices=None, ops_of=tpu_ops, async_of=tpu_async_ops,
           top=10):
    """The window's device numbers, averaged over the devices used.

    ``devices``: the device indices to read (default: every device plane
    with operations).  ``ops_of`` picks the device operations from the
    planes (the default reads TPU planes; tests pass a CPU stand-in)."""
    events = host_events(planes)
    lo, hi = window_of(events)
    ops, aops = ops_of(planes), async_of(planes)
    if devices is None:
        devices = sorted(ops)
    busy, totals, async_totals, gaps = [], {}, {}, []
    for d in devices:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in ops.get(d, ())
               if e > lo and s < hi]
        merged = union(evs)
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in evs:
            if not n.startswith(CONTAINERS):
                totals[n] = totals.get(n, 0.0) + (e - s)
        for n, s, e in aops.get(d, ()):
            if e > lo and s < hi:
                async_totals[n] = (async_totals.get(n, 0.0)
                                   + min(e, hi) - max(s, lo))
        if d == devices[0]:
            gaps = holes(merged, lo, hi)
    nd = max(len(devices), 1)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / nd * 1e-9,
        "op_s": {n: t / nd * 1e-9 for n, t in totals.items()},
        "async_op_s": {n: t / nd * 1e-9 for n, t in async_totals.items()},
        "device_ops": [[n, t / nd * 1e-9] for n, t in
                       sorted(totals.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[name_gap(g, events), (g[1] - g[0]) * 1e-9]
                      for g in longest],
        "devices": len(devices),
    }
