"""The program's own spans and device scopes in a ``jax.profiler`` trace.

``trace.reduce`` names device time by HLO instruction and idle gaps by
host event.  This module reads what the program itself marks on the
same clock (``gossip_tpu/utils/trace``: ``gossip.*`` host spans and
``jax.named_scope`` device scopes) into three more keys:

* ``span_s``: for each ``gossip.*`` host span name, the seconds of the
  union of its events inside the window (a span nested in one of the
  same name counts once);
* ``scope_s``: device-op seconds, averaged over the devices like
  ``op_s`` and without its control-flow containers, grouped by the
  innermost ``gossip.*`` component of each op's framework op name;
  ops with none are ``unscoped``;
* ``unnamed_idle_s``: per device, the idle time (the holes in the busy
  union) that no ``gossip.*`` span covers, averaged over the devices.

The TPU trace gives a device op only its HLO instruction text and the
``XLA Modules`` event it runs in.  The framework op name comes from the
HLO protos the profiler stores in the ``/host:metadata`` plane: the
instruction's ``metadata.op_name``, found by module and instruction
name.  A fusion is one instruction there, whose metadata XLA takes from
the fusion's root op, so a fusion takes its root op's scope.  On the
XLA CPU client the ops carry their module and instruction in their own
stats.
"""

import glob
import os

import harness

tr = harness.load_module("trace.py")

PREFIX = "gossip."
UNSCOPED = "unscoped"
TRACE_DIR = os.path.join(harness.CACHE, "trace")
_METADATA_PLANE = "/host:metadata"
_HLO_PROTO_STAT = "Hlo Proto"
_MODULE_LINE = "XLA Modules"


# -- protobuf wire format, for the HLO protos ProfileData does not expose

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace file")
        yield num, value


def _field(buf, num):
    return next((v for k, v in _fields(buf) if k == num), None)


def hlo_protos(path):
    """{module event name (``jit_loop(5231986109366318902)``): HloProto
    bytes} from the trace file's metadata plane (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5; map entries
    key = 1, value = 2; XEventMetadata.name = 2, stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, bytes_value = 6)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if name != _METADATA_PLANE:
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                md = _field(entry, 2)
                stat_names[_field(entry, 1)] = bytes(_field(md, 2)).decode()
        out = {}
        for k, entry in fields:
            if k != 4:
                continue
            md = _field(entry, 2)
            for j, v in _fields(md):
                if j == 5 and stat_names.get(_field(v, 1)) == _HLO_PROTO_STAT:
                    out[bytes(_field(md, 2)).decode()] = _field(v, 6)
        return out
    return {}


def innermost_scope(op_name):
    """The last ``gossip.*`` component of a framework op name, or None."""
    found = None
    for part in op_name.split("/"):
        if part.startswith(PREFIX):
            found = part
    return found


def instruction_scopes(proto):
    """{instruction name: innermost scope or None} of one HloProto
    (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    module = _field(proto, 1)
    for k, comp in _fields(module):
        if k != 3:
            continue
        for j, ins in _fields(comp):
            if j != 2:
                continue
            name, op = None, ""
            for f, v in _fields(ins):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 7:
                    op = bytes(_field(v, 2) or b"").decode()
            out[name] = innermost_scope(op)
    return out


class _Scopes:
    """Instruction scopes by module, decoded on first use."""

    def __init__(self, protos):
        self.protos, self.done = protos, {}

    def of(self, module, op):
        if module not in self.done:
            proto = self.protos.get(module)
            self.done[module] = {} if proto is None else \
                instruction_scopes(proto)
        return self.done[module].get(op)


def _module_at(modules, t):
    """The name of the module event in ``modules`` (sorted by start)
    that holds time ``t``, or None."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][2] >= t:
        return modules[lo - 1][0]
    return None


def load(trace_dir=TRACE_DIR):
    """``trace.load``'s planes of the newest trace under ``trace_dir``,
    with every line of XLA operations also carrying ``scopes``: the
    innermost ``gossip.*`` scope of each event's instruction, or None."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    scopes = _Scopes(hlo_protos(paths[-1]))
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        raw = list(plane.lines)
        modules = sorted(((e.name, float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
                          for line in raw if line.name == _MODULE_LINE
                          for e in line.events), key=lambda m: m[1])
        lines = []
        for line in raw:
            evs, marks = [], []
            cpu_client = line.name.startswith("tf_XLA")
            for e in line.events:
                s = float(e.start_ns)
                evs.append((e.name, s, s + float(e.duration_ns)))
                if line.name == tr._OP_LINE:
                    marks.append(scopes.of(_module_at(modules, s),
                                           tr.op_name(e.name)))
                elif cpu_client:
                    st = dict(e.stats)
                    marks.append(scopes.of(
                        f"{st.get('hlo_module')}({st.get('program_id')})",
                        st.get("hlo_op")))
            out = {"name": line.name, "events": evs}
            if line.name == tr._OP_LINE or cpu_client:
                out["scopes"] = marks
            lines.append(out)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def tpu_scoped_ops(planes):
    """{device index: [(op, scope, start, end)]} of the device planes'
    XLA operations."""
    out = {}
    for plane in planes:
        m = tr._DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == tr._OP_LINE:
                out.setdefault(int(m.group(1)), []).extend(
                    (tr.op_name(n), scope, s, e) for (n, s, e), scope in
                    zip(line["events"], line["scopes"]))
    return out


def overlap(a, b):
    """Total length shared by two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(planes, devices=None, ops_of=tr.tpu_ops,
           scoped_of=tpu_scoped_ops):
    """``span_s``, ``scope_s`` and ``unnamed_idle_s`` of the window
    (module doc).  ``devices`` and ``ops_of`` as in ``trace.reduce``;
    ``scoped_of`` gives each device's operations with their scopes."""
    events = tr.host_events(planes)
    lo, hi = tr.window_of(events)
    spans = [(n, s, e) for n, s, e in events if n.startswith(PREFIX)]
    span_s = {}
    for name in sorted({n for n, _, _ in spans}):
        merged = tr.clip(tr.union([x for x in spans if x[0] == name]),
                         lo, hi)
        span_s[name] = sum(e - s for s, e in merged) * 1e-9
    covered = tr.clip(tr.union(spans), lo, hi)
    ops, scoped = ops_of(planes), scoped_of(planes)
    if devices is None:
        devices = sorted(ops)
    unnamed, scope_ns = [], {}
    for d in devices:
        busy = tr.union([(n, max(s, lo), min(e, hi))
                         for n, s, e in ops.get(d, ()) if e > lo and s < hi])
        idle = tr.holes(busy, lo, hi)
        unnamed.append(sum(e - s for s, e in idle) - overlap(idle, covered))
        for op, scope, s, e in scoped.get(d, ()):
            if e > lo and s < hi and not op.startswith(tr.CONTAINERS):
                key = scope or UNSCOPED
                scope_ns[key] = (scope_ns.get(key, 0.0)
                                 + min(e, hi) - max(s, lo))
    nd = max(len(devices), 1)
    return {"span_s": span_s,
            "scope_s": {k: v / nd * 1e-9 for k, v in scope_ns.items()},
            "unnamed_idle_s": sum(unnamed) / nd * 1e-9}


def of(run):
    """The run's three keys: ``run.trace``'s own where it carries them,
    else reduced once from the traced run's trace directory.  None
    without a trace, or when the trace cannot be read (said on stderr):
    a reader of these keys finds nothing and never fails the run."""
    if run.trace is None:
        return None
    if "span_s" in run.trace:
        return run.trace
    if not hasattr(run, "program_trace"):
        try:
            run.program_trace = reduce(
                load(TRACE_DIR), devices=list(range(run.cell.chips)))
        except Exception as e:       # the readers' boundary: report, go on
            import sys
            print(f"program_trace: {type(e).__name__}: {e}",
                  file=sys.stderr)
            run.program_trace = None
    return run.program_trace
