"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the deployment (protocol, size, target);
* ``traffic/<traffic>.json`` — the mix: which driver runs the window
  (``drivers/<driver>.py``), the fault program, the engine expected per
  chip count, the control, and how many answers each run checks;
* ``metrics/<metric>.py`` — a reader ``read(run) -> number or None``;
* ``reference/<engine>.py`` — the plain reference for what an engine
  reports, picked by the engine and layout the report names.

The program is driven only through ``gossip_tpu.backend.run_simulation``,
the entry the CLI and the sidecar call.
"""

import hashlib
import importlib.util
import json
import os
import random
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".perfbench_cache")
WARM_SEED = 1
SEED_SPAN = (1 << 31) - 3        # window seeds lie in [2, 2**31 - 1)


class Refused(SystemExit):
    """The run cannot be made here (no chip, too few chips): no result."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "perfbench_" + "_".join(parts).replace(".py", "").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def slug(text):
    out = "".join(c if c.isalnum() else "_" for c in text.lower())
    while "__" in out:
        out = out.replace("__", "_")
    return out.strip("_")


def sim_seed(seed, i):
    """The window's i-th simulation seed: fresh for every (seed, i) and
    never the set-up seed."""
    h = hashlib.sha256(f"perfbench:{int(seed)}:{int(i)}".encode()).digest()
    return 2 + int.from_bytes(h[:8], "big") % SEED_SPAN


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name, bench=None, n=None):
        bench = bench or load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"perfbench: no workload {name!r} in "
                             f"BENCHMARK.json ({sorted(cells)})")
        self.bench = bench
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        self.cfg = load_json(HERE, "configs", self.spec["config"] + ".json")
        if n is not None:            # tests: the same cell at a small size
            self.cfg["topology"]["n"] = int(n)
        self.traffic = load_json(HERE, "traffic",
                                 self.spec["traffic"] + ".json")
        self.n = int(self.cfg["topology"]["n"])
        self.fault = resolve_fault(self.traffic.get("fault", {}), self.n)
        expect = self.traffic["expect"].get(str(self.chips))
        if expect is None:
            raise SystemExit(f"perfbench: traffic {self.spec['traffic']!r} "
                             f"states no engine for {self.chips} chips")
        self.expect = expect

    def metrics(self, trace):
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def resolve_fault(spec, n):
    """The traffic's fault program with sizes resolved against ``n``:
    a partition cut given as ``cut_of_n: [a, b]`` lies at ``n * a // b``."""
    out = {k: v for k, v in spec.items() if k != "partitions"}
    out["events"] = [list(e) for e in spec.get("events", ())]
    out["partitions"] = [[w["start"], w["end"],
                          n * w["cut_of_n"][0] // w["cut_of_n"][1]]
                         for w in spec.get("partitions", ())]
    return out


def program_args(cell, seed):
    """The ``run_simulation`` arguments of one simulation."""
    from gossip_tpu.config import (ChurnConfig, FaultConfig, MeshConfig,
                                   ProtocolConfig, RunConfig, TopologyConfig)
    cfg, f = cell.cfg, cell.fault
    proto = ProtocolConfig(**cfg["protocol"])
    tc = TopologyConfig(**cfg["topology"])
    run = RunConfig(seed=int(seed), engine="auto", **cfg["run"])
    churn = None
    if f["events"] or f["partitions"] or f.get("ramp"):
        churn = ChurnConfig(events=tuple(map(tuple, f["events"])),
                            partitions=tuple(map(tuple, f["partitions"])),
                            ramp=tuple(f["ramp"]) if f.get("ramp") else None)
    fault = None
    if churn or f.get("drop_prob") or f.get("node_death_rate"):
        fault = FaultConfig(drop_prob=f.get("drop_prob", 0.0),
                            node_death_rate=f.get("node_death_rate", 0.0),
                            churn=churn)
    mesh = MeshConfig(n_devices=cell.chips) if cell.chips > 1 else None
    return proto, tc, run, fault, mesh


def require_chips(chips, allow_cpu=False):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"perfbench: needs a TPU, JAX found "
                      f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"perfbench: the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    return devs


def import_program():
    """gossip_tpu from THIS checkout, never one found elsewhere."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import gossip_tpu
    pkg = os.path.dirname(os.path.abspath(gossip_tpu.__file__))
    if os.path.dirname(pkg) != ROOT:
        raise Refused(f"perfbench: gossip_tpu comes from {pkg}, not from "
                      f"this checkout ({ROOT})")
    from gossip_tpu import backend
    return backend


def configure_cache():
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or the
    checkout's fixed ``.perfbench_cache/jax``; the program's own AOT
    store in ``.perfbench_cache/run``, emptied every run, so no
    executable a simulation compiled in an earlier run's window can
    serve a later one (``freeze_cache`` stops the persistent writes)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(CACHE, "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run_dir = os.path.join(CACHE, "run")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    os.environ["GOSSIP_COMPILE_CACHE"] = run_dir


def freeze_cache(frozen):
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      1e9 if frozen else 0.0)


class CompileCounter:
    """Backend compiles, counted from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


class Sim:
    """One simulation of the window, as the harness saw it."""

    def __init__(self, index, seed, t0, t1, report, error):
        self.index, self.seed, self.t0, self.t1 = index, seed, t0, t1
        self.report, self.error = report, error

    @property
    def wall_s(self):
        return self.t1 - self.t0

    def reached(self, target):
        import numpy as np
        rep = self.report
        return (rep is not None and rep["rounds"] >= 0
                and np.float32(rep["coverage"]) >= np.float32(target))


class Run:
    """What the metric readers read: the window's simulations, set-up,
    compile count and, in a traced run, the reduced trace."""

    def __init__(self, cell, sims, setup_s, compiles, trace, peaks):
        self.cell, self.sims, self.setup_s = cell, sims, setup_s
        self.compiles, self.trace, self.peaks = compiles, trace, peaks
        target = cell.cfg["run"]["target_coverage"]
        self.ok = [s for s in sims if s.reached(target)]

    def rounds_executed(self, sim):
        rounds = sim.report["rounds"]
        return rounds if rounds >= 0 else self.cell.cfg["run"]["max_rounds"]


def simulate(backend, cell, seed):
    """One ``run_simulation`` call: (report dict or None, error or None)."""
    try:
        rep = backend.run_simulation("jax-tpu", *program_args(cell, seed))
        return rep.to_dict(), None
    except Exception as e:               # a failed simulation, counted
        return None, f"{type(e).__name__}: {e}"


def engine_mismatch(cell, report):
    meta = report.get("meta", {})
    want = (cell.expect["engine"], cell.chips)
    got = (meta.get("engine"), meta.get("devices"))
    return None if got == want else f"engine/devices {got}, want {want}"


def choose_sample(cell, sims, seed):
    """The simulations whose answers are checked: the longest one, then
    others drawn from the seed, ``check_sample`` in all."""
    done = [s for s in sims if s.report is not None]
    k = int(cell.traffic.get("check_sample", 2))
    if not done:
        return []
    longest = max(done, key=lambda s: s.report["rounds"])
    rest = [s for s in done if s is not longest]
    random.Random(int(seed)).shuffle(rest)
    return [longest] + rest[:k - 1]


def reference_for(report):
    meta = report["meta"]
    key = meta["engine"] + (" " + meta["layout"] if meta.get("layout")
                            else "")
    return load_module("reference", slug(key) + ".py")


def compare(sims, answer_of):
    """The numbers compared, each the worst over ``sims`` against
    ``answer_of``: rounds; msgs in float32 ulps of the reference's count
    (the engines sum it in float32, past 2**24); coverage in nodes of
    the denominator."""
    import numpy as np
    gaps = {"rounds_gap": 0.0, "msgs_gap_ulp": 0.0, "coverage_gap": 0.0}
    for s in sims:
        a = answer_of(s.seed)
        rep = s.report
        ulp = float(np.spacing(np.float32(a["msgs"])))
        gaps["rounds_gap"] = max(gaps["rounds_gap"],
                                 float(abs(rep["rounds"] - a["rounds"])))
        gaps["msgs_gap_ulp"] = max(gaps["msgs_gap_ulp"],
                                   abs(rep["msgs"] - a["msgs"]) / ulp)
        gaps["coverage_gap"] = max(
            gaps["coverage_gap"],
            abs(rep["coverage"] - a["coverage"]) * a["denom"])
    return gaps


def check(cell, sims, seed, wanted_sample=None):
    """(correct, checks): every sampled answer against the reference,
    plus the simulations that never answered or ran another engine."""
    limits = cell.traffic["limits"]
    lost = [s for s in sims if s.report is None]
    wrong_engine = [s for s in sims
                    if s.report is not None and engine_mismatch(cell, s.report)]
    sample = wanted_sample if wanted_sample is not None else choose_sample(
        cell, sims, seed)
    gaps = {}
    if sample:
        ref = reference_for(sample[0].report).make(cell.cfg, cell.fault)
        gaps = compare(sample, ref)
    checks = {"lost": {"value": len(lost), "limit": 0},
              "wrong_engine": {"value": len(wrong_engine), "limit": 0},
              "unchecked": {"value": 0 if sample else 1, "limit": 0}}
    for name, value in gaps.items():
        checks[name] = {"value": value, "limit": limits[name]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return correct, checks


def memory_peak(devs):
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def read_metrics(cell, run, trace):
    out = {}
    for m in cell.metrics(trace):
        value = load_module("metrics", m["name"] + ".py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed, seconds, trace, t_start, allow_cpu=False):
    """One run of ``cell``: set-up, the measured window, the check.
    Returns the result object the command prints."""
    import jax
    backend = import_program()
    devs = require_chips(cell.chips, allow_cpu)
    configure_cache()
    peaks = load_json(HERE, "peaks.json")
    kind = devs[0].device_kind
    if devs[0].platform == "tpu" and kind not in peaks:
        raise SystemExit(f"perfbench: no peaks for device kind {kind!r}")
    counter = CompileCounter()

    # set-up: one simulation on the set-up seed warms this cell's shapes
    warm, err = simulate(backend, cell, WARM_SEED)
    if warm is None:
        raise RuntimeError(f"perfbench: set-up simulation failed: {err}")
    bad = engine_mismatch(cell, warm)
    if bad:
        raise RuntimeError(f"perfbench: set-up ran {bad}")
    freeze_cache(True)
    driver = load_module("drivers", cell.traffic["driver"] + ".py")
    trace_dir = os.path.join(CACHE, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = counter.count
    t_window = time.perf_counter()
    setup_s = t_window - t_start

    def one(i):
        s = sim_seed(seed, i)
        with jax.profiler.TraceAnnotation("perfbench.sim"):
            t0 = time.perf_counter()
            report, error = simulate(backend, cell, s)
            t1 = time.perf_counter()
        return Sim(i, s, t0 - t_window, t1 - t_window, report, error)

    with jax.profiler.TraceAnnotation("perfbench.window"):
        sims = driver.run_window(one, seconds)
    compiles = counter.count - compiles0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        tr = load_module("trace.py")
        reduced = tr.reduce(tr.load(trace_dir),
                            devices=list(range(cell.chips)))
    freeze_cache(False)
    mem = memory_peak(devs[:cell.chips])
    run = Run(cell, sims, setup_s, compiles, reduced,
              peaks.get(kind, {}))
    metrics = read_metrics(cell, run, trace)
    t_check = time.perf_counter()
    correct, checks = check(cell, sims, seed)
    print(f"reference check: {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr)
    target = cell.cfg["run"]["target_coverage"]
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(sims),
              "failed": sum(1 for s in sims if not s.reached(target)),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for s in sims:
        if s.error:
            print(f"simulation {s.index} seed {s.seed}: {s.error}", file=sys.stderr)
        elif engine_mismatch(cell, s.report):
            print(f"simulation {s.index}: {engine_mismatch(cell, s.report)}",
                  file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    return result

