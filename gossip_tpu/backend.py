"""The ``Backend`` seam: one interface, two engines.

BASELINE.json's north star requires the simulator to sit behind a backend
interface — ``go-native`` (the event-driven engine reproducing the reference
semantics, :mod:`gossip_tpu.runtime.gonative`) vs ``jax-tpu`` (the batched
round-synchronous engine) — "so the existing CLI selects the simulator at
runtime".  The CLI (:mod:`gossip_tpu.cli`) and the gRPC sidecar
(:mod:`gossip_tpu.rpc.sidecar`) both speak only this seam.

The two engines report on their native clocks (SURVEY.md §7, the parity
mapping documented in runtime/gonative.py): ``jax-tpu`` rounds are
synchronous gossip rounds; ``go-native`` "rounds" are hop depths, plus
wall-clock convergence in ``meta``.  Coverage values and curves are directly
comparable (the parity artifact).  Message counts are NOT: go-native counts
every wire message including the per-delivery ``broadcast_ok`` ack
(reference semantics, main.go:109), while the batched kernels count
transmissions only — roughly a 2x accounting gap on flood, recorded per
backend in ``meta["msgs_counts"]``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from gossip_tpu.config import (FaultConfig, LogConfig, MeshConfig,
                               ProtocolConfig, RunConfig,
                               TopologyConfig, TxnConfig)

BACKENDS = ("jax-tpu", "go-native")

# go-native materializes every edge as python objects; past this it is no
# longer the quick parity fixture it exists to be.
_GONATIVE_MAX_NODES = 20_000
# engine='native' forces the C++ event core (20-100x the Python engine,
# README) and raises the ceiling so large-N parity spot checks stay
# CLI-reachable (VERDICT r2 item 8).
_GONATIVE_NATIVE_MAX_NODES = 1_000_000


@dataclasses.dataclass
class RunReport:
    """One simulation's outcome, backend-agnostic (JSON-serializable)."""

    backend: str
    mode: str
    n: int
    rounds: int              # rounds (jax-tpu) / hop depth (go-native)
    coverage: float
    msgs: float
    wall_s: float
    curve: Optional[List[float]] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _build_topology(tc: TopologyConfig, for_gonative: bool):
    from gossip_tpu.topology import generators as G
    if for_gonative and tc.family == "complete":
        # the event sim needs explicit neighbor lists
        if tc.n > 2048:
            raise ValueError(
                "go-native on a complete graph materializes n^2 edges; "
                f"n={tc.n} is past sanity (use a sparse family or jax-tpu)")
        return G.complete_table(tc.n)
    return G.build(tc)


def run_gonative(proto: ProtocolConfig, tc: TopologyConfig, run: RunConfig,
                 fault: Optional[FaultConfig] = None,
                 want_curve: bool = False) -> RunReport:
    """Event-driven reference-semantics run (flood relay — the only protocol
    the reference implements; SURVEY.md §2).  Faults map to partitions is
    not supported here: the event sim exposes explicit partition windows via
    its own API for targeted tests."""
    from gossip_tpu.runtime.gonative import topology_from_table
    from gossip_tpu.runtime.native_sim import (make_event_sim,
                                               native_available)
    if run.engine in ("xla", "fused"):
        raise ValueError(
            f"engine {run.engine!r} selects jax kernels; the go-native "
            "backend takes engine 'auto' (C++ core when buildable, "
            "Python otherwise) or 'native' (force the C++ core, 1M cap)")
    force_native = run.engine == "native"
    if force_native and not native_available():
        # ValueError like every sibling misconfiguration: the CLI turns
        # these into 'error: ...' + exit 2 instead of a traceback
        raise ValueError(
            "engine='native' needs the C++ event core and no compiler is "
            "available; drop the flag for the Python engine (20k cap)")
    cap = _GONATIVE_NATIVE_MAX_NODES if force_native else _GONATIVE_MAX_NODES
    if tc.n > cap:
        raise ValueError(
            f"go-native backend capped at {cap} nodes "
            + ("(C++ event core ceiling); " if force_native else
               "(parity fixture, not the scale path; engine='native' "
               "raises the cap to 1M); ")
            + f"got n={tc.n}")
    if proto.mode != "flood":
        raise ValueError(
            "go-native reproduces the reference's relay-to-all-neighbors "
            f"semantics (flood); mode {proto.mode!r} has no Go equivalent")
    if fault is not None:
        raise ValueError(
            "go-native takes no FaultConfig: faults there are explicit "
            "partition windows on the GoNativeSim API (Maelstrom-style), "
            "not per-round masks")
    topo = _build_topology(tc, for_gonative=True)
    t0 = time.perf_counter()
    # C++ event core when a compiler is present (equivalence proven in
    # tests/test_native.py), pure Python otherwise
    sim = make_event_sim(topology_from_table(topo))
    for r in range(proto.rumors):
        sim.broadcast(origin=(run.origin + r) % tc.n, message=r)
    sim.run()
    wall = time.perf_counter() - t0
    max_h = run.max_rounds
    # one hop_depths pass per rumor serves curve, convergence AND final
    # coverage (delivered <=> present in the log <=> min_hop exists) —
    # the per-node read() loop this replaces marshalled every node's
    # whole log and dominated wall time past ~100k nodes.  The curve is
    # a bincount-cumsum (O(n + max_h)), not a python double loop: nodes
    # first reached PAST max_h land in the overflow bucket and stay out
    # of every curve entry.
    import numpy as np
    depths = [sim.hop_depths(r) for r in range(proto.rumors)]
    curves = []
    for dp in depths:
        vals = np.fromiter(dp.values(), np.int64, count=len(dp))
        hist = np.bincount(np.clip(vals, 0, max_h + 1),
                           minlength=max_h + 2)
        curves.append(hist[:max_h + 1].cumsum() / tc.n)
    curve = [float(min(c[h] for c in curves)) for h in range(max_h + 1)]
    hops = next((h for h in range(max_h + 1)
                 if curve[h] >= run.target_coverage), -1)
    final_cov = min(len(dp) / tc.n for dp in depths)
    return RunReport(
        backend="go-native", mode="flood", n=tc.n,
        rounds=hops, coverage=final_cov, msgs=float(sim.msgs_sent),
        wall_s=round(wall, 4),
        curve=curve[1:] if want_curve else None,
        meta={"clock": "hop-depth", "sim_time_s": sim.now,
              "deliveries": sim.delivery_count(),
              "msgs_counts": "requests+acks",
              "engine": type(sim).__name__})


def _timing_meta(timing: Dict[str, float],
                 wall: Optional[float] = None) -> Dict[str, float]:
    """compile_s / steady_wall_s meta columns from a driver timing dict
    (round-2 verdict: reported walls must not mix one-off compile cost
    with steady-state throughput).  Empty when the driver didn't run
    the AOT split.

    With ``wall`` (the engine wall the report carries), also reconciles
    it: ``driver_overhead_s = wall - compile_s - steady_wall_s`` — the
    state/table builders, host transfers, and dispatch inside the timed
    driver but outside the AOT-split call — so every reported wall
    decomposes in the artifact itself (VERDICT r4 task 5: wall must ~=
    sum of reported parts)."""
    if not timing:
        return {}
    out = {"compile_s": round(timing["compile_s"], 4),
           "steady_wall_s": round(timing["steady_s"], 4)}
    if "init_build_s" in timing:
        # drivers that decompose further (sharded_fused._init_and_masks)
        # report the state/mask build — a named slice of the overhead
        out["init_build_s"] = round(timing["init_build_s"], 4)
    if "exec_memo" in timing:
        # whether the compile layer's in-process memo served the
        # executable (utils/compile_cache)
        out["exec_memo"] = timing["exec_memo"]
    if wall is not None:
        out["driver_overhead_s"] = round(
            max(0.0, wall - timing["compile_s"] - timing["steady_s"]), 4)
    return out


def _curve_summary(covs, msgs, target):
    """(rounds_to_target, final_cov, final_msgs, curve) from per-round
    series — the one place the -1 sentinel / target comparison lives."""
    hit = [i for i, c in enumerate(covs) if c >= target]
    return ((hit[0] + 1) if hit else -1, float(covs[-1]), float(msgs[-1]),
            [float(c) for c in covs])


def _run_fused(proto: ProtocolConfig, tc: TopologyConfig, run: RunConfig,
               fault: Optional[FaultConfig], n_dev: int,
               want_curve: bool) -> RunReport:
    """engine='fused': the Pallas VMEM pull kernels as a product surface.

    Single device: the node-packed (rumors=1) or one-word-per-node
    (rumors<=32) kernel.  Multi-device: rumor-plane sharding
    (parallel/sharded_fused.py) — planes of 32 rumors across the mesh,
    identical partner stream per device, zero per-round ICI.

    Validates eagerly and loudly — the fused kernels cover exactly the
    flagship envelope (TPU, pull, implicit complete graph; static fault
    masks on EVERY layout since round 4 — node-packed,
    one-word-per-node, staged big path, plane-sharded — with scripted
    dead_nodes still rejected) and silently substituting a different
    engine would mislabel the wall-clock numbers, same policy as the
    exchange routing above.
    """
    import jax as _jax
    import jax.numpy as jnp

    from gossip_tpu.ops.pallas_round import (
        BITS, compiled_until_fused, compiled_until_fused_multirumor,
        coverage_node_packed, coverage_words, fused_table_bytes)

    reason = _fused_ineligible_reason(proto, tc, fault, n_dev)
    if reason is not None:
        raise ValueError(reason)
    # multi-device shards rumor PLANES, so the per-device table is always
    # the one-word-per-node layout regardless of total rumor count
    table_bytes = fused_table_bytes(tc.n,
                                    proto.rumors if n_dev == 1 else BITS)

    n = tc.n
    if n_dev > 1:
        from gossip_tpu.parallel.sharded_fused import (
            make_plane_mesh, plane_count, simulate_curve_sharded_fused,
            simulate_until_sharded_fused)
        mesh = make_plane_mesh(n_dev)
        w = plane_count(proto.rumors, n_dev)
        timing: Dict[str, float] = {}
        t0 = time.perf_counter()
        if want_curve:
            # fixed-length scan (no early exit): rounds-to-target and
            # the -1 sentinel derive from the curve like the XLA paths
            covs, final = simulate_curve_sharded_fused(
                n, proto.rumors, run, mesh, fanout=proto.fanout,
                fault=fault, timing=timing)
            _jax.block_until_ready(final)
            wall = time.perf_counter() - t0
            # _curve_summary reads only msgs[-1]; the fused accounting
            # is the closed form 2*fanout*n per round over the full scan
            rounds, cov, msgs, curve = _curve_summary(
                covs, [2.0 * proto.fanout * n * run.max_rounds],
                run.target_coverage)
        else:
            rounds_ex, cov, msgs, final = simulate_until_sharded_fused(
                n, proto.rumors, run, mesh, fanout=proto.fanout,
                fault=fault, timing=timing)
            _jax.block_until_ready(final)
            wall = time.perf_counter() - t0
            hit = cov >= float(jnp.float32(run.target_coverage))
            rounds, curve = (rounds_ex if hit else -1), None
        return RunReport(
            backend="jax-tpu", mode=proto.mode, n=n,
            rounds=rounds, coverage=cov, msgs=msgs,
            wall_s=round(wall, 4), curve=curve,
            meta={"clock": "rounds", "devices": n_dev,
                  "msgs_counts": "transmissions",
                  "engine": "fused-pallas-planes",
                  "layout": f"{w} rumor planes x one 32-rumor word per node",
                  "vmem_table_bytes_per_plane": table_bytes,
                  "ici_bytes_per_round": 0.0,
                  **_timing_meta(timing, wall)})

    if want_curve:
        from gossip_tpu.ops.pallas_round import (
            compiled_curve_fused, compiled_curve_fused_multirumor)
        if proto.rumors == 1:
            scan, init = compiled_curve_fused(
                n, fanout=proto.fanout,
                max_rounds=run.max_rounds, origin=run.origin,
                interpret=False, fault=fault)
        else:
            scan, init = compiled_curve_fused_multirumor(
                n, proto.rumors, fanout=proto.fanout,
                max_rounds=run.max_rounds, origin=run.origin,
                interpret=False, fault=fault)
        from gossip_tpu.utils.trace import maybe_aot_timed
        timing: Dict[str, float] = {}
        t0 = time.perf_counter()
        final, covs = maybe_aot_timed(scan, timing, init,
                                      jnp.int32(run.seed), label="solo")
        wall = time.perf_counter() - t0
        # the scanned state already accumulated the closed-form total
        rounds, cov, msgs, curve = _curve_summary(
            covs, [float(final.msgs)], run.target_coverage)
        return RunReport(
            backend="jax-tpu", mode=proto.mode, n=n, rounds=rounds,
            coverage=cov, msgs=msgs, wall_s=round(wall, 4), curve=curve,
            meta={"clock": "rounds", "devices": 1,
                  "msgs_counts": "transmissions", "engine": "fused-pallas",
                  "layout": ("node-packed bitmap" if proto.rumors == 1
                             else "one 32-rumor word per node"),
                  "vmem_table_bytes": table_bytes,
                  **_timing_meta(timing, wall)})

    from gossip_tpu.utils.trace import BUILD, READBACK, maybe_aot_timed, span
    with span(BUILD):
        if proto.rumors == 1:
            loop, init = compiled_until_fused(
                n, fanout=proto.fanout,
                target_coverage=run.target_coverage,
                max_rounds=run.max_rounds, origin=run.origin, fault=fault)
            # the SAME weighting chooser the loop's cond uses — cannot drift
            from gossip_tpu.ops.pallas_round import fused_cov_fn
            cov_fn = fused_cov_fn(n, fault, run.origin)
        else:
            loop, init = compiled_until_fused_multirumor(
                n, proto.rumors, fanout=proto.fanout,
                target_coverage=run.target_coverage,
                max_rounds=run.max_rounds, origin=run.origin, fault=fault)
            from gossip_tpu.ops.pallas_round import fused_mr_cov_fn
            cov_fn = fused_mr_cov_fn(n, proto.rumors, fault, run.origin)

    timing: Dict[str, float] = {}
    t0 = time.perf_counter()
    # the seed is an operand of the loop, not a constant: one executable
    # (one compile-layer key) serves every seed of a study
    final = maybe_aot_timed(loop, timing, init, jnp.int32(run.seed),
                            label="solo")
    wall = time.perf_counter() - t0
    with span(READBACK):
        cov = float(cov_fn(final.table))
        rounds = int(final.round)
        msgs = float(final.msgs)
    # float32 target compare, same threshold the loop's cond used
    hit = cov >= float(jnp.float32(run.target_coverage))
    return RunReport(
        backend="jax-tpu", mode=proto.mode, n=n,
        rounds=rounds if hit else -1, coverage=cov,
        msgs=msgs, wall_s=round(wall, 4),
        meta={"clock": "rounds", "devices": 1,
              "msgs_counts": "transmissions", "engine": "fused-pallas",
              "layout": ("node-packed bitmap" if proto.rumors == 1
                         else "one 32-rumor word per node"),
              "vmem_table_bytes": table_bytes,
              **_timing_meta(timing, wall)})


def _fused_ineligible_reason(proto: ProtocolConfig, tc: TopologyConfig,
                             fault: Optional[FaultConfig],
                             n_dev: int,
                             plane_stack: bool = False) -> Optional[str]:
    """Why this run cannot use the fused Pallas engine, or None if it can.

    The ONE list of preconditions: engine='fused' raises it verbatim,
    engine='auto' checks it quietly — so the two can never drift apart.
    Config reasons come before the platform probe so forced-fused config
    errors surface identically on any backend.  ``plane_stack``: the
    caller routes to the plane-sharded drivers regardless of n_dev (the
    checkpointed CLI path), which run churn EVENTS as alive-word
    operands — only that combination relaxes the churn rejection."""
    from gossip_tpu.ops.pallas_round import BITS, check_fused_fits
    import jax as _jax
    if proto.mode != "pull":
        return (f"engine='fused' implements pull rounds only "
                f"(got mode {proto.mode!r})")
    if tc.family != "complete":
        return ("engine='fused' runs on the implicit complete "
                f"topology only (got family {tc.family!r})")
    if fault is not None and fault.dead_nodes:
        # scripted dead_nodes/fail_round is a SWIM scenario; the fused
        # kernels' static masks do not implement it — reject loudly
        # rather than run fault-free under a fault flag
        return ("engine='fused' does not implement scripted dead_nodes/"
                "fail_round; use engine='auto' (or node_death_rate for "
                "random static deaths)")
    if (fault is not None and fault.churn is not None
            and not plane_stack and n_dev == 1):
        # the plane-sharded fused drivers run the FULL nemesis — churn
        # events, partition windows (per-round side-word cut masks),
        # and drop-rate ramps (the threshold table behind the SMEM
        # scalar operand): every multi-device fused route and the
        # plane_stack surfaces (--checkpoint, churn-sweep --engine
        # fused) land there.  Only the SINGLE-device fused routing
        # rejects churn: its compiled_*_fused paths predate the churn
        # denominator — auto falls back to the XLA kernels, which run
        # every schedule
        return ("engine='fused' routing does not run churn "
                "schedules single-device; use engine='auto' (XLA "
                "kernels run the full nemesis scenario catalog — "
                "docs/ROBUSTNESS.md), or the plane-sharded fused "
                "surfaces (--devices > 1, --checkpoint, churn-sweep "
                "--engine fused), which run events + partitions + "
                "ramps as runtime operands")
    # node_death_rate / drop_prob: in-kernel static fault masks cover
    # every fused layout since round 4 (node-packed, one-word-per-node,
    # staged big path, plane-sharded) — no restriction to return
    if n_dev == 1 and proto.rumors > BITS:
        return (f"engine='fused' packs <= {BITS} rumors per word "
                f"on one device (got rumors={proto.rumors}); "
                "shard rumor planes with --devices")
    # curve capture is no longer a restriction — round 4 added
    # fixed-length scan twins of every fused driver (compiled_curve_*,
    # simulate_curve_sharded_fused), so eligibility no longer consults
    # want_curve at all
    try:
        check_fused_fits(tc.n, proto.rumors if n_dev == 1 else BITS,
                         proto.fanout)
    except ValueError as e:
        return str(e)
    if _jax.default_backend() != "tpu":
        return ("engine='fused' needs a TPU (the kernel samples partners "
                "with the TPU hardware PRNG, which has no CPU "
                "equivalent); use engine='auto' for the XLA bit-packed "
                "path")
    return None


def swim_scenario(proto: ProtocolConfig, n: int,
                  fault: Optional[FaultConfig]):
    """Failure scenario for a SWIM run, shared by the streaming and
    checkpointed drivers: ``(dead_subjects, fail_round,
    default_scenario)``.  From the FaultConfig (CLI --dead-nodes /
    --fail-round, RPC fault.dead_nodes); default: node ``1 % S`` fails
    at round 2 (recorded in run meta so the scenario is discoverable).
    Scripted CHURN events are a scenario too — a churn-only run gets no
    default death injected on top of its schedule, and the detection
    metric targets the permanent churn crashes
    (models/swim.detection_targets).  Validates the metric targets
    against ``n`` and — without rotation — against the fixed subject
    window."""
    from gossip_tpu.models.swim import detection_targets
    from gossip_tpu.ops import nemesis as NE
    churn = NE.get(fault)
    scripted = fault is not None and (
        bool(fault.dead_nodes) or (churn is not None and churn.events))
    default_scenario = not scripted
    if default_scenario:
        dead = (1 % proto.swim_subjects,)
        fail_round = 2
    else:
        dead = fault.dead_nodes
        fail_round = fault.fail_round
    targets = detection_targets(dead, fault)
    bad = [d for d in targets if d >= n]
    if bad:
        raise ValueError(f"dead_nodes {bad} out of range for n={n}")
    if not proto.swim_rotate:
        outside = [d for d in targets if d >= proto.swim_subjects]
        if outside:
            raise ValueError(
                f"dead/churn-dead nodes {outside} are outside the fixed "
                f"subject window 0..{proto.swim_subjects - 1}; enable "
                "--swim-rotate for full-membership detection")
    return dead, fail_round, default_scenario


def swim_scenario_meta(proto: ProtocolConfig, n: int,
                       fault: Optional[FaultConfig]):
    """(dead, fail_round, meta) — the scenario plus the discoverability
    meta keys EVERY swim driver reports (streaming, checkpointed,
    ensemble), so the three surfaces cannot drift."""
    dead, fail_round, default_scenario = swim_scenario(proto, n, fault)
    from gossip_tpu.models.swim import detection_targets
    meta = {"metric": "detection_fraction",
            # what the metric actually measures: static scripted deaths
            # + permanent churn deaths (== dead for no-churn configs)
            "dead_subjects": list(detection_targets(dead, fault)),
            "fail_round": fail_round, "default_scenario": default_scenario}
    return dead, fail_round, meta


def _fused_auto_ok(proto: ProtocolConfig, tc: TopologyConfig,
                   fault: Optional[FaultConfig]) -> bool:
    """True when a single-device run is eligible for the fused Pallas
    engine and it is safe to pick it silently under engine='auto'."""
    return _fused_ineligible_reason(proto, tc, fault, 1) is None


def run_jax(proto: ProtocolConfig, tc: TopologyConfig, run: RunConfig,
            fault: Optional[FaultConfig] = None,
            mesh_cfg: Optional[MeshConfig] = None,
            want_curve: bool = False) -> RunReport:
    """Batched round-synchronous run; shards over a device mesh when
    ``mesh_cfg.n_devices > 1``.

    The returned report's ``wall_s`` is the ENGINE wall (driver call
    only); ``meta["topo_build_s"]`` carries the device-side topology
    build separately — on a cold backend the first device op also pays
    client/runtime init here, which round 4's hardware table left as
    ~10 s of unattributed wall on its first row (VERDICT r4 task 5)."""
    from gossip_tpu.topology import generators as G
    if run.engine == "native":
        raise ValueError(
            "engine='native' is the go-native backend's C++ event core; "
            "jax-tpu engines are auto|xla|fused (use --backend go-native)")
    import jax as _jax

    from gossip_tpu.utils.trace import BUILD, span
    with span(BUILD):
        t0_build = time.perf_counter()
        topo = G.build(tc)
        if topo.nbrs is not None:
            _jax.block_until_ready((topo.nbrs, topo.deg))
        topo_build_s = time.perf_counter() - t0_build
    rep = _run_jax_with_topo(proto, tc, run, fault, mesh_cfg, want_curve,
                             topo)
    rep.meta["topo_build_s"] = round(topo_build_s, 4)
    return rep


def _run_jax_with_topo(proto: ProtocolConfig, tc: TopologyConfig,
                       run: RunConfig, fault: Optional[FaultConfig],
                       mesh_cfg: Optional[MeshConfig], want_curve: bool,
                       topo) -> RunReport:
    n_dev = 1 if mesh_cfg is None else mesh_cfg.n_devices
    _exchange = "dense" if mesh_cfg is None else mesh_cfg.exchange
    if _exchange != "dense":
        # never silently substitute the dense path for a requested
        # sparse/halo run — the traffic numbers would be mislabeled
        if n_dev == 1:
            raise ValueError(
                f"exchange={_exchange!r} is a cross-shard pattern; it needs "
                "n_devices > 1 (single-device runs have no exchange)")
        if proto.mode in ("swim", "rumor"):
            raise ValueError(
                f"exchange={_exchange!r} is not implemented for "
                f"{proto.mode}; swim and rumor shard via the dense "
                "kernels (pmax / psum_scatter + all_gather)")

    if run.engine == "fused":
        if _exchange != "dense":
            raise ValueError(
                f"exchange={_exchange!r} requests a cross-shard digest "
                "pattern; engine='fused' shards rumor planes with zero "
                "per-round ICI and implements no exchange — use "
                "engine='auto' for sparse/halo runs")
        return _run_fused(proto, tc, run, fault, n_dev, want_curve)

    # engine='auto' picks the fused Pallas kernel when a single-device run
    # is eligible — it is strictly faster than the XLA paths there.
    # Multi-device auto keeps the node-dim sharded engines (fused shards
    # rumor PLANES, a different scaling story the user opts into).
    if (run.engine == "auto" and n_dev == 1
            and _fused_auto_ok(proto, tc, fault)):
        rep = _run_fused(proto, tc, run, fault, 1, want_curve)
        rep.meta["engine_auto"] = "fused"
        return rep

    if proto.mode == "swim":
        from gossip_tpu.models.swim import (resolve_epoch_rounds,
                                            suggested_suspect_rounds)
        from gossip_tpu.runtime.simulator import simulate_swim_curve
        mesh = None
        if n_dev > 1:
            from gossip_tpu.parallel.sharded import make_mesh
            mesh = make_mesh(n_dev)
        dead, fail_round, meta = swim_scenario_meta(proto, tc.n, fault)
        swim_topo = None if tc.family == "complete" else topo
        from gossip_tpu.models.swim import effective_diss
        meta.update({"clock": "rounds",
                     "suggested_suspect_rounds":
                         suggested_suspect_rounds(tc.n, proto.fanout),
                     "devices": n_dev,
                     # the lowering disseminate_max actually ran: 'pack'
                     # degrades to 'sort' when max_rounds proves no
                     # transport-lane bound (bitwise-identical results,
                     # but a benchmark must see the substitution)
                     "swim_diss_effective": effective_diss(
                         proto.swim_diss, run.max_rounds),
                     "swim_rng": proto.swim_rng})
        if proto.swim_rotate:
            meta["subject_window"] = "rotating"
            meta["epoch_rounds"] = resolve_epoch_rounds(proto, tc.n)
        t0 = time.perf_counter()
        if want_curve:
            fracs, final = simulate_swim_curve(
                proto, tc.n, run.max_rounds, dead_nodes=dead,
                fail_round=fail_round, fault=fault, topo=swim_topo,
                seed=run.seed, mesh=mesh)
            wall = time.perf_counter() - t0
            hit = [i for i, f in enumerate(fracs)
                   if f >= run.target_coverage]
            rounds_out = (hit[0] + 1) if hit else -1
            det_final = float(fracs[-1])
            if proto.swim_rotate:
                # rotation: detection is scoped to the dead node's epoch;
                # the headline number is the best in-window detection
                meta["peak_detection"] = float(max(fracs))
            curve = [float(f) for f in fracs]
        else:
            # early-exit driver: stops the round detection hits the
            # target instead of scanning the full max_rounds budget
            import jax.numpy as jnp

            from gossip_tpu.runtime.simulator import simulate_swim_until
            timing: Dict[str, float] = {}
            r, det_final, det_peak, final = simulate_swim_until(
                proto, tc.n, run.max_rounds, run.target_coverage,
                dead_nodes=dead, fail_round=fail_round, fault=fault,
                topo=swim_topo, seed=run.seed, mesh=mesh, timing=timing)
            wall = time.perf_counter() - t0
            meta.update(_timing_meta(timing, wall))
            # same f32 threshold the loop's cond compared against
            tgt32 = float(jnp.float32(run.target_coverage))
            rounds_out = r if det_final >= tgt32 else -1
            if proto.swim_rotate:
                # peak over the whole run, like the curve path's
                # max(fracs): the window may have rotated past the dead
                # node's epoch by the time the loop stops
                meta["peak_detection"] = det_peak
            curve = None
        return RunReport(
            backend="jax-tpu", mode="swim", n=tc.n, rounds=rounds_out,
            coverage=det_final, msgs=float(final.msgs),
            wall_s=round(wall, 4), curve=curve, meta=meta)

    if proto.mode == "rumor":
        import jax.numpy as jnp

        from gossip_tpu.models.rumor import (simulate_curve_rumor,
                                             simulate_until_rumor)
        timing: Dict[str, float] = {}
        t0 = time.perf_counter()
        if want_curve:
            if n_dev > 1:
                from gossip_tpu.parallel.sharded import make_mesh
                from gossip_tpu.parallel.sharded_rumor import (
                    simulate_curve_rumor_sharded)
                covs, hots, msgs, final = simulate_curve_rumor_sharded(
                    proto, topo, run, make_mesh(n_dev), fault)
            else:
                covs, hots, msgs, final = simulate_curve_rumor(
                    proto, topo, run, fault)
            wall = time.perf_counter() - t0
            _, cov, msgs_f, curve = _curve_summary(
                covs, msgs, run.target_coverage)
            # rounds means ROUNDS-TO-EXTINCTION for rumor mongering, same
            # as the non-curve path (meta["rounds_semantics"]); -1 if the
            # hot set survived to max_rounds
            import numpy as _np
            dead_at = _np.nonzero(_np.asarray(hots) == 0.0)[0]
            rounds = int(dead_at[0]) + 1 if len(dead_at) else -1
            residue = 1.0 - float(covs[-1])
            hot_left = float(hots[-1])
        else:
            if n_dev > 1:
                from gossip_tpu.parallel.sharded import make_mesh
                from gossip_tpu.parallel.sharded_rumor import (
                    simulate_until_rumor_sharded)
                mesh = make_mesh(n_dev)
                rounds_ext, cov, residue, msgs_f, final = (
                    simulate_until_rumor_sharded(proto, topo, run, mesh,
                                                 fault, timing=timing))
            else:
                rounds_ext, cov, residue, msgs_f, final = (
                    simulate_until_rumor(proto, topo, run, fault,
                                         timing=timing))
            wall = time.perf_counter() - t0
            curve = None
            # rounds reports rounds-to-extinction; -1 only if hot pairs
            # survived to max_rounds (no self-termination).  Slice to the
            # real n rows: the sharded state pads to the mesh.
            from gossip_tpu.utils.trace import READBACK, span
            with span(READBACK):
                hot_left = float(jnp.mean(jnp.any(final.hot[:tc.n], axis=1)
                                          .astype(jnp.float32)))
            rounds = rounds_ext if hot_left == 0.0 else -1
        return RunReport(
            backend="jax-tpu", mode="rumor", n=tc.n, rounds=rounds,
            coverage=cov, msgs=msgs_f, wall_s=round(wall, 4), curve=curve,
            meta={"clock": "rounds", "devices": n_dev,
                  "msgs_counts": "transmissions", "engine": "rumor-xla",
                  "rounds_semantics": "extinction",
                  "variant": proto.rumor_variant, "rumor_k": proto.rumor_k,
                  "residue": round(residue, 6),
                  "hot_fraction_final": hot_left,
                  "terminated": hot_left == 0.0,
                  **_timing_meta(timing, wall)})

    if n_dev > 1 and _exchange == "sparse":
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_sparse import (
            simulate_curve_sparse, simulate_curve_topo_sparse,
            simulate_until_sparse, simulate_until_topo_sparse)
        mesh = make_mesh(n_dev)
        if tc.family != "complete":
            # Explicit topology: capacity-capped all_to_all by partner's
            # owning shard (VERDICT r2 item 5) — pull and anti-entropy;
            # the factory raises loudly for other modes (never silently
            # densified).
            timing: Dict[str, float] = {}
            t0 = time.perf_counter()
            overflow = None
            if want_curve:
                covs, msgs, _, smeta, ovfs = simulate_curve_topo_sparse(
                    proto, topo, run, mesh, fault, timing=timing)
                wall = time.perf_counter() - t0
                rounds, cov, msgs_f, curve = _curve_summary(
                    covs, msgs, run.target_coverage)
                overflow = float(ovfs[-1])
            else:
                (rounds, cov, msgs_f, _, smeta,
                 overflow) = simulate_until_topo_sparse(
                    proto, topo, run, mesh, fault, timing=timing)
                wall = time.perf_counter() - t0
                curve = None
            return RunReport(
                backend="jax-tpu", mode=proto.mode, n=tc.n, rounds=rounds,
                coverage=cov, msgs=msgs_f, wall_s=round(wall, 4),
                curve=curve,
                meta={"clock": "rounds", "devices": n_dev,
                      "msgs_counts": "transmissions", "exchange": "sparse",
                      "overflow_dropped_requests": overflow,
                      "bucket_cap": smeta.cap,
                      # for anti-entropy with period>1 the WHOLE
                      # exchange is cond-skipped on quiescent rounds, so
                      # every sparse byte figure is per EXCHANGE round
                      # (steady average = /period — SparseMeta doc);
                      # reverse broken out as the AE-only payload
                      "ici_bytes_per_round": {
                          "sparse": smeta.sparse_bytes,
                          "dense_equivalent": smeta.dense_bytes,
                          "reverse_exchange_only": smeta.reverse_bytes},
                      **_timing_meta(timing, wall)})
        timing = {}
        t0 = time.perf_counter()
        if want_curve:
            covs, msgs, _, smeta = simulate_curve_sparse(
                proto, tc.n, run, mesh, fault, timing=timing)
            wall = time.perf_counter() - t0
            rounds, cov, msgs_f, curve = _curve_summary(
                covs, msgs, run.target_coverage)
        else:
            rounds, cov, msgs_f, _, smeta = simulate_until_sparse(
                proto, tc.n, run, mesh, fault, timing=timing)
            wall = time.perf_counter() - t0
            curve = None
        return RunReport(
            backend="jax-tpu", mode=proto.mode, n=tc.n, rounds=rounds,
            coverage=cov, msgs=msgs_f, wall_s=round(wall, 4), curve=curve,
            meta={"clock": "rounds", "devices": n_dev,
                  "msgs_counts": "transmissions", "exchange": "sparse",
                  "ici_bytes_per_round": {
                      "sparse": smeta.sparse_bytes,
                      "dense_equivalent": smeta.dense_bytes,
                      "reverse_exchange_only": smeta.reverse_bytes},
                  **_timing_meta(timing, wall)})

    if n_dev > 1 and _exchange == "halo":
        from gossip_tpu.parallel.halo import (simulate_curve_halo,
                                              simulate_until_halo)
        from gossip_tpu.parallel.sharded import make_mesh
        mesh = make_mesh(n_dev)
        timing = {}
        t0 = time.perf_counter()
        if want_curve:
            covs, msgs, _, band = simulate_curve_halo(proto, topo, run,
                                                      mesh, fault,
                                                      timing=timing)
            wall = time.perf_counter() - t0
            rounds, cov, msgs_f, curve = _curve_summary(
                covs, msgs, run.target_coverage)
        else:
            rounds, cov, msgs_f, _, band = simulate_until_halo(
                proto, topo, run, mesh, fault, timing=timing)
            wall = time.perf_counter() - t0
            curve = None
        return RunReport(
            backend="jax-tpu", mode=proto.mode, n=tc.n, rounds=rounds,
            coverage=cov, msgs=msgs_f, wall_s=round(wall, 4), curve=curve,
            meta={"clock": "rounds", "devices": n_dev,
                  "msgs_counts": "transmissions", "exchange": "halo",
                  "band": band, **_timing_meta(timing, wall)})

    # Pull and anti-entropy route through the bit-packed engines (32 rumor
    # bits per gathered word) — bitwise-identical trajectories to the bool
    # kernels (tests/test_packed.py), just less HBM/ICI traffic.  The curve
    # drivers stay on the bool path (no packed scan driver yet).
    packed_ok = proto.mode in ("pull", "antientropy") and not want_curve

    if n_dev > 1:
        from gossip_tpu.parallel.sharded import (
            make_mesh, simulate_curve_sharded, simulate_until_sharded)
        mesh = make_mesh(n_dev)
        if packed_ok:
            from gossip_tpu.parallel.sharded_packed import (
                simulate_until_packed_sharded)
            timing = {}
            t0 = time.perf_counter()
            rounds, cov, msgs, _ = simulate_until_packed_sharded(
                proto, topo, run, mesh, fault, timing=timing)
            wall = time.perf_counter() - t0
            return RunReport(backend="jax-tpu", mode=proto.mode, n=tc.n,
                             rounds=rounds, coverage=cov, msgs=msgs,
                             wall_s=round(wall, 4),
                             meta={"clock": "rounds", "devices": n_dev,
                                   "msgs_counts": "transmissions",
                                   "engine": "bit-packed",
                                   **_timing_meta(timing, wall)})
        timing = {}
        t0 = time.perf_counter()
        if want_curve:
            covs, msgs, _ = simulate_curve_sharded(proto, topo, run, mesh,
                                                   fault, timing=timing)
            wall = time.perf_counter() - t0
            rounds, cov, msgs_f, curve = _curve_summary(
                covs, msgs, run.target_coverage)
            return RunReport(
                backend="jax-tpu", mode=proto.mode, n=tc.n, rounds=rounds,
                coverage=cov, msgs=msgs_f,
                wall_s=round(wall, 4), curve=curve,
                meta={"clock": "rounds", "devices": n_dev,
                      "msgs_counts": "transmissions",
                      **_timing_meta(timing, wall)})
        rounds, cov, msgs, _ = simulate_until_sharded(proto, topo, run, mesh,
                                                      fault, timing=timing)
        wall = time.perf_counter() - t0
        return RunReport(backend="jax-tpu", mode=proto.mode, n=tc.n,
                         rounds=rounds, coverage=cov, msgs=msgs,
                         wall_s=round(wall, 4),
                         meta={"clock": "rounds", "devices": n_dev,
                               "msgs_counts": "transmissions",
                               **_timing_meta(timing, wall)})

    if packed_ok:
        from gossip_tpu.models.si_packed import simulate_until_packed
        timing: Dict[str, float] = {}
        t0 = time.perf_counter()
        rounds, cov, msgs, _ = simulate_until_packed(proto, topo, run,
                                                     fault, timing=timing)
        wall = time.perf_counter() - t0
        return RunReport(backend="jax-tpu", mode=proto.mode, n=tc.n,
                         rounds=rounds, coverage=cov, msgs=msgs,
                         wall_s=round(wall, 4),
                         meta={"clock": "rounds", "devices": 1,
                               "msgs_counts": "transmissions",
                               "engine": "bit-packed",
                               **_timing_meta(timing, wall)})

    from gossip_tpu.runtime.simulator import simulate_curve, simulate_until
    t0 = time.perf_counter()
    if want_curve:
        res = simulate_curve(proto, topo, run, fault)
        wall = time.perf_counter() - t0
        return RunReport(
            backend="jax-tpu", mode=proto.mode, n=tc.n,
            rounds=res.rounds_to_target, coverage=res.final_coverage,
            msgs=float(res.msgs[-1]), wall_s=round(wall, 4),
            curve=[float(c) for c in res.coverage],
            meta={"clock": "rounds", "devices": 1,
                  "msgs_counts": "transmissions"})
    timing = {}
    res = simulate_until(proto, topo, run, fault, timing=timing)
    wall = time.perf_counter() - t0
    return RunReport(backend="jax-tpu", mode=proto.mode, n=tc.n,
                     rounds=res.rounds, coverage=res.coverage, msgs=res.msgs,
                     wall_s=round(wall, 4),
                     meta={"clock": "rounds", "devices": 1,
                           "msgs_counts": "transmissions",
                           **_timing_meta(timing, wall)})


def run_payload_workload(proto: ProtocolConfig, tc: TopologyConfig,
                         run: RunConfig, cfg,
                         fault: Optional[FaultConfig] = None,
                         want_curve: bool = False) -> RunReport:
    """The replicated-payload workload behind the ``Run`` RPC's ``log``
    and ``txn`` fields (models/payload drivers; single-process
    single-device — the node mesh shards via the library API, the
    Ensemble RPC rule).  ``coverage`` reports the final convergence of
    the config's payload row; meta carries its truth summary."""
    from gossip_tpu.models.payload import (row_for,
                                           simulate_curve_payload,
                                           simulate_until_payload)
    from gossip_tpu.topology import generators as G
    row = row_for(cfg)
    row.check_mode(proto)
    if run.engine not in ("auto", "xla"):
        raise ValueError(f"engine={run.engine!r} cannot run the "
                         f"{row.label} workload (XLA pull kernels only)")
    topo = G.build(tc)
    t0 = time.perf_counter()
    if want_curve:
        conv, msgs, _, truth = simulate_curve_payload(cfg, proto, topo,
                                                      run, fault)
        hit = [i for i, c in enumerate(conv)
               if c >= run.target_coverage]
        rounds = (hit[0] + 1) if hit else -1
        cv, msgs_f = float(conv[-1]), float(msgs[-1])
        curve = [float(c) for c in conv]
    else:
        rounds, cv, msgs_f, _, truth = simulate_until_payload(
            cfg, proto, topo, run, fault)
        curve = None
    wall = time.perf_counter() - t0
    return RunReport(
        backend="jax-tpu", mode=row.label, n=tc.n, rounds=rounds,
        coverage=cv, msgs=msgs_f, wall_s=round(wall, 4), curve=curve,
        meta={"clock": "rounds", "devices": 1,
              "msgs_counts": "transmissions",
              "engine": f"{row.label}-xla", "workload": row.label,
              "truth": truth})


def run_simulation(backend: str, proto: ProtocolConfig, tc: TopologyConfig,
                   run: RunConfig, fault: Optional[FaultConfig] = None,
                   mesh_cfg: Optional[MeshConfig] = None,
                   want_curve: bool = False,
                   log_cfg: Optional[LogConfig] = None,
                   txn_cfg: Optional[TxnConfig] = None) -> RunReport:
    """The one entry point both the CLI and the sidecar call."""
    if log_cfg is not None and txn_cfg is not None:
        raise ValueError("a request carries at most one payload "
                         "workload; pick 'log' or 'txn'")
    cfg = txn_cfg if txn_cfg is not None else log_cfg
    if cfg is not None:
        from gossip_tpu.models.payload import row_for
        label = row_for(cfg).label
        if backend != "jax-tpu":
            raise ValueError(f"the {label} workload needs the jax-tpu "
                             "backend")
        if mesh_cfg is not None:
            raise ValueError(f"the {label} workload over RPC is "
                             "single-process single-device; shard the "
                             "node mesh via the library API "
                             "(parallel/sharded_payload)")
        return run_payload_workload(proto, tc, run, cfg, fault,
                                    want_curve)
    if backend == "go-native" and run.engine not in ("auto", "native"):
        raise ValueError(f"engine={run.engine!r} is a jax-tpu kernel "
                         "selection; go-native takes 'auto' (C++ core "
                         "when buildable, Python otherwise) or 'native' "
                         "(force the C++ core, 1M node cap)")
    if backend == "go-native":
        return run_gonative(proto, tc, run, fault, want_curve)
    if backend == "jax-tpu":
        return run_jax(proto, tc, run, fault, mesh_cfg, want_curve)
    raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")


# -- (de)serialization for the RPC/CLI boundary --------------------------

_CFG_TYPES = {"proto": ProtocolConfig, "topology": TopologyConfig,
              "run": RunConfig, "fault": FaultConfig,
              "mesh": MeshConfig, "log": LogConfig, "txn": TxnConfig}


def run_ensemble(proto: ProtocolConfig, tc: TopologyConfig, run: RunConfig,
                 fault: Optional[FaultConfig] = None, seeds=None,
                 count: Optional[int] = None, mesh=None):
    """Mode-dispatched seed ensemble — the ONE place the CLI's
    ``--ensemble`` and the sidecar's ``Ensemble`` RPC share: SI modes,
    SIR rumor mongering (residue/extinction distributions), and SWIM
    failure detection (detection-latency distribution for one scenario).
    Pass ``seeds`` explicitly or ``count`` (seeds become ``run.seed +
    i`` — the ONE place that default lives); ``mesh`` shards the seed
    axis (value-invariant).  Flood is admitted but varies across seeds
    only through fault randomness (its relay has no peer draw).
    Returns ``(ens, extra)`` — the ensemble result and the
    mode-specific report keys."""
    from gossip_tpu.parallel.sweep import (ensemble_curves,
                                           ensemble_rumor_curves,
                                           ensemble_swim_curves)
    from gossip_tpu.topology import generators as G
    if run.engine == "fused":
        raise ValueError("ensembles run the threefry XLA kernels; "
                         "engine='fused' is single-run only")
    if seeds is None and count is not None:
        seeds = [run.seed + i for i in range(int(count))]
    seeds = list(seeds) if seeds else None
    if not seeds:
        raise ValueError("need at least one seed (pass seeds or count)")
    extra: Dict[str, Any] = {}
    if proto.mode == "rumor":
        ens = ensemble_rumor_curves(proto, G.build(tc), run, seeds,
                                    fault, mesh=mesh)
    elif proto.mode == "swim":
        dead, fail_round, extra = swim_scenario_meta(proto, tc.n, fault)
        swim_topo = None if tc.family == "complete" else G.build(tc)
        ens = ensemble_swim_curves(proto, tc.n, run, seeds,
                                   dead_nodes=dead, fail_round=fail_round,
                                   fault=fault, topo=swim_topo, mesh=mesh)
        if proto.swim_rotate:
            # rotation: detection drops after the window leaves the dead
            # node's epoch — the headline is the per-seed PEAK (the solo
            # drivers' contract)
            peaks = ens.curves.max(axis=1)
            extra["subject_window"] = "rotating"
            extra["peak_detection_mean"] = float(peaks.mean())
            extra["peak_detection_min"] = float(peaks.min())
    else:
        ens = ensemble_curves(proto, G.build(tc), run, seeds, fault,
                              mesh=mesh)
    return ens, extra


def request_to_args(req: Dict[str, Any]) -> Dict[str, Any]:
    """JSON request dict -> kwargs for :func:`run_simulation`.  Unknown
    fields are rejected (typos should not silently become defaults)."""
    known_top = set(_CFG_TYPES) | {"backend", "curve"}
    bad_top = set(req) - known_top
    if bad_top:
        raise ValueError(f"unknown request fields: {sorted(bad_top)}")
    curve = req.get("curve", False)
    if not isinstance(curve, bool):
        raise ValueError(f"curve must be a bool, got {curve!r}")
    out: Dict[str, Any] = {"backend": req.get("backend", "jax-tpu"),
                           "want_curve": curve}
    for key, cls in _CFG_TYPES.items():
        val = req.get(key)
        if val is None:
            cfg = None
        else:
            known = {f.name for f in dataclasses.fields(cls)}
            bad = set(val) - known
            if bad:
                raise ValueError(f"unknown {key} fields: {sorted(bad)}")
            cfg = cls(**val)
        out[{"proto": "proto", "topology": "tc", "run": "run",
             "fault": "fault", "mesh": "mesh_cfg",
             "log": "log_cfg", "txn": "txn_cfg"}[key]] = cfg
    if out["proto"] is None:
        out["proto"] = ProtocolConfig()
    if out["tc"] is None:
        out["tc"] = TopologyConfig()
    if out["run"] is None:
        out["run"] = RunConfig()
    return out
