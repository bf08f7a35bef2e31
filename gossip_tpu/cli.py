"""Command-line interface: pick a backend, run, sweep, serve.

The reference has no flags, no env vars, no config of any kind — its only
runtime configuration is the ``topology`` message (reference main.go:132-149,
SURVEY.md §5).  This CLI makes every implicit constant explicit and
sweepable, and selects the engine at runtime through the Backend seam
(BASELINE.json north star):

    python -m gossip_tpu run --backend jax-tpu --mode pushpull --n 100000
    python -m gossip_tpu run --backend go-native --mode flood --n 1024 \
        --family ring --curve
    python -m gossip_tpu sweep --scale 0.01          # the 5 BASELINE configs
    python -m gossip_tpu serve --port 50051          # gRPC sidecar
    python -m gossip_tpu maelstrom                   # protocol node on stdio

Output is JSON lines (one report per line) so harnesses can consume it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from gossip_tpu.config import (FaultConfig, MeshConfig, ProtocolConfig,
                               RunConfig, TopologyConfig)


_CACHE_DEFAULT = os.environ.get(
    "GOSSIP_COMPILE_CACHE",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), ".jax_cache"))


def _add_cache_flags(p: argparse.ArgumentParser) -> None:
    """JAX persistent compilation cache (default ON for every jax-driven
    subcommand).  Rationale: the SWIM-1M BASELINE row's wall is ~88%
    XLA compile (127.7 s of 145.5 s, artifacts/baseline_sweep_r04b.jsonl)
    and the r04 ablation (artifacts/swim_compile_ablation_r04.json,
    tools/swim_compile_ablation.py) showed that cost is structural —
    spread across the whole 1M-row program (every component stub is
    within the +-4 s repeat-compile noise; compile scales with n, see
    the artifact's scaling_compile_s_by_n: 28.5 s at 100k -> ~120 s at
    1M) — so the fix is to pay it once per shape EVER, not once per
    process."""
    p.add_argument("--compile-cache", default=_CACHE_DEFAULT, metavar="DIR",
                   help="compilation cache directory (default: "
                        "$GOSSIP_COMPILE_CACHE, else .jax_cache/ in the "
                        "checkout); $JAX_COMPILATION_CACHE_DIR, when "
                        "set, wins over both")
    p.add_argument("--no-compile-cache", action="store_true",
                   help="disable the persistent compilation cache (e.g. "
                        "to measure cold compile_s)")


def _enable_compile_cache(a) -> None:
    """One definition of "the cache is on": utils/compile_cache, which
    places it ($JAX_COMPILATION_CACHE_DIR over --compile-cache).  An
    explicit disable also overrides $JAX_COMPILATION_CACHE_DIR, or the
    documented "honest cold compile" measurement could silently hit
    that cache."""
    if not hasattr(a, "no_compile_cache"):   # subcommand without the flags
        return
    from gossip_tpu.utils import compile_cache
    if a.no_compile_cache or not a.compile_cache:
        compile_cache.enable_persistent(None)
        # the AOT executable store reads GOSSIP_COMPILE_CACHE directly
        # (trace.aot_timed chokepoint) — an explicit disable must shut
        # BOTH layers, or the store serves a warm compile_s that
        # _cache_stamp then records as cold
        os.environ[compile_cache.ENV_VAR] = ""
        return
    # cache anything that took >2 s to compile; below that the disk
    # round-trip costs more than the recompile
    status = compile_cache.enable_persistent(a.compile_cache,
                                             min_compile_time_secs=2.0)
    if not status["persistent"]:   # read-only checkout: uncached
        a.no_compile_cache = True  # keep _cache_stamp honest
        os.environ[compile_cache.ENV_VAR] = ""
        return
    # both layers in one dir: the AOT store lands beside the XLA cache
    a.compile_cache = status["dir"]
    os.environ[compile_cache.ENV_VAR] = status["dir"]


def _cache_stamp(a):
    """What a report row records about the compile cache, so warm-cache
    compile_s can never masquerade as a cold measurement in an artifact."""
    if not hasattr(a, "no_compile_cache") or a.no_compile_cache or \
            not a.compile_cache:
        return None
    return a.compile_cache


def _add_run_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--backend", default="jax-tpu",
                   choices=("jax-tpu", "go-native"))
    p.add_argument("--mode", default="push",
                   choices=("push", "pull", "pushpull", "flood",
                            "antientropy", "swim", "rumor"))
    p.add_argument("--rumor-k", type=int, default=2,
                   help="rumor mongering: remove a rumor after this many "
                        "unnecessary (feedback) or total (blind) pushes")
    p.add_argument("--rumor-variant", default="feedback",
                   choices=("feedback", "blind"))
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--fanout", type=int, default=1)
    p.add_argument("--rumors", type=int, default=1)
    p.add_argument("--period", type=int, default=1,
                   help="anti-entropy exchange period (rounds)")
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--k", type=int, default=4,
                   help="ring/WS neighbors; BA attachment edges")
    p.add_argument("--p", type=float, default=0.01,
                   help="ER edge prob / WS rewire prob")
    p.add_argument("--degree-cap", type=int, default=None)
    p.add_argument("--target", type=float, default=0.99)
    p.add_argument("--max-rounds", type=int, default=256)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--origin", type=int, default=0)
    p.add_argument("--drop", type=float, default=0.0,
                   help="per-message drop probability per round")
    p.add_argument("--death", type=float, default=0.0,
                   help="fraction of nodes statically dead")
    p.add_argument("--devices", type=int, default=1,
                   help="mesh size for node-dim sharding (jax-tpu)")
    p.add_argument("--exchange", default="dense",
                   choices=("dense", "sparse", "halo"),
                   help="cross-shard pattern: dense all_gather (any), "
                        "sparse all_to_all (complete topology, "
                        "pull/antientropy, O(messages)), halo ppermute "
                        "(band-limited topologies, O(band))")
    p.add_argument("--engine", default="auto",
                   choices=("auto", "fused", "xla", "native"),
                   help="round kernel: auto = best eligible (fused Pallas "
                        "on TPU for single-device pull on the complete "
                        "graph — static fault masks and --curve "
                        "included since round 4 — bit-packed XLA "
                        "otherwise); fused "
                        "= force the Pallas kernel (TPU, pull, complete "
                        "graph; <= 32 rumors on one device, rumor planes "
                        "sharded zero-ICI with --devices beyond that); "
                        "xla = force the XLA kernels (the threefry stream "
                        "that matches the sharded paths bitwise); native "
                        "= go-native backend only: force the C++ event "
                        "core and raise the node cap to 1M")
    p.add_argument("--curve", action="store_true",
                   help="include the per-round coverage curve")
    p.add_argument("--parity-check", action="store_true",
                   help="flood only: run the SAME topology through both "
                        "backends (jax-tpu rounds vs go-native hop "
                        "depths — the C++ event core above 20k nodes) "
                        "and report the parity-contract checks: "
                        "curve_gap (~0 on race-free graphs), "
                        "hop_bound_violation (~0 always: races only "
                        "slow the event sim), fixed_point_gap (~0 "
                        "always: identical final coverage) — the "
                        "backend-parity artifact at any n up to 1M")
    p.add_argument("--profile", default=None, metavar="LOGDIR",
                   help="capture a jax.profiler trace of the run into "
                        "LOGDIR (TensorBoard profile plugin / Perfetto)")
    p.add_argument("--checkpoint", default=None, metavar="PATH",
                   help="checkpointed driver (SI single-device, sharded "
                        "packed via --devices, --engine fused planes, "
                        "swim, or rumor — the last two single-device or "
                        "sharded): "
                        "run max_rounds rounds saving an atomic npz every "
                        "--checkpoint-every rounds; with --resume, "
                        "continue a previous run from PATH (bitwise "
                        "continuation incl. the PRNG key); composes with "
                        "--curve/--save-curve (curve persists in the "
                        "checkpoint and resumes seamlessly)")
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--resume", action="store_true",
                   help="load --checkpoint PATH and continue to "
                        "max_rounds total rounds")
    p.add_argument("--plan", default=None, metavar="FILE",
                   help="execute a ScalePlan (from `gossip_tpu plan`) "
                        "through the streamed word-plane tile driver "
                        "instead of the flag-configured run; composes "
                        "with --checkpoint/--resume (the plan carries "
                        "n/rumors/fanout/faults/segments — "
                        "docs/SCALING.md)")
    p.add_argument("--save-curve", default=None, metavar="PATH",
                   help="write the coverage curve as JSONL (implies --curve)")
    p.add_argument("--ensemble", type=int, default=0, metavar="S",
                   help="run S seeds as one vmapped batch and report "
                        "ensemble statistics (jax-tpu; for swim this "
                        "is the detection-latency distribution of one "
                        "failure scenario across seeds; --devices "
                        "shards the SEED axis over a mesh)")
    p.add_argument("--swim-subjects", type=int, default=8)
    p.add_argument("--swim-proxies", type=int, default=3)
    p.add_argument("--swim-suspect-rounds", type=int, default=0,
                   help="0 = use suggested_suspect_rounds(n)")
    p.add_argument("--swim-rotate", action="store_true",
                   help="rotate the subject window over all n nodes "
                        "(full-membership failure detection)")
    p.add_argument("--swim-epoch-rounds", type=int, default=0,
                   help="rounds per rotating-window epoch (0 = auto)")
    p.add_argument("--swim-diss", choices=("scatter", "sort", "pack"),
                   default="sort",
                   help="dissemination reduce lowering (all bitwise-"
                        "identical): 'sort' = sort-by-receiver + "
                        "segment-max (default; 2.2x faster on TPU, "
                        "artifacts/swim_ab_r04.json); 'scatter' = "
                        "duplicate-index scatter-max control; 'pack' = "
                        "sort with the row gather on 8/16-bit packed "
                        "codes — needs --max-rounds to prove its lane "
                        "bound, silently falls back to sort without it")
    p.add_argument("--swim-rng", choices=("split", "packed"),
                   default="split",
                   help="per-round randomness lowering: 'split' = one "
                        "independent threefry chain per quantity (the "
                        "original contract); 'packed' = one key chain + "
                        "one multi-word draw per node, fields split by "
                        "bits (opt-in statistical contract — different "
                        "trajectories, uniform marginals up to a "
                        "documented <= m/2^32 modulo bias, mesh-"
                        "invariant; models/swim.packed_round_draws)")
    p.add_argument("--dead-nodes", nargs="*", type=int, default=None,
                   metavar="ID",
                   help="node ids that fail at --fail-round (swim scenario; "
                        "default: node 1%%S fails at round 2)")
    p.add_argument("--fail-round", type=int, default=0)
    # time-varying nemesis schedule (ChurnConfig -> ops/nemesis,
    # compiled into the round loops; docs/ROBUSTNESS.md)
    p.add_argument("--churn-event", action="append", default=None,
                   metavar="NODE:DIE[:REC]",
                   help="scripted crash/recover churn: NODE dies at round "
                        "DIE and recovers at round REC (omit REC or pass "
                        "-1 for a permanent crash); repeatable")
    p.add_argument("--partition", action="append", default=None,
                   metavar="START:END:CUT",
                   help="network partition window: for rounds [START, END) "
                        "every message crossing node-id CUT is lost; "
                        "repeatable, windows must not overlap")
    p.add_argument("--drop-ramp", default=None, metavar="START:END:P0:P1",
                   help="drop-rate ramp: link drop probability moves "
                        "linearly P0 -> P1 over rounds [START, END), then "
                        "holds P1")


def _parse_churn(a):
    """--churn-event/--partition/--drop-ramp -> ChurnConfig or None.
    Field validation (ranges, overlap) lives in ChurnConfig itself —
    this only parses the colon syntax."""
    def ints(s, what, lens):
        parts = s.split(":")
        if len(parts) not in lens:
            raise ValueError(
                f"--{what} takes {'|'.join(map(str, sorted(lens)))} "
                f"colon-separated fields, got {s!r}")
        return parts

    events = []
    for s in (getattr(a, "churn_event", None) or ()):
        parts = ints(s, "churn-event", {2, 3})
        if len(parts) == 2:
            parts.append("-1")
        events.append(tuple(int(x) for x in parts))
    partitions = []
    for s in (getattr(a, "partition", None) or ()):
        partitions.append(tuple(int(x) for x in ints(s, "partition", {3})))
    ramp = None
    if getattr(a, "drop_ramp", None):
        f = ints(a.drop_ramp, "drop-ramp", {4})
        ramp = (int(f[0]), int(f[1]), float(f[2]), float(f[3]))
    if not (events or partitions or ramp):
        return None
    from gossip_tpu.config import ChurnConfig
    return ChurnConfig(events=tuple(events),
                       partitions=tuple(partitions), ramp=ramp)


def _parse_byz(a):
    """--byz NODE:ROUND:KIND[:ARG] (+ --byz-quorum) -> ByzConfig or
    None.  Field validation (known kinds, one action per node, quorum
    range) lives in ByzConfig itself — this only parses the colon
    syntax, the _parse_churn discipline."""
    specs = getattr(a, "byz", None) or ()
    if not specs:
        return None
    liars = []
    for s in specs:
        p = s.split(":")
        if len(p) not in (3, 4):
            raise ValueError("--byz takes NODE:ROUND:KIND[:ARG] "
                             f"colon-separated fields, got {s!r}")
        liars.append((int(p[0]), int(p[1]), p[2],
                      int(p[3]) if len(p) == 4 else 0))
    from gossip_tpu.config import ByzConfig
    return ByzConfig(liars=tuple(liars),
                     quorum=getattr(a, "byz_quorum", 2))


def _args_to_configs(a):
    t = a.swim_suspect_rounds
    if not t and a.mode == "swim":    # import only when needed: pulls in jax
        from gossip_tpu.models.swim import suggested_suspect_rounds
        t = suggested_suspect_rounds(a.n, a.fanout)
    t = t or 4
    proto = ProtocolConfig(mode=a.mode, fanout=a.fanout, rumors=a.rumors,
                           period=a.period, swim_subjects=a.swim_subjects,
                           swim_proxies=a.swim_proxies,
                           swim_suspect_rounds=t,
                           swim_rotate=a.swim_rotate,
                           swim_epoch_rounds=a.swim_epoch_rounds,
                           swim_diss=a.swim_diss,
                           swim_rng=a.swim_rng,
                           rumor_k=a.rumor_k,
                           rumor_variant=a.rumor_variant)
    tc = TopologyConfig(family=a.family, n=a.n, k=a.k, p=a.p,
                        degree_cap=a.degree_cap, seed=a.seed)
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed, origin=a.origin,
                    engine=getattr(a, "engine", "auto"))
    fault = None
    churn = _parse_churn(a)
    if a.drop > 0 or a.death > 0 or a.dead_nodes or churn is not None:
        fault = FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                            seed=a.seed,
                            dead_nodes=tuple(a.dead_nodes or ()),
                            fail_round=a.fail_round, churn=churn)
    mesh = (MeshConfig(n_devices=a.devices, exchange=a.exchange)
            if a.devices > 1 else None)
    return proto, tc, run, fault, mesh


def cmd_run(a) -> int:
    from gossip_tpu.backend import run_simulation
    from gossip_tpu.utils.trace import trace   # trace(None) is a no-op
    if a.plan:
        # a plan file IS the run configuration (n/mode/rumors/faults/
        # segments all come from it); any run-shape flag changed from
        # its parser default would be silently discarded, so it is
        # refused instead (no-silent-drop policy).  The default map is
        # read from the live parser at registration time
        # (_PLAN_GUARDED_RUN_FLAGS in main), so this check cannot
        # drift from the real defaults.
        changed = [f"--{k.replace('_', '-')}"
                   for k, d in a.plan_guard_defaults.items()
                   if getattr(a, k) != d]
        if a.ensemble > 1 or a.parity_check or a.curve or a.save_curve:
            print("error: --plan executes the streamed scale driver; "
                  "drop --ensemble/--parity-check/--curve/--save-curve",
                  file=sys.stderr)
            return 2
        if changed:
            print("error: --plan takes the run shape from the plan "
                  f"file; drop {' '.join(sorted(changed))} (regenerate "
                  "the plan with `gossip_tpu plan` to change them)",
                  file=sys.stderr)
            return 2
        return _run_plan_file(a.plan, checkpoint=a.checkpoint,
                              resume=a.resume)
    proto, tc, run, fault, mesh = _args_to_configs(a)
    if a.parity_check and a.ensemble > 1:
        # the ensemble branch would otherwise win and silently discard
        # the parity request (no-silent-drop policy)
        print("error: --parity-check and --ensemble are separate run "
              "shapes; pick one", file=sys.stderr)
        return 2
    if a.ensemble > 1:
        if a.backend != "jax-tpu":
            print("error: --ensemble needs the jax-tpu backend",
                  file=sys.stderr)
            return 2
        from gossip_tpu.backend import run_ensemble
        ens_mesh = None
        if a.devices > 1:
            if a.exchange != "dense":
                # the seed-axis mesh has no cross-shard exchange to
                # route; a requested pattern must not be silently
                # dropped (no-silent-drop policy)
                print("error: --ensemble shards the SEED axis; "
                      "--exchange does not apply (drop it)",
                      file=sys.stderr)
                return 2
            # the SEED axis shards over the mesh (embarrassingly
            # parallel, value-invariant; seeds must divide devices)
            from gossip_tpu.parallel.sharded import make_mesh
            ens_mesh = make_mesh(a.devices, axis_name="seed")
        with trace(a.profile):
            # mode dispatch (SI / rumor / swim-scenario) lives in
            # backend.run_ensemble, shared with the sidecar's Ensemble
            # RPC so the two surfaces cannot drift
            # run_ensemble owns the seed default, the engine guard,
            # and the mode dispatch (shared with the Ensemble RPC)
            ens, out_extra = run_ensemble(proto, tc, run, fault,
                                          count=a.ensemble, mesh=ens_mesh)
        out = {"ensemble": ens.summary(), "mode": a.mode, "n": tc.n,
               "backend": a.backend, **out_extra}
        if a.profile:
            out["profile_logdir"] = a.profile
        if a.save_curve:
            # per-round ensemble band: mean / min / max over seeds
            from gossip_tpu.utils.metrics import dump_curve_jsonl
            import numpy as np
            dump_curve_jsonl(a.save_curve, ens.curves.mean(axis=0),
                             meta={**out, "band_min":
                                   np.round(ens.curves.min(axis=0), 6
                                            ).tolist(),
                                   "band_max":
                                   np.round(ens.curves.max(axis=0), 6
                                            ).tolist()})
        if a.curve:
            out["curve_mean"] = [float(c) for c in ens.curves.mean(axis=0)]
        print(json.dumps(out))
        return 0
    if a.parity_check:
        # large-N backend parity spot check (VERDICT r2 item 8): both
        # backends on one explicit topology, gap of the coverage curves
        # on the flood clock mapping (one jax round == one hop depth)
        if a.mode != "flood" or a.backend != "jax-tpu":
            print("error: --parity-check compares the jax-tpu flood "
                  "rounds against go-native hop depths; use --backend "
                  "jax-tpu --mode flood", file=sys.stderr)
            return 2
        if fault is not None:
            print("error: --parity-check needs a fault-free run "
                  "(go-native takes no FaultConfig)", file=sys.stderr)
            return 2
        if a.curve or a.save_curve or a.checkpoint:
            # never silently discard a requested output shape (the
            # repo's incompatible-flag policy)
            print("error: --parity-check is a self-contained artifact "
                  "run; drop --curve/--save-curve/--checkpoint",
                  file=sys.stderr)
            return 2
        import dataclasses as _dc
        from gossip_tpu.backend import _GONATIVE_MAX_NODES
        from gossip_tpu.utils.metrics import curve_gap
        with trace(a.profile):
            rep = run_simulation(a.backend, proto, tc, run, None, mesh,
                                 want_curve=True)
            # the C++ event core above the Python engine's cap
            ref_run = _dc.replace(
                run,
                engine="native" if tc.n > _GONATIVE_MAX_NODES else "auto")
            ref = run_simulation("go-native", proto, tc, ref_run,
                                 want_curve=True)
        if rep.rounds < 0:
            # the event sim always runs to quiescence; a jax run cut off
            # by --max-rounds would report a bogus fixed_point_gap that
            # reads as backend divergence
            print("error: the jax flood run did not reach --target "
                  f"within --max-rounds={run.max_rounds}; raise "
                  "--max-rounds past the graph diameter so the parity "
                  "fixed point is the converged state", file=sys.stderr)
            return 2
        # The parity contract (tests/test_gonative.py): the flood kernel
        # is the exact BFS ball per round; event-order races can only
        # SLOW the event sim's hop curve (never push it above the
        # kernel's), and both backends converge to the identical fixed
        # point.  curve_gap therefore reads ~0 only on race-free
        # graphs (ring k=2); on racy graphs the contract is the bound +
        # the fixed point, reported separately.
        m = min(len(rep.curve), len(ref.curve))
        bound = max((ref.curve[t] - rep.curve[t] for t in range(m)),
                    default=0.0)
        out = {"curve_gap": curve_gap(rep.curve, ref.curve),
               "hop_bound_violation": max(0.0, bound),
               "fixed_point_gap": abs(rep.coverage - ref.coverage),
               "n": tc.n, "family": a.family,
               "compile_cache": _cache_stamp(a),
               "jax": {**rep.to_dict(), "curve": None},
               "gonative": {**ref.to_dict(), "curve": None}}
        if a.profile:
            out["profile_logdir"] = a.profile
        print(json.dumps(out))
        return 0
    if a.resume and not a.checkpoint:
        print("error: --resume needs --checkpoint PATH (the file to "
              "continue from)", file=sys.stderr)
        return 2
    if a.checkpoint:
        with trace(a.profile):
            return _cmd_run_checkpointed(a, proto, tc, run, fault, mesh)
    want_curve = a.curve or bool(a.save_curve)
    with trace(a.profile):
        report = run_simulation(a.backend, proto, tc, run, fault, mesh,
                                want_curve=want_curve)
    out = report.to_dict()
    out["compile_cache"] = _cache_stamp(a)
    if a.profile:
        out["profile_logdir"] = a.profile
    if a.save_curve:
        from gossip_tpu.utils.metrics import dump_curve_jsonl
        meta = dict(out)
        curve = meta.pop("curve")
        dump_curve_jsonl(a.save_curve, curve, meta=meta)
        if not a.curve:          # curve went to the file, not the report
            out["curve"] = None
    print(json.dumps(out))
    return 0


def _cmd_run_checkpointed(a, proto, tc, run, fault, mesh) -> int:
    """--checkpoint driver: fixed-round run in compiled segments with an
    atomic npz every --checkpoint-every rounds; --resume continues a
    saved run to max_rounds TOTAL rounds, bitwise identical to an
    uninterrupted run (tests/test_utils.py, test_checkpoint_sharded.py).

    Five engines (round-4; the reference loses all state on process
    death, main.go:22-26):

    * single device, engine auto/xla  — the SI XLA kernels;
    * --devices > 1, dense exchange   — the node-sharded packed engine
      (pull/antientropy);
    * --engine fused                  — the rumor-plane fused engine
      (any --devices; the checkpoint carries the plane stack);
    * --mode swim                     — failure detection, single-device
      or node-sharded (runtime/simulator.checkpointed_swim; the
      rotating window is in-trace, so resume is bitwise);
    * --mode rumor                    — SIR rumor mongering, single-
      device or node-sharded (models/rumor.checkpointed_rumor; fixed
      segments, no extinction early-exit — the extinct state is
      absorbing).

    --curve/--save-curve compose with all of them: segments run as a
    compiled scan recording per-round coverage (SWIM: detection
    fraction; rumor: coverage + hot-fraction channels, extinction being
    recoverable only from the hot channel), and the curve-so-far is
    persisted in the checkpoint so --resume continues it seamlessly.

    Nemesis fault programs compose too (crash-safety round): each
    engine runs every schedule feature its straight twin honors, the
    checkpoint stamps the fault-program fingerprint + absolute round
    cursor + exact dropped total, and --resume continues the SAME
    program bitwise or refuses loudly (docs/ROBUSTNESS.md "Crash
    safety"; tools/crashloop.py is the live SIGKILL harness)."""
    import os

    n_dev = 1 if mesh is None else mesh.n_devices
    exchange = "dense" if mesh is None else mesh.exchange
    want_curve = a.curve or bool(a.save_curve)
    if a.backend != "jax-tpu":
        print("error: --checkpoint drives the jax-tpu engines only",
              file=sys.stderr)
        return 2
    fused = run.engine == "fused"
    if fused:
        from gossip_tpu.backend import _fused_ineligible_reason
        # plane_stack: the checkpointed fused driver is ALWAYS the
        # plane-sharded engine (make_plane_mesh, any n_dev), which runs
        # churn events as alive-word operands
        reason = _fused_ineligible_reason(proto, tc, fault, n_dev,
                                          plane_stack=True)
        if reason is not None:
            print(f"error: {reason}", file=sys.stderr)
            return 2
    elif n_dev > 1 and a.mode not in ("swim", "rumor"):
        # swim/rumor shard through their own engines; this check guards
        # the packed SI exchange only
        from gossip_tpu.parallel.sharded_packed import (
            sharded_checkpoint_ineligible_reason)
        reason = sharded_checkpoint_ineligible_reason(proto, exchange)
        if reason is not None:
            print(f"error: {reason}", file=sys.stderr)
            return 2
    import dataclasses

    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.topology import generators as G
    from gossip_tpu.utils.checkpoint import load_meta, load_state

    # Config fingerprint stored with every checkpoint: resume refuses
    # mismatched flags instead of silently continuing a DIFFERENT run
    # (the bitwise-continuation promise is per-config; devices is part
    # of it — mesh padding and plane layout depend on the mesh shape).
    fingerprint = {"proto": dataclasses.asdict(proto),
                   "tc": dataclasses.asdict(tc),
                   "fault": None if fault is None
                   else dataclasses.asdict(fault),
                   "seed": run.seed, "origin": run.origin,
                   "devices": n_dev, "exchange": exchange,
                   "engine": "fused" if fused else "xla"}
    # Fault-program fingerprint: a digest of the BUILT nemesis schedule
    # content + the eventual-alive denominator (ops/nemesis
    # .schedule_fingerprint) — semantic, where the config fingerprint
    # above is syntactic.  Resume refuses a missing fingerprint loudly
    # (a checkpoint that cannot prove which schedule produced it — e.g.
    # a pre-crash-safety build's — must not be continued under one);
    # the digest-mismatch branch below is today shadowed by the config
    # fingerprint (churn is inside it) and stands as the semantic
    # backstop should a refactor ever move the schedule out of the
    # syntactic fingerprint.
    fault_fp = NE.schedule_fingerprint(fault, tc.n, run.origin)
    ch = NE.get(fault)
    resumed = False
    resume_state = None
    curve_prefix = ()
    lost_prefix = 0.0
    if a.resume:
        if not os.path.exists(a.checkpoint):
            print(f"error: --resume: no checkpoint at {a.checkpoint}",
                  file=sys.stderr)
            return 2
        try:
            meta = load_meta(a.checkpoint)
        except ValueError as e:
            # corrupt/truncated/foreign file: the module crash contract
            # (utils/checkpoint) turns it into one ValueError naming the
            # file — surface it as a clean CLI error, never a traceback
            print(f"error: --resume: {e}", file=sys.stderr)
            return 2
        saved = meta.get("extra", {}).get("config")
        if saved is not None:
            # pre-round-4 checkpoints lack the devices/exchange/engine
            # keys; they were all written by the single-device XLA
            # driver, so defaulting preserves their resumability
            saved = {"devices": 1, "exchange": "dense", "engine": "xla",
                     **saved}
        if saved is not None and saved != json.loads(
                json.dumps(fingerprint)):
            diff = [k for k in fingerprint
                    if json.loads(json.dumps(fingerprint[k]))
                    != saved.get(k)]
            print("error: --resume config mismatch vs the checkpoint "
                  f"(differs in: {', '.join(diff)}); rerun with the "
                  "flags the checkpoint was written with",
                  file=sys.stderr)
            return 2
        saved_fp = meta.get("extra", {}).get("fault_program")
        if fault_fp is not None and saved_fp is None:
            print("error: --resume under a fault program, but the "
                  "checkpoint carries no fault-program fingerprint (it "
                  "was written without a churn schedule, or by a "
                  "pre-crash-safety build); a resumed run cannot prove "
                  "it continues the SAME schedule — restart without "
                  "--resume or drop the churn flags", file=sys.stderr)
            return 2
        if saved_fp is not None and fault_fp is None:
            print("error: the checkpoint was written under a fault "
                  "program but this resume scripts none; rerun with "
                  "the churn flags the checkpoint was written with",
                  file=sys.stderr)
            return 2
        if fault_fp is not None and saved_fp != fault_fp:
            print("error: --resume fault-program mismatch vs the "
                  "checkpoint (schedule digest "
                  f"{saved_fp[:12]}... != {fault_fp[:12]}...); a "
                  "different churn/partition/ramp program would fork "
                  "the trajectory — rerun with the schedule the "
                  "checkpoint was written with", file=sys.stderr)
            return 2
        lost_prefix = float(meta.get("extra", {}).get("dropped", 0.0))
        saved_curve = meta.get("extra", {}).get("curve")
        # curve history must match the request, both ways — a silently
        # truncated or silently dropped curve is worse than an error
        # (the repo's incompatible-flag policy)
        if want_curve and saved_curve is None:
            print("error: --resume with --curve/--save-curve, but the "
                  "checkpoint has no curve history (it was written "
                  "without curve capture); drop the curve flags or "
                  "restart without --resume", file=sys.stderr)
            return 2
        if saved_curve is not None and not want_curve:
            print("error: the checkpoint carries a curve history; add "
                  "--curve or --save-curve to continue it (refusing to "
                  "silently drop it)", file=sys.stderr)
            return 2
        # rumor checkpoints carry named channels (dict of lists); the
        # scalar engines carry one flat list
        curve_prefix = (saved_curve if isinstance(saved_curve, dict)
                        else tuple(saved_curve or ()))
        try:
            resume_state = load_state(a.checkpoint)
        except ValueError as e:
            # meta parsed but the arrays are torn/missing (module crash
            # contract): same clean refusal as the load_meta path above
            print(f"error: --resume: {e}", file=sys.stderr)
            return 2
        resumed = True

    extra = {"config": fingerprint}
    if fault_fp is not None:
        extra["fault_program"] = fault_fp
    out_extra = {}
    if a.mode == "swim":
        from gossip_tpu.backend import swim_scenario
        from gossip_tpu.runtime.simulator import checkpointed_swim
        dead, fail_round, default_scenario = swim_scenario(proto, tc.n,
                                                           fault)
        swim_topo = None if tc.family == "complete" else G.build(tc)
        mesh_obj = None
        if n_dev > 1:
            from gossip_tpu.parallel.sharded import make_mesh
            mesh_obj = make_mesh(n_dev)
        final, cov, curve = checkpointed_swim(
            proto, tc.n, run, a.checkpoint, every=a.checkpoint_every,
            dead_nodes=dead, fail_round=fail_round, fault=fault,
            topo=swim_topo, mesh=mesh_obj, resume_state=resume_state,
            want_curve=want_curve, curve_prefix=curve_prefix,
            extra_meta=extra)
        out_extra["metric"] = "detection_fraction"
        out_extra["default_scenario"] = default_scenario
        if proto.swim_rotate and curve:
            # rotation: the window can leave the dead node's epoch, so
            # the headline is the best in-window detection (exact only
            # with curve capture; without it only the final is known)
            out_extra["peak_detection"] = float(max(curve))
        engine_label = "swim-sharded" if n_dev > 1 else "swim-xla"
    elif a.mode == "rumor":
        import numpy as _np

        from gossip_tpu.models.rumor import checkpointed_rumor
        mesh_obj = None
        if n_dev > 1:
            from gossip_tpu.parallel.sharded import make_mesh
            mesh_obj = make_mesh(n_dev)
        final, cov, residue, curve = checkpointed_rumor(
            proto, G.build(tc), run, a.checkpoint,
            every=a.checkpoint_every, fault=fault, mesh=mesh_obj,
            resume_state=resume_state, want_curve=want_curve,
            curve_prefix=curve_prefix, extra_meta=extra,
            lost_prefix=lost_prefix)
        out_extra["residue"] = residue
        out_extra["extinct"] = not bool(_np.any(_np.asarray(final.hot)))
        if curve:
            dead_at = _np.nonzero(_np.asarray(curve["hot"]) == 0.0)[0]
            out_extra["extinction_round"] = (int(dead_at[0]) + 1
                                             if len(dead_at) else -1)
        engine_label = "rumor-sharded" if n_dev > 1 else "rumor-xla"
    elif fused:
        from gossip_tpu.parallel.sharded_fused import (
            checkpointed_fused_planes, make_plane_mesh)
        final, cov, curve = checkpointed_fused_planes(
            tc.n, proto.rumors, run, make_plane_mesh(n_dev), a.checkpoint,
            every=a.checkpoint_every, fanout=proto.fanout,
            resume_state=resume_state, want_curve=want_curve,
            curve_prefix=curve_prefix, extra_meta=extra, fault=fault)
        engine_label = "fused-pallas-planes"
    elif n_dev > 1:
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_packed import (
            checkpointed_packed_sharded)
        final, cov, curve = checkpointed_packed_sharded(
            proto, G.build(tc), run, make_mesh(n_dev), a.checkpoint,
            every=a.checkpoint_every, fault=fault,
            resume_state=resume_state, want_curve=want_curve,
            curve_prefix=curve_prefix, extra_meta=extra,
            lost_prefix=lost_prefix)
        engine_label = "sharded-packed"
    else:
        from gossip_tpu.models.si import coverage, make_si_round
        from gossip_tpu.models.state import init_state
        from gossip_tpu.utils.checkpoint import run_with_checkpoints
        topo = G.build(tc)
        # churn runs in the segments exactly as in the straight driver:
        # the step indexes its ABSOLUTE state.round, which the
        # checkpoint persists, so resume == straight run bitwise under
        # the fault program (utils/checkpoint crash contract); the
        # metric denominator is the eventual alive set (metric_alive
        # falls back to the static mask without churn)
        step, tables = make_si_round(proto, topo, fault, run.origin,
                                     tabled=True)
        state = resume_state if resumed else init_state(run, proto, tc.n)
        curve_fn = None
        if want_curve:
            def curve_fn(s):
                return coverage(s.seen, NE.metric_alive(fault, tc.n,
                                                        run.origin))
        remaining = max(0, run.max_rounds - int(state.round))
        out_state = run_with_checkpoints(step, state, remaining,
                                         a.checkpoint,
                                         every=a.checkpoint_every,
                                         step_args=tables,
                                         curve_fn=curve_fn,
                                         curve_prefix=curve_prefix,
                                         extra_meta=extra,
                                         track_lost=ch is not None,
                                         lost_prefix=lost_prefix)
        final, curve = (out_state if want_curve else (out_state, None))
        cov = float(coverage(final.seen,
                             NE.metric_alive(fault, tc.n, run.origin)))
        engine_label = "si-xla"
    out = {"backend": a.backend, "mode": a.mode, "n": tc.n,
           "rounds": int(final.round), "coverage": cov,
           "msgs": float(final.msgs), "checkpoint": a.checkpoint,
           "checkpoint_every": a.checkpoint_every, "resumed": resumed,
           "engine": engine_label, "devices": n_dev,
           "compile_cache": _cache_stamp(a)}
    if ch is not None:
        # the nemesis observables of the run as persisted: the exact
        # destroyed-message total accumulated across every segment AND
        # every kill/resume (engines that track it — run_with_checkpoints
        # track_lost), and the fault-program fingerprint the checkpoint
        # refuses mismatched resumes against
        final_meta = load_meta(a.checkpoint).get("extra", {})
        if "dropped" in final_meta:
            out["dropped"] = final_meta["dropped"]
        out["fault_program"] = fault_fp
    out.update(out_extra)
    if a.profile:
        out["profile_logdir"] = a.profile
    # rumor curves carry named channels; the headline curve is coverage
    # (the hot channel rides alongside under its own key — in the
    # save-curve artifact's meta line too, because extinction is only
    # recoverable from it and a silently dropped channel violates the
    # curve-history policy above)
    curve_list = curve["coverage"] if isinstance(curve, dict) else curve
    if a.save_curve:
        from gossip_tpu.utils.metrics import dump_curve_jsonl
        save_meta = dict(out)
        if isinstance(curve, dict):
            save_meta["hot_curve"] = list(curve["hot"])
        dump_curve_jsonl(a.save_curve, list(curve_list), meta=save_meta)
    if a.curve:
        out["curve"] = list(curve_list)
        if isinstance(curve, dict):
            out["hot_curve"] = list(curve["hot"])
    print(json.dumps(out))
    return 0


# The five BASELINE.json benchmark configs, scalable for CPU smoke runs.
def baseline_configs(scale: float, devices: int):
    def sn(n):                       # scaled node count
        return max(64, int(n * scale))
    n2 = sn(10_000)
    n3 = sn(100_000)
    n4 = sn(1_000_000)
    n5 = sn(10_000_000)
    return [
        dict(name="push-complete-64-goref", backend="jax-tpu",
             proto=ProtocolConfig(mode="push", fanout=1),
             tc=TopologyConfig(family="complete", n=64),
             run=RunConfig(max_rounds=64), compare_gonative=True),
        dict(name="pushpull-er-10k", backend="jax-tpu",
             proto=ProtocolConfig(mode="pushpull", fanout=1),
             tc=TopologyConfig(family="erdos_renyi", n=n2,
                               p=min(1.0, 0.01 * 10_000 / n2)),
             run=RunConfig(max_rounds=64)),
        dict(name="antientropy-ws-100k", backend="jax-tpu",
             proto=ProtocolConfig(mode="antientropy", fanout=1, period=2),
             tc=TopologyConfig(family="watts_strogatz", n=n3, k=6, p=0.1),
             run=RunConfig(max_rounds=256)),
        dict(name="swim-powerlaw-1m", backend="jax-tpu",
             proto=ProtocolConfig(mode="swim", fanout=2, swim_proxies=3,
                                  swim_subjects=8, swim_suspect_rounds=24),
             tc=TopologyConfig(family="power_law", n=n4, k=3,
                               degree_cap=256),
             run=RunConfig(max_rounds=80)),
        # BASELINE.json configs[4]: "10M-node multi-rumor broadcast,
        # node-dim sharded".  Mode pull: on a multi-chip mesh the node
        # dimension shards across devices; on one chip engine='auto'
        # routes to the fused Pallas multi-rumor kernel.  revision=2
        # records the round-2 mode change (pushpull -> pull) so old and
        # new sweep artifacts are machine-distinguishable (ADVICE r2).
        dict(name="multirumor-10m-sharded", backend="jax-tpu",
             proto=ProtocolConfig(mode="pull", fanout=1, rumors=8),
             tc=TopologyConfig(family="complete", n=n5),
             run=RunConfig(max_rounds=64),
             mesh=MeshConfig(n_devices=devices), revision=2),
    ]


def cmd_sweep(a) -> int:
    from gossip_tpu.backend import run_simulation
    import jax
    devices = a.devices or len(jax.devices())
    configs = baseline_configs(a.scale, devices)
    if a.only:
        configs = [c for c in configs if c["name"] in a.only]
    if a.swim_diss:
        import dataclasses as _dc
        configs = [dict(cfg, proto=_dc.replace(cfg["proto"],
                                               swim_diss=a.swim_diss))
                   if cfg["proto"].mode == "swim" else cfg
                   for cfg in configs]
    import time as _time
    for cfg in configs:
        t0_row = _time.perf_counter()
        report = run_simulation(cfg["backend"], cfg["proto"], cfg["tc"],
                                cfg["run"], None, cfg.get("mesh"),
                                want_curve=a.curve)
        out = report.to_dict()
        out["config"] = cfg["name"]
        # bump a config's revision whenever its workload definition
        # changes so sweep artifacts from different definitions can never
        # be compared as if they measured the same thing
        out["config_revision"] = cfg.get("revision", 1)
        # same principle for timings: a warm-cache compile_s must be
        # distinguishable from a cold one in the artifact itself
        out["compile_cache"] = _cache_stamp(a)
        if cfg.get("compare_gonative"):
            ref = run_simulation("go-native",
                                 ProtocolConfig(mode="flood"), cfg["tc"],
                                 cfg["run"], want_curve=a.curve)
            out["gonative_ref"] = ref.to_dict()
        # row-level reconciliation (VERDICT r4 task 5): the ROW wall is
        # everything this config cost — engine wall + topo build + the
        # go-native reference run + residual host overhead — so
        # row_wall_s ~= wall_s + meta.topo_build_s +
        # gonative_ref.wall_s + row_overhead_s by construction, and the
        # r04 table's ~10 s of unattributed first-row time can never
        # recur unexplained
        row_wall = _time.perf_counter() - t0_row
        parts = (out["wall_s"]
                 + (out.get("meta") or {}).get("topo_build_s", 0.0)
                 + (out.get("gonative_ref") or {}).get("wall_s", 0.0))
        out["row_wall_s"] = round(row_wall, 4)
        out["row_overhead_s"] = round(max(0.0, row_wall - parts), 4)
        print(json.dumps(out), flush=True)
    return 0


def cmd_grid(a) -> int:
    """Batched config sweep: the cartesian product of --modes/--fanouts/
    --drops/--periods/--seeds — and, with --families, topology families —
    runs as ONE compiled XLA program (the north-star "sweep fanout, mode,
    and graph topology across a pod" sentence —
    parallel/sweep.config_sweep_curves).  --devices shards the config axis
    over a mesh; --pod-mesh S N runs the full 2-D (configs x node-shards)
    shard_map program, families included."""
    from gossip_tpu.parallel.sweep import (SweepPoint, config_sweep_curves,
                                           config_sweep_curves_2d)
    from gossip_tpu.topology import generators as G
    if any(r < 1 for r in a.rumors):
        # 0 is SweepPoint's internal batch-default sentinel; letting it
        # through would run 1 rumor while the summary prints 0
        print("error: --rumors values must be >= 1", file=sys.stderr)
        return 2
    families = a.families or [a.family]
    ns = a.ns or [a.n]
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed)
    fault = (FaultConfig(node_death_rate=a.death, seed=a.seed)
             if a.death > 0 else None)
    # the topology stack enumerates (family, n) pairs; topo_idx t maps
    # back as family t // len(ns), size t % len(ns)
    fam_n = [(f, n) for f in families for n in ns]
    points = [
        SweepPoint(mode=m, fanout=f, drop_prob=d,
                   period=(p if m == "antientropy" else 1), seed=s,
                   topo_idx=t, rumors=r)
        for t in range(len(fam_n))
        for m in a.modes for f in a.fanouts for d in a.drops
        for p in (a.periods if 'antientropy' in a.modes else [1])
        for s in a.seeds for r in a.rumors]
    # periods multiply only anti-entropy points; dedupe the rest
    points = list(dict.fromkeys(points))
    topos = [G.build(TopologyConfig(family=f, n=n, k=a.k, p=a.p,
                                    degree_cap=a.degree_cap, seed=a.seed))
             for f, n in fam_n]
    topo_arg = topos if len(topos) > 1 else topos[0]
    if a.pod_mesh:
        # DCN-aware: configs (communication-free) ride the outer/slice
        # axis, node shards (O(N) collectives) stay intra-slice on ICI.
        from gossip_tpu.parallel.multislice import make_hybrid_mesh
        s, nd = a.pod_mesh
        mesh2d = make_hybrid_mesh(s, nd, axis_names=("sweep", "nodes"))
        res = config_sweep_curves_2d(points, topo_arg, run, mesh2d,
                                     fault=fault)
    elif a.devices > 1:
        from gossip_tpu.parallel.sharded import make_mesh
        res = config_sweep_curves(points, topo_arg, run, fault=fault,
                                  mesh=make_mesh(a.devices,
                                                 axis_name="sweep"))
    else:
        # single-device grids partition by mode bucket so pure buckets
        # never pay the masked other half (falls through to the plain
        # batch when the grid is single-bucket)
        from gossip_tpu.parallel.sweep import config_sweep_curves_partitioned
        res = config_sweep_curves_partitioned(points, topo_arg, run,
                                              fault=fault)
    for i, summary in enumerate(res.summaries()):
        fam, n = fam_n[points[i].topo_idx]
        summary["n"] = n
        summary["family"] = fam
        if a.curve:
            summary["curve"] = [float(c) for c in res.curves[i]]
        print(json.dumps(summary), flush=True)
    return 0


def _parse_scenario(spec: str):
    """One ``--scenario`` spec -> ChurnConfig: ';'-separated
    ``event=NODE:DIE[:REC]`` / ``partition=START:END:CUT`` /
    ``ramp=START:END:P0:P1`` items (the colon syntax of the run
    command's --churn-event/--partition/--drop-ramp, reused via
    _parse_churn so the two surfaces cannot drift)."""
    events, partitions, ramp = [], [], None
    for item in filter(None, (s.strip() for s in spec.split(";"))):
        key, _, val = item.partition("=")
        if key == "event":
            events.append(val)
        elif key == "partition":
            partitions.append(val)
        elif key == "ramp":
            if ramp is not None:
                raise ValueError(
                    f"scenario {spec!r} has more than one ramp")
            ramp = val
        else:
            raise ValueError(
                f"unknown scenario field {key!r} in {spec!r} "
                "(use event= / partition= / ramp=)")
    ch = _parse_churn(argparse.Namespace(
        churn_event=events or None, partition=partitions or None,
        drop_ramp=ramp))
    if ch is None:
        raise ValueError(f"scenario {spec!r} scripts no faults")
    return ch


def cmd_churn_sweep(a) -> int:
    """K nemesis scenarios — distinct churn/partition/drop-ramp fault
    programs over ONE protocol config — for the cost of ONE compile.
    --engine xla (default): the schedule stack rides ONE compiled
    vmapped loop as a runtime operand (parallel/sweep
    .churn_sweep_curves); per-scenario trajectories are bitwise the
    solo ``run`` command's, and --devices shards the scenario axis.
    --engine fused: the plane-sharded fused Pallas engine runs the K
    scenarios serially through ONE memoized compiled loop — schedule
    content (alive words, partition cut masks, the 20-bit drop
    threshold) is all runtime operands since the fused-operand PR, so
    scenarios 1..K-1 re-enter scenario 0's executable
    (parallel/sweep.fused_churn_sweep_curves); --devices shards the
    rumor-plane axis and per-scenario trajectories are bitwise the
    solo fused curve driver's."""
    from gossip_tpu.topology import generators as G
    scens = [_parse_scenario(s) for s in a.scenario]
    proto = ProtocolConfig(mode=a.mode, fanout=a.fanout, rumors=a.rumors,
                           period=a.period)
    tc = TopologyConfig(family=a.family, n=a.n, k=a.k, p=a.p,
                        seed=a.seed)
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed)
    faults = [FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                          seed=a.seed, churn=ch) for ch in scens]
    if a.engine == "fused":
        from gossip_tpu.backend import _fused_ineligible_reason
        from gossip_tpu.parallel.sharded_fused import make_plane_mesh
        from gossip_tpu.parallel.sweep import fused_churn_sweep_curves
        reason = _fused_ineligible_reason(proto, tc, faults[0],
                                          a.devices, plane_stack=True)
        if reason is not None:
            print(f"error: {reason}", file=sys.stderr)
            return 2
        res = fused_churn_sweep_curves(
            tc.n, proto.rumors, run, faults,
            make_plane_mesh(a.devices), fanout=proto.fanout)
    else:
        from gossip_tpu.parallel.sweep import churn_sweep_curves
        mesh = None
        if a.devices > 1:
            if len(faults) % a.devices:
                print(f"error: {len(faults)} scenarios do not divide "
                      f"over {a.devices} devices", file=sys.stderr)
                return 2
            from gossip_tpu.parallel.sharded import make_mesh
            mesh = make_mesh(a.devices, axis_name="scenario")
        res = churn_sweep_curves(proto, G.build(tc), run, faults,
                                 mesh=mesh)
    out = {"churn_sweep": res.summaries(), "n": tc.n, "mode": a.mode,
           "engine": a.engine,
           "scenarios": len(faults), "target": run.target_coverage}
    if a.curve:
        out["curves"] = [[round(float(c), 6) for c in row]
                         for row in res.curves]
    print(json.dumps(out))
    return 0


def _parse_crdt_injections(a):
    """--add NODE:ROUND:AMOUNT / --set-add ELEM:ROUND / --set-remove
    ELEM:ROUND -> CrdtConfig kwargs (field validation lives in
    CrdtConfig itself — this only parses the colon syntax, the
    _parse_churn discipline)."""
    def parts(s, what, arity):
        p = s.split(":")
        if len(p) != arity:
            raise ValueError(f"--{what} takes {arity} colon-separated "
                             f"fields, got {s!r}")
        return tuple(int(x) for x in p)

    return dict(
        adds=tuple(parts(s, "add", 3) for s in (a.add or ())),
        set_adds=tuple(parts(s, "set-add", 2)
                       for s in (a.set_add or ())),
        set_removes=tuple(parts(s, "set-remove", 2)
                          for s in (a.set_remove or ())))


def cmd_crdt(a) -> int:
    """CRDT gossip run: a commutative-merge payload (Gossip Glomers
    counter/set workloads) on the pull exchange fabric, value
    convergence judged integer-exact against the ground-truth merge on
    the eventual-alive set (docs/WORKLOADS.md)."""
    from gossip_tpu.config import CrdtConfig
    from gossip_tpu.topology import generators as G
    cfg = CrdtConfig(kind=a.type, elements=a.elements,
                     **_parse_crdt_injections(a))
    proto = ProtocolConfig(mode="pull", fanout=a.fanout)
    tc = TopologyConfig(family=a.family, n=a.n, k=a.k, p=a.p,
                        seed=a.seed)
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed, origin=a.origin)
    churn = _parse_churn(a)
    byz = _parse_byz(a)
    fault = None
    if (a.drop > 0 or a.death > 0 or churn is not None
            or byz is not None):
        fault = FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                            seed=a.seed, churn=churn, byz=byz)
    topo = G.build(tc)
    want_curve = a.curve or bool(a.save_curve)
    import time as _time
    t0 = _time.perf_counter()
    if a.devices > 1:
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_crdt import (
            simulate_curve_crdt_sharded, simulate_until_crdt_sharded)
        mesh = make_mesh(a.devices)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_crdt_sharded(
                cfg, proto, topo, run, mesh, fault, defend=a.defend)
        else:
            rounds, vc, msgs_f, final, truth = (
                simulate_until_crdt_sharded(cfg, proto, topo, run,
                                            mesh, fault,
                                            defend=a.defend))
        engine = "crdt-sharded"
    else:
        from gossip_tpu.models.crdt import (simulate_curve_crdt,
                                            simulate_until_crdt)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_crdt(
                cfg, proto, topo, run, fault, defend=a.defend)
        else:
            rounds, vc, msgs_f, final, truth = simulate_until_crdt(
                cfg, proto, topo, run, fault, defend=a.defend)
        engine = "crdt-xla"
    wall = _time.perf_counter() - t0
    if want_curve:
        hit = [i for i, c in enumerate(conv) if c >= a.target]
        rounds = (hit[0] + 1) if hit else -1
        vc, msgs_f = float(conv[-1]), float(msgs[-1])
    out = {"backend": "jax-tpu", "mode": "crdt", "type": a.type,
           "n": a.n, "rounds": rounds, "value_conv": vc,
           "converged": vc >= a.target, "truth_value": truth,
           "msgs": msgs_f, "wall_s": round(wall, 4),
           "devices": a.devices, "engine": engine,
           "compile_cache": _cache_stamp(a)}
    if churn is not None:
        out["fault_program"] = True
    if byz is not None:
        out["byz_program"] = True
        out["defended"] = bool(a.defend)
    if a.save_curve:
        from gossip_tpu.utils.metrics import dump_curve_jsonl
        dump_curve_jsonl(a.save_curve, [float(c) for c in conv],
                         meta=dict(out))
    if a.curve:
        out["curve"] = [float(c) for c in conv]
    print(json.dumps(out))
    return 0


def _parse_log_injections(a):
    """--send NODE:KEY:ROUND:VALUE / --commit NODE:KEY:ROUND:UPTO ->
    LogConfig kwargs (field validation lives in LogConfig itself —
    the _parse_crdt_injections discipline)."""
    def parts(s, what):
        p = s.split(":")
        if len(p) != 4:
            raise ValueError(f"--{what} takes 4 colon-separated "
                             f"fields, got {s!r}")
        return tuple(int(x) for x in p)

    return dict(
        sends=tuple(parts(s, "send") for s in (a.send or ())),
        commits=tuple(parts(s, "commit") for s in (a.commit or ())))


def cmd_log(a) -> int:
    """Replicated kafka-style log run: ordered per-key offset payloads
    on the pull exchange fabric, convergence judged integer-exact
    against the acked-appends ground truth on the eventual-alive set
    (docs/WORKLOADS.md "Replicated logs")."""
    from gossip_tpu.config import LogConfig
    from gossip_tpu.topology import generators as G
    cfg = LogConfig(keys=a.keys, capacity=a.capacity,
                    **_parse_log_injections(a))
    proto = ProtocolConfig(mode="pull", fanout=a.fanout)
    tc = TopologyConfig(family=a.family, n=a.n, k=a.k, p=a.p,
                        seed=a.seed)
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed, origin=a.origin)
    churn = _parse_churn(a)
    fault = None
    if a.drop > 0 or a.death > 0 or churn is not None:
        fault = FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                            seed=a.seed, churn=churn)
    topo = G.build(tc)
    want_curve = a.curve or bool(a.save_curve)
    import time as _time
    t0 = _time.perf_counter()
    if a.devices > 1:
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_log import (
            simulate_curve_log_sharded, simulate_until_log_sharded)
        mesh = make_mesh(a.devices)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_log_sharded(
                cfg, proto, topo, run, mesh, fault)
        else:
            rounds, lc, msgs_f, final, truth = (
                simulate_until_log_sharded(cfg, proto, topo, run,
                                           mesh, fault))
        engine = "log-sharded"
    else:
        from gossip_tpu.models.log import (simulate_curve_log,
                                           simulate_until_log)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_log(
                cfg, proto, topo, run, fault)
        else:
            rounds, lc, msgs_f, final, truth = simulate_until_log(
                cfg, proto, topo, run, fault)
        engine = "log-xla"
    wall = _time.perf_counter() - t0
    if want_curve:
        hit = [i for i, c in enumerate(conv) if c >= a.target]
        rounds = (hit[0] + 1) if hit else -1
        lc, msgs_f = float(conv[-1]), float(msgs[-1])
    out = {"backend": "jax-tpu", "mode": "log", "n": a.n,
           "keys": a.keys, "capacity": a.capacity, "rounds": rounds,
           "log_conv": lc, "converged": lc >= a.target,
           "truth": truth, "msgs": msgs_f, "wall_s": round(wall, 4),
           "devices": a.devices, "engine": engine,
           "compile_cache": _cache_stamp(a)}
    if churn is not None:
        out["fault_program"] = True
    if a.save_curve:
        from gossip_tpu.utils.metrics import dump_curve_jsonl
        dump_curve_jsonl(a.save_curve, [float(c) for c in conv],
                         meta=dict(out))
    if a.curve:
        out["curve"] = [float(c) for c in conv]
    print(json.dumps(out))
    return 0


def _parse_txn_writes(a):
    """--write NODE:KEY:ROUND:VALUE -> TxnConfig kwargs (field
    validation lives in TxnConfig itself — the _parse_log_injections
    discipline)."""
    def parts(s):
        p = s.split(":")
        if len(p) != 4:
            raise ValueError("--write takes 4 colon-separated fields, "
                             f"got {s!r}")
        return tuple(int(x) for x in p)

    return dict(writes=tuple(parts(s) for s in (a.write or ())))


def cmd_txn(a) -> int:
    """LWW-register transaction run: totally-available multi-key
    writes on the pull exchange fabric, convergence judged
    integer-exact against the acked-writes LWW ground truth on the
    eventual-alive set (docs/WORKLOADS.md "Transactions")."""
    from gossip_tpu.config import TxnConfig
    from gossip_tpu.topology import generators as G
    cfg = TxnConfig(keys=a.keys, txns=a.txns, zipf_alpha=a.zipf_alpha,
                    hot_key=a.hot_key, load=a.load,
                    spread_rounds=a.spread, **_parse_txn_writes(a))
    proto = ProtocolConfig(mode="pull", fanout=a.fanout)
    tc = TopologyConfig(family=a.family, n=a.n, k=a.k, p=a.p,
                        seed=a.seed)
    run = RunConfig(target_coverage=a.target, max_rounds=a.max_rounds,
                    seed=a.seed, origin=a.origin)
    churn = _parse_churn(a)
    byz = _parse_byz(a)
    fault = None
    if (a.drop > 0 or a.death > 0 or churn is not None
            or byz is not None):
        fault = FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                            seed=a.seed, churn=churn, byz=byz)
    topo = G.build(tc)
    want_curve = a.curve or bool(a.save_curve)
    import time as _time
    t0 = _time.perf_counter()
    if a.devices > 1:
        from gossip_tpu.parallel.sharded import make_mesh
        from gossip_tpu.parallel.sharded_register import (
            simulate_curve_txn_sharded, simulate_until_txn_sharded)
        mesh = make_mesh(a.devices)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_txn_sharded(
                cfg, proto, topo, run, mesh, fault, defend=a.defend)
        else:
            rounds, tcv, msgs_f, final, truth = (
                simulate_until_txn_sharded(cfg, proto, topo, run,
                                           mesh, fault,
                                           defend=a.defend))
        engine = "txn-sharded"
    else:
        from gossip_tpu.models.register import (simulate_curve_txn,
                                                simulate_until_txn)
        if want_curve:
            conv, msgs, final, truth = simulate_curve_txn(
                cfg, proto, topo, run, fault, defend=a.defend)
        else:
            rounds, tcv, msgs_f, final, truth = simulate_until_txn(
                cfg, proto, topo, run, fault, defend=a.defend)
        engine = "txn-xla"
    wall = _time.perf_counter() - t0
    if want_curve:
        hit = [i for i, c in enumerate(conv) if c >= a.target]
        rounds = (hit[0] + 1) if hit else -1
        tcv, msgs_f = float(conv[-1]), float(msgs[-1])
    out = {"backend": "jax-tpu", "mode": "txn", "n": a.n,
           "keys": a.keys, "rounds": rounds, "txn_conv": tcv,
           "converged": tcv >= a.target, "truth": truth,
           "msgs": msgs_f, "wall_s": round(wall, 4),
           "devices": a.devices, "engine": engine,
           "zipf_alpha": a.zipf_alpha, "hot_key": a.hot_key,
           "load": a.load, "compile_cache": _cache_stamp(a)}
    if churn is not None:
        out["fault_program"] = True
    if byz is not None:
        out["byz_program"] = True
        out["defended"] = bool(a.defend)
    if a.save_curve:
        from gossip_tpu.utils.metrics import dump_curve_jsonl
        dump_curve_jsonl(a.save_curve, [float(c) for c in conv],
                         meta=dict(out))
    if a.curve:
        out["curve"] = [float(c) for c in conv]
    print(json.dumps(out))
    return 0


def cmd_serve(a) -> int:
    from gossip_tpu.config import ServingConfig
    from gossip_tpu.rpc.sidecar import serve
    from gossip_tpu.utils import telemetry
    # the replica's flight recorder: GOSSIP_TELEMETRY in the child env
    # (tools/trace_capture.py points every replica at ONE shared file —
    # the multi-writer torn-line contract) or the NullLedger; without
    # this activation a replica's batch/request_trace events would
    # vanish and no cross-ledger waterfall could ever join
    telemetry.activate(telemetry.from_env(argv=sys.argv))
    batching = None
    if not a.no_batching:
        try:
            batching = ServingConfig(tick_ms=a.batch_tick_ms,
                                     max_batch=a.batch_max,
                                     max_queue=a.batch_queue,
                                     devices=a.devices,
                                     coordinator=a.coordinator,
                                     num_processes=a.num_processes,
                                     process_id=a.process_id)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    try:
        server, port = serve(a.port, a.workers, batching=batching)
    except ValueError as e:
        # the mesh refusal (fewer devices than --devices) must be a
        # clean CLI error, not a traceback — the fleet's spawn gate
        # reads the child's stderr tail
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"serving": True, "port": port,
                      "batching": batching is not None,
                      "devices": (batching.devices
                                  if batching is not None else 1)}),
          flush=True)
    server.wait_for_termination()
    return 0


def cmd_route(a) -> int:
    """Spawn N sidecar replicas and front them with the health-gated
    failover router (rpc/router, docs/SERVING.md "Fleet")."""
    from gossip_tpu.config import FleetConfig
    from gossip_tpu.rpc.router import Fleet, fleet_env
    try:
        cfg = FleetConfig(replicas=a.replicas,
                          probe_interval_ms=a.probe_interval_ms,
                          down_after=a.down_after, up_after=a.up_after,
                          max_inflight=a.max_inflight,
                          devices_per_replica=a.devices_per_replica)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    replica_argv = []
    if a.no_batching:
        if cfg.devices_per_replica > 1:
            print("error: --devices-per-replica needs batching "
                  "replicas (the mesh shards the admission megabatch); "
                  "drop --no-batching", file=sys.stderr)
            return 2
        replica_argv.append("--no-batching")
    if cfg.devices_per_replica > 1:
        # BOTH halves of the mesh contract: the child's ServingConfig
        # width (--devices) AND the host-device-count env (fleet_env
        # devices=) — either alone silently degrades, which the
        # post-spawn serving_devices gate then refuses
        replica_argv += ["--devices", str(cfg.devices_per_replica)]
    fleet = Fleet(cfg=cfg, port=a.port, max_workers=a.workers,
                  replica_argv=replica_argv,
                  env=fleet_env(platform=a.replica_platform,
                                devices=cfg.devices_per_replica))
    try:
        if not fleet.router.wait_healthy(a.replicas, timeout_s=60):
            # a fleet that never admitted all replicas must not print
            # a success-looking status line and serve only sheds
            print(f"error: only {fleet.router.healthy_count()}/"
                  f"{a.replicas} replicas admitted within 60s (see "
                  f"the replica logs under {fleet.workdir})",
                  file=sys.stderr)
            return 1
        print(json.dumps({
            "routing": True, "port": fleet.port,
            "replicas": [r.address for r in fleet.router.replicas],
            "healthy": fleet.router.healthy_count()}), flush=True)
        fleet.server.wait_for_termination()
    except KeyboardInterrupt:
        pass
    finally:
        fleet.close()
    return 0


def _fleet_degraded(m: dict) -> List[str]:
    """Degradation reasons from one Metrics reply (empty = healthy).
    One definition for the CLI exit code, the --json document, and the
    --out artifact — fleet-status cannot disagree with itself."""
    reasons = []
    if m.get("router"):
        if m.get("healthy", 0) < m.get("replicas", 0):
            reasons.append(f"{m.get('healthy', 0)}/"
                           f"{m.get('replicas', 0)} replicas healthy")
        for row in m.get("fleet", ()):
            if not row.get("healthy"):
                reasons.append(f"replica {row.get('replica')} "
                               f"{(row.get('state') or 'down')}")
            elif "error" in row:
                reasons.append(f"replica {row.get('replica')} metrics "
                               f"unreachable: {row['error']}")
    elif not m.get("ok"):
        reasons.append("replica reports not ok")
    return reasons


def _render_fleet_status(m: dict) -> str:
    """The human fleet table (one poll).  A router reply renders the
    fleet; a bare replica reply renders its own window."""
    if not m.get("router"):
        w = m.get("window", {})
        line = (f"replica | rps {w.get('rps', 0)} "
                f"p50 {w.get('p50_ms', 0)}ms p99 {w.get('p99_ms', 0)}ms"
                f" | inflight {m.get('inflight', 0)} compiles "
                f"{m.get('compiles_total')} (+{m.get('compiles_delta')})"
                f" devices {m.get('serving_devices')}")
        lc = m.get("last_compile")
        if lc:
            line += (f" | last compile {lc.get('label')} "
                     f"[{lc.get('cache')}]")
        return line
    w = m.get("window", {})
    c = m.get("counters", {})
    lines = [f"fleet {m.get('healthy', 0)}/{m.get('replicas', 0)} "
             f"healthy | rps {w.get('rps', 0)} p50 {w.get('p50_ms', 0)}"
             f"ms p99 {w.get('p99_ms', 0)}ms | dispatched "
             f"{c.get('dispatched', 0)} failovers "
             f"{c.get('failovers', 0)} sheds {c.get('sheds', 0)}"]
    for row in m.get("fleet", ()):
        state = "up" if row.get("healthy") \
            else (row.get("state") or "down").upper()
        line = (f"  r{row.get('replica')} {row.get('address', ''):<21}"
                f" {state:<5} epoch {row.get('epoch')} "
                f"inflight {row.get('inflight')}")
        rm = row.get("metrics")
        if rm:
            rw = rm.get("window", {})
            line += (f" | rps {rw.get('rps', 0)} "
                     f"p50 {rw.get('p50_ms', 0)}ms "
                     f"p99 {rw.get('p99_ms', 0)}ms | compiles "
                     f"{rm.get('compiles_total')} "
                     f"(+{rm.get('compiles_delta')}) devices "
                     f"{rm.get('serving_devices')}")
            lc = rm.get("last_compile")
            if lc:
                line += (f" | last compile {lc.get('label')} "
                         f"[{lc.get('cache')}]")
        elif "error" in row:
            line += f" | error: {row['error']}"
        lines.append(line)
    return "\n".join(lines)


def cmd_fleet_status(a) -> int:
    """Live fleet health over the Metrics RPC (docs/OBSERVABILITY.md
    "Live fleet metrics").  Exit codes: 0 = every replica healthy and
    reporting, 1 = degraded (a down replica, an unreachable metrics
    leaf, or healthy < replicas), 2 = the target itself unreachable —
    a rollout gate can `fleet-status && proceed` directly."""
    import time as _time

    import grpc

    from gossip_tpu.rpc.sidecar import SidecarClient
    from gossip_tpu.utils import telemetry
    client = SidecarClient(a.address, max_attempts=1)
    rc = 2
    try:
        while True:
            try:
                m = client.metrics(timeout=a.timeout_s)
            except (grpc.RpcError, ValueError) as e:
                code = e.code() if callable(getattr(e, "code", None)) \
                    else None
                print(f"error: {a.address} unreachable "
                      f"({code or type(e).__name__})", file=sys.stderr)
                rc = 2
                m = None
            if m is not None:
                reasons = _fleet_degraded(m)
                rc = 1 if reasons else 0
                if a.as_json:
                    print(json.dumps({"degraded": bool(reasons),
                                      "reasons": reasons,
                                      "metrics": m}), flush=True)
                else:
                    print(_render_fleet_status(m), flush=True)
                    for reason in reasons:
                        print(f"  DEGRADED: {reason}", flush=True)
                if a.out:
                    # *fleet_status* artifacts are provenance-required
                    # (tools/validate_artifacts.py, never grandfathered)
                    with open(a.out, "w") as f:
                        json.dump({"provenance": telemetry.provenance(),
                                   "degraded": bool(reasons),
                                   "reasons": reasons, "metrics": m},
                                  f, indent=1)
            if not a.watch:
                return rc
            _time.sleep(a.interval_s)
    except KeyboardInterrupt:
        return rc
    finally:
        client.close()


def _device_spec_from_flags(a):
    from gossip_tpu.planner.budget import DeviceSpec
    return DeviceSpec(
        chips=a.chips,
        hbm_bytes_per_chip=int(a.hbm_gb * 1024**3),
        slices=a.slices,
        host_ram_bytes=int(a.host_ram_gb * 1024**3))


def _plan_fault_from_flags(a):
    ch = _parse_scenario(a.scenario) if a.scenario else None
    if ch is None and a.death == 0.0 and a.drop == 0.0:
        return None
    return FaultConfig(node_death_rate=a.death, drop_prob=a.drop,
                       seed=a.fault_seed, churn=ch)


def cmd_plan(a) -> int:
    """Capacity planning without a device: print (or validate) a
    ScalePlan as JSON — what word-plane tiling / segment schedule /
    mesh shape fits N on the given topology, or a LOUD refusal naming
    the binding constraint (planner/budget, docs/SCALING.md).  Pure
    host arithmetic; needs no chip."""
    from gossip_tpu.planner import budget as PB
    if a.validate:
        try:
            with open(a.validate) as f:
                doc = json.load(f)
            plan = PB.plan_from_dict(doc)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"plan_valid": True, "n": plan.n,
                          "tiles": plan.tiles,
                          "bucket_words": plan.bucket_words,
                          "fingerprint": PB.plan_fingerprint(
                              plan.to_dict())}))
        return 0
    try:
        fault = _plan_fault_from_flags(a)
        reserve = (PB.DEFAULT_RESERVE_FRAC if a.reserve is None
                   else a.reserve)
        plan = PB.plan_scale(
            a.n, rumors=a.rumors, device=_device_spec_from_flags(a),
            engine=a.engine, fanout=a.fanout, max_rounds=a.max_rounds,
            seed=a.seed, origin=a.origin, fault=fault,
            segment_every=a.segment_every, reserve_frac=reserve)
    except PB.InfeasiblePlanError as e:
        # the refusal IS the product here: one line, constraint named
        print(f"error: {e}", file=sys.stderr)
        return 2
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    text = plan.to_json()
    if a.out:
        with open(a.out, "w") as f:
            f.write(text + "\n")
        print(json.dumps({"plan_written": a.out, "n": plan.n,
                          "tiles": plan.tiles,
                          "bucket_words": plan.bucket_words,
                          "predicted_peak_device_bytes":
                          plan.predicted_peak_device_bytes,
                          "binding": plan.binding}))
    else:
        print(text)
    return 0


def _run_plan_file(path: str, *, checkpoint=None, resume=False,
                   check_bitwise=False, measure_memory=False,
                   overlap=True) -> int:
    """Load a plan file and execute it through the streamed driver —
    shared by ``scale-run`` and ``run --plan`` so the two surfaces
    cannot drift."""
    from gossip_tpu.planner import budget as PB
    from gossip_tpu.planner.stream import run_at_scale
    try:
        with open(path) as f:
            doc = json.load(f)
        plan = PB.plan_from_dict(doc)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if resume and not checkpoint:
        print("error: --resume needs --checkpoint PATH",
              file=sys.stderr)
        return 2
    try:
        res = run_at_scale(plan, checkpoint_path=checkpoint,
                           resume=resume, check_bitwise=check_bitwise,
                           measure_memory=measure_memory,
                           overlap=overlap)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    out = res.to_dict()
    out["plan_fingerprint"] = PB.plan_fingerprint(plan.to_dict())
    print(json.dumps(out))
    if check_bitwise and res.bitwise_equal is not True:
        return 1
    return 0


def cmd_scale_run(a) -> int:
    """Execute a ScalePlan: stream word-plane tiles through the packed
    engine per checkpoint segment (planner/stream, docs/SCALING.md)."""
    return _run_plan_file(a.plan, checkpoint=a.checkpoint,
                          resume=a.resume,
                          check_bitwise=a.check_bitwise,
                          measure_memory=a.measure_memory,
                          overlap=not a.no_overlap)


def cmd_staticcheck(a) -> int:
    """AST invariant analyzer over the repo's own source (pure stdlib
    — never initializes jax, so it needs no chip):
    recompile-hazard lint for the serving/sweep paths, lock discipline
    for rpc/, convention gates, and the suppression-baseline
    discipline (docs/STATIC_ANALYSIS.md)."""
    from gossip_tpu.analysis import runner
    argv = []
    if a.root is not None:
        argv += ["--root", a.root]
    if a.baseline is not None:
        argv += ["--baseline", a.baseline]
    if a.ledger:
        argv += ["--ledger", a.ledger]
    if a.json_summary:
        argv += ["--json"]
    return runner.main(argv)


def cmd_maelstrom(a) -> int:
    from gossip_tpu.runtime.maelstrom_node import main as node_main
    node_main(["--gossip-interval", str(a.gossip_interval),
               "--workload", a.workload])
    return 0


def _node_argv(gossip_interval: float, workload: str = "broadcast"):
    """Node command for the harnesses; None keeps their default (the
    immediate-relay broadcast node) so the reference-shaped path stays
    the default."""
    if gossip_interval <= 0 and workload == "broadcast":
        return None
    argv = [sys.executable, "-u", "-m",
            "gossip_tpu.runtime.maelstrom_node",
            "--workload", workload]
    if gossip_interval > 0:
        argv += ["--gossip-interval", str(gossip_interval)]
    return argv


def cmd_maelstrom_check(a) -> int:
    argv = _node_argv(a.gossip_interval, a.workload)
    if a.workload == "kafka":
        if a.router == "native":
            print("error: the kafka workload runs on the python "
                  "router (the C++ router speaks the broadcast "
                  "envelope set only)", file=sys.stderr)
            return 2
        import asyncio

        from gossip_tpu.runtime.maelstrom_harness import (
            run_kafka_workload)
        stats = asyncio.run(run_kafka_workload(
            a.n, a.ops, rate=a.rate, latency=a.latency,
            topology=a.topology, partition_mid=a.partition, seed=a.seed,
            argv=argv))
    elif a.workload == "txn":
        if a.router == "native":
            print("error: the txn workload runs on the python "
                  "router (the C++ router speaks the broadcast "
                  "envelope set only)", file=sys.stderr)
            return 2
        import asyncio

        from gossip_tpu.runtime.maelstrom_harness import (
            run_txn_workload)
        stats = asyncio.run(run_txn_workload(
            a.n, a.ops, rate=a.rate, latency=a.latency,
            topology=a.topology, partition_mid=a.partition, seed=a.seed,
            argv=argv))
    elif a.workload == "counter":
        if a.router == "native":
            print("error: the counter workload runs on the python "
                  "router (the C++ router speaks the broadcast "
                  "envelope set only)", file=sys.stderr)
            return 2
        import asyncio

        from gossip_tpu.runtime.maelstrom_harness import (
            run_counter_workload)
        stats = asyncio.run(run_counter_workload(
            a.n, a.ops, rate=a.rate, latency=a.latency,
            topology=a.topology, partition_mid=a.partition, seed=a.seed,
            argv=argv))
    elif a.router == "native":
        from gossip_tpu.runtime.native_router import run_native_workload
        stats = run_native_workload(
            a.n, a.ops, rate=a.rate, latency=a.latency,
            topology=a.topology, partition_mid=a.partition, seed=a.seed,
            argv=argv)
    else:
        import asyncio

        from gossip_tpu.runtime.maelstrom_harness import (
            run_broadcast_workload)
        stats = asyncio.run(run_broadcast_workload(
            a.n, a.ops, rate=a.rate, latency=a.latency,
            topology=a.topology, partition_mid=a.partition, seed=a.seed,
            argv=argv))
    stats["workload"] = a.workload
    stats["gossip_interval"] = a.gossip_interval
    ok = stats["invariant_ok"]
    if a.assert_msgs_per_op is not None:
        # Glomers-style efficiency gate: the report carries the target
        # and the verdict, and the exit code enforces it
        stats["msgs_per_op_target"] = a.assert_msgs_per_op
        stats["msgs_per_op_ok"] = (stats["msgs_per_op"]
                                   <= a.assert_msgs_per_op)
        ok = ok and stats["msgs_per_op_ok"]
    if a.assert_latency_ms is not None:
        stats["op_latency_target_ms"] = a.assert_latency_ms
        stats["op_latency_ok"] = (stats["op_latency_ms"]["max"]
                                  <= a.assert_latency_ms)
        ok = ok and stats["op_latency_ok"]
    print(json.dumps(stats))
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="gossip_tpu",
        description="TPU-native gossip simulation framework")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("run", help="run one simulation")
    _add_run_flags(p)
    _add_cache_flags(p)
    # Flags that COMPOSE with --plan (everything else is run-shape the
    # plan file carries, and cmd_run refuses it when changed from its
    # default — no-silent-drop).  The guarded set is EVERY other run
    # flag, derived from the live parser's own defaults via
    # parse_args([]), so a future _add_run_flags addition is guarded
    # automatically instead of silently discarded; the four
    # output-shape flags get their own earlier refusal message.
    _PLAN_COMPOSABLE_FLAGS = {
        "plan", "checkpoint", "resume", "compile_cache",
        "no_compile_cache", "ensemble", "parity_check", "curve",
        "save_curve"}
    _run_defaults = {k: v for k, v in vars(p.parse_args([])).items()
                     if k not in _PLAN_COMPOSABLE_FLAGS}
    p.set_defaults(fn=cmd_run, plan_guard_defaults=_run_defaults)

    p = sub.add_parser("sweep", help="run the 5 BASELINE benchmark configs")
    p.add_argument("--scale", type=float, default=1.0,
                   help="node-count scale factor (CPU smoke: 0.01)")
    p.add_argument("--devices", type=int, default=0,
                   help="mesh size for the sharded config (0 = all)")
    p.add_argument("--only", nargs="*", default=None,
                   help="subset of config names")
    p.add_argument("--curve", action="store_true")
    p.add_argument("--swim-diss", choices=("scatter", "sort", "pack"),
                   default=None,
                   help="override the SWIM config's dissemination "
                        "lowering (bitwise-identical trajectories; lets "
                        "the hardware capture re-measure the SWIM row "
                        "under an A/B-arbitrated winner without a code "
                        "change — tools/hw_refresh.py)")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_sweep)

    p = sub.add_parser("grid", help="batched config sweep: cartesian "
                       "product of modes/fanouts/drops/seeds in ONE "
                       "compiled program")
    p.add_argument("--modes", nargs="+", default=["push", "pull", "pushpull"],
                   choices=("push", "pull", "pushpull", "antientropy"))
    p.add_argument("--fanouts", nargs="+", type=int, default=[1, 2])
    p.add_argument("--drops", nargs="+", type=float, default=[0.0])
    p.add_argument("--periods", nargs="+", type=int, default=[2],
                   help="anti-entropy cadences (ignored for other modes)")
    p.add_argument("--seeds", nargs="+", type=int, default=[0])
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--ns", nargs="+", type=int, default=None,
                   help="sweep MULTIPLE graph sizes in the same program "
                        "(overrides --n; smaller graphs pad with inert "
                        "phantom rows — or, on the implicit complete "
                        "graph, bound each point's partner draw by its "
                        "own traced n — and each point's coverage uses "
                        "its own n)")
    p.add_argument("--rumors", nargs="+", type=int, default=[1],
                   help="rumor counts to sweep; multiple values batch "
                        "into the same program (the rumor axis pads to "
                        "the max with inert all-false phantom columns, "
                        "masked out of each point's coverage; 1-D grids "
                        "only — the pod mesh takes one value)")
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--families", nargs="+", default=None,
                   choices=("ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"),
                   help="sweep MULTIPLE same-n explicit families as one "
                        "stacked table operand (overrides --family; the "
                        "implicit complete graph has no table to stack)")
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--p", type=float, default=0.01)
    p.add_argument("--degree-cap", type=int, default=None)
    p.add_argument("--target", type=float, default=0.99)
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--death", type=float, default=0.0)
    p.add_argument("--curve", action="store_true")
    p.add_argument("--devices", type=int, default=1,
                   help="shard the config axis over this many devices")
    p.add_argument("--pod-mesh", nargs=2, type=int, default=None,
                   metavar=("SWEEP", "NODES"),
                   help="2-D mesh: configs sharded over SWEEP devices, "
                        "each config's nodes over NODES devices")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_grid)

    p = sub.add_parser("churn-sweep",
                       help="run K nemesis scenarios (churn/partition/"
                            "drop-ramp fault programs) through ONE "
                            "compiled loop and report per-scenario "
                            "convergence + exact dropped totals")
    p.add_argument("--scenario", action="append", required=True,
                   metavar="SPEC",
                   help="one fault program: ';'-separated "
                        "event=NODE:DIE[:REC] / partition=START:END:CUT "
                        "/ ramp=START:END:P0:P1 items; repeat the flag "
                        "per scenario")
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--p", type=float, default=0.01)
    p.add_argument("--mode", default="pushpull",
                   choices=("push", "pull", "pushpull", "flood",
                            "antientropy"))
    p.add_argument("--fanout", type=int, default=2)
    p.add_argument("--rumors", type=int, default=1)
    p.add_argument("--period", type=int, default=1)
    p.add_argument("--target", type=float, default=0.99)
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--drop", type=float, default=0.0,
                   help="base link drop probability (the drop table "
                        "outside any ramp; may differ per run, not per "
                        "scenario)")
    p.add_argument("--death", type=float, default=0.0,
                   help="static death rate (shared by every scenario — "
                        "the one compiled step bakes the static mask)")
    p.add_argument("--curve", action="store_true")
    p.add_argument("--devices", type=int, default=1,
                   help="shard the scenario axis (xla) or the "
                        "rumor-plane axis (fused) over this many "
                        "devices")
    p.add_argument("--engine", default="xla", choices=("xla", "fused"),
                   help="xla: K scenarios as ONE vmapped program; "
                        "fused: the plane-sharded Pallas engine, K "
                        "scenarios re-entering ONE memoized compiled "
                        "loop (--mode pull, complete family, TPU)")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_churn_sweep)

    p = sub.add_parser("crdt",
                       help="run a commutative-merge CRDT payload "
                            "(Gossip Glomers counter/set workloads) on "
                            "the pull exchange fabric with optional "
                            "nemesis fault programs; value convergence "
                            "is integer-exact against the ground-truth "
                            "merge on the eventual-alive set")
    p.add_argument("--type", default="gcounter",
                   choices=("gcounter", "pncounter", "gset", "orset"),
                   help="payload kind (ops/crdt.py): grow-only / PN "
                        "counter shards (merge = per-column max) or "
                        "packed set bit-planes (merge = OR)")
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--fanout", type=int, default=2)
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--p", type=float, default=0.01)
    p.add_argument("--target", type=float, default=1.0,
                   help="value-convergence target (default 1.0: EVERY "
                        "eventual-alive node equals the ground truth "
                        "exactly — the Gossip Glomers invariant)")
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--origin", type=int, default=0,
                   help="set-element owner rotation origin (element e "
                        "injects at node (origin + e) %% n)")
    p.add_argument("--devices", type=int, default=1,
                   help="node-dim mesh size (sharded pull exchange)")
    p.add_argument("--drop", type=float, default=0.0)
    p.add_argument("--death", type=float, default=0.0)
    p.add_argument("--add", action="append", default=None,
                   metavar="NODE:ROUND:AMOUNT",
                   help="scripted counter add (repeatable; negative "
                        "amounts decrement a pncounter; default "
                        "program: node j adds 1 + j%%7 at round 0)")
    p.add_argument("--set-add", action="append", default=None,
                   metavar="ELEM:ROUND",
                   help="scripted set add at the element's owner node "
                        "(repeatable; default: every element at "
                        "round 0)")
    p.add_argument("--set-remove", action="append", default=None,
                   metavar="ELEM:ROUND",
                   help="scripted orset remove (tombstone; repeatable)")
    p.add_argument("--elements", type=int, default=64,
                   help="set element universe size E (packed to "
                        "ceil(E/32) uint32 words per plane)")
    p.add_argument("--churn-event", action="append", default=None,
                   metavar="NODE:DIE[:REC]",
                   help="nemesis crash/recover churn (the run "
                        "command's syntax; repeatable)")
    p.add_argument("--partition", action="append", default=None,
                   metavar="START:END:CUT",
                   help="nemesis partition window (repeatable)")
    p.add_argument("--drop-ramp", default=None,
                   metavar="START:END:P0:P1",
                   help="nemesis drop-rate ramp")
    p.add_argument("--byz", action="append", default=None,
                   metavar="NODE:ROUND:KIND[:ARG]",
                   help="scripted byzantine liar: from ROUND on, NODE "
                        "serves forged state of KIND (corrupt | replay "
                        "| equivocate | inflate), ARG the kind-specific "
                        "payload knob; repeatable, one action per node "
                        "(docs/ROBUSTNESS.md \"Byzantine adversaries\")")
    p.add_argument("--byz-quorum", type=int, default=2,
                   help="independent-witness count q for defended set "
                        "bit admission (1-3; needs fanout >= q)")
    p.add_argument("--defend", action="store_true",
                   help="enable the array-form defenses (owner-column "
                        "guards, monotonicity clamps, quorum echo); "
                        "off = the undefended control arm")
    p.add_argument("--curve", action="store_true",
                   help="include the per-round value-convergence curve")
    p.add_argument("--save-curve", default=None, metavar="PATH",
                   help="write the value-convergence curve as JSONL")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_crdt)

    p = sub.add_parser("log",
                       help="run a replicated kafka-style log "
                            "(ordered per-key offset payloads with "
                            "committed offsets) on the pull exchange "
                            "fabric with optional nemesis fault "
                            "programs; convergence is integer-exact "
                            "against the acked-appends ground truth "
                            "on the eventual-alive set")
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--keys", type=int, default=4,
                   help="number of per-key logs K (ops/logs.py)")
    p.add_argument("--capacity", type=int, default=16,
                   help="ring slots per key C (at most C sends per "
                        "key — a wrap would alias offsets and is "
                        "rejected loudly)")
    p.add_argument("--fanout", type=int, default=2)
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--p", type=float, default=0.01)
    p.add_argument("--target", type=float, default=1.0,
                   help="log-convergence target (default 1.0: EVERY "
                        "eventual-alive node holds the exact acked "
                        "log + committed offsets — the Gossip "
                        "Glomers invariant)")
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--origin", type=int, default=0)
    p.add_argument("--devices", type=int, default=1,
                   help="node-dim mesh size (sharded pull exchange)")
    p.add_argument("--drop", type=float, default=0.0)
    p.add_argument("--death", type=float, default=0.0)
    p.add_argument("--send", action="append", default=None,
                   metavar="NODE:KEY:ROUND:VALUE",
                   help="scripted append (repeatable; values >= 1; "
                        "per-key rounds must be nondecreasing — "
                        "offset order is time order; default "
                        "program: 4 sends per key, rounds 0-3)")
    p.add_argument("--commit", action="append", default=None,
                   metavar="NODE:KEY:ROUND:UPTO",
                   help="scripted commit (repeatable; commits "
                        "min(upto, acked_len) — clamped to the "
                        "eventually-acked log length; default: one "
                        "commit per key at round 4)")
    p.add_argument("--churn-event", action="append", default=None,
                   metavar="NODE:DIE[:REC]",
                   help="nemesis crash/recover churn (repeatable)")
    p.add_argument("--partition", action="append", default=None,
                   metavar="START:END:CUT",
                   help="nemesis partition window (repeatable)")
    p.add_argument("--drop-ramp", default=None,
                   metavar="START:END:P0:P1",
                   help="nemesis drop-rate ramp")
    p.add_argument("--curve", action="store_true",
                   help="include the per-round log-convergence curve")
    p.add_argument("--save-curve", default=None, metavar="PATH",
                   help="write the log-convergence curve as JSONL")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_log)

    p = sub.add_parser("txn",
                       help="run totally-available transactions over "
                            "LWW registers (the Maelstrom "
                            "txn-rw-register shape) on the pull "
                            "exchange fabric with optional nemesis "
                            "fault programs; convergence is "
                            "integer-exact against the acked-writes "
                            "LWW ground truth on the eventual-alive "
                            "set")
    p.add_argument("--n", type=int, default=1024)
    p.add_argument("--keys", type=int, default=8,
                   help="register universe K (ops/registers.py)")
    p.add_argument("--txns", type=int, default=16,
                   help="default-program write count T (the skewed "
                        "closed-form traffic generator)")
    p.add_argument("--zipf-alpha", type=float, default=1.1,
                   help="key-popularity skew (> 0; 1.0 = classic "
                        "zipf, larger = more skewed)")
    p.add_argument("--hot-key", type=float, default=0.0,
                   help="hot-key storm: probability mass redirected "
                        "onto key 0 during the middle third of the "
                        "write program")
    p.add_argument("--load", default="uniform",
                   choices=("uniform", "diurnal"),
                   help="writes-over-rounds shape: uniform, or "
                        "diurnal (1 + sin density, one peak "
                        "mid-window)")
    p.add_argument("--spread", type=int, default=8,
                   help="rounds the default write program spans")
    p.add_argument("--fanout", type=int, default=2)
    p.add_argument("--family", default="complete",
                   choices=("complete", "ring", "grid", "erdos_renyi",
                            "watts_strogatz", "power_law"))
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--p", type=float, default=0.01)
    p.add_argument("--target", type=float, default=1.0,
                   help="txn-convergence target (default 1.0: EVERY "
                        "eventual-alive node holds the exact LWW "
                        "winner + timestamp per key — the "
                        "total-availability convergence invariant)")
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--origin", type=int, default=0)
    p.add_argument("--devices", type=int, default=1,
                   help="node-dim mesh size (sharded pull exchange)")
    p.add_argument("--drop", type=float, default=0.0)
    p.add_argument("--death", type=float, default=0.0)
    p.add_argument("--write", action="append", default=None,
                   metavar="NODE:KEY:ROUND:VALUE",
                   help="scripted write micro-op (repeatable; values "
                        ">= 1; at most one write per (key, round, "
                        "node) — the unique-timestamp contract; "
                        "overrides the skewed default program)")
    p.add_argument("--churn-event", action="append", default=None,
                   metavar="NODE:DIE[:REC]",
                   help="nemesis crash/recover churn (repeatable)")
    p.add_argument("--partition", action="append", default=None,
                   metavar="START:END:CUT",
                   help="nemesis partition window (repeatable)")
    p.add_argument("--drop-ramp", default=None,
                   metavar="START:END:P0:P1",
                   help="nemesis drop-rate ramp")
    p.add_argument("--byz", action="append", default=None,
                   metavar="NODE:ROUND:KIND[:ARG]",
                   help="scripted byzantine liar: from ROUND on, NODE "
                        "serves forged register state of KIND (corrupt "
                        "| replay | equivocate | inflate), ARG the "
                        "kind-specific payload knob; repeatable "
                        "(docs/ROBUSTNESS.md \"Byzantine adversaries\")")
    p.add_argument("--byz-quorum", type=int, default=2,
                   help="independent-witness count q (register defense "
                        "is owner-provenance, q applies to set planes)")
    p.add_argument("--defend", action="store_true",
                   help="enable the array-form defenses (owner-"
                        "provenance admission); off = the undefended "
                        "control arm")
    p.add_argument("--curve", action="store_true",
                   help="include the per-round txn-convergence curve")
    p.add_argument("--save-curve", default=None, metavar="PATH",
                   help="write the txn-convergence curve as JSONL")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_txn)

    p = sub.add_parser("serve", help="start the gRPC sidecar")
    p.add_argument("--port", type=int, default=50051)
    p.add_argument("--workers", type=int, default=16)
    p.add_argument("--no-batching", action="store_true",
                   help="disable the admission-batching serving layer "
                        "(per-request solo dispatch, the pre-serving "
                        "behavior)")
    p.add_argument("--batch-tick-ms", type=float, default=20.0,
                   help="admission collector cadence (docs/SERVING.md)")
    p.add_argument("--batch-max", type=int, default=64,
                   help="per-tick per-key megabatch lane cap")
    p.add_argument("--batch-queue", type=int, default=256,
                   help="backpressure cap: admissions past this depth "
                        "get RESOURCE_EXHAUSTED")
    p.add_argument("--devices", type=int, default=1,
                   help="megabatch mesh width (power of two): shard "
                        "each tick's megabatch over the first K JAX "
                        "devices; refuses at startup when the process "
                        "has fewer (docs/SERVING.md \"Mesh-sharded "
                        "replicas\")")
    p.add_argument("--coordinator", default=None,
                   metavar="HOST:PORT",
                   help="jax.distributed coordinator address when one "
                        "logical replica spans processes")
    p.add_argument("--num-processes", type=int, default=1,
                   help="process count of the jax.distributed "
                        "topology (1 = the degenerate single-process "
                        "case, no initialization)")
    p.add_argument("--process-id", type=int, default=0,
                   help="this process's rank in [0, num-processes)")
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("route",
                       help="front N sidecar replicas with the "
                            "health-gated failover router "
                            "(docs/SERVING.md \"Fleet\")")
    p.add_argument("--replicas", type=int, default=2,
                   help="sidecar replica processes to spawn")
    p.add_argument("--port", type=int, default=50051,
                   help="router port (replicas pick free ports)")
    p.add_argument("--workers", type=int, default=16)
    p.add_argument("--probe-interval-ms", type=float, default=250.0,
                   help="health-probe cadence per replica")
    p.add_argument("--down-after", type=int, default=2,
                   help="consecutive probe failures before a replica "
                        "leaves rotation")
    p.add_argument("--up-after", type=int, default=3,
                   help="consecutive healthy probes before a downed "
                        "replica re-enters rotation (flap hysteresis)")
    p.add_argument("--max-inflight", type=int, default=8,
                   help="per-replica in-flight cap; past it the "
                        "router sheds with RESOURCE_EXHAUSTED")
    p.add_argument("--no-batching", action="store_true",
                   help="disable admission batching in the replicas")
    p.add_argument("--devices-per-replica", type=int, default=1,
                   help="megabatch mesh width per replica (power of "
                        "two): children get XLA_FLAGS=--xla_force_"
                        "host_platform_device_count=K and serve "
                        "--devices K; the fleet refuses loudly if a "
                        "child reports fewer serving devices")
    p.add_argument("--replica-platform", default=None,
                   help="JAX_PLATFORMS pin for replica children "
                        "(default: the ambient platform).  A chip "
                        "belongs to one process, so --replicas > 1 "
                        "refuses unless the replicas run on cpu")
    p.set_defaults(fn=cmd_route)

    p = sub.add_parser(
        "fleet-status",
        help="live fleet metrics table over the Metrics RPC; exits "
             "nonzero on a degraded replica (docs/OBSERVABILITY.md "
             "\"Live fleet metrics\")")
    p.add_argument("address", metavar="HOST:PORT",
                   help="router address (renders the whole fleet) or "
                        "a single replica address (renders its window)")
    p.add_argument("--watch", action="store_true",
                   help="re-render every --interval seconds until ^C "
                        "(exit code reflects the LAST poll)")
    p.add_argument("--interval", dest="interval_s", type=float,
                   default=2.0, help="--watch poll cadence, seconds")
    p.add_argument("--timeout", dest="timeout_s", type=float,
                   default=10.0, help="per-poll Metrics RPC timeout")
    p.add_argument("--json", dest="as_json", action="store_true",
                   help="one JSON document per poll instead of the "
                        "table")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="also write the latest poll as a provenance-"
                        "stamped fleet_status JSON artifact")
    p.set_defaults(fn=cmd_fleet_status)

    p = sub.add_parser(
        "plan",
        help="HBM budget model: what word-plane tiling fits N on this "
             "topology? (prints a ScalePlan as JSON, or refuses "
             "naming the binding constraint; pure host arithmetic — "
             "docs/SCALING.md)")
    p.add_argument("--n", type=int, default=100_000_000,
                   help="target node count")
    p.add_argument("--rumors", type=int, default=64)
    p.add_argument("--fanout", type=int, default=1)
    p.add_argument("--engine", default="packed",
                   choices=("packed", "dense", "fused"),
                   help="engine byte model (only 'packed' is "
                        "executable by scale-run)")
    p.add_argument("--max-rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--origin", type=int, default=0)
    p.add_argument("--chips", type=int, default=1,
                   help="total chip count")
    p.add_argument("--hbm-gb", type=float, default=16.0,
                   help="HBM per chip (GiB); fractional values allowed "
                        "(the dry-run family plans against artificial "
                        "budgets)")
    p.add_argument("--slices", type=int, default=1,
                   help="DCN slices (chips/slices = the ICI inner "
                        "axis; >1 emits the hybrid mesh)")
    p.add_argument("--host-ram-gb", type=float, default=64.0)
    p.add_argument("--segment-every", type=int, default=None,
                   help="checkpoint segment length in rounds")
    p.add_argument("--reserve", type=float, default=None,
                   help="HBM fraction held back from the plan "
                        "(default: planner/budget"
                        ".DEFAULT_RESERVE_FRAC, 0.08)")
    p.add_argument("--death", type=float, default=0.0)
    p.add_argument("--drop", type=float, default=0.0)
    p.add_argument("--fault-seed", type=int, default=0)
    p.add_argument("--scenario", default=None,
                   help="fault program spec, the churn-sweep syntax: "
                        "'event=N:D[:R];partition=S:E:C;ramp=S:E:P0:P1'")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the plan JSON here instead of stdout")
    p.add_argument("--validate", default=None, metavar="FILE",
                   help="validate an existing plan file instead of "
                        "planning")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser(
        "scale-run",
        help="execute a ScalePlan: stream word-plane tiles through "
             "the packed engine per checkpoint segment "
             "(docs/SCALING.md)")
    p.add_argument("--plan", required=True, metavar="FILE",
                   help="plan JSON from `gossip_tpu plan`")
    p.add_argument("--checkpoint", default=None, metavar="PATH",
                   help="publish an atomic npz checkpoint per segment")
    p.add_argument("--resume", action="store_true",
                   help="continue from --checkpoint (refuses a "
                        "mismatched plan or fault-program fingerprint)")
    p.add_argument("--check-bitwise", action="store_true",
                   help="also run the untiled in-memory reference and "
                        "gate byte equality (exit 1 on mismatch)")
    p.add_argument("--measure-memory", action="store_true",
                   help="AOT memory analysis of the tile loop "
                        "(one extra compile)")
    p.add_argument("--no-overlap", action="store_true",
                   help="drain each tile synchronously instead of "
                        "running the three-stage fetch pipeline — the "
                        "serial A/B leg for overlap capture "
                        "(trajectories are bitwise identical either "
                        "way; docs/SCALING.md)")
    # the same cache + multi-host init the equivalent `run --plan`
    # path gets (main()'s dispatch list includes scale-run): a big-N
    # tile loop's compile is exactly what the persistent cache exists
    # to amortize
    _add_cache_flags(p)
    p.set_defaults(fn=cmd_scale_run)

    p = sub.add_parser(
        "staticcheck",
        help="AST invariant analyzer over the repo source: "
             "recompile-hazard lint (serving/sweep), rpc lock "
             "discipline, convention gates; exit 1 on findings "
             "(docs/STATIC_ANALYSIS.md)")
    p.add_argument("--root", default=None, metavar="DIR",
                   help="tree to analyze (default: this repo)")
    p.add_argument("--baseline", default=None, metavar="JSON",
                   help="suppression baseline (default: tools/"
                        "staticcheck_baseline.json; '' disables)")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="write the provenance-stamped findings ledger")
    p.add_argument("--json", dest="json_summary", action="store_true",
                   help="one summary JSON line instead of per-finding "
                        "text")
    p.set_defaults(fn=cmd_staticcheck)

    p = sub.add_parser("maelstrom",
                       help="run the Maelstrom protocol node on stdio")
    p.add_argument("--gossip-interval", type=float, default=0.0,
                   help="batch relays per neighbor every INTERVAL "
                        "seconds (0 = immediate per-message fan-out)")
    p.add_argument("--workload", default="broadcast",
                   choices=("broadcast", "counter", "kafka", "txn"),
                   help="node personality: broadcast log (the "
                        "reference), Gossip Glomers counter (CRDT "
                        "shards, merge = per-key max), the "
                        "replicated kafka-style log (owner-assigned "
                        "offsets, committed-offset max merge), or "
                        "txn-rw-register (totally-available "
                        "transactions over LWW registers)")
    p.set_defaults(fn=cmd_maelstrom)

    p = sub.add_parser("maelstrom-check",
                       help="run the Maelstrom broadcast workload against "
                            "N real node processes and check the "
                            "eventual-delivery invariant (the external "
                            "harness the reference was tested with, "
                            "in-repo)")
    p.add_argument("--n", type=int, default=5)
    p.add_argument("--ops", type=int, default=20)
    p.add_argument("--rate", type=float, default=50.0, help="ops/sec")
    p.add_argument("--latency", type=float, default=0.002,
                   help="simulated link latency (s)")
    p.add_argument("--topology", default="line", choices=("line", "grid"))
    p.add_argument("--partition", action="store_true",
                   help="cut a mid-cluster link for the middle third of "
                        "the run (fault-tolerance variant)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--router", default="python",
                   choices=("python", "native"),
                   help="harness engine: the asyncio router or the C++ "
                        "poll()-loop router (native/router.cpp, built on "
                        "demand)")
    p.add_argument("--workload", default="broadcast",
                   choices=("broadcast", "counter", "kafka", "txn"),
                   help="broadcast (every value in every read), the "
                        "Gossip Glomers counter (every node's final "
                        "read == the sum of acked adds, through a "
                        "--partition), kafka (acked sends exactly "
                        "once per key in offset order, monotone "
                        "committed offsets, gapless polls — through "
                        "a --partition), or txn (txn-rw-register: "
                        "no G0/G1a weak-isolation anomalies + "
                        "cross-node LWW convergence — through a "
                        "--partition)")
    p.add_argument("--gossip-interval", type=float, default=0.0,
                   help="run the nodes with interval-batched relays "
                        "(seconds; 0 = the reference's immediate "
                        "per-message fan-out)")
    p.add_argument("--assert-msgs-per-op", type=float, default=None,
                   metavar="T",
                   help="Glomers-style efficiency gate: fail (exit 1) if "
                        "msgs_per_op exceeds T; the report records the "
                        "target and verdict")
    p.add_argument("--assert-latency-ms", type=float, default=None,
                   metavar="MS",
                   help="fail if the max client-op latency exceeds MS")
    p.set_defaults(fn=cmd_maelstrom_check)

    a = ap.parse_args(argv)
    try:
        if a.cmd in ("run", "sweep", "grid", "churn-sweep", "crdt",
                     "log", "txn", "serve", "scale-run"):
            # multi-host pods: one jax.distributed.initialize() per host
            # before any jax API (no-op without the coordinator env vars)
            from gossip_tpu.parallel.multislice import maybe_init_distributed
            maybe_init_distributed()
            _enable_compile_cache(a)
        return a.fn(a)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
