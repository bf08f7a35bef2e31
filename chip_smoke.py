"""On-chip smoke: drive the main path once on a TPU, through the entry
points a user calls, at the north-star size (10M nodes).

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four-chip host: the sharded paths

One process holds the chip; nothing here starts a child that touches
JAX.  Each phase prints one JSON line (the engine the report names,
rounds, coverage, msgs, compile_s, steady_wall_s) — bring-up evidence,
not benchmark numbers.  Any failed check raises, so the script exits
nonzero; without a TPU it fails before the first phase.  The last line
of standard output is the one-object verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Phases (one chip):

* flagship — ``run --mode pull --n 10000000`` (engine auto): the fused
  Pallas engine, coverage >= 0.99.
* multirumor-10m — the same with ``--rumors 8`` (BASELINE config 5 on
  one chip): fused Pallas, coverage >= 0.99.
* xla-packed — ``--engine xla``: the bit-packed XLA engine.
* served — the gRPC sidecar in this process, batching on as ``serve``
  has it: one fused-eligible request and two the batcher may coalesce;
  every reply covers >= 0.99 and Health reports backend ``tpu``.
* reference — ``run --parity-check`` flood on the 1024-node grid
  against the go-native reference: curve_gap exactly 0.0.

``--four-chips``: the node-sharded bit-packed run on 4 devices against
the same run on one (identical rounds and msgs, coverage within 1e-6),
and the plane-sharded fused run (128 rumors) with every plane checked
bitwise against the single-device kernel over the same rounds — each
with its state on four distinct devices.

The compile cache is wherever $JAX_COMPILATION_CACHE_DIR says, else the
checkout's fixed ``.jax_cache/`` (utils/compile_cache).
"""

import argparse
import contextlib
import io
import json
import os
import sys
import threading

N = 10_000_000
TARGET = 0.99


def _emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def require_tpu():
    """The chip's devices, or SystemExit: there is no CPU path."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    return devs


def import_repo():
    """The repo's package from THIS checkout — never one found
    elsewhere on the path (a lone copy of this script must fail)."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import gossip_tpu
    pkg = os.path.dirname(os.path.abspath(gossip_tpu.__file__))
    if os.path.dirname(pkg) != here:
        raise SystemExit(f"chip_smoke: gossip_tpu comes from {pkg}, not "
                         f"from this checkout ({here})")


def cli_json(argv):
    """``gossip_tpu.cli.main(argv)`` in this process; its one JSON
    report line, parsed.  A nonzero exit raises."""
    from gossip_tpu import cli
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(list(argv))
    _check(rc == 0, f"cli {' '.join(argv)} exited {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def report_line(phase, rep):
    meta = rep.get("meta", {})
    _emit(phase, engine=meta.get("engine"), devices=meta.get("devices"),
          n=rep["n"], rounds=rep["rounds"], coverage=rep["coverage"],
          msgs=rep["msgs"], compile_s=meta.get("compile_s"),
          steady_wall_s=meta.get("steady_wall_s"))


def run_phase(phase, argv, engine, n=N):
    rep = cli_json(["run", "--mode", "pull", "--n", str(n)] + argv)
    report_line(phase, rep)
    _check(rep["meta"].get("engine") == engine,
           f"{phase}: engine {rep['meta'].get('engine')!r}, want {engine!r}")
    _check(rep["rounds"] >= 0 and rep["coverage"] >= TARGET,
           f"{phase}: coverage {rep['coverage']} in {rep['rounds']} rounds")
    return rep


def phase_served(n=1_000_000, expect_backend="tpu"):
    """The sidecar in-process: batching on (the ``serve`` default), one
    fused-eligible request plus two concurrent XLA requests of one
    shape that the batcher may coalesce into a megabatch."""
    from gossip_tpu.config import ServingConfig
    from gossip_tpu.rpc.sidecar import SidecarClient, serve
    server, port = serve(port=0, max_workers=8, batching=ServingConfig())
    client = SidecarClient(f"127.0.0.1:{port}")
    try:
        health = client.health()
        _check(health["backend"] == expect_backend,
               f"served: health backend {health['backend']!r}")

        def req(engine, seed):
            return dict(backend="jax-tpu",
                        proto={"mode": "pull", "fanout": 1},
                        topology={"family": "complete", "n": n},
                        run={"max_rounds": 64, "seed": seed,
                             "engine": engine})

        reps = {"fused-eligible": client.run(timeout=900,
                                             **req("auto", 0))}
        out = {}

        def fire(name, seed):
            out[name] = client.run(timeout=900, **req("xla", seed))

        threads = [threading.Thread(target=fire, args=(f"xla-{s}", s))
                   for s in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        reps.update(out)
        _check(len(reps) == 3, f"served: {sorted(reps)} replied")
        for name, rep in sorted(reps.items()):
            meta = rep.get("meta", {})
            _emit(f"served:{name}", engine=meta.get("engine"),
                  batch=meta.get("batch"), n=rep["n"],
                  rounds=rep["rounds"], coverage=rep["coverage"],
                  msgs=rep["msgs"], compile_s=meta.get("compile_s"),
                  steady_wall_s=meta.get("steady_wall_s"))
            _check(rep["rounds"] >= 0 and rep["coverage"] >= TARGET,
                   f"served:{name}: coverage {rep['coverage']}")
        _emit("served:health", backend=health["backend"],
              devices=health["devices"])
    finally:
        client.close()
        if server.gossip_batcher is not None:
            server.gossip_batcher.close()
        server.stop(grace=None)


def phase_reference():
    out = cli_json(["run", "--parity-check", "--mode", "flood",
                    "--family", "grid", "--n", "1024",
                    "--max-rounds", "200"])
    jx = out["jax"]
    _emit("reference", engine="jax-tpu flood vs go-native",
          n=out["n"], rounds=jx["rounds"], coverage=jx["coverage"],
          msgs=jx["msgs"], curve_gap=out["curve_gap"],
          hop_bound_violation=out["hop_bound_violation"],
          fixed_point_gap=out["fixed_point_gap"],
          compile_s=jx["meta"].get("compile_s"),
          steady_wall_s=jx["meta"].get("steady_wall_s"))
    _check(out["curve_gap"] == 0.0, f"reference: curve_gap "
           f"{out['curve_gap']} (the exact tier needs 0.0)")


def _state_devices(arr):
    return sorted({s.device.id for s in arr.addressable_shards})


def phase_node_sharded(n=N, devices=4):
    """Node-sharded bit-packed pull on ``devices`` chips against the
    same run on one: the mesh-invariant trajectory claim."""
    from gossip_tpu.config import ProtocolConfig, RunConfig
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_packed import (
        simulate_until_packed_sharded)
    from gossip_tpu.topology import generators as G
    one = run_phase("node-sharded:1", ["--engine", "xla"], "bit-packed",
                    n=n)
    many = run_phase(f"node-sharded:{devices}",
                     ["--engine", "xla", "--devices", str(devices)],
                     "bit-packed", n=n)
    _check((many["rounds"], many["msgs"]) == (one["rounds"], one["msgs"]),
           f"node-sharded: rounds/msgs {many['rounds']}/{many['msgs']} vs "
           f"one device {one['rounds']}/{one['msgs']}")
    _check(abs(many["coverage"] - one["coverage"]) <= 1e-6,
           f"node-sharded: coverage {many['coverage']} vs {one['coverage']}")
    # the same driver the CLI ran, for its state's placement
    rounds, cov, _, final = simulate_until_packed_sharded(
        ProtocolConfig(mode="pull", fanout=1, rumors=1), G.complete(n),
        RunConfig(target_coverage=TARGET, max_rounds=256, seed=0),
        make_mesh(devices), timing={})
    placed = _state_devices(final.seen)
    _emit("node-sharded:placement", state_devices=placed, rounds=rounds,
          coverage=cov)
    _check(len(placed) == devices, f"node-sharded: state on {placed}")
    _check(rounds == many["rounds"], "node-sharded: driver rerun differs")


def phase_plane_sharded(n=N, rumors=128, devices=4, interpret=False):
    """Plane-sharded fused pull: the CLI run, then every plane checked
    bitwise against the single-device multi-rumor kernel run the same
    rounds from that plane's origins (the shared partner stream IS the
    semantic — tests/test_sharded_fused.py), after the per-device PRNG
    stream invariant."""
    import jax
    import numpy as np
    from gossip_tpu.config import RunConfig
    from gossip_tpu.ops.pallas_round import (BITS,
                                             fused_multirumor_pull_round,
                                             init_multirumor_state)
    from gossip_tpu.parallel.sharded_fused import (
        assert_prng_invariant, make_plane_mesh,
        simulate_until_sharded_fused)
    if not interpret:
        rep = run_phase(f"plane-sharded:{devices}",
                        ["--rumors", str(rumors), "--engine", "fused",
                         "--devices", str(devices)],
                        "fused-pallas-planes", n=n)
    mesh = make_plane_mesh(devices)
    digests = assert_prng_invariant(n, mesh, interpret=interpret)
    run = RunConfig(target_coverage=TARGET, max_rounds=256, seed=0)
    rounds, cov, _, planes = simulate_until_sharded_fused(
        n, rumors, run, mesh, interpret=interpret)
    placed = _state_devices(planes)
    _check(len(placed) == devices, f"plane-sharded: state on {placed}")
    if not interpret:
        _check(rounds == rep["rounds"], "plane-sharded: driver rerun "
               f"took {rounds} rounds, the CLI {rep['rounds']}")
    planes = np.asarray(planes)

    @jax.jit
    def reference(table):
        # the single-device kernel, stepped exactly `rounds` times
        return jax.lax.fori_loop(
            0, rounds, lambda r, t: fused_multirumor_pull_round(
                t, run.seed, r, n, 1, interpret), table)

    same = []
    for p in range(planes.shape[0]):
        init = init_multirumor_state(n, BITS, origin=p * BITS)
        ref = np.asarray(reference(init.table))
        same.append(bool(np.array_equal(ref, planes[p])))
    _emit("plane-sharded:check", state_devices=placed, rounds=rounds,
          coverage=cov, planes_bitwise_equal=same,
          prng_digest=np.asarray(digests)[0].tolist())
    _check(all(same), f"plane-sharded: planes equal to one device: {same}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths on 4 chips and what "
                         "they are compared with")
    a = ap.parse_args(argv)
    devs = require_tpu()
    import_repo()
    from gossip_tpu.utils import compile_cache
    cache = compile_cache.enable_persistent(compile_cache.DEFAULT_DIR,
                                            min_compile_time_secs=2.0)
    os.environ[compile_cache.ENV_VAR] = cache["dir"] or ""
    _emit("setup", compile_cache=cache["dir"], devices=len(devs),
          kind=devs[0].device_kind)
    if a.four_chips:
        _check(len(devs) >= 4, f"--four-chips: {len(devs)} devices")
        phase_node_sharded()
        phase_plane_sharded()
    else:
        run_phase("flagship", [], "fused-pallas")
        run_phase("multirumor-10m", ["--rumors", "8"], "fused-pallas")
        run_phase("xla-packed", ["--engine", "xla"], "bit-packed")
        phase_served()
        phase_reference()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
