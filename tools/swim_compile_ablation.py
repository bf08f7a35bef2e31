#!/usr/bin/env python
"""Ablate the SWIM step's compile time on the real chip.

The r04 capture decomposed SWIM-1M's wall into ~120 s of XLA compile
(sort lowering) + ~12-16 s steady (docs/PERF.md "SWIM-1M cost budget"),
making compile the dominant cost of the whole BASELINE row.  This
experiment answers *what* XLA spends that time on, by AOT-lowering and
compiling the 1M-node step with each major component stubbed out in
turn (the stubs keep all shapes/dtypes so the rest of the program is
unchanged):

  full       the real step (sort dissemination default)
  no_probe   probe_draws -> constant zeros (kills the 1M-lane threefry
             probe/proxy draw chain: 5 fold_in+randint streams)
  no_diss    disseminate_max -> zeros (kills sort + segment-max)
  no_sample  sample_peers -> ring targets (kills the table gather +
             per-node partner threefry)
  scatter    swim_diss='scatter' control (the pre-r04 lowering)
  barrier_alive
             base_alive wrapped in lax.optimization_barrier — tests
             whether XLA's interpreted constant-folding of the 1M-bool
             liveness subgraph (and everything folded through it) is
             the residual ~120 s (first run's verdict: no_probe /
             no_diss / no_sample each save only ~3 s, so the hog is
             none of the three data-movement components)

Each variant reports trace+lower seconds and backend compile seconds
for the BARE step (the sweep row additionally compiles the early-exit
until-driver around it, so absolute numbers here sit below the row's
compile_s; the *deltas* are the signal).  Writes one JSON line per
variant and artifacts/swim_compile_ablation_r04.json.

Run on the chip.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts", "swim_compile_ablation_r04.json")

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
PROTO_KW = dict(mode="swim", fanout=2, swim_proxies=3, swim_subjects=8,
                swim_suspect_rounds=24)


def main():
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from gossip_tpu.config import ProtocolConfig, TopologyConfig
    from gossip_tpu import topology
    from gossip_tpu.models import swim as SW

    print(f"devices: {jax.devices()}", file=sys.stderr)
    topo = topology.build(TopologyConfig(family="power_law", n=N, k=3,
                                         degree_cap=256))
    real_probe = SW.probe_draws
    real_diss = SW.disseminate_max
    real_sample = SW.sample_peers
    real_alive = SW.base_alive

    def barrier_alive(n, dead_nodes, fault):
        return jax.lax.optimization_barrier(
            real_alive(n, dead_nodes, fault))

    def stub_probe(rkey, gids, s_count, n, proxies, drop_prob):
        m = len(gids)
        return (jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.bool_),
                jnp.zeros((m, proxies), jnp.int32),
                jnp.zeros((m, proxies), jnp.bool_),
                jnp.zeros((m, proxies), jnp.bool_))

    def stub_diss(targets, wire, num_rows, impl="sort", max_rounds=None):
        return jnp.zeros((num_rows, wire.shape[1]), jnp.int32)

    def stub_sample(key, ids, topo_, fanout, exclude_self=True,
                    local_nbrs=None, local_deg=None):
        ring = (ids[:, None] + 1 + jnp.arange(fanout)[None, :]) % N
        return ring.astype(jnp.int32)

    variants = [
        ("full", "sort", {}),
        ("no_probe", "sort", {"probe_draws": stub_probe}),
        ("no_diss", "sort", {"disseminate_max": stub_diss}),
        ("no_sample", "sort", {"sample_peers": stub_sample}),
        ("scatter", "scatter", {}),
        ("barrier_alive", "sort", {"base_alive": barrier_alive}),
    ]
    if len(sys.argv) > 2:      # run a named subset, e.g. barrier_alive
        want = set(sys.argv[2:])
        variants = [v for v in variants if v[0] in want or v[0] == "full"]
    rows = []
    for name, impl, patches in variants:
        proto = ProtocolConfig(swim_diss=impl, **PROTO_KW)
        for attr, fn in patches.items():
            setattr(SW, attr, fn)
        try:
            step, tables = SW.make_swim_round(proto, N, dead_nodes=(1,),
                                              fail_round=2, topo=topo,
                                              tabled=True)
            st = SW.init_swim_state(N, proto.swim_subjects, seed=0)
            t0 = time.time()
            lowered = jax.jit(step).lower(st, *tables)
            t1 = time.time()
            lowered.compile()
            t2 = time.time()
            row = {"variant": name, "lower_s": round(t1 - t0, 2),
                   "compile_s": round(t2 - t1, 2)}
        finally:
            SW.probe_draws = real_probe
            SW.disseminate_max = real_diss
            SW.sample_peers = real_sample
            SW.base_alive = real_alive
        print(json.dumps(row), flush=True)
        rows.append(row)

    full = next(r for r in rows if r["variant"] == "full")
    for r in rows:
        r["delta_vs_full_s"] = round(r["compile_s"] - full["compile_s"], 2)
    prior = {}
    if os.path.exists(ART):
        with open(ART) as f:
            prior = json.load(f)
    if N == 1_000_000:
        # subset runs merge into earlier rows
        merged = {r["variant"]: r for r in prior.get("rows", [])}
        merged.update({r["variant"]: r for r in rows})
        # deltas must all be relative to the full row IN THIS FILE —
        # a subset merge replaces "full", so recompute every delta
        full_c = merged["full"]["compile_s"]
        for r in merged.values():
            r["delta_vs_full_s"] = round(r["compile_s"] - full_c, 2)
        prior.update({"n": N, "proto": PROTO_KW,
                      "note": __doc__.split("\n")[0],
                      "rows": list(merged.values())})
    elif prior:
        # non-1M full runs feed the compile-vs-n scaling curve instead
        # of the ablation rows (and never clobber them)
        scaling = prior.setdefault("scaling_compile_s_by_n", {})
        scaling[str(N)] = full["compile_s"]
    else:
        return 0    # CPU smoke before any 1M artifact exists: no write
    # stamped per write: the merged artifact's attribution is the run
    # that last touched it (the one artifact schema —
    # tools/validate_artifacts.py / staticcheck writer gate)
    from _telemetry import telemetry
    prior["provenance"] = telemetry().provenance()
    with open(ART, "w") as f:
        json.dump(prior, f, indent=1)
    print(f"wrote {ART}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
