#!/usr/bin/env python
"""Decompose SWIM-1M's STEADY-STATE ms/round on the chip (VERDICT r4 #4).

The r04 captures left SWIM's steady state at ~374 ms/round (sort
lowering, 1M nodes) with two named suspects — the dissemination reduce
and the 5-per-node threefry draws — but no runtime decomposition: the
r04 ablation (tools/swim_compile_ablation.py) decomposed COMPILE time
only.  This is its steady-state twin: the same stub-one-component-
at-a-time scheme (stubs keep all shapes/dtypes), but measuring executed
ms/round via a timed fori_loop chain instead of AOT compile seconds:

  full        the real step (sort dissemination)
  no_probe    probe_draws -> constant zeros (the per-node threefry
              probe/proxy chain: is it the lever PERF.md guesses?)
  no_diss     disseminate_max -> zeros (sort + gather + segment-max)
  no_sample   sample_peers -> static ring (table gather + partner draw)
  pack        swim_diss='pack' (the 8-bit transport-code gather)
  scatter     swim_diss='scatter' control

The deltas vs ``full`` are the decomposition; their sum vs ``full``
says how much is unattributed (fused overlap / everything-else).  The
artifact is the "measured floor statement" VERDICT r4 task 4 accepts if
no fix reaches steady < 10 s: whichever component dominates is the
floor's name.  Writes artifacts/swim_steady_ablation_r05.json
(merging variant rows across retries — a window that closes mid-run
keeps the measured variants).

Run on the chip (exit 2 = a variant timed out).  ``--smoke`` rehearses at CPU scale (n=20k).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
try:
    from _timing import timed_chain  # noqa: E402
finally:
    sys.path.pop(0)

PROTO_KW = dict(mode="swim", fanout=2, swim_proxies=3, swim_subjects=8,
                swim_suspect_rounds=24)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--rounds", type=int, default=10,
                    help="rounds per timed chain (x3 median)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    if a.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    n = 20_000 if a.smoke else a.n

    import jax
    import jax.numpy as jnp

    from gossip_tpu import topology
    from gossip_tpu.config import ProtocolConfig, TopologyConfig
    from gossip_tpu.models import swim as SW

    backend = jax.default_backend()
    print(f"backend: {backend}", file=sys.stderr)
    topo = topology.build(TopologyConfig(family="power_law", n=n, k=3,
                                         degree_cap=256))
    jax.block_until_ready((topo.nbrs, topo.deg))

    real_probe = SW.probe_draws
    real_diss = SW.disseminate_max
    real_sample = SW.sample_peers

    def stub_probe(rkey, gids, s_count, n_, proxies, drop_prob):
        m = len(gids)
        return (jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.bool_),
                jnp.zeros((m, proxies), jnp.int32),
                jnp.zeros((m, proxies), jnp.bool_),
                jnp.zeros((m, proxies), jnp.bool_))

    def stub_diss(targets, wire, num_rows, impl="sort", max_rounds=None):
        return jnp.zeros((num_rows, wire.shape[1]), jnp.int32)

    def stub_sample(key, ids, topo_, fanout, exclude_self=True,
                    local_nbrs=None, local_deg=None):
        # hash-scattered targets, NOT a ring: the dissemination sort's
        # cost downstream depends on its input order, and feeding it
        # already-sorted ring segments would charge part of the sort's
        # real cost to this stub (attribution leak).  A multiplicative
        # hash keeps the input as disordered as real draws while
        # removing the threefry + table-gather work being measured.
        h = (ids[:, None].astype(jnp.uint32) * jnp.uint32(2654435761)
             + jnp.arange(fanout, dtype=jnp.uint32)[None, :]
             * jnp.uint32(40503))
        return (h % jnp.uint32(n)).astype(jnp.int32)

    variants = [
        ("full", "sort", {}),
        ("no_probe", "sort", {"probe_draws": stub_probe}),
        ("no_diss", "sort", {"disseminate_max": stub_diss}),
        ("no_sample", "sort", {"sample_peers": stub_sample}),
        ("pack", "pack", {}),
        ("scatter", "scatter", {}),
        # the real candidate lever (ProtocolConfig.swim_rng='packed'):
        # one key chain + one multi-word draw per node instead of ~5
        # threefry streams — unlike the stubs above this is a SHIPPED
        # lowering, so its row is a measurement of an actual option
        ("packed_rng", "sort", {"swim_rng": "packed"}),
        ("packed_rng_pack", "pack", {"swim_rng": "packed"}),
    ]
    if a.only:
        variants = [v for v in variants
                    if v[0] in a.only or v[0] == "full"]

    art = os.path.join(REPO, "artifacts",
                       f"swim_steady_ablation_r05{'.smoke' if a.smoke else ''}"
                       ".json")
    try:
        with open(art) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    merged = {r["variant"]: r for r in doc.get("rows", [])}

    rows = []
    for name, impl, patches in variants:
        if merged.get(name, {}).get("backend") == backend and not a.only:
            continue                       # measured in an earlier window
        rng = patches.pop("swim_rng", "split")
        proto = ProtocolConfig(swim_diss=impl, swim_rng=rng, **PROTO_KW)
        for attr, fn in patches.items():
            setattr(SW, attr, fn)
        try:
            step, tables = SW.make_swim_round(
                proto, n, dead_nodes=(1,), fail_round=2, topo=topo,
                tabled=True, max_rounds=80)
            st = SW.init_swim_state(n, proto.swim_subjects, seed=0)
            t0 = time.time()
            ms = timed_chain(lambda i, s: step(s, *tables), st,
                             a.rounds) * 1e3
            row = {"variant": name, "ms_per_round": round(ms, 2),
                   "compile_plus_measure_s": round(time.time() - t0, 1),
                   "backend": backend}
        finally:
            SW.probe_draws = real_probe
            SW.disseminate_max = real_diss
            SW.sample_peers = real_sample
        print(json.dumps(row), flush=True)
        rows.append(row)
        merged[name] = row
        # persist after EVERY variant: a wedge mid-run keeps the rest
        full = merged.get("full")
        if full:
            for r in merged.values():
                r["delta_vs_full_ms"] = round(
                    r["ms_per_round"] - full["ms_per_round"], 2)
        from gossip_tpu.utils import telemetry
        doc = {"what": ("steady-state ms/round decomposition of the "
                        "BASELINE SWIM shape by component stubbing "
                        "(runtime twin of swim_compile_ablation); "
                        "negative delta = that component's steady "
                        "cost"),
               # the one artifact schema (tools/validate_artifacts.py)
               "provenance": telemetry.provenance(),
               "n": n, "proto": PROTO_KW, "rounds_timed": a.rounds,
               "rows": list(merged.values())}
        with open(art, "w") as f:
            json.dump(doc, f, indent=1)

    print(json.dumps({r["variant"]: r["ms_per_round"]
                      for r in merged.values()}), flush=True)
    print(f"wrote {art}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
