#!/usr/bin/env python
"""Re-measure docs/PERF.md's interactive-provenance kernel numbers into a
committed artifact (VERDICT r4 task 1b).

docs/PERF.md "Kernel-level numbers" still carries four round-1/2
interactive-session measurements no committed artifact records: the
fused single-rumor ms/round at 10M, the VMEM-OOM ladder that justified
the staged big-MR split, the device-side topology-build speedup, and
(from the round-5 candidates list) the fused fault-mask on-cost.  This
tool re-measures all of them in one session and writes
artifacts/kernel_numbers_r05.json:

  1. fused single-rumor round at N=10M: ms/round (the "~3 ms" bullet)
  2. VMEM OOM ladder: the 10M x 32-rumor VALUE kernel force-compiled
     (bypassing the staged-path routing) so XLA's own VMEM-exceeded
     message — with its MiB figure — lands in the artifact (the
     "152.7 MiB vs 128 MiB" bullet)
  3. 1M-node power_law (cap 256) topology build, end-to-end device
     seconds (the "110 s -> 21 s" bullet)
  4. fault-mask on-cost at the 10M flagship shape: ms/round with
     masks off vs drop_prob=0.05 + 1% dead nodes in-kernel (designed
     ~zero off / one VMEM AND per pull on — round-5 candidate #3)
  5. the staged big-MR path at fanout 2 (round-5 multi-pass
     accumulation) timed at the flagship shape — VERDICT r4 task 8's
     "route works at 10M x 32 fanout=2" as a measured row

Reference for the hot loop all of these serve: /root/reference/
main.go:72-88 (semantics contract; the numbers are ours).

Run on the chip.  ``--smoke`` rehearses on the CPU
interpreter at tiny shapes (.smoke artifact, repo convention).
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
try:
    from _timing import timed_chain as _timed_chain  # noqa: E402
finally:
    sys.path.pop(0)


def _time_rounds(step, init_table, rounds: int) -> float:
    """ms/round (shared scaffold: tools/_timing.timed_chain, seconds)."""
    return _timed_chain(step, init_table, rounds) * 1e3


def single_rumor_ms(n: int, interpret: bool, rounds: int) -> dict:
    from gossip_tpu.ops.pallas_round import (fused_pull_round,
                                             init_fused_state)
    st = init_fused_state(n)
    ms = _time_rounds(
        lambda i, t: fused_pull_round(t, 0, i, n, 1, interpret),
        st.table, rounds)
    return {"n": n, "ms_per_round": round(ms, 4),
            "node_rounds_per_s": round(n / ms * 1e3, 1)}


def vmem_oom_ladder(n: int, rumors: int, interpret: bool) -> dict:
    """Force the whole-table VALUE kernel at a shape the router sends to
    the staged path, so the XLA VMEM-exceeded message (with its MiB
    requirement) is captured verbatim.  In smoke/interpreter mode there
    is no VMEM to exceed — the rehearsal just proves the bypass plumbing
    compiles."""
    import jax
    import jax.numpy as jnp

    from gossip_tpu.ops import pallas_round as PR

    rows = PR.mr_rows(n)
    table_bytes = rows * PR.LANES * 4
    kernel = functools.partial(PR._fused_mr_kernel, rows=rows, fanout=1,
                               n=n, inject=False)

    def forced_round(table):
        return PR._fused_call(kernel, rows, jnp.int32(0), jnp.int32(1),
                              table, None, interpret, round_salt=0x5D0)

    spec = jax.ShapeDtypeStruct((rows, PR.LANES), jnp.uint32)
    out = {"n": n, "rumors": rumors, "rows": rows,
           "table_mib": round(table_bytes / 2**20, 2),
           "routed_to_staged": PR._mr_wants_big(table_bytes, 1)}
    try:
        jax.jit(forced_round).lower(spec).compile()
        out["value_kernel_compiles"] = True
    except Exception as e:
        msg = str(e)
        out["value_kernel_compiles"] = False
        # keep the juicy part: XLA prints the VMEM requirement in MiB
        idx = msg.lower().find("vmem")
        out["oom_message"] = msg[max(0, idx - 200):idx + 500] or msg[:700]
    return out


def mr_staged_fanout2_ms(n: int, rumors: int, interpret: bool,
                         rounds: int) -> dict:
    """Per-round ms of the staged big-MR path at fanout 2 (round-5
    multi-pass accumulation — VERDICT r4 task 8 wants the route proven
    at the flagship 10M x 32 shape; expected ~2x the fanout-1 HBM
    cost)."""
    from gossip_tpu.ops import pallas_round as PR
    st = PR.init_multirumor_state(n, rumors)
    # call the staged path DIRECTLY: at smoke scale the public router
    # would pick the value kernel and the artifact row would mislabel
    # which code path produced the number
    ms = _time_rounds(
        lambda i, t: PR._fused_mr_round_big(t, 0, i, n, interpret, None,
                                            fanout=2),
        st.table, rounds)
    return {"n": n, "rumors": rumors, "fanout": 2, "path": "staged_big",
            "ms_per_round": round(ms, 4)}


def topology_build_s(n: int) -> dict:
    from gossip_tpu.config import TopologyConfig
    from gossip_tpu.topology import generators as G
    import jax
    tc = TopologyConfig(family="power_law", n=n, k=3, degree_cap=256)
    t0 = time.perf_counter()
    topo = G.build(tc)
    jax.block_until_ready((topo.nbrs, topo.deg))
    wall = time.perf_counter() - t0
    return {"n": n, "family": "power_law", "degree_cap": 256,
            "build_s": round(wall, 2),
            "table_shape": list(topo.nbrs.shape)}


def fault_mask_cost(n: int, interpret: bool, rounds: int) -> dict:
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import (fault_masks_node_packed,
                                             fused_pull_round,
                                             init_fused_state)
    st = init_fused_state(n)
    off_ms = _time_rounds(
        lambda i, t: fused_pull_round(t, 0, i, n, 1, interpret),
        st.table, rounds)
    fault = FaultConfig(node_death_rate=0.01, drop_prob=0.05, seed=0)
    alive_table, thresh = fault_masks_node_packed(fault, n)
    on_ms = _time_rounds(
        lambda i, t: fused_pull_round(t, 0, i, n, 1, interpret,
                                      drop_threshold=thresh,
                                      alive_table=alive_table),
        st.table, rounds)
    return {"n": n, "masks_off_ms_per_round": round(off_ms, 4),
            "masks_on_ms_per_round": round(on_ms, 4),
            "on_cost_pct": round((on_ms / off_ms - 1) * 100, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--topo-n", type=int, default=1_000_000)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    smoke = a.smoke
    if smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        n, topo_n, rounds = 4096 * 8, 20_000, 2
    else:
        n, topo_n, rounds = a.n, a.topo_n, a.rounds

    import jax
    backend = jax.default_backend()
    from gossip_tpu.utils import telemetry
    doc = {"what": ("re-measurement of docs/PERF.md's interactive-"
                    "provenance kernel numbers (VERDICT r4 1b); see "
                    "module doc for the four items"),
           # the one artifact schema (tools/validate_artifacts.py):
           # regenerations must be attributable even though the
           # committed file is legacy-allowlisted by name
           # (staticcheck artifact-writer-provenance gate)
           "provenance": telemetry.provenance(),
           "backend": backend, "smoke": smoke}
    doc["single_rumor"] = single_rumor_ms(n, smoke, rounds)
    doc["mr_staged_fanout2"] = mr_staged_fanout2_ms(n, 32, smoke, rounds)
    doc["vmem_oom_ladder"] = vmem_oom_ladder(n, 32, smoke)
    doc["topology_build"] = topology_build_s(topo_n)
    doc["fault_mask"] = fault_mask_cost(n, smoke, rounds)

    infix = ".smoke" if smoke else ""
    art = os.path.join(REPO, "artifacts", f"kernel_numbers_r05{infix}.json")
    with open(art, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"single_ms": doc["single_rumor"]["ms_per_round"],
                      "mr_fanout2_ms": doc["mr_staged_fanout2"]
                      ["ms_per_round"],
                      "oom_captured": not doc["vmem_oom_ladder"]
                      .get("value_kernel_compiles", True),
                      "topo_build_s": doc["topology_build"]["build_s"],
                      "fault_on_cost_pct": doc["fault_mask"]["on_cost_pct"],
                      "backend": backend, "smoke": smoke}))
    print(f"wrote {art}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
