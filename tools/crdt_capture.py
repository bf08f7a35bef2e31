#!/usr/bin/env python
"""Capture the CRDT value-convergence record (the CRDT-subsystem PR's
acceptance artifact).

Runs the G-Counter, PN-Counter, and OR-Set drivers on the 4-device
sharded pull fabric under ONE mixed nemesis fault program — a
crash/recover event, a permanent crash, an open partition window, and
a drop-rate ramp — and gates, per kind:

  * ``value_conv == 1.0``: EVERY eventually-alive node's merged state
    equals the global ground truth (integer-exact full-row equality —
    ops/crdt.converged_count, divided once on the host);
  * the scalar truth value matches the config-computed merge of all
    APPLIED injections (the acked-adds semantics);
  * 1-device/4-device trajectory parity BITWISE (the fabric's
    mesh-invariance contract, re-proven on the committed evidence).

Everything lands in one run ledger (utils/telemetry — provenance first
line; the drivers flush their own ``round_metrics`` events with the
``value_conv`` column), so the committed artifact passes
tools/validate_artifacts.py's ``*crdt*`` provenance gate.

    python tools/crdt_capture.py [OUT.jsonl]    # default
        artifacts/ledger_crdt_r13.jsonl

Runs on the hermetic CPU tier by design (value convergence is integer
arithmetic, not a chip rate).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = 64
DEVICES = 4
MAX_ROUNDS = 24


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    out_path = (argv[0] if argv else
                os.path.join(REPO, "artifacts", "ledger_crdt_r13.jsonl"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={DEVICES}"
        ).strip()

    import numpy as np
    from gossip_tpu.config import (ChurnConfig, CrdtConfig, FaultConfig,
                                   ProtocolConfig, RunConfig)
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.parallel.sharded import make_mesh
    from gossip_tpu.parallel.sharded_payload import (
        simulate_curve_payload_sharded)
    from gossip_tpu.topology import generators as G
    from gossip_tpu.utils import telemetry

    proto = ProtocolConfig(mode="pull", fanout=2)
    topo = G.complete(N)
    run = RunConfig(seed=0, max_rounds=MAX_ROUNDS, target_coverage=1.0)
    mesh = make_mesh(DEVICES)
    # the mixed fault program: crash/recover, permanent crash, open
    # partition window, drop ramp — every schedule feature at once
    fault = FaultConfig(drop_prob=0.05, seed=1, churn=ChurnConfig(
        events=((3, 2, 5), (7, 1, -1)),
        partitions=((0, 6, N // 2),),
        ramp=(1, 4, 0.0, 0.3)))
    kinds = [
        ("gcounter", CrdtConfig(kind="gcounter")),
        ("pncounter", CrdtConfig(kind="pncounter")),
        ("orset", CrdtConfig(kind="orset", elements=48,
                             set_removes=((5, 3), (11, 8)))),
    ]

    led = telemetry.Ledger(out_path)
    prev = telemetry.activate(led)
    ok = True
    try:
        led.record_runtime()
        led.event("crdt_fault_program",
                  events=[list(e) for e in fault.churn.events],
                  partitions=[list(w) for w in fault.churn.partitions],
                  ramp=list(fault.churn.ramp), drop_prob=fault.drop_prob,
                  n=N, max_rounds=MAX_ROUNDS)
        for name, cfg in kinds:
            with led.span(f"crdt:{name}", kind=name):
                conv4, msgs4, fin4, truth4 = simulate_curve_payload_sharded(
                    cfg, proto, topo, run, mesh, fault)
                conv1, msgs1, fin1, truth1 = simulate_curve_payload(
                    cfg, proto, topo, run, fault)
            parity = bool(
                (np.asarray(conv1) == np.asarray(conv4)).all()
                and (np.asarray(fin1.val)
                     == np.asarray(fin4.val)[:N]).all()
                and truth1 == truth4)
            kind_ok = bool(conv4[-1] == 1.0) and parity
            ok = ok and kind_ok
            led.event("crdt_scenario", crdt=name,
                      value_conv_final=float(conv4[-1]),
                      value_conv_curve=[round(float(c), 6)
                                        for c in conv4],
                      truth_value=truth4,
                      msgs=float(msgs4[-1]),
                      mesh_parity_bitwise=parity,
                      devices=DEVICES, ok=kind_ok)
        led.event("crdt_verdict", ok=ok)
    finally:
        telemetry.activate(prev)
        led.close()
    print(json.dumps({"out": out_path, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
