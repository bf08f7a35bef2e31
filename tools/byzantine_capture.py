#!/usr/bin/env python
"""Capture the byzantine-adversary convergence record (the Byzantine
nemesis PR's acceptance artifact).

One mixed scenario, two arms, one provenance-stamped ledger:

* **Scenario** — a 16-node complete-graph pull fabric under a MIXED
  nemesis program: one fail-stop churn event (node 4 dies at round 6,
  recovers at 12) plus a scripted liar program (node 3 INFLATES
  foreign components from round 2, node 11 CORRUPTS them with a
  high-bit xor from round 0; quorum 2).  Liar content never enters
  the compiled loop — the byz program lowers to padded integer
  operands on the step's table tail (ops/nemesis), so both arms below
  share ONE executable per driver.

* **Defended arm** (``defend=True``) — the array-form lattice
  defenses (owner-column admission, monotonicity clamps, provenance-
  checked register entries).  Gate: the honest eventual-alive set
  converges EXACTLY — ``byz_conv == denominator/denominator`` as an
  integer count, the value_conv discipline — for both the gcounter
  and the LWW-register payloads.

* **Undefended arm** (``defend=False``, the control) — the same
  executable shape with the defenses off MUST diverge: the liars'
  forged components stick under max/OR/LWW merge and the honest count
  stays below the denominator.  A defense whose absence changes
  nothing defends nothing.

* **Mesh parity** — the defended trajectory is BITWISE identical on a
  1-device and a 4-device mesh, and equal to the single-device model
  driver (the fabric's mesh-invariance contract, re-proven on the
  committed evidence).  The sharded runs flush their
  ``round_metrics`` events with the ``byz_conv`` column into the same
  ledger.

Everything lands in one run ledger (utils/telemetry — provenance
first line), so the committed artifact passes
tools/validate_artifacts.py's ``*byz*`` provenance gate.

    python tools/byzantine_capture.py [--smoke] [OUT.jsonl]
        # default artifacts/ledger_byz_r25.jsonl
        # --smoke: gcounter leg only, .smoke-infixed artifact
        #          (the hw_refresh convention)

Runs on the hermetic CPU tier by design (byz convergence is integer
arithmetic on the honest-owned components, not a chip rate).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = 16
DEVICES = 4
MAX_ROUNDS = 100
FANOUT = 3
LIARS = ((3, 2, "inflate", 5), (11, 0, "corrupt", 1 << 20))
QUORUM = 2
CHURN_EVENTS = ((4, 6, 12),)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    argv = [a for a in argv if a != "--smoke"]
    infix = ".smoke" if smoke else ""
    out_path = (argv[0] if argv else
                os.path.join(REPO, "artifacts",
                             f"ledger_byz_r25{infix}.jsonl"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={DEVICES}"
        ).strip()

    import jax
    import numpy as np
    from jax.sharding import Mesh
    from gossip_tpu.config import (ByzConfig, ChurnConfig, CrdtConfig,
                                   FaultConfig, ProtocolConfig,
                                   RunConfig, TxnConfig)
    from gossip_tpu.models.payload import simulate_curve_payload
    from gossip_tpu.ops import crdt as CR
    from gossip_tpu.ops import nemesis as NE
    from gossip_tpu.ops import registers as RG
    from gossip_tpu.parallel.sharded_payload import (
        simulate_curve_payload_sharded)
    from gossip_tpu.topology.generators import complete
    from gossip_tpu.utils import telemetry

    topo = complete(N)
    proto = ProtocolConfig(mode="pull", fanout=FANOUT)
    run = RunConfig(max_rounds=MAX_ROUNDS, seed=7)
    byz = ByzConfig(liars=LIARS, quorum=QUORUM)
    fault = FaultConfig(churn=ChurnConfig(events=CHURN_EVENTS),
                        byz=byz)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("nodes",))
    mesh4 = Mesh(np.array(jax.devices()[:DEVICES]), ("nodes",))

    led = telemetry.Ledger(out_path)
    prev = telemetry.activate(led)
    ok = True
    try:
        led.record_runtime()
        led.event("byz_fault_program",
                  liars=[list(a) for a in LIARS], quorum=QUORUM,
                  churn_events=[list(e) for e in CHURN_EVENTS],
                  n=N, fanout=FANOUT, max_rounds=MAX_ROUNDS,
                  smoke=smoke)

        # -- gcounter leg: defended exact vs undefended divergence ----
        cfg = CrdtConfig(kind="gcounter")
        with led.span("byz:gcounter"):
            conv_u, _, fin_u, _ = simulate_curve_payload(
                cfg, proto, topo, run, fault, defend=False)
            conv_d, _, fin_d, _ = simulate_curve_payload(
                cfg, proto, topo, run, fault, defend=True)
        inj = CR.inject_args(cfg, N)
        truth = CR.ground_truth(cfg, inj, fault, N, 0)
        honest = NE.honest_mask(fault, N)
        alive_h = CR.eventual_alive_crdt(fault, N, 0) & honest
        comp = CR.honest_component_mask(cfg, N, 0, honest)
        denom = int(alive_h.sum())
        cnt_u = int(CR.byz_converged_count(cfg, fin_u.val, truth,
                                           alive_h, comp))
        cnt_d = int(CR.byz_converged_count(cfg, fin_d.val, truth,
                                           alive_h, comp))

        # mesh parity: defended trajectory bitwise across mesh widths
        # (the sharded runs flush round_metrics w/ byz_conv into the
        # ledger under the active telemetry)
        with led.span("byz:mesh_parity"):
            _, _, f1, _ = simulate_curve_payload_sharded(
                cfg, proto, topo, run, mesh1, fault, defend=True)
            c4, _, f4, _ = simulate_curve_payload_sharded(
                cfg, proto, topo, run, mesh4, fault, defend=True)
        parity = bool(
            np.array_equal(np.asarray(f1.val)[:N],
                           np.asarray(f4.val)[:N])
            and np.array_equal(np.asarray(f1.val)[:N],
                               np.asarray(fin_d.val))
            and np.array_equal(np.asarray(conv_d), np.asarray(c4)))
        counter_ok = bool(cnt_d == denom and cnt_u < denom
                          and denom > 0 and parity)
        led.event("byz_scenario", payload="gcounter",
                  defended_count=cnt_d, undefended_count=cnt_u,
                  denominator=denom,
                  defended_exact=bool(cnt_d == denom),
                  undefended_diverged=bool(cnt_u < denom),
                  mesh_parity_bitwise=parity, devices=DEVICES,
                  defended_curve=[round(float(c), 6) for c in conv_d],
                  undefended_curve=[round(float(c), 6)
                                    for c in conv_u],
                  ok=counter_ok)
        ok = ok and counter_ok

        # -- register leg (skipped in smoke: one payload class is
        # enough to smoke the plumbing; the full capture proves the
        # provenance defense on the LWW timestamps too) --------------
        if not smoke:
            cfgt = TxnConfig(txns=12, keys=6, spread_rounds=8)
            with led.span("byz:register"):
                ru = simulate_curve_payload(cfgt, proto, topo, run, fault,
                                        defend=False)
                rd = simulate_curve_payload(cfgt, proto, topo, run, fault,
                                        defend=True)
                r4 = simulate_curve_payload_sharded(cfgt, proto, topo,
                                                run, mesh4, fault,
                                                defend=True)
            injt = RG.inject_args(cfgt, N)
            trt = RG.ground_truth(cfgt, injt, fault, N, 0)
            alive_ht = RG.eventual_alive_crdt(fault, N, 0) & honest
            km = RG.honest_key_mask(cfgt, injt, fault, N, 0, honest)
            denomt = int(alive_ht.sum())
            tcnt_u = int(RG.byz_converged_count(cfgt, ru[2].val, trt,
                                                alive_ht, km))
            tcnt_d = int(RG.byz_converged_count(cfgt, rd[2].val, trt,
                                                alive_ht, km))
            tparity = bool(np.array_equal(np.asarray(r4[2].val)[:N],
                                          np.asarray(rd[2].val)))
            txn_ok = bool(tcnt_d == denomt and tcnt_u < denomt
                          and denomt > 0 and tparity)
            led.event("byz_txn_scenario", keys=cfgt.keys,
                      defended_count=tcnt_d, undefended_count=tcnt_u,
                      denominator=denomt,
                      defended_exact=bool(tcnt_d == denomt),
                      undefended_diverged=bool(tcnt_u < denomt),
                      mesh_parity_bitwise=tparity, devices=DEVICES,
                      ok=txn_ok)
            ok = ok and txn_ok

        led.event("byz_verdict", ok=ok, smoke=smoke)
    finally:
        telemetry.activate(prev)
        led.close()
    print(json.dumps({"out": out_path, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
