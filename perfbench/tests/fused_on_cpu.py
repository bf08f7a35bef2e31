"""Run the fused engine's staged big-table path through run_simulation on
the CPU: Pallas in interpret mode, whose hardware PRNG draws zeros off the
chip (the engine's own off-chip lowering).  Tests only."""

import functools


def patch(monkeypatch):
    from gossip_tpu import backend
    from gossip_tpu.ops import pallas_round
    real_reason = backend._fused_ineligible_reason

    def reason(*a, **kw):
        r = real_reason(*a, **kw)
        return None if r and "needs a TPU" in r else r

    monkeypatch.setattr(backend, "_fused_ineligible_reason", reason)
    monkeypatch.setattr(pallas_round, "_mr_wants_big", lambda *a: True)
    monkeypatch.setattr(
        pallas_round, "compiled_until_fused_multirumor",
        functools.partial(pallas_round.compiled_until_fused_multirumor,
                          interpret=True))
