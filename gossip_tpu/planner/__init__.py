"""Scale planner: HBM budget model + streamed bit-plane tiling.

ROADMAP item 3's executable half: the repo has every ingredient for
100M+-node runs (packed word planes, sharded exchanges, multi-slice
hybrid meshes, chunked crash-safe checkpoint segments) but, until this
subsystem, nothing that could answer "what tiling fits N on this
topology?" — or execute the answer.

* :mod:`gossip_tpu.planner.budget` — the pure host-side HBM/host-RAM
  budget model.  NEVER imports jax (the analysis/ rationale: capacity
  questions must be answerable before any device exists).  ``plan_scale`` emits a validated :class:`ScalePlan`
  or refuses loudly with the binding constraint named.
* :mod:`gossip_tpu.planner.stream` — ``run_at_scale``: executes a
  ScalePlan through the existing packed drivers by streaming word-
  plane tiles host<->device per checkpoint segment, bitwise identical
  to the untiled in-memory run.

docs/SCALING.md has the contract; CLI: ``gossip_tpu plan`` /
``gossip_tpu scale-run``.
"""

from gossip_tpu.planner.budget import (  # noqa: F401
    DeviceSpec, InfeasiblePlanError, ScalePlan, plan_fingerprint,
    plan_scale, validate_plan)
