"""Build + drive the native (C++) mini-Maelstrom router.

``native/router.cpp`` is the standalone L-1 harness twin of
:mod:`gossip_tpu.runtime.maelstrom_harness`: one poll() event loop that
spawns the protocol-node processes, routes envelopes with latency and a
partition window, runs the broadcast workload, and checks the
eventual-delivery invariant.  This module compiles it on demand (same
policy as native/__init__.load_eventsim: g++ or graceful None) and
parses its one-line JSON stats.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from typing import List, Optional

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "native")
_SRC = os.path.join(_DIR, "router.cpp")
_REPO = os.path.dirname(os.path.dirname(_DIR))
_lock = threading.Lock()


def build_router() -> Optional[str]:
    """Path to the router binary built from router.cpp as it stands,
    building it first if needed; None if no compiler is available."""
    from gossip_tpu.native import build_native
    with _lock:
        return build_native(_SRC, shared=False)


def run_native_workload(n: int, ops: int, rate: float = 50.0,
                        latency: float = 0.002, topology: str = "line",
                        partition_mid: bool = False, seed: int = 0,
                        argv: Optional[List[str]] = None,
                        timeout: float = 180.0) -> dict:
    """The broadcast workload through the NATIVE router; same stats dict
    shape as maelstrom_harness.run_broadcast_workload (plus
    ``engine: native-router``).  Raises RuntimeError if no compiler."""
    binary = build_router()
    if binary is None:
        raise RuntimeError("no C++ compiler available for the native "
                           "router; use the python harness "
                           "(runtime/maelstrom_harness.py)")
    node_cmd = argv or [sys.executable, "-u", "-m",
                        "gossip_tpu.runtime.maelstrom_node"]
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [binary, "--n", str(n), "--latency-ms", str(latency * 1e3),
           "--ops", str(ops), "--rate", str(rate),
           "--topology", topology, "--seed", str(seed)]
    if partition_mid:
        cmd.append("--partition")
    cmd += ["--"] + node_cmd
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                       env=env)
    lines = [line for line in p.stdout.splitlines() if line.strip()]
    if not lines:
        raise RuntimeError(f"native router produced no stats "
                           f"(rc={p.returncode}): {p.stderr[-300:]}")
    return json.loads(lines[-1])
