"""Single-source loader for ``gossip_tpu.utils.telemetry`` from tools/
scripts (which run by path with tools/, not the repo root, on
sys.path) — one definition, so the ledger-bootstrap idiom cannot drift
between the capture tools."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def telemetry():
    sys.path.insert(0, REPO)
    try:
        from gossip_tpu.utils import telemetry as mod
    finally:
        sys.path.pop(0)
    return mod


def open_ledger(default_path):
    """telemetry.from_env with the tool's default path — never raises
    (from_env degrades to Null/EchoLedger on an unwritable path)."""
    return telemetry().from_env(default_path=default_path)
