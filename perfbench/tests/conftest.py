"""The benchmark's own tests run on the CPU with four virtual devices (the
four-chip cell's mesh) and Pallas in interpret mode; nothing here needs a
chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """XLA:CPU executables loaded back from a cache fail on this host
    (machine features differ), so runs here compile everything fresh."""
    import harness

    def configure():
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        os.environ["GOSSIP_COMPILE_CACHE"] = ""

    monkeypatch.setattr(harness, "configure_cache", configure)
    monkeypatch.setattr(harness, "freeze_cache", lambda frozen: None)
    monkeypatch.setenv("GOSSIP_COMPILE_CACHE", "")
