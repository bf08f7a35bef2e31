"""The main-path kernels compile for a described v5e, no chip attached.

The TPU compiler is installed here, and it compiles for a chip that is
described rather than attached: a refused tiling, a kernel over its
VMEM limit or a program that does not fit the device fails in these
tests, at no chip time.  Nothing runs, so nothing here says anything
about results or times — chip_smoke.py is the on-chip check.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every xdist worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from gossip_tpu.config import ProtocolConfig, RunConfig
from gossip_tpu.topology import generators as G

HBM_BYTES = 16 * 1024**3     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, peak
    return compiled.as_text()


@pytest.mark.parametrize("n,rumors", [(1_000_000, 1), (10_000_000, 8)],
                         ids=["fused-1M", "fused-mr-10Mx8"])
def test_fused_loop_compiles_with_kernel(one_chip, n, rumors):
    from gossip_tpu.ops import pallas_round as PR
    if rumors == 1:
        loop, init = PR.compiled_until_fused(n, seed=0)
    else:
        loop, init = PR.compiled_until_fused_multirumor(n, rumors, seed=0)
    text = _fits(loop.lower(_shapes(init, one_chip)).compile())
    assert "tpu_custom_call" in text


def test_packed_xla_loop_compiles(one_chip):
    from gossip_tpu.models.si_packed import compiled_until_packed
    n = 1_000_000
    loop, init, tables = compiled_until_packed(
        ProtocolConfig(mode="pull", fanout=1, rumors=1), G.complete(n),
        RunConfig(target_coverage=0.99, max_rounds=256, seed=0))
    args = _shapes((init,) + tuple(tables), one_chip)
    text = _fits(loop.lower(*args).compile())
    assert "tpu_custom_call" not in text       # plain XLA, no kernel


def test_node_sharded_packed_step_compiles_on_2x2(topo):
    from gossip_tpu.models.si_packed import init_packed_state
    from gossip_tpu.parallel.sharded_packed import (
        make_sharded_packed_round)
    n = 10_000_000
    mesh = Mesh(np.array(topo.devices[:4]), ("nodes",))
    proto = ProtocolConfig(mode="pull", fanout=1, rumors=1)
    step = make_sharded_packed_round(proto, G.complete(n), mesh)
    state = jax.eval_shape(
        lambda: init_packed_state(RunConfig(seed=0), proto, n))
    args = _shapes(state, NamedSharding(mesh, P()))._replace(
        seen=_shapes(state.seen, NamedSharding(mesh, P("nodes", None))))
    text = _fits(jax.jit(step).lower(args).compile())
    # the partner exchange crosses chips (the TPU compiler may lower the
    # all_gather as an all-reduce of the placed shards)
    assert "all-gather" in text or "all-reduce" in text
