"""C++ event-sim core == Python gonative, event-for-event.

The Python GoNativeSim is the readable semantics contract; the native core
must reproduce its deliveries (times, nodes, hops), logs, message counts,
and hop depths exactly on shared scenarios — including partitions and both
context-bug modes — or it has no business existing."""

import os

import pytest

from gossip_tpu.runtime.gonative import (GoNativeSim, NetConfig,
                                         topology_from_table)
from gossip_tpu.runtime.native_sim import (NativeGoSim, make_event_sim,
                                           native_available)
from gossip_tpu.topology import generators as G

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="g++ unavailable")


def run_pair(topology, scenario, net=NetConfig(), horizon=120.0):
    out = []
    for cls in (GoNativeSim, NativeGoSim):
        sim = cls(topology, net=net, horizon=horizon)
        scenario(sim)
        sim.run()
        out.append(sim)
    return out


def assert_equivalent(py, cc, messages, n):
    assert py.msgs_sent == cc.msgs_sent
    for m in messages:
        assert py.hop_depths(m) == cc.hop_depths(m), f"hop depths msg {m}"
    for i in range(n):
        assert py.read(i) == cc.read(i), f"log node {i}"
    pd = sorted(py.deliveries)
    cd = sorted(cc.deliveries)
    assert len(pd) == len(cd)
    for (t1, n1, m1, h1), (t2, n2, m2, h2) in zip(pd, cd):
        assert (n1, m1, h1) == (n2, m2, h2)
        assert t1 == pytest.approx(t2, abs=1e-9)


def test_equivalence_er_graph():
    topo = topology_from_table(G.erdos_renyi(512, 0.015, seed=4))

    def scen(sim):
        sim.broadcast(0, 42)
        sim.broadcast(100, 7, t=0.003)

    py, cc = run_pair(topo, scen)
    assert_equivalent(py, cc, [42, 7], 512)


def test_equivalence_with_partitions_faithful_and_fixed():
    topo = {0: [1], 1: [0, 2, 3], 2: [1], 3: [1]}
    for faithful in (True, False):
        net = NetConfig(faithful_ctx_bug=faithful)

        def scen(sim):
            sim.partition(1, 2, 0.0, 5.0)
            sim.broadcast(0, 1)

        py, cc = run_pair(topo, scen, net=net, horizon=60.0)
        assert_equivalent(py, cc, [1], 4)


def test_equivalence_dedup_and_duplicate_injection():
    topo = {0: [1], 1: [0]}

    def scen(sim):
        sim.broadcast(0, 9)
        sim.broadcast(0, 9, t=1.0)     # duplicate client injection

    py, cc = run_pair(topo, scen)
    assert_equivalent(py, cc, [9], 2)


def test_native_is_actually_faster():
    import time
    topo = topology_from_table(G.watts_strogatz(2048, 6, 0.1, seed=2))

    def scen(sim):
        for i in range(20):
            sim.broadcast(i * 97 % 2048, i, t=0.0005 * i)

    t0 = time.perf_counter()
    py = GoNativeSim(topo)
    scen(py)
    py.run()
    t_py = time.perf_counter() - t0
    t0 = time.perf_counter()
    cc = NativeGoSim(topo)
    scen(cc)
    cc.run()
    t_cc = time.perf_counter() - t0
    assert py.msgs_sent == cc.msgs_sent
    assert t_cc < t_py, (t_cc, t_py)   # typically 20-100x


def test_factory_fallback():
    sim = make_event_sim({0: [1], 1: [0]}, prefer_native=False)
    assert isinstance(sim, GoNativeSim)
    sim2 = make_event_sim({0: [1], 1: [0]}, prefer_native=True)
    assert isinstance(sim2, NativeGoSim)


def test_native_build_is_keyed_on_the_source_hash(tmp_path):
    """A build is trusted only for the exact source it came from: its
    name carries the source hash, so an edited source (or a copy whose
    mtimes say nothing) names a new file, and a present build of the
    same source is reused without compiling."""
    from gossip_tpu.native import build_native, built_path
    src = tmp_path / "tiny.cpp"
    src.write_text("int f() { return 1; }\n")
    first = built_path(str(src), shared=True)
    assert os.path.basename(first).startswith("libtiny-")
    assert built_path(str(src), shared=False) != first
    open(first, "w").close()         # a "build" of this exact source
    assert build_native(str(src), shared=True) == first
    src.write_text("int f() { return 2; }\n")
    assert built_path(str(src), shared=True) != first
