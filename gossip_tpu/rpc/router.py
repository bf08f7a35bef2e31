"""Replicated sidecar serving: a fronting router with health-gated
failover over N sidecar replicas.

PAPER.md's reference node survives Maelstrom's nemesis because every
peer retries until acked; until this layer the serving story had no
such property — the admission-batched sidecar (rpc/batcher) is one
process on one device, and a SIGKILL lost every in-flight request.
This module is ROADMAP item 2(b): a router that fronts N ``serve()``
replicas, health-probes them on the existing ``SidecarClient.health``
path, routes ``Run``/``Ensemble`` to healthy replicas, and on a
replica transport failure **re-dispatches the in-flight request to a
survivor**.  The re-dispatch is safe by construction: a request is a
deterministic pure function of its payload (seeded threefry streams,
no server state), so a replay returns the bitwise-same reply — pinned
in tests/test_router.py and gated end-to-end by
tools/fleet_crashloop.py's committed record.

Contract (docs/SERVING.md "Fleet"):

  * **Transparent bytes**: the router proxies request/reply bytes
    untouched — a reply through the router is byte-identical to the
    replica's (and therefore to solo dispatch; the fleet_crashloop
    parity gate).  Failover visibility lives in the run ledger
    (``replica_down`` / ``failover`` / ``replica_up`` events), never
    in mutated replies.
  * **Failover**: only a TRANSPORT failure (UNAVAILABLE — connection
    refused/reset, the replica process died) triggers re-dispatch; any
    well-formed replica reply (INVALID_ARGUMENT, RESOURCE_EXHAUSTED
    from its batcher, INTERNAL) means the replica processed the call
    and is propagated verbatim — the SidecarClient never-retry rule,
    one layer up.
  * **Deadlines propagate end-to-end**: each dispatch attempt gets the
    client's REMAINING budget as its timeout, so a failover retry can
    never run a request its client already abandoned —
    DEADLINE_EXCEEDED is terminal, never replayed.
  * **Shed, never queue**: the router holds no queue.  When no healthy
    replica has a free in-flight slot (``FleetConfig.max_inflight``)
    the request is shed with RESOURCE_EXHAUSTED + a ``shed`` ledger
    event — bounded by construction, never a silent drop.
  * **Hysteresis**: a dispatch failure or ``down_after`` consecutive
    probe failures mark a replica down; a previously-down replica
    re-enters rotation only after ``up_after`` CONSECUTIVE healthy
    probes, so a flapping replica cannot oscillate in and out faster
    than the re-admission threshold (scripted-probe-sequence pinned).

Control plane — the fleet eats its own dogfood (ops/logs): replica
admission/config state replicates as entries on a per-replica OWNER
key of a replicated log (``LogConfig(keys=n_replicas)``), state
transitions append monotonically, and the committed offset of a
replica's key IS its config epoch.  Each replica holds a VIEW row-set
merged by the log's join (``ops.logs.merge_max`` — elementwise max
over owner-indexed slot planes, the exact kafka-log lattice), gossiped
one rotating partner per probe tick; a replica that rejoins after a
kill starts from a ZERO view and catches up from the survivors' gossip
(``control_catchup``), never from operator state.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from gossip_tpu.config import FleetConfig, LogConfig

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# Control-plane admission states, appended as log-entry values (>= 1 by
# the LogConfig contract: 0 is the empty-slot sentinel).
STATE_UP = 1
STATE_DOWN = 2
_STATE_NAMES = {STATE_UP: "up", STATE_DOWN: "down"}


class ControlPlane:
    """The fleet's replicated admission/config log (module doc).

    One ``ops/logs`` row per replica VIEW over ``LogConfig(keys=n,
    capacity=control_capacity)``: replica ``i`` owns key ``i``; its
    state transitions append values at offsets ``0..e-1`` and the
    committed count of key ``i`` is its config epoch.  Views merge by
    the log join (``merge_max``), so gossip order/duplication can
    never corrupt an epoch, and a zeroed (rejoined) view recovers the
    whole fleet state by merging any survivor — exactly the kafka-log
    recovery semantics, applied to the serving layer's own control
    state.  All mutation happens under the Router lock."""

    def __init__(self, n: int, capacity: int):
        from gossip_tpu.ops import logs
        self._logs = logs
        self.cfg = LogConfig(keys=n, capacity=capacity)
        self.n = n
        self.width = logs.state_width(self.cfg)
        self.views = np.zeros((n, self.width), np.int32)
        self._gtick = 0

    def _merged(self) -> np.ndarray:
        out = self.views[0]
        for i in range(1, self.n):
            out = np.asarray(self._logs.merge_max(out, self.views[i]),
                             np.int32)
        return out

    def append(self, owner: int, state: int) -> int:
        """Append ``state`` as the next entry on ``owner``'s key (in
        the owner's view; gossip carries it out) and commit it —
        returns the new epoch.  The epoch is derived from the MERGED
        fleet view so a catchup-lagged owner can never reuse an
        offset."""
        cap = self.cfg.capacity
        lens = np.asarray(self._logs.log_len(self.cfg,
                                             self._merged()), np.int32)
        e = int(lens[owner])
        if e >= cap:
            raise ValueError(
                f"control-plane log for replica {owner} is full "
                f"({e}/{cap} epochs) — a ring wrap would alias epochs; "
                "raise FleetConfig.control_capacity")
        self.views[owner, owner * cap + e] = state
        com = self.cfg.keys * cap + owner
        self.views[owner, com] = max(int(self.views[owner, com]), e + 1)
        return e + 1

    def gossip_tick(self):
        """One rotating-partner pull per replica (the dense pull
        exchange shape on the fleet's own state): view ``i`` merges
        partner ``(i + k) % n`` — full convergence within n-1 ticks."""
        if self.n < 2:
            return
        self._gtick += 1
        k = 1 + (self._gtick % (self.n - 1))
        for i in range(self.n):
            j = (i + k) % self.n
            self.views[i] = np.asarray(
                self._logs.merge_max(self.views[i], self.views[j]),
                np.int32)

    def flush(self, i: int):
        """Push view ``i``'s entries out to every peer (the router's
        last gossip on a dying replica's behalf): the down-transition
        the router just appended must reach a survivor BEFORE the view
        is recycled, or the epoch record would lose an entry and a
        later append could alias its offset."""
        for j in range(self.n):
            if j != i:
                self.views[j] = np.asarray(
                    self._logs.merge_max(self.views[j], self.views[i]),
                    np.int32)

    def wipe(self, i: int):
        """Replica ``i`` died: its in-memory view is gone."""
        self.views[i] = 0

    def catchup(self, i: int) -> int:
        """Rejoin: replica ``i`` rebuilds its view by merging every
        survivor (gossip, not operator state) — returns its recovered
        epoch."""
        merged = np.zeros((self.width,), np.int32)
        for j in range(self.n):
            if j != i:
                merged = np.asarray(
                    self._logs.merge_max(merged, self.views[j]),
                    np.int32)
        self.views[i] = np.asarray(
            self._logs.merge_max(self.views[i], merged), np.int32)
        return self.epoch(i)

    def epoch(self, i: int) -> int:
        """Replica ``i``'s config epoch per ITS OWN view (committed
        offset of its key — the module-doc contract)."""
        com = np.asarray(self._logs.committed_of(self.cfg,
                                                 self.views[i]),
                         np.int32)
        return int(com[i])

    def epochs(self) -> list:
        """Fleet-merged epoch vector (one per replica key)."""
        com = np.asarray(self._logs.committed_of(self.cfg,
                                                 self._merged()),
                         np.int32)
        return [int(c) for c in com]

    def state_of(self, i: int) -> Optional[str]:
        """Replica ``i``'s current admission state from the merged
        log: the LAST committed entry on its key."""
        merged = self._merged()
        e = self.epochs()[i]
        if e == 0:
            return None
        val = int(merged[i * self.cfg.capacity + e - 1])
        return _STATE_NAMES.get(val, f"state{val}")


class _Replica:
    """One fronted replica: address, raw stubs (the router owns
    failover — no client-level retries), health counters, in-flight
    gauge."""

    def __init__(self, index: int, address: str):
        self.index = index
        self.address = address
        self.proc: Optional[subprocess.Popen] = None
        self.healthy = False
        self.ever_down = False
        self.wiped = False
        self.consec_ok = 0
        self.consec_fail = 0
        self.inflight = 0
        self._connect(address)

    def _connect(self, address: str):
        from gossip_tpu.rpc.sidecar import SidecarClient
        self.address = address
        self.client = SidecarClient(address, max_attempts=1)
        self.stubs = {"run": self.client._run,
                      "ensemble": self.client._ensemble,
                      "health": self.client._health,
                      "metrics": self.client._metrics}

    def close(self):
        try:
            self.client.close()
        except Exception:
            pass


class Router:
    """Health-gated failover dispatch over a replica set (module doc).

    ``start_probes()`` runs the prober thread (``serve_router`` does);
    tests drive :meth:`observe_probe` directly with scripted
    sequences.  All state transitions go through the one lock and the
    control-plane log."""

    def __init__(self, addresses: Sequence[str],
                 cfg: Optional[FleetConfig] = None):
        if not addresses:
            raise ValueError("router needs at least one replica "
                             "address")
        self.cfg = cfg or FleetConfig()
        self._lock = threading.Lock()
        self.replicas = [_Replica(i, a) for i, a in enumerate(addresses)]
        self.control = ControlPlane(len(self.replicas),
                                    self.cfg.control_capacity)
        self.counters = {"dispatched": 0, "failovers": 0, "sheds": 0,
                         "deadline_rejects": 0, "downs": 0, "ups": 0,
                         "catchups": 0}
        # the router's own live-metrics window: end-to-end dispatch
        # latencies (queue wait + run + failover retries, as the
        # CLIENT experiences them) plus shed/failover counters — the
        # fleet half of the Metrics reply (docs/OBSERVABILITY.md)
        from gossip_tpu.utils import telemetry
        self.metrics = telemetry.MetricsWindow()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- health state machine -----------------------------------------

    def observe_probe(self, r: _Replica, ok: bool):
        """Feed one probe outcome into the hysteresis state machine
        (the prober calls this; tests script it).  Re-admission after
        a down needs ``up_after`` CONSECUTIVE healthy probes; initial
        admission needs one (nothing was lost yet)."""
        with self._lock:
            if ok:
                r.consec_fail = 0
                r.consec_ok += 1
                need = self.cfg.up_after if r.ever_down else 1
                if not r.healthy and r.consec_ok >= need:
                    self._mark_up_locked(r)
            else:
                r.consec_ok = 0
                r.consec_fail += 1
                if r.healthy and r.consec_fail >= self.cfg.down_after:
                    self._mark_down_locked(
                        r, f"{r.consec_fail} consecutive probe "
                        "failures")

    def _control_append(self, index: int, state: int):
        """Record a transition on the control-plane log; a FULL ring
        must never take health gating down with it (the prober thread
        and the dispatch failover path both run through here), so the
        overflow is ledgered + counted loudly and the admission state
        machine keeps working with the epoch record frozen."""
        try:
            return self.control.append(index, state)
        except ValueError as e:
            self.counters["control_plane_full"] = \
                self.counters.get("control_plane_full", 0) + 1
            from gossip_tpu.utils import telemetry
            telemetry.current().event(
                "control_plane_full", sync=False, replica=index,
                state=_STATE_NAMES.get(state, state),
                error=str(e).splitlines()[0][:200])
            return None

    def _mark_down_locked(self, r: _Replica, reason: str):
        if not r.healthy:
            return
        r.healthy = False
        r.ever_down = True
        r.consec_ok = 0
        self.counters["downs"] += 1
        epoch = self._control_append(r.index, STATE_DOWN)
        from gossip_tpu.utils import telemetry
        telemetry.current().event(
            "replica_down", sync=False, replica=r.index,
            address=r.address, reason=reason, epoch=epoch)

    def _mark_up_locked(self, r: _Replica):
        if r.wiped:
            # rejoin: the view died with the process — catch up from
            # the survivors' gossip, never from operator state
            epoch = self.control.catchup(r.index)
            r.wiped = False
            self.counters["catchups"] += 1
            from gossip_tpu.utils import telemetry
            telemetry.current().event(
                "control_catchup", sync=False, replica=r.index,
                epoch=epoch, epochs=self.control.epochs())
        r.healthy = True
        r.consec_fail = 0
        self.counters["ups"] += 1
        epoch = self._control_append(r.index, STATE_UP)
        from gossip_tpu.utils import telemetry
        telemetry.current().event(
            "replica_up", sync=False, replica=r.index,
            address=r.address, epoch=epoch)

    def mark_down(self, r: _Replica, reason: str):
        with self._lock:
            self._mark_down_locked(r, reason)

    def drain_replica(self, i: int, wait_s: float = 10.0) -> bool:
        """Router-initiated graceful drain: take replica ``i`` out of
        rotation FIRST (new dispatches stop landing on it), then wait
        for its in-flight requests to finish — the ordering twin of
        the batcher's stop-before-flush contract.  Returns True once
        in-flight hit zero."""
        r = self.replicas[i]
        self.mark_down(r, "drain")
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            with self._lock:
                if r.inflight == 0:
                    return True
            time.sleep(0.01)
        return False

    def replace_replica(self, i: int, address: str,
                        proc: Optional[subprocess.Popen] = None):
        """A replica process was replaced (fleet restart after a
        kill): point the handle at the new address, zero its
        control-plane view (the old process's state is gone), and
        leave it DOWN until the probe hysteresis re-admits it."""
        r = self.replicas[i]
        with self._lock:
            self._mark_down_locked(r, "replaced")
            r.close()
            r._connect(address)
            r.proc = proc
            r.consec_ok = r.consec_fail = 0
            # replicate the dying view's entries (incl. the down
            # transition just appended) before recycling it — an
            # unflushed wipe would lose epochs and alias offsets
            self.control.flush(i)
            self.control.wipe(i)
            r.wiped = True
        return r

    # -- probing -------------------------------------------------------

    def _probe(self, r: _Replica) -> bool:
        import grpc
        try:
            r.stubs["health"](b"{}", timeout=self.cfg.probe_timeout_s)
            return True
        except (grpc.RpcError, ValueError):
            # ValueError: grpcio raises it (not RpcError) when the
            # channel was CLOSED under this call — replace_replica
            # racing a probe; either way the probe failed, and the
            # prober thread must survive it
            return False

    def probe_once(self):
        for r in list(self.replicas):
            self.observe_probe(r, self._probe(r))
        with self._lock:
            self.control.gossip_tick()

    def start_probes(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._probe_loop,
                                        name="gossip-fleet-prober",
                                        daemon=True)
        self._thread.start()

    def _probe_loop(self):
        interval = self.cfg.probe_interval_ms / 1e3
        while not self._stop.wait(interval):
            self.probe_once()

    def wait_healthy(self, count: int, timeout_s: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.healthy_count() >= count:
                return True
            time.sleep(0.02)
        return False

    def healthy_count(self) -> int:
        with self._lock:
            return sum(1 for r in self.replicas if r.healthy)

    def stats(self) -> dict:
        with self._lock:
            return {**self.counters,
                    "replicas": len(self.replicas),
                    "healthy": sum(1 for r in self.replicas
                                   if r.healthy),
                    "inflight": [r.inflight for r in self.replicas],
                    "epochs": self.control.epochs(),
                    "states": [self.control.state_of(i)
                               for i in range(len(self.replicas))]}

    # -- dispatch ------------------------------------------------------

    def _pick(self, tried) -> Optional[_Replica]:
        """Least-inflight healthy replica not yet tried for this
        request (ties break to the lowest index — deterministic under
        serial load, spreading under concurrency); reserves an
        in-flight slot."""
        with self._lock:
            cands = [r for r in self.replicas
                     if r.healthy and r.index not in tried
                     and r.inflight < self.cfg.max_inflight]
            if not cands:
                return None
            r = min(cands, key=lambda x: (x.inflight, x.index))
            r.inflight += 1
            self.counters["dispatched"] += 1
            return r

    def dispatch(self, method: str, payload: bytes, context) -> bytes:
        """Route one RPC with failover (module-doc contract); aborts
        the gRPC context on shed/deadline/replica-reply errors.

        Tracing: the incoming ``gossip-trace-id`` metadata (rpc/sidecar
        TRACE_KEY) is read once, stamped on every span this dispatch
        emits (``dispatch_attempt`` per attempt, ``failover``/``shed``/
        ``deadline_exceeded`` on those paths, a terminal
        ``request_trace`` on success), and FORWARDED verbatim to the
        replica — the reply bytes stay untouched.  All emits are
        sync=False: the dispatch loop IS the timed path."""
        import grpc

        from gossip_tpu.rpc import batcher as B
        from gossip_tpu.rpc.sidecar import trace_id_of, trace_metadata
        from gossip_tpu.utils import telemetry
        deadline = B.deadline_of(context)
        trace_id = trace_id_of(context)
        metadata = trace_metadata(trace_id)
        t_start = time.monotonic()
        tried: list = []
        while True:
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    # the client already abandoned this request — a
                    # failover retry must never run it
                    self.counters["deadline_rejects"] += 1
                    telemetry.current().event(
                        "deadline_exceeded", sync=False,
                        source="router", method=method,
                        tried=list(tried), trace_id=trace_id)
                    context.abort(
                        grpc.StatusCode.DEADLINE_EXCEEDED,
                        "deadline expired before a replica could "
                        "serve the request (tried "
                        f"{len(tried)} replicas)")
            r = self._pick(tried)
            if r is None:
                with self._lock:
                    healthy = sum(1 for x in self.replicas
                                  if x.healthy)
                    inflight = [x.inflight for x in self.replicas]
                    self.counters["sheds"] += 1
                self.metrics.bump("sheds")
                reason = ("no healthy replica"
                          if healthy == 0 else "all replicas at the "
                          "in-flight cap")
                telemetry.current().event(
                    "shed", sync=False, method=method, reason=reason,
                    healthy=healthy, inflight=inflight,
                    tried=list(tried), trace_id=trace_id)
                context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED,
                    f"fleet shed: {reason} "
                    f"({healthy}/{len(self.replicas)} healthy); back "
                    "off and retry")
            if trace_id is not None:
                # one span per dispatch attempt: which replica, its
                # probe state at pick time, and the deadline budget
                # still available — the failover half of the waterfall
                telemetry.current().event(
                    "dispatch_attempt", sync=False, trace_id=trace_id,
                    method=method, attempt=len(tried) + 1,
                    replica=r.index, consec_ok=r.consec_ok,
                    consec_fail=r.consec_fail,
                    remaining_s=(None if remaining is None
                                 else round(remaining, 3)))
            try:
                try:
                    reply = r.stubs[method](payload, timeout=remaining,
                                            metadata=metadata)
                finally:
                    with self._lock:
                        r.inflight -= 1
            except (grpc.RpcError, ValueError) as e:
                code = e.code() if callable(getattr(e, "code", None)) \
                    else None
                if code in (grpc.StatusCode.UNAVAILABLE,
                            grpc.StatusCode.CANCELLED) \
                        or isinstance(e, ValueError):
                    # transport failure: the replica is gone
                    # (UNAVAILABLE — connection refused/reset) or its
                    # channel was closed under this call (CANCELLED
                    # mid-RPC, or grpcio's ValueError "Cannot invoke
                    # RPC on closed channel!" when the close landed
                    # before the invoke — a fleet restart replacing
                    # the handle races both ways).  Mark it down and
                    # replay on a survivor — safe in every case:
                    # requests are deterministic pure functions of
                    # their payload, so even a processed-but-reply-
                    # lost call replays to the bitwise-same answer
                    self.mark_down(r, f"dispatch {method}: "
                                   f"{code or type(e).__name__}")
                    tried.append(r.index)
                    with self._lock:
                        self.counters["failovers"] += 1
                    self.metrics.bump("failovers")
                    telemetry.current().event(
                        "failover", sync=False, method=method,
                        from_replica=r.index, tried=list(tried),
                        remaining_s=(None if remaining is None
                                     else round(remaining, 3)),
                        trace_id=trace_id)
                    continue
                # a WELL-FORMED replica reply (it processed the call)
                # or the propagated client deadline: verbatim, never
                # replayed
                details = e.details() if callable(
                    getattr(e, "details", None)) else str(e)
                context.abort(code, details or str(code))
            proxy_ms = (time.monotonic() - t_start) * 1e3
            self.metrics.record(proxy_ms)
            if trace_id is not None:
                # the terminal router-side waterfall half: end-to-end
                # proxy wall, retry count, and how much of the client
                # deadline this request consumed
                budget_s = (None if deadline is None
                            else deadline - t_start)
                telemetry.current().event(
                    "request_trace", sync=False, trace_id=trace_id,
                    source="router", method=method, replica=r.index,
                    retries=len(tried),
                    proxy_ms=round(proxy_ms, 1),
                    deadline_consumed=(
                        None if not budget_s
                        else round(proxy_ms / 1e3 / budget_s, 4)))
            return reply

    def close(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        for r in self.replicas:
            r.close()


def serve_router(addresses: Sequence[str], port: int = 0,
                 max_workers: int = 16,
                 cfg: Optional[FleetConfig] = None,
                 host: str = "127.0.0.1", start_probes: bool = True):
    """Start the fronting router over ``addresses``; returns
    ``(server, bound_port, router)``.  The router speaks the SAME
    ``gossip.Simulator`` service as a sidecar, so any ``SidecarClient``
    targets it transparently; its ``Health`` reply carries the fleet
    summary (healthy count, config epochs) instead of device facts.
    ``start_probes=False`` leaves the prober thread OFF — callers that
    need deterministic admission timing (the dry-run family, tests)
    drive ``router.probe_once()`` themselves."""
    import grpc
    from concurrent import futures

    from gossip_tpu.rpc.sidecar import SERVICE, _identity
    router = Router(addresses, cfg)

    def _run(request, context):
        return router.dispatch("run", request, context)

    def _ensemble(request, context):
        return router.dispatch("ensemble", request, context)

    def _health(request, context):
        s = router.stats()
        return json.dumps({
            "ok": s["healthy"] > 0, "router": True,
            "replicas": s["replicas"], "healthy": s["healthy"],
            "epochs": s["epochs"], "states": s["states"],
            "service": SERVICE}).encode()

    def _metrics(request, context):
        """The fleet metrics plane: the router's own dispatch window
        plus one row per replica (its Metrics reply fanned in, or the
        error that kept it out — a dead replica is a row, never a
        silent hole).  `gossip_tpu fleet-status` renders exactly this
        reply and exits nonzero on any degraded row."""
        s = router.stats()
        rows = []
        for r in list(router.replicas):
            row = {"replica": r.index, "address": r.address,
                   "healthy": r.healthy,
                   "state": s["states"][r.index],
                   "epoch": s["epochs"][r.index],
                   "inflight": s["inflight"][r.index]}
            try:
                raw = r.stubs["metrics"](
                    b"{}", timeout=router.cfg.probe_timeout_s)
                row["metrics"] = json.loads(raw)
            except Exception as e:          # noqa: BLE001 — a dead
                # replica's row must carry WHY, not kill the fan-out
                row["error"] = (f"{type(e).__name__}: "
                                + str(e).splitlines()[0][:200]
                                if str(e) else type(e).__name__)
            rows.append(row)
        return json.dumps({
            "ok": s["healthy"] > 0, "router": True,
            "service": SERVICE, "role": "router",
            "replicas": s["replicas"], "healthy": s["healthy"],
            "window": router.metrics.snapshot(),
            "counters": {k: s[k] for k in
                         ("dispatched", "failovers", "sheds",
                          "deadline_rejects", "downs", "ups",
                          "catchups") if k in s},
            "fleet": rows}).encode()

    server = grpc.server(futures.ThreadPoolExecutor(
        max_workers=max_workers))
    handlers = {
        "Run": grpc.unary_unary_rpc_method_handler(
            _run, request_deserializer=_identity,
            response_serializer=_identity),
        "Ensemble": grpc.unary_unary_rpc_method_handler(
            _ensemble, request_deserializer=_identity,
            response_serializer=_identity),
        "Health": grpc.unary_unary_rpc_method_handler(
            _health, request_deserializer=_identity,
            response_serializer=_identity),
        "Metrics": grpc.unary_unary_rpc_method_handler(
            _metrics, request_deserializer=_identity,
            response_serializer=_identity),
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),))
    bound = server.add_insecure_port(f"{host}:{port}")
    if bound == 0 and port != 0:
        raise OSError(f"could not bind {host}:{port} (port in use?)")
    server.start()
    if start_probes:
        router.start_probes()
    server.gossip_router = router
    return server, bound, router


# -- spawned fleets (subprocess replicas) ------------------------------

def spawn_replica(workdir: str, name: str, extra_argv=(),
                  env: Optional[dict] = None,
                  timeout_s: float = 90.0) -> Tuple[subprocess.Popen,
                                                    int]:
    """Launch one ``gossip_tpu serve --port 0`` replica subprocess and
    read its bound port from the serve command's first stdout JSON
    line.  Child output goes to ``<workdir>/<name>.out/.err`` FILES,
    never pipes (the crashloop lesson: a chatty child filling an
    undrained pipe blocks mid-write and deadlocks its supervisor)."""
    os.makedirs(workdir, exist_ok=True)
    out_path = os.path.join(workdir, name + ".out")
    err_path = os.path.join(workdir, name + ".err")
    argv = [sys.executable, "-m", "gossip_tpu", "serve", "--port", "0",
            *extra_argv]
    with open(out_path, "wb") as fo, open(err_path, "wb") as fe:
        proc = subprocess.Popen(argv, stdout=fo, stderr=fe,
                                env=env, cwd=_REPO)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            err = open(err_path, errors="replace").read()
            raise RuntimeError(
                f"replica {name} died during startup "
                f"rc={proc.returncode}:\n{err[-2000:]}")
        try:
            with open(out_path) as f:
                line = f.readline().strip()
            if line:
                return proc, int(json.loads(line)["port"])
        except (OSError, ValueError, KeyError):
            pass
        time.sleep(0.05)
    proc.kill()
    proc.wait()
    raise RuntimeError(f"replica {name} did not report a port within "
                       f"{timeout_s}s")


def fleet_env(compile_cache_dir: Optional[str] = None,
              platform: Optional[str] = None,
              devices: Optional[int] = None) -> dict:
    """Replica-child environment: repo importable, the ambient platform
    unless ``platform`` pins one (``JAX_PLATFORMS``), and an optional
    SHARED compile-cache dir so a respawned replica starts warm from
    its predecessors' executables.  Whether N replicas may run on that
    platform is :func:`check_replica_platform`'s call.

    ``devices`` threads the host-device-count env
    (``XLA_FLAGS=--xla_force_host_platform_device_count=K``) for
    mesh-sharded replicas: a child pinned to CPU has exactly ONE
    device without it, so its megabatch mesh would silently degrade —
    the bug the devices_per_replica satellite closes.  An ambient
    host-device-count flag is left alone (the caller pinned it);
    otherwise the flag is appended to any other ambient XLA_FLAGS."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    if compile_cache_dir is not None:
        env["GOSSIP_COMPILE_CACHE"] = compile_cache_dir
    if devices is not None and devices > 1:
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{devices}").strip()
    return env


def check_replica_platform(env: dict, replicas: int) -> None:
    """Refuse a fleet whose replicas would share one chip: a chip
    belongs to one process, so more than one replica needs the CPU
    platform (``JAX_PLATFORMS=cpu`` in the replicas' env); an unset
    platform means JAX's default, the chip where there is one."""
    platform = env.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if replicas > 1 and platform != "cpu":
        raise ValueError(
            f"{replicas} replica processes would share one chip "
            f"(replica platform {platform or 'default'!r}); a chip "
            "belongs to one process — run --replicas 1, or pin the "
            "replicas to the CPU (--replica-platform cpu)")


def _verify_replica_devices(addr: str, name: str, want: int,
                            timeout_s: float = 30.0):
    """The devices-per-replica gate: a freshly spawned child must
    REPORT the mesh width it actually serves with (the health reply's
    ``serving_devices`` — rpc/sidecar._health) or the fleet refuses
    loudly.  Without this, a replica missing the host-device-count env
    (or spawned without ``--devices``) comes up healthy, answers
    correctly, and silently serves a 1-device mesh — throughput
    degradation no probe would ever surface."""
    if want <= 1:
        return
    from gossip_tpu.rpc.sidecar import SidecarClient
    client = SidecarClient(addr)
    try:
        h = client.health(timeout=timeout_s)
    finally:
        client.close()
    got = int(h.get("serving_devices", h.get("devices", 1)))
    if got < want:
        raise RuntimeError(
            f"replica {name} at {addr} reports serving_devices={got} "
            f"but the fleet requires devices_per_replica={want} — the "
            "megabatch mesh silently degraded; spawn children with "
            "fleet_env(devices=K) (XLA_FLAGS=--xla_force_host_platform"
            "_device_count=K) AND the serve --devices flag")


class Fleet:
    """N spawned sidecar replicas behind a served router — the
    process-level fleet tools/fleet_crashloop.py SIGKILLs and the CLI
    ``route`` command runs.  ``kill(i)`` SIGKILLs a replica;
    ``restart(i)`` spawns a replacement on a fresh port and leaves the
    router's hysteresis to re-admit it (after a control-plane
    catchup).  When ``cfg.devices_per_replica > 1`` every spawn (and
    respawn) is gated by :func:`_verify_replica_devices` — a child
    serving a narrower mesh than configured fails the fleet loudly at
    startup instead of degrading throughput silently."""

    def __init__(self, n: Optional[int] = None,
                 cfg: Optional[FleetConfig] = None,
                 workdir: Optional[str] = None, replica_argv=(),
                 env: Optional[dict] = None, port: int = 0,
                 max_workers: int = 16):
        self.cfg = cfg or FleetConfig()
        n = self.cfg.replicas if n is None else n
        if workdir is None:
            import tempfile
            workdir = tempfile.mkdtemp(prefix="gossip_fleet_")
        self.workdir = workdir
        self.replica_argv = tuple(replica_argv)
        self.env = env if env is not None else fleet_env()
        check_replica_platform(self.env, n)
        self._gen = [0] * n
        procs, addrs = [], []
        try:
            for i in range(n):
                proc, rport = spawn_replica(workdir, f"r{i}_g0",
                                            self.replica_argv, self.env)
                procs.append(proc)
                addrs.append(f"127.0.0.1:{rport}")
                _verify_replica_devices(
                    addrs[-1], f"r{i}_g0", self.cfg.devices_per_replica)
            # serve_router inside the same net: a router bind failure
            # (port in use) must not strand N orphaned replica children
            self.server, self.port, self.router = serve_router(
                addrs, port=port, max_workers=max_workers, cfg=self.cfg)
        except Exception:
            for p in procs:
                p.kill()
                p.wait()
            raise
        for i, proc in enumerate(procs):
            self.router.replicas[i].proc = proc

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.port}"

    def kill(self, i: int) -> int:
        """SIGKILL replica ``i`` (the nemesis pointed at our own
        serving process); returns the killed pid."""
        r = self.router.replicas[i]
        if r.proc is None or r.proc.poll() is not None:
            raise ValueError(f"replica {i} has no live process")
        pid = r.proc.pid
        r.proc.send_signal(signal.SIGKILL)
        r.proc.wait()
        return pid

    def restart(self, i: int) -> str:
        """Spawn a replacement for replica ``i`` on a fresh port; the
        router wipes its control-plane view and the probe hysteresis
        re-admits it after ``up_after`` consecutive healthy probes
        (with a gossip catchup first)."""
        self._gen[i] += 1
        name = f"r{i}_g{self._gen[i]}"
        proc, rport = spawn_replica(self.workdir, name,
                                    self.replica_argv, self.env)
        addr = f"127.0.0.1:{rport}"
        try:
            _verify_replica_devices(addr, name,
                                    self.cfg.devices_per_replica)
        except Exception:
            # a degraded replacement must not join the rotation — kill
            # it and re-raise (the caller decides whether to retry)
            proc.kill()
            proc.wait()
            raise
        self.router.replace_replica(i, addr, proc)
        return addr

    def close(self):
        self.server.stop(grace=None)
        self.router.close()
        for r in self.router.replicas:
            if r.proc is not None and r.proc.poll() is None:
                r.proc.kill()
                r.proc.wait()
