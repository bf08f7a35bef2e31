"""Configuration dataclasses.

The reference has *no* config system at all — no flags, no env vars; its only
runtime configuration is the ``topology`` message (reference main.go:132-149).
The new framework makes every implicit constant explicit and sweepable:
cluster size N, fanout, protocol mode, topology family, mesh shape, backend.

All configs are frozen (hashable) so they can be closed over by jitted
functions or used as static arguments.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Protocol modes.
PUSH = "push"            # infected nodes push the rumor to sampled peers
PULL = "pull"            # all nodes pull from sampled peers
PUSH_PULL = "pushpull"   # both directions in one round
FLOOD = "flood"          # push to ALL neighbors every round (Go-parity mode:
                         # the reference relays to its full neighbor list,
                         # main.go:72-75; coverage(t) == BFS ball of radius t)
ANTI_ENTROPY = "antientropy"  # periodic bidirectional digest reconciliation
SWIM = "swim"            # SWIM-style suspect/confirm failure detection
RUMOR = "rumor"          # SIR rumor mongering: infective nodes push until
                         # they lose interest (counter death, models/rumor.py)

MODES = (PUSH, PULL, PUSH_PULL, FLOOD, ANTI_ENTROPY, SWIM, RUMOR)

RUMOR_VARIANTS = ("feedback", "blind")

# Topology families.
COMPLETE = "complete"    # implicit: uniform random peer, no neighbor table
RING = "ring"
GRID = "grid"
ERDOS_RENYI = "erdos_renyi"
WATTS_STROGATZ = "watts_strogatz"
POWER_LAW = "power_law"  # Barabasi-Albert preferential attachment

FAMILIES = (COMPLETE, RING, GRID, ERDOS_RENYI, WATTS_STROGATZ, POWER_LAW)


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Which graph the rumor spreads on.

    The reference receives its topology at runtime as a ``node -> [neighbors]``
    JSON map (main.go:132-149).  Here topologies are generated up front as
    static padded neighbor tables (``int32[N, D]`` with out-of-range sentinel
    padding) so shapes stay static for XLA; the ``complete`` family is
    *implicit* (uniform sampling, no table) so it scales to 10M+ nodes with
    zero adjacency memory.
    """

    family: str = COMPLETE
    n: int = 1024
    # family-specific parameters:
    k: int = 4            # ring/WS: neighbors per side*2; BA: edges per new node
    p: float = 0.01       # ER edge probability / WS rewire probability
    degree_cap: Optional[int] = None  # cap padded table width (power-law tails)
    seed: int = 0

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown topology family {self.family!r}")
        if self.n < 2:
            raise ValueError("need at least 2 nodes")


@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    """Gossip protocol semantics for one simulation.

    ``fanout`` generalizes the reference's fixed "all neighbors" fan-out
    (main.go:72): sampled-peer protocols contact ``fanout`` random peers per
    round; ``flood`` ignores it and contacts every neighbor, which is the
    faithful Go-parity behavior.
    """

    mode: str = PUSH
    fanout: int = 1
    rumors: int = 1          # R: number of concurrent rumors (multi-rumor broadcast)
    exclude_self: bool = True
    # anti-entropy: run a bidirectional digest reconciliation every
    # `period` rounds (both partners merge; off-rounds are quiescent).
    period: int = 1
    # SWIM parameters (see models/swim.py):
    swim_proxies: int = 3        # indirect-probe proxies (the "k" of SWIM)
    swim_suspect_rounds: int = 4 # rounds a suspect waits before confirm-dead
    swim_subjects: int = 8       # tracked subjects (window width when rotating)
    # Full-membership mode: the S-subject window rotates over ALL n nodes,
    # advancing by S every `swim_epoch_rounds` rounds (0 = auto: long enough
    # for detect + disseminate + confirm).  Every node is eventually watched
    # without an [N, N] view table (models/swim.py module doc).
    swim_rotate: bool = False
    swim_epoch_rounds: int = 0
    # Dissemination scatter implementation (models/swim.disseminate_max):
    # 'scatter' = direct duplicate-index scatter-max; 'sort' = sort pushes
    # by receiver then a sorted segment-max; 'pack' = the sort lowering
    # with the row gather done on 8/16-bit packed transport codes (an
    # order isomorphism on the wires a round-bounded run can produce) —
    # all bitwise-identical results (max is order-independent),
    # different TPU lowerings.  Hardware arbitrated
    # (artifacts/swim_ab_r04.json, 1M-node BASELINE shape): sort is
    # 2.2x faster steady-state AND 1.5x faster to compile than scatter,
    # so it is the default; 'scatter' stays selectable as the control;
    # 'pack' needs the driver's max_rounds to prove its lane bound and
    # falls back to 'sort' where that is unknown.
    swim_diss: str = "sort"
    # Per-round randomness lowering (models/swim.packed_round_draws):
    # 'split' = the original contract — an independent fold_in+draw
    # chain per random quantity (subject, proxies, peers, drop coins),
    # ~5 threefry streams per node per round; 'packed' = ONE per-node
    # key chain and ONE multi-word draw per round, bit-fields split
    # into the same quantities.  Packed is an OPT-IN statistical
    # contract change, not a relowering: trajectories differ from
    # 'split' (different streams), per-draw marginals are uniform up to
    # a documented modulo bias <= m/2^32 (m = the draw's range), and
    # mesh-invariance (draws keyed by global node id) is preserved —
    # the same contract class as the fused SI kernels vs the threefry
    # path.  Motivation: PERF.md names the per-node threefry chains as
    # a steady-state suspect at 1M nodes (VERDICT r4 task 4).
    swim_rng: str = "split"
    # Rumor mongering (mode='rumor', models/rumor.py): an infective
    # (node, rumor) stops spreading — becomes removed, SIR — once its
    # unnecessary-contact counter reaches `rumor_k` (Demers et al. §1.4
    # counter death).  'feedback' counts only pushes whose recipient
    # already knew the rumor; 'blind' counts every attempted push, lost
    # ones included, so a pair sends exactly `rumor_k` times
    # (memberlist's retransmit limit).
    rumor_k: int = 2
    rumor_variant: str = "feedback"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown protocol mode {self.mode!r}")
        if self.fanout < 1:
            raise ValueError("fanout must be >= 1")
        if self.rumors < 1:
            raise ValueError("rumors must be >= 1")
        if self.swim_subjects < 1:
            raise ValueError("swim_subjects must be >= 1")
        if self.swim_epoch_rounds < 0:
            raise ValueError("swim_epoch_rounds must be >= 0 (0 = auto)")
        if self.swim_diss not in ("scatter", "sort", "pack"):
            raise ValueError(f"unknown swim_diss {self.swim_diss!r}; "
                             "choose 'scatter', 'sort', or 'pack'")
        if self.swim_rng not in ("split", "packed"):
            raise ValueError(f"unknown swim_rng {self.swim_rng!r}; "
                             "choose 'split' or 'packed'")
        if self.rumor_k < 1:
            raise ValueError("rumor_k must be >= 1")
        if self.rumor_variant not in RUMOR_VARIANTS:
            raise ValueError(f"unknown rumor_variant "
                             f"{self.rumor_variant!r}; choose from "
                             f"{RUMOR_VARIANTS}")


# Ceiling on the schedule horizon (ops/nemesis table length T): the
# lowering materializes [T]-sized device tables plus host lists per
# trace, so an absurd partition/ramp end must error loudly instead of
# hanging and OOMing.  100k rounds x 4 bytes = 400 KB per table —
# orders of magnitude past any real run ("partitioned forever" just
# needs end >= the run's max_rounds: beyond the horizon the schedule
# holds its final row, i.e. partitions closed, drop at the ramp's
# final value).
MAX_CHURN_HORIZON = 100_000


@dataclasses.dataclass(frozen=True)
class ChurnConfig:
    """A fault *program over rounds* — the compiled nemesis schedule.

    Maelstrom's nemesis partitions the network MID-RUN and heals it
    (reference main.go:77-87 survives via at-least-once retry); the
    static masks of :class:`FaultConfig` cannot express that.  This
    config scripts time-varying faults, lowered by
    :mod:`gossip_tpu.ops.nemesis` into small round-indexed schedule
    tables consumed INSIDE the compiled round loops:

    * ``events`` — crash/recover churn: ``(node, die_round,
      recover_round)`` triples.  The node is down for rounds
      ``die_round <= r < recover_round`` (it neither sends, responds,
      nor receives); ``recover_round < 0`` means it never comes back.
      Scripted events override nothing else — they stack on top of the
      static ``node_death_rate`` mask (and a scripted death of the
      rumor origin is honored: explicit scripts are the user's call,
      unlike the random mask, which pins the origin alive).
    * ``partitions`` — network partition windows: ``(start, end,
      cut)``.  For rounds ``start <= r < end`` every message crossing
      the node-id cut (one side is ``id < cut``, the other
      ``id >= cut``) is lost; both sides keep gossiping internally.
      Applied to the dense, sparse, and halo exchanges (the
      plane-sharded fused engine has no per-pair messages to cut —
      it rejects partition windows rather than silently ignoring
      them).  A cut at a multiple of ``n_pad / n_devices`` is
      shard-group aligned (no shard straddles the cut).  Windows must
      not overlap.
    * ``ramp`` — a drop-rate ramp ``(start, end, from_p, to_p)``:
      ``drop_prob`` is ``FaultConfig.drop_prob`` before ``start``,
      moves linearly from ``from_p`` to ``to_p`` over
      ``[start, end)``, and holds ``to_p`` after.

    All fields JSON-friendly (the RPC ``fault.churn`` object delivers
    lists, coerced here).  An all-default ChurnConfig is normalized to
    ``None`` by :class:`FaultConfig` so the fault-free/static-only hot
    paths stay untouched.
    """

    events: Tuple[Tuple[int, int, int], ...] = ()
    partitions: Tuple[Tuple[int, int, int], ...] = ()
    ramp: Optional[Tuple[int, int, float, float]] = None

    def __post_init__(self):
        object.__setattr__(self, "events", tuple(
            tuple(int(x) for x in e) for e in self.events))
        object.__setattr__(self, "partitions", tuple(
            tuple(int(x) for x in w) for w in self.partitions))
        if self.ramp is not None:
            r = tuple(self.ramp)
            if len(r) != 4:
                raise ValueError(f"drop ramp {r} must be "
                                 "(start, end, from_p, to_p)")
            object.__setattr__(
                self, "ramp",
                (int(r[0]), int(r[1]), float(r[2]), float(r[3])))
        for e in self.events:
            if len(e) != 3:
                raise ValueError(f"churn event {e} must be "
                                 "(node, die_round, recover_round)")
            node, die, rec = e
            if node < 0:
                raise ValueError(f"churn event node {node} must be >= 0")
            if die < 0:
                raise ValueError(f"churn event die_round {die} must be "
                                 ">= 0")
            if rec >= 0 and rec <= die:
                raise ValueError(
                    f"churn event {e}: recover_round must be > die_round "
                    "(or < 0 for a permanent crash)")
            if die > MAX_CHURN_HORIZON or rec > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"churn event {e}: rounds exceed the schedule "
                    f"horizon cap {MAX_CHURN_HORIZON} (rec < 0 already "
                    "means 'down forever'; larger values would collide "
                    "with the kernels' int32 NEVER sentinel)")
        nodes = [e[0] for e in self.events]
        if len(set(nodes)) != len(nodes):
            raise ValueError("churn events must script each node at most "
                             "once (one die/recover pair per node)")
        spans = []
        for w in self.partitions:
            if len(w) != 3:
                raise ValueError(f"partition window {w} must be "
                                 "(start, end, cut)")
            start, end, cut = w
            if start < 0 or end <= start:
                raise ValueError(f"partition window {w}: need "
                                 "0 <= start < end")
            if cut <= 0:
                raise ValueError(f"partition window {w}: cut must be a "
                                 "positive node id (both sides non-empty)")
            if end > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"partition window {w}: end {end} exceeds the "
                    f"schedule horizon cap {MAX_CHURN_HORIZON} (any end "
                    ">= the run's max_rounds already means 'open for "
                    "the whole run' — the lowered tables are sized by "
                    "the largest end)")
            spans.append((start, end))
        spans.sort()
        for (s0, e0), (s1, _) in zip(spans, spans[1:]):
            if s1 < e0:
                raise ValueError("partition windows overlap: "
                                 f"[{s0}, {e0}) and [{s1}, ...)")
        if self.ramp is not None:
            start, end, p0, p1 = self.ramp
            if start < 0 or end <= start:
                raise ValueError(f"drop ramp {self.ramp}: need "
                                 "0 <= start < end")
            if end > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"drop ramp {self.ramp}: end {end} exceeds the "
                    f"schedule horizon cap {MAX_CHURN_HORIZON} (the "
                    "ramp holds its final value beyond end, so a "
                    "shorter ramp expresses the same steady state)")
            for p in (p0, p1):
                if not 0.0 <= p <= 1.0:
                    raise ValueError(
                        f"drop ramp probability {p} outside [0, 1]")

    @property
    def empty(self) -> bool:
        return not (self.events or self.partitions or self.ramp)

    def horizon(self) -> int:
        """Rounds after which the schedule is constant: the table
        length T of the ops/nemesis lowering.  Beyond it, partitions
        are closed and the drop rate holds its final value."""
        ends = [1]
        ends += [end for _, end, _ in self.partitions]
        if self.ramp is not None:
            ends.append(self.ramp[1])
        return max(ends) + 1


# Byzantine liar actions (ops/nemesis byz lowering).  Every kind is a
# SERVE-side transform of the state row a liar hands to pulling peers:
BYZ_CORRUPT = "corrupt"        # flip payload words it forwards (xor arg)
BYZ_REPLAY = "replay"          # serve a stale snapshot of its own planes
BYZ_EQUIVOCATE = "equivocate"  # different state per partner (keyed by id)
BYZ_INFLATE = "inflate"        # write columns/keys it does not own

BYZ_KINDS = (BYZ_CORRUPT, BYZ_REPLAY, BYZ_EQUIVOCATE, BYZ_INFLATE)


@dataclasses.dataclass(frozen=True)
class ByzConfig:
    """A scripted *byzantine program* — nodes that LIE (ROADMAP item 4),
    the adversarial half of the nemesis subsystem.

    Where :class:`ChurnConfig` scripts fail-stop faults (a down node is
    silent), this scripts liars: ``liars`` are ``(node, round, kind,
    arg)`` quadruples — from ``round`` onward, ``node`` serves every
    pull with a transformed state row (:data:`BYZ_KINDS` catalog;
    docs/ROBUSTNESS.md "Byzantine adversaries").  The program lowers to
    padded runtime operands on the step's ``tables`` tail exactly like
    the churn schedule (ops/nemesis.byz_args — compiled loops carry
    shapes, never liar content), and the transforms render RECEIVER
    side, so a liar's own durable state stays honest: the lie is on the
    wire, which is the BFT model (a faulty replica can say anything but
    cannot rewrite history it already gossiped).

    A liar corrupts only components it does NOT own — its own
    column/element/key writes are its own to make and are
    indistinguishable from honest writes (the standard BFT limitation;
    the ``byz_conv`` metric judges convergence on HONEST-owned
    components for exactly this reason).

    ``quorum`` is the echo-sampling threshold q of the defended packed
    set kernels: a broadcast bit not served by its owner directly is
    admitted only when seen from >= q distinct partners in one round.
    It lowers as a TRACED scalar operand, and bounds the non-colluding
    liar tolerance at f < q (q identically-scripted colluders can meet
    their own quorum — docs/ROBUSTNESS.md).

    One action per node (the ChurnConfig one-event rule); an empty
    program is normalized to ``None`` by :class:`FaultConfig`.
    """

    liars: Tuple[Tuple[int, int, str, int], ...] = ()
    quorum: int = 2

    def __post_init__(self):
        object.__setattr__(self, "liars", tuple(
            (int(a[0]), int(a[1]), str(a[2]), int(a[3]) if len(a) > 3
             else 0)
            for a in (tuple(x) for x in self.liars)))
        for a in self.liars:
            if len(a) != 4:
                raise ValueError(f"byz liar {a} must be "
                                 "(node, round, kind[, arg])")
            node, rnd, kind, arg = a
            if node < 0:
                raise ValueError(f"byz liar node {node} must be >= 0")
            if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"byz liar round {rnd} outside "
                    f"[0, {MAX_CHURN_HORIZON}] (the schedule horizon "
                    "cap, shared with ChurnConfig)")
            if kind not in BYZ_KINDS:
                raise ValueError(f"unknown byz kind {kind!r}; choose "
                                 f"from {BYZ_KINDS}")
            if arg < 0:
                raise ValueError(f"byz liar {a}: arg must be >= 0 (an "
                                 "xor/inflation pattern, not a sign)")
        nodes = [a[0] for a in self.liars]
        if len(set(nodes)) != len(nodes):
            raise ValueError("byz program must script each node at "
                             "most once (one standing lie per node — "
                             "the ChurnConfig one-event rule)")
        if not 1 <= self.quorum <= 3:
            raise ValueError(
                f"quorum={self.quorum} outside [1, 3]: the defended "
                "set kernels count echoes with a carry-save chain of "
                "depth 3 (ops/crdt.pull_merge_crdt_byz); a larger "
                "quorum needs a deeper chain, added when an engine "
                "needs it")

    @property
    def empty(self) -> bool:
        return not self.liars


# CRDT payload kinds (ops/crdt.py).  The Gossip Glomers sibling
# workloads of the reference's broadcast: same epidemic exchange, a
# commutative-merge payload instead of the infected bit.
GCOUNTER = "gcounter"      # grow-only counter: per-node shards, merge=max
PNCOUNTER = "pncounter"    # inc/dec counter: P and N shard planes
GSET = "gset"              # grow-only set: packed add bit-planes, merge=OR
ORSET = "orset"            # add/remove set: add + tombstone planes, merge=OR
VCLOCK = "vclock"          # per-node vector clocks, merge=elementwise max

CRDT_KINDS = (GCOUNTER, PNCOUNTER, GSET, ORSET, VCLOCK)
CRDT_COUNTER_KINDS = (GCOUNTER, PNCOUNTER)
CRDT_SET_KINDS = (GSET, ORSET)


@dataclasses.dataclass(frozen=True)
class CrdtConfig:
    """A commutative-merge payload workload (ops/crdt.py, models/crdt.py).

    The injections are a *program over rounds*, exactly like the nemesis
    schedule: counter ``adds`` are ``(node, round, amount)`` triples
    (node adds ``amount`` to its own shard at ``round``; for
    ``pncounter`` a negative amount lands in the N plane, for
    ``gcounter`` amounts must be positive), set ``set_adds`` /
    ``set_removes`` are ``(element, round)`` pairs injected at the
    element's owner node ``(origin + element) % n`` (the rumor-origin
    convention).  Empty ``adds`` on a counter kind means the default
    program: node ``j`` adds ``1 + j % 7`` at round 0 (closed form, so
    no O(N) config is ever materialized); empty ``set_adds`` means
    every element is added at round 0 at its owner.

    Ground truth is the merge of all *applied* injections — an
    injection is applied iff its owner is alive at the injection round
    AND eventually alive under the fault program (ops/crdt.ground
    truth doc: the batched analog of the Maelstrom counter checker
    counting only ACKED adds — a node destined for permanent death
    contributes nothing, which is what makes exact value convergence
    on the eventual-alive set a guaranteed invariant).
    """

    kind: str = GCOUNTER
    adds: Tuple[Tuple[int, int, int], ...] = ()
    set_adds: Tuple[Tuple[int, int], ...] = ()
    set_removes: Tuple[Tuple[int, int], ...] = ()
    elements: int = 64          # set element universe E (W = ceil(E/32))

    def __post_init__(self):
        object.__setattr__(self, "adds", tuple(
            tuple(int(x) for x in a) for a in self.adds))
        object.__setattr__(self, "set_adds", tuple(
            tuple(int(x) for x in a) for a in self.set_adds))
        object.__setattr__(self, "set_removes", tuple(
            tuple(int(x) for x in a) for a in self.set_removes))
        if self.kind not in CRDT_KINDS:
            raise ValueError(f"unknown CRDT kind {self.kind!r}; choose "
                             f"from {CRDT_KINDS}")
        if self.elements < 1:
            raise ValueError("elements must be >= 1")
        if self.kind in CRDT_SET_KINDS:
            if self.adds:
                raise ValueError(f"{self.kind} takes set_adds/"
                                 "set_removes, not counter adds")
        else:
            if self.set_adds or self.set_removes:
                raise ValueError(f"{self.kind} takes counter adds, not "
                                 "set_adds/set_removes")
        if self.kind == VCLOCK and self.adds:
            # vclock carries no injection program at all (owner ticks
            # only) — silently dropping a scripted one would violate
            # the reject-loudly policy every other kind mismatch obeys
            raise ValueError("vclock takes no injection program (the "
                             "owner tick is the only local event); "
                             "drop the adds")
        if self.kind == GSET and self.set_removes:
            raise ValueError("gset is grow-only; removes need kind="
                             "'orset'")
        for a in self.adds:
            if len(a) != 3:
                raise ValueError(f"counter add {a} must be "
                                 "(node, round, amount)")
            node, rnd, amt = a
            if node < 0:
                raise ValueError(f"add node {node} must be >= 0")
            if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"add round {rnd} outside [0, {MAX_CHURN_HORIZON}] "
                    "(the schedule horizon cap, shared with ChurnConfig)")
            if self.kind == GCOUNTER and amt <= 0:
                raise ValueError(
                    f"gcounter add {a}: amounts must be positive "
                    "(grow-only; use pncounter for decrements)")
            if self.kind == PNCOUNTER and amt == 0:
                raise ValueError(f"pncounter add {a}: amount must be "
                                 "nonzero")
        for name, pairs in (("set_add", self.set_adds),
                            ("set_remove", self.set_removes)):
            for p in pairs:
                if len(p) != 2:
                    raise ValueError(f"{name} {p} must be "
                                     "(element, round)")
                elem, rnd = p
                if not 0 <= elem < self.elements:
                    raise ValueError(
                        f"{name} element {elem} outside the universe "
                        f"[0, {self.elements})")
                if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                    raise ValueError(
                        f"{name} round {rnd} outside "
                        f"[0, {MAX_CHURN_HORIZON}]")
        seen_elems = [e for e, _ in self.set_adds]
        if len(set(seen_elems)) != len(seen_elems):
            raise ValueError("set_adds must script each element at most "
                             "once (the packed-plane OR-set models one "
                             "unique add tag per element — "
                             "docs/WORKLOADS.md)")
        seen_rems = [e for e, _ in self.set_removes]
        if len(set(seen_rems)) != len(seen_rems):
            raise ValueError("set_removes must script each element at "
                             "most once")
        # A remove at-or-before its element's add would make the
        # packed tombstone plane remove-wins where the documented
        # contract is add-wins == 2P (the remove must happen-after the
        # observed add tag) — reject the silent semantic fork.  An
        # unscripted add means the default program's round 0; a remove
        # of a never-added element is a harmless no-op and allowed.
        add_round = {e: r for e, r in self.set_adds}
        for e, rr in self.set_removes:
            ra = add_round.get(e, 0 if not self.set_adds else None)
            if ra is not None and rr <= ra:
                raise ValueError(
                    f"set_remove ({e}, {rr}) fires at or before the "
                    f"element's add (round {ra}): a remove must "
                    "happen-after the add it tombstones, or add-wins "
                    "and 2P semantics diverge (docs/WORKLOADS.md)")

    def horizon(self) -> int:
        """Rounds after which no further injection fires (the zero-row
        steady state of the lowered injection tables)."""
        rounds = [0]
        rounds += [r for _, r, _ in self.adds]
        rounds += [r for _, r in self.set_adds]
        rounds += [r for _, r in self.set_removes]
        return max(rounds) + 1


@dataclasses.dataclass(frozen=True)
class LogConfig:
    """A replicated kafka-style log workload (ops/logs.py,
    models/log.py) — the last Gossip Glomers sibling of the
    reference's broadcast: ordered per-key offset payloads with
    committed offsets, gossiped as fixed-capacity ring buffers whose
    merge is elementwise max over owner-indexed slot planes.

    ``sends`` are ``(node, key, round, value)`` — node appends
    ``value`` to key's log at ``round``; ``commits`` are ``(node, key,
    round, upto)`` — node commits key's offsets below
    ``min(upto, acked_len(key))`` at ``round``.  Both are *programs
    over rounds* lowered to runtime operands (the nemesis/CRDT
    pattern); empty means the closed-form default programs
    (ops/logs.log_sends / log_commits — no O(K) config object).

    Contracts the validation enforces loudly:

    * values >= 1 (0 is the empty-slot sentinel — a 0 value would be
      invisible to the merge);
    * at most ``capacity`` sends per key (the ring position is
      ``offset % capacity``; more sends would wrap onto an unconsumed
      slot and silently alias two offsets);
    * per-key script order is round-nondecreasing (offsets are
      assigned in script order — ops/logs.send_offsets — so this is
      what makes offset order equal time order, the ORDERED half of
      the kafka invariants);
    * commit ``upto`` >= 1 (committing nothing is the default state).
    """

    keys: int = 4               # K: number of per-key logs
    capacity: int = 16          # C: ring slots per key
    sends: Tuple[Tuple[int, int, int, int], ...] = ()
    commits: Tuple[Tuple[int, int, int, int], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "sends", tuple(
            tuple(int(x) for x in s) for s in self.sends))
        object.__setattr__(self, "commits", tuple(
            tuple(int(x) for x in c) for c in self.commits))
        if self.keys < 1:
            raise ValueError("keys must be >= 1")
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        per_key_rounds: dict = {}
        for s in self.sends:
            if len(s) != 4:
                raise ValueError(f"log send {s} must be "
                                 "(node, key, round, value)")
            node, key, rnd, val = s
            if node < 0:
                raise ValueError(f"send node {node} must be >= 0")
            if not 0 <= key < self.keys:
                raise ValueError(f"send key {key} outside "
                                 f"[0, {self.keys})")
            if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"send round {rnd} outside [0, {MAX_CHURN_HORIZON}]"
                    " (the schedule horizon cap, shared with "
                    "ChurnConfig)")
            if val < 1:
                raise ValueError(
                    f"send {s}: values must be >= 1 (0 is the "
                    "empty-slot sentinel the merge identity rides)")
            rounds = per_key_rounds.setdefault(key, [])
            if rounds and rnd < rounds[-1]:
                raise ValueError(
                    f"send {s}: key {key}'s sends must be scripted in "
                    "round-nondecreasing order — offsets are assigned "
                    "in script order, so out-of-order rounds would "
                    "break offset-order == time-order (the kafka "
                    "ordered-append contract, ops/logs module doc)")
            rounds.append(rnd)
        for key, rounds in per_key_rounds.items():
            if len(rounds) > self.capacity:
                raise ValueError(
                    f"key {key} scripts {len(rounds)} sends but "
                    f"capacity is {self.capacity}: the ring would wrap "
                    "onto an unconsumed slot and alias two offsets — "
                    "raise capacity or split the program")
        # the DEFAULT send program appends 4 entries per key
        # (ops/logs.log_sends) — it must obey the same no-wrap
        # contract, or an unscripted tiny-capacity config would alias
        # slots silently where a scripted one errors loudly
        if not self.sends and self.capacity < 4:
            raise ValueError(
                f"capacity={self.capacity} cannot hold the default "
                "send program (4 sends per key — ops/logs.log_sends): "
                "the ring would wrap and alias offsets; raise "
                "capacity to >= 4 or script the sends")
        for c in self.commits:
            if len(c) != 4:
                raise ValueError(f"log commit {c} must be "
                                 "(node, key, round, upto)")
            node, key, rnd, upto = c
            if node < 0:
                raise ValueError(f"commit node {node} must be >= 0")
            if not 0 <= key < self.keys:
                raise ValueError(f"commit key {key} outside "
                                 f"[0, {self.keys})")
            if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"commit round {rnd} outside "
                    f"[0, {MAX_CHURN_HORIZON}]")
            if upto < 1:
                raise ValueError(f"commit {c}: upto must be >= 1 "
                                 "(nothing-committed is the default "
                                 "state, not a scripted op)")

    def horizon(self) -> int:
        """Rounds after which no further send/commit fires (the
        zero-row steady state of the lowered injection tables).  The
        DEFAULT programs end at rounds 3 (sends) / 4 (commits —
        ops/logs.log_sends / log_commits), so an empty config still
        needs max_rounds > 4."""
        rounds = [3 if not self.sends else 0,
                  4 if not self.commits else 0]
        rounds += [r for _, _, r, _ in self.sends]
        rounds += [r for _, _, r, _ in self.commits]
        return max(rounds) + 1


# Txn traffic load shapes (ops/registers.txn_writes): how the default
# skewed write program spreads over rounds.
TXN_LOADS = ("uniform", "diurnal")


@dataclasses.dataclass(frozen=True)
class TxnConfig:
    """A totally-available transaction workload over last-writer-wins
    registers (ops/registers.py, models/register.py) — the Maelstrom
    ``txn-rw-register`` shape batched: K per-key LWW registers gossiped
    on the pull fabric, each a ``(value, timestamp)`` pair whose
    timestamp is the lexicographic ``(round, owner)`` key packed into
    one int32 plane, so the merge is the exact lattice join (the
    PR 8/10 column discipline extended to a two-plane key).

    ``writes`` script the transactions' write micro-ops as a *program
    over rounds* — ``(node, key, round, value)`` quadruples, lowered to
    runtime operands exactly like the nemesis schedule and the CRDT/log
    injections (compiled loops carry shapes, never content).  Empty
    means the **skewed default program** (ops/registers.txn_writes): a
    closed-form generator — no RNG, no O(T) config object — of
    ``txns`` writes whose key popularity is zipfian(``zipf_alpha``)
    over ``keys``, optionally concentrated onto key 0 with probability
    ``hot_key`` during the middle third of the program (a hot-key
    storm), spread over ``spread_rounds`` rounds by the ``load`` curve
    (``uniform``, or ``diurnal``: density ``1 + sin`` peaking
    mid-window).  Because the builders are closed forms over the
    config scalars, a scenario sweep across skews stays one
    executable.

    Contracts validated loudly:

    * values >= 1 (0 is the never-written sentinel of the value
      plane);
    * at most one write per ``(key, round, node)`` — the packed
      timestamp is what makes LWW deterministic, and two writes
      sharing one timestamp would fork the winner silently (the
      CrdtConfig one-add-tag convention; the default program is
      collision-free by construction, re-checked at lowering);
    * zipf_alpha > 0, 0 <= hot_key <= 1, spread_rounds >= 1.

    Ground truth is LWW over the *applied* writes — a write applies
    iff its owner is alive at the write round AND eventually alive
    under the fault program (the acked-adds rule shared with ops/crdt
    and ops/logs) — computed in-trace from the same operands and
    liveness predicate as the in-loop injection.
    """

    keys: int = 8               # K: register universe
    txns: int = 16              # T: default-program write count
    zipf_alpha: float = 1.1     # key-popularity skew (> 0)
    hot_key: float = 0.0        # storm mass onto key 0, middle third
    load: str = "uniform"       # writes-over-rounds shape (TXN_LOADS)
    spread_rounds: int = 8      # rounds the default program spans
    writes: Tuple[Tuple[int, int, int, int], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "writes", tuple(
            tuple(int(x) for x in w) for w in self.writes))
        if self.keys < 1:
            raise ValueError("keys must be >= 1")
        if self.txns < 1:
            raise ValueError("txns must be >= 1")
        if self.zipf_alpha <= 0:
            raise ValueError(
                f"zipf_alpha={self.zipf_alpha} must be > 0 (1.0 is "
                "classic zipf; larger is more skewed)")
        if not 0.0 <= self.hot_key <= 1.0:
            raise ValueError(
                f"hot_key={self.hot_key} outside [0, 1] (the storm "
                "probability mass redirected onto key 0)")
        if self.load not in TXN_LOADS:
            raise ValueError(f"unknown load {self.load!r}; choose "
                             f"from {TXN_LOADS}")
        if self.spread_rounds < 1:
            raise ValueError("spread_rounds must be >= 1")
        seen = set()
        for w in self.writes:
            if len(w) != 4:
                raise ValueError(f"txn write {w} must be "
                                 "(node, key, round, value)")
            node, key, rnd, val = w
            if node < 0:
                raise ValueError(f"write node {node} must be >= 0")
            if not 0 <= key < self.keys:
                raise ValueError(f"write key {key} outside "
                                 f"[0, {self.keys})")
            if rnd < 0 or rnd > MAX_CHURN_HORIZON:
                raise ValueError(
                    f"write round {rnd} outside [0, {MAX_CHURN_HORIZON}]"
                    " (the schedule horizon cap, shared with "
                    "ChurnConfig)")
            if val < 1:
                raise ValueError(
                    f"write {w}: values must be >= 1 (0 is the "
                    "never-written sentinel of the value plane)")
            trip = (key, rnd, node)
            if trip in seen:
                raise ValueError(
                    f"write {w}: duplicate (key, round, node) — the "
                    "(round, owner) timestamp is what makes LWW "
                    "deterministic, and two writes sharing one "
                    "timestamp would fork the winner silently "
                    "(docs/WORKLOADS.md \"Transactions\")")
            seen.add(trip)

    def horizon(self) -> int:
        """Rounds after which no further write fires (the zero-row
        steady state of the lowered write tables).  The DEFAULT
        program spans ``spread_rounds`` rounds."""
        if self.writes:
            return max(r for _, _, r, _ in self.writes) + 1
        return self.spread_rounds


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """In-kernel fault injection.

    The reference never injects faults itself — Maelstrom partitions the
    network externally, and the node survives via an unbounded retry loop
    (main.go:77-87).  In the batched simulator faults are masks applied inside
    the round kernel: a dead node neither sends nor receives; a dropped edge
    loses this round's message (retried implicitly next round, which mirrors
    at-least-once delivery + idempotent receipt, main.go:80-87 + 113).

    ``churn`` scripts TIME-VARYING faults (crash/recover churn,
    partition windows, drop-rate ramps — :class:`ChurnConfig`), lowered
    into round-indexed schedule tables by :mod:`gossip_tpu.ops.nemesis`
    and consumed inside the compiled round loops; ``None`` (or an
    all-default ChurnConfig, normalized to None here) keeps every
    kernel on its static-fault path, bitwise unchanged.
    """

    node_death_rate: float = 0.0   # fraction of nodes dead (static mask)
    drop_prob: float = 0.0         # per-message drop probability per round
    seed: int = 0
    # Explicit failure scenario (SWIM kernels): exactly these node ids fail
    # permanently at round `fail_round`.  Complements the random static mask
    # above; empty = no scripted deaths.  Reachable from the CLI
    # (--dead-nodes/--fail-round) and the RPC `fault` object.
    dead_nodes: Tuple[int, ...] = ()
    fail_round: int = 0
    # Time-varying fault schedule (CLI --churn-event/--partition/
    # --drop-ramp, RPC fault.churn object).
    churn: Optional["ChurnConfig"] = None
    # Scripted byzantine liars (CLI --byz NODE:ROUND:KIND[:ARG], RPC
    # fault.byz object) — ByzConfig; None keeps every kernel on its
    # honest-exchange path, bitwise unchanged.
    byz: Optional["ByzConfig"] = None

    def __post_init__(self):
        # JSON/RPC delivers lists; coerce so the config stays hashable.
        if not isinstance(self.dead_nodes, tuple):
            object.__setattr__(self, "dead_nodes", tuple(self.dead_nodes))
        if any(d < 0 for d in self.dead_nodes):
            raise ValueError("dead_nodes must be non-negative node ids")
        if self.fail_round < 0:
            raise ValueError("fail_round must be >= 0")
        # probabilities are probabilities: an out-of-range rate would
        # silently skew the bernoulli mask draws instead of failing
        if not 0.0 <= self.node_death_rate <= 1.0:
            raise ValueError(
                f"node_death_rate={self.node_death_rate} outside [0, 1]")
        if not 0.0 <= self.drop_prob <= 1.0:
            raise ValueError(
                f"drop_prob={self.drop_prob} outside [0, 1]")
        if isinstance(self.churn, dict):      # RPC: nested JSON object
            object.__setattr__(self, "churn", ChurnConfig(**self.churn))
        if self.churn is not None and self.churn.empty:
            # all-default schedule == no schedule: keep the static hot
            # path (and its bitwise pins) for configs that carry a
            # vacuous churn object
            object.__setattr__(self, "churn", None)
        if isinstance(self.byz, dict):        # RPC: nested JSON object
            object.__setattr__(self, "byz", ByzConfig(**self.byz))
        if self.byz is not None and self.byz.empty:
            # no liars == no byzantine program: keep the honest
            # exchange path (the churn normalization rule)
            object.__setattr__(self, "byz", None)


ENGINES = ("auto", "fused", "xla", "native")


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Simulation driver parameters.

    ``engine`` selects the round implementation:

    * ``auto``  — the best eligible engine.  On a TPU, single-device
      pull runs on the implicit complete topology (<= 32 rumors) route
      to the fused Pallas kernel automatically (meta records
      ``engine_auto``) — since round 4 that includes static-fault and
      --curve runs (in-kernel masks; fixed-length scan twins); other
      pull / anti-entropy runs take the bit-packed XLA fast path
      (models/si_packed.py); everything else the bool kernels
      (models/si.py).  Works on any backend, any mode.
    * ``xla``   — force the XLA kernels even where the fused engine is
      eligible (pull/anti-entropy bit-packed, bool otherwise) — the
      opt-out for cross-validating against the sharded paths, whose
      threefry partner streams match the single-device XLA engine
      bitwise but not the fused kernel's hardware-PRNG stream.
    * ``fused`` — force the fused Pallas kernels (ops/pallas_round.py):
      hardware-PRNG partner sampling + in-row gather + OR-merge in one
      ``pallas_call`` (tables past the VMEM envelope use the staged
      big-table path).  TPU only (the hardware PRNG has no CPU
      equivalent); pull mode on the implicit complete topology; static
      fault masks (node_death_rate / drop_prob) in-kernel on every
      layout, scripted dead_nodes rejected.  Single device: <= 32
      rumors packed in one word per node.  Multi-device: rumor planes
      of 32 sharded across the mesh (parallel/sharded_fused.py), zero
      per-round ICI.  Ineligible configs raise rather than silently
      substituting another engine.
    * ``native`` — go-native backend only: force the C++ event core
      (native/eventsim.cpp, 20-100x the Python engine) and raise the
      node cap to 1M, making large-N parity spot checks CLI-reachable
      (VERDICT r2 item 8).  Raises if no C++ compiler is available —
      never a silent Python fallback.  The jax-tpu backend rejects it.
    """

    target_coverage: float = 0.99
    max_rounds: int = 256
    seed: int = 0
    origin: int = 0          # node where rumor 0 starts (rumor r starts at origin+r)
    engine: str = "auto"

    def __post_init__(self):
        if not 0.0 < self.target_coverage <= 1.0:
            raise ValueError("target_coverage must be in (0, 1]")
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; "
                             f"choose from {ENGINES}")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Admission-batching knobs for the gRPC sidecar (rpc/batcher).

    The serving layer coalesces in-flight ``Run``/``Ensemble`` requests
    into one device-resident megabatch per collector tick
    (parallel/sweep.request_sweep_curves); these are the queue-shape
    parameters — everything about WHICH requests may share an
    executable lives in the batch key (rpc/batcher.batch_key,
    docs/SERVING.md memo-key vs operand table), not here.

    * ``tick_ms`` — the collector cadence: every tick the queue drains
      and each batch-key group runs as one megabatch.  Smaller ticks
      trade batch size for admission latency.
    * ``max_batch`` — per-tick per-key cap on coalesced requests
      (ensemble members count individually); the rest stay queued for
      the next tick.
    * ``max_queue`` — the backpressure cap: an admission past this
      depth is rejected with RESOURCE_EXHAUSTED instead of growing the
      queue without bound (the reply tells the client to back off —
      SidecarClient's retry policy treats it as a well-formed error,
      never a transport failure).
    * ``devices`` — megabatch mesh width: the batcher shards the
      request axis of each tick's megabatch over the first ``devices``
      JAX devices (a 1-D ``Mesh`` on the ``"request"`` axis —
      parallel/sweep.request_sweep_curves).  Must be a power of two so
      every pow2 lane bucket divides the mesh and dispatch never
      fragments the executable cache; 1 (default) is the solo
      single-device path, bit-identical everywhere.  The batcher
      REFUSES at construction when the process has fewer devices than
      requested — a mesh must never silently degrade.
    * ``coordinator`` / ``num_processes`` / ``process_id`` — the
      jax.distributed topology for one logical replica spanning
      processes (``jax.distributed.initialize`` in rpc/sidecar.serve);
      ``num_processes == 1`` (default) is the degenerate single-process
      case that skips initialization entirely and runs everywhere.
    """

    tick_ms: float = 20.0
    max_batch: int = 64
    max_queue: int = 256
    devices: int = 1
    coordinator: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0

    def __post_init__(self):
        if self.tick_ms <= 0:
            raise ValueError("tick_ms must be > 0")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if self.devices < 1 or (self.devices & (self.devices - 1)):
            raise ValueError(
                "devices must be a power of two >= 1 (pow2 lane "
                "buckets must divide the mesh so dispatch never "
                f"fragments the executable cache), got {self.devices}")
        if self.num_processes < 1:
            raise ValueError("num_processes must be >= 1")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError("process_id must be in [0, num_processes)")
        if self.num_processes > 1 and not self.coordinator:
            raise ValueError(
                "a multi-process replica (num_processes > 1) needs a "
                "coordinator address (host:port) for "
                "jax.distributed.initialize")


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Replicated-serving knobs for the fronting router (rpc/router).

    The router fronts N sidecar replicas, health-probes each on the
    ``SidecarClient.health`` path, dispatches ``Run``/``Ensemble`` to
    healthy replicas, and on a replica transport failure re-dispatches
    the in-flight request to a survivor (safe: requests are
    deterministic pure functions of their payload — docs/SERVING.md
    "Fleet").  These are the fleet-shape parameters:

    * ``replicas`` — replica count for spawned fleets (``Fleet`` /
      CLI ``route``); a router built over explicit addresses takes its
      count from the address list.
    * ``probe_interval_ms`` / ``probe_timeout_s`` — health-probe
      cadence and per-probe deadline; a probe past the deadline counts
      as a failure.
    * ``down_after`` — consecutive probe failures before a replica
      leaves rotation (a dispatch-path transport failure marks it down
      immediately: a dead connection is definitive).
    * ``up_after`` — consecutive healthy probes before a DOWNED
      replica re-enters rotation — the flap hysteresis: a replica
      alternating healthy/unhealthy can never oscillate back in
      faster than this (test-pinned).  First-time admission at startup
      needs only one healthy probe (nothing was lost yet).
    * ``max_inflight`` — per-replica in-flight dispatch cap; when every
      healthy replica is at its cap the router SHEDS with
      RESOURCE_EXHAUSTED + a ``shed`` ledger event (it never queues
      unboundedly, never silently drops).
    * ``control_capacity`` — ring capacity of each replica's
      control-plane log key (ops/logs; committed offset = config
      epoch); a fleet exceeding it in one run errors loudly rather
      than alias epochs on a ring wrap.
    * ``devices_per_replica`` — megabatch mesh width each spawned
      replica must serve with (ServingConfig.devices in the child).
      The fleet threads the host-device-count env to children
      (``XLA_FLAGS=--xla_force_host_platform_device_count=K`` via
      router.fleet_env) and REFUSES loudly after spawn when a child's
      health probe reports fewer serving devices than requested — the
      child pins ``JAX_PLATFORMS=cpu``, so without the env the mesh
      would silently degrade to 1 device.  Power of two, like
      ServingConfig.devices.
    """

    replicas: int = 2
    probe_interval_ms: float = 250.0
    probe_timeout_s: float = 2.0
    down_after: int = 2
    up_after: int = 3
    max_inflight: int = 8
    control_capacity: int = 64
    devices_per_replica: int = 1

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if (self.devices_per_replica < 1
                or (self.devices_per_replica
                    & (self.devices_per_replica - 1))):
            raise ValueError(
                "devices_per_replica must be a power of two >= 1, "
                f"got {self.devices_per_replica}")
        if self.probe_interval_ms <= 0:
            raise ValueError("probe_interval_ms must be > 0")
        if self.probe_timeout_s <= 0:
            raise ValueError("probe_timeout_s must be > 0")
        if self.down_after < 1:
            raise ValueError("down_after must be >= 1")
        if self.up_after < 1:
            raise ValueError("up_after must be >= 1")
        if self.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        if self.control_capacity < 4:
            # the LogConfig floor: an unscripted log config must hold
            # the default send program's ring
            raise ValueError("control_capacity must be >= 4")


EXCHANGES = ("dense", "sparse", "halo")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh for node-dimension sharding (the SP/CP analog: the scaled
    long dimension here is *nodes*, not tokens — see SURVEY.md §5).

    ``exchange`` picks the cross-shard communication pattern:

    * ``dense``  — all_gather / psum_scatter of full digest tables (any
      topology, any mode; O(N) ICI bytes per round);
    * ``sparse`` — stratified all_to_all request/response (implicit
      complete topology, pull/anti-entropy; O(messages) bytes —
      parallel/sharded_sparse.py);
    * ``halo``   — ppermute halo exchange (band-limited explicit
      topologies, flood/pull/push/pushpull; O(band) bytes —
      parallel/halo.py).
    """

    n_devices: int = 1
    axis_name: str = "nodes"
    exchange: str = "dense"

    def __post_init__(self):
        if self.exchange not in EXCHANGES:
            raise ValueError(f"unknown exchange {self.exchange!r}; "
                             f"choose from {EXCHANGES}")
