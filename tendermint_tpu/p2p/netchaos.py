"""netchaos — deterministic, seeded network-fault engine.

Generalizes the single-connection FuzzedConnection (p2p/fuzz.py) into a
process-wide controller applying per-(src, dst) LINK rules at the
switch/transport boundary: full partitions by peer-set, one-way drops
(asymmetric partitions), fixed+jittered delay, bandwidth throttling
(riding libs/flowrate), and forced disconnect/reconnect storms.

A scenario is a DATA object — a FaultPlan: a seed plus a list of timed
phases `(at_s, until_s, LinkRule)`. All randomness (drop coin flips,
delay jitter, disconnect storms) comes from per-link `random.Random`
instances derived from (plan seed, src, dst), so the decision sequence
each link sees is a pure function of the seed and its own packet
stream: re-running a scenario with the same seed replays the same fault
timeline regardless of scheduling in OTHER links, and concurrent tests
cannot perturb each other (the bug the global-`random` fuzz layer had).

Faults act on the SENDING side of each link: every peer connection a
Switch creates while a controller is installed gets wrapped in a
ChaosConn whose write path consults the controller. MConnection's
write() is a whole number of length-prefixed packets (a batch of up to
ten, or one ping or pong), so dropping a write loses messages — exactly
a lossy/partitioned network — without ever corrupting framing.
One-way rules therefore model asymmetric partitions naturally: A's
outbound wrapper drops A->B while B's wrapper keeps delivering B->A.

In-process localnets (tools/scenarios.py, tests) install ONE controller
covering every node in the process; a real node enables it via the
[chaos] config section, where rules name peer IDs.
"""

from __future__ import annotations

import hashlib
import json
import logging
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..libs.flowrate import Monitor

LOG = logging.getLogger("p2p.netchaos")

# rule kinds a LinkRule may carry
KIND_DROP = "drop"
KIND_DELAY = "delay"
KIND_THROTTLE = "throttle"
KIND_DISCONNECT = "disconnect"
_KINDS = (KIND_DROP, KIND_DELAY, KIND_THROTTLE, KIND_DISCONNECT)

# hard ceiling on one injected sleep — a mis-built plan must degrade a
# link, never wedge a send routine for minutes
MAX_INJECT_DELAY_S = 5.0


@dataclass(frozen=True)
class LinkRule:
    """One fault applied to the links it matches.

    src/dst are peer-ID sets (None = any). A packet travelling
    sender->receiver matches when sender ∈ src and receiver ∈ dst —
    or, with symmetric=True (the default), the reverse direction too,
    which is what a full partition between two peer-sets means. A
    one-way drop (asymmetric partition) is symmetric=False.

    kind semantics:
      drop        lose matching writes with probability `prob`
      delay       sleep delay_s + U(0, jitter_s) before the write
      throttle    cap the link at `rate` bytes/s (flowrate token bucket)
      disconnect  close the underlying conn with probability `prob`
                  per write — reconnect storms when the peer redials
    """

    kind: str
    src: Optional[frozenset] = None
    dst: Optional[frozenset] = None
    prob: float = 1.0
    delay_s: float = 0.0
    jitter_s: float = 0.0
    rate: int = 0
    symmetric: bool = True

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown chaos rule kind {self.kind!r}")
        if not 0.0 <= self.prob <= 1.0:
            raise ValueError(f"rule prob {self.prob} outside [0, 1]")
        # accept any iterable of ids; store hashable frozensets
        for name in ("src", "dst"):
            v = getattr(self, name)
            if v is not None and not isinstance(v, frozenset):
                object.__setattr__(self, name, frozenset(v))

    def matches(self, sender: str, receiver: str) -> bool:
        def _in(s, x):
            return s is None or x in s

        if _in(self.src, sender) and _in(self.dst, receiver):
            return True
        if self.symmetric and _in(self.src, receiver) and _in(self.dst, sender):
            return True
        return False

    def to_obj(self) -> dict:
        return {
            "kind": self.kind,
            "src": sorted(self.src) if self.src is not None else None,
            "dst": sorted(self.dst) if self.dst is not None else None,
            "prob": self.prob,
            "delay_s": self.delay_s,
            "jitter_s": self.jitter_s,
            "rate": self.rate,
            "symmetric": self.symmetric,
        }

    @classmethod
    def from_obj(cls, o: dict) -> "LinkRule":
        return cls(
            kind=o["kind"],
            src=frozenset(o["src"]) if o.get("src") is not None else None,
            dst=frozenset(o["dst"]) if o.get("dst") is not None else None,
            prob=float(o.get("prob", 1.0)),
            delay_s=float(o.get("delay_s", 0.0)),
            jitter_s=float(o.get("jitter_s", 0.0)),
            rate=int(o.get("rate", 0)),
            symmetric=bool(o.get("symmetric", True)),
        )


@dataclass(frozen=True)
class FaultPhase:
    """One timed rule: active while at_s <= elapsed < until_s."""

    at_s: float
    until_s: float
    rule: LinkRule

    def __post_init__(self):
        if self.until_s <= self.at_s:
            raise ValueError(
                f"phase window [{self.at_s}, {self.until_s}) is empty")


@dataclass
class FaultPlan:
    """A scenario's fault timeline: a seed + timed phases. Serializable
    both ways so a scenario is a replayable data object."""

    seed: int = 0
    phases: List[FaultPhase] = field(default_factory=list)

    def add(self, at_s: float, until_s: float, rule: LinkRule) -> "FaultPlan":
        # floats throughout so a plan and its JSON round-trip compare
        # equal (the replayability contract is textual identity)
        self.phases.append(FaultPhase(float(at_s), float(until_s), rule))
        return self

    def active(self, elapsed_s: float) -> List[LinkRule]:
        return [p.rule for p in self.phases
                if p.at_s <= elapsed_s < p.until_s]

    def end_s(self) -> float:
        """When the last phase expires (0 for an empty plan)."""
        return max((p.until_s for p in self.phases), default=0.0)

    def to_json(self) -> str:
        return json.dumps({
            "seed": self.seed,
            "phases": [[p.at_s, p.until_s, p.rule.to_obj()]
                       for p in self.phases],
        }, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        o = json.loads(text)
        plan = cls(seed=int(o.get("seed", 0)))
        for at_s, until_s, rule in o.get("phases", []):
            plan.add(float(at_s), float(until_s), LinkRule.from_obj(rule))
        return plan


@dataclass(frozen=True)
class Decision:
    """What the controller decided for one outbound write."""

    drop: bool = False
    delay_s: float = 0.0
    close: bool = False
    rate: int = 0  # 0 = unthrottled


class NetChaosController:
    """Process-wide fault decider: per-(src, dst) rule evaluation with
    per-link seeded RNG streams, injection counters, and a monotonic
    epoch started by start() (or lazily on first decision)."""

    def __init__(self, plan: FaultPlan, metrics=None,
                 time_fn=time.monotonic):
        from ..metrics import P2PMetrics

        self.plan = plan
        self.metrics = metrics if metrics is not None else P2PMetrics()
        self._time = time_fn
        self._t0: Optional[float] = None
        self._lock = threading.Lock()
        self._rngs: Dict[Tuple[str, str], random.Random] = {}
        self._monitors: Dict[Tuple[str, str], Monitor] = {}
        # exact injection counts, also mirrored into the metrics sink
        self.injected: Dict[str, int] = {k: 0 for k in _KINDS}
        # last value written to the active-rules gauge: outbound() runs
        # on every frame of every link, so the gauge only pays a
        # registry write when the active-phase count actually changes
        self._last_active_gauge: Optional[int] = None
        # incident ledger (libs/incident.py) + the phase-index set it
        # last saw, so activations/deactivations are recorded exactly
        # once each no matter how many links observe them
        self._incidents = None
        self._active_idx: Optional[frozenset] = None

    # -- lifecycle -----------------------------------------------------

    def set_incidents(self, ledger) -> None:
        """Record every phase activation/deactivation into an
        IncidentLedger: uid ``net:<seed>:<phase_idx>``, detail fully
        plan-derived (the seeded-replay contract)."""
        self._incidents = ledger

    def _observe_phases(self, t: float) -> None:
        """Diff the active phase-index set against the last one seen and
        ledger the transitions. Driven by outbound() (every write) and
        status() (every /debug scrape — catches phases expiring on a
        quiet network)."""
        if self._incidents is None:
            return
        idx = frozenset(i for i, p in enumerate(self.plan.phases)
                        if p.at_s <= t < p.until_s)
        # diff-and-swap under the lock (every send path races through
        # here); the ledger calls run outside it — the ledger has its
        # own lock and never calls back into the controller
        with self._lock:
            prev = self._active_idx
            if idx == prev:
                return
            self._active_idx = idx
        prev = prev or frozenset()
        for i in sorted(idx - prev):
            p = self.plan.phases[i]
            self._incidents.open_incident(
                f"net:{self.plan.seed}:{i}", p.rule.kind,
                phase=i, at_s=p.at_s, until_s=p.until_s,
                rule=p.rule.to_obj())
        for i in sorted(prev - idx):
            p = self.plan.phases[i]
            self._incidents.note_heal(
                f"net:{self.plan.seed}:{i}",
                phase=i, at_s=p.at_s, until_s=p.until_s)

    def start(self) -> None:
        """Pin the plan's t=0. Idempotent."""
        with self._lock:
            if self._t0 is None:
                self._t0 = self._time()
        t = self.elapsed()
        n = len(self.plan.active(t))
        self._last_active_gauge = n
        self.metrics.chaos_active_rules.set(n)
        self._observe_phases(t)

    def elapsed(self) -> float:
        with self._lock:
            if self._t0 is None:
                self._t0 = self._time()
            return self._time() - self._t0

    def set_plan(self, plan: FaultPlan) -> None:
        """Swap in a new plan and restart its clock at t=0. The scenario
        runner installs an IDLE controller before the net boots (so
        every link is wrapped from birth), then arms the scenario's
        plan once the chain is warm; per-link RNG streams reset so the
        armed plan replays identically regardless of warmup traffic."""
        with self._lock:
            self.plan = plan
            self._t0 = self._time()
            self._rngs.clear()
            self._monitors.clear()
            self._last_active_gauge = None  # re-publish on next decision
            self._active_idx = None  # re-diff against the new plan

    # -- determinism core ----------------------------------------------

    def _rng(self, sender: str, receiver: str) -> random.Random:
        """Per-link RNG seeded from (plan seed, sender, receiver): each
        link's decision stream is independent of every other link's
        scheduling, so a scenario replays bit-for-bit from its seed."""
        key = (sender, receiver)
        with self._lock:
            rng = self._rngs.get(key)
            if rng is None:
                digest = hashlib.sha256(
                    b"netchaos:%d:%s>%s" % (self.plan.seed,
                                            sender.encode(),
                                            receiver.encode())).digest()
                rng = random.Random(int.from_bytes(digest[:8], "big"))
                self._rngs[key] = rng
            return rng

    def _monitor(self, sender: str, receiver: str) -> Monitor:
        key = (sender, receiver)
        with self._lock:
            mon = self._monitors.get(key)
            if mon is None:
                mon = Monitor()
                self._monitors[key] = mon
            return mon

    def _count(self, kind: str) -> None:
        with self._lock:
            self.injected[kind] += 1
        self.metrics.chaos_injected.with_labels(kind).inc()

    # -- the per-write decision ----------------------------------------

    def outbound(self, sender: str, receiver: str, nbytes: int) -> Decision:
        """Evaluate the active rules for one sender->receiver write.
        Draw discipline: probabilistic kinds (drop/disconnect) consume
        exactly one RNG draw per matching rule per packet, delay-jitter
        one per matching jittered rule — the stream consumed by a link
        depends only on its own packet sequence."""
        t = self.elapsed()
        active = self.plan.active(t)
        if len(active) != self._last_active_gauge:
            self._last_active_gauge = len(active)
            self.metrics.chaos_active_rules.set(len(active))
            self._observe_phases(t)
        if not active:
            return Decision()
        rules = [r for r in active if r.matches(sender, receiver)]
        if not rules:
            return Decision()
        rng = self._rng(sender, receiver)
        drop = close = False
        delay = 0.0
        rate = 0
        for r in rules:
            if r.kind == KIND_DROP:
                if rng.random() < r.prob:
                    drop = True
            elif r.kind == KIND_DELAY:
                delay += r.delay_s
                if r.jitter_s > 0:
                    delay += rng.random() * r.jitter_s
            elif r.kind == KIND_THROTTLE:
                rate = r.rate if rate == 0 else min(rate, r.rate)
            elif r.kind == KIND_DISCONNECT:
                if rng.random() < r.prob:
                    close = True
        if close:
            self._count(KIND_DISCONNECT)
            return Decision(close=True)
        if drop:
            self._count(KIND_DROP)
        if delay > 0:
            self._count(KIND_DELAY)
        if rate > 0:
            self._count(KIND_THROTTLE)
        return Decision(drop=drop,
                        delay_s=min(delay, MAX_INJECT_DELAY_S),
                        rate=rate)

    def status(self) -> dict:
        with self._lock:
            injected = dict(self.injected)
        t = self.elapsed()
        self._observe_phases(t)
        return {
            "seed": self.plan.seed,
            "elapsed_s": round(t, 3),
            "phases": len(self.plan.phases),
            "active_rules": len(self.plan.active(t)),
            "injected": injected,
        }


class ChaosConn:
    """Wraps a SecretConnection-shaped object (write / read_exact /
    close), applying the controller's outbound decisions for one
    (local node -> peer) link. One decision — drop, delay, throttle —
    a write(), and MConnection's write() is a whole number of
    length-prefixed packets (its batch of up to ten, or a ping or pong):
    a dropped write is a lost message, never torn framing, and a delay
    rule costs once a batch."""

    def __init__(self, conn, controller: NetChaosController,
                 src_id: str, dst_id: str):
        self._conn = conn
        self._ctrl = controller
        self.src_id = src_id
        self.dst_id = dst_id

    def write(self, data: bytes) -> None:
        d = self._ctrl.outbound(self.src_id, self.dst_id, len(data))
        if d.close:
            try:
                self._conn.close()
            finally:
                raise ConnectionError(
                    f"netchaos: forced disconnect {self.src_id[:8]}->"
                    f"{self.dst_id[:8]}")
        if d.delay_s > 0:
            time.sleep(d.delay_s)
        if d.drop:
            return  # silently lost, framing intact
        if d.rate > 0:
            mon = self._ctrl._monitor(self.src_id, self.dst_id)
            sent = 0
            while sent < len(data):
                allowance = mon.limit(len(data) - sent, d.rate)
                chunk = data[sent:sent + allowance]
                self._conn.write(chunk)
                mon.update(len(chunk))
                sent += len(chunk)
            return
        self._conn.write(data)

    def read_exact(self, n: int) -> bytes:
        return self._conn.read_exact(n)

    def close(self) -> None:
        self._conn.close()

    def __getattr__(self, item):
        # anything else (remote_pub_key, settimeout, ...) passes through
        return getattr(self._conn, item)


# --- process-wide installation ----------------------------------------

_controller: Optional[NetChaosController] = None
_install_lock = threading.Lock()


def install(controller: NetChaosController) -> NetChaosController:
    """Install the process-wide controller consulted by every Switch.
    Replaces any previous one (scenarios install per run)."""
    global _controller
    with _install_lock:
        _controller = controller
    controller.start()
    return controller


def get_controller() -> Optional[NetChaosController]:
    return _controller


def uninstall() -> None:
    global _controller
    with _install_lock:
        _controller = None


def wrap_conn(sc, src_id: str, dst_id: str):
    """Wrap a peer connection when a controller is installed (the
    Switch's hook); identity pass-through otherwise."""
    ctrl = get_controller()
    if ctrl is None:
        return sc
    return ChaosConn(sc, ctrl, src_id, dst_id)


# --- named-partition helpers (plan builders) --------------------------


def _idset(x):
    return frozenset(x) if x is not None else None


def partition(group_a, group_b) -> LinkRule:
    """Full bidirectional partition between two peer-ID sets (None =
    every peer)."""
    return LinkRule(KIND_DROP, src=_idset(group_a), dst=_idset(group_b),
                    prob=1.0, symmetric=True)


def one_way_drop(srcs, dsts, prob: float = 1.0) -> LinkRule:
    """Asymmetric partition: srcs' traffic TOWARD dsts is lost; the
    reverse direction flows."""
    return LinkRule(KIND_DROP, src=_idset(srcs), dst=_idset(dsts),
                    prob=prob, symmetric=False)


def delay(delay_s: float, jitter_s: float = 0.0,
          srcs=None, dsts=None) -> LinkRule:
    return LinkRule(KIND_DELAY, src=srcs, dst=dsts,
                    delay_s=delay_s, jitter_s=jitter_s)


def throttle(rate: int, srcs=None, dsts=None) -> LinkRule:
    return LinkRule(KIND_THROTTLE, src=srcs, dst=dsts, rate=rate)


def disconnect_storm(prob: float, srcs=None, dsts=None) -> LinkRule:
    return LinkRule(KIND_DISCONNECT, src=srcs, dst=dsts, prob=prob)
