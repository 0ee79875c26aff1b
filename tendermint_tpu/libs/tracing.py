"""Span tracer — hot-path timeline visibility (chrome://tracing).

The reference has no tracing subsystem; PROFILE.md's round-4 findings
(h2d transfer vs device compute vs dispatch latency) had to be
reverse-engineered with one-off scripts. This module gives every layer
boundary of the node — RPC, ingest, block sync, block execution, the
batch-verify funnel down to the stages of a device batch, compiles —
always-available spans (README "Spans" lists the names):

- Causal: every span has an id, the id of the span that caused it (the
  span open on the same thread when it was entered, or the `cause` a
  queue item carried from another thread) and a request id shared by
  everything done for one block, POST, drain or batch. Self time and
  "waited in a queue" are then measured, not inferred.
- Ring-buffered: a bounded deque of finished spans; steady-state
  tracing never grows memory, the newest `capacity` spans win and
  `dropped` counts the ones that lost.
- In-flight visible: spans open at export time are synthesized into
  the trace with `dur = now - start` and `args.inflight = true`, so a
  snapshot taken mid-operation still nests correctly (a finished child
  is never exported without its enclosing span) and a stuck thread's
  open span shows up instead of silently missing.
- Lock-free on the hot path: open spans live on a per-thread stack, so
  entering a span takes one clock read and no lock, leaving it one
  append to the ring. The lock guards only a thread's first span,
  enable/disable/clear and export.
- Near-zero overhead when disabled: `span()` returns one shared no-op
  context manager — no allocation, no clock read, no lock.
- One clock with the device: while enabled, each span is also entered
  as a `jax.profiler.TraceAnnotation` of the same name (once the
  process has imported jax.profiler; this module never does), so any
  profiler session holds the host spans beside `XLA Ops`. record()ed
  spans (queue waits, `runtime.gc`) exist in the recorder only.
- Names the process's stalls: while enabled, a `gc.callbacks` hook
  records `runtime.gc` for every full collection and any other that
  takes over a millisecond. What those collections walk is kept small
  by `hold_frozen_heap()` (below the recorder), recorded as
  `runtime.gcFreeze`.

Export is Chrome trace event format ("X" complete events, µs units),
loadable in chrome://tracing or https://ui.perfetto.dev, served from
the ProfServer's /debug/trace route (rpc/prof.py).

Like logging, there is one process-global default tracer
(`get_tracer()`), disabled until `node.Node` enables it from
config.instrumentation.tracing — call sites never branch.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

# 40 s at the 3,100 spans a second the busiest measured traffic makes
# (620 RPC POSTs a second, four spans each, PERF.md); ~60 MB when full
DEFAULT_CAPACITY = 131072

# runtime.gc: every full collection is recorded, a younger one only
# when it held the process this long
GC_SPAN_FLOOR_NS = 1_000_000

# (span id, request id) of a span, handed across a queue so the span the
# worker opens can name what caused it
Cause = Tuple[int, Optional[tuple]]


@dataclass(frozen=True, slots=True)
class SpanRecord:
    """One finished span. Times from time.perf_counter_ns (monotonic).
    `parent_id` is the span that caused this one: the span open on the
    same thread when it was entered, or the `cause` a queue carried
    from another thread (0: a root). `request` is shared by everything
    done for one unit of work — ("block", height), ("post", n),
    ("drain", n), ("batch", n) — inherited from the parent unless
    given."""

    name: str
    cat: str
    start_ns: int
    dur_ns: int
    thread_id: int
    thread_name: str
    args: Optional[Dict] = None
    span_id: int = 0
    parent_id: int = 0
    request: Optional[tuple] = None

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


class _NopSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NOP_SPAN = _NopSpan()


class _Timer:
    """timed() with the recorder off: the two clock reads the caller's
    stage histogram needs, and nothing else."""

    __slots__ = ("_start_ns", "_end_ns")

    def __enter__(self):
        self._start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._end_ns = time.perf_counter_ns()
        return False

    def set(self, **args) -> None:
        pass

    @property
    def seconds(self) -> float:
        return (self._end_ns - self._start_ns) / 1e9


class _ThreadState:
    """One thread's open spans (innermost last) and its count of
    finished ones. Only the owning thread writes it, so entering and
    leaving a span take no lock."""

    __slots__ = ("stack", "ident", "name", "appended")

    def __init__(self):
        t = threading.current_thread()
        self.stack: list = []
        self.ident = t.ident or 0
        self.name = t.name
        self.appended = 0


class _Span:
    __slots__ = ("_tracer", "_state", "_name", "_cat", "_args", "_start_ns",
                 "_end_ns", "_annotation", "span_id", "parent_id", "request")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args,
                 cause: Optional[Cause], request: Optional[tuple]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._start_ns = 0
        self.parent_id, self.request = cause if cause is not None else (0, None)
        if request is not None:
            self.request = request

    def __enter__(self):
        tracer = self._tracer
        st = self._state = tracer._thread_state()
        stack = st.stack
        if stack and not self.parent_id:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if self.request is None:
                self.request = parent.request
        self.span_id = next(tracer._ids)
        stack.append(self)
        # the same span on the profiler's own clock: a TraceMe costs a
        # few hundred ns and writes nothing unless a session is open
        cls = tracer._annotation_cls()
        if cls is not None:
            self._annotation = cls(self._name)
            self._annotation.__enter__()
        else:
            self._annotation = None
        self._start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = self._end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        st = self._state
        stack = st.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # left out of order: keep the others' nesting
            stack.remove(self)
        st.appended += 1
        self._tracer._buf.append(SpanRecord(
            self._name, self._cat, self._start_ns, end - self._start_ns,
            st.ident, st.name, self._args or None,
            self.span_id, self.parent_id, self.request))
        return False

    def set(self, **args) -> None:
        """Counts known only at the end (n, bytes, rejected, ...)."""
        if self._args:
            self._args.update(args)
        else:
            self._args = args

    @property
    def cause(self) -> Cause:
        return (self.span_id, self.request)

    @property
    def start_ns(self) -> int:
        return self._start_ns

    @property
    def seconds(self) -> float:
        return (self._end_ns - self._start_ns) / 1e9


class Tracer:
    """Ring-buffered span recorder; one per process is the norm."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled: bool = False):
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        # per-thread stacks of open spans, keyed by thread ident — read
        # at export so a snapshot taken mid-operation still shows every
        # enclosing span (a closed child is never orphaned) and a stuck
        # thread's open span stays visible. A thread that reuses a dead
        # thread's ident replaces its entry, so the table stays as
        # large as the most threads alive at once.
        self._tls = threading.local()
        self._threads: Dict[int, _ThreadState] = {}
        self._retired = 0  # spans finished by threads since replaced
        self._cleared = 0  # spans finished before the last clear()
        self._ids = itertools.count(1)
        self._requests: Dict[str, "itertools.count"] = {}
        self._enabled = enabled
        self._annotation = None
        self._gc_hook = None
        self._gc_t0 = 0
        # epoch pins perf_counter to the wall clock once, so exported
        # timestamps are comparable across processes' traces
        self._epoch_wall_us = time.time() * 1e6
        self._epoch_perf_ns = time.perf_counter_ns()
        self._skew_us = 0.0

    def set_skew(self, skew_s: float) -> None:
        """Synthetic wall-clock offset on exported timestamps, matching
        Timeline.set_skew — keeps /debug/trace spans coherent with the
        skewed timeline marks fleettrace rebases (test/chaos knob)."""
        self._skew_us = float(skew_s) * 1e6

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def enable(self, capacity: Optional[int] = None) -> None:
        """Starts recording. Also names the process's stalls: a
        gc.callbacks hook records `runtime.gc` until disable()."""
        if capacity is not None and capacity != self._buf.maxlen:
            # spans are appended without the lock, so the swap takes
            # none either: one finishing meanwhile may land in the old
            # ring, and is then counted by `dropped`
            self._buf = collections.deque(self._buf, maxlen=capacity)
        with self._lock:
            self._enabled = True
            if self._gc_hook is None:
                self._gc_hook = self._on_gc
                gc.callbacks.append(self._gc_hook)

    def disable(self) -> None:
        with self._lock:
            self._enabled = False
            if self._gc_hook is not None:
                try:
                    gc.callbacks.remove(self._gc_hook)
                except ValueError:
                    pass
                self._gc_hook = None

    # --- recording ------------------------------------------------------

    def _thread_state(self) -> _ThreadState:
        try:
            return self._tls.state
        except AttributeError:
            st = self._tls.state = _ThreadState()
            with self._lock:
                old = self._threads.get(st.ident)
                if old is not None:
                    self._retired += old.appended
                self._threads[st.ident] = st
            return st

    def _annotation_cls(self):
        """jax.profiler.TraceAnnotation once the process has imported
        jax.profiler, else None: this module never imports JAX itself,
        and a process without it has no profiler session to write to."""
        cls = self._annotation
        if cls is None:
            mod = sys.modules.get("jax.profiler")
            cls = getattr(mod, "TraceAnnotation", None)
            self._annotation = cls
        return cls

    def span(self, name: str, cat: str = "", *, cause: Optional[Cause] = None,
             request: Optional[tuple] = None, **args):
        """Context manager timing one operation. Keyword args become the
        chrome-trace event's `args` payload (keep them cheap: scalars).
        `cause` is what cause() returned on the thread that queued this
        work; `request` starts a new unit of work (see request())."""
        if not self._enabled:
            return _NOP_SPAN
        return _Span(self, name, cat, args, cause, request)

    def timed(self, name: str, cat: str = "", *, cause: Optional[Cause] = None,
              request: Optional[tuple] = None, **args):
        """span() for call sites that feed a histogram from the same two
        clock reads: `.seconds` is valid after exit whether or not the
        recorder is on."""
        if not self._enabled:
            return _Timer()
        return _Span(self, name, cat, args, cause, request)

    def cause(self) -> Optional[Cause]:
        """(id, request) of the innermost span open on this thread, for
        a queue item to carry to the thread that will do the work."""
        if not self._enabled:
            return None
        stack = self._thread_state().stack
        return stack[-1].cause if stack else None

    def request(self, kind: str) -> Optional[tuple]:
        """A fresh request id (kind, n) — None while the recorder is off."""
        if not self._enabled:
            return None
        counter = self._requests.get(kind)
        if counter is None:
            counter = self._requests.setdefault(kind, itertools.count(1))
        return (kind, next(counter))

    def record(self, name: str, start_ns: int, end_ns: int, cat: str = "", *,
               cause: Optional[Cause] = None, request: Optional[tuple] = None,
               **args) -> Optional[Cause]:
        """A span whose two clock readings the caller already took: a
        wait measured from the far side of a queue, or work that is only
        worth a span once it is known what it was. Parent and request
        default as span()'s do. Returns the new span's cause()."""
        if not self._enabled:
            return None
        st = self._thread_state()
        if cause is None and st.stack:
            cause = st.stack[-1].cause
        parent_id, inherited = cause if cause is not None else (0, None)
        if request is None:
            request = inherited
        span_id = next(self._ids)
        st.appended += 1
        self._buf.append(SpanRecord(
            name, cat, start_ns, max(0, end_ns - start_ns), st.ident, st.name,
            args or None, span_id, parent_id, request))
        return (span_id, request)

    def _on_gc(self, phase: str, info: dict) -> None:
        # one collection runs at a time, process-wide: one start suffices
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
            return
        t0, now = self._gc_t0, time.perf_counter_ns()
        if t0 and (info["generation"] == 2 or now - t0 >= GC_SPAN_FLOOR_NS):
            self.record("runtime.gc", t0, now, "runtime",
                        generation=info["generation"],
                        collected=info["collected"])

    def _finished_locked(self) -> int:
        return self._retired + sum(
            st.appended for st in self._threads.values())

    @property
    def dropped(self) -> int:
        """Finished spans the ring has lost since the last clear(): a
        reader whose window reaches back past them is not whole."""
        with self._lock:
            return max(0, self._finished_locked() - self._cleared
                       - len(self._buf))

    def clear(self) -> None:
        with self._lock:
            self._cleared = self._finished_locked()
        self._buf.clear()

    def events(self) -> List[SpanRecord]:
        """Snapshot of recorded spans, oldest first."""
        return list(self._buf)

    # --- export -------------------------------------------------------------

    def _ts_us(self, t_ns: int) -> float:
        return (self._epoch_wall_us + self._skew_us
                + (t_ns - self._epoch_perf_ns) / 1e3)

    def chrome_trace(self) -> dict:
        """Chrome trace event format: {"traceEvents": [...]} with "X"
        (complete) events plus thread-name metadata, ts/dur in µs. Each
        event also carries `span_id`, `parent_id` and, where it has one,
        `request` beside `args`; `dropped` counts what the ring lost.

        Spans still open at snapshot time are included too, with
        `dur = now - start` and `args.inflight = true`. The open spans
        are read before the finished ones, so a finished child always
        has its enclosing span present — finished in the buffer, or
        synthesized as in-flight if it closed in between."""
        pid = os.getpid()
        with self._lock:
            states = list(self._threads.values())
        open_spans = [(st, sp) for st in states for sp in list(st.stack)]
        finished = list(self._buf)
        dropped = self.dropped
        now_ns = time.perf_counter_ns()
        events = []
        seen_threads: Dict[int, str] = {}
        seen_ids = set()

        def event(name, cat, start_ns, dur_ns, tid, args, span_id,
                  parent_id, request) -> dict:
            ev = {
                "name": name,
                "cat": cat or "default",
                "ph": "X",
                "ts": self._ts_us(start_ns),
                "dur": dur_ns / 1e3,
                "pid": pid,
                "tid": tid,
                "span_id": span_id,
                "parent_id": parent_id,
            }
            if request is not None:
                ev["request"] = list(request)
            if args:
                ev["args"] = args
            return ev

        for rec in finished:
            seen_threads.setdefault(rec.thread_id, rec.thread_name)
            seen_ids.add(rec.span_id)
            events.append(event(
                rec.name, rec.cat, rec.start_ns, rec.dur_ns, rec.thread_id,
                rec.args, rec.span_id, rec.parent_id, rec.request))
        for st, sp in open_spans:
            start_ns = sp._start_ns
            if not start_ns or sp.span_id in seen_ids:
                continue  # not yet started, or closed since
            seen_threads.setdefault(st.ident, st.name)
            events.append(event(
                sp._name, sp._cat, start_ns, now_ns - start_ns, st.ident,
                dict(sp._args or {}, inflight=True), sp.span_id,
                sp.parent_id, sp.request))
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in seen_threads.items()
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "dropped": dropped}

    def chrome_trace_json(self) -> str:
        return json.dumps(self.chrome_trace(), separators=(",", ":"))

    def spans_where(self, **match) -> List[dict]:
        """Finished spans whose args carry every given key=value, as
        JSON-able dicts with wall-clock µs timestamps. The timeline
        endpoint uses this to stitch a height's tracer spans into its
        lifecycle record (spans are tagged height=N at the call sites)."""
        out = []
        for rec in self.events():
            if rec.args and all(
                    rec.args.get(k) == v for k, v in match.items()):
                out.append({
                    "name": rec.name,
                    "cat": rec.cat,
                    "ts_us": self._ts_us(rec.start_ns),
                    "dur_us": rec.dur_ns / 1e3,
                    "thread": rec.thread_name,
                    "args": dict(rec.args),
                })
        return out


def self_times(records: List[SpanRecord]) -> Dict[int, int]:
    """{span_id: ns} — each span's self time: its duration less the part
    of it that its children on the same thread cover (a child on another
    thread ran beside it, not instead of it)."""
    children: Dict[int, list] = {}
    for r in records:
        if r.parent_id:
            children.setdefault(r.parent_id, []).append(r)
    out = {}
    for r in records:
        covered, reach = 0, r.start_ns
        for k in sorted((k for k in children.get(r.span_id, ())
                         if k.thread_id == r.thread_id),
                        key=lambda k: k.start_ns):
            lo, hi = max(k.start_ns, reach), min(k.end_ns, r.end_ns)
            if hi > lo:
                covered += hi - lo
                reach = hi
        out[r.span_id] = r.dur_ns - covered
    return out


_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer (disabled until a Node enables it)."""
    return _GLOBAL


# --- the frozen heap -----------------------------------------------------
#
# What a process holds once it has started — modules, jax, the loaded
# kernels — lives as long as the process, and a full collection walks
# all of it every time (65-94 ms a collection on a joiner catching up,
# PERF.md section 5). gc.freeze() moves it to the permanent generation,
# which no collection walks. The switch is the interpreter's, not a
# node's, and several nodes run in one process (tests, scenario tools),
# so holds are counted here: the last release unfreezes.

_heap_lock = threading.Lock()
_heap_holds = 0


def hold_frozen_heap() -> int:
    """Collects once (so no garbage cycle is frozen in), then freezes
    everything alive; returns gc.get_freeze_count(). Every hold collects
    and freezes again, so what a later node built joins the permanent
    generation too. Thresholds stay the interpreter's and no collection
    is skipped: what is allocated afterwards is collected as before.
    One `runtime.gcFreeze` span a hold. A cycle made of frozen objects
    that dies later is kept until the last release_frozen_heap(); a
    process that never releases keeps its start-up cycles until exit,
    as any long-running node does."""
    global _heap_holds
    t0 = time.perf_counter_ns()
    with _heap_lock:
        collected = gc.collect()
        gc.freeze()
        frozen = gc.get_freeze_count()
        _heap_holds += 1
    _GLOBAL.record("runtime.gcFreeze", t0, time.perf_counter_ns(), "runtime",
                   frozen=frozen, collected=collected)
    return frozen


def release_frozen_heap() -> None:
    """Gives back one hold_frozen_heap(); the last one unfreezes (the
    permanent generation rejoins the oldest, and is collected again)."""
    global _heap_holds
    with _heap_lock:
        _heap_holds -= 1
        if _heap_holds == 0:
            gc.unfreeze()


class FrozenHeap:
    """One owner's hold on the frozen heap, for one start of it: take()
    when the start-up is over, on whatever thread ends it; drop() when
    the owner stops, which gives back only what take() took. After
    drop() a take() that comes late takes nothing. `publish(n)` is
    called under the hold's own lock with the objects frozen (0 at
    drop()), so what the owner shows never lags the hold."""

    def __init__(self, publish):
        self._lock = threading.Lock()
        self._publish = publish
        self._held = False
        self._dropped = False

    def take(self) -> None:
        with self._lock:
            if self._held or self._dropped:
                return
            self._held = True
            self._publish(hold_frozen_heap())

    def drop(self) -> None:
        with self._lock:
            self._dropped = True
            if self._held:
                self._held = False
                release_frozen_heap()
                self._publish(0)


def span(name: str, cat: str = "", **args):
    """Convenience: a span on the global tracer."""
    return _GLOBAL.span(name, cat, **args)


def timed(name: str, cat: str = "", **args):
    """Convenience: Tracer.timed on the global tracer."""
    return _GLOBAL.timed(name, cat, **args)


def cause() -> Optional[Cause]:
    """Convenience: Tracer.cause on the global tracer."""
    return _GLOBAL.cause()
