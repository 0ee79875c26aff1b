"""In-process pubsub with query-language subscriptions.

Replaces the reference's libs/pubsub (+ its PEG query parser,
libs/pubsub/query/query.peg.go) and libs/events. Events carry string
tags; subscribers filter with a small query language:

    tm.event = 'NewBlock' AND tx.height > 5

supporting =, <, <=, >, >=, CONTAINS over tag values, plus typed
`DATE 2006-01-02` / `TIME 2006-01-02T15:04:05Z` operands
(reference libs/pubsub/query/query.go:81-83 DateLayout/TimeLayout),
combined with AND.
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field
from datetime import date, datetime, timezone
from typing import Callable, Dict, List, Optional

from . import tracing


class QueryError(ValueError):
    pass


def match_op(op: str, have: str, want: str) -> bool:
    """One operator of the query language; shared by pubsub filtering and
    the kv tx indexer's secondary-index scans."""
    if op == "=":
        return have == want
    if op == "CONTAINS":
        return want in have
    # numeric comparisons
    try:
        a, b = float(have), float(want)
    except ValueError:
        return False
    return {"<": a < b, "<=": a <= b, ">": a > b, ">=": a >= b}[op]


def _parse_tag_time(value: str) -> Optional[float]:
    """Tag value -> epoch seconds, trying RFC3339 then the date layout
    (reference query.go:251-263 match's time conversion). None if the
    value is not a time — the reference panics; we just don't match.

    RFC3339 requires an explicit offset: an offset-less "...T14:45:00"
    is rejected (Go's time.Parse(RFC3339) parity) rather than being
    interpreted in the machine's local timezone, which would make query
    matches timezone-dependent. Date-only values are midnight UTC."""
    try:
        if "T" in value:
            dt = datetime.fromisoformat(value.replace("Z", "+00:00"))
            if dt.tzinfo is None:
                return None
            return dt.timestamp()
        d = date.fromisoformat(value)
        return datetime(d.year, d.month, d.day, tzinfo=timezone.utc).timestamp()
    except ValueError:
        return None


def _compare_typed(op: str, a: float, b: float) -> bool:
    return {
        "=": a == b, "<": a < b, "<=": a <= b, ">": a > b, ">=": a >= b,
    }.get(op, False)


@dataclass(frozen=True)
class _Condition:
    key: str
    op: str
    value: str
    # "str" (untyped; numeric comparison attempted for </>), or the typed
    # operand kinds "date"/"time" with the parsed epoch in tvalue
    kind: str = "str"
    tvalue: float = 0.0

    def matches(self, tags: Dict[str, str]) -> bool:
        if self.key not in tags:
            return False
        if self.op == "EXISTS":
            return True
        return self.compare_value(tags[self.key])

    def compare_value(self, have: str) -> bool:
        """Compare one tag value against the operand, honoring the
        operand's type (shared by pubsub matching and the kv indexer)."""
        if self.kind in ("date", "time"):
            t = _parse_tag_time(have)
            return t is not None and _compare_typed(self.op, t, self.tvalue)
        return match_op(self.op, have, self.value)


class Query:
    """Parsed conjunctive tag query (reference libs/pubsub/query)."""

    def __init__(self, s: str):
        self.raw = s.strip()
        self.conditions: List[_Condition] = []
        if self.raw:
            self._parse(self.raw)

    def _parse(self, s: str) -> None:
        # split on AND only outside single-quoted values ("x = 'A AND B'"
        # is one condition): an AND is a separator iff an even number of
        # quotes follows it
        parts = re.split(r"\bAND\b(?=(?:[^']*'[^']*')*[^']*$)", s)
        for part in parts:
            part = part.strip()
            m = re.match(r"^(?P<key>[\w.\-]+)\s+EXISTS$", part)
            if m:
                self.conditions.append(
                    _Condition(key=m.group("key"), op="EXISTS", value=""))
                continue
            m = re.match(
                r"^(?P<key>[\w.\-]+)\s*(?P<op>=|<=|>=|<|>|CONTAINS)\s*"
                r"(?:(?P<kind>DATE|TIME)\s+(?P<tval>[\w:+.\-]+)"
                r"|'(?P<qval>[^']*)'|(?P<val>[\w.\-]+))$",
                part,
            )
            if not m:
                raise QueryError(f"cannot parse query condition {part!r}")
            if m.group("kind") is not None:
                # typed operand: `DATE 2006-01-02` / `TIME <RFC3339>`
                # (reference query.go:81-83; layouts per query.peg)
                kind = m.group("kind").lower()
                raw = m.group("tval")
                op = m.group("op")
                if op == "CONTAINS":
                    raise QueryError(
                        f"CONTAINS does not apply to {kind.upper()} operands")
                if (kind == "time") != ("T" in raw):
                    raise QueryError(
                        f"{kind.upper()} operand has the wrong layout: {raw!r}")
                t = _parse_tag_time(raw)
                if t is None:
                    raise QueryError(f"bad {kind.upper()} operand {raw!r}")
                self.conditions.append(
                    _Condition(key=m.group("key"), op=op, value=raw,
                               kind=kind, tvalue=t)
                )
                continue
            self.conditions.append(
                _Condition(
                    key=m.group("key"),
                    op=m.group("op"),
                    value=m.group("qval") if m.group("qval") is not None else m.group("val"),
                )
            )

    def matches(self, tags: Dict[str, str]) -> bool:
        return all(c.matches(tags) for c in self.conditions)

    def condition_keys(self) -> tuple:
        """The tag keys this query reads — a match verdict is a pure
        function of exactly these tags' values, which is what lets
        publish_batch evaluate the query once per distinct value-shape
        instead of once per message."""
        return tuple(c.key for c in self.conditions)

    def __eq__(self, other):
        return isinstance(other, Query) and self.raw == other.raw

    def __hash__(self):
        return hash(self.raw)

    def __str__(self):
        return self.raw


@dataclass
class Message:
    data: object
    tags: Dict[str, str] = field(default_factory=dict)
    # libs/tracing cause() of the publishing thread (None while the
    # recorder is off): a subscriber's span names it as its parent
    cause: Optional[tuple] = None


class Subscription:
    """Buffered subscription; read with get()/poll() or drain via callback."""

    def __init__(self, query: Query, capacity: int = 1024):
        self.query = query
        self._buf: List[Message] = []
        self._cond = threading.Condition()
        self._cancelled = False
        self.capacity = capacity
        # messages shed because the buffer was full — consumers that
        # care about loss (the RPC fan-out layer applies its own
        # slow-client policy downstream) can watch this instead of the
        # drop being silent
        self.dropped = 0

    def publish(self, msg: Message) -> bool:
        with self._cond:
            if self._cancelled:
                return False
            if len(self._buf) >= self.capacity:
                # slow subscriber: drop (reference: err/unsubscribe),
                # but never silently — the counter is the trace
                self.dropped += 1
                return False
            self._buf.append(msg)
            self._cond.notify_all()
            return True

    # max messages appended per publish_batch lock hold: amortizes the
    # lock ~64x while still RELEASING it between chunks, so a consumer
    # draining concurrently can interleave — a block bigger than a
    # subscription's capacity sheds only what the consumer genuinely
    # can't keep up with (the per-tx publish behavior), not
    # deterministically everything past `capacity`
    PUBLISH_CHUNK = 64

    def publish_batch(self, msgs: List[Message]) -> int:
        """Append a batch in chunked lock holds. Semantics match
        calling publish() per message: drops are accounted PER MESSAGE
        (a burst overflowing the buffer by k bumps `dropped` by k, not
        by 1), consumers are notified per chunk and can drain between
        chunks. Returns the number actually buffered."""
        appended = 0
        n = len(msgs)
        for start in range(0, n, self.PUBLISH_CHUNK):
            chunk = msgs[start:start + self.PUBLISH_CHUNK]
            with self._cond:
                if self._cancelled:
                    return appended
                chunk_appended = 0
                for msg in chunk:
                    if len(self._buf) >= self.capacity:
                        self.dropped += 1
                    else:
                        self._buf.append(msg)
                        chunk_appended += 1
                if chunk_appended:
                    self._cond.notify_all()
                    appended += chunk_appended
        return appended

    def get(self, timeout: Optional[float] = None) -> Optional[Message]:
        with self._cond:
            if not self._buf:
                self._cond.wait(timeout)
            if self._buf:
                return self._buf.pop(0)
            return None

    def get_batch(self, max_n: int = 1024,
                  timeout: Optional[float] = None) -> List[Message]:
        """Drain up to max_n buffered messages in one lock acquisition
        (order preserved); waits like get() when the buffer is empty.
        Block-at-a-time consumers (the tx indexer, the websocket pumps)
        use this so a block's burst costs one wakeup, not one per tx."""
        with self._cond:
            if not self._buf:
                self._cond.wait(timeout)
            if not self._buf:
                return []
            out = self._buf[:max_n]
            del self._buf[:max_n]
            return out

    def poll(self) -> Optional[Message]:
        with self._cond:
            return self._buf.pop(0) if self._buf else None

    def cancel(self) -> None:
        with self._cond:
            self._cancelled = True
            self._cond.notify_all()

    @property
    def cancelled(self) -> bool:
        return self._cancelled


class PubSub:
    """Tag-filtered pubsub server (reference libs/pubsub/pubsub.go)."""

    def __init__(self):
        self._subs: Dict[tuple, Subscription] = {}
        self._lock = threading.Lock()

    def subscribe(self, subscriber: str, query: Query, capacity: int = 1024) -> Subscription:
        key = (subscriber, str(query))
        with self._lock:
            if key in self._subs:
                raise ValueError(f"already subscribed: {key}")
            sub = Subscription(query, capacity)
            self._subs[key] = sub
            return sub

    def unsubscribe(self, subscriber: str, query: Query) -> None:
        key = (subscriber, str(query))
        with self._lock:
            sub = self._subs.pop(key, None)
        if sub:
            sub.cancel()

    def unsubscribe_all(self, subscriber: str) -> None:
        with self._lock:
            keys = [k for k in self._subs if k[0] == subscriber]
            subs = [self._subs.pop(k) for k in keys]
        for s in subs:
            s.cancel()

    def publish(self, data: object, tags: Dict[str, str]) -> None:
        msg = Message(data, tags, tracing.cause())
        with self._lock:
            subs = list(self._subs.values())
        for sub in subs:
            if sub.query.matches(tags):
                sub.publish(msg)

    def publish_batch(self, items) -> None:
        """Publish a whole block's worth of (data, tags) pairs in one
        call. Subscriber-observed semantics are identical to calling
        publish() per item in order (property-tested), but the cost
        model is block-scoped: the subscription list is snapshotted
        once, each subscription's buffer lock is taken once, and each
        query is evaluated once per DISTINCT tag-shape — the tuple of
        values under the keys the query actually reads — instead of
        once per (message x subscription). A block of N txs matched by
        a `tm.event = 'Tx'` subscription costs one evaluation, not N;
        a per-hash query still evaluates per message (every shape is
        distinct) and loses nothing."""
        cause = tracing.cause()
        msgs = [Message(d, t, cause) for d, t in items]
        if not msgs:
            return
        with self._lock:
            subs = list(self._subs.values())
        for sub in subs:
            q = sub.query
            keys = q.condition_keys()
            shape_verdicts: Dict[tuple, bool] = {}
            matched: List[Message] = []
            for msg in msgs:
                shape = tuple(msg.tags.get(k) for k in keys)
                verdict = shape_verdicts.get(shape)
                if verdict is None:
                    verdict = q.matches(msg.tags)
                    shape_verdicts[shape] = verdict
                if verdict:
                    matched.append(msg)
            if matched:
                sub.publish_batch(matched)

    def num_subscriptions(self) -> int:
        with self._lock:
            return len(self._subs)
