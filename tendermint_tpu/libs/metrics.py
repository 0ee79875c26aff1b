"""Minimal Prometheus-style metrics (reference uses go-kit prometheus
metrics per package; node/node.go:100-113 MetricsProvider +
node/node.go:692-709 the /metrics HTTP listener).

Counter/Gauge/Histogram with labels, a Registry rendering Prometheus
text exposition format v0.0.4, and a tiny HTTP server.
"""

from __future__ import annotations

import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Sequence, Tuple

from . import lockdep


def _esc_label(v) -> str:
    """Prometheus label-value escaping: backslash, quote, and newline —
    a newline smuggled into a label value must not break the line-based
    exposition format."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(names: Sequence[str], values: Sequence[str]) -> str:
    if not names:
        return ""
    inner = ",".join(
        f'{n}="{_esc_label(v)}"' for n, v in zip(names, values)
    )
    return "{" + inner + "}"


class _Metric:
    def __init__(self, name: str, help_: str, label_names: Sequence[str]):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        # leaf lock (lockdep-exempt): no metric critical section
        # acquires another lock, and every instrumented hot path
        # observes through one — see libs/lockdep.leaf_lock
        self._lock = lockdep.leaf_lock()

    def with_labels(self, *values: str) -> "_Metric":
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected {len(self.label_names)} labels")
        return _Child(self, tuple(str(v) for v in values))

    def _label_selector(self, by_name: Dict[str, str]):
        """Predicate over stored label-value tuples matching every given
        name=value pair, or None if this family lacks one of the names
        (then nothing can match and callers skip the scan)."""
        if not by_name:
            return None
        try:
            keys = [(self.label_names.index(n), str(v))
                    for n, v in by_name.items()]
        except ValueError:
            return None
        return lambda values: all(values[i] == v for i, v in keys)

    def _series_maps(self) -> Sequence[Dict]:
        """The per-labelset storage dicts to prune (subclass-specific)."""
        raise NotImplementedError

    def remove_labels(self, **by_name) -> int:
        """Drop every series whose labels match all name=value pairs;
        returns the number of series removed. Families without one of
        the names are untouched — so a registry-wide prune by peer_id
        is safe to broadcast. This is the churn valve: without it a
        labeled family keeps series for disconnected peers forever."""
        sel = self._label_selector(by_name)
        if sel is None:
            return 0
        with self._lock:
            maps = self._series_maps()
            doomed = {k for k in maps[0] if sel(k)}
            for m in maps:
                for k in doomed:
                    m.pop(k, None)
        return len(doomed)

    def render(self) -> List[str]:
        raise NotImplementedError


class _Child:
    """A metric bound to one label-value tuple."""

    def __init__(self, parent, values: Tuple[str, ...]):
        self._parent = parent
        self._values = values

    def __getattr__(self, item):
        fn = getattr(self._parent, "_" + item)
        return lambda *a: fn(self._values, *a)


class Counter(_Metric):
    TYPE = "counter"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._vals: Dict[Tuple[str, ...], float] = {}

    def inc(self, amount: float = 1.0) -> None:
        self._inc((), amount)

    def _inc(self, labels: Tuple[str, ...], amount: float = 1.0) -> None:
        with self._lock:
            self._vals[labels] = self._vals.get(labels, 0.0) + amount

    def _series_maps(self):
        return (self._vals,)

    def render(self) -> List[str]:
        with self._lock:
            items = sorted(self._vals.items())
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.TYPE}"]
        if not items and not self.label_names:
            # a label-less metric legitimately exposes 0 before first use;
            # a labeled one with no children must render NO samples — a
            # bare `name 0` line under a labeled family is invalid
            # exposition (and Prometheus would ingest a phantom series)
            items = [((), 0.0)]
        for labels, v in items:
            out.append(
                f"{self.name}{_fmt_labels(self.label_names, labels)} {v:g}")
        return out


class Gauge(_Metric):
    TYPE = "gauge"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._vals: Dict[Tuple[str, ...], float] = {}

    def set(self, value: float) -> None:
        self._set((), value)

    def add(self, amount: float) -> None:
        self._add((), amount)

    def _set(self, labels: Tuple[str, ...], value: float) -> None:
        with self._lock:
            self._vals[labels] = float(value)

    def _add(self, labels: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._vals[labels] = self._vals.get(labels, 0.0) + amount

    def _series_maps(self):
        return (self._vals,)

    def render(self) -> List[str]:
        with self._lock:
            items = sorted(self._vals.items())
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.TYPE}"]
        if not items and not self.label_names:
            items = [((), 0.0)]  # see Counter.render
        for labels, v in items:
            out.append(
                f"{self.name}{_fmt_labels(self.label_names, labels)} {v:g}")
        return out


DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                   5.0, 10.0)


class Histogram(_Metric):
    TYPE = "histogram"

    def __init__(self, name, help_="", label_names=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help_, label_names)
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        self._totals: Dict[Tuple[str, ...], int] = {}

    def observe(self, value: float) -> None:
        self._observe((), value)

    def _observe(self, labels: Tuple[str, ...], value: float) -> None:
        with self._lock:
            counts = self._counts.setdefault(
                labels, [0] * len(self.buckets))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            self._sums[labels] = self._sums.get(labels, 0.0) + value
            self._totals[labels] = self._totals.get(labels, 0) + 1

    def _series_maps(self):
        return (self._totals, self._counts, self._sums)

    def render(self) -> List[str]:
        with self._lock:
            items = sorted(self._totals.items())
            counts = {k: list(v) for k, v in self._counts.items()}
            sums = dict(self._sums)
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.TYPE}"]
        for labels, total in items:
            for i, b in enumerate(self.buckets):
                lf = _fmt_labels(self.label_names + ("le",),
                                 labels + (f"{b:g}",))
                out.append(f"{self.name}_bucket{lf} {counts[labels][i]}")
            lf_inf = _fmt_labels(self.label_names + ("le",),
                                 labels + ("+Inf",))
            out.append(f"{self.name}_bucket{lf_inf} {total}")
            lf = _fmt_labels(self.label_names, labels)
            out.append(f"{self.name}_sum{lf} {sums[labels]:g}")
            out.append(f"{self.name}_count{lf} {total}")
        return out


class ProcessCPU(_Metric):
    """`process_cpu_seconds_total`: user + system CPU seconds of the whole
    process, every thread, ended ones included (time.process_time()),
    read when the registry renders: nothing between scrapes. Name and
    meaning are a Prometheus client's process collector's (what a Go
    node's default registry serves), so it carries no namespace."""

    TYPE = "counter"

    def __init__(self):
        super().__init__("process_cpu_seconds_total",
                         "Total user and system CPU time spent in seconds.",
                         ())

    def render(self) -> List[str]:
        return [f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} {self.TYPE}",
                f"{self.name} {time.process_time()!r}"]


class Registry:
    def __init__(self):
        self._metrics: List[_Metric] = []
        # leaf: held only to copy the metric list; child renders and
        # prunes run after release
        self._lock = lockdep.leaf_lock()

    def register(self, metric: _Metric) -> _Metric:
        with self._lock:
            self._metrics.append(metric)
        return metric

    def counter(self, name, help_="", label_names=()) -> Counter:
        return self.register(Counter(name, help_, label_names))

    def gauge(self, name, help_="", label_names=()) -> Gauge:
        return self.register(Gauge(name, help_, label_names))

    def histogram(self, name, help_="", label_names=(),
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self.register(Histogram(name, help_, label_names, buckets))

    def remove_labels(self, **by_name) -> int:
        """Prune matching series from EVERY registered family (families
        lacking one of the label names are untouched); returns the total
        series removed. Called on peer disconnect so peer-labeled
        cardinality tracks the live peer set, not its history."""
        with self._lock:
            metrics = list(self._metrics)
        return sum(m.remove_labels(**by_name) for m in metrics)

    def render(self) -> str:
        with self._lock:
            metrics = list(self._metrics)
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


class MetricsServer:
    """Serves the registry at /metrics (node/node.go:692-709)."""

    def __init__(self, registry: Registry, host: str, port: int):
        self.registry = registry
        handler = _make_handler(registry)
        self._httpd = ThreadingHTTPServer((host, port), handler)
        self._httpd.daemon_threads = True
        self._thread: Optional[threading.Thread] = None

    @property
    def listen_addr(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"{host}:{port}"

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics-http",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def _make_handler(registry: Registry):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            if self.path.rstrip("/") not in ("", "/metrics"):
                self.send_error(404)
                return
            body = registry.render().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    return Handler
