"""From the profiler's .xplane.pb to numbers: device busy time, the time
of named programs and operations, and the idle gaps by what the host
was doing. Kept with the yardstick; `tests/test_trace.py` holds it to a
recorded trace.

Times inside are nanoseconds on the trace's own clock. Host spans come
from the program's `libs/tracing` recorder (perf_counter_ns); the
harness drops one `bench.clock_sync` annotation into the trace at a
known perf_counter_ns reading, which is the bridge between the clocks.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time

CLOCK_SYNC = "bench.clock_sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_FLOOR_NS = 20_000  # shorter gaps are inside one program


def start_profile(trace_dir: str) -> int:
    """Starts the profiler (no Python tracer: it would slow the host it
    watches) and drops the clock-sync annotation; returns the
    perf_counter_ns reading taken inside it."""
    import jax.profiler as jp

    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jp.start_trace(trace_dir, profiler_options=opts)
    with jp.TraceAnnotation(CLOCK_SYNC):
        return time.perf_counter_ns()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return found[-1]


class Trace:
    """devices: {plane name: {"ops": [(name, start, dur)], "modules": [...]}};
    sync_ns: the trace-clock start of the clock-sync annotation, or None."""

    def __init__(self):
        self.devices: dict = {}
        self.sync_ns = None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    out = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue  # a sparse-core or other auxiliary plane
            dev = out.devices.setdefault(plane.name, {"ops": [], "modules": []})
            for key, line_name in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if line_name in lines:
                    dev[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in lines[line_name].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == CLOCK_SYNC:
                        out.sync_ns = int(e.start_ns)
    return out


def union(intervals: list, lo: int, hi: int) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(trace: Trace, lo: int, hi: int) -> float:
    """Seconds in [lo, hi) in which an operation ran, averaged over chips."""
    if not trace.devices:
        return 0.0
    total = 0
    for dev in trace.devices.values():
        total += sum(e - s for s, e in union(
            [(s, s + d) for _, s, d in dev["ops"]], lo, hi))
    return total / len(trace.devices) / 1e9


def named_seconds(trace: Trace, key: str, pattern: str, lo: int, hi: int):
    """(seconds, events) of the `key` ("ops" | "modules") events whose
    name matches `pattern` and that start inside [lo, hi), summed over
    chips."""
    rx = re.compile(pattern)
    ns = n = 0
    for dev in trace.devices.values():
        for name, s, d in dev[key]:
            if lo <= s < hi and rx.search(name):
                ns += d
                n += 1
    return ns / 1e9, n


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """`%while.108 while`, `%_unknown_.1 custom-call tpu_custom_call`:
    the trace names an operation by its whole HLO text."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:100]
    parts = [head]
    m = _OPCODE.search(" " + rest)
    if m:
        parts.append(m.group(1))
    t = _TARGET.search(rest)
    if t:
        parts.append(t.group(1))
    return " ".join(parts)[:100]


def top_ops(trace: Trace, lo: int, hi: int, limit: int = 10) -> list:
    acc: dict = {}
    for dev in trace.devices.values():
        for name, s, d in dev["ops"]:
            if lo <= s < hi:
                name = short_name(name)
                acc[name] = acc.get(name, 0) + d
    return [[name, ns / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:limit]]


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two lists of merged intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(trace: Trace, spans: list, lo: int, hi: int, limit: int = 10) -> list:
    """Idle time of the first chip in [lo, hi) by the host span open at
    the middle of each gap. `spans` are (name, start, end) on the trace
    clock; of those open, the one that started last (the innermost)
    takes the gap."""
    if not trace.devices:
        return []
    dev = trace.devices[sorted(trace.devices)[0]]
    busy = union([(s, s + d) for _, s, d in dev["ops"]], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    latest_end, m = [], 0  # latest_end[i]: the latest end among spans[..i]
    for _, _, e in spans:
        m = max(m, e)
        latest_end.append(m)
    acc: dict = {}
    for k in range(0, len(edges), 2):
        s, e = edges[k], edges[k + 1]
        if e <= s:
            continue
        name = "within_a_program"
        if e - s >= GAP_FLOOR_NS:
            mid = (s + e) // 2
            name = "unattributed"
            i = bisect.bisect_right(starts, mid) - 1
            while i >= 0 and latest_end[i] >= mid:
                if spans[i][2] >= mid:
                    name = spans[i][0]
                    break
                i -= 1
        acc[name] = acc.get(name, 0) + (e - s)
    return [[name, ns / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:limit]]
