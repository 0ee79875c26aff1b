"""Time under the program's own spans in the traced window.

The spans are the `libs/tracing` recorder's records (`run._spans_raw`,
whole SpanRecords with ids), put on the trace's clock by the same
bridge `Run.reduce_trace` uses. Params:

- `name`: one span name or a list (summed); `match`: args that must be
  equal; `has`: args that must be present.
- `what`: `total_ms` | `self_ms` (duration less the union of the span's
  children on its own thread) | `gap_to_parent_ms` (start minus the end
  of the span that caused it: what a queue held) | `pct_of_window`
  (100 x the time under the spans, clipped to the window, over it).
- `per`: `span` | `request` (distinct request ids: per block, per POST)
  | `arg:<n>` (the sum of that arg); not used by `pct_of_window`.
- `scale`: multiplies a millisecond result (1000: microseconds).

None when there is no trace, when the program's records carry no ids
(a program older than the spans) or when the ring was full at the
snapshot (a span of the window may have been dropped): the records
cannot be trusted. Where they can and no span of the name is in the
window, `pct_of_window` is 0.0 (the recorder was on and whole, and the
span never opened: `fastsync.poolWait` with a pool that never starved),
and every other `what` is None: a mean over nothing is not 0. The
records are read when the profiler's stop returns, seconds after the
window (run.py `trace_stop`), so a span recorded when it ends
(`fastsync.poolWait`, `runtime.gc`, `p2p.recvThrottle`) that was open at
the window's end is in them and counts up to that end. Only one still
open seconds later is missed: a wait that never ends, in which the cell
applies no block and says so end to end. A throttled stretch is cut and
recorded every 100 ms by the program, so a limiter that sleeps through
the whole window reads its share, not 0.
"""
from ..harness import trace as tr


def window_spans(run):
    """[(record, start, end)] on the trace's clock for every record that
    touches the traced window, or None where they cannot be trusted."""
    trace = run.trace
    if trace is None or trace.sync_ns is None or not run._spans_raw:
        return None
    if getattr(run._spans_raw[0], "span_id", None) is None:
        return None
    from tendermint_tpu.libs import tracing

    if len(run._spans_raw) >= tracing.get_tracer().capacity:
        return None  # the ring wrapped: the window is not whole
    shift = trace.sync_ns - run._sync_perf_ns
    lo, hi = run.trace_window
    out = []
    for rec in run._spans_raw:
        start = rec.start_ns + shift
        end = start + rec.dur_ns
        if end > lo and start < hi:
            out.append((rec, start, end))
    return out


def _selected(spans, p, lo, hi):
    """The spans `p` names: those that start inside the window, or, for
    a share of the window, all that reach into it."""
    names = p["name"] if isinstance(p["name"], list) else [p["name"]]
    match, has = p.get("match", {}), p.get("has", [])
    share = p["what"] == "pct_of_window"
    out = []
    for rec, start, end in spans:
        if rec.name not in names or not (share or lo <= start < hi):
            continue
        args = rec.args or {}
        if (all(args.get(k) == v for k, v in match.items())
                and all(k in args for k in has)):
            out.append((rec, start, end))
    return out


def _self_ns(rec, start, end, children) -> int:
    kids = [(s, e) for r, s, e in children.get(rec.span_id, ())
            if r.thread_id == rec.thread_id]
    return (end - start) - sum(e - s for s, e in tr.union(kids, start, end))


def read(p: dict, run) -> float | None:
    spans = window_spans(run)
    if spans is None:
        return None
    lo, hi = run.trace_window
    chosen = _selected(spans, p, lo, hi)
    what = p["what"]
    if what == "pct_of_window":
        # the records are whole: no span of the name is a share of 0
        covered = tr.union([(s, e) for _, s, e in chosen], lo, hi)
        return 100.0 * sum(e - s for s, e in covered) / (hi - lo)
    if not chosen:
        return None  # a mean over nothing is not 0
    if what == "total_ms":
        ns = sum(e - s for _, s, e in chosen)
    elif what == "self_ms":
        children: dict = {}
        for item in spans:
            children.setdefault(item[0].parent_id, []).append(item)
        ns = sum(_self_ns(r, s, e, children) for r, s, e in chosen)
    elif what == "gap_to_parent_ms":
        ends = {r.span_id: e for r, _, e in spans}
        chosen = [c for c in chosen if c[0].parent_id in ends]
        if not chosen:
            return None
        ns = sum(max(0, s - ends[r.parent_id]) for r, s, _ in chosen)
    else:
        raise ValueError(f"span_time: unknown what {what!r}")
    per = p.get("per", "span")
    if per == "span":
        den = len(chosen)
    elif per == "request":
        den = len({r.request for r, _, _ in chosen if r.request is not None})
    elif per.startswith("arg:"):
        den = sum((r.args or {}).get(per[4:], 0) for r, _, _ in chosen)
    else:
        raise ValueError(f"span_time: unknown per {per!r}")
    if not den:
        return None
    return p.get("scale", 1.0) * ns / 1e6 / den
