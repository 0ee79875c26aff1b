"""Thread CPU under the program's own spans in the traced window.

The CPU twin of `span_time`: the same records on the same clock, the
same choice of spans, read through `span_time`'s own `window_spans` and
`_selected`. Each record a program since PR 38 finishes carries
`cpu_ns`, the CPU its own thread spent between the span's entry and
exit (`time.thread_time_ns`: children and collections on that thread
included). Beside the wall of the same span, the difference is what the
span waited off the CPU: for the interpreter lock, a blocking system
call, an event, the device. Params:

- `name`, `match`, `has`: as `span_time`'s.
- `what`: `cpu_ms` (the chosen spans' `cpu_ns`) | `self_cpu_ms` (less
  the `cpu_ns` of their children on the same thread; a child without a
  reading takes nothing off).
- `per`: `span` | `request` | `arg:<n>`, as `span_time`'s.
- `scale`: multiplies a millisecond result (1000: microseconds).

Only spans that start inside the window and carry a reading count.
None where `span_time` would give None (no trace, records without ids,
a ring that wrapped), where no record of the window carries `cpu_ns`
(a program older than PR 38: the metric is left out of its line), or
where no chosen span carries one: a mean over nothing is not 0.
"""
from .span_time import _selected, window_spans


def read(p: dict, run) -> float | None:
    spans = window_spans(run)
    if spans is None or all(getattr(r, "cpu_ns", None) is None
                            for r, _, _ in spans):
        return None
    lo, hi = run.trace_window
    chosen = [c for c in _selected(spans, dict(p, what="cpu"), lo, hi)
              if c[0].cpu_ns is not None]
    if not chosen:
        return None
    what = p["what"]
    if what == "cpu_ms":
        ns = sum(r.cpu_ns for r, _, _ in chosen)
    elif what == "self_cpu_ms":
        kids: dict = {}
        for r, _, _ in spans:
            if r.parent_id and r.cpu_ns is not None:
                kids.setdefault(r.parent_id, []).append(r)
        ns = sum(max(0, r.cpu_ns - sum(k.cpu_ns for k in kids.get(r.span_id, ())
                                       if k.thread_id == r.thread_id))
                 for r, _, _ in chosen)
    else:
        raise ValueError(f"span_cpu: unknown what {what!r}")
    per = p.get("per", "span")
    if per == "span":
        den = len(chosen)
    elif per == "request":
        den = len({r.request for r, _, _ in chosen if r.request is not None})
    elif per.startswith("arg:"):
        den = sum((r.args or {}).get(per[4:], 0) for r, _, _ in chosen)
    else:
        raise ValueError(f"span_cpu: unknown per {per!r}")
    if not den:
        return None
    return p.get("scale", 1.0) * ns / 1e6 / den
