"""100 x the device's idle time in the traced window whose middle no
span of the program covers, over the window: the `unattributed` line of
`breakdown.idle_gaps` as a share. Each idle gap of the first chip goes
whole to the innermost span open at its middle (`harness/trace.py`
`idle_gaps`), so a cell whose chip is idle for long stretches reads in
coarse steps. None as `span_time` says."""
from ..harness import trace as tr
from .span_time import window_spans


def read(p: dict, run) -> float | None:
    spans = window_spans(run)
    if not spans or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    gaps = dict(tr.idle_gaps(run.trace, [(r.name, s, e) for r, s, e in spans],
                             lo, hi, limit=1 << 30))
    return 100.0 * gaps.get("unattributed", 0.0) / ((hi - lo) / 1e9)
