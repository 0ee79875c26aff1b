"""Device time of the verify programs in the traced window over the
signatures they verified, and its share of the roofline.

Time: the profiler's device events on the `line` ("modules" | "ops")
whose name matches `pattern`. Signatures: the `n` of the program's
crypto.batchVerify spans of backend `backend` inside the same window.
Operations and bytes: benchmark/ops.py, a function of that number of
signatures only. Peak: benchmark/peaks.json by device kind."""
from .. import ops
from ..harness import trace as tr


def read(p: dict, run) -> float | None:
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    seconds, events = tr.named_seconds(run.trace, p["line"], p["pattern"], lo, hi)
    items = sum(s["n"] for s in run.verify_spans
                if s["backend"] == p["backend"] and lo <= s["start"] < hi)
    if not events or not items or seconds <= 0:
        return None
    if p["what"] == "us_per_item":
        return 1e6 * seconds / items
    peak = run.peaks  # a device that is not in the table is an error
    least = max(ops.verify_ops(items) / peak["vpu_scalar_ops_per_s"],
                ops.verify_bytes(items) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
