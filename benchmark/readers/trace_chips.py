"""What only a host with several chips has to say, from the device
planes of the traced window (benchmark/README-4chip.md).

`what`:
- `chips_busy`: the device planes on which a program whose name matches
  `pattern` started inside the window.
- `busy_skew_pct`: 100 x (max - min) / max of the per-chip busy seconds
  (each chip's union of operation intervals, as `busy_seconds` takes
  them before it averages). Needs two planes.
- `wall_ms_per_batch`: the union over every plane of the matching
  programs' intervals, over the number of merged intervals: a batch's
  wall on the device from the first chip's start to the last chip's
  end, while batches lie further apart than a batch is long.

None where there is no trace, no device plane or no matching program:
never 0 for what could not be read."""
import re

from ..harness import trace as tr


def read(p: dict, run) -> float | None:
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    planes = list(run.trace.devices.values())
    if p["what"] == "busy_skew_pct":
        busy = [sum(e - s for s, e in tr.union(
            [(s, s + d) for _, s, d in dev["ops"]], lo, hi)) for dev in planes]
        if len(busy) < 2 or max(busy) <= 0:
            return None
        return 100.0 * (max(busy) - min(busy)) / max(busy)
    rx = re.compile(p["pattern"])
    per_chip = [[(s, s + d) for name, s, d in dev["modules"]
                 if lo <= s < hi and rx.search(name)] for dev in planes]
    if p["what"] == "chips_busy":
        return float(sum(1 for chip in per_chip if chip)) or None
    batches = tr.union([iv for chip in per_chip for iv in chip], lo, hi)
    if not batches:
        return None
    return sum(e - s for s, e in batches) / len(batches) / 1e6
