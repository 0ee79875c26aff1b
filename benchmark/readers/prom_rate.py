"""One counter's gain across the window, as a rate.

The window's two scrapes of the node's `/metrics` (`run.prom`, taken at
`window_opens` and `window_closes`). Params:

- `family`: the counter; `labels`: labels a sample must carry (summed
  over the rest).
- `per`: `window` gives 100 x the gain over the window's seconds
  (`t_close - t_open`): for `process_cpu_seconds_total`, the % of one
  core the process kept busy | `fact:<key>` gives 1000 x the gain over
  the driver's fact `key` (`blocks`): ms of the counter's seconds a
  block.

None where either scrape lacks the family (a program without it reads
null, not 0) or the fact is missing or 0. `process_cpu_seconds_total`
is the whole process's CPU: in a cell that is the node and what the
benchmark runs inside its process beside it (load generator, serving
peers, height poller) and, in a traced run, the profiler.
"""
from ..harness import prom


def _has(samples: dict, family: str) -> bool:
    return any(name == family for name, _ in samples)


def read(p: dict, run) -> float | None:
    before, after = run.prom
    family = p["family"]
    if not (_has(before, family) and _has(after, family)):
        return None
    gain = prom.delta(before, after, family, p.get("labels"))
    per = p["per"]
    if per == "window":
        if run.t_open is None or run.t_close <= run.t_open:
            return None
        return 100.0 * gain / (run.t_close - run.t_open)
    if per.startswith("fact:"):
        den = run.facts.get(per[5:])
        return 1000.0 * gain / den if den else None
    raise ValueError(f"prom_rate: unknown per {per!r}")
