"""100 x the part of the traced window in which spans `name` from at
least as many distinct values of the arg `arg` are open at once, over
the window: with `p2p.recvThrottle` by `peer` and as many values as the
configuration has `peers`, the share in which every link's limiter was
asleep, where `p2p_recv_throttled_pct.*` (the union) says some link's
was. `distinct_from_config` names the configuration's key that holds
the number of values wanted.

None as `span_time` says, and where no span that touches the window
carries `arg` at all: a program that does not say whose connection a
span belongs to (spans of other names count as saying so: a window in
which blocks arrived labelled and no limiter slept reads 0.0).
"""
from ..harness import trace as tr
from .span_time import window_spans


def read(p: dict, run) -> float | None:
    spans = window_spans(run)
    if spans is None:
        return None
    arg, lo, hi = p["arg"], *run.trace_window
    if not any(arg in (rec.args or {}) for rec, _, _ in spans):
        return None
    need = int(run.cell.config[p["distinct_from_config"]])
    by_value: dict = {}
    for rec, start, end in spans:
        if rec.name == p["name"] and arg in (rec.args or {}):
            by_value.setdefault(rec.args[arg], []).append((start, end))
    # each value's own stretches first, so that two spans of one link
    # that touch never count as two links
    edges = []
    for stretches in by_value.values():
        for start, end in tr.union(stretches, lo, hi):
            edges += [(start, 1), (end, -1)]
    covered = depth = 0
    since = lo
    for at, step in sorted(edges):
        if depth >= need:
            covered += at - since
        depth += step
        since = at
    return 100.0 * covered / (hi - lo)
