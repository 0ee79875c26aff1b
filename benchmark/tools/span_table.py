#!/usr/bin/env python3
"""A traced run of a cell, then the recorder's spans by name: count,
total, self time (duration less the children on the same thread) and
mean, for PERF.md's "where the time goes". Prints run.py's result line
first, unchanged.

    python3 benchmark/tools/span_table.py --workload <cell> --seed <n> \
        --seconds <s> [--profiler 0] [--longest <k>] [--out <file.json>]

The table covers the spans that started in the `trace_seconds` of the
cell's traffic file after the recorder was cleared, which is when the
profiler started: the window's last `trace_seconds` (the traced window,
to within the profiler's start-up), read from the ring after the run; it
says so if the ring wrapped meanwhile.
`--profiler 0` makes an untraced run with the recorder switched on from
here (as tools/recorder_cost.py does) and tables everything the ring
holds at the end: the way to catch a stall in a whole window, which
`--longest` then names (the k longest spans, with thread and start).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def table(events, window_ns: int) -> list:
    from tendermint_tpu.libs import tracing

    if not events:
        return []
    t0 = min(r.start_ns for r in events)
    events = [r for r in events if r.start_ns - t0 < window_ns]
    own = tracing.self_times(events)
    rows: dict = {}
    for r in events:
        row = rows.setdefault(r.name, {"name": r.name, "count": 0,
                                       "total_ms": 0.0, "self_ms": 0.0,
                                       "threads": set()})
        row["count"] += 1
        row["total_ms"] += r.dur_ns / 1e6
        row["self_ms"] += own[r.span_id] / 1e6
        row["threads"].add(r.thread_name)
    out = sorted(rows.values(), key=lambda x: -x["total_ms"])
    for row in out:
        row["mean_ms"] = row["total_ms"] / row["count"]
        row["threads"] = sorted(row["threads"])[:4]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profiler", type=int, choices=(0, 1), default=1)
    ap.add_argument("--longest", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run
    from benchmark.harness import manifest
    from tendermint_tpu.libs import tracing

    tracer = tracing.get_tracer()
    if not args.profiler:
        tracer.enable()
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", str(args.profiler)])
    events = tracer.events()
    cap = manifest.Cell(args.workload).traffic.get("trace_seconds", 8)
    rows = table(events, int(min(cap, args.seconds) * 1e9) if args.profiler
                 else 1 << 62)
    print(f"span_table: {len(rows)} names, ring dropped {tracer.dropped}",
          file=sys.stderr)
    if events:
        t0 = min(r.start_ns for r in events)
        for r in sorted(events, key=lambda r: -r.dur_ns)[:args.longest]:
            print(f"  longest {r.name:<24} {r.dur_ns / 1e6:>9.1f} ms at "
                  f"+{(r.start_ns - t0) / 1e9:7.3f} s on {r.thread_name[:28]} "
                  f"request={r.request} args={r.args}", file=sys.stderr)
    for row in rows:
        print(f"  {row['name']:<26} n={row['count']:<6} total={row['total_ms']:>10.1f} ms"
              f"  self={row['self_ms']:>10.1f} ms  mean={row['mean_ms']:>8.3f} ms"
              f"  {','.join(row['threads'])}", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"dropped": tracer.dropped, "rows": rows}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
