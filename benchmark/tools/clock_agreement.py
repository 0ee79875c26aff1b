#!/usr/bin/env python3
"""Do the two clocks agree? The recorder enters every span also as a
`jax.profiler.TraceAnnotation`, so a trace holds each host span twice:
by its annotation on the profiler's clock, and by the recorder's
perf_counter_ns reading carried over the `bench.clock_sync` bridge.
Reads what tools/record_trace.py wrote and prints, per span name, how
far the two starts lie apart.

    python3 benchmark/tools/clock_agreement.py <dir>/verify3.xplane.pb <dir>/verify3.spans.json
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def differences(xplane: str, spans_json: str) -> dict:
    """{span name: [annotation start - bridged recorder start, ns]},
    pairing the k-th annotation of a name with its k-th record."""
    from jax.profiler import ProfileData

    from benchmark.harness import trace as tr

    with open(spans_json) as f:
        meta = json.load(f)
    names = {s["name"] for s in meta["spans"]}
    annotated: dict = {}
    sync_ns = None
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == tr.CLOCK_SYNC:
                    sync_ns = int(e.start_ns)
                elif e.name in names:
                    annotated.setdefault(e.name, []).append(int(e.start_ns))
    if sync_ns is None:
        raise SystemExit("the trace holds no bench.clock_sync annotation")
    shift = sync_ns - meta["sync_perf_ns"]
    out: dict = {}
    for name in sorted(names):
        recorded = sorted(s["start_ns"] + shift for s in meta["spans"]
                          if s["name"] == name)
        seen = sorted(annotated.get(name, []))
        if len(seen) == len(recorded):
            out[name] = [a - r for a, r in zip(seen, recorded)]
    return out


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    diffs = differences(argv[0], argv[1])
    worst = 0
    for name, ds in diffs.items():
        worst = max([worst] + [abs(d) for d in ds])
        print(f"{name}: n={len(ds)} annotation-minus-bridge ns "
              f"min={min(ds)} max={max(ds)}")
    print(f"clock_agreement: {sum(len(d) for d in diffs.values())} spans, "
          f"largest |difference| {worst} ns")
    return 0 if diffs else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
