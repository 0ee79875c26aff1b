#!/usr/bin/env python3
"""What the program's span recorder costs when it is on: one untraced
run of a cell (`run.py --trace 0`, so the profiler stays off and the
line carries the end-to-end metrics) with the recorder switched on from
outside the program, or left off for the other side of the comparison.

    python3 benchmark/tools/recorder_cost.py --workload <cell> --seed <n> \
        --seconds <s> --recorder <0|1>

The program has no switch for this: the process-global tracer is
enabled here, before the node is built with `instrumentation.tracing`
off (a node only disables what it enabled). After the run the last
stderr line counts the spans the run finished, which over the run's
length is the rate the per-span cost is reckoned from. With
`--spans-out` (and the recorder on) the ring's records are written out,
for tools/span_table.py.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--recorder", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run
    from tendermint_tpu.libs import tracing

    tracer = tracing.get_tracer()
    if args.recorder:
        tracer.enable()
    t0 = time.monotonic()
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "0"])
    print(f"recorder: on={args.recorder} spans_finished="
          f"{tracer.dropped + len(tracer.events())} dropped={tracer.dropped} "
          f"process_s={time.monotonic() - t0:.1f}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
