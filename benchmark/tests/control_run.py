#!/usr/bin/env python3
"""A run of a cell with a fault planted under the timed path: the
control (`accept_all`) and the faults of tests/faults.py, on the chip at
the cell's own size. `correct` has to read false (or the run has to end
with no result at all).

    python3 benchmark/tests/control_run.py --fault accept_all \\
        --workload sync500-light --seed 7 --seconds 5 --trace 0
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    sys.path.insert(0, ROOT)
    argv = sys.argv[1:]
    i = argv.index("--fault")
    name = argv[i + 1]
    del argv[i:i + 2]
    from benchmark import run
    from benchmark.tests import faults

    try:
        return run.main(argv, fault=faults.FAULTS[name])
    finally:
        faults.undo()


if __name__ == "__main__":
    sys.exit(main())
