#!/usr/bin/env python3
"""The sweep that found kv-signed-steady's rate: the cell's driver at
each of a few offered rates, one after another in one process on the
chip. A rate is sustained when the mempool is no fuller at the window's
end than at its middle and the generator's own p99 lateness stays under
one block interval. Run once; the rate is then a number in the cell's
file (four fifths of the highest sustained).

    python3 benchmark/tools/sweep_rate.py kv-signed-steady 1000,1400,1800,2200 10 77
"""

from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(workload: str, rates: str, seconds: str, seed: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import run as runmod
    from benchmark.harness import device, manifest

    device.cache_dir(ROOT)
    cell = manifest.Cell(workload)
    device.require(cell.chips)
    driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
    for k, rate in enumerate(int(r) for r in rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_tx_per_s=rate)
        out = driver.run(runmod.Run(cell, int(seed) + k, float(seconds), False))
        print(json.dumps({"rate": rate, **out["end_to_end"],
                          "attempted": out["attempted"], "failed": out["failed"],
                          **out["facts"],
                          "checks": {k: v[0] for k, v in out["numbers"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
