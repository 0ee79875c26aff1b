#!/usr/bin/env python3
"""Quartile spreads of the two sets that tools/sets.sh left under
chiprun_out/<tag>/: for each end-to-end metric the median, the spread
(third minus first quartile of statistics.quantiles(n=4), as a share of
the median) of each set, and whether every run said `correct`.

    python3 benchmark/tools/spreads.py chiprun_out/light
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        lines = f.read().strip().splitlines()
    return json.loads(lines[-1]) if lines else {}


def main(directory: str) -> int:
    runs = {os.path.basename(p): last_line(p)
            for p in sorted(glob.glob(os.path.join(directory, "*.out")))}
    bad = [n for n, r in runs.items() if not r.get("correct")]
    print(f"{len(runs)} runs, not correct: {bad}")
    for prefix in ("s1_", "s2_"):
        rows = [r for n, r in runs.items() if n.startswith(prefix) and r]
        if len(rows) < 2:
            continue
        for metric in rows[0]["metrics"]:
            vals = [r["metrics"][metric]["value"] for r in rows]
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            print(f"{prefix}{metric}: median {med:.6g} spread "
                  f"{100 * (q[2] - q[0]) / med:.2f}% min {min(vals):.6g} "
                  f"max {max(vals):.6g} n={len(vals)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
