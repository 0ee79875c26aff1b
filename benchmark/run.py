#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Fails before compiling anything unless JAX comes up on a
TPU with as many chips as the cell asks for. Builds the deployment from
the cell's configuration file and the seed, warms the shapes the cell
uses, measures for --seconds, checks what the window produced against
the plain reference, and prints one JSON object as its last line.
Cells, configurations, traffic and metrics are found by file name
(benchmark/README.md); this file knows none of them.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # as near the process's start as Python lets us

import argparse
import importlib
import json
import logging
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Run:
    """What a driver is given, and where it leaves what the readers read."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 fault=None):
        self.cell, self.seed, self.seconds, self.trace_on = cell, seed, seconds, trace
        self.workers = max(1, min(12, (os.cpu_count() or 2) - 1))
        self.fault = fault
        self.setup_s = None
        self.trace = None
        self.trace_window = (0, 0)
        self.traced_s = self.busy_s = 0.0
        self.verify_spans: list = []
        self.host_spans: list = []
        self.prom = self.crypto = ({}, {})
        self.facts: dict = {}
        self.peaks: dict = {}
        self.t_open = self.t_close = None
        self._trace_dir = None
        self._trace_at = self._trace_t0 = self._sync_perf_ns = None
        self._spans_raw: list = []

    # -- hooks the drivers call ----------------------------------------

    def install(self, node) -> None:
        """The rehearsal tests break the timed path here."""
        if self.fault is not None:
            self.fault(node)

    def window_opens(self, t_open: float, surf) -> None:
        """The window's first instant and its first readings. The window
        is [t_open, t_open + seconds] whatever any thread does after."""
        self.setup_s = t_open - _T0
        self.t_open, self.t_close = t_open, t_open + self.seconds
        self._surf = surf
        self._prom0, self._crypto0 = self._readings()
        print(f"benchmark: window open after {self.setup_s:.1f}s of set-up; its "
              f"first readings took {time.monotonic() - t_open:.3f}s",
              file=sys.stderr)
        if self.trace_on:
            # --trace 1 traces the window's last `trace_seconds`
            cap = self.cell.traffic.get("trace_seconds", 8)
            self._trace_at = max(t_open, self.t_close - cap)

    def _readings(self) -> tuple:
        from benchmark.harness import prom

        return (prom.scrape(self._surf.metrics_addr),
                self._surf.debug("/debug/crypto"))

    def wait_until(self, when: float) -> None:
        """Sleeps the driver's main thread up to the instant `when` of the
        window, and starts the profiler when the traced part begins."""
        while time.monotonic() < when:
            if self._trace_at is not None and time.monotonic() >= self._trace_at:
                self._trace_start()
            time.sleep(min(0.05, max(0.0, when - time.monotonic())))

    def _trace_start(self) -> None:
        from benchmark.harness import trace as tr
        from tendermint_tpu.libs import tracing

        self._trace_at = None
        t = time.monotonic()
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        tracing.get_tracer().clear()
        self._sync_perf_ns = tr.start_profile(self._trace_dir)
        self._trace_t0 = time.monotonic()
        print(f"benchmark: profiler started {self._trace_t0 - self.t_open:.3f}s "
              f"into the window, in {self._trace_t0 - t:.3f}s", file=sys.stderr)

    def window_closes(self) -> int:
        """Called at t_close: the window's last readings; nothing here
        takes long. The traced window ends at t_close, whenever this is
        called. Returns the device's memory peak."""
        from benchmark.harness import device

        prom1, crypto1 = self._readings()
        self.prom, self.crypto = (self._prom0, prom1), (self._crypto0, crypto1)
        if self._trace_t0 is not None:
            self.traced_s = self.t_close - self._trace_t0
        return device.memory_peak_bytes()

    def trace_stop(self) -> None:
        """Stops the profiler. Writing the trace out loads the process
        for seconds (0.12 s a traced program), so a driver calls this
        after window_closes() and after whatever else the window's
        answers still wait for: never inside [t_open, t_close]. The
        recorder's spans are read when it returns, not at t_close: a
        span is recorded when it ends, and one that was open at t_close
        (a wait, a collection, a throttled stretch) has ended by then,
        so a share of the window counts it (readers/span_time.py)."""
        if self._trace_t0 is None:
            return
        import jax.profiler as jp

        from tendermint_tpu.libs import tracing

        if time.monotonic() < self.t_close:
            raise RuntimeError("the profiler may not be stopped inside the window")
        self._trace_t0 = None
        t = time.monotonic()
        jp.stop_trace()
        self._spans_raw = tracing.get_tracer().events()
        print(f"benchmark: profiler stopped in {time.monotonic() - t:.1f}s, "
              f"{t - self.t_close:.3f}s after the window; the recorder holds "
              f"{len(self._spans_raw)} spans", file=sys.stderr)

    # -- after the window ----------------------------------------------

    def reduce_trace(self) -> dict | None:
        """Reads the trace the window left; returns `breakdown`."""
        if self._trace_dir is None:
            return None
        from benchmark.harness import trace as tr

        try:
            self.trace = tr.load(tr.find_xplane(self._trace_dir))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        if self.trace.sync_ns is None:
            raise RuntimeError("the trace holds no bench.clock_sync annotation")
        shift = self.trace.sync_ns - self._sync_perf_ns
        lo = self.trace.sync_ns
        hi = lo + int(self.traced_s * 1e9)
        self.trace_window = (lo, hi)
        self.busy_s = tr.busy_seconds(self.trace, lo, hi)
        for rec in self._spans_raw:
            start = rec.start_ns + shift
            self.host_spans.append((rec.name, start, start + rec.dur_ns))
            if rec.name == "crypto.batchVerify" and rec.args:
                self.verify_spans.append({"start": start, "n": rec.args.get("n", 0),
                                          "backend": rec.args.get("backend")})
        dev = next(iter(self.trace.devices.values()), None)
        if dev is not None and self.busy_s > 0:
            covered = tr.overlap_ns(
                tr.union([(s, s + d) for _, s, d in dev["ops"]], lo, hi),
                tr.union([(s, e) for n, s, e in self.host_spans
                          if n == "crypto.batchVerify"], lo, hi))
            print(f"benchmark: {100 * covered / 1e9 / self.busy_s:.1f}% of the "
                  f"device's busy time lies inside crypto.batchVerify spans "
                  f"(the clock bridge)", file=sys.stderr)
        return {"device_ops": tr.top_ops(self.trace, lo, hi),
                "idle_gaps": tr.idle_gaps(self.trace, self.host_spans, lo, hi)}


def per_layer(cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
        value = reader.read(m.get("params", {}), run)
        if value is not None:  # nothing to read: the metric is left out
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, allow_cpu: bool = False, fault=None, cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "tendermint_tpu")):
        print(f"benchmark: the program is not in this checkout (no "
              f"{ROOT}/tendermint_tpu)", file=sys.stderr)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # this checkout, not an installed copy

    from benchmark.harness import device as devlib
    from benchmark.harness import manifest

    cell = cell or manifest.Cell(args.workload)
    devlib.cache_dir(ROOT)
    device = devlib.require(cell.chips, allow_cpu=allow_cpu)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    run = Run(cell, args.seed, args.seconds, bool(args.trace), fault=fault)
    peaks = manifest.load_json("peaks.json")
    if args.trace and not allow_cpu:
        run.peaks = peaks[device["kind"]]  # not in the table: an error
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}")
    out = driver.run(run)

    run.facts = out["facts"]
    device["memory_peak_bytes"] = out["peak"]
    metrics = {}
    line = {}
    if args.trace:
        breakdown = run.reduce_trace()
        metrics = per_layer(cell, run)
        device["busy_s"], device["window_s"] = run.busy_s, run.traced_s
        if breakdown is not None:
            line["breakdown"] = breakdown
    else:
        values = dict(out["end_to_end"], setup_s=run.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = {}
    correct = out["failed"] == 0
    for name, (value, limit) in out["numbers"].items():
        ok = limit is not None and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    # the driver's own counts go into every run's line (no metric: the
    # checker ignores the key): window_s, heights, chain_used_pct, ...
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device,
              **line, "facts": run.facts, "checks": checks}
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"check failed_operations: {out['failed']} of {out['attempted']} "
          f"(limit 0)", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
