#!/usr/bin/env python3
"""One chip against all the chips of a host, bucket by bucket, and the
two ways a commit can be cut over them. Run on the chip (a host with
several); direct calls, no node.

For each size: the same seeded triples (110-byte messages, a few
signatures corrupted; every mask is held to the reference's) through
`verify_batch(devices=1)` and `verify_batch(devices=<all>)`, warm, the
two sides alternating: the median wall of the call, the spans under it,
and from one device trace over further calls the wall on the device
(first chip's start to last chip's end) and the chip-seconds of a call.
Then, at `--commit` signatures, `sharded_commit_verify` (the psum step)
beside `verify_batch` on all chips. Every shape is compiled or loaded
first, several at a time. Prints a table and one JSON line; the same
JSON goes to `--out`.

    python3 benchmark/tools/chips_table.py --seed 7 \\
        --out chiprun_out/chips_table.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import types
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SPANS = ("verify.pack", "verify.h2d", "verify.launch", "verify.wait")


def triples(seed: int, n: int):
    """n seeded (msg, sig, pub) with every 97th signature corrupted, and
    the reference's mask."""
    import numpy as np

    from benchmark.harness import reference, signer

    seeds = [signer.seed_of(b"chips-%d" % seed, i) for i in range(n)]
    signer.init_worker(seeds)
    rng = np.random.default_rng(seed)
    body = rng.integers(0, 256, (n, 110), dtype=np.uint8)
    msgs = [body[i].tobytes() for i in range(n)]
    blob = signer.sign_messages(list(enumerate(msgs)))
    sigs = [blob[64 * i:64 * i + 64] for i in range(n)]
    for i in range(n - 1, -1, -97):  # the last lane, then every 97th down
        sigs[i] = sigs[i][:7] + bytes([sigs[i][7] ^ 4]) + sigs[i][8:]
    pubs = [signer.public_key(s) for s in seeds]
    want = [reference.verify_one(m, s, p) for m, s, p in zip(msgs, sigs, pubs)]
    assert want.count(False) == len(range(n - 1, -1, -97))
    return msgs, sigs, pubs, want


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="8,64,512,2048,10000",
                    help="signatures a batch: buckets 8, 64, 512, 2,048, 10,240")
    ap.add_argument("--commit", type=int, default=10000,
                    help="signatures of the commit cut two ways; 0 leaves it out")
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--traced-reps", type=int, default=5)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--compile-threads", type=int, default=6)
    ap.add_argument("--out", default=None)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal on virtual devices: no number of it counts")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import device as devlib
    from benchmark.harness import trace as tr
    from benchmark.readers import trace_chips

    devlib.cache_dir(ROOT)
    device = devlib.require(2, allow_cpu=args.allow_cpu)
    import jax.profiler as jp

    from tendermint_tpu.crypto.jaxed25519 import verify as V
    from tendermint_tpu.libs import tracing

    chips = device["count"]
    sizes = [int(n) for n in args.sizes.split(",")]
    data = {n: triples(args.seed + n, n)
            for n in sorted({*sizes, args.commit} - {0})}
    powers = [10] * args.commit
    for_block = [1] * args.commit

    def verify(n: int, ndev: int):
        msgs, sigs, pubs, want = data[n]
        got = V.verify_batch(msgs, sigs, pubs, devices=ndev)
        assert got == want, f"mask differs from the reference at n={n} ndev={ndev}"

    def commit_step():
        msgs, sigs, pubs, want = data[args.commit]
        got, tally = V.sharded_commit_verify(msgs, sigs, pubs, powers, for_block,
                                             devices=chips)
        assert got == want and tally == 10 * want.count(True)

    # every shape made ready first, several compiles at a time
    jobs = [(f"verify n={n} ndev={d}", lambda n=n, d=d: verify(n, d))
            for n in sizes for d in (1, chips)]
    if args.commit:
        jobs.append((f"commit_step n={args.commit} ndev={chips}", commit_step))

    def ready(job):
        t0 = time.monotonic()
        job[1]()
        return job[0], round(time.monotonic() - t0, 1)

    t0 = time.monotonic()
    with ThreadPoolExecutor(args.compile_threads) as pool:
        ready_s = dict(pool.map(ready, jobs))
    print(f"chips_table: {len(jobs)} shapes ready in {time.monotonic() - t0:.1f}s: "
          f"{ready_s}", file=sys.stderr, flush=True)

    tracer = tracing.get_tracer()
    tracer.enable()

    def walls(calls: dict, reps: int) -> dict:
        """Median wall of each call in ms, the sides alternating; and the
        mean of each verify.* span under it."""
        out = {}
        took: dict = {k: [] for k in calls}
        marks: dict = {k: [] for k in calls}
        tracer.clear()
        for _ in range(reps):
            for k, call in calls.items():
                a = time.perf_counter_ns()
                call()
                b = time.perf_counter_ns()
                took[k].append((b - a) / 1e6)
                marks[k].append((a, b))
        events = [e for e in tracer.events() if e.name in SPANS]
        for k in calls:
            spans = {name: [] for name in SPANS}
            for e in events:
                if any(a <= e.start_ns < b for a, b in marks[k]):
                    spans[e.name].append(e.dur_ns / 1e6)
            out[k] = {"wall_ms_median": statistics.median(took[k]),
                      "wall_ms_min": min(took[k]), "wall_ms_max": max(took[k]),
                      **{name.split(".")[1] + "_ms": statistics.fmean(v)
                         for name, v in spans.items() if v}}
        return out

    def device_times(calls: dict, reps: int, pattern: str) -> dict:
        """One trace over `reps` calls of each: a call's wall on the
        device and its chip-seconds, by the call's own stretch of the
        trace's clock."""
        tmp = tempfile.mkdtemp(prefix="chips_table_")
        sync_perf = tr.start_profile(tmp)
        marks = {}
        for k, call in calls.items():
            a = time.perf_counter_ns()
            for _ in range(reps):
                call()
            marks[k] = (a, time.perf_counter_ns())
        jp.stop_trace()
        try:
            trace = tr.load(tr.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        shift = trace.sync_ns - sync_perf
        out = {}
        for k, (a, b) in marks.items():
            lo, hi = a + shift, b + shift
            seconds, events = tr.named_seconds(trace, "modules", pattern, lo, hi)
            stretch = types.SimpleNamespace(trace=trace, trace_window=(lo, hi))

            def chips(what):
                return trace_chips.read({"what": what, "pattern": pattern},
                                        stretch) or 0.0

            out[k] = {"programs": events, "chips_busy": int(chips("chips_busy")),
                      "device_wall_ms": chips("wall_ms_per_batch"),
                      "chip_ms": 1e3 * seconds / reps}
        return out

    table = []
    for n in sizes:
        calls = {d: (lambda n=n, d=d: verify(n, d)) for d in (1, chips)}
        w = walls(calls, args.reps)
        t = device_times(calls, args.traced_reps, "ed25519_verify")
        for d in (1, chips):
            table.append({"n": n, "bucket": V._bucket(n), "ndev": d, **w[d], **t[d]})
            print(f"chips_table: {table[-1]}", file=sys.stderr, flush=True)

    paths = []
    if args.commit:
        calls = {"verify_batch": lambda: verify(args.commit, chips),
                 "sharded_commit_verify": commit_step}
        w = walls(calls, args.reps)
        t = device_times(calls, args.traced_reps, "ed25519_")
        paths = [{"path": k, "n": args.commit, "ndev": chips, **w[k], **t[k]}
                 for k in calls]

    print(f"{'n':>6} {'ndev':>4} {'wall ms':>9} {'pack':>8} {'h2d':>7} "
          f"{'launch':>7} {'wait':>8} {'dev wall':>9} {'chip ms':>8}")
    for r in table:
        print(f"{r['n']:>6} {r['ndev']:>4} {r['wall_ms_median']:>9.3f} "
              f"{r.get('pack_ms', 0):>8.3f} {r.get('h2d_ms', 0):>7.3f} "
              f"{r.get('launch_ms', 0):>7.3f} {r.get('wait_ms', 0):>8.3f} "
              f"{r['device_wall_ms']:>9.3f} {r['chip_ms']:>8.3f}")
    for r in paths:
        print(f"{r['path']:>22} n={r['n']} ndev={r['ndev']} wall "
              f"{r['wall_ms_median']:.3f} ms, device wall "
              f"{r['device_wall_ms']:.3f} ms, chip-ms {r['chip_ms']:.3f}")
    result = {"device": device, "seed": args.seed, "reps": args.reps,
              "traced_reps": args.traced_reps, "ready_s": ready_s,
              "table": table, "paths": paths}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
