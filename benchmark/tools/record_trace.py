#!/usr/bin/env python3
"""Records the small trace that tests/test_trace.py reads: three device
batches of 500 signatures through crypto.batch.batch_verify under the
profiler, with the program's span recorder on and the clock-sync
annotation in. Run on the chip; writes <out>/verify3.xplane.pb and
<out>/verify3.spans.json (perf_counter_ns of the sync point, the spans).

    python3 benchmark/tools/record_trace.py chiprun_out/fixture
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark.harness import device, signer
    from benchmark.harness import trace as tr

    device.cache_dir(ROOT)
    device.require(1)
    import jax.profiler as jp

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.libs import tracing

    seeds = [signer.seed_of(b"fixture", i) for i in range(500)]
    signer.init_worker(seeds)
    pubs = [signer.public_key(s) for s in seeds]

    def triples(round_: int):
        msgs = [b"fixture-%d-%d-" % (round_, i) + b"x" * 100 for i in range(500)]
        blob = signer.sign_messages(list(enumerate(msgs)))
        return [(m, blob[64 * i:64 * i + 64], pubs[i]) for i, m in enumerate(msgs)]

    assert all(crypto_batch.batch_verify(triples(0)))  # compiles or loads
    batches = [triples(r) for r in (1, 2, 3)]
    tracing.get_tracer().enable()
    tracing.get_tracer().clear()
    tmp = tempfile.mkdtemp(prefix="bench_fixture_")
    sync = tr.start_profile(tmp)
    t0 = time.monotonic()
    for b in batches:
        assert all(crypto_batch.batch_verify(b))
        time.sleep(0.02)
    jp.stop_trace()
    traced_s = time.monotonic() - t0
    spans = [{"name": r.name, "start_ns": r.start_ns, "dur_ns": r.dur_ns,
              "args": r.args} for r in tracing.get_tracer().events()]
    crypto_batch.shutdown_dispatchers()
    os.makedirs(out_dir, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out_dir, "verify3.xplane.pb"))
    with open(os.path.join(out_dir, "verify3.spans.json"), "w") as f:
        json.dump({"sync_perf_ns": sync, "traced_s": traced_s, "spans": spans}, f)
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(os.path.join(out_dir, "verify3.xplane.pb")).planes:
        print("PLANE", plane.name)
        for ln in plane.lines:
            evs = list(ln.events)
            print("  LINE", ln.name, len(evs))
            for e in evs[:6]:
                print("     ", e.name[:100], e.start_ns, e.duration_ns)
    print("sync_perf_ns", sync, "traced_s", traced_s)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
