#!/usr/bin/env python3
"""What the batch funnel's own Python costs a batch, alone: the adaptive
router and the verified-signature cache pass round a backend that
answers at once, one thread on a quiet host, no device.

    python scripts/funnel_cost.py [--triples 10000] [--batches 12] [--cache 65536]

Every batch is new random triples (a catch-up commit: all miss), then
the last one again (all hit). In a benchmark cell the same work reads
more, because the dispatch thread shares the interpreter lock:
`crypto.dispatchWait` holds what comes before `crypto.batchVerify`
opens (the 32-byte look, the keys, the look-up), `fastsync.verifyWait`
the rest. Runs on any checkout of this repo (copy it there); a number
from here is a host cost of the machine it ran on.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--triples", type=int, default=10000)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--cache", type=int, default=65536)
    args = ap.parse_args(argv)

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.sigcache import SigCache

    class Answers(batch.BatchVerifier):
        BACKEND = "jax"

        def _verify(self):
            return [True] * len(self._items)

    def one(items) -> float:
        bv = batch.AdaptiveBatchVerifier(Answers, min_device_batch=1)
        for t in items:
            bv.add(*t)
        t0 = time.perf_counter()
        mask = bv.verify()
        ms = (time.perf_counter() - t0) * 1e3
        assert mask == [True] * len(items)
        return ms

    cache = SigCache(args.cache)
    batch.set_sig_cache(cache)
    rnd = os.urandom
    cold = []
    for _ in range(args.batches):
        items = [(rnd(110), rnd(64), rnd(32)) for _ in range(args.triples)]
        cold.append(one(items))
    hit = one(items)
    print(f"{args.triples} triples a batch, cache {cache.capacity}: "
          f"all miss {min(cold):.2f} / {statistics.median(cold):.2f} / "
          f"{max(cold):.2f} ms a batch (min / median / max of "
          f"{args.batches}), all hit {hit:.2f}; "
          f"hits {cache.hits} misses {cache.misses}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
