"""Driver kind `rpc_open_loop`: signed envelopes offered to one node's
JSON-RPC port at a fixed rate, timed from the client's side.

Set-up starts the node, waits for its verifier, signs every envelope the
run will send (worker processes, OpenSSL), makes the envelope-shaped
kernel buckets ready, subscribes to NewBlock on the node's websocket and
runs the cell's own traffic for `warmup_seconds`. The window follows
without a break: the generator never stops between them.

Open loop: arrival times are fixed before the first byte is sent (the
same exponential gaps for every seed, in a seeded order); tx i belongs
to connection i mod `connections`; a connection posts, as one JSON-RPC
batch, every tx of its own that is due, and a tx that waited for the
previous POST is late, which its latency counts: latency runs from the
instant a tx was due to the arrival at the client of the block that
holds it.
"""

from __future__ import annotations

import base64
import hashlib
import math
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import chain as chainlib
from ..harness import node as nodelib
from ..harness import prom, signer
from ..harness.reference import KVReference
from ..harness.rpcclient import Rpc, RpcError, Subscription

MAGIC = b"sgtx1"
CODE_BAD_SIGNATURE = 0x53
SIG_OFF = len(MAGIC) + 1 + 32


def say(*parts) -> None:
    print("benchmark:", *parts, file=sys.stderr, flush=True)


def make_envelopes(seed: int, tag: bytes, n: int, n_keys: int, tx_bytes: int,
                   bad: set, pool, pubs: list, rng) -> list:
    """n envelopes magic|priority|pubkey|sig|payload of tx_bytes bytes,
    the signature over all but itself; those in `bad` get one signature
    bit flipped."""
    heads = []
    for i in range(n):
        pub = pubs[i % n_keys]
        head = tag + b"-%07d=" % i
        payload = head + rng.bytes(tx_bytes - SIG_OFF - 64 - len(head))
        heads.append((MAGIC + b"\x00" + pub, payload))
    sigs = pool.sign_messages([(i % n_keys, h + p) for i, (h, p) in enumerate(heads)])
    txs = []
    for i, (h, p) in enumerate(heads):
        sig = sigs[i]
        if i in bad:
            sig = chainlib.flip_bit(sig, int(rng.integers(0, 512)))
        txs.append(h + sig + p)
    return txs


def fragment(i: int, tx: bytes) -> bytes:
    return (b'{"jsonrpc":"2.0","id":%d,"method":"broadcast_tx_async",'
            b'"params":{"tx":"%s"}}' % (i, base64.b64encode(tx)))


class Connection(threading.Thread):
    """One of the generator's connections; see the module docstring."""

    def __init__(self, addr: str, due: list, frags: list, clock, halt):
        super().__init__(name="bench-conn", daemon=True)
        self.rpc = Rpc(addr)
        self.due, self.frags, self.clock, self.halt = due, frags, clock, halt
        self.sent_at = [None] * len(due)
        self.refused = 0
        self.post_s = 0.0
        self.posted = 0
        self.error = None

    def run(self) -> None:
        try:
            i, n = 0, len(self.due)
            while i < n and not self.halt.is_set():
                now = self.clock()
                if self.due[i] > now:
                    time.sleep(min(0.05, self.due[i] - now))
                    continue
                j = i + 1
                while j < n and self.due[j] <= now and j - i < 512:
                    j += 1
                body = b"[" + b",".join(self.frags[i:j]) + b"]"
                t0 = self.clock()
                replies = self.rpc.post(body)
                t1 = self.clock()
                for k in range(i, j):
                    self.sent_at[k] = t0
                self.post_s += t1 - t0
                self.posted += j - i
                if len(replies) != j - i:
                    raise RuntimeError(f"{len(replies)} replies to {j - i} calls")
                self.refused += sum(1 for r in replies if r.get("error"))
                i = j
        except BaseException as e:  # noqa: BLE001 - reported by the driver
            self.error = e
        finally:
            self.rpc.close()


def percentile(sorted_values: list, q: float) -> float:
    """The smallest value with at least q of the sample at or below it."""
    k = max(0, math.ceil(q * len(sorted_values)) - 1)
    return sorted_values[k]


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    traffic, cfg = cell.traffic, cell.config
    rate, conns = traffic["rate_tx_per_s"], traffic["connections"]
    warm_s, n_keys = traffic["warmup_seconds"], cfg["sender_keys"]
    rng = np.random.default_rng(seed)
    clock = time.monotonic

    n = int(rate * (warm_s + seconds))
    # the same gaps for every seed, in a seeded order: quantiles of the
    # exponential distribution with mean 1/rate
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    offsets = np.cumsum(rng.permutation(gaps))
    bad = {int(i) for i in rng.choice(n, max(1, round(n * cfg["corrupted_share"])),
                                      replace=False)}
    tag = b"bench-%d" % seed
    seeds = [signer.seed_of(tag + b"-sender", i) for i in range(n_keys)]
    pubs = [signer.public_key(s) for s in seeds]
    t_sign = time.monotonic()
    pool = chainlib.SignerPool(seeds, ctx.workers)
    try:
        txs = make_envelopes(seed, tag, n, n_keys, cfg["tx_bytes"], bad, pool,
                             pubs, rng)
        warm_txs = make_envelopes(seed, tag + b"-warm",
                                  sum(traffic["warm_buckets"]), n_keys,
                                  cfg["tx_bytes"], set(), pool, pubs, rng)
    finally:
        pool.close()
    frags = [fragment(i, tx) for i, tx in enumerate(txs)]
    digest = {hashlib.sha256(tx).digest(): i for i, tx in enumerate(txs)}
    say(f"{n} envelopes of {len(txs[0])} bytes from {n_keys} keys, "
        f"{len(bad)} corrupted, signed in {time.monotonic() - t_sign:.1f}s")

    home = tempfile.mkdtemp(prefix="bench_home_")
    node = sub = None
    halt = threading.Event()
    threads: list = []
    try:
        node = nodelib.build_node(home, f"bench-kv-{seed}", cfg, genesis_json=None,
                                  trace=ctx.trace_on)
        ctx.install(node)
        node.start()
        surf = nodelib.Surfaces(node)
        verifier = surf.wait_verifier(1100)
        say("node verifier:", verifier)
        if str(verifier.get("warmup")).startswith("error"):
            raise RuntimeError(f"verify warm-up failed: {verifier}")
        _warm_envelope_shapes(warm_txs, verifier, traffic["warm_buckets"])

        arrived: dict = {}      # tx index -> (arrival, height)
        blocks: list = []       # (height, header time ns, [tx bytes])
        lock = threading.Lock()

        def on_block(result: dict, arrival: float) -> None:
            block = result["data"]["value"]["block"]
            height = int(block["header"]["height"])
            raw = [base64.b64decode(t) for t in block["data"]["txs"] or []]
            with lock:
                blocks.append((height, int(block["header"]["time"]), raw, arrival))
                for tx in raw:
                    i = digest.get(hashlib.sha256(tx).digest())
                    if i is not None and i not in arrived:
                        arrived[i] = (arrival, height)

        sub = Subscription(surf.rpc_addr, "tm.event='NewBlock'", on_block, clock)
        t0 = clock() + 0.2
        due = (t0 + offsets).tolist()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        for c in range(conns):
            idx = list(range(c, n, conns))
            th = Connection(surf.rpc_addr, [due[i] for i in idx],
                            [frags[i] for i in idx], clock, halt)
            th.idx = idx
            threads.append(th)
            th.start()

        while clock() < t_open:
            time.sleep(min(0.05, max(0.0, t_open - clock())))
        ctx.window_opens(t_open, surf)
        rpc = Rpc(surf.rpc_addr)
        backlog = {}
        for name, when in (("middle", t_open + seconds / 2), ("end", t_close)):
            ctx.wait_until(when)
            backlog[name] = int(rpc.call("num_unconfirmed_txs")["n_txs"])
        peak = ctx.window_closes()

        # --- drain: every valid tx due in the window, a bounded wait ---
        for th in threads:
            th.join(traffic["drain_seconds"])
        errors = [th.error for th in threads if th.error is not None]
        if errors or any(th.is_alive() for th in threads):
            raise RuntimeError(f"the generator failed: {errors}")
        in_window = [i for i in range(n)
                     if t_open <= due[i] < t_close and i not in bad]
        deadline = clock() + traffic["drain_seconds"]
        while clock() < deadline:
            with lock:
                if all(i in arrived for i in in_window):
                    break
            time.sleep(0.05)
        t_drained = clock()
        # only now: the stop loads the node for seconds, and until here
        # the window's last txs were still waiting for their block
        ctx.trace_stop()
        with lock:
            got = dict(arrived)
            seen_blocks = sorted(blocks)
        # a tx counts by what became of it, not by what its POST was told:
        # a POST sent again after a dropped connection is answered "already
        # in cache" for txs that the first sending got in
        lat, missing = [], 0
        for i in in_window:
            if i in got:
                lat.append(got[i][0] - due[i])
            else:
                missing += 1
                lat.append(t_drained - due[i])  # a miss: at least this long
        lat.sort()
        late = sorted(th.sent_at[k] - th.due[k] for th in threads
                      for k in range(len(th.due))
                      if t_open <= th.due[k] < t_close and th.sent_at[k] is not None)
        window_blocks = [b for b in seen_blocks if t_open <= b[3] < t_close]
        intervals = [(b[1] - a[1]) / 1e6 for a, b in
                     zip(window_blocks, window_blocks[1:])]
        say(f"window: {len(in_window)} valid txs due, {missing} missing, "
            f"{sum(th.refused for th in threads)} calls answered with an error, "
            f"backlog middle/end {backlog['middle']}/{backlog['end']}, "
            f"generator p99 late {1e3 * percentile(late, 0.99):.1f} ms, "
            f"commit latency p50 {1e3 * percentile(lat, 0.50):.1f} ms")

        numbers = _check_served(rpc, surf, txs, bad, got, seen_blocks, rng,
                                traffic["check_txs"])
        rpc.close()
        posted = sum(th.posted for th in threads)
        facts = {
            "gen_late_p99_s": percentile(late, 0.99) if late else None,
            "rpc_accept_s_per_tx": (sum(th.post_s for th in threads) / posted
                                    if posted else None),
            "block_interval_mean_ms": (sum(intervals) / len(intervals)
                                       if intervals else None),
            "backlog_middle": backlog["middle"], "backlog_end": backlog["end"],
            "samples": len(in_window),
            # per layer (`commit_tail_p99_ms`): in a window of some 18
            # blocks it is the window's one slowest block, not a tail
            "commit_latency_p99_ms": 1e3 * percentile(lat, 0.99),
        }
        return {
            "end_to_end": {
                "commit_latency_p50_ms": 1e3 * percentile(lat, 0.50)},
            "attempted": len(in_window), "failed": missing,
            "numbers": numbers, "facts": facts, "peak": peak,
        }
    finally:
        halt.set()
        for th in threads:
            th.join(10)
        if sub is not None:
            sub.close()
        if node is not None:
            node.stop()
            node.wait(60)
        shutil.rmtree(home, ignore_errors=True)


def _warm_envelope_shapes(warm_txs: list, verifier: dict, buckets: list) -> None:
    """The node warms vote-sized buckets only; an envelope batch is
    another shape key, and the first one of each bucket would compile (or
    load) inside the ingest worker, in the window. So each bucket the
    ingest drain can reach goes once through the call the ingest worker
    makes, with envelopes that are never sent (the verified-signature
    cache must not know the traffic). Every bucket, whatever cutoff this
    node calibrated: the cutoff moves from run to run (15 to 45 seen), and
    a run whose cutoff fell to 15 compiled bucket 16 inside its window and
    committed nothing (PERF.md, Findings)."""
    if verifier["backend"] == "cpu":
        return
    from tendermint_tpu.crypto import batch as crypto_batch

    triples = [(tx[:SIG_OFF] + tx[SIG_OFF + 64:], tx[SIG_OFF:SIG_OFF + 64],
                tx[len(MAGIC) + 1:SIG_OFF]) for tx in warm_txs]
    lo = 0
    for b in buckets:
        t = time.monotonic()
        # b fresh triples: cached ones would not reach the device
        chunk = triples[lo:lo + b] if lo + b <= len(triples) else None
        if chunk is None:
            raise RuntimeError("not enough warm-up envelopes for the buckets")
        lo += b
        # the device backend by name: below the cutoff `adaptive` would
        # send this batch to the host and warm nothing
        if not all(crypto_batch.batch_verify(chunk, backend="jax")):
            raise RuntimeError(f"warm-up batch of {b} envelopes did not verify")
        say(f"envelope bucket {b} ready in {time.monotonic() - t:.1f}s")


def _check_served(rpc, surf, txs, bad, got, seen_blocks, rng, n_check: int) -> dict:
    """What the node served, held to the configuration's guarantees; read
    once the drain is over, so that everything sent has been judged."""
    numbers = {}
    in_blocks = {hashlib.sha256(tx).digest() for b in seen_blocks for tx in b[2]}
    numbers["corrupted_in_a_block"] = (
        sum(1 for i in bad if hashlib.sha256(txs[i]).digest() in in_blocks), 0)
    sent_bad = len(bad)
    rejected = prom.total(prom.scrape(surf.metrics_addr),
                          "tendermint_mempool_preverify_rejected_total")
    numbers["corrupted_not_rejected"] = (abs(sent_bad - int(rejected)), 0)
    wrong_code = 0
    for i in sorted(bad)[:n_check]:
        try:
            res = rpc.call("broadcast_tx_sync",
                           {"tx": base64.b64encode(txs[i]).decode()})
        except RpcError:  # "already in cache": it was let in before
            res = {}
        if res.get("code") != CODE_BAD_SIGNATURE:
            wrong_code += 1
    numbers["corrupted_wrong_code"] = (wrong_code, 0)

    # the reference over everything committed so far, in block order
    ref = KVReference()
    for _, _, raw, _ in seen_blocks:
        for tx in raw:
            ref.deliver(tx)
    info = rpc.call("abci_info")["response"]
    numbers["app_hash_differs"] = (
        int(base64.b64decode(info["last_block_app_hash"]) != ref.commit()), 0)

    # a seeded sample of committed txs, the last committed with it: in
    # the block /block serves, and its key read back through abci_query
    # with the value the reference holds (a later write to the key wins)
    committed = sorted(got, key=lambda i: (got[i][1], i))
    wrong = 0
    if committed:
        pick = {committed[-1], *(committed[int(k)] for k in
                                 rng.integers(0, len(committed), n_check))}
        for i in sorted(pick):
            blk = rpc.call("block", {"height": got[i][1]})["block"]
            if base64.b64encode(txs[i]).decode() not in (blk["data"]["txs"] or []):
                wrong += 1
            key = txs[i].partition(b"=")[0]
            q = rpc.call("abci_query", {"path": "", "data": key.hex()})["response"]
            if base64.b64decode(q.get("value") or "") != ref.kv[key]:
                wrong += 1
    numbers["committed_read_back_wrong"] = (wrong, 0)
    return numbers
