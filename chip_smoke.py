#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, data from --seed, every stage checked against the serial
oracle (CPUBatchVerifier / SignedTx.verify: OpenSSL, one signature at a
time). No stage's exception is caught, so the exit code is 0 only if
every stage passed. Fails at once — before compiling anything — unless
jax.default_backend() == "tpu", and in a directory that does not hold
the program.

  1. a node that answers requests, with the chip doing the work:
     `tendermint-tpu init`, the node `cmd_node` builds
     (node.default_new_node: kvstore app, RPC, adaptive backend, filedb,
     [mempool] preverify_batch, Prometheus), warm-up joined and required
     ok, then over HTTP a few broadcast_tx_commit + abci_query read-backs
     and a burst of signed envelopes by broadcast_tx_async
  2. the committee-scale funnel through the calls the node makes
     (BASELINE.json configs 5, 3, 4 at their stated sizes); on more than
     one device also the shard_map + psum path, called directly
  3. compiled, not interpreted: fused Mosaic kernel == XLA path, bit for
     bit, on 512 mixed-validity items
  4. report: one line per kernel shape made ready (seconds, compiled or
     loaded from the store), kernel_cache stats, the cache directory

Last stdout line: {"ok": true, "device": {"platform", "kind", "count"}}.

    python chip_smoke.py                  # cold or warm, all stages
    python chip_smoke.py --expect-warm    # also require compiles == 0
    python chip_smoke.py --stages 2,3,4   # the four-chip host
"""

from __future__ import annotations

import argparse
import base64
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
from urllib.request import Request, urlopen

HERE = os.path.dirname(os.path.abspath(__file__))

# a WARNING or ERROR from these loggers is a fallback taken or a device
# failure survived: either fails the run ("node" only at ERROR — its
# warnings are about peers and disks, its errors include the warm-up)
_WATCHED = (("crypto", logging.WARNING), ("types.validator_set", logging.WARNING),
            ("mempool.preverify", logging.WARNING), ("node", logging.ERROR))


def say(*parts) -> None:
    print(*parts, flush=True)


class _Watch(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.records: list = []

    def emit(self, record: logging.LogRecord) -> None:
        for prefix, level in _WATCHED:
            if ((record.name == prefix or record.name.startswith(prefix + "."))
                    and record.levelno >= level):
                self.records.append(
                    f"{record.levelname} {record.name}: {record.getMessage()}")


# --- seeded data + the serial oracle -----------------------------------


def _keys(tag: bytes, n: int):
    from tendermint_tpu.crypto.keys import PrivKeyEd25519

    return [PrivKeyEd25519.gen_from_secret(tag + b"-%d" % i) for i in range(n)]


def _flip_bit(sig: bytes, bit: int) -> bytes:
    b = bytearray(sig)
    b[bit // 8] ^= 1 << (bit % 8)
    return bytes(b)


def _one_percent(rng, n: int) -> set:
    """Seeded choice of 1% of range(n), at least one."""
    return {int(i) for i in rng.choice(n, max(1, n // 100), replace=False)}


def oracle_mask(triples) -> list:
    """Serial per-signature verification, independent of every batching,
    caching and device layer under test."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier

    bv = CPUBatchVerifier()
    for t in triples:
        bv.add(*t)
    return bv._verify()


def _valset(tag: bytes, n: int):
    """(ValidatorSet, secret keys aligned to its address-sorted order)."""
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    sks = _keys(tag, n)
    vs = ValidatorSet([Validator.new(sk.pub_key(), 10) for sk in sks])
    by_addr = {sk.pub_key().address(): sk for sk in sks}
    return vs, [by_addr[v.address] for v in vs.validators]


def _votes(chain: str, vs, sks, height: int, type_: int, bid) -> list:
    from tendermint_tpu.types import Vote

    out = []
    for idx, sk in enumerate(sks):
        addr, _ = vs.get_by_index(idx)
        v = Vote(validator_address=addr, validator_index=idx, height=height,
                 round=0, timestamp=1_700_000_000_000_000_000 + idx,
                 type=type_, block_id=bid)
        v.signature = sk.sign(v.sign_bytes(chain))
        out.append(v)
    return out


def _corrupt(votes: list, bad: set, rng) -> list:
    out = list(votes)
    for i in bad:
        v = votes[i].copy()
        v.signature = _flip_bit(v.signature, int(rng.integers(0, 512)))
        out[i] = v
    return out


def _block_id(h: int):
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.basic import PartSetHeader

    return BlockID(bytes([h % 256]) * 20, PartSetHeader(1, b"\x0c" * 20))


def _metric(text: str, name: str, label: str = "") -> float:
    """Sum of the samples of `name` whose label set contains `label`."""
    total = 0.0
    for line in text.splitlines():
        head, _, value = line.rpartition(" ")
        if (head == name or head.startswith(name + "{")) and label in head:
            total += float(value)
    return total


def _expect_raises_at(fn, exc, index: int, what: str) -> None:
    """fn() must raise `exc` naming validator `index`."""
    try:
        fn()
    except exc as e:
        if f"validator {index} " not in str(e):
            raise RuntimeError(f"{what}: expected index {index}, got: {e}")
    else:
        raise RuntimeError(f"{what}: corrupted signatures verified")


# --- stage 1: a node that answers requests ------------------------------


def stage_node(home: str, seed: int, *, n_txs: int = 2048, n_keys: int = 256,
               payload_bytes: int = 250, n_commit_txs: int = 3,
               post_batch: int = 128, tweak=None,
               deadline_s: float = 900.0) -> dict:
    """init + default_new_node + RPC traffic; see the module docstring.
    The envelope burst goes out as JSON-RPC batch POSTs of `post_batch`
    broadcast_tx_async calls from 8 connections, the way a load
    generator sends: single calls arrive slower than the ingest worker
    verifies them one by one, so no backlog ever reaches the adaptive
    cutoff and the device is never asked. `tweak(config)` edits the
    loaded config before the node is built (the toy-size test shortens
    consensus timeouts with it)."""
    import numpy as np

    from tendermint_tpu.cmd import main as cli
    from tendermint_tpu.mempool import preverify
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.rpc import jsonrpc
    from tendermint_tpu.rpc.client import HTTPClient

    if cli.main(["--home", home, "init", "--chain-id", f"chip-smoke-{seed}"]):
        raise RuntimeError("tendermint-tpu init failed")
    c = cli._load_config(home)
    c.base.proxy_app = "kvstore"
    c.rpc.laddr = c.p2p.laddr = c.base.prof_laddr = "tcp://127.0.0.1:0"
    c.mempool.preverify_batch = True
    c.instrumentation.prometheus = True
    c.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    if tweak is not None:
        tweak(c)

    node = default_new_node(c)
    node.start()
    try:
        rpc = HTTPClient(node.rpc_listen_addr, timeout=60)

        def debug_crypto() -> dict:
            url = f"http://{node._prof_server.listen_addr}/debug/crypto"
            with urlopen(url, timeout=30) as r:
                return json.load(r)

        def metrics_text() -> str:
            url = f"http://{node._metrics_server.listen_addr}/metrics"
            with urlopen(url, timeout=30) as r:
                return r.read().decode()

        def height() -> int:
            return int(rpc.status()["sync_info"]["latest_block_height"])

        # the verifier the node resolved, read from the running node
        node._verify_warmup_thread.join(deadline_s)
        if node._verify_warmup_thread.is_alive():
            raise RuntimeError(f"verify warm-up still running after "
                               f"{deadline_s:.0f}s: {debug_crypto()}")
        crypto0 = debug_crypto()
        v = crypto0["verifier"]
        say("stage 1: node verifier:", json.dumps(v))
        on_device = v["backend"] != "cpu"
        if on_device and v["warmup"] != "ok":
            raise RuntimeError(f"verify warm-up did not succeed: {v['warmup']}")
        cutoff = v["batch_cutoff"]
        if on_device:
            say(f"stage 1: calibrated adaptive cutoff = {cutoff} signatures")
            if cutoff > node.mempool._ingest.batch_max:
                # the cutoff is the node's own measurement and stands;
                # the ingest drain is widened so a full drain can reach it
                raised = 1 << cutoff.bit_length()
                say(f"stage 1: cutoff {cutoff} exceeds [mempool] "
                    f"preverify_batch_max {node.mempool._ingest.batch_max}: "
                    f"raising preverify_batch_max to {raised}")
                c.mempool.preverify_batch_max = raised
                node.mempool._ingest.batch_max = raised

        # a few writes and read-backs through consensus
        h0 = height()
        for i in range(n_commit_txs):
            key, val = b"smoke-%d-%d" % (seed, i), b"value-%d" % i
            res = rpc.broadcast_tx_commit(key + b"=" + val)
            if (res["check_tx"].get("code", 0) or res["deliver_tx"].get("code", 0)
                    or int(res["height"]) <= 0):
                raise RuntimeError(f"broadcast_tx_commit failed: {res}")
            got = rpc.abci_query("", key)["response"]
            if base64.b64decode(got["value"]) != val:
                raise RuntimeError(f"abci_query read back {got} for {key!r}")

        # the envelope burst: the only node traffic that reaches the kernel
        rng = np.random.default_rng(seed)
        sks = _keys(b"chip-smoke-%d-sender" % seed, n_keys)
        bad = _one_percent(rng, n_txs)
        sig_off = len(preverify.MAGIC) + 1 + 32  # magic | priority | pubkey | sig
        txs = []
        for i in range(n_txs):
            head = b"env-%d-%06d=" % (seed, i)
            tx = preverify.make_signed_tx(
                sks[i % n_keys], head + rng.bytes(payload_bytes - len(head)))
            if i in bad:
                tx = (tx[:sig_off]
                      + _flip_bit(tx[sig_off:sig_off + 64], int(rng.integers(0, 512)))
                      + tx[sig_off + 64:])
            if preverify.parse(tx).verify() != (i not in bad):  # the oracle
                raise RuntimeError(f"seeded envelope {i}: oracle disagrees")
            txs.append(tx)
        say(f"stage 1: {n_txs} signed envelopes of {len(txs[0])} bytes from "
            f"{n_keys} keys, {len(bad)} corrupted; signed message "
            f"{len(preverify.parse(txs[0]).msg)} bytes")

        h_burst = height()
        t_burst = time.monotonic()
        errors: list = []

        def send(chunk):
            try:
                for lo in range(0, len(chunk), post_batch):
                    body = jsonrpc.dumps([
                        jsonrpc.request(lo + k, "broadcast_tx_async",
                                        {"tx": base64.b64encode(tx).decode()})
                        for k, tx in enumerate(chunk[lo:lo + post_batch])])
                    req = Request(f"http://{node.rpc_listen_addr}", data=body,
                                  headers={"Content-Type": "application/json"})
                    with urlopen(req, timeout=60) as r:
                        replies = jsonrpc.loads(r.read())
                    refused = [x for x in replies if x.get("error")]
                    if refused or len(replies) != len(chunk[lo:lo + post_batch]):
                        raise RuntimeError(f"broadcast_tx_async: {refused[:3]}")
            except BaseException as e:  # noqa: BLE001 - re-raised by the caller
                errors.append(e)

        senders = [threading.Thread(target=send, args=(txs[j::8],))
                   for j in range(8)]
        for t in senders:
            t.start()
        for t in senders:
            t.join(deadline_s)
        if errors or any(t.is_alive() for t in senders):
            raise RuntimeError(f"broadcast_tx_async burst failed: {errors}")

        want = {tx for i, tx in enumerate(txs) if i not in bad}
        seen: set = set()
        next_h, t_first = h_burst + 1, None
        deadline = time.monotonic() + deadline_s
        while not want <= seen:
            latest = height()
            while next_h <= latest:
                for b64 in rpc.block(next_h)["block"]["data"]["txs"] or []:
                    seen.add(base64.b64decode(b64))
                next_h += 1
            if t_first is None and seen & want:
                t_first = time.monotonic() - t_burst
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{len(want - seen)} of {len(want)} valid envelopes not "
                    f"committed after {deadline_s:.0f}s (height {latest})")
            time.sleep(0.2)
        if {txs[i] for i in bad} & seen:
            raise RuntimeError("a corrupted envelope was committed in a block")

        # every corrupted one bounced, with the signature code
        deadline = time.monotonic() + 30
        while (_metric(metrics_text(), "tendermint_mempool_preverify_rejected_total")
               < len(bad)):
            if time.monotonic() > deadline:
                raise RuntimeError("not every corrupted envelope was rejected")
            time.sleep(0.2)
        m = metrics_text()
        rejected = _metric(m, "tendermint_mempool_preverify_rejected_total")
        if rejected != len(bad):
            raise RuntimeError(f"{rejected} rejections for {len(bad)} corrupted")
        for i in sorted(bad):
            code = rpc.broadcast_tx_sync(txs[i])["code"]
            if code != preverify.CODE_BAD_SIGNATURE:
                raise RuntimeError(f"corrupted envelope {i} answered code {code}")
        if height() <= h0:
            raise RuntimeError("height did not advance")

        routed = _metric(m, "tendermint_crypto_batch_routing_total", 'route="device"')
        jax_batches = _metric(m, "tendermint_crypto_batch_verify_seconds_count",
                              'backend="jax"')
        crypto1 = debug_crypto()
        live = crypto1["kernels"][len(crypto0["kernels"]):]
        if on_device:
            if routed <= 0 or jax_batches <= 0:
                raise RuntimeError(
                    f"no envelope batch reached the device: routing_decisions"
                    f"{{device}}={routed}, batch_verify_seconds"
                    f"{{backend=jax}} samples={jax_batches}")
            # the stall ISSUE 21 §5 asks to report, not to tune: envelope
            # shapes are not in the warm-up set, so they were made ready
            # inside the ingest worker while the burst waited
            say(f"stage 1: {len(live)} envelope kernel shape(s) made ready "
                f"inside the ingest worker, "
                f"{sum(k['seconds'] for k in live):.1f}s in total: "
                f"{[(k['static_key'][:3], k['seconds'], k['source']) for k in live]}")
        say(f"stage 1: {len(want)} valid envelopes committed by height "
            f"{next_h - 1} (first after {t_first:.1f}s), {len(bad)} bounced "
            f"with code 0x{preverify.CODE_BAD_SIGNATURE:02x}; device batches="
            f"{int(routed)}, jax batch samples={int(jax_batches)}")
    finally:
        node.stop()
    if not node._stopped.is_set():
        raise RuntimeError("node.stop() returned without completing")
    say("stage 1: node stopped cleanly")
    return {"verifier": v, "committed": len(want), "bounced": len(bad),
            "device_batches": int(routed)}


# --- stage 2: the committee-scale funnel --------------------------------


def stage_funnel(seed: int, *, n_mega: int = 10000, n_round: int = 150,
                 n_sync_vals: int = 500, n_sync_blocks: int = 20) -> dict:
    """BASELINE.json configs 5, 3, 4 through ValidatorSet.verify_commit,
    crypto.batch.batch_verify, VoteSet.add_votes and begin_verify_commit."""
    import numpy as np

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.types import (VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE)
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.validator_set import ErrInvalidCommitSignatures
    from tendermint_tpu.types.vote_set import ErrVoteInvalid, VoteSet

    rng = np.random.default_rng(seed + 2)
    chain = f"chip-smoke-{seed}"
    backend = crypto_batch.default_backend_name()
    cutoff = crypto_batch.effective_batch_min() if backend == "adaptive" else 1

    def route(n: int) -> str:
        if backend == "cpu":
            return "host (cpu backend)"
        return "device" if n >= cutoff else f"host (below the cutoff {cutoff})"

    def triples(vs, votes):
        return [(v.sign_bytes(chain), v.signature,
                 vs.validators[v.validator_index].pub_key.bytes())
                for v in votes]

    # config 5: the 10k-validator commit
    vs, sks = _valset(b"chip-smoke-%d-mega" % seed, n_mega)
    bid = _block_id(5)
    good = _votes(chain, vs, sks, 5, VOTE_TYPE_PRECOMMIT, bid)
    bad = _one_percent(rng, n_mega)
    mixed = _corrupt(good, bad, rng)
    mixed_triples = triples(vs, mixed)
    want = oracle_mask(mixed_triples)
    if [i for i, ok in enumerate(want) if not ok] != sorted(bad):
        raise RuntimeError("mega: the oracle disagrees with the seeded corruption")
    vs.verify_commit(chain, bid, 5, Commit(bid, good))  # raises if not valid
    _expect_raises_at(
        lambda: vs.verify_commit(chain, bid, 5, Commit(bid, mixed)),
        ErrInvalidCommitSignatures, min(bad), "mega")
    got = crypto_batch.batch_verify(mixed_triples)
    if got != want:
        diff = [i for i in range(n_mega) if got[i] != want[i]]
        raise RuntimeError(f"mega: batch_verify mask != oracle at {diff[:10]}")
    say(f"stage 2: {n_mega}-validator commit: verify_commit ok / raised at "
        f"index {min(bad)}; batch_verify mask == oracle on all {n_mega} "
        f"({len(bad)} invalid) -> {route(n_mega)}")

    import jax

    multi = {}
    if backend != "cpu" and len(jax.devices()) > 1:
        multi = _check_sharded(mixed_triples, want)

    # config 3: a 150-validator prevote + precommit round
    vs3, sks3 = _valset(b"chip-smoke-%d-round" % seed, n_round)
    bid3 = _block_id(3)
    prevotes = _votes(chain, vs3, sks3, 3, VOTE_TYPE_PREVOTE, bid3)
    pv_set = VoteSet(chain, 3, 0, VOTE_TYPE_PREVOTE, vs3)
    if pv_set.add_votes(prevotes) != oracle_mask(triples(vs3, prevotes)):
        raise RuntimeError("round: prevote acceptance != oracle")
    precommits = _corrupt(_votes(chain, vs3, sks3, 3, VOTE_TYPE_PRECOMMIT, bid3),
                          {n_round // 2}, rng)
    pc_set = VoteSet(chain, 3, 0, VOTE_TYPE_PRECOMMIT, vs3)
    try:
        pc_set.add_votes(precommits)
    except ErrVoteInvalid:
        pass  # raised AFTER every valid vote of the batch was applied
    else:
        raise RuntimeError("round: a corrupted precommit was accepted")
    accepted = [pc_set.get_by_index(i) is not None for i in range(n_round)]
    if accepted != oracle_mask(triples(vs3, precommits)):
        raise RuntimeError("round: precommit acceptance != oracle")
    if not (pv_set.has_two_thirds_majority() and pc_set.has_two_thirds_majority()):
        raise RuntimeError("round: +2/3 not reached")
    say(f"stage 2: {n_round}-validator prevote+precommit round: +2/3 reached, "
        f"per-vote acceptance == oracle -> routing decision: {route(n_round)}")

    # config 4: fast-sync, 20 blocks of 500-validator commits
    vs4, sks4 = _valset(b"chip-smoke-%d-sync" % seed, n_sync_vals)
    commits = []
    for h in range(1, n_sync_blocks + 1):
        votes = _votes(chain, vs4, sks4, h, VOTE_TYPE_PRECOMMIT, _block_id(h))
        if not all(oracle_mask(triples(vs4, votes))):
            raise RuntimeError(f"sync: the oracle rejects seeded block {h}")
        commits.append((h, _block_id(h), votes))
    for h, b, votes in commits:  # the fast-sync loop
        vs4.verify_commit(chain, b, h, Commit(b, votes))
    pend = None  # its pipelined form: verify(k+1) in flight while k "applies"
    for h, b, votes in commits:
        nxt = vs4.begin_verify_commit(chain, b, h, Commit(b, votes))
        if pend is not None:
            pend.result()
        pend = nxt
    pend.result()
    h, b, votes = commits[-1]
    bad4 = n_sync_vals // 3
    _expect_raises_at(
        vs4.begin_verify_commit(
            chain, b, h, Commit(b, _corrupt(votes, {bad4}, rng))).result,
        ErrInvalidCommitSignatures, bad4, "sync")
    crypto_batch.shutdown_dispatchers()
    say(f"stage 2: {n_sync_blocks} x {n_sync_vals}-validator commits through "
        f"verify_commit and begin_verify_commit: all ok, corrupted one raised "
        f"at index {bad4} -> {route(n_sync_vals)}")
    return {"cutoff": cutoff, "multi_device": multi}


def _pack(triples, ndev: int):
    """(buf, nb, mrows, bpad): the packed h2d buffer verify_batch builds."""
    import numpy as np

    from tendermint_tpu.crypto.jaxed25519 import verify as jv

    msgs, sigs, pks = zip(*triples)
    n = len(msgs)
    sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
    pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32)
    return jv.pack_buffer(list(msgs), sig_arr, pk_arr, ndev)


def _check_sharded(triples, want) -> dict:
    """More than one device: the shard_map + psum commit path, called
    directly (verify_commit skips it whenever a sig cache is installed,
    which a node always has), and where the packed kernel's result lives."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto.jaxed25519 import verify as jv

    ndev = len(jax.devices())
    msgs, sigs, pks = (list(c) for c in zip(*triples))
    powers = [1 + i % 7 for i in range(len(triples))]  # uneven, so the tally bites
    mask, tally = jv.sharded_commit_verify(msgs, sigs, pks, powers,
                                           [1] * len(triples))
    if mask != want:
        raise RuntimeError("sharded_commit_verify mask != oracle")
    host_tally = sum(p for p, ok in zip(powers, want) if ok)
    if tally != host_tally:
        raise RuntimeError(f"psum tally {tally} != host tally {host_tally}")

    n = len(msgs)
    buf, nb, mrows, bpad = _pack(triples, ndev)
    # the program verify_batch dispatches (already compiled above)
    fn = jv._jitted_packed(nb, mrows, bpad, ndev)
    out = fn(jv._put(buf, ndev))
    out.block_until_ready()
    spans = len(out.sharding.device_set)
    if spans != ndev:
        raise RuntimeError(f"result sharding spans {spans} of {ndev} devices")
    if np.asarray(out)[:n].tolist() != want:
        raise RuntimeError("packed shard_map mask != oracle")
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    if not all(b > 0 for b in in_use):
        raise RuntimeError(f"a device reports no memory in use: {in_use}")
    say(f"stage 2: {ndev} devices: sharded_commit_verify mask == oracle, psum "
        f"tally {tally} == host tally; packed result sharded over {spans} "
        f"devices; bytes in use per device {in_use}")
    return {"devices": ndev, "psum_tally": tally, "bytes_in_use": in_use}


# --- stage 3: compiled, not interpreted ---------------------------------


def stage_compiled(seed: int, *, n: int = 512) -> dict:
    """The fused Mosaic kernel and the XLA path give bit-identical masks
    on real hardware — the tier that catches what interpret-mode CPU
    tests cannot (the MXU's default f32 path rounds inputs to bf16 and
    corrupts 13-bit limbs; the kernel relies on Precision.HIGHEST)."""
    import numpy as np

    from tendermint_tpu.crypto.jaxed25519 import verify as jv

    flags = jv._pallas_flags()
    if flags != (True, False):
        raise RuntimeError(f"_pallas_flags() = {flags}: the fused kernel is "
                           "off or interpreted on this backend")
    rng = np.random.default_rng(seed + 3)
    sks = _keys(b"chip-smoke-%d-tail" % seed, 64)
    triples = []
    for i in range(n):
        sk = sks[i % len(sks)]
        msg = rng.bytes(int(rng.integers(1, 201)))
        sig = sk.sign(msg)
        if i % 17 == 3:  # both mask polarities occur
            sig = _flip_bit(sig, int(rng.integers(0, 512)))
        triples.append((msg, sig, sk.pub_key().bytes()))
    want = np.array(oracle_mask(triples))
    buf, nb, mrows, bpad = _pack(triples, 1)
    fused = np.asarray(jv._jitted_packed(nb, mrows, bpad, 1, force_pallas=True)(
        jv._put(buf, 1)))
    xla = np.asarray(jv._jitted_packed(nb, mrows, bpad, 1, force_pallas=False)(
        jv._put(buf, 1)))
    if fused.dtype != xla.dtype or fused.shape != xla.shape:
        raise RuntimeError("fused/XLA masks differ in dtype or shape")
    if not (fused == xla).all():
        raise RuntimeError(f"fused/XLA mask divergence at "
                           f"{np.nonzero(fused != xla)[0][:10]}")
    if not (xla[:n] == want).all():
        raise RuntimeError("XLA path mask != oracle")
    say(f"stage 3: fused kernel compiled (not interpreted); fused == XLA == "
        f"oracle on {n} items, {int(want.sum())} valid, 1-200 byte messages")
    return {"items": n, "valid": int(want.sum())}


# --- stage 4: report ------------------------------------------------------


def stage_report(watch: _Watch, expect_warm: bool) -> dict:
    from tendermint_tpu.crypto import kernel_cache

    st = kernel_cache.status()
    say(f"stage 4: compile cache directory: {st['dir']} "
        f"({kernel_cache.ENV_CACHE_DIR}="
        f"{os.environ.get(kernel_cache.ENV_CACHE_DIR)!r})")
    for k in st["kernels"]:
        say(f"stage 4: kernel {k['kernel']} {k['static_key']} "
            f"arg0{k['arg0_shape']}: {k['seconds']:.1f}s, "
            f"{'loaded from the cache' if k['source'] == 'aot-store' else 'compiled'}")
    stats = kernel_cache.stats()
    say("stage 4: kernel_cache.stats():", json.dumps(stats))
    if stats["load_errors"]:
        raise RuntimeError(f"{stats['load_errors']} AOT artifacts failed to load")
    if expect_warm and (stats["compiles"] != 0 or stats["hits"] < 1):
        raise RuntimeError(f"--expect-warm: the run compiled: {stats}")
    if watch.records:
        raise RuntimeError("WARNING/ERROR records from watched loggers:\n  "
                           + "\n  ".join(watch.records))
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--stages", default="1,2,3,4",
                    help="comma-separated subset of 1,2,3,4")
    ap.add_argument("--expect-warm", action="store_true",
                    help="fail unless nothing compiled (second run against "
                         "one cache directory)")
    args = ap.parse_args(argv)
    stages = {int(s) for s in args.stages.split(",")}

    if not os.path.isdir(os.path.join(HERE, "tendermint_tpu")):
        print("chip_smoke.py: the program is not beside this script "
              f"(no {HERE}/tendermint_tpu); run it from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)  # this checkout, not an installed copy

    import jax

    backend = jax.default_backend()  # initialises the backend, compiles nothing
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if backend != "tpu":
        print(f"chip_smoke.py: no accelerator: jax came up on platform="
              f"{device['platform']} device_kind={device['kind']!r} "
              f"count={device['count']} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 3
    say(f"chip_smoke: platform={device['platform']} device_kind="
        f"{device['kind']!r} count={device['count']} jax={jax.__version__} "
        f"seed={args.seed} stages={sorted(stages)}")

    # stderr: warnings from everywhere, plus what the node and the crypto
    # layer say at INFO (which verifier, each kernel shape made ready)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    for name in ("node", "crypto"):
        logging.getLogger(name).setLevel(logging.INFO)
    watch = _Watch()
    logging.getLogger().addHandler(watch)

    t0 = time.monotonic()
    if 1 in stages:
        home = tempfile.mkdtemp(prefix="chip_smoke_home_")
        try:
            stage_node(home, args.seed)
        finally:
            shutil.rmtree(home, ignore_errors=True)
        say(f"stage 1 passed at {time.monotonic() - t0:.0f}s")
    if 2 in stages:
        stage_funnel(args.seed)
        say(f"stage 2 passed at {time.monotonic() - t0:.0f}s")
    if 3 in stages:
        stage_compiled(args.seed)
        say(f"stage 3 passed at {time.monotonic() - t0:.0f}s")
    stage_report(watch, args.expect_warm)
    say(f"chip_smoke: all of stages {sorted(stages)} passed in "
        f"{time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
