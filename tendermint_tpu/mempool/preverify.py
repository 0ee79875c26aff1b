"""Signed-tx envelope + batched CheckTx pre-verification ingest queue.

Txs are opaque bytes to consensus, but the mempool can shed app load by
refusing bad signatures before the per-tx ABCI round trip. Txs that opt
in carry a self-describing envelope:

    b"sgtx1" | priority(1) | pubkey(32) | sig(64) | payload

where sig is Ed25519 over everything except itself (magic + priority +
pubkey + payload), so neither the priority nor the payload can be
tampered without invalidating the tx. The priority byte also feeds the
mempool's lane assignment and reap ordering. Txs without the magic are
admitted exactly as before (no signature check, priority 0).

The v2 envelope adds an optional ACCESS-HINT segment between the
priority byte and the pubkey — the declared key footprint the parallel
block executor (state/parallel.py) partitions txs by:

    b"sgtx2" | priority(1) | nhints(1) | {hlen(1) | hint}*n
             | pubkey(32) | sig(64) | payload

Hints are app-level state keys (<= 255 bytes each, <= 255 of them) and
are covered by the signature like everything else, so a relay cannot
re-group a tx by rewriting its declared footprint. A v1 (or plain) tx
simply has no hints, which the executor treats as "conflicts with
everything" — conservatively correct, never wrong.

The IngestQueue is the batching layer in front of Mempool admission:
callers submit() and get a future; a single worker drains up to
batch_max waiting txs, pre-verifies every enveloped signature in ONE
crypto/batch call — riding the PR-2 verified-signature cache and async
dispatch threads, so the Ed25519 cost is paid once per batch instead of
once per tx — and only then runs the per-tx ABCI CheckTx for the
survivors. Invalid-sig txs are rejected without the app ever seeing
them.
"""

from __future__ import annotations

import concurrent.futures as _futures
import logging
import queue as _queue
import threading
import time
from dataclasses import dataclass
from typing import List, Optional

from ..abci import types as abci
from ..libs import tracing

LOG = logging.getLogger("mempool.preverify")

MAGIC = b"sgtx1"
MAGIC2 = b"sgtx2"  # v2: carries the optional access-hint segment
_PRIO_OFF = len(MAGIC)  # 5
_PK_OFF = _PRIO_OFF + 1  # 6
_SIG_OFF = _PK_OFF + 32  # 38
_PAYLOAD_OFF = _SIG_OFF + 64  # 102

# ABCI result code for an envelope whose signature fails verification —
# rejected by the NODE, before (and instead of) the app's CheckTx
CODE_BAD_SIGNATURE = 0x53  # 'S'


@dataclass(frozen=True)
class SignedTx:
    """Parsed view of one enveloped tx."""

    priority: int
    pubkey: bytes
    sig: bytes
    payload: bytes
    msg: bytes  # the signed bytes: everything except sig
    # declared access hints (v2 envelopes only; () = undeclared). The
    # distinction between "declared empty" and "undeclared" doesn't
    # arise: a v2 tx with zero hints is treated as unhinted too, since
    # an empty footprint claims the tx touches nothing — not credible.
    hints: tuple = ()

    def verify(self) -> bool:
        """Serial single-tx verification (the non-batched path)."""
        from ..crypto.keys import PubKeyEd25519

        try:
            return PubKeyEd25519(self.pubkey).verify_bytes(self.msg, self.sig)
        except ValueError:
            return False


def parse(tx: bytes) -> Optional[SignedTx]:
    """The envelope view of tx (either version), or None for a plain
    (unsigned) tx — including anything malformed, which stays opaque
    app bytes exactly like pre-envelope behavior."""
    if tx.startswith(MAGIC2):
        return _parse_v2(tx)
    if len(tx) < _PAYLOAD_OFF or not tx.startswith(MAGIC):
        return None
    return SignedTx(
        priority=tx[_PRIO_OFF],
        pubkey=tx[_PK_OFF:_SIG_OFF],
        sig=tx[_SIG_OFF:_PAYLOAD_OFF],
        payload=tx[_PAYLOAD_OFF:],
        msg=tx[:_SIG_OFF] + tx[_PAYLOAD_OFF:],
    )


def _parse_v2(tx: bytes) -> Optional[SignedTx]:
    # magic(5) | priority(1) | nhints(1) | {hlen(1)|hint}*n
    #         | pubkey(32) | sig(64) | payload
    if len(tx) < _PK_OFF + 1:  # through nhints
        return None
    off = _PK_OFF  # nhints byte position
    n = tx[off]
    off += 1
    hints = []
    for _ in range(n):
        if off >= len(tx):
            return None
        hlen = tx[off]
        off += 1
        if off + hlen > len(tx):
            return None
        hints.append(tx[off:off + hlen])
        off += hlen
    pk_off, sig_off, payload_off = off, off + 32, off + 32 + 64
    if len(tx) < payload_off:
        return None
    return SignedTx(
        priority=tx[_PRIO_OFF],
        pubkey=tx[pk_off:sig_off],
        sig=tx[sig_off:payload_off],
        payload=tx[payload_off:],
        msg=tx[:sig_off] + tx[payload_off:],
        hints=tuple(hints),
    )


def make_signed_tx(priv_key, payload: bytes, priority: int = 0,
                   hints=None) -> bytes:
    """Build one enveloped tx (load harness / client-side helper).
    `hints` (an iterable of state-key bytes) selects the v2 envelope
    carrying a declared access footprint for the parallel executor."""
    if not 0 <= priority <= 255:
        raise ValueError("priority must fit one byte")
    pk = priv_key.pub_key().bytes()
    if hints is None:
        head = MAGIC + bytes([priority]) + pk
    else:
        hints = [bytes(h) for h in hints]
        if len(hints) > 255:
            raise ValueError("at most 255 access hints per tx")
        seg = bytes([len(hints)])
        for h in hints:
            if not 1 <= len(h) <= 255:
                raise ValueError("each access hint must be 1..255 bytes")
            seg += bytes([len(h)]) + h
        head = MAGIC2 + bytes([priority]) + seg + pk
    sig = priv_key.sign(head + payload)
    return head + sig + payload


def reject_response() -> abci.ResponseCheckTx:
    return abci.ResponseCheckTx(
        code=CODE_BAD_SIGNATURE, log="invalid tx signature")


class TxFuture(_futures.Future):
    """concurrent.futures.Future resolving to the ResponseCheckTx
    (including signature rejections) or re-raising the admission error
    (ErrTxInCache, ErrMempoolIsFull, transport); stamps submit time for
    the queue-wait histogram."""

    def __init__(self):
        super().__init__()
        self.submitted_at = time.perf_counter()
        # the submitting thread's open span (an rpc.call): the
        # ingest.drain that takes this tx names it as its cause
        self.cause = tracing.cause()


class IngestQueue:
    """Single-worker batching front end to Mempool admission.

    submit() enqueues and returns a TxFuture; the worker drains up to
    batch_max queued txs per round, batch-verifies the enveloped
    signatures through crypto/batch (sig cache + async dispatch), then
    admits survivors one at a time via mempool._admit_preverified. A
    full queue rejects at submit() (ErrMempoolIsFull) so backpressure
    reaches RPC clients instead of growing unbounded.
    """

    # queue-full warnings are rate limited: under saturation every
    # submit would otherwise log (callers often discard the future, so
    # this is the ONLY operator-visible trace besides /debug/mempool)
    _FULL_WARN_INTERVAL_S = 10.0

    def __init__(self, mempool, batch_max: int, queue_size: int):
        self.mempool = mempool
        self.batch_max = max(1, int(batch_max))
        self._q: "_queue.Queue" = _queue.Queue(maxsize=max(1, int(queue_size)))
        self._stop_lock = threading.Lock()
        self._stopping = False
        self._last_full_warn = 0.0
        self._thread = threading.Thread(
            target=self._run, name="mempool-ingest", daemon=True)
        self._thread.start()

    def qsize(self) -> int:
        return self._q.qsize()

    @property
    def capacity(self) -> int:
        return self._q.maxsize

    def submit(self, tx: bytes) -> TxFuture:
        from .mempool import ErrMempoolIsFull

        fut = TxFuture()
        with self._stop_lock:
            if self._stopping:
                fut.set_exception(
                    ErrMempoolIsFull("mempool ingest queue is shut down"))
                return fut
            try:
                self._q.put_nowait((tx, fut))
            except _queue.Full:
                now = time.monotonic()
                if now - self._last_full_warn >= self._FULL_WARN_INTERVAL_S:
                    self._last_full_warn = now
                    LOG.warning(
                        "mempool ingest queue full (%d txs): dropping "
                        "submissions (further warnings suppressed for "
                        "%.0fs)", self._q.maxsize, self._FULL_WARN_INTERVAL_S)
                fut.set_exception(ErrMempoolIsFull(
                    f"mempool ingest queue is full ({self._q.maxsize} txs)"))
        return fut

    def stop(self, timeout: float = 10.0) -> None:
        """Drain already-queued txs (their futures always resolve), then
        join the worker. Never blocks holding _stop_lock: the sentinel
        is offered with put_nowait retries, so a wedged worker behind a
        full queue stalls only this call's bounded wait — submit()
        keeps failing fast with "shut down" instead of freezing on the
        lock."""
        with self._stop_lock:
            already, self._stopping = self._stopping, True
        if not already:
            deadline = time.monotonic() + timeout
            while True:
                try:
                    self._q.put_nowait(None)
                    break
                except _queue.Full:
                    if time.monotonic() >= deadline:
                        break  # wedged worker: join below times out too
                    time.sleep(0.01)
        self._thread.join(timeout)

    # --- worker -------------------------------------------------------

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            batch = [item]
            while len(batch) < self.batch_max:
                try:
                    nxt = self._q.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:  # sentinel: finish this batch, then exit
                    self._q.put(None)
                    break
                batch.append(nxt)
            try:
                self._process(batch)
            except BaseException as e:  # noqa: BLE001 - worker must survive
                # belt-and-braces: _process resolves futures itself; an
                # error escaping it must not strand waiters (check_tx
                # blocks on result()) or kill the worker
                LOG.exception("ingest batch failed")
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)

    def _process(self, batch: List[tuple]) -> None:
        # one span per drain, never per tx; its cause is the oldest
        # tx's rpc.call (`causes` counts the distinct ones it took from)
        tracer = tracing.get_tracer()
        with tracer.span("ingest.drain", cat="mempool",
                         cause=batch[0][1].cause,
                         request=tracer.request("drain"),
                         n=len(batch)) as sp:
            rejected = self._drain(batch)
            if tracer.enabled:
                now = time.perf_counter()
                sp.set(rejected=rejected,
                       causes=len({fut.cause for _, fut in batch}),
                       wait_max_ms=round(max(
                           now - fut.submitted_at for _, fut in batch) * 1e3, 3))

    def _drain(self, batch: List[tuple]) -> int:
        """Pre-verify and admit one drained batch; returns how many
        enveloped txs were rejected for a bad signature."""
        from ..crypto import batch as crypto_batch

        metrics = self.mempool.metrics
        now = time.perf_counter()
        for _, fut in batch:
            metrics.ingest_queue_wait.observe(max(0.0, now - fut.submitted_at))
        metrics.checktx_batch_size.observe(len(batch))

        parsed = [self.mempool.parse_envelope(tx) for tx, _ in batch]
        signed_idx = [i for i, p in enumerate(parsed) if p is not None]
        mask: List[bool] = []
        if signed_idx:
            # cache hits inside the batch are counted by the crypto
            # layer (crypto_sig_cache_hits_total in BatchVerifier's
            # cache pass) — peeking here would hash every triple twice
            bv = crypto_batch.new_batch_verifier()
            for i in signed_idx:
                p = parsed[i]
                bv.add(p.msg, p.sig, p.pubkey)
            try:
                # one batch on the backend's dispatch thread: exceptions
                # surface here, and the sig cache absorbs duplicates
                mask = bv.verify_async().result()
            except Exception as e:  # noqa: BLE001 - backend failure
                LOG.warning("batch pre-verification failed, falling back "
                            "to serial verify: %s", e)
                mask = [parsed[i].verify() for i in signed_idx]
        verdict = dict(zip(signed_idx, mask))

        # admission for the signature-valid subset is ONE batched call:
        # one mempool-lock hold and one (pipelined) app CheckTx batch
        # per drain, instead of a lock + app round trip per tx
        admit_slots = []
        admit_items = []
        rejected = 0
        for i, (tx, fut) in enumerate(batch):
            p = parsed[i]
            if p is not None and not verdict.get(i, False):
                metrics.preverify_rejected.inc()
                fut.set_result(reject_response())
                rejected += 1
                continue
            admit_slots.append(i)
            admit_items.append((tx, p))
        if not admit_items:
            return rejected
        with tracing.span("ingest.checkTx", cat="mempool",
                          n=len(admit_items)):
            results = self.mempool._admit_preverified_batch(admit_items)
        for i, res in zip(admit_slots, results):
            fut = batch[i][1]
            if isinstance(res, BaseException):
                fut.set_exception(res)
            else:
                fut.set_result(res)
        return rejected
