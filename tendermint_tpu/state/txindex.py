"""Tx indexing (reference state/txindex/).

KVTxIndexer stores TxResult by hash and tag for `tx_search`; the
IndexerService subscribes to the event bus and indexes every committed
tx (reference state/txindex/indexer_service.go:17-69, kv/kv.go:28,144).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import List, Optional

from ..abci import types as abci
from ..libs import fail
from ..libs.db import DB
from ..libs.events import Query
from ..libs.service import BaseService
from ..types import serde
from ..types.block import tx_hash
from ..types.event_bus import (
    EVENT_TX,
    TX_HASH_KEY,
    TX_HEIGHT_KEY,
    EventBus,
    query_for_event,
)


@dataclass
class TxResult:
    height: int
    index: int
    tx: bytes
    result: abci.ResponseDeliverTx

    def to_bytes(self) -> bytes:
        r = self.result
        return serde.pack([
            self.height, self.index, self.tx,
            [r.code, r.data, r.log, r.gas_wanted, r.gas_used,
             [[kv.key, kv.value] for kv in r.tags]],
        ])

    @classmethod
    def from_bytes(cls, raw: bytes) -> "TxResult":
        o = serde.unpack(raw)
        return cls(
            height=o[0], index=o[1], tx=o[2],
            result=abci.ResponseDeliverTx(
                code=o[3][0], data=o[3][1], log=o[3][2],
                gas_wanted=o[3][3], gas_used=o[3][4],
                tags=[abci.KVPair(k, v) for k, v in o[3][5]],
            ),
        )


class TxIndexer:
    def index(self, result: TxResult) -> None:
        raise NotImplementedError

    def index_batch(self, height: int, results: List[TxResult]) -> None:
        """Ingest a whole block's TxResults in one operation. The base
        implementation loops index(); KVTxIndexer overrides it with one
        DB write-batch and ONE generation bump for the block."""
        for r in results:
            self.index(r)

    def get(self, hash_: bytes) -> Optional[TxResult]:
        raise NotImplementedError

    def search(self, query: Query) -> List[TxResult]:
        raise NotImplementedError

    def indexed_height(self) -> int:
        """Highest block height this indexer has ingested txs for."""
        return 0

    def index_generation(self) -> int:
        """Monotonic ingest counter — the generation key the RPC cache
        stamps tx_search results with. A search result is a pure
        function of the index contents, and the contents change exactly
        when this advances. Per-tx index() bumps it per ingest;
        index_batch bumps it ONCE per block, AFTER the block's rows are
        all written — so the tx_search cache invalidates per block, and
        a search that read the pre-block generation while the batch was
        being written can never be served once the block lands (its key
        is stale the moment the bump happens)."""
        return 0


class NullTxIndexer(TxIndexer):
    """reference state/txindex/null/null.go"""

    def index(self, result: TxResult) -> None:
        pass

    def index_batch(self, height: int, results: List[TxResult]) -> None:
        pass

    def get(self, hash_: bytes) -> Optional[TxResult]:
        return None

    def search(self, query: Query) -> List[TxResult]:
        return []


def _tag_prefix(key: str) -> bytes:
    """NUL-terminated tag key: values/heights live in a msgpack suffix, so
    a '/' (or any byte) inside a tag value can't corrupt row parsing."""
    kb = key.encode()
    if b"\x00" in kb:
        raise ValueError(f"tag key may not contain NUL: {key!r}")
    return kb + b"\x00"


def _tag_key(key: str, value: str, height: int, index: int) -> bytes:
    return _tag_prefix(key) + serde.pack([value, height, index])


class KVTxIndexer(TxIndexer):
    """reference state/txindex/kv/kv.go:28. Primary rows are hash->TxResult;
    secondary rows are tagkey/value/height/index -> hash.

    Crash consistency: every ingest batch carries a durable marker row
    (_META_HEIGHT, written LAST in the batch) holding the highest fully
    ingested height. A torn batch append (FileDB tail tear) loses the
    marker with the tail, so a partially-landed block reads as
    not-ingested — recover_index() then re-indexes it from the stored
    blocks + ABCI responses. Row keys are deterministic functions of
    (tx, height, index), so re-indexing is idempotent."""

    # NUL-prefixed, 21 bytes: cannot collide with tag rows (tag keys
    # refuse NUL) or primary rows (32-byte tx hashes)
    _META_HEIGHT = b"\x00meta:indexed_height"

    def __init__(self, db: DB, index_tags: Optional[List[str]] = None, index_all_tags: bool = False):
        self._db = db
        self._tags = set(index_tags or [])
        self._all = index_all_tags
        self._lock = threading.Lock()
        # _marker: the durable floor ("every block <= this is FULLY
        # ingested" — what recovery trusts); _indexed_height: live
        # ingest progress (highest height any tx landed for — what
        # waiters poll). They coincide at boot and after every batch
        # ingest; the per-tx path keeps the marker one block behind.
        self._marker = self._load_marker()
        self._indexed_height = self._marker
        self._index_generation = 0

    def _load_marker(self) -> int:
        raw = self._db.get(self._META_HEIGHT)
        if raw:
            try:
                return int(serde.unpack(raw))
            except (ValueError, TypeError):
                return 0
        # pre-marker data dir (or marker lost to a tear): seed the
        # floor from the existing height tag rows in ONE read-only
        # pass minus 1 (the top block may be half-ingested) — without
        # this, every legacy boot would re-index the whole chain
        top = 0
        prefix = _tag_prefix(TX_HEIGHT_KEY)
        for k, _v in self._db.iterator(prefix, prefix + b"\xff" * 8):
            try:
                _val, h, _i = serde.unpack(k[len(prefix):])
                top = max(top, int(h))
            except (ValueError, TypeError):
                continue
        return max(0, top - 1)

    def indexed_height(self) -> int:
        with self._lock:
            return self._indexed_height

    def index_generation(self) -> int:
        with self._lock:
            return self._index_generation

    def _add_rows(self, batch, result: TxResult) -> None:
        """One tx's primary + secondary rows into `batch` (shared by the
        per-tx and block-batch ingest paths so they cannot drift)."""
        h = tx_hash(result.tx)
        for kv in result.result.tags:
            try:
                key = kv.key.decode()
                val = kv.value.decode()
            except UnicodeDecodeError:
                continue
            if self._all or key in self._tags:
                batch.set(_tag_key(key, val, result.height, result.index), h)
        batch.set(
            _tag_key(TX_HEIGHT_KEY, str(result.height), result.height, result.index), h
        )
        batch.set(h, result.to_bytes())

    def index(self, result: TxResult) -> None:
        with self._lock:
            self._index_generation += 1
            # per-tx ingest cannot know when a block is COMPLETE, so
            # the durable marker only advances to height-1 (the prior
            # block must be done once this one's txs arrive) — stamping
            # the current height would mark a half-indexed block as
            # fully ingested and recovery would skip its missing tail.
            # Recovery re-indexes the in-flight block; rows are
            # idempotent, so the overlap is harmless.
            self._marker = max(self._marker, result.height - 1)
            if result.height > self._indexed_height:
                self._indexed_height = result.height
            batch = self._db.batch()
            self._add_rows(batch, result)
            batch.set(self._META_HEIGHT, serde.pack(self._marker))
            batch.write()

    def index_batch(self, height: int, results: List[TxResult]) -> None:
        """Block-scoped ingest: compose ALL of the block's tag + primary
        rows and write ONE DB batch, then bump the generation once —
        search/get results are identical to per-tx index() calls in
        order (property-tested), but the tx_search RPC cache now expires
        once per block instead of once per tx, and the DB pays one
        lock/flush instead of one per tx. The generation bump happens
        AFTER the write so a search stamped with the pre-block
        generation can never outlive the block's landing."""
        if not results:
            return
        with self._lock:
            batch = self._db.batch()
            for result in results:
                self._add_rows(batch, result)
            # durable commit record for the block's ingest: written LAST
            # in the one-flush batch, so any tear strands the block's
            # rows BELOW the marker and recovery re-indexes the block
            self._marker = max(self._marker, height)
            batch.set(self._META_HEIGHT, serde.pack(self._marker))
            fail.fail_point("Index.BeforeBatchWrite")
            batch.write()
            fail.fail_point("Index.AfterBatchWrite")
            fail.fail_point("Index.BeforeGenerationBump")
            self._index_generation += 1
            if height > self._indexed_height:
                self._indexed_height = height

    def advance_marker(self, height: int) -> None:
        """Move the durable ingest marker forward without writing rows
        (recovery bookkeeping for tx-less heights)."""
        with self._lock:
            if height > self._marker:
                self._marker = height
                self._db.set(self._META_HEIGHT, serde.pack(height))
            if height > self._indexed_height:
                self._indexed_height = height

    def get(self, hash_: bytes) -> Optional[TxResult]:
        raw = self._db.get(hash_)
        return TxResult.from_bytes(raw) if raw else None

    def search(self, query: Query) -> List[TxResult]:
        """Conjunctive tag search (reference kv.go Search:144-231). A
        tx.hash condition short-circuits to a point lookup; otherwise
        intersect hash sets across conditions, scanning secondary rows."""
        for c in query.conditions:
            if c.key == TX_HASH_KEY and c.op == "=":
                try:
                    h = bytes.fromhex(c.value)
                except ValueError:
                    return []
                res = self.get(h)
                return [res] if res else []

        hashes: Optional[set] = None
        for c in query.conditions:
            matching = set()
            prefix = _tag_prefix(c.key)
            for k, v in self._db.iterator(prefix, prefix + b"\xff" * 8):
                try:
                    val, _h, _i = serde.unpack(k[len(prefix):])
                except (ValueError, TypeError):
                    continue
                if c.compare_value(val):
                    matching.add(bytes(v))
            hashes = matching if hashes is None else hashes & matching
            if not hashes:
                return []
        results = [self.get(h) for h in (hashes or set())]
        out = [r for r in results if r is not None]
        out.sort(key=lambda r: (r.height, r.index))
        return out


def recover_index(indexer: TxIndexer, block_store, state_db,
                  logger=None) -> int:
    """Boot-time index convergence: re-ingest every committed block
    above the indexer's durable marker from the stored blocks + ABCI
    responses (both durable before the indexer ever sees a tx).

    This closes the two crash windows the event-driven IndexerService
    cannot: (a) a block whose ingest batch was lost or torn mid-append
    (the FileDB reload drops the torn tail, and the marker — written
    last in the batch — vanished with it), and (b) blocks committed or
    handshake-replayed while the service wasn't subscribed. Re-indexing
    is idempotent (row keys are pure functions of tx/height/index), so
    overlapping with a live ingest of the same block is harmless.
    Returns the number of blocks re-indexed."""
    if not isinstance(indexer, KVTxIndexer):
        return 0
    from .store import load_abci_responses

    target = block_store.height()
    n_blocks = 0
    h = max(indexer.indexed_height() + 1, block_store.base())
    while h <= target:
        block = block_store.load_block(h)
        if block is None:
            break
        if block.data.txs:
            try:
                responses = load_abci_responses(state_db, h)
            except Exception:  # noqa: BLE001 - unreadable == not stored
                responses = None
            if (responses is None
                    or len(responses.deliver_tx) < len(block.data.txs)):
                # not applied yet (crash between block save and apply):
                # the post-handshake re-apply will index it live
                break
            results = [
                TxResult(height=h, index=i, tx=bytes(tx),
                         result=responses.deliver_tx[i])
                for i, tx in enumerate(block.data.txs)
            ]
            indexer.index_batch(h, results)
            n_blocks += 1
            if logger is not None:
                logger.info("re-indexed block %d (%d txs) after restart",
                            h, len(results))
        else:
            indexer.advance_marker(h)
        h += 1
    return n_blocks


class IndexerService(BaseService):
    """Event-bus subscriber indexing committed txs (reference
    state/txindex/indexer_service.go:17-69). With `batch` on (default)
    the drainer takes everything buffered in one wakeup, groups it by
    height, and hands each block to index_batch — one DB write-batch
    and one generation bump per block instead of per tx. `batch=False`
    restores the per-tx index() path ([tx_index] batch)."""

    SUBSCRIBER = "IndexerService"

    def __init__(self, indexer: TxIndexer, event_bus: EventBus,
                 batch: bool = True, stage_profile=None):
        super().__init__("IndexerService")
        self.indexer = indexer
        self.event_bus = event_bus
        self.batch = batch
        # commit-path profiler hook (state/execution.CommitStageProfile):
        # ingest wall time reports as the "index" stage
        self.stage_profile = stage_profile
        self._thread: Optional[threading.Thread] = None

    def on_start(self) -> None:
        self._sub = self.event_bus.subscribe(
            self.SUBSCRIBER, query_for_event(EVENT_TX), capacity=8192
        )
        self._thread = threading.Thread(target=self._run, name="tx-indexer", daemon=True)
        self._thread.start()

    def _ingest(self, msgs) -> None:
        from ..libs import tracing

        results = [
            TxResult(height=m.data["height"], index=m.data["index"],
                     tx=m.data["tx"], result=m.data["result"])
            for m in msgs
        ]
        # txindex.drain: one span per block of a drain, caused by the
        # commit.events that published the block's txs; the "index"
        # stage is the spans' own clock reads, summed over the drain
        seconds = 0.0
        if not self.batch:
            with tracing.timed("txindex.drain", cat="index",
                               cause=msgs[0].cause, txs=len(results)) as sp:
                for r in results:
                    self.indexer.index(r)
            seconds = sp.seconds
        else:
            # group consecutive same-height runs: one index_batch per
            # block even when a drain straddles several blocks
            start = 0
            for i in range(1, len(results) + 1):
                if i == len(results) or results[i].height != results[start].height:
                    height = results[start].height
                    with tracing.timed("txindex.drain", cat="index",
                                       cause=msgs[start].cause,
                                       request=("block", height),
                                       height=height, txs=i - start) as sp:
                        self.indexer.index_batch(height, results[start:i])
                    seconds += sp.seconds
                    start = i
        if self.stage_profile is not None and results:
            self.stage_profile.observe("index", seconds)

    def _run(self) -> None:
        while not self._quit.is_set():
            msgs = self._sub.get_batch(8192, timeout=0.2)
            if msgs:
                self._ingest(msgs)

    def on_stop(self) -> None:
        self.event_bus.unsubscribe_all(self.SUBSCRIBER)
        # _quit was set by BaseService.stop() before this hook runs;
        # join so no tx-indexer thread outlives its service
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
