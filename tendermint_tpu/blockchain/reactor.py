"""BlockchainReactor — fast sync on channel 0x40.

Reference parity: blockchain/reactor.go.  Downloads blocks in parallel
via the BlockPool, verifies each block's commit with the *next* block's
LastCommit — ★ the second north-star call site (:310): one
`validators.verify_commit` per block, which our build routes through
the TPU batch verifier so a 500-validator commit is one device batch,
not 500 serial verifies — then applies and stores it, finally handing
off to consensus once caught up (:258-274). With async dispatch on,
the sync loop pipelines: block k+1's commit batch is on the device
while block k's apply runs on the host (_try_sync_batch_pipelined).

Messages (["kind", ...] over serde): block_request(height),
block_response(block), no_block_response(height), status_request,
status_response(height).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from ..libs import tracing
from ..p2p.base_reactor import ChannelDescriptor, Reactor
from ..state.validation import VerifiedCommit
from ..types import serde
from ..types.basic import BlockID
from ..types.part_set import PartSet

LOG = logging.getLogger("blockchain.reactor")

BLOCKCHAIN_CHANNEL = 0x40

# valid wire message kinds; the per-peer msg_type metric label is drawn
# from this set so a peer can't mint arbitrary label values
_KNOWN_MSG_KINDS = frozenset((
    "block_request", "block_response", "no_block_response",
    "status_request", "status_response",
))

TRY_SYNC_INTERVAL = 0.01  # reactor.go:31 trySyncIntervalMS
STATUS_UPDATE_INTERVAL = 10.0  # reactor.go:34
# replica tail mode never hands off to consensus, so peer status polls
# are its only way to learn new heights — poll much faster than the
# catch-up default or the replica trails the chain by whole seconds
TAIL_STATUS_UPDATE_INTERVAL = 0.5
SWITCH_TO_CONSENSUS_INTERVAL = 1.0  # reactor.go:37
SYNC_BATCH = 10  # blocks applied per didProcess burst


def _enc(obj) -> bytes:
    return serde.pack(obj)


# a block_response is the array ["block_response", block]: what follows
# this head is the block's own encoding
_BLOCK_RESPONSE_HEAD = _enc(["block_response", None])[:-1]


def _part_set(block) -> PartSet:
    """The part set of a block a peer sent, cut from the bytes it came
    in. They are block.encode() because serde is deterministic
    (tests/test_committee_scale.py); were they not (a peer that packs
    another way), the part-set hash would not be the one the commit
    signed and the block would be refused, as any altered block is."""
    data = block.arrived_as
    return PartSet.from_data(data if data is not None else block.encode())


class _SpeculativeVerify:
    """One in-flight pipelined block verification: the block pair, its
    part set / BlockID, the pending (possibly async) commit verify, and
    the validator-set hash it was dispatched under."""

    __slots__ = ("first", "second", "parts", "block_id", "pending",
                 "val_hash")

    def __init__(self, first, second, parts, block_id, pending, val_hash):
        self.first = first
        self.second = second
        self.parts = parts
        self.block_id = block_id
        self.pending = pending
        self.val_hash = val_hash


class BlockchainReactor(Reactor):
    def __init__(self, state, block_exec, block_store, fast_sync: bool,
                 consensus_reactor=None, tail_forever: bool = False):
        """`tail_forever` is replica mode ([base] mode = replica): the
        sync loop never stops and never hands off to consensus — the
        node permanently tails committed blocks (verify → apply →
        publish events) and serves reads. resume_fast_sync after a
        state-sync bootstrap re-enters the same endless loop."""
        super().__init__("BlockchainReactor")
        self.initial_state = state
        self.state = state
        self.block_exec = block_exec
        self.store = block_store
        self.fast_sync = fast_sync
        self.tail_forever = tail_forever
        self.consensus_reactor = consensus_reactor  # for switch_to_consensus
        self._stop = threading.Event()
        self._pool_thread: Optional[threading.Thread] = None
        self.blocks_synced = 0
        # what _apply_verified noted of the last commit the loop verified
        self._verified_commit: Optional[VerifiedCommit] = None
        # height -> cause of the p2p.recvBlock that decoded it, so the
        # sync loop's fastsync.block names the download as its parent;
        # filled only while the recorder is on, emptied as blocks apply
        self._recv_cause: dict = {}

        from .pool import BlockPool

        self.pool = BlockPool(
            start_height=self.store.height() + 1,
            request_fn=self._send_block_request,
            error_fn=self._on_peer_error,
        )
        # replica fan-out tree (attach_tree): when set, only the
        # current parent's heights feed the pool and every
        # status_response we send carries the tree meta element
        self.tree = None
        # push-based tip announcement (enable_tip_announce)
        self._tip_bus = None
        self._tip_sub = None
        self._tip_thread: Optional[threading.Thread] = None
        self._tip_subscriber = f"bc-tip-{id(self):x}"

    def get_channels(self):
        return [
            ChannelDescriptor(
                id=BLOCKCHAIN_CHANNEL, priority=10, send_queue_capacity=1000,
                recv_message_capacity=10 * 1024 * 1024,
            )
        ]

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        if self.fast_sync:
            self._start_pool()
        self._start_tip_announce()

    def _start_pool(self) -> None:
        self.pool.start()
        self._pool_thread = threading.Thread(
            target=self._pool_routine, name="bc-pool", daemon=True
        )
        self._pool_thread.start()

    def resume_fast_sync(self, state) -> None:
        """State-sync hand-off: the restore path installed `state` at
        the snapshot height and seeded the block store, so fast sync
        now covers only the residual tail. Rebuilds the pool at the
        store's (post-seed) height and starts the sync routine — the
        reactor must have been constructed with fast_sync=False so the
        original start() was a no-op."""
        from .pool import BlockPool

        if self.fast_sync:
            return  # already syncing
        self.state = state
        self.initial_state = state
        self.fast_sync = True
        self.pool = BlockPool(
            start_height=self.store.height() + 1,
            request_fn=self._send_block_request,
            error_fn=self._on_peer_error,
        )
        self._start_pool()
        # peers connected before the hand-off never saw our status
        # request routed to the (dead) pool; re-ask immediately
        self._broadcast_status_request()

    def attach_tree(self, tree) -> None:
        """Arm the replica fan-out tree (blockchain/replica_tree.py).
        From here on the pool tails exactly one upstream — the tree's
        current parent — and re-parenting re-wires the pool: the old
        parent's in-flight requests redispatch, the new parent's height
        seeds the pool, and the tail resumes from our own store height
        (the pool never rewinds)."""
        self.tree = tree
        tree.on_switch = self._on_tree_switch

    def _on_tree_switch(self, old_parent, new_parent, reason,
                        new_height) -> None:
        if old_parent is not None:
            self.pool.remove_peer(old_parent)
        if new_parent is not None and new_height > 0:
            self.pool.set_peer_height(new_parent, new_height)

    def _status_msg(self) -> bytes:
        """Our status_response; carries the tree meta element when the
        fan-out tree is armed (wire-compatible: untreed peers unpack
        the 2-element form, treed peers tolerate its absence)."""
        msg = ["status_response", self.store.height()]
        if self.tree is not None:
            msg.append(self.tree.local_meta())
        return _enc(msg)

    def enable_tip_announce(self, event_bus) -> None:
        """Arm push-based tip announcement: once started, every
        committed block (NewBlock on the node's event bus — consensus
        commits AND replica tail applies both fire it) broadcasts an
        unsolicited status_response on the blockchain channel, so a
        tailing replica learns the new height in one RTT instead of
        waiting out its 0.5s status poll. Peers already absorb
        unsolicited status_responses (receive() routes them to
        pool.set_peer_height), so the announcement is wire-compatible
        with every existing node. The subscription + announcer thread
        spin up in start() (and are joined by stop()), so an armed but
        never-started reactor owns no resources."""
        self._tip_bus = event_bus

    def _start_tip_announce(self) -> None:
        from ..types.event_bus import EVENT_NEW_BLOCK, query_for_event

        if self._tip_bus is None or self._tip_sub is not None:
            return
        self._tip_sub = self._tip_bus.subscribe(
            self._tip_subscriber, query_for_event(EVENT_NEW_BLOCK), 64)
        self._tip_thread = threading.Thread(
            target=self._tip_announce_loop, name="bc-tip-announce",
            daemon=True)
        self._tip_thread.start()

    def _tip_announce_loop(self) -> None:
        sub = self._tip_sub
        while not self._stop.is_set() and not sub.cancelled:
            msgs = sub.get_batch(64, timeout=0.5)
            if not msgs:
                continue
            # a burst coalesces: only the newest tip matters, and the
            # store height is the authoritative one
            if self.switch is not None:
                self.switch.broadcast(BLOCKCHAIN_CHANNEL,
                                      self._status_msg())

    def stop(self) -> None:
        self._stop.set()
        if self._tip_bus is not None:
            self._tip_bus.unsubscribe_all(self._tip_subscriber)
            self._tip_bus = None
        t = self._tip_thread
        if t is not None:
            t.join(timeout=2.0)
            self._tip_thread = None
        self.pool.stop()

    # -- peers ---------------------------------------------------------

    def add_peer(self, peer) -> None:
        """reactor.go:139-148: tell the new peer our height."""
        peer.try_send(BLOCKCHAIN_CHANNEL, self._status_msg())

    def remove_peer(self, peer, reason) -> None:
        if self.tree is not None:
            self.tree.on_peer_removed(peer.id)
        self.pool.remove_peer(peer.id)

    # -- inbound -------------------------------------------------------

    def receive(self, ch_id: int, peer, msg_bytes: bytes) -> None:
        """reactor.go:174-214."""
        tracer = tracing.get_tracer()
        t_recv = cpu_recv = 0
        if tracer.enabled:
            t_recv, cpu_recv = time.perf_counter_ns(), time.thread_time_ns()
        obj = serde.unpack(msg_bytes)
        kind = obj[0]
        if self.switch is not None and peer.is_running():
            # label from the whitelist only — `kind` is raw wire input
            # and must not name an unbounded (or malformed) series
            label = kind if kind in _KNOWN_MSG_KINDS else "unknown"
            self.switch.metrics.peer_msg_recv_total.with_labels(
                peer.id, f"{ch_id:#04x}", label).inc()
        if kind == "block_request":
            height = obj[1]
            block = self.store.load_block(height)
            if block is not None:
                peer.try_send(
                    BLOCKCHAIN_CHANNEL, _enc(["block_response", serde.block_obj(block)])
                )
            else:
                peer.try_send(BLOCKCHAIN_CHANNEL, _enc(["no_block_response", height]))
        elif kind == "block_response":
            block = serde.block_from(obj[1])
            if msg_bytes.startswith(_BLOCK_RESPONSE_HEAD):
                block.arrived_as = msg_bytes[len(_BLOCK_RESPONSE_HEAD):]
            if self.tree is not None:
                self.tree.note_delivery(peer.id)
            self.pool.add_block(peer.id, block, len(msg_bytes))
            if t_recv:
                # decode + hand-over on the p2p thread, known to be a
                # block only now: recorded from its two clock readings
                height = block.header.height
                if len(self._recv_cause) > 4096:  # blocks never applied
                    self._recv_cause.clear()
                cpu = time.thread_time_ns() - cpu_recv
                self._recv_cause[height] = tracer.record(
                    "p2p.recvBlock", t_recv, time.perf_counter_ns(), "p2p",
                    request=("block", height), cpu_ns=cpu,
                    cpu_start_ns=cpu_recv, height=height,
                    txs=len(block.data.txs), bytes=len(msg_bytes),
                    peer=peer.id[:8])
        elif kind == "no_block_response":
            LOG.debug("peer %s has no block at %d", peer.id[:8], obj[1])
        elif kind == "status_request":
            peer.try_send(BLOCKCHAIN_CHANNEL, self._status_msg())
        elif kind == "status_response":
            if self.tree is not None:
                # tree gating: only the (possibly just-adopted) parent
                # feeds the pool — everyone else is a scored candidate
                meta = obj[2] if len(obj) > 2 else None
                if self.tree.note_status(peer.id, obj[1], meta):
                    self.pool.set_peer_height(peer.id, obj[1])
            else:
                self.pool.set_peer_height(peer.id, obj[1])
        else:
            raise ValueError(f"unknown blockchain message {kind!r}")

    # -- pool plumbing -------------------------------------------------

    def _send_block_request(self, peer_id: str, height: int) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is not None:
            peer.try_send(BLOCKCHAIN_CHANNEL, _enc(["block_request", height]))

    def _on_peer_error(self, peer_id: str, reason: str) -> None:
        if self.tree is not None:
            self.tree.note_garbage(peer_id)
        if self.switch is not None:
            peer = self.switch.peers.get(peer_id)
            if peer is not None:
                self.switch.stop_peer_for_error(peer, RuntimeError(reason))

    def _broadcast_status_request(self) -> None:
        if self.switch is not None:
            self.switch.broadcast(BLOCKCHAIN_CHANNEL, _enc(["status_request"]))

    # -- the sync loop -------------------------------------------------

    @property
    def catching_up(self) -> bool:
        """/status sync_info.catching_up: a tailing replica that is at
        (or within the one-block verify lag of) its best peer height is
        serving live data, not catching up."""
        if not self.fast_sync:
            return False
        if not self.tail_forever:
            return True
        max_peer = self.pool.max_peer_height()
        if max_peer <= 0:
            # no peer height known (fresh boot, partition): claiming
            # "caught up" here would route read traffic to a replica
            # serving arbitrarily stale data — stay conservative
            return True
        # the tail verifies block h with h+1's commit, so a healthy
        # replica legitimately sits one block behind the tip it knows
        return self.store.height() < max_peer - 1

    def _pool_routine(self) -> None:
        """reactor.go:216-359."""
        last_status = 0.0
        last_switch_check = 0.0
        status_interval = (TAIL_STATUS_UPDATE_INTERVAL if self.tail_forever
                           else STATUS_UPDATE_INTERVAL)
        self._broadcast_status_request()
        # fastsync.poolWait: from the first pass that found no pair of
        # blocks ready to the pass that found one — the joiner starved
        # by its peers. Recorded when it ends, so it never covers a
        # loop that has nothing more to wait for.
        tracer = tracing.get_tracer()
        wait_t0 = 0
        while not self._stop.is_set() and self.pool.is_running():
            now = time.monotonic()
            if now - last_status >= status_interval:
                last_status = now
                self._broadcast_status_request()
            if now - last_switch_check >= SWITCH_TO_CONSENSUS_INTERVAL:
                last_switch_check = now
                if self._maybe_switch_to_consensus():
                    return
            t_try = time.perf_counter_ns() if wait_t0 else 0
            if self._try_sync_batch():
                if wait_t0:
                    tracer.record("fastsync.poolWait", wait_t0, t_try,
                                  "fastsync")
                    wait_t0 = 0
            else:
                if not wait_t0 and tracer.enabled:
                    wait_t0 = time.perf_counter_ns()
                time.sleep(TRY_SYNC_INTERVAL)

    def _maybe_switch_to_consensus(self) -> bool:
        """reactor.go:258-280. Replicas (tail_forever) never switch:
        the pool keeps running and the loop keeps tailing new blocks."""
        if self.tail_forever:
            return False
        height, num_pending, total = self.pool.get_status()
        if self.pool.is_caught_up():
            LOG.info("caught up at height %d; switching to consensus", height - 1)
            self.pool.stop()
            # the node is no longer syncing: /status catching_up must
            # flip here, not stay pinned at the boot-time value
            self.fast_sync = False
            if self.consensus_reactor is not None:
                self.consensus_reactor.switch_to_consensus(self.state, self.blocks_synced)
            return True
        return False

    def _try_sync_batch(self) -> bool:
        """reactor.go:283-353: verify-then-apply up to SYNC_BATCH blocks.
        Returns True if at least one block was processed. With async
        dispatch enabled (config [crypto] async_dispatch) the loop runs
        as a two-stage pipeline — block k+1's commit verifies on-device
        while block k applies on the host."""
        from ..crypto import batch as crypto_batch

        # BLS chains take the serial path even with async dispatch on:
        # aggregate certificates have no Ed25519 device batch to
        # overlap, and the serial loop batches the window's pairing
        # checks into one multi-pair product check instead
        if (crypto_batch.async_enabled()
                and not self.state.validators.is_bls()):
            return self._try_sync_batch_pipelined()
        return self._try_sync_batch_serial()

    def _preverify_agg_window(self):
        """Replica catch-up certificate batching: when commits are BLS
        AggregateCommits, the contiguous downloaded window's pairing
        checks collapse into ONE bls.verify_aggregates_many call
        (2k pairs, one Miller loop) instead of up to SYNC_BATCH
        sequential 2-pairing checks. Only certificates that PASS are
        memoized — any failure is left for the per-block verify path to
        re-derive its exact error (and redo the height). The memo pins
        the validator-set hash plus the exact block/commit objects, so
        a val-set change mid-window or a redone block simply misses."""
        vals = self.state.validators
        if not vals.is_bls():
            return {}
        from ..types.block import AggregateCommit

        window = self.pool.peek_window(SYNC_BATCH + 1)
        if len(window) < 3:  # fewer than two pairs: nothing to batch
            return {}
        checks = []
        meta = []  # (first, second, parts, block_id)
        for first, second in zip(window, window[1:]):
            commit = second.last_commit
            if not isinstance(commit, AggregateCommit):
                continue
            parts = _part_set(first)
            block_id = BlockID(hash=first.hash(),
                               parts_header=parts.header())
            checks.append((block_id, first.header.height, commit))
            meta.append((first, second, parts, block_id))
        if len(checks) < 2:
            return {}
        errs = vals.verify_commits_aggregate_many(self.state.chain_id,
                                                  checks)
        vhash = vals.hash()
        pre = {}
        for err, (first, second, parts, block_id) in zip(errs, meta):
            if err is None:
                pre[first.header.height] = (vhash, first,
                                            second.last_commit, parts,
                                            block_id)
        return pre

    def _block_span(self, block):
        """fastsync.block: the root of everything done for one height in
        the sync loop, caused by the p2p.recvBlock that decoded it."""
        height = block.header.height
        return tracing.span(
            "fastsync.block", cat="fastsync",
            cause=self._recv_cause.pop(height, None),
            request=("block", height), height=height,
            txs=len(block.data.txs))

    def _try_sync_batch_serial(self) -> bool:
        processed = 0
        pre = self._preverify_agg_window()
        for _ in range(SYNC_BATCH):
            first, second = self.pool.peek_two_blocks()
            if first is None or second is None:
                break
            with self._block_span(first):
                if not self._sync_one_serial(first, second, pre):
                    return processed > 0
            processed += 1
        return processed > 0

    def _sync_one_serial(self, first, second, pre) -> bool:
        """Verify, save and apply one block; False if its commit failed."""
        height = first.header.height
        hit = pre.pop(height, None)
        if (hit is not None and hit[1] is first
                and hit[2] is second.last_commit
                and hit[0] == self.state.validators.hash()):
            # certificate already verified in the window batch
            first_parts, first_id = hit[3], hit[4]
        else:
            with tracing.span("fastsync.partSet", cat="fastsync",
                              height=height):
                first_parts = _part_set(first)
                first_id = BlockID(hash=first.hash(),
                                   parts_header=first_parts.header())
            try:
                # ★ batch-verify the +2/3 commit for `first` carried
                # in `second.last_commit` (reactor.go:310) — one TPU
                # batch
                with tracing.span("fastsync.verifyWait", cat="fastsync",
                                  height=height):
                    self.state.validators.verify_commit(
                        self.state.chain_id, first_id, height,
                        second.last_commit,
                    )
            except Exception as e:
                LOG.warning("invalid block %d during fast sync: %s",
                            height, e)
                self._redo(height)
                return False
        self.pool.pop_request()
        self.store.save_block(first, first_parts, second.last_commit)
        # the pool head moved to k+1 after pop: stage it so the
        # executor can run it speculatively on k's un-promoted
        # overlay ([execution] speculate_depth >= 2; no-op default)
        stage = getattr(self.block_exec, "stage_next_block", None)
        if stage is not None:
            nfirst, _ = self.pool.peek_two_blocks()
            if nfirst is not None:
                stage(nfirst)
        self._apply_verified(first, first_id, second.last_commit)
        return True

    def _redo(self, height: int) -> None:
        """Block `height`'s commit, carried by block height+1, was
        refused: either may be the altered one, so the pool drops both
        with whatever else their peers delivered and asks again
        (reactor.go:318-330). Nothing this loop holds of a dropped
        block outlives it: the caller lets go of its speculative
        verify, the note of the last verified commit goes here (the
        copies that come back are judged from scratch, LastCommit
        included), and the executor settles a staged block by its hash
        when the next one is applied."""
        with tracing.span("fastsync.redo", cat="fastsync",
                          height=height) as sp:
            self._verified_commit = None
            dropped, peers = self.pool.redo_request(height)
            sp.set(dropped=dropped, peers=len(peers))

    def _apply_verified(self, block, block_id, commit) -> None:
        """Apply a block whose commit (carried by its successor) this
        loop has just verified against self.state.validators. That
        commit is the next block's LastCommit, which validate_block
        would verify once more under the same set (by then
        state.last_validators): note what was verified, keyed by the
        set's Merkle root, and hand the executor the note of one
        iteration earlier for this block's own LastCommit."""
        self.block_exec.verified_last_commit = self._verified_commit
        self._verified_commit = VerifiedCommit(
            commit, self.state.validators.hash(), self.state.chain_id,
            block_id, block.header.height)
        self.state = self.block_exec.apply_block(self.state, block_id, block)
        self.blocks_synced += 1
        if self.blocks_synced % 100 == 0:
            LOG.info("fast sync at height %d", self.state.last_block_height)

    # -- pipelined sync (verify k+1 on-device while k applies) ---------

    def _try_sync_batch_pipelined(self) -> bool:
        """Two-stage pipeline over the serial loop above: after block k
        verifies, block k+1's commit batch is dispatched (async) BEFORE
        apply(k) runs, so the device round trip hides behind host-side
        block execution — per-block wall drops from verify+apply toward
        max(verify, apply). Ordering and failure semantics match the
        serial path: a block is only popped/saved/applied after ITS
        commit verified; a failed verify redos that height and leaves
        the already-applied prefix in place."""
        processed = 0
        spec = None
        while processed < SYNC_BATCH:
            if spec is None:
                first, second = self.pool.peek_two_blocks()
                if first is None or second is None:
                    break
            else:
                first = spec.first
            # everything from here to the end of apply(k) is block k's,
            # except the dispatch of verify(k+1), which carries k+1's
            # request id under k's span
            with self._block_span(first):
                if spec is None:
                    spec = self._begin_block_verify(first, second)
                nxt = self._sync_one_pipelined(
                    spec, more=processed + 1 < SYNC_BATCH)
            if nxt is False:
                return processed > 0
            processed += 1
            spec = nxt
        return processed > 0

    def _sync_one_pipelined(self, spec, more: bool):
        """Resolve block k's verify, save it, dispatch verify(k+1), apply
        k. Returns k+1's speculative verify (None if there is no next
        pair yet), or False if k's commit failed."""
        height = spec.first.header.height
        with tracing.span("fastsync.verifyWait", cat="fastsync",
                          height=height):
            err = self._resolve_block_verify(spec)
        if err is not None:
            LOG.warning("invalid block %d during fast sync: %s", height, err)
            self._redo(height)
            return False
        self.pool.pop_request()
        self.store.save_block(spec.first, spec.parts, spec.second.last_commit)
        # dispatch verify(k+1) before apply(k): the pool head moved
        # to k+1 after pop, so peek now yields the next pair
        nxt = None
        if more:
            nfirst, nsecond = self.pool.peek_two_blocks()
            if nfirst is not None and nsecond is not None:
                nxt = self._begin_block_verify(nfirst, nsecond)
                # cross-height speculation: let k+1 execute on k's
                # un-promoted overlay while k applies (no-op unless
                # [execution] speculate_depth >= 2)
                stage = getattr(self.block_exec, "stage_next_block", None)
                if stage is not None:
                    stage(nfirst)
        self._apply_verified(spec.first, spec.block_id,
                             spec.second.last_commit)
        return nxt

    def _begin_block_verify(self, first, second) -> "_SpeculativeVerify":
        """Start (async) commit verification of `first` against
        second.last_commit, recording the validator-set hash it was
        dispatched under so _resolve_block_verify can detect a set that
        changed while the batch was in flight."""
        from ..types.validator_set import PendingCommitVerify

        height = first.header.height
        request = ("block", height)
        with tracing.span("fastsync.partSet", cat="fastsync",
                          request=request, height=height):
            parts = _part_set(first)
            block_id = BlockID(hash=first.hash(),
                               parts_header=parts.header())
        vals = self.state.validators
        with tracing.span("fastsync.verifyBegin", cat="fastsync",
                          request=request, height=height):
            try:
                pending = vals.begin_verify_commit(
                    self.state.chain_id, block_id, height,
                    second.last_commit,
                )
            except Exception as e:  # structural pre-check failed synchronously
                pending = PendingCommitVerify(exc=e)
            val_hash = vals.hash()
        return _SpeculativeVerify(first, second, parts, block_id, pending,
                                  val_hash)

    def _resolve_block_verify(self, spec) -> Optional[Exception]:
        """Wait for a speculative verification; returns the failure (or
        None). If apply(k) changed the validator set while verify(k+1)
        was in flight, the speculative result is discarded — neither
        trusted nor assumed wrong — and the commit re-verifies
        synchronously against the CURRENT set (validator updates are
        rare; the speculation wins every other block)."""
        vals = self.state.validators
        if spec.val_hash != vals.hash():
            try:
                vals.verify_commit(
                    self.state.chain_id, spec.block_id,
                    spec.first.header.height, spec.second.last_commit,
                )
            except Exception as e:
                return e
            return None
        try:
            spec.pending.result()
        except Exception as e:
            return e
        return None
