"""ConsensusState — the Tendermint BFT state machine.

Reference parity: consensus/state.go. The single-writer receive loop
(receiveRoutine :561-622) consumes peer messages, internal (self-signed)
messages, and timeouts from one queue; every message is WAL'd before
processing (fsync'd for internal ones). The transition graph —
enterNewRound :730 → enterPropose :800 → enterPrevote :942 →
enterPrevoteWait :997 → enterPrecommit (lock/unlock/POL) :1025 →
enterPrecommitWait :1121 → enterCommit :1149 → finalizeCommit :1225 —
is reproduced exactly, including proposer selection, POL locking rules,
and the commit fsync ordering with fail points.

Vote ingestion (addVote :1495-1639) is north-star call site #2, and the
live path batches ADAPTIVELY: the receive loop drains the contiguous run
of queued VoteMessages and pre-verifies their signatures as ONE
BatchVerifier call (per-item masks) before running the per-vote
transitions (_handle_vote_msgs / _preverify_votes). Light traffic →
batch of 1 → serial CPU verify, zero added latency; heavy traffic
(catch-up streams, big valsets) → device-sized batches. Bulk ingestion
(VoteSet.add_votes for commit reconstruction, ValidatorSet.verify_commit
for fast sync) rides the same engine. With [crypto] async_dispatch on,
the drained run's batch is dispatched (verify_async) BEFORE its WAL
writes, so the fsync overlaps the device round trip.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from contextlib import contextmanager
from typing import Callable, List, Optional

from ..config import ConsensusConfig
from ..libs import fail, timeline as timeline_mod, tracing
from ..libs.lockdep import GenStamp, stamped_read
from ..state import BlockExecutor
from ..state import state as sm_state
from ..types.basic import (
    VOTE_TYPE_PRECOMMIT,
    VOTE_TYPE_PREVOTE,
    BlockID,
    ErrVoteConflictingVotes,
    Proposal,
    Vote,
    now_ns,
)
from ..types.block import Block, Commit
from ..types.event_bus import EventBus
from ..types.part_set import PartSet
from ..types.vote_set import ErrVoteInvalid, VoteSet
from . import cstypes
from .cstypes import (
    STEP_COMMIT,
    STEP_NEW_HEIGHT,
    STEP_NEW_ROUND,
    STEP_PRECOMMIT,
    STEP_PRECOMMIT_WAIT,
    STEP_PREVOTE,
    STEP_PREVOTE_WAIT,
    STEP_PROPOSE,
    HeightVoteSet,
    RoundState,
    RoundStepType,
)
from .messages import (
    AggregateCommitMessage,
    BlockPartMessage,
    HandelContributionMessage,
    ProposalMessage,
    VoteMessage,
)
from .ticker import TimeoutInfo, TimeoutTicker
from .wal import NilWAL, WAL, EndHeightMessage, TimedWALMessage

LOG = logging.getLogger("consensus")

# cap on one drained vote batch — bounds the pre-commit-event latency of
# the first vote in the run and the device bucket size
MAX_VOTE_BATCH = 1024


class ConsensusState:
    """The consensus machine for one node (reference ConsensusState
    :63-119). Not a BaseService subclass: lifecycle is start()/stop()
    with a dedicated receive thread."""

    def __init__(
        self,
        config: ConsensusConfig,
        state,  # sm.State
        block_exec: BlockExecutor,
        block_store,
        mempool=None,
        evpool=None,
        event_bus: Optional[EventBus] = None,
        priv_validator=None,
        wal=None,
        metrics=None,
        handel_cfg=None,
    ):
        self.config = config
        self.block_exec = block_exec
        self.block_store = block_store
        from ..metrics import ConsensusMetrics
        from ..types.event_bus import NopEventBus

        self.metrics = metrics if metrics is not None else ConsensusMetrics()

        self.mempool = mempool
        self.evpool = evpool
        self.event_bus = event_bus or NopEventBus()
        self.priv_validator = priv_validator
        self.wal = wal if wal is not None else NilWAL()
        # process-global tracer (libs/tracing.py): disabled → no-op spans
        self.tracer = tracing.get_tracer()
        # per-height lifecycle recorder (libs/timeline.py), disabled until
        # the node enables it. Per-instance (unlike the tracer): each
        # node's marks and peer attribution must stay its own, even with
        # several in-process nodes (tests, sim harnesses)
        self.timeline = timeline_mod.Timeline()
        # incident ledger (libs/incident.py): the node (or scenario
        # runner) wires one in; None = every incident hook is a no-op.
        # The commit path closes healed incidents (the MTTR clock) and
        # the watchdog attaches stall classifications (the MTTD clock)
        self.incidents = None
        # wall clock of the last (height, round) change — the stall
        # watchdog's dwell anchor; written only by the receive thread.
        # _height_entered anchors the HEIGHT-level dwell: a partition
        # churns rounds fast enough that no single round ever crosses
        # the threshold while the height stays stuck for the whole fault
        self._round_entered = time.time()
        self._height_entered = time.time()

        self.rs = RoundState()
        # seqlock generation stamp over self.rs: the receive loop (the
        # single writer) brackets each message/timeout's processing with
        # write_begin/write_end, so get_round_state() can prove a
        # shallow copy did not interleave with a transition — the
        # PR-10 torn-read class (discipline rule CD-5)
        self._rs_stamp = GenStamp()
        # writer-published fallback snapshot: one (gen, snapshot)
        # tuple, swapped atomically (GIL) after every mutation burst,
        # so readers that lose the stamped-read race get a CONSISTENT,
        # at-most-one-burst-stale copy instead of a torn one — with
        # the generation that MATCHES it (a tuple, not two fields: two
        # loads could pair an old snapshot with a newer gen). Without
        # the fallback a busy receive loop (single-validator producer
        # committing back to back) keeps the generation odd most of
        # the time and every gossip tick would skip — catch-up
        # starves.
        self._rs_published = None  # Optional[(gen, RoundState)]
        self.state = None  # set by update_to_state

        # message queues (reference :38 msgQueueSize=1000)
        self._queue: "queue.Queue" = queue.Queue(maxsize=2000)
        self.ticker = TimeoutTicker()
        self._thread: Optional[threading.Thread] = None
        self._tock_thread: Optional[threading.Thread] = None
        self._done = threading.Event()
        self._stopped = threading.Event()
        self._replay_mode = False
        # reactor.go:114-117: a fast-synced node skips WAL catchup
        self.do_wal_catchup = True

        # test/reactor hooks (reference :106-108,150-153)
        self.decide_proposal: Callable = self._default_decide_proposal
        self.do_prevote: Callable = self._default_do_prevote
        self.set_proposal_fn: Callable = self._default_set_proposal
        # called with each new (height, round, step) — reactor broadcast hook
        self.on_new_round_step: Optional[Callable] = None
        # called with each vote we add — reactor HasVote broadcast hook
        self.on_vote_added: Optional[Callable] = None

        self.n_height_committed = 0  # metrics
        # BLS aggregate lane diagnostics (stall_snapshot / monitor)
        self.n_agg_merges = 0
        self.last_agg_cert_bytes = 0

        # Handel aggregation overlay (consensus/handel.py): built only
        # when [handel] enable is set — None keeps every hook below a
        # no-op and the flat certificate lane byte-identical to a build
        # without the overlay
        self.handel = None
        if handel_cfg is not None and getattr(handel_cfg, "enable", False):
            from .handel import HandelManager

            addr = (priv_validator.get_address()
                    if priv_validator is not None else None)
            self.handel = HandelManager(handel_cfg, state.chain_id, addr)

        self.update_to_state(state)
        self._reconstruct_last_commit_if_needed(state)

    # --- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self.wal.start()
        self.ticker.start()
        if self.do_wal_catchup:
            self._catchup_replay(self.rs.height)
        self._tock_thread = threading.Thread(
            target=self._tock_forwarder, name="cs-tock", daemon=True
        )
        self._tock_thread.start()
        self._thread = threading.Thread(
            target=self._receive_routine, name="cs-receive", daemon=True
        )
        self._thread.start()
        self._schedule_round0(self.rs)

    def stop(self) -> None:
        self._done.set()
        self.ticker.stop()
        self._stopped.wait(timeout=5.0)
        self.wal.stop()
        # settle any in-flight speculative execution so no exec-spec
        # thread (or open overlay session) outlives consensus
        stop_exec = getattr(self.block_exec, "stop", None)
        if stop_exec is not None:
            stop_exec()

    def wait_until_stopped(self, timeout: Optional[float] = None) -> bool:
        return self._stopped.wait(timeout)

    # --- external API (reactor / RPC entry points) --------------------------

    def add_peer_message(self, msg, peer_id: str = "") -> None:
        """Queue a message from a peer (reference :356-365 peerMsgQueue)."""
        try:
            self._queue.put(("msg", (peer_id, msg)), timeout=1.0)
        except queue.Full:
            LOG.warning("consensus queue full; dropping peer message")

    def _send_internal(self, msg) -> None:
        # internal messages must not drop (reference sendInternalMessage :332)
        self._queue.put(("msg", ("", msg)))

    def get_round_state(self) -> RoundState:
        """Stamped snapshot (shallow; the receive loop is the only
        writer). The returned RoundState carries `snapshot_gen` (the
        seqlock generation it was taken at) and `snapshot_consistent`
        (False when no provably-untorn copy could be produced).
        Consumers that build WIRE messages must check the flag — a torn
        forward-jumping (height, round, step) poisons every peer's view
        (PR-10's multi-node stall signature); diagnostic readers may
        tolerate tears but should report the flag.

        Reads from the receive thread itself are always consistent and
        skip the retry loop. Readers that lose the stamped-read race
        against a busy receive loop get the writer-published fallback —
        consistent by construction, at most one burst stale — so
        gossip never starves waiting for a quiet window; inconsistent
        snapshots only escape before the machine has processed its
        first message."""
        import copy

        snap, gen, consistent = stamped_read(
            self._rs_stamp, lambda: copy.copy(self.rs), retries=3)
        if not consistent:
            pub = self._rs_published
            if pub is not None:
                gen, published = pub
                snap, consistent = copy.copy(published), True
        snap.snapshot_gen = gen
        snap.snapshot_consistent = consistent
        return snap

    def is_proposer(self, address: Optional[bytes] = None) -> bool:
        if address is None:
            if self.priv_validator is None:
                return False
            address = self.priv_validator.get_address()
        return self.rs.validators.get_proposer().address == address

    # --- state update -------------------------------------------------------

    def update_to_state(self, state) -> None:
        """Reset the RoundState for state.last_block_height+1 (reference
        updateToState :471-557)."""
        with self._mutating():
            self._update_to_state_inner(state)

    def _update_to_state_inner(self, state) -> None:
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height != state.last_block_height:
            raise RuntimeError(
                f"update_to_state expected height {rs.height}, got {state.last_block_height}"
            )

        # last precommits become LastCommit (reference :497-508)
        last_precommits: Optional[VoteSet] = None
        if rs.commit_round > -1 and rs.votes is not None:
            pc = rs.votes.precommits(rs.commit_round)
            if pc is None or not pc.has_two_thirds_majority():
                raise RuntimeError("update_to_state with no +2/3 precommits")
            last_precommits = pc

        height = state.last_block_height + 1
        validators = state.validators.copy()

        rs.height = height
        self.wal.height = height  # the request id of its spans
        rs.round = 0
        rs.step = STEP_NEW_HEIGHT
        if rs.commit_time == 0:
            rs.start_time = self.config.commit_time(time.time())
        else:
            rs.start_time = self.config.commit_time(rs.commit_time)
        rs.validators = validators
        rs.proposal = None
        rs.proposal_block = None
        rs.proposal_block_parts = None
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        rs.valid_round = -1
        rs.valid_block = None
        rs.valid_block_parts = None
        rs.votes = HeightVoteSet(state.chain_id, height, validators)
        rs.commit_round = -1
        rs.last_commit = last_precommits
        rs.last_validators = state.last_validators
        rs.triggered_timeout_precommit = False

        if self.handel is not None:
            self.handel.advance_height(height)

        self._round_entered = time.time()
        self._height_entered = time.time()
        self.timeline.mark(height, "new_height")
        self.state = state
        self._new_step()

    def _reconstruct_last_commit_if_needed(self, state) -> None:
        """Rebuild LastCommit from the block store's seen commit after a
        restart (reference reconstructLastCommit :446-468)."""
        if state.last_block_height == 0 or self.rs.last_commit is not None:
            return
        seen = self.block_store.load_seen_commit(state.last_block_height)
        if seen is None:
            raise RuntimeError(
                f"no seen commit for height {state.last_block_height} to reconstruct LastCommit"
            )
        last_precommits = VoteSet(
            state.chain_id,
            state.last_block_height,
            seen.round(),
            VOTE_TYPE_PRECOMMIT,
            state.last_validators,
        )
        from ..types.block import AggregateCommit

        if isinstance(seen, AggregateCommit):
            # BLS lane: ONE certificate verification (a pairing) instead
            # of re-verifying N stored precommits
            if not last_precommits.absorb_certificate(seen):
                raise RuntimeError(
                    "stored aggregate seen-commit failed verification")
            if not last_precommits.has_two_thirds_majority():
                raise RuntimeError("reconstructed LastCommit lacks +2/3")
            self.rs.last_commit = last_precommits
            return
        votes = [v for v in seen.precommits if v is not None]
        # bulk path: ONE batched (TPU) verification for the whole commit.
        # add_votes applies per-item — a corrupt signature in the stored
        # commit must not discard the valid +2/3 riding with it; the
        # quorum check below is the authoritative gate.
        try:
            last_precommits.add_votes(votes)
        except ErrVoteInvalid as e:
            LOG.warning("reconstructing LastCommit: %s", e)
        if not last_precommits.has_two_thirds_majority():
            raise RuntimeError("reconstructed LastCommit lacks +2/3")
        self.rs.last_commit = last_precommits

    def _new_step(self) -> None:
        rs = self.get_round_state()
        self.event_bus.publish_new_round_step(rs)
        if self.on_new_round_step is not None:
            self.on_new_round_step(rs)

    @contextmanager
    def _step_span(self, span_name: str, step: str, height: int, round_: int):
        """Wraps the effective body of one step transition (after its
        height/round/step gate passed): a tracer span named after the
        reference transition (enterPropose, …) plus one sample in the
        consensus_step_duration_seconds{step=...} histogram. Both are
        no-ops until the node enables instrumentation."""
        sp = self.tracer.timed("consensus." + span_name, cat="consensus",
                               request=("block", height), height=height,
                               round=round_)
        try:
            with sp:
                yield
        finally:
            self.metrics.step_duration.with_labels(step).observe(sp.seconds)

    # --- the receive loop ---------------------------------------------------

    def _tock_forwarder(self) -> None:
        while not self._done.is_set():
            try:
                ti = self.ticker.tock_queue.get(timeout=0.1)
            except queue.Empty:
                continue
            self._queue.put(("timeout", ti))

    def _receive_routine(self) -> None:
        """Single-writer loop (reference receiveRoutine :561-622). All
        state mutation happens on this thread.

        Adaptive vote batching (SURVEY §7 "latency discipline"): when the
        head of the queue is a VoteMessage, the CONTIGUOUS run of queued
        VoteMessages behind it is drained and signature-verified as ONE
        BatchVerifier call before the per-vote state transitions run.
        Batch size is whatever accumulated while this thread was busy —
        zero added latency when idle (batch of 1 → serial CPU verify via
        the adaptive backend), device-sized batches exactly when vote
        traffic is heavy (catch-up peers, large valsets). Queue order is
        preserved: draining stops at the first non-vote message."""
        try:
            while not self._done.is_set():
                try:
                    item = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                try:
                    if item[0] == "msg" and isinstance(item[1][1], VoteMessage):
                        votes = [item[1]]
                        tail = None
                        while len(votes) < MAX_VOTE_BATCH:
                            try:
                                nxt = self._queue.get_nowait()
                            except queue.Empty:
                                break
                            if nxt[0] == "msg" and isinstance(nxt[1][1], VoteMessage):
                                votes.append(nxt[1])
                            else:
                                tail = nxt
                                break
                        # dispatch the batched signature verification
                        # BEFORE the WAL writes: the (fsync'd) write of
                        # the drained run overlaps the device round trip
                        finish = None
                        if len(votes) > 1:
                            finish = self._preverify_votes_begin(
                                [m.vote for _, m in votes])
                        try:
                            for peer_id, msg in votes:
                                if peer_id == "":
                                    self.wal.write_sync((peer_id, msg))  # :604-609
                                else:
                                    self.wal.write((peer_id, msg))
                            self._handle_vote_msgs(votes, finish)
                        finally:
                            # the tail was already dequeued — it must not
                            # be lost to a WAL or vote-handling exception
                            if tail is not None:
                                self._handle_item(tail)
                    elif item[0] == "msg" and isinstance(
                            item[1][1], HandelContributionMessage):
                        # same drain idiom for Handel contributions: a
                        # contiguous run becomes ONE multi-pair check in
                        # the session (bls.verify_aggregates_many)
                        run = [item[1][1]]
                        tail = None
                        while len(run) < MAX_VOTE_BATCH:
                            try:
                                nxt = self._queue.get_nowait()
                            except queue.Empty:
                                break
                            if nxt[0] == "msg" and isinstance(
                                    nxt[1][1], HandelContributionMessage):
                                run.append(nxt[1][1])
                            else:
                                tail = nxt
                                break
                        try:
                            with self._mutating():
                                self._add_handel_contributions(
                                    run, item[1][0])
                        finally:
                            if tail is not None:
                                self._handle_item(tail)
                    else:
                        self._handle_item(item)
                except Exception:
                    LOG.exception("error in consensus receive loop")
        finally:
            self._stopped.set()

    @contextmanager
    def _mutating(self):
        """Seqlock bracket around one receive-loop processing burst: any
        RoundState mutation inside is invisible to stamped readers
        until write_end. Re-entrant on the writer thread (the vote
        path's tail handling nests). The outermost exit publishes a
        fresh consistent snapshot for readers that lose the race."""
        import copy

        self._rs_stamp.write_begin()
        try:
            yield
        finally:
            self._rs_stamp.write_end()
            if not self._rs_stamp.is_writer():
                self._rs_published = (self._rs_stamp.gen,
                                      copy.copy(self.rs))

    def _handle_item(self, item) -> None:
        # the seqlock bracket covers ONLY the state transition, not the
        # WAL write (an fsync-scale stall inside the bracket would keep
        # the generation odd for milliseconds and starve every stamped
        # reader into torn-skip fallbacks — gossip ticks would mostly
        # no-op under load)
        kind, payload = item
        if kind == "msg":
            peer_id, msg = payload
            if isinstance(msg, HandelContributionMessage):
                # transient overlay traffic is never WAL'd: it is
                # re-derivable, and replaying pairing checks would slow
                # crash recovery for zero safety (the certificates it
                # yields re-enter through absorb_certificate's gates)
                with self._mutating():
                    self._handle_msg(msg, peer_id)
                return
            if peer_id == "":
                self.wal.write_sync((peer_id, msg))  # :604-609
            else:
                self.wal.write((peer_id, msg))
            with self._mutating():
                self._handle_msg(msg, peer_id)
        elif kind == "timeout":
            ti: TimeoutInfo = payload
            self.wal.write(ti)
            with self._mutating():
                self._handle_timeout(ti)

    def _handle_vote_msgs(self, items, finish=None) -> None:
        """Apply a drained run of VoteMessages: one batched signature
        verification (per-item masks), then the normal per-vote
        transition logic with the verify skipped for items that passed.
        `finish` is the callable returned by _preverify_votes_begin when
        the receive loop already dispatched the batch (to overlap the
        WAL write with the device round trip)."""
        if len(items) == 1:
            peer_id, msg = items[0]
            with self._mutating():
                self._try_add_vote(msg.vote, peer_id)
            return
        if finish is None:
            finish = self._preverify_votes_begin(
                [m.vote for _, m in items])
        # wait for the (device) verification OUTSIDE the bracket: the
        # round trip is milliseconds and mutates nothing — only the
        # tally/transition loop below needs tear protection
        mask = finish()
        with self._mutating():
            for (peer_id, msg), ok in zip(items, mask):
                self._try_add_vote(msg.vote, peer_id, verified=ok)

    def _preverify_votes(self, votes) -> List[bool]:
        """Batch-verify vote signatures against the SAME (valset, chain_id)
        the per-vote add path would use: rs.validators for the current
        height, the LastCommit's valset for late precommits. Votes that
        can't be mapped (wrong height/index/address) come back False and
        take the serial path's normal rejection."""
        return self._preverify_votes_begin(votes)()

    def _preverify_votes_begin(self, votes) -> Callable[[], List[bool]]:
        """Start batched signature verification for a drained vote run.
        The triples are collected synchronously — they read RoundState,
        which this (receive) thread owns — and the batch is dispatched
        async when [crypto] async_dispatch is on, so the caller can
        overlap the run's WAL writes with the device round trip. The
        returned callable blocks for and returns the per-vote mask."""
        from ..crypto import batch as crypto_batch

        triples, slots = self._collect_vote_triples(votes)
        n = len(votes)
        if not triples:
            return lambda: [False] * n

        def _map(mask) -> List[bool]:
            return [bool(mask[s]) if s is not None else False for s in slots]

        tracer = self.tracer
        height = self.rs.height
        if crypto_batch.async_enabled():
            bv = crypto_batch.new_batch_verifier()
            for t in triples:
                bv.add(*t)
            fut = bv.verify_async()

            def finish() -> List[bool]:
                with tracer.span("consensus.preverifyVotes", cat="consensus",
                                 request=("block", height), n=n,
                                 height=height):
                    return _map(fut.result())

            return finish

        def finish_sync() -> List[bool]:
            with tracer.span("consensus.preverifyVotes", cat="consensus",
                             request=("block", height), n=n, height=height):
                return _map(crypto_batch.batch_verify(triples))

        return finish_sync

    def _collect_vote_triples(self, votes):
        """Map each vote to its (sign_bytes, sig, pubkey) triple, or to
        no slot when it can't be mapped (wrong height/index/address)."""
        rs = self.rs
        chain_id = self.state.chain_id
        triples = []
        slots: List[Optional[int]] = []
        for vote in votes:
            val_set = None
            if vote.height == rs.height:
                val_set = rs.validators
            elif (
                vote.height + 1 == rs.height
                and rs.last_commit is not None
                and vote.type == VOTE_TYPE_PRECOMMIT
            ):
                val_set = rs.last_commit.val_set
            slot = None
            if (
                val_set is not None
                and 0 <= vote.validator_index < len(val_set)
                and vote.signature is not None
                and len(vote.signature) in (64, 96)  # ed25519 | bls12381
            ):
                addr, val = val_set.get_by_index(vote.validator_index)
                if addr == vote.validator_address:
                    slot = len(triples)
                    triples.append(
                        (vote.sign_bytes(chain_id), vote.signature, val.pub_key.bytes())
                    )
            slots.append(slot)
        return triples, slots

    def _handle_msg(self, msg, peer_id: str) -> None:
        """reference handleMsg :625-674"""
        if isinstance(msg, ProposalMessage):
            self.set_proposal_fn(msg.proposal)
            # mark AFTER set_proposal accepted it (signature verified,
            # height/round matched): a byzantine peer must not steal the
            # first-wins attribution with a garbage proposal, nor churn
            # the bounded timeline window with unvalidated heights.
            # "" peer_id = our own signed proposal.
            if self.rs.proposal is msg.proposal:
                self.timeline.mark(self.rs.height, "proposal_received",
                                   peer_id=peer_id,
                                   round_=msg.proposal.round)
        elif isinstance(msg, BlockPartMessage):
            self._add_proposal_block_part(msg, peer_id)
        elif isinstance(msg, VoteMessage):
            self._try_add_vote(msg.vote, peer_id)
        elif isinstance(msg, AggregateCommitMessage):
            self._add_aggregate_certificate(msg.commit, peer_id)
        elif isinstance(msg, HandelContributionMessage):
            self._add_handel_contributions([msg], peer_id)
        else:
            LOG.warning("unknown message type %s", type(msg))

    def _add_handel_contributions(self, msgs, peer_id: str) -> None:
        """Handel overlay receive lane: feed a drained run of level
        contributions into their sessions (one multi-pair aggregate
        check per run via bls.verify_aggregates_many) and route any
        quorum-crossing aggregate through the SAME
        _add_aggregate_certificate gate the flat gossip lane uses —
        absorb_certificate re-verifies it, so the overlay adds zero
        trust surface."""
        rs = self.rs
        if self.handel is None or rs.validators is None:
            return
        _, _, certs = self.handel.absorb(
            msgs, rs.validators, rs.height, time.monotonic())
        for cert in certs:
            # "" peer attribution: the certificate was assembled locally
            # from verified contributions, not received on the wire
            self._add_aggregate_certificate(cert, peer_id="")

    def _add_aggregate_certificate(self, cert, peer_id: str) -> None:
        """Handel-lite lane: merge a gossiped precommit certificate into
        the matching VoteSet (current height) or LastCommit (previous
        height). Verification and composability live in
        VoteSet.absorb_certificate; a merged certificate drives the
        same step transitions a 2/3-crossing precommit would."""
        rs = self.rs
        if cert is None:
            return
        if cert.agg_height == rs.height and rs.votes is not None:
            vs = rs.votes.precommits(cert.agg_round)
            if vs is None:
                return
            if vs.absorb_certificate(cert, peer_id=peer_id):
                self.metrics.agg_gossip_merges.inc()
                self.n_agg_merges += 1
                LOG.debug("absorbed aggregate certificate %s from %s",
                          cert, peer_id[:8] if peer_id else "self")
                self._on_precommit_progress(cert.agg_round)
        elif (cert.agg_height + 1 == rs.height
              and rs.last_commit is not None
              and cert.agg_round == rs.last_commit.round):
            if rs.last_commit.absorb_certificate(cert, peer_id=peer_id):
                self.metrics.agg_gossip_merges.inc()
                self.n_agg_merges += 1
                if self.config.skip_timeout_commit and rs.last_commit.has_all():
                    self._enter_new_round(rs.height, 0)

    def _handle_timeout(self, ti: TimeoutInfo) -> None:
        """reference handleTimeout :677-711"""
        rs = self.rs
        if ti.height != rs.height or ti.round < rs.round or (
            ti.round == rs.round and ti.step < rs.step
        ):
            return
        if ti.step == STEP_NEW_HEIGHT:
            self._enter_new_round(ti.height, 0)
        elif ti.step == STEP_NEW_ROUND:
            self._enter_propose(ti.height, 0)
        elif ti.step == STEP_PROPOSE:
            self.event_bus.publish_timeout_propose(self.get_round_state())
            self._enter_prevote(ti.height, ti.round)
        elif ti.step == STEP_PREVOTE_WAIT:
            self.event_bus.publish_timeout_wait(self.get_round_state())
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == STEP_PRECOMMIT_WAIT:
            self.event_bus.publish_timeout_wait(self.get_round_state())
            self._enter_precommit(ti.height, ti.round)
            self._enter_new_round(ti.height, ti.round + 1)
        else:
            raise RuntimeError(f"invalid timeout step {ti.step}")

    def _schedule_timeout(self, duration: float, height: int, round_: int, step: int) -> None:
        self.ticker.schedule_timeout(TimeoutInfo(duration, height, round_, step))

    def _schedule_round0(self, rs: RoundState) -> None:
        """reference scheduleRound0 :324-329"""
        sleep = max(0.0, rs.start_time - time.time())
        self._schedule_timeout(sleep, rs.height, 0, STEP_NEW_HEIGHT)

    # --- transitions --------------------------------------------------------

    def _enter_new_round(self, height: int, round_: int) -> None:
        """reference enterNewRound :730-794"""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step != STEP_NEW_HEIGHT
        ):
            return
        LOG.debug("enterNewRound(%d/%d) cur=%s", height, round_, rs)

        with self._step_span("enterNewRound", "new_round", height, round_):
            # round advance: rotate proposer (reference :747-753)
            validators = rs.validators
            if rs.round < round_:
                validators = validators.copy()
                validators.increment_proposer_priority(round_ - rs.round)

            if rs.round != round_:
                self._round_entered = time.time()
            # round-churn accounting: entry counts per (height, round)
            # let stitched fleet traces tell "extra rounds" apart from
            # "slow gossip" (first-wins marks alone cannot)
            self.timeline.mark_round(height, round_)
            rs.round = round_
            rs.step = STEP_NEW_ROUND
            rs.validators = validators
            if round_ != 0:
                # round 0 fields were set in update_to_state (reference :760-768)
                rs.proposal = None
                rs.proposal_block = None
                rs.proposal_block_parts = None
            rs.votes.set_round(round_ + 1)
            rs.triggered_timeout_precommit = False
            self.event_bus.publish_new_round(self.get_round_state())
            self._new_step()

            # WaitForTxs semantics (reference :775-792 + config.WaitForTxs):
            # with create_empty_blocks off (or paced by an interval), an empty
            # mempool waits — except when a proof block is needed (app hash
            # changed; needProofBlock :713-721)
            wait_for_txs = (
                (not self.config.create_empty_blocks or self.config.create_empty_blocks_interval > 0)
                and round_ == 0
                and self.mempool is not None
                and self.mempool.size() == 0
                and not self._need_proof_block(height)
            )
            if wait_for_txs:
                if self.config.create_empty_blocks_interval > 0:
                    self._schedule_timeout(
                        self.config.create_empty_blocks_interval, height, round_, STEP_NEW_ROUND
                    )
                self.mempool.notify_txs_available(
                    lambda: self._queue.put(("timeout", TimeoutInfo(0, height, round_, STEP_NEW_ROUND)))
                )
                return
        self._enter_propose(height, round_)

    def _need_proof_block(self, height: int) -> bool:
        """A block is needed even without txs when the app hash changed,
        to get the new hash signed (reference needProofBlock :713-721)."""
        if height == 1:
            return True
        last_meta = self.block_store.load_block_meta(height - 1)
        return last_meta is None or self.state.app_hash != last_meta.header.app_hash

    def _enter_propose(self, height: int, round_: int) -> None:
        """reference enterPropose :800-847"""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PROPOSE
        ):
            return
        LOG.debug("enterPropose(%d/%d)", height, round_)
        # if we already have the complete proposal, go straight to prevote
        # (guarded at the end, reference :812-820); the cascade runs
        # OUTSIDE the step span so 'propose' never includes prevote time
        try:
            with self._step_span("enterPropose", "propose", height, round_):
                rs.round = round_
                rs.step = STEP_PROPOSE
                self._new_step()

                self._schedule_timeout(self.config.propose(round_), height, round_, STEP_PROPOSE)

                if self.priv_validator is None:
                    return
                if not self.is_proposer():
                    return
                self.decide_proposal(height, round_)
        finally:
            if self._is_proposal_complete():
                self._enter_prevote(height, round_)

    def _default_decide_proposal(self, height: int, round_: int) -> None:
        """reference defaultDecideProposal :850-905; skipped during WAL
        replay (the original signed proposal is in the WAL)."""
        if self._replay_mode:
            return
        rs = self.rs
        if rs.valid_block is not None:
            # re-propose the valid block (the most recent polka winner;
            # a locked block is always also the valid block since locking
            # requires the complete proposal) — reference :855-858
            block, block_parts = rs.valid_block, rs.valid_block_parts
        else:
            made = self._create_proposal_block()
            if made is None:
                return
            block, block_parts = made

        # POLRound is OUR valid_round (reference :868 NewProposal(...,
        # cs.ValidRound, ...)), never a live polka query: a nil polka in
        # the CURRENT round would make pol_round == round, which every
        # honest node (including us) rejects as an invalid proposal.
        pol_round = rs.valid_round
        pol_block_id = (
            BlockID(hash=block.hash(), parts_header=block_parts.header())
            if pol_round >= 0 else BlockID()
        )
        proposal = Proposal(
            height=height,
            round=round_,
            block_parts_header=block_parts.header(),
            pol_round=pol_round,
            pol_block_id=pol_block_id,
            timestamp=now_ns(),
        )
        try:
            self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except Exception:
            LOG.exception("propose: failed to sign proposal")
            return
        # proposer-only mark: the signed proposal leaves for gossip HERE
        # — fleettrace's proposal_build/delivery boundary
        self.timeline.mark(height, "proposal_emit", round_=round_)
        self._send_internal(ProposalMessage(proposal))
        for i in range(block_parts.total()):
            self._send_internal(BlockPartMessage(height, round_, block_parts.get_part(i)))
        LOG.info("signed proposal %s", proposal)

    def _create_proposal_block(self):
        """reference createProposalBlock :907-940"""
        rs = self.rs
        if rs.height == 1:
            commit = Commit(block_id=BlockID(), precommits=[])
            commit_ok = True
        elif rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
            commit = rs.last_commit.make_commit()
            commit_ok = True
        else:
            commit_ok = False
        if not commit_ok:
            LOG.error("propose step; cannot propose without LastCommit")
            return None

        max_bytes = self.state.consensus_params.block_size.max_bytes
        max_gas = self.state.consensus_params.block_size.max_gas
        if self.mempool is not None:
            txs = self.mempool.reap_max_bytes_max_gas(max_bytes // 2, max_gas)
        else:
            txs = []
        evidence = self.evpool.pending_evidence() if self.evpool is not None else []
        proposer = self.priv_validator.get_address()
        from ..types.block import AggregateCommit

        if rs.height == 1:
            t = self.state.last_block_time  # genesis time (reference state.go:146)
        elif isinstance(commit, AggregateCommit):
            # BLS lane: no per-vote timestamps to take a median of — the
            # proposer's clock sets block time, clamped strictly past the
            # previous block (validators enforce monotonicity only)
            t = max(now_ns(),
                    self.state.last_block_time + self.config.blocktime_iota)
        else:
            t = sm_state.median_time(commit, self.state.last_validators)
        block = self.state.make_block(rs.height, txs, commit if rs.height > 1 else None, evidence, proposer, time_ns=t)
        if rs.height == 1:
            block.last_commit = None
        from ..types.block import make_part_set

        return block, make_part_set(block)

    def _is_proposal_complete(self) -> bool:
        """reference isProposalComplete :796-809"""
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def _enter_prevote(self, height: int, round_: int) -> None:
        """reference enterPrevote :942-975"""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PREVOTE
        ):
            return
        LOG.debug("enterPrevote(%d/%d)", height, round_)
        with self._step_span("enterPrevote", "prevote", height, round_):
            rs.round = round_
            rs.step = STEP_PREVOTE
            self._new_step()
            self.do_prevote(height, round_)

    def _default_do_prevote(self, height: int, round_: int) -> None:
        """reference defaultDoPrevote :977-995"""
        rs = self.rs
        if rs.locked_block is not None:
            self._speculate(rs.locked_block)
            self._sign_add_vote(VOTE_TYPE_PREVOTE, rs.locked_block.hash(), rs.locked_block_parts.header())
            return
        if rs.proposal_block is None:
            self._sign_add_vote(VOTE_TYPE_PREVOTE, b"", None)
            return
        try:
            self.block_exec.validate_block(self.state, rs.proposal_block)
        except Exception as e:
            LOG.warning("prevote: ProposalBlock is invalid: %s", e)
            self._sign_add_vote(VOTE_TYPE_PREVOTE, b"", None)
            return
        # the block we are about to prevote is the likely decision:
        # start executing it NOW on the speculation thread so commit
        # only finalizes already-computed state ([execution]
        # speculative; adopted at finalize only on exact block +
        # base-state match, discarded otherwise)
        self._speculate(rs.proposal_block)
        self._sign_add_vote(
            VOTE_TYPE_PREVOTE, rs.proposal_block.hash(), rs.proposal_block_parts.header()
        )

    def _speculate(self, block) -> None:
        if block is None or not self.block_exec.speculation_enabled:
            return
        try:
            self.block_exec.begin_speculation(self.state, block)
        except Exception:  # noqa: BLE001 - speculation must never stall a vote
            LOG.exception("begin_speculation failed (ignored)")

    def _enter_prevote_wait(self, height: int, round_: int) -> None:
        """reference enterPrevoteWait :997-1022"""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PREVOTE_WAIT
        ):
            return
        prevotes = rs.votes.prevotes(round_)
        if prevotes is None or not prevotes.has_two_thirds_any():
            raise RuntimeError("enter_prevote_wait without +2/3 prevotes (any)")
        LOG.debug("enterPrevoteWait(%d/%d)", height, round_)
        with self._step_span("enterPrevoteWait", "prevote_wait", height, round_):
            rs.round = round_
            rs.step = STEP_PREVOTE_WAIT
            self._new_step()
            self._schedule_timeout(self.config.prevote(round_), height, round_, STEP_PREVOTE_WAIT)

    def _enter_precommit(self, height: int, round_: int) -> None:
        """reference enterPrecommit :1025-1118 — the POL lock/unlock
        logic."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PRECOMMIT
        ):
            return
        LOG.debug("enterPrecommit(%d/%d)", height, round_)
        with self._step_span("enterPrecommit", "precommit", height, round_):
            rs.round = round_
            rs.step = STEP_PRECOMMIT
            self._new_step()

            prevotes = rs.votes.prevotes(round_)
            block_id = prevotes.two_thirds_majority() if prevotes else None

            # no polka: precommit nil (reference :1044-1052)
            if block_id is None:
                self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", None)
                return

            self.event_bus.publish_polka(self.get_round_state())

            # polka for nil: unlock if locked (reference :1061-1075)
            if not block_id.hash:
                if rs.locked_block is not None:
                    rs.locked_round = -1
                    rs.locked_block = None
                    rs.locked_block_parts = None
                    self.event_bus.publish_unlock(self.get_round_state())
                self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", None)
                return

            # polka for our locked block: re-lock (reference :1078-1086)
            if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
                rs.locked_round = round_
                self.event_bus.publish_relock(self.get_round_state())
                self._sign_add_vote(VOTE_TYPE_PRECOMMIT, block_id.hash, block_id.parts_header)
                return

            # polka for our proposal block: lock it (reference :1089-1103)
            if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
                try:
                    # decided=True: +2/3 already prevoted this block, so
                    # SUBJECTIVE proposal-time checks (the aggregate-lane
                    # clock-drift bound) must not be re-asserted — a
                    # clock-lagging validator that re-judged timeliness
                    # here would abstain from a polka'd block and lose
                    # its precommit every affected round
                    self.block_exec.validate_block(self.state, rs.proposal_block,
                                                   decided=True)
                except Exception as e:
                    raise RuntimeError(f"enter_precommit: +2/3 prevoted an invalid block: {e}")
                rs.locked_round = round_
                rs.locked_block = rs.proposal_block
                rs.locked_block_parts = rs.proposal_block_parts
                self.event_bus.publish_lock(self.get_round_state())
                self._sign_add_vote(VOTE_TYPE_PRECOMMIT, block_id.hash, block_id.parts_header)
                return

            # polka for a block we don't have: unlock, fetch (reference :1106-1116)
            rs.locked_round = -1
            rs.locked_block = None
            rs.locked_block_parts = None
            if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                block_id.parts_header
            ):
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet(block_id.parts_header)
            self.event_bus.publish_unlock(self.get_round_state())
            self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", None)

    def _enter_precommit_wait(self, height: int, round_: int) -> None:
        """reference enterPrecommitWait :1121-1146"""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.triggered_timeout_precommit
        ):
            return
        precommits = rs.votes.precommits(round_)
        if precommits is None or not precommits.has_two_thirds_any():
            raise RuntimeError("enter_precommit_wait without +2/3 precommits (any)")
        LOG.debug("enterPrecommitWait(%d/%d)", height, round_)
        with self._step_span("enterPrecommitWait", "precommit_wait", height, round_):
            rs.triggered_timeout_precommit = True
            self._new_step()
            self._schedule_timeout(self.config.precommit(round_), height, round_, STEP_PRECOMMIT_WAIT)

    def _enter_commit(self, height: int, commit_round: int) -> None:
        """reference enterCommit :1149-1198"""
        rs = self.rs
        if rs.height != height or rs.step >= STEP_COMMIT:
            return
        LOG.debug("enterCommit(%d/%d)", height, commit_round)
        self.timeline.mark(height, "commit", round_=commit_round)
        try:
            with self._step_span("enterCommit", "commit", height, commit_round):
                rs.step = STEP_COMMIT
                rs.commit_round = commit_round
                rs.commit_time = time.time()

                block_id = rs.votes.precommits(commit_round).two_thirds_majority()
                if block_id is None:
                    raise RuntimeError("enter_commit without +2/3 precommit majority")
                # our locked block IS the committed block (reference :1168-1174)
                if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
                    rs.proposal_block = rs.locked_block
                    rs.proposal_block_parts = rs.locked_block_parts
                if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
                    if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                        block_id.parts_header
                    ):
                        # need to fetch the committed block (reference :1180-1190)
                        rs.proposal_block = None
                        rs.proposal_block_parts = PartSet(block_id.parts_header)
        finally:
            # the reference runs newStep in a defer (:1152-1160), i.e.
            # AFTER ProposalBlockParts is set — the step event carries the
            # parts header the reactor's CommitStepMessage advertises; an
            # event fired before the parts are set would deadlock catch-up.
            # Both run OUTSIDE the step span so 'commit' never includes
            # finalize_commit time (that has its own histogram label).
            self._new_step()
            self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        """reference tryFinalizeCommit :1201-1222"""
        rs = self.rs
        if rs.height != height:
            raise RuntimeError("try_finalize_commit wrong height")
        block_id = rs.votes.precommits(rs.commit_round).two_thirds_majority()
        if block_id is None or not block_id.hash:
            return
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            return  # don't have the block yet
        self._finalize_commit(height)

    def _finalize_commit(self, height: int) -> None:
        """reference finalizeCommit :1225-1318 — the fsync-ordered commit
        sequence with fail points."""
        rs = self.rs
        if rs.height != height or rs.step != STEP_COMMIT:
            return
        with self._step_span("finalizeCommit", "finalize_commit", height, rs.commit_round):
            block_id = rs.votes.precommits(rs.commit_round).two_thirds_majority()
            block, block_parts = rs.proposal_block, rs.proposal_block_parts
            if block is None or block.hash() != block_id.hash:
                raise RuntimeError("cannot finalize: no proposal block / hash mismatch")

            # 2/3 already precommitted this block — it is decided, so
            # proposal-time-only checks (agg clock drift) don't apply
            self.block_exec.validate_block(self.state, block, decided=True)  # :1243

            LOG.info(
                "finalizing commit of block h=%d hash=%s txs=%d",
                block.header.height,
                (block.hash() or b"").hex()[:12],
                len(block.data.txs),
            )

            fail.fail_point("FinalizeCommit.BeforeSave")  # :1251
            if self.block_store.height() < block.header.height:
                seen_commit = rs.votes.precommits(rs.commit_round).make_commit()
                from ..types.block import AggregateCommit

                if isinstance(seen_commit, AggregateCommit):
                    self.last_agg_cert_bytes = seen_commit.size_bytes()
                    from ..crypto import batch as crypto_batch

                    cm = crypto_batch.get_metrics()
                    if cm is not None:
                        cm.agg_commit_size_bytes.set(self.last_agg_cert_bytes)
                self.block_store.save_block(block, block_parts, seen_commit)  # :1254-1259
            fail.fail_point("FinalizeCommit.AfterSave")  # :1265

            # WAL EndHeight BEFORE ApplyBlock: on crash we replay from here and
            # the handshake re-applies the block to the app (reference :1271-1285)
            _t_wal = time.perf_counter()
            self.wal.write_end_height(height)
            _sp = getattr(self.block_exec, "stage_profile", None)
            if _sp is not None:  # stub executors in tests have none
                _sp.observe("wal", time.perf_counter() - _t_wal)
            self.timeline.mark(height, "wal_fsync", round_=rs.commit_round)
            fail.fail_point("FinalizeCommit.AfterWAL")  # :1282

            state_copy = self.state.copy()
            try:
                state_copy = self.block_exec.apply_block(
                    state_copy, BlockID(block.hash(), block_parts.header()), block
                )
            except Exception:
                LOG.exception("failed to apply block; exiting consensus")
                raise
            self.timeline.mark(height, "apply_block", round_=rs.commit_round)
            fail.fail_point("FinalizeCommit.AfterApplyBlock")  # :1300

            self.n_height_committed += 1
            if self.incidents is not None:
                self.incidents.note_commit(height)
            self._record_metrics(block, block_parts)
            self.update_to_state(state_copy)  # :1306
            self._schedule_round0(self.rs)  # :1312

    def _record_metrics(self, block, block_parts) -> None:
        """reference consensus/state.go recordMetrics:1320-1350."""
        m = self.metrics
        m.height.set(block.header.height)
        m.committed_height.set(block.header.height)
        m.rounds.set(self.rs.round)
        if self.rs.validators is not None:
            m.validators.set(len(self.rs.validators))
            m.validators_power.set(self.rs.validators.total_voting_power())
        if block.last_commit is not None:
            from ..types.block import AggregateCommit

            if isinstance(block.last_commit, AggregateCommit):
                m.missing_validators.set(block.last_commit.num_absent())
            else:
                m.missing_validators.set(
                    sum(1 for v in block.last_commit.precommits if v is None))
        m.byzantine_validators.set(len(block.evidence.evidence))
        m.num_txs.set(len(block.data.txs))
        m.total_txs.add(len(block.data.txs))
        # the part set already holds the encoded block — no re-encode
        m.block_size_bytes.set(sum(
            len(block_parts.get_part(i).bytes)
            for i in range(block_parts.total())
            if block_parts.get_part(i) is not None))
        prev = self.block_store.load_block_meta(block.header.height - 1)
        if prev is not None:
            m.block_interval_seconds.observe(
                max(block.header.time - prev.header.time, 0) / 1e9)

    # --- proposal handling --------------------------------------------------

    def _default_set_proposal(self, proposal: Proposal) -> None:
        """reference defaultSetProposal :1324-1357"""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or (
            proposal.pol_round >= 0 and proposal.pol_round >= proposal.round
        ):
            raise ErrVoteInvalid("invalid proposal POL round")
        proposer = rs.validators.get_proposer()
        if not proposer.pub_key.verify_bytes(
            proposal.sign_bytes(self.state.chain_id), proposal.signature
        ):
            raise ErrVoteInvalid("invalid proposal signature")
        rs.proposal = proposal
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet(proposal.block_parts_header)
        LOG.info("received proposal %s", proposal)

    def _add_proposal_block_part(self, msg: BlockPartMessage, peer_id: str) -> bool:
        """reference addProposalBlockPart :1361-1462"""
        rs = self.rs
        if msg.height != rs.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        try:
            added = rs.proposal_block_parts.add_part(msg.part)
        except ValueError as e:
            # a part whose proof fails against OUR current parts header
            # is usually not malice: gossip for the previous round's
            # proposal racing our round change lands here (the sender's
            # view of our round was a beat stale). Reject the part,
            # keep the peer and the receive loop.
            LOG.debug("rejecting block part h=%d r=%d from %s: %s",
                      msg.height, msg.round, peer_id[:8] or "self", e)
            return False
        if not added:
            return False
        if rs.proposal_block_parts.is_complete():
            from ..types import serde

            rs.proposal_block = serde.decode_block(rs.proposal_block_parts.assemble())
            LOG.info("received complete proposal block %s", rs.proposal_block)
            self.event_bus.publish_complete_proposal(self.get_round_state())

            prevotes = rs.votes.prevotes(rs.round)
            block_id = prevotes.two_thirds_majority() if prevotes else None
            if block_id is not None and block_id.hash and rs.valid_round < rs.round:
                if rs.proposal_block.hash() == block_id.hash:
                    rs.valid_round = rs.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts

            if rs.step <= STEP_PROPOSE and self._is_proposal_complete():
                self._enter_prevote(rs.height, rs.round)
            elif rs.step == STEP_COMMIT:
                self._try_finalize_commit(rs.height)
        return True

    # --- vote handling ------------------------------------------------------

    def _try_add_vote(self, vote: Vote, peer_id: str, verified: bool = False) -> bool:
        """reference tryAddVote :1468-1493 — conflicting votes become
        evidence. verified=True: signature already checked by the batched
        pre-verification in _handle_vote_msgs."""
        try:
            return self._add_vote(vote, peer_id, verified=verified)
        except ErrVoteConflictingVotes as e:
            if self.priv_validator is not None and vote.validator_address == self.priv_validator.get_address():
                LOG.error("found conflicting vote from ourselves: %s", vote)
                return False
            if self.evpool is not None:
                from ..types.evidence import DuplicateVoteEvidence

                _, val = self.rs.validators.get_by_address(vote.validator_address)
                if val is not None:
                    self.evpool.add_evidence(
                        DuplicateVoteEvidence(val.pub_key, e.vote_a, e.vote_b)
                    )
            return False
        except ErrVoteInvalid as e:
            LOG.warning("invalid vote from %s: %s", peer_id or "self", e)
            return False

    def _add_vote(self, vote: Vote, peer_id: str, verified: bool = False) -> bool:
        """reference addVote :1495-1639"""
        rs = self.rs

        # late precommit for the previous height (reference :1504-1527)
        if vote.height + 1 == rs.height:
            if not (vote.type == VOTE_TYPE_PRECOMMIT and rs.step == STEP_NEW_HEIGHT and rs.last_commit is not None):
                return False
            added = rs.last_commit.add_vote(vote, verified=verified)
            if added:
                LOG.debug("added late precommit to last commit: %s", rs.last_commit)
                self.timeline.mark_vote(vote.height, "precommit",
                                        vote.validator_index, peer_id,
                                        round_=vote.round)
                self.event_bus.publish_vote(vote)
                if self.on_vote_added is not None:
                    self.on_vote_added(vote)
                if self.config.skip_timeout_commit and rs.last_commit.has_all():
                    self._enter_new_round(rs.height, 0)
            return added

        if vote.height != rs.height:
            LOG.debug("vote ignored: wrong height %d vs %d", vote.height, rs.height)
            return False

        added = rs.votes.add_vote(vote, peer_id, verified=verified)
        if not added:
            return False
        self.timeline.mark_vote(
            vote.height,
            "prevote" if vote.type == VOTE_TYPE_PREVOTE else "precommit",
            vote.validator_index, peer_id, round_=vote.round)
        self.event_bus.publish_vote(vote)
        if self.on_vote_added is not None:
            self.on_vote_added(vote)

        if vote.type == VOTE_TYPE_PREVOTE:
            self._on_prevote_added(vote)
        elif vote.type == VOTE_TYPE_PRECOMMIT:
            self._on_precommit_added(vote)
        return True

    def _on_prevote_added(self, vote: Vote) -> None:
        """reference addVote prevote branch :1539-1601"""
        rs = self.rs
        prevotes = rs.votes.prevotes(vote.round)
        block_id = prevotes.two_thirds_majority()

        if block_id is not None:
            self.timeline.mark(rs.height, "prevote_23", peer_id="",
                               round_=vote.round)
            # unlock on newer polka (reference :1547-1558)
            if (
                rs.locked_block is not None
                and rs.locked_round < vote.round
                and vote.round <= rs.round
                and rs.locked_block.hash() != block_id.hash
            ):
                LOG.info("unlocking because of POL at round %d", vote.round)
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self.event_bus.publish_unlock(self.get_round_state())
            # valid-block update (reference :1561-1581)
            if block_id.hash and rs.valid_round < vote.round and vote.round == rs.round:
                if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
                    rs.valid_round = vote.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
                else:
                    rs.proposal_block = None
                if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                    block_id.parts_header
                ):
                    rs.proposal_block_parts = PartSet(block_id.parts_header)

        # step transitions (reference :1585-1601)
        if rs.round < vote.round and prevotes.has_two_thirds_any():
            self._enter_new_round(rs.height, vote.round)
        elif rs.round == vote.round and rs.step >= STEP_PREVOTE:
            if block_id is not None and (self._is_proposal_complete() or not block_id.hash):
                self._enter_precommit(rs.height, vote.round)
            elif prevotes.has_two_thirds_any():
                self._enter_prevote_wait(rs.height, vote.round)
        elif rs.proposal is not None and 0 <= rs.proposal.pol_round == vote.round:
            if self._is_proposal_complete():
                self._enter_prevote(rs.height, rs.round)

    def _on_precommit_added(self, vote: Vote) -> None:
        """reference addVote precommit branch :1603-1632"""
        self._on_precommit_progress(vote.round)

    def _on_precommit_progress(self, round_: int) -> None:
        """Shared precommit-quorum transitions: driven by a single added
        vote OR a merged aggregate certificate (the Handel-lite lane) —
        both can cross 2/3 for the round."""
        rs = self.rs
        precommits = rs.votes.precommits(round_)
        block_id = precommits.two_thirds_majority()
        if block_id is not None:
            self.timeline.mark(rs.height, "precommit_23", peer_id="",
                               round_=round_)
            self._enter_new_round(rs.height, round_)
            self._enter_precommit(rs.height, round_)
            if block_id.hash:
                self._enter_commit(rs.height, round_)
                if self.config.skip_timeout_commit and precommits.has_all():
                    self._enter_new_round(rs.height, 0)
            else:
                self._enter_precommit_wait(rs.height, round_)
        elif rs.round <= round_ and precommits.has_two_thirds_any():
            self._enter_new_round(rs.height, round_)
            self._enter_precommit_wait(rs.height, round_)

    # --- vote signing -------------------------------------------------------

    def _sign_vote(self, type_: int, hash_: bytes, header) -> Vote:
        """reference signVote :1641-1668"""
        rs = self.rs
        addr = self.priv_validator.get_address()
        idx, _ = rs.validators.get_by_address(addr)
        from ..types.basic import PartSetHeader

        vote = Vote(
            validator_address=addr,
            validator_index=idx,
            height=rs.height,
            round=rs.round,
            timestamp=self._vote_time(),
            type=type_,
            block_id=BlockID(hash_, header or PartSetHeader()),
        )
        self.priv_validator.sign_vote(self.state.chain_id, vote)
        return vote

    def _vote_time(self) -> int:
        """Vote time must exceed the voted block's time by iota, so the
        next block's median commit time is strictly increasing (reference
        voteTime :1658-1673).

        BLS fast lane: votes carry timestamp 0 — aggregation requires
        every precommit for (height, round, block_id) to sign IDENTICAL
        bytes, and the timestamp is the only per-validator field. Block
        time then comes from the proposer's clock under a strict
        monotonicity rule (PARITY_DEVIATIONS.md)."""
        rs = self.rs
        if rs.validators is not None and rs.validators.is_bls():
            return 0
        now = now_ns()
        min_t = now
        if rs.locked_block is not None:
            min_t = rs.locked_block.header.time + self.config.blocktime_iota
        elif rs.proposal_block is not None:
            min_t = rs.proposal_block.header.time + self.config.blocktime_iota
        return max(now, min_t)

    def _sign_add_vote(self, type_: int, hash_: bytes, header) -> Optional[Vote]:
        """reference signAddVote :1676-1690. Signing happens during WAL
        replay too: the privval double-sign filter makes a re-sign of an
        already-WAL'd vote idempotent (same timestamp restored), and a
        vote that was never signed before the crash — e.g. killed between
        completing the proposal and prevoting — gets signed now, which is
        what un-sticks the height after replay. Sign errors are expected
        in replay (privval may be ahead) and only logged live."""
        rs = self.rs
        if self.priv_validator is None:
            return None
        idx, _ = rs.validators.get_by_address(self.priv_validator.get_address())
        if idx < 0:
            return None  # not a validator
        try:
            vote = self._sign_vote(type_, hash_, header)
        except Exception:
            if not self._replay_mode:
                LOG.exception("failed signing %s vote", "prevote" if type_ == VOTE_TYPE_PREVOTE else "precommit")
            return None
        self._send_internal(VoteMessage(vote))
        if (self.handel is not None and type_ == VOTE_TYPE_PRECOMMIT
                and hash_ != b"" and not self._replay_mode):
            # seed the Handel session with our own precommit — level 1
            # starts offering it on the next reactor tick
            try:
                self.handel.note_own_precommit(vote, rs.validators)
            except Exception:  # noqa: BLE001 - overlay must not kill voting
                LOG.exception("handel: seeding own precommit failed")
        LOG.debug("signed and queued vote %s", vote)
        return vote

    # --- stall diagnostics --------------------------------------------------

    def round_dwell_seconds(self) -> float:
        """Wall seconds since the machine entered the current
        (height, round) — the watchdog's primary signal."""
        return max(0.0, time.time() - self._round_entered)

    def height_dwell_seconds(self) -> float:
        """Wall seconds since the machine entered the current HEIGHT —
        the partition/churn signal: round churn (propose timeout →
        nil prevotes → next round) keeps every per-round dwell short
        while the height itself goes nowhere."""
        return max(0.0, time.time() - self._height_entered)

    def handel_status(self) -> dict:
        """Handel overlay view for /debug/handel and stall_snapshot —
        {"enabled": False} when the overlay is off so the route surface
        is identical either way."""
        if self.handel is None:
            return {"enabled": False}
        try:
            return self.handel.status(time.monotonic())
        except Exception:  # noqa: BLE001 - diagnostics must not raise
            LOG.exception("handel status failed")
            return {"enabled": True, "error": "status failed"}

    def stall_snapshot(self, switch=None, reason: str = "",
                       dwell_s: float = 0.0) -> dict:
        """Structured diagnostic bundle for the current round: RoundState
        summary, vote bit arrays, the validators we're missing votes
        from, per-peer PeerState, and the crypto engine's in-flight
        batch count. Read-only over shallow snapshots, so it is safe to
        call from the watchdog thread while the receive loop runs."""
        from ..crypto import batch as crypto_batch

        rs = self.get_round_state()
        out = {
            "reason": reason,
            "dwell_s": round(dwell_s, 3),
            "time": time.time(),
            "round_state": {
                "height": rs.height,
                "round": rs.round,
                "step": RoundStepType.name(rs.step),
                "start_time": rs.start_time,
                "have_proposal": rs.proposal is not None,
                "have_proposal_block": rs.proposal_block is not None,
                "locked_round": rs.locked_round,
                "valid_round": rs.valid_round,
            },
            "votes": {},
            "n_validators": (len(rs.validators)
                             if rs.validators is not None else 0),
            "missing_validators": [],
            "peers": [],
            "inflight_verify_batches": crypto_batch.inflight_count(),
            # BLS aggregate fast lane: whether this chain runs it, how
            # many gossiped certificates merged, and the last persisted
            # certificate's wire size (monitor surfaces these)
            "agg": {
                "enabled": bool(rs.validators is not None
                                and rs.validators.is_bls()),
                "gossip_merges": self.n_agg_merges,
                "last_cert_bytes": self.last_agg_cert_bytes,
            },
            "handel": self.handel_status(),
        }
        try:
            if rs.votes is not None and rs.validators is not None:
                n_vals = len(rs.validators)
                missing: set = set()
                for name, vs in (("prevotes", rs.votes.prevotes(rs.round)),
                                 ("precommits", rs.votes.precommits(rs.round))):
                    if vs is None:
                        continue
                    ba = vs.bit_array()
                    out["votes"][name] = {
                        "bits": _bits_str(ba),
                        "have": ba.num_true(),
                        "total": n_vals,
                    }
                    missing.update(
                        i for i in range(n_vals) if not ba.get_index(i))
                for i in sorted(missing):
                    addr, _ = rs.validators.get_by_index(i)
                    out["missing_validators"].append(
                        {"index": i, "address": (addr or b"").hex()})
        except Exception:  # noqa: BLE001 - diagnostics must not raise
            LOG.exception("stall snapshot: vote section failed")
        if switch is not None:
            try:
                out["peers"] = _peer_states_json(switch, rs.height)
            except Exception:  # noqa: BLE001
                LOG.exception("stall snapshot: peer section failed")
        return out

    # --- WAL catchup replay -------------------------------------------------

    def _catchup_replay(self, height: int) -> None:
        """Replay WAL messages for `height` after a crash (reference
        catchupReplay :97-155)."""
        msgs = self.wal.search_for_end_height(height - 1)
        if msgs is None:
            if height == 1:
                return
            LOG.info("no WAL data for height %d; relying on handshake", height)
            return
        self._replay_mode = True
        try:
            for m in msgs:
                with self._mutating():
                    self._replay_one(m)
            LOG.info("WAL replay for height %d done: %d messages", height, len(msgs))
        finally:
            self._replay_mode = False

    def _replay_one(self, msg) -> None:
        if isinstance(msg, EndHeightMessage):
            return
        if isinstance(msg, TimedWALMessage):
            msg = msg.msg
        if isinstance(msg, TimeoutInfo):
            self._handle_timeout(msg)
        elif isinstance(msg, tuple):
            peer_id, m = msg
            try:
                self._handle_msg(m, peer_id)
            except Exception:
                LOG.exception("error replaying WAL message")


# --- stall watchdog ---------------------------------------------------------


def _bits_str(ba) -> str:
    """BitArray as a compact '1011…' string for diagnostic bundles."""
    if ba is None:
        return ""
    return "".join("1" if ba.get_index(i) else "0" for i in range(ba.bits))


# a peer that delivered no packet for this long is silent: either gone,
# or the far side of a partition whose writes never reach us. Live
# consensus peers gossip steps/votes many times a second, so anything
# healthy sits far under it; a freshly (re)dialed connection counts as
# silent until its first packet lands — a redial straight into a
# partition (the handshake rides the raw socket, only post-upgrade
# traffic hits the fault rules) must not look reachable. Partition
# classification scales this with the watchdog threshold (a stalled
# production round legitimately goes seconds between messages); this
# default serves the /debug payload's per-peer view.
PEER_SILENT_AFTER_S = 3.0


def _peer_is_silent(peer, after_s: float = PEER_SILENT_AFTER_S) -> bool:
    try:
        last = peer.mconn.last_recv_time
    except Exception:  # noqa: BLE001 - diagnostics never raise
        return True
    return last == 0.0 or time.monotonic() - last >= after_s


def _reachable_peer_count(switch,
                          after_s: float = PEER_SILENT_AFTER_S) -> int:
    """Peers we are actually HEARING from — the quorum-reachability
    input for partition classification."""
    return sum(1 for p in switch.peers.list()
               if not _peer_is_silent(p, after_s))


def _peer_states_json(switch, our_height: int) -> List[dict]:
    """Per-peer consensus PeerState summaries (heights, steps, vote bit
    arrays, lag vs our height) for /debug/consensus and the monitor."""
    peers = []
    for p in switch.peers.list():
        ps = p.get("consensus_peer_state")
        entry = {"peer_id": p.id, "moniker": p.node_info.moniker,
                 "silent": _peer_is_silent(p)}
        if ps is not None:
            prs = ps.get_round_state()
            entry.update({
                "height": prs.height,
                "round": prs.round,
                "step": prs.step,
                "prevotes": _bits_str(prs.prevotes),
                "precommits": _bits_str(prs.precommits),
                "lag_blocks": max(0, our_height - prs.height)
                if prs.height > 0 else 0,
            })
        peers.append(entry)
    return peers


def classify_stall(rs: RoundState, switch=None, state=None,
                   silent_after_s: float = PEER_SILENT_AFTER_S) -> str:
    """Map the stuck round's state to a coarse diagnosis, used as the
    consensus_stalls_total{reason} label (bounded cardinality).

    With network/chain context (the watchdog passes both), two sharper
    diagnoses outrank the generic missing-quorum labels:

    - partition_suspected: quorum is missing AND the peers we can still
      reach cannot possibly carry +2/3 even if every one of them were a
      distinct validator — count-based quorum-reachability, the netchaos
      partition signature.
    - valset_rotation: quorum is missing right after a validator-set
      change took effect (churn epoch) — votes may be aimed at (or
      coming from) a set the sender no longer agrees on.
    """
    if rs.step in (STEP_NEW_HEIGHT, STEP_NEW_ROUND):
        return "slow_round_start"
    if rs.step == STEP_PROPOSE and rs.proposal is None:
        base = "no_proposal"
    elif rs.step == STEP_PROPOSE:
        base = "incomplete_proposal"
    elif rs.step in (STEP_PREVOTE, STEP_PREVOTE_WAIT):
        base = "no_prevote_quorum"
    elif rs.step in (STEP_PRECOMMIT, STEP_PRECOMMIT_WAIT):
        base = "no_precommit_quorum"
    elif rs.step == STEP_COMMIT:
        base = "commit_not_finalized"
    else:
        return "unknown"
    quorum_missing = base in ("no_proposal", "no_prevote_quorum",
                              "no_precommit_quorum")
    if quorum_missing and rs.validators is not None:
        n_vals = len(rs.validators)
        # rotation FIRST: while a validator-set change is still taking
        # effect, missing quorum most likely reflects the churn itself,
        # and the count-based partition heuristic below is unreliable
        # (phantom/offline validators make every peer-count look like a
        # minority). rs.height > 1 guard: genesis state reports
        # last_height_validators_changed == 1, which is bootstrap.
        if (state is not None and rs.height > 1
                and state.last_height_validators_changed >= rs.height):
            return "valset_rotation"
        if switch is not None and n_vals > 1:
            # responsive peers + ourselves: even if every one were a
            # distinct validator, could they carry +2/3?
            reachable = _reachable_peer_count(switch, silent_after_s) + 1
            if 3 * reachable <= 2 * n_vals:
                return "partition_suspected"
    return base


class StallWatchdog:
    """Detects a consensus machine dwelling too long in one
    (height, round) and snapshots why (no reference equivalent; the
    reference leaves operators to diff dump_consensus_state by hand).

    A daemon thread samples ConsensusState.round_dwell_seconds() every
    `interval`, publishes it as consensus_round_dwell_seconds, and —
    once the dwell crosses `threshold_s` — increments
    consensus_stalls_total{reason} and captures a structured diagnostic
    bundle (RoundState, vote BitArrays, missing validators, per-peer
    PeerState, in-flight verify batches). One trip per (height, round):
    a round that stays stuck doesn't spam bundles. Bundles + a live
    snapshot are served at /debug/consensus on the ProfServer. on_tick
    callables run every sample — the node hooks per-peer gauge refresh
    (flow rates, queue depths, p2p_peer_lag_blocks) here so peer
    telemetry shares the watchdog's cadence."""

    def __init__(self, cs: ConsensusState, threshold_s: float = 30.0,
                 switch=None, interval: Optional[float] = None,
                 max_bundles: int = 8,
                 height_threshold_s: Optional[float] = None):
        self.cs = cs
        self.switch = switch
        self.threshold_s = threshold_s
        # height-level stall detection: a partition/churn fault churns
        # ROUNDS (each under threshold_s) while the HEIGHT goes nowhere;
        # default = 3x the round threshold, 0 disables
        if height_threshold_s is None:
            height_threshold_s = 3.0 * threshold_s if threshold_s > 0 else 0.0
        self.height_threshold_s = height_threshold_s
        if interval is None:
            interval = min(1.0, threshold_s / 4.0) if threshold_s > 0 else 1.0
        self.interval = max(0.05, interval)
        self.on_tick: List[Callable[[], None]] = []
        self._bundles: collections.deque = collections.deque(
            maxlen=max_bundles)
        self._stalls_total = 0
        self._flagged: Optional[tuple] = None
        self._flagged_height: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="cs-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self._tick()
            except Exception:  # noqa: BLE001 - watchdog must outlive bugs
                LOG.exception("stall watchdog tick failed")

    # -- sampling ------------------------------------------------------

    def _tick(self) -> None:
        dwell = self.cs.round_dwell_seconds()
        self.cs.metrics.round_dwell.set(dwell)
        for fn in self.on_tick:
            try:
                fn()
            except Exception:  # noqa: BLE001
                LOG.exception("watchdog on_tick hook failed")
        rs = self.cs.rs
        if self.threshold_s > 0 and dwell >= self.threshold_s:
            # one bundle per (height, round) — unless the DIAGNOSIS
            # shifts while the round stays stuck (e.g. a quorum stall
            # sharpening into partition_suspected once the cut-off
            # peers have been silent long enough): a changed reason
            # records again, a constant one never spams
            reason = self._classify(rs)
            key = (rs.height, rs.round, reason)
            if self._flagged != key:
                self._flagged = key
                self._trip(rs, dwell, "round", reason)
                return
        # height-level detection: rounds may churn under the per-round
        # threshold while the height dwells (partition signature)
        h_dwell = self.cs.height_dwell_seconds()
        if self.height_threshold_s > 0 and h_dwell >= self.height_threshold_s:
            reason = self._classify(rs)
            if self._flagged_height != (rs.height, reason):
                self._flagged_height = (rs.height, reason)
                self._trip(rs, h_dwell, "height", reason)

    def _classify(self, rs: RoundState) -> str:
        # silence cutoff tracks the threshold: a prod deployment's
        # stalled rounds legitimately go seconds between messages, a
        # fast-timeout test net goes milliseconds
        cutoff = max(1.0, min(PEER_SILENT_AFTER_S, self.threshold_s)) \
            if self.threshold_s > 0 else PEER_SILENT_AFTER_S
        return classify_stall(rs, switch=self.switch, state=self.cs.state,
                              silent_after_s=cutoff)

    def _trip(self, rs: RoundState, dwell: float, scope: str,
              reason: str) -> None:
        self.cs.metrics.stalls.with_labels(reason).inc()
        self._stalls_total += 1
        if self.cs.incidents is not None:
            self.cs.incidents.note_detection(
                reason, height=rs.height, round=rs.round,
                scope=scope, dwell_s=round(dwell, 3))
        bundle = self.cs.stall_snapshot(
            switch=self.switch, reason=reason, dwell_s=dwell)
        bundle["scope"] = scope  # which dwell crossed: round | height
        self._bundles.append(bundle)
        LOG.warning(
            "consensus stall (%s): h=%d r=%d dwelt %.1fs reason=%s",
            scope, rs.height, rs.round, dwell, reason)

    # -- export (/debug/consensus) -------------------------------------

    @property
    def stalls_total(self) -> int:
        return self._stalls_total

    def stall_bundles(self) -> List[dict]:
        return list(self._bundles)

    def status(self) -> dict:
        """The /debug/consensus payload: live diagnostics + the bundles
        captured at stall time."""
        dwell = self.cs.round_dwell_seconds()
        rs = self.cs.rs
        return {
            "height": rs.height,
            "round": rs.round,
            "step": RoundStepType.name(rs.step),
            "dwell_s": round(dwell, 3),
            "height_dwell_s": round(self.cs.height_dwell_seconds(), 3),
            "threshold_s": self.threshold_s,
            "height_threshold_s": self.height_threshold_s,
            "stalls_total": self._stalls_total,
            "stalls": list(self._bundles),
            "live": self.cs.stall_snapshot(
                switch=self.switch, reason="live", dwell_s=dwell),
        }


