"""ConsensusReactor — vote/proposal/block-part gossip.

Reference parity: consensus/reactor.go.  Four p2p channels
(State 0x20, Data 0x21, Vote 0x22, VoteSetBits 0x23, :23-26,125-157);
per-peer gossip threads (gossipDataRoutine :456, gossipVotesRoutine
:593, queryMaj23Routine :720); PeerState tracks what each peer has
(:895-1334) so gossip sends only what's missing.  Broadcasts of
NewRoundStep/HasVote ride the node event bus (the reference uses an
internal event switch, reactor.go:371-395).

Vote gossip is where the TPU batch-verify engine aggregates work: gossiped
votes are queued to the consensus receive loop, which drains each
contiguous run of queued VoteMessages and pre-verifies it as one
BatchVerifier call (consensus/state.py _handle_vote_msgs) — a catch-up
peer's vote stream therefore lands on the device in batches, not one
serial verify per message.
"""

from __future__ import annotations

import copy
import logging
import threading
import time
from typing import Dict, Optional

from ..libs.bit_array import BitArray
from ..metrics import ConsensusMetrics
from ..p2p.base_reactor import ChannelDescriptor, Reactor
from ..types import serde
from ..types.basic import BlockID, PartSetHeader
from ..types.basic import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE
from ..types.event_bus import (
    EVENT_NEW_ROUND_STEP,
    EVENT_VOTE,
    query_for_event,
)
from .cstypes import STEP_COMMIT, STEP_NEW_HEIGHT, STEP_PREVOTE_WAIT
from .messages import (
    AggregateCommitMessage,
    BlockPartMessage,
    CommitStepMessage,
    HandelContributionMessage,
    HasVoteMessage,
    NewRoundStepMessage,
    ProposalMessage,
    ProposalPOLMessage,
    VoteMessage,
    VoteSetBitsMessage,
    VoteSetMaj23Message,
    message_from_obj,
    message_to_obj,
)

LOG = logging.getLogger("consensus.reactor")

NOP_CONSENSUS = ConsensusMetrics()

STATE_CHANNEL = 0x20
DATA_CHANNEL = 0x21
VOTE_CHANNEL = 0x22
VOTE_SET_BITS_CHANNEL = 0x23
# Handel overlay contributions (consensus/handel.py). Advertised only
# when [handel] enable is set — with it off the channel vector, and
# therefore the p2p handshake, is byte-identical to a build without
# the overlay.
HANDEL_CHANNEL = 0x24

PEER_GOSSIP_SLEEP = 0.1  # reactor.go:36 peerGossipSleepDuration
PEER_QUERY_MAJ23_SLEEP = 2.0  # reactor.go:39

# flat certificate lane re-send gate (PR 19): a merged cert re-sends to
# a peer only after this interval, UNLESS it grew by at least
# _AGG_RESEND_DELTA signers since the last send — steady-state chatter
# collapses to one message per interval while real aggregation progress
# still propagates immediately
_AGG_RESEND_MIN_S = 0.25
_AGG_RESEND_DELTA = 8


def encode_msg(m) -> bytes:
    return serde.pack(message_to_obj(m))


def decode_msg(b: bytes):
    return message_from_obj(serde.unpack(b))


class PeerRoundState:
    """What we know the peer knows (reference cstypes/peer_round_state.go)."""

    def __init__(self):
        self.height = 0
        self.round = -1
        self.step = STEP_NEW_HEIGHT
        self.start_time = 0.0
        self.proposal = False
        self.proposal_block_parts_header: Optional[PartSetHeader] = None
        self.proposal_block_parts: Optional[BitArray] = None
        self.proposal_pol_round = -1
        self.proposal_pol: Optional[BitArray] = None
        self.prevotes: Optional[BitArray] = None
        self.precommits: Optional[BitArray] = None
        self.last_commit_round = -1
        self.last_commit: Optional[BitArray] = None
        self.catchup_commit_round = -1
        self.catchup_commit: Optional[BitArray] = None


class PeerState:
    """Thread-safe peer-knowledge tracker (reactor.go:895-1334)."""

    def __init__(self, peer):
        self.peer = peer
        self._lock = threading.RLock()
        self.prs = PeerRoundState()
        # gossip-mark self-healing bookkeeping: when the peer's HEIGHT
        # last advanced, and when we last expired our sent-marks for it
        # (see expire_gossip_marks_if_stalled)
        self.last_height_advance = time.monotonic()
        self._marks_expired_at = time.monotonic()
        # flat-lane cert re-send gate: (height, round) -> (sent_at,
        # num_signers at send) — see agg_cert_should_send
        self._agg_sent: Dict[tuple, tuple] = {}

    # -- queries -------------------------------------------------------

    def get_round_state(self) -> PeerRoundState:
        # a shallow COPY under the lock (reference GetRoundState,
        # reactor.go:921-927): gossip threads act on a consistent
        # (height, round, step) instead of racing the receive thread's
        # in-place updates field by field
        with self._lock:
            return copy.copy(self.prs)

    def get_height(self) -> int:
        with self._lock:
            return self.prs.height

    # -- updates from messages ----------------------------------------

    def apply_new_round_step(self, msg: NewRoundStepMessage) -> None:
        """reactor.go:1091-1137."""
        with self._lock:
            prs = self.prs
            # ignore duplicates or decreases (reference CompareHRS guard,
            # reactor.go:1096-1099): a stale/replayed round-step must not
            # regress our view of the peer and wipe its vote bit arrays
            if (msg.height, msg.round, msg.step) <= (prs.height, prs.round, prs.step):
                return
            ps_height, ps_round = prs.height, prs.round
            ps_catchup_round = prs.catchup_commit_round
            ps_catchup_commit = prs.catchup_commit
            # snapshot BEFORE the wipe below: v0.27's reactor.go:1131
            # reads Precommits after nil-ing it, losing the peer's
            # last-commit knowledge on every height transition (fixed in
            # later upstream); we keep the fixed semantics — the bits are
            # genuine peer knowledge and skipping them avoids re-sending
            # every precommit the peer already has
            ps_precommits = prs.precommits

            if ps_height != msg.height:
                self.last_height_advance = time.monotonic()
            prs.height = msg.height
            prs.round = msg.round
            prs.step = msg.step
            prs.start_time = time.time() - msg.seconds_since_start_time
            if ps_height != msg.height or ps_round != msg.round:
                prs.proposal = False
                prs.proposal_block_parts_header = None
                prs.proposal_block_parts = None
                prs.proposal_pol_round = -1
                prs.proposal_pol = None
                prs.prevotes = None
                prs.precommits = None
            if ps_height == msg.height and ps_round != msg.round and msg.round == ps_catchup_round:
                prs.precommits = ps_catchup_commit
            if ps_height != msg.height:
                # peer moved a height: shift precommits to last_commit
                if ps_height + 1 == msg.height and ps_round == msg.last_commit_round:
                    prs.last_commit_round = msg.last_commit_round
                    prs.last_commit = ps_precommits
                else:
                    prs.last_commit_round = msg.last_commit_round
                    prs.last_commit = None
                prs.catchup_commit_round = -1
                prs.catchup_commit = None

    def apply_commit_step(self, msg: CommitStepMessage) -> None:
        with self._lock:
            if self.prs.height != msg.height:
                return
            self.prs.proposal_block_parts_header = msg.block_parts_header
            self.prs.proposal_block_parts = msg.block_parts

    def apply_proposal_pol(self, msg: ProposalPOLMessage) -> None:
        with self._lock:
            prs = self.prs
            if prs.height != msg.height or prs.proposal_pol_round != msg.proposal_pol_round:
                return
            prs.proposal_pol = msg.proposal_pol

    def apply_has_vote(self, msg: HasVoteMessage) -> None:
        with self._lock:
            if self.prs.height != msg.height:
                return
            self._set_has_vote_locked(msg.height, msg.round, msg.type, msg.index)

    def apply_vote_set_bits(self, msg: VoteSetBitsMessage, our_votes: Optional[BitArray]) -> None:
        """reactor.go:1319-1334: if we have our_votes, the peer's claim is
        OR'd with what we already track (union of knowledge)."""
        with self._lock:
            votes = self._get_vote_bit_array_locked(msg.height, msg.round, msg.type)
            if votes is not None and our_votes is not None:
                have = votes.or_(msg.votes)
                self._set_vote_bit_array_locked(msg.height, msg.round, msg.type, have)
            else:
                self._set_vote_bit_array_locked(msg.height, msg.round, msg.type, msg.votes)

    def set_has_proposal(self, proposal) -> None:
        with self._lock:
            prs = self.prs
            if prs.height != proposal.height or prs.round != proposal.round or prs.proposal:
                return
            prs.proposal = True
            prs.proposal_block_parts_header = proposal.block_parts_header
            if prs.proposal_block_parts is None:
                prs.proposal_block_parts = BitArray(proposal.block_parts_header.total)
            prs.proposal_pol_round = proposal.pol_round
            prs.proposal_pol = None

    def set_has_proposal_block_part(self, height: int, round_: int, index: int) -> None:
        with self._lock:
            prs = self.prs
            if prs.height != height or prs.round != round_:
                return
            if prs.proposal_block_parts is not None:
                prs.proposal_block_parts.set_index(index, True)

    def set_has_vote(self, vote) -> None:
        with self._lock:
            self._set_has_vote_locked(
                vote.height, vote.round, vote.type, vote.validator_index
            )

    def apply_agg_commit(self, cert) -> None:
        """Mark every signer bit of an aggregate precommit certificate
        as known to the peer (sent to it, or received from it) — the
        BLS lane's bulk set_has_vote."""
        with self._lock:
            ba = self._get_vote_bit_array_locked(
                cert.agg_height, cert.agg_round, VOTE_TYPE_PRECOMMIT)
            if ba is None:
                return
            # bulk OR: at mega-committee sizes a per-bit set_index loop
            # is size() lock round-trips per gossip send
            ba.or_update(cert.signers)

    def agg_cert_has_news(self, cert) -> bool:
        """Does the certificate cover any signer the peer isn't known to
        have? (Gossip guard: merged certificates re-send only while they
        still grow the peer's view.)"""
        with self._lock:
            ba = self._get_vote_bit_array_locked(
                cert.agg_height, cert.agg_round, VOTE_TYPE_PRECOMMIT)
            if ba is None:
                # no tracking slot for that (height, round) — stay quiet
                # rather than re-sending every gossip tick; the per-vote
                # path covers mismatched-round peers
                return False
            # any signer bit the peer lacks? — one bulk numpy op, not
            # 2×size() per-bit lock acquisitions per gossip tick
            return not cert.signers.sub(ba).is_empty()

    def agg_cert_should_send(self, cert, now: float,
                             min_s: float, delta: int) -> bool:
        """agg_cert_has_news PLUS the per-peer re-send gate: a growing
        certificate re-sends immediately once it gained `delta` signers,
        anything else waits out `min_s`. apply_agg_commit normally stops
        pure duplicates already — this bounds the chatter left when mark
        expiry (expire_gossip_marks_if_stalled) wipes the peer's bitmap
        during a stall and every tick would otherwise re-offer the same
        bytes."""
        if not self.agg_cert_has_news(cert):
            return False
        with self._lock:
            sent_at, sent_n = self._agg_sent.get(
                (cert.agg_height, cert.agg_round), (0.0, 0))
            n = cert.num_signers()
            return now - sent_at >= min_s or n - sent_n >= delta

    def note_agg_cert_sent(self, cert, now: float) -> None:
        with self._lock:
            self._agg_sent[(cert.agg_height, cert.agg_round)] = (
                now, cert.num_signers())
            if len(self._agg_sent) > 8:  # GC: committed heights
                for k in [k for k in self._agg_sent
                          if k[0] < cert.agg_height - 1]:
                    del self._agg_sent[k]

    def ensure_catchup_commit_round(self, height: int, round_: int, num_validators: int) -> None:
        """reactor.go:975-994."""
        with self._lock:
            prs = self.prs
            if prs.height != height:
                return
            if prs.catchup_commit_round == round_:
                return
            prs.catchup_commit_round = round_
            if round_ == prs.round:
                prs.catchup_commit = prs.precommits
            else:
                prs.catchup_commit = BitArray(num_validators)

    def expire_gossip_marks_if_stalled(self, stall_s: float,
                                       our_height: int = None) -> bool:
        """Self-healing under silent message loss (netchaos drops, lossy
        links, asymmetric partitions): gossip marks votes/parts as
        known-to-the-peer ON SEND, but a dropped send means the peer
        never got them — and with the TCP connection surviving the
        fault, nothing ever clears the poisoned marks, so after the
        fault both sides sit forever believing there is nothing left to
        send (the reference never hits this because TCP either delivers
        or kills the conn, which resets PeerState wholesale).

        When the peer's HEIGHT has not advanced for `stall_s`, wipe the
        knowledge marks so the gossip routines re-offer everything the
        peer might have missed; duplicates are cheap (dup-check + sig
        cache) and the wipe re-arms at most once per stall_s. A peer
        AHEAD of us is excluded via our_height: nothing we hold can
        unstick it, so wiping would only generate duplicate traffic."""
        with self._lock:
            now = time.monotonic()
            if our_height is not None and self.prs.height > our_height:
                return False
            if (now - self.last_height_advance < stall_s
                    or now - self._marks_expired_at < stall_s):
                return False
            self._marks_expired_at = now
            prs = self.prs
            prs.proposal = False
            if prs.proposal_block_parts is not None:
                prs.proposal_block_parts = BitArray(
                    prs.proposal_block_parts.bits)
            prs.proposal_pol = None
            prs.prevotes = None
            prs.precommits = None
            prs.last_commit = None
            # reset the catchup round too: ensure_catchup_commit_round
            # early-returns on a matching round and would otherwise
            # leave catchup_commit None forever
            prs.catchup_commit_round = -1
            prs.catchup_commit = None
            return True

    def ensure_vote_bit_arrays(self, height: int, num_validators: int) -> None:
        """reactor.go:996-1018."""
        with self._lock:
            prs = self.prs
            if prs.height == height:
                if prs.prevotes is None:
                    prs.prevotes = BitArray(num_validators)
                if prs.precommits is None:
                    prs.precommits = BitArray(num_validators)
                if prs.catchup_commit is None and prs.catchup_commit_round >= 0:
                    prs.catchup_commit = BitArray(num_validators)
                if prs.proposal_pol is None and prs.proposal_pol_round >= 0:
                    prs.proposal_pol = BitArray(num_validators)
            elif prs.height == height + 1:
                if prs.last_commit is None:
                    prs.last_commit = BitArray(num_validators)

    def has_vote_bit_arrays(self, height: int) -> bool:
        """Would ensure_vote_bit_arrays(height, ·) and (height - 1, ·)
        both leave the peer's state as it is? (The vote path skips them
        then: a height's votes after its first find every array made.)"""
        with self._lock:
            prs = self.prs
            return (prs.height == height and prs.prevotes is not None
                    and prs.precommits is not None
                    and prs.last_commit is not None
                    and (prs.catchup_commit is not None
                         or prs.catchup_commit_round < 0)
                    and (prs.proposal_pol is not None
                         or prs.proposal_pol_round < 0))

    # -- internals -----------------------------------------------------

    def _set_has_vote_locked(self, height: int, round_: int, type_: int, index: int) -> None:
        ba = self._get_vote_bit_array_locked(height, round_, type_)
        if ba is not None and index is not None and index >= 0:
            ba.set_index(index, True)

    def _get_vote_bit_array_locked(self, height: int, round_: int, type_: int) -> Optional[BitArray]:
        prs = self.prs
        if prs.height == height:
            if round_ == prs.round:
                return prs.prevotes if type_ == VOTE_TYPE_PREVOTE else prs.precommits
            if round_ == prs.catchup_commit_round and type_ == VOTE_TYPE_PRECOMMIT:
                return prs.catchup_commit
            if round_ == prs.proposal_pol_round and type_ == VOTE_TYPE_PREVOTE:
                return prs.proposal_pol
        elif prs.height == height + 1:
            if round_ == prs.last_commit_round and type_ == VOTE_TYPE_PRECOMMIT:
                return prs.last_commit
        return None

    def _set_vote_bit_array_locked(self, height, round_, type_, ba) -> None:
        prs = self.prs
        if prs.height == height:
            if round_ == prs.round:
                if type_ == VOTE_TYPE_PREVOTE:
                    prs.prevotes = ba
                else:
                    prs.precommits = ba
            elif round_ == prs.catchup_commit_round and type_ == VOTE_TYPE_PRECOMMIT:
                prs.catchup_commit = ba
            elif round_ == prs.proposal_pol_round and type_ == VOTE_TYPE_PREVOTE:
                prs.proposal_pol = ba
        elif prs.height == height + 1:
            if round_ == prs.last_commit_round and type_ == VOTE_TYPE_PRECOMMIT:
                prs.last_commit = ba

    def pick_vote_to_send(self, votes) -> Optional[object]:
        """Pick a random vote from `votes` (a VoteSet) that the peer
        lacks; marks it sent (reactor.go:1077-1089)."""
        if votes is None or votes.size() == 0:
            return None
        with self._lock:
            height, round_, type_ = votes.height, votes.round, votes.type
            self.ensure_vote_bit_arrays(height, len(votes.val_set))
            ps_votes = self._get_vote_bit_array_locked(height, round_, type_)
            if ps_votes is None:
                return None
            missing = votes.bit_array().sub(ps_votes)
            idx = missing.pick_random()
            if idx is None:
                return None
            vote = votes.get_by_index(idx)
            if vote is not None:
                self._set_has_vote_locked(height, round_, type_, idx)
            return vote


class ReplicaConsensusAbsorber(Reactor):
    """Owns the four consensus channels on a read replica ([base]
    mode = replica) WITHOUT any consensus machinery behind them.

    Peers running real consensus gossip votes/steps to every connected
    peer; a node that advertised no owner for those channels would
    disconnect each validator on the first inbound frame (the switch
    treats an unowned channel as a protocol error). The absorber keeps
    the wire protocol intact and drops the traffic — validators' gossip
    routines see a peer that never advances past height 0 and mostly
    sleep (reactor.go's prs.height == 0 guards). The replica itself
    never sends a consensus message."""

    def __init__(self, handel: bool = False):
        super().__init__("ReplicaConsensusAbsorber")
        self.absorbed = 0  # frames dropped; /debug visibility only
        self._handel = handel

    def get_channels(self):
        channels = [
            ChannelDescriptor(id=STATE_CHANNEL, priority=1,
                              send_queue_capacity=2),
            ChannelDescriptor(id=DATA_CHANNEL, priority=1,
                              send_queue_capacity=2,
                              recv_message_capacity=1048576),
            ChannelDescriptor(id=VOTE_CHANNEL, priority=1,
                              send_queue_capacity=2,
                              recv_message_capacity=100 * 1024),
            ChannelDescriptor(id=VOTE_SET_BITS_CHANNEL, priority=1,
                              send_queue_capacity=2,
                              recv_message_capacity=1024),
        ]
        if self._handel:
            # a [handel]-enabled fleet advertises 0x24; the replica must
            # own it too or the first inbound contribution disconnects
            # the validator (unowned channel = protocol error)
            channels.append(ChannelDescriptor(
                id=HANDEL_CHANNEL, priority=1, send_queue_capacity=2,
                recv_message_capacity=100 * 1024))
        return channels

    def receive(self, ch_id: int, peer, msg_bytes: bytes) -> None:
        self.absorbed += 1

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass


class ConsensusReactor(Reactor):
    """reactor.go:37."""

    def __init__(self, consensus_state, fast_sync: bool = False):
        super().__init__("ConsensusReactor")
        self.cs = consensus_state
        self.fast_sync = fast_sync
        # a bare test stub may carry no metrics: count nothing then
        received = getattr(consensus_state, "metrics", NOP_CONSENSUS).votes_received
        self._count_received = {VOTE_TYPE_PREVOTE: received.with_labels("prevote").inc,
                                VOTE_TYPE_PRECOMMIT: received.with_labels("precommit").inc}
        self._peer_states: Dict[str, PeerState] = {}
        self._peer_threads: Dict[str, list] = {}
        self._stop = threading.Event()
        self._bcast_thread: Optional[threading.Thread] = None
        self._handel_thread: Optional[threading.Thread] = None
        # validator index -> peer id, learned from the `origin` field of
        # received contributions (GIL-atomic dict ops; no lock needed)
        self._handel_val_peer: Dict[int, str] = {}
        self._subs = []
        # gossip-mark expiry horizon (expire_gossip_marks_if_stalled):
        # roughly one full round at this chain's timeouts — long enough
        # that normal progress never expires, short enough that a
        # silent-loss stall re-offers within a few rounds
        try:
            conf = consensus_state.config
            self._gossip_resend_s = max(
                2.0,
                2 * (conf.propose(1) + conf.prevote(1) + conf.precommit(1)))
        except Exception:  # noqa: BLE001 - absent config in bare tests
            self._gossip_resend_s = 10.0

    def get_channels(self):
        """reactor.go:125-157."""
        channels = [
            ChannelDescriptor(id=STATE_CHANNEL, priority=5, send_queue_capacity=100),
            ChannelDescriptor(
                id=DATA_CHANNEL, priority=10, send_queue_capacity=100,
                recv_message_capacity=1048576,
            ),
            ChannelDescriptor(
                id=VOTE_CHANNEL, priority=5, send_queue_capacity=100,
                recv_message_capacity=100 * 1024,
            ),
            ChannelDescriptor(
                id=VOTE_SET_BITS_CHANNEL, priority=1, send_queue_capacity=2,
                recv_message_capacity=1024,
            ),
        ]
        if getattr(self.cs, "handel", None) is not None:
            channels.append(ChannelDescriptor(
                id=HANDEL_CHANNEL, priority=5, send_queue_capacity=100,
                recv_message_capacity=100 * 1024,
            ))
        return channels

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        if not self.fast_sync:
            self.cs.start()
        self._bcast_thread = threading.Thread(
            target=self._broadcast_routine, name="cons-bcast", daemon=True
        )
        self._bcast_thread.start()
        self._step_refresh_thread = threading.Thread(
            target=self._step_refresh_routine, name="cons-step-refresh",
            daemon=True)
        self._step_refresh_thread.start()
        if getattr(self.cs, "handel", None) is not None:
            self._handel_thread = threading.Thread(
                target=self._handel_tick_routine, name="cons-handel",
                daemon=True)
            self._handel_thread.start()

    def _step_refresh_routine(self) -> None:
        """Periodically re-announce our round step to every peer.

        Step transitions broadcast NewRoundStep once; under silent
        message loss (netchaos drops, asymmetric partitions) that one
        copy can vanish, and several steps (PREVOTE before 2/3-any,
        PRECOMMIT_WAIT) have NO timeout — a wedged node then emits
        nothing, every peer's view of its (height, round) goes stale,
        and vote gossip keeps aiming at the wrong round forever. A
        ~tiny periodic refresh (one <100B message per peer) re-anchors
        peer views so the mark-expiry resend actually lands.

        It re-sends the LAST step broadcast's bytes rather than
        re-reading RoundState: a fresh shallow copy taken from this
        thread can tear mid-transition, and a torn (height, round,
        step) that jumps FORWARD would poison every peer's view (the
        receive guard only rejects regressions). Stale-but-consistent
        bytes are harmless — receivers ignore anything <= their view."""
        interval = max(0.5, self._gossip_resend_s / 2.0)
        while not self._stop.wait(interval):
            if self.fast_sync:
                continue
            step_bytes = getattr(self, "_last_step_bcast", None)
            if step_bytes is None:
                continue
            try:
                self._broadcast(STATE_CHANNEL, step_bytes)
            except Exception:  # noqa: BLE001 - refresh must outlive bugs
                LOG.exception("round-step refresh failed")

    def stop(self) -> None:
        self._stop.set()
        try:
            self.cs.stop()
        except Exception:
            pass

    def switch_to_consensus(self, state, blocks_synced: int = 0) -> None:
        """Fast-sync handoff (reactor.go:101-123).

        Note the reconstruct AFTER update_to_state: the reference (v0.27)
        calls reconstructLastCommit first and updateToState then clobbers
        cs.LastCommit back to nil (state.go:497-501,533) — a proposer
        that fast-synced could then never build a block. Later upstream
        versions fixed the order; we do the fixed order.
        """
        self.cs.update_to_state(state)
        self.cs._reconstruct_last_commit_if_needed(state)
        self.fast_sync = False
        if blocks_synced > 0:
            # don't bother with the WAL if we fast synced (reactor.go:114-117)
            self.cs.do_wal_catchup = False
        self.cs.start()

    # -- peers ---------------------------------------------------------

    def init_peer(self, peer) -> None:
        peer.set("consensus_peer_state", PeerState(peer))

    def add_peer(self, peer) -> None:
        ps: PeerState = peer.get("consensus_peer_state")
        self._peer_states[peer.id] = ps
        # announce our current state so the peer can gossip to us. This
        # runs on the peer's accept/dial thread: only a CONSISTENT
        # stamped snapshot may be turned into wire bytes (CD-5) — a
        # torn forward-jumping round step poisons the peer's view. On a
        # torn read, fall back to the last receive-thread-built
        # broadcast bytes (always safe, may be stale) or stay quiet;
        # the periodic step refresh re-anchors the peer either way.
        rs = self.cs.get_round_state()
        if getattr(rs, "snapshot_consistent", True):
            peer.send(STATE_CHANNEL, encode_msg(_new_round_step_msg(rs)))
            cs_msg = _commit_step_msg(rs)
            if cs_msg is not None:
                peer.send(STATE_CHANNEL, encode_msg(cs_msg))
        else:
            step_bytes = getattr(self, "_last_step_bcast", None)
            if step_bytes is not None:
                peer.send(STATE_CHANNEL, step_bytes)
        threads = []
        for fn, nm in (
            (self._gossip_data_routine, "gossip-data"),
            (self._gossip_votes_routine, "gossip-votes"),
            (self._query_maj23_routine, "query-maj23"),
        ):
            t = threading.Thread(target=fn, args=(peer, ps), name=f"{nm}-{peer.id[:8]}", daemon=True)
            t.start()
            threads.append(t)
        self._peer_threads[peer.id] = threads

    def remove_peer(self, peer, reason) -> None:
        self._peer_states.pop(peer.id, None)
        self._peer_threads.pop(peer.id, None)
        for idx in [i for i, pid in self._handel_val_peer.items()
                    if pid == peer.id]:
            self._handel_val_peer.pop(idx, None)
        # threads exit on peer.is_running() checks

    # -- inbound -------------------------------------------------------

    def receive(self, ch_id: int, peer, msg_bytes: bytes) -> None:
        """reactor.go:199-320."""
        msg = decode_msg(msg_bytes)
        if self.switch is not None and peer.is_running():
            self.switch.metrics.peer_msg_recv_total.with_labels(
                peer.id, f"{ch_id:#04x}", type(msg).__name__).inc()
        ps: Optional[PeerState] = peer.get("consensus_peer_state")
        if ps is None:
            return
        if ch_id == STATE_CHANNEL:
            if isinstance(msg, NewRoundStepMessage):
                ps.apply_new_round_step(msg)
            elif isinstance(msg, CommitStepMessage):
                ps.apply_commit_step(msg)
            elif isinstance(msg, HasVoteMessage):
                ps.apply_has_vote(msg)
            elif isinstance(msg, VoteSetMaj23Message):
                self._handle_vote_set_maj23(peer, ps, msg)
        elif ch_id == DATA_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, ProposalMessage):
                ps.set_has_proposal(msg.proposal)
                self.cs.add_peer_message(msg, peer.id)
            elif isinstance(msg, ProposalPOLMessage):
                ps.apply_proposal_pol(msg)
            elif isinstance(msg, BlockPartMessage):
                ps.set_has_proposal_block_part(msg.height, msg.round, msg.part.index)
                self.cs.add_peer_message(msg, peer.id)
        elif ch_id == VOTE_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, VoteMessage):
                # the WAL splices these bytes into the vote's record
                msg.arrived_as = msg_bytes
                count = self._count_received.get(msg.vote.type)
                if count is not None:
                    count()
                # three integers, not a RoundState copy (reactor.go
                # Receive: cs.Height, Validators.Size(), LastCommit.Size())
                h, n, last_n = self.cs.vote_sizes
                if not ps.has_vote_bit_arrays(h):
                    ps.ensure_vote_bit_arrays(h, n)
                    ps.ensure_vote_bit_arrays(h - 1, last_n)
                ps.set_has_vote(msg.vote)
                self.cs.add_peer_message(msg, peer.id)
            elif isinstance(msg, AggregateCommitMessage):
                # Handel-lite lane: everything the cert covers, the peer
                # knows; the consensus loop verifies + merges it
                if msg.commit is not None:
                    rs = self.cs.get_round_state()
                    n = len(rs.validators) if rs.validators else 0
                    ps.ensure_vote_bit_arrays(rs.height, n)
                    ps.ensure_vote_bit_arrays(rs.height - 1, n)
                    ps.apply_agg_commit(msg.commit)
                    self.cs.add_peer_message(msg, peer.id)
        elif ch_id == HANDEL_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, HandelContributionMessage):
                # pin down the peer's validator index from the claimed
                # origin — a lie only misroutes that peer's OWN window
                # traffic (contribution verification is unaffected), and
                # the session's scoring prunes senders of garbage
                if 0 <= msg.origin < (1 << 20):
                    self._handel_val_peer[msg.origin] = peer.id
                self.cs.add_peer_message(msg, peer.id)
        elif ch_id == VOTE_SET_BITS_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, VoteSetBitsMessage):
                rs = self.cs.get_round_state()
                if rs.height == msg.height and rs.votes is not None:
                    vs = (
                        rs.votes.prevotes(msg.round)
                        if msg.type == VOTE_TYPE_PREVOTE
                        else rs.votes.precommits(msg.round)
                    )
                    ours = vs.bit_array_by_block_id(msg.block_id) if vs else None
                    ps.apply_vote_set_bits(msg, ours)
                else:
                    ps.apply_vote_set_bits(msg, None)

    def _handle_vote_set_maj23(self, peer, ps: PeerState, msg: VoteSetMaj23Message) -> None:
        """reactor.go:249-304: record the claim, respond with our bits."""
        rs = self.cs.get_round_state()
        # wire reply below: never answer from a torn snapshot (CD-5);
        # the peer's maj23 query repeats every PEER_QUERY_MAJ23_SLEEP
        if not getattr(rs, "snapshot_consistent", True):
            return
        if rs.height != msg.height or rs.votes is None:
            return
        rs.votes.set_peer_maj23(msg.round, msg.type, peer.id, msg.block_id)
        vs = (
            rs.votes.prevotes(msg.round)
            if msg.type == VOTE_TYPE_PREVOTE
            else rs.votes.precommits(msg.round)
        )
        if vs is None:
            return
        our_votes = vs.bit_array_by_block_id(msg.block_id)
        if our_votes is None:
            our_votes = BitArray(len(vs.val_set))
        peer.try_send(
            VOTE_SET_BITS_CHANNEL,
            encode_msg(
                VoteSetBitsMessage(
                    height=msg.height, round=msg.round, type=msg.type,
                    block_id=msg.block_id, votes=our_votes,
                )
            ),
        )

    # -- broadcast routine (event bus -> all peers) --------------------

    def _broadcast_routine(self) -> None:
        """reactor.go:371-395 subscribeToBroadcastEvents."""
        bus = getattr(self.cs, "event_bus", None)
        if bus is None or not hasattr(bus, "subscribe"):
            return
        sub_step = bus.subscribe("cons-reactor-step", query_for_event(EVENT_NEW_ROUND_STEP))
        sub_vote = bus.subscribe("cons-reactor-vote", query_for_event(EVENT_VOTE))
        self._subs = [sub_step, sub_vote]
        while not self._stop.is_set():
            msg = sub_step.get(timeout=0.05)
            if msg is not None:
                rs = msg.data
                step_bytes = encode_msg(_new_round_step_msg(rs))
                # cache for the periodic refresh: these bytes were built
                # from a receive-thread-published snapshot, so re-sending
                # them later can never leak a torn (height, round, step)
                self._last_step_bcast = step_bytes
                self._broadcast(STATE_CHANNEL, step_bytes)
                cs_msg = _commit_step_msg(rs)
                if cs_msg is not None:
                    # reference makeRoundStepMessages (reactor.go:404-412):
                    # entering commit advertises our block-parts header +
                    # bitmap so peers can feed us the parts we're missing —
                    # WITHOUT this a node that enters commit via catch-up
                    # precommits (e.g. right after the fast-sync handoff)
                    # deadlocks: peers never learn which parts to send
                    self._broadcast(STATE_CHANNEL, encode_msg(cs_msg))
            vmsg = sub_vote.get(timeout=0.0)
            if vmsg is not None:
                vote = vmsg.data["vote"]
                self._broadcast(
                    STATE_CHANNEL,
                    encode_msg(
                        HasVoteMessage(
                            height=vote.height, round=vote.round,
                            type=vote.type, index=vote.validator_index,
                        )
                    ),
                )

    def _broadcast(self, ch_id: int, msg_bytes: bytes) -> None:
        if self.switch is not None:
            self.switch.broadcast(ch_id, msg_bytes)

    # -- per-peer gossip -----------------------------------------------

    def _gossip_data_routine(self, peer, ps: PeerState) -> None:
        """reactor.go:456-526."""
        while peer.is_running() and not self._stop.is_set():
            try:
                if self._gossip_data_once(peer, ps):
                    continue
            except Exception:
                LOG.exception("gossip data error for %s", peer.id[:8])
            time.sleep(PEER_GOSSIP_SLEEP)

    def _gossip_data_once(self, peer, ps: PeerState) -> bool:
        """One attempt; True if something was sent (skip the sleep)."""
        rs = self.cs.get_round_state()
        # everything below builds wire messages from rs: skip the tick
        # on a torn snapshot (CD-5) — the next one is 100ms away
        if not getattr(rs, "snapshot_consistent", True):
            return False
        prs = ps.get_round_state()

        # send proposal block parts the peer is missing
        if (
            rs.proposal_block_parts is not None
            and prs.proposal_block_parts_header is not None
            and rs.proposal_block_parts.has_header(prs.proposal_block_parts_header)
            and prs.proposal_block_parts is not None
        ):
            missing = rs.proposal_block_parts.bit_array().sub(prs.proposal_block_parts)
            idx = missing.pick_random()
            if idx is not None:
                part = rs.proposal_block_parts.get_part(idx)
                if part is not None and peer.send(
                    DATA_CHANNEL,
                    encode_msg(BlockPartMessage(height=rs.height, round=rs.round, part=part)),
                ):
                    ps.set_has_proposal_block_part(prs.height, prs.round, idx)
                    return True

        # peer is catching up: send parts of the committed block at their height
        block_store = getattr(self.cs, "block_store", None)
        if prs.height != 0 and prs.height < rs.height and block_store is not None:
            if prs.height < (block_store.height() or 0) + 1:
                return self._gossip_catchup_block_part(peer, ps, prs, block_store)

        if rs.height != prs.height or rs.round != prs.round:
            return False

        # send the proposal (+POL) if the peer lacks it
        if rs.proposal is not None and not prs.proposal:
            if peer.send(DATA_CHANNEL, encode_msg(ProposalMessage(proposal=rs.proposal))):
                ps.set_has_proposal(rs.proposal)
            if 0 <= rs.proposal.pol_round and rs.votes is not None:
                pol = rs.votes.prevotes(rs.proposal.pol_round)
                if pol is not None:
                    peer.send(
                        DATA_CHANNEL,
                        encode_msg(
                            ProposalPOLMessage(
                                height=rs.height,
                                proposal_pol_round=rs.proposal.pol_round,
                                proposal_pol=pol.bit_array(),
                            )
                        ),
                    )
            return True
        return False

    def _gossip_catchup_block_part(self, peer, ps: PeerState, prs, block_store) -> bool:
        """reactor.go:528-591: feed an old block part by part."""
        meta = block_store.load_block_meta(prs.height)
        if meta is None:
            return False
        if prs.proposal_block_parts_header is None or not (
            prs.proposal_block_parts_header.hash == meta.block_id.parts_header.hash
        ):
            # the peer hasn't advertised the matching parts header yet —
            # it will via its CommitStepMessage once catch-up precommits
            # drive it into the commit step (reactor.go:536-544 just
            # sleeps here too)
            return False
        if prs.proposal_block_parts is None:
            return False
        missing = BitArray(prs.proposal_block_parts.bits)
        for i in range(missing.bits):
            missing.set_index(i, True)
        missing = missing.sub(prs.proposal_block_parts)
        idx = missing.pick_random()
        if idx is None:
            return False
        part = block_store.load_block_part(prs.height, idx)
        if part is None:
            return False
        if peer.send(
            DATA_CHANNEL,
            encode_msg(BlockPartMessage(height=prs.height, round=prs.round, part=part)),
        ):
            ps.set_has_proposal_block_part(prs.height, prs.round, idx)
            return True
        return False

    def _gossip_votes_routine(self, peer, ps: PeerState) -> None:
        """reactor.go:593-717."""
        while peer.is_running() and not self._stop.is_set():
            try:
                if self._gossip_votes_once(peer, ps):
                    continue
                # nothing to send: if the peer's height has been stuck
                # for a full round span, our sent-marks may be lies
                # (silently dropped sends) — expire and re-offer
                ps.expire_gossip_marks_if_stalled(
                    self._gossip_resend_s, our_height=self.cs.rs.height)
            except Exception:
                LOG.exception("gossip votes error for %s", peer.id[:8])
            time.sleep(PEER_GOSSIP_SLEEP)

    def _gossip_votes_once(self, peer, ps: PeerState) -> bool:
        rs = self.cs.get_round_state()
        # wire sends built from rs below: torn snapshot -> skip the
        # tick (CD-5)
        if not getattr(rs, "snapshot_consistent", True):
            return False
        prs = ps.get_round_state()

        def send(vote) -> bool:
            if vote is None:
                return False
            return peer.send(VOTE_CHANNEL, encode_msg(VoteMessage(vote=vote)))

        # BLS fast lane: one merged certificate beats N VoteMessages
        if self._gossip_agg_cert_once(peer, ps, rs, prs):
            return True

        # same height: current-round votes, POL prevotes, last commit
        if rs.height == prs.height and rs.votes is not None:
            # last commit to help the peer finish the previous height
            if prs.step == STEP_NEW_HEIGHT and rs.last_commit is not None:
                if send(ps.pick_vote_to_send(rs.last_commit)):
                    return True
            # POL prevotes for the peer's proposal_pol_round
            if 0 <= prs.proposal_pol_round:
                pol = rs.votes.prevotes(prs.proposal_pol_round)
                if pol is not None and send(ps.pick_vote_to_send(pol)):
                    return True
            # current round votes
            if 0 <= prs.round <= rs.round:
                pv = rs.votes.prevotes(prs.round)
                if prs.step <= STEP_PREVOTE_WAIT and pv is not None:
                    if send(ps.pick_vote_to_send(pv)):
                        return True
                pc = rs.votes.precommits(prs.round)
                if pc is not None and send(ps.pick_vote_to_send(pc)):
                    return True
        # peer one height behind: our last commit is their current precommits
        if rs.height == prs.height + 1 and rs.last_commit is not None:
            if send(ps.pick_vote_to_send(rs.last_commit)):
                return True
        # further behind: stored commit for their height
        block_store = getattr(self.cs, "block_store", None)
        if prs.height != 0 and rs.height >= prs.height + 2 and block_store is not None:
            from ..types.block import AggregateCommit

            commit = block_store.load_block_commit(prs.height)
            if isinstance(commit, AggregateCommit):
                # BLS catch-up: the stored certificate IS the commit —
                # one message instead of one per validator
                ps.ensure_catchup_commit_round(prs.height, commit.round(),
                                               commit.size())
                if ps.agg_cert_has_news(commit) and peer.send(
                    VOTE_CHANNEL,
                    encode_msg(AggregateCommitMessage(commit)),
                ):
                    ps.apply_agg_commit(commit)
                    return True
            elif commit is not None:
                ps.ensure_catchup_commit_round(prs.height, commit.round(), len(commit.precommits))
                vote = ps.pick_vote_to_send(_CommitVoteSetView(commit))
                if send(vote):
                    return True
        return False

    def _gossip_agg_cert_once(self, peer, ps: PeerState, rs, prs) -> bool:
        """Handel-lite aggregation-aware precommit gossip (BLS valsets
        only; Ed25519 chains never reach this). Send our current merged
        (bitmap, aggregate) pair whenever it covers signers the peer
        lacks: the peer merges it with its own running aggregate and
        re-gossips, so quorum assembly takes O(log n) messages instead
        of one per validator."""
        if rs.validators is None or not rs.validators.is_bls():
            return False
        try:
            now = time.monotonic()
            # Handel overlay suppression: while the overlay is on and its
            # frontier is healthy, same-height certificates travel as
            # O(log n) level contributions instead — the flat lane stays
            # armed as the fallback and re-opens the moment a session
            # reports a stuck level (byzantine-silent subtree, partition)
            mgr = getattr(self.cs, "handel", None)
            handel_quiet = (mgr is not None and mgr.enabled(rs.validators)
                            and mgr.stuck(now) == 0)
            # same height: the peer's current round precommits
            if (not handel_quiet and prs.height == rs.height
                    and rs.votes is not None
                    and 0 <= prs.round <= rs.round):
                pc = rs.votes.precommits(prs.round)
                cert = pc.aggregate_certificate() if pc is not None else None
                if cert is not None and cert.num_signers() > 1:
                    ps.ensure_vote_bit_arrays(rs.height, cert.size())
                    if ps.agg_cert_should_send(
                        cert, now, _AGG_RESEND_MIN_S, _AGG_RESEND_DELTA
                    ) and peer.send(
                        VOTE_CHANNEL, encode_msg(AggregateCommitMessage(cert))
                    ):
                        ps.apply_agg_commit(cert)
                        ps.note_agg_cert_sent(cert, now)
                        return True
            # peer one height behind: our last commit as one certificate
            # (never suppressed — catch-up is not an aggregation problem)
            if prs.height + 1 == rs.height and rs.last_commit is not None:
                cert = rs.last_commit.aggregate_certificate()
                if cert is not None:
                    ps.ensure_vote_bit_arrays(prs.height, cert.size())
                    if ps.agg_cert_should_send(
                        cert, now, _AGG_RESEND_MIN_S, _AGG_RESEND_DELTA
                    ) and peer.send(
                        VOTE_CHANNEL, encode_msg(AggregateCommitMessage(cert))
                    ):
                        ps.apply_agg_commit(cert)
                        ps.note_agg_cert_sent(cert, now)
                        return True
        except Exception:
            LOG.exception("aggregate cert gossip error for %s", peer.id[:8])
        return False

    def _handel_tick_routine(self) -> None:
        """One thread drives every Handel session's gossip (not
        per-peer: a tick drains ALL sessions and fans the sends out to
        whichever peers currently back the target validator indices).
        Unmapped targets mean we have not yet seen that validator's
        peer; one representative contribution per still-unmapped peer
        per tick bootstraps the index map (receivers learn OUR index
        from `origin` and their replies pin theirs) without flooding."""
        mgr = self.cs.handel
        interval = max(0.01, getattr(mgr.cfg, "tick_ms", 50) / 1000.0)
        while not self._stop.wait(interval):
            if self.fast_sync:
                continue
            try:
                rs = self.cs.get_round_state()
                # contributions are wire messages: only a consistent
                # snapshot may pick the (height, validators) they bind
                # to (CD-5); retry next tick
                if not getattr(rs, "snapshot_consistent", True):
                    continue
                if rs.validators is None:
                    continue
                sends = mgr.outgoing(rs.validators, rs.height,
                                     time.monotonic())
                if not sends:
                    continue
                self._handel_fan_out(sends)
            except Exception:  # noqa: BLE001 - overlay must outlive bugs
                LOG.exception("handel tick failed")

    def _handel_fan_out(self, sends) -> None:
        """Route [(validator_index, HandelContributionMessage)] to peers.
        Only peers ADVERTISING the channel may receive on it: a frame on
        an undeclared channel is a protocol error that tears down the
        connection (connection.py recv loop), so a mixed fleet — handel
        validators peered with [handel]-off nodes or replicas — would
        flap without this gate."""
        peers = {
            pid: ps for pid, ps in self._peer_states.items()
            if HANDEL_CHANNEL in ps.peer.node_info.channels
        }
        val_peer = self._handel_val_peer
        bootstrap_msg = None
        for target, m in sends:
            pid = val_peer.get(target)
            ps = peers.get(pid) if pid is not None else None
            if ps is not None and ps.peer.is_running():
                ps.peer.try_send(HANDEL_CHANNEL, encode_msg(m))
            else:
                bootstrap_msg = m
        if bootstrap_msg is not None:
            data = encode_msg(bootstrap_msg)
            mapped = set(val_peer.values())
            for pid, ps in peers.items():
                if pid not in mapped and ps.peer.is_running():
                    ps.peer.try_send(HANDEL_CHANNEL, data)

    def _query_maj23_routine(self, peer, ps: PeerState) -> None:
        """reactor.go:720-802: periodically ask the peer for vote bits of
        claimed majorities."""
        while peer.is_running() and not self._stop.is_set():
            time.sleep(PEER_QUERY_MAJ23_SLEEP)
            try:
                rs = self.cs.get_round_state()
                # maj23 claims are wire messages: only from a
                # consistent snapshot (CD-5); retry in 2s
                if not getattr(rs, "snapshot_consistent", True):
                    continue
                prs = ps.get_round_state()
                if rs.votes is None:
                    continue
                if rs.height == prs.height:
                    pv = rs.votes.prevotes(prs.round) if prs.round >= 0 else None
                    if pv is not None:
                        maj = pv.two_thirds_majority()
                        if maj is not None:
                            peer.try_send(
                                STATE_CHANNEL,
                                encode_msg(
                                    VoteSetMaj23Message(
                                        height=prs.height, round=prs.round,
                                        type=VOTE_TYPE_PREVOTE, block_id=maj,
                                    )
                                ),
                            )
                    pc = rs.votes.precommits(prs.round) if prs.round >= 0 else None
                    if pc is not None:
                        maj = pc.two_thirds_majority()
                        if maj is not None:
                            peer.try_send(
                                STATE_CHANNEL,
                                encode_msg(
                                    VoteSetMaj23Message(
                                        height=prs.height, round=prs.round,
                                        type=VOTE_TYPE_PRECOMMIT, block_id=maj,
                                    )
                                ),
                            )
            except Exception:
                LOG.exception("query maj23 error for %s", peer.id[:8])


class _CommitVoteSetView:
    """Adapter presenting a stored Commit as a minimal VoteSet for
    pick_vote_to_send (reference uses Commit.BitArray/GetByIndex via the
    VoteSetReader interface, types/block.go:540-620)."""

    def __init__(self, commit):
        self.commit = commit
        votes = [v for v in commit.precommits]
        self.height = commit.height()
        self.round = commit.round()
        self.type = VOTE_TYPE_PRECOMMIT
        self._votes = votes

        class _VS:
            def __init__(self, n):
                self._n = n

            def __len__(self):
                return self._n

        self.val_set = _VS(len(votes))

    def size(self) -> int:
        return len(self._votes)

    def bit_array(self) -> BitArray:
        return BitArray.from_bools([v is not None for v in self._votes])

    def get_by_index(self, idx: int):
        return self._votes[idx]


def _new_round_step_msg(rs) -> NewRoundStepMessage:
    since = int(time.time() - rs.start_time) if rs.start_time else 0
    last_commit_round = rs.last_commit.round if rs.last_commit is not None else -1
    return NewRoundStepMessage(
        height=rs.height,
        round=rs.round,
        step=rs.step,
        seconds_since_start_time=max(since, 0),
        last_commit_round=last_commit_round,
    )


def _commit_step_msg(rs) -> Optional[CommitStepMessage]:
    """reference makeRoundStepMessages (reactor.go:404-412): at commit
    step, advertise the parts header + which parts we already have."""
    if rs.step != STEP_COMMIT or rs.proposal_block_parts is None:
        return None
    return CommitStepMessage(
        height=rs.height,
        block_parts_header=rs.proposal_block_parts.header(),
        block_parts=rs.proposal_block_parts.bit_array(),
    )
