"""Consensus messages (reference consensus/reactor.go:1340-1577).

The same message types flow over p2p channels, into the WAL, and through
the state machine's receive loop. Wire/WAL form is a ["kind", ...] list
via message_to_obj/message_from_obj.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..libs.bit_array import BitArray
from ..types import serde
from ..types.basic import BlockID, Proposal, Vote
from ..types.part_set import Part


@dataclass
class NewRoundStepMessage:
    """Peer's current HRS (reactor State channel; reference :1359-1385)."""

    height: int
    round: int
    step: int
    seconds_since_start_time: int = 0
    last_commit_round: int = -1


@dataclass
class CommitStepMessage:
    """reference :1388-1401"""

    height: int
    block_parts_header: object  # PartSetHeader
    block_parts: BitArray


@dataclass
class ProposalMessage:
    proposal: Proposal


@dataclass
class ProposalPOLMessage:
    """reference :1425-1441"""

    height: int
    proposal_pol_round: int
    proposal_pol: BitArray


@dataclass
class BlockPartMessage:
    height: int
    round: int
    part: Part


@dataclass
class VoteMessage:
    vote: Vote
    # Not part of the message: the bytes it was received as, kept by the
    # reactor that decoded them so that the WAL splices them into the
    # vote's record and does not encode the vote again. The record
    # replays as what was decoded from them; for a peer of this program
    # (serde is deterministic) they are encode_msg()'s, byte for byte.
    arrived_as: Optional[bytes] = field(default=None, repr=False,
                                        compare=False)


@dataclass
class HasVoteMessage:
    """reference :1477-1491"""

    height: int
    round: int
    type: int
    index: int


@dataclass
class VoteSetMaj23Message:
    """Peer claims +2/3 for block_id (reference :1494-1510)."""

    height: int
    round: int
    type: int
    block_id: BlockID


@dataclass
class VoteSetBitsMessage:
    """Bit-array of votes we have for the claimed maj23 (reference
    :1513-1535)."""

    height: int
    round: int
    type: int
    block_id: BlockID
    votes: BitArray


@dataclass
class AggregateCommitMessage:
    """Handel-lite precommit aggregation (no reference equivalent; BLS
    fast lane only): a running (signer bitmap, aggregate signature)
    certificate for (height, round, block_id). Peers merge disjoint
    certificates and re-gossip, so a node assembles 2/3+ from O(log n)
    messages instead of one VoteMessage per validator. `commit` is a
    types.block.AggregateCommit."""

    commit: object


@dataclass
class HandelContributionMessage:
    """Handel overlay level contribution (consensus/handel.py; no
    reference equivalent): origin's combined aggregate over its own
    half-subtree at `level` for the precommit on (height, round,
    block_id). signers is a full-committee-sized bitmap (the level
    constrains which bits may be set); agg_sig is the 96-byte BLS
    aggregate over exactly those signers."""

    height: int
    round: int
    level: int
    origin: int
    block_id: BlockID
    signers: BitArray
    agg_sig: bytes


def _ba_obj(ba: Optional[BitArray]):
    return None if ba is None else [ba.bits, ba.to_bytes()]


def _ba_from(o) -> Optional[BitArray]:
    if o is None:
        return None
    return BitArray.from_bytes_size(o[1], o[0])


def message_to_obj(m) -> list:
    if isinstance(m, NewRoundStepMessage):
        return ["new_round_step", m.height, m.round, m.step,
                m.seconds_since_start_time, m.last_commit_round]
    if isinstance(m, CommitStepMessage):
        return ["commit_step", m.height, serde.psh_obj(m.block_parts_header), _ba_obj(m.block_parts)]
    if isinstance(m, ProposalMessage):
        return ["proposal", serde.proposal_obj(m.proposal)]
    if isinstance(m, ProposalPOLMessage):
        return ["proposal_pol", m.height, m.proposal_pol_round, _ba_obj(m.proposal_pol)]
    if isinstance(m, BlockPartMessage):
        return ["block_part", m.height, m.round, serde.part_obj(m.part)]
    if isinstance(m, VoteMessage):
        return ["vote", serde.vote_obj(m.vote)]
    if isinstance(m, HasVoteMessage):
        return ["has_vote", m.height, m.round, m.type, m.index]
    if isinstance(m, VoteSetMaj23Message):
        return ["vote_set_maj23", m.height, m.round, m.type, serde.block_id_obj(m.block_id)]
    if isinstance(m, VoteSetBitsMessage):
        return ["vote_set_bits", m.height, m.round, m.type,
                serde.block_id_obj(m.block_id), _ba_obj(m.votes)]
    if isinstance(m, AggregateCommitMessage):
        return ["agg_commit", serde.commit_obj(m.commit)]
    if isinstance(m, HandelContributionMessage):
        return ["handel", m.height, m.round, m.level, m.origin,
                serde.block_id_obj(m.block_id), _ba_obj(m.signers),
                m.agg_sig]
    raise TypeError(f"unknown consensus message {type(m)}")


def message_from_obj(o: list):
    kind = o[0]
    if kind == "new_round_step":
        return NewRoundStepMessage(o[1], o[2], o[3], o[4], o[5])
    if kind == "commit_step":
        return CommitStepMessage(o[1], serde.psh_from(o[2]), _ba_from(o[3]))
    if kind == "proposal":
        return ProposalMessage(serde.proposal_from(o[1]))
    if kind == "proposal_pol":
        return ProposalPOLMessage(o[1], o[2], _ba_from(o[3]))
    if kind == "block_part":
        return BlockPartMessage(o[1], o[2], serde.part_from(o[3]))
    if kind == "vote":
        return VoteMessage(serde.vote_from(o[1]))
    if kind == "has_vote":
        return HasVoteMessage(o[1], o[2], o[3], o[4])
    if kind == "vote_set_maj23":
        return VoteSetMaj23Message(o[1], o[2], o[3], serde.block_id_from(o[4]))
    if kind == "vote_set_bits":
        return VoteSetBitsMessage(o[1], o[2], o[3], serde.block_id_from(o[4]), _ba_from(o[5]))
    if kind == "agg_commit":
        return AggregateCommitMessage(serde.commit_from(o[1]))
    if kind == "handel":
        return HandelContributionMessage(o[1], o[2], o[3], o[4],
                                         serde.block_id_from(o[5]),
                                         _ba_from(o[6]), o[7])
    raise ValueError(f"unknown consensus message kind {kind!r}")
