"""BlockID, PartSetHeader, vote types, canonical sign-bytes.

Reference parity: types/block.go (BlockID :480), types/part_set.go
(PartSetHeader), types/vote.go (Vote :51-60, SignBytes :62-68),
types/canonical.go (CanonicalVote/CanonicalProposal :35-73). Timestamps
are integer unix nanoseconds everywhere (deterministic; the reference's
RFC3339Nano canonical-time rule collapses to the same total order).
"""

from __future__ import annotations

import struct
import time as _time
from dataclasses import dataclass, field as dc_field
from typing import List, Optional, Sequence

from .. import codec
from ..crypto import tmhash

# vote types (reference types/vote.go VoteTypePrevote/Precommit)
VOTE_TYPE_PREVOTE = 1
VOTE_TYPE_PRECOMMIT = 2

MAX_VOTE_BYTES = 256  # conservative analogue of types/vote.go:15 (223)


def now_ns() -> int:
    return _time.time_ns()


class ErrVoteConflictingVotes(Exception):
    def __init__(self, vote_a: "Vote", vote_b: "Vote"):
        super().__init__("conflicting votes from validator")
        self.vote_a = vote_a
        self.vote_b = vote_b


@dataclass(frozen=True)
class PartSetHeader:
    total: int = 0
    hash: bytes = b""

    def is_zero(self) -> bool:
        return self.total == 0 and not self.hash

    def encode(self) -> bytes:
        return codec.t_uvarint(1, self.total) + codec.t_bytes(2, self.hash)

    def __str__(self):
        return f"{self.total}:{self.hash.hex()[:12]}"


@dataclass(frozen=True)
class BlockID:
    hash: bytes = b""
    parts_header: PartSetHeader = dc_field(default_factory=PartSetHeader)

    def is_zero(self) -> bool:
        return not self.hash and self.parts_header.is_zero()

    def encode(self) -> bytes:
        return codec.t_bytes(1, self.hash) + codec.t_message(
            2, self.parts_header.encode()
        )

    def key(self) -> bytes:
        # length-prefixed: without separation, (hash, psh.hash) pairs that
        # concatenate identically would collide into one vote-tally bucket
        return (
            codec.uvarint(len(self.hash))
            + self.hash
            + codec.uvarint(len(self.parts_header.hash))
            + self.parts_header.hash
            + codec.uvarint(self.parts_header.total)
        )

    def __str__(self):
        return f"{self.hash.hex()[:12]}:{self.parts_header}"


ZERO_BLOCK_ID = BlockID()


def canonical_vote_sign_bytes(
    chain_id: str,
    vote_type: int,
    height: int,
    round_: int,
    block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """Deterministic sign-bytes (replaces amino CanonicalVote,
    types/canonical.go:35-42). Height/round are fixed64 like the
    reference's binary:fixed64 annotations."""
    return (
        codec.t_uvarint(1, vote_type)
        + codec.t_fixed64(2, height)
        + codec.t_fixed64(3, round_)
        + codec.t_message(4, block_id.encode())
        + codec.t_fixed64(5, timestamp_ns)
        + codec.t_string(6, chain_id)
    )


def canonical_proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    parts_header: PartSetHeader,
    pol_round: int,
    pol_block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """Sign-bytes for proposals (types/canonical.go CanonicalProposal)."""
    return (
        codec.t_uvarint(1, 32)  # message kind discriminator: proposal
        + codec.t_fixed64(2, height)
        + codec.t_fixed64(3, round_)
        + codec.t_message(4, parts_header.encode())
        + codec.t_fixed64(5, pol_round + 1)  # -1 (no POL) encodes as 0
        + codec.t_message(6, pol_block_id.encode())
        + codec.t_fixed64(7, timestamp_ns)
        + codec.t_string(8, chain_id)
    )


@dataclass(slots=True)
class Vote:
    """A signed prevote or precommit (reference types/vote.go:51-60).
    Slots: a commit of 10,000 is 10,000 of these a block, and a dict
    apiece is as many more objects for the collector to walk."""

    validator_address: bytes
    validator_index: int
    height: int
    round: int
    timestamp: int  # unix ns
    type: int
    block_id: BlockID
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical_vote_sign_bytes(
            chain_id, self.type, self.height, self.round, self.block_id, self.timestamp
        )

    def verify(self, chain_id: str, pub_key) -> bool:
        """Single-vote verify (reference types/vote.go:102-111). The bulk
        path goes through ValidatorSet.verify_commit / VoteSet batching."""
        if pub_key.address() != self.validator_address:
            return False
        return pub_key.verify_bytes(self.sign_bytes(chain_id), self.signature)

    def is_prevote(self) -> bool:
        return self.type == VOTE_TYPE_PREVOTE

    def copy(self) -> "Vote":
        return Vote(
            self.validator_address,
            self.validator_index,
            self.height,
            self.round,
            self.timestamp,
            self.type,
            self.block_id,
            self.signature,
        )

    def encode(self) -> bytes:
        return (
            codec.t_bytes(1, self.validator_address)
            + codec.t_uvarint(2, self.validator_index + 1)
            + codec.t_fixed64(3, self.height)
            + codec.t_fixed64(4, self.round)
            + codec.t_fixed64(5, self.timestamp)
            + codec.t_uvarint(6, self.type)
            + codec.t_message(7, self.block_id.encode())
            + codec.t_bytes(8, self.signature)
        )

    def hash(self) -> bytes:
        return tmhash.sum(self.encode())

    def __str__(self):
        t = "prevote" if self.type == VOTE_TYPE_PREVOTE else "precommit"
        return (
            f"Vote{{{self.validator_index}:{self.validator_address.hex()[:8]} "
            f"{self.height}/{self.round} {t} {self.block_id}}}"
        )


_FIXED64 = struct.Struct("<Q").pack
_U64 = 2**64 - 1


def votes_sign_bytes(chain_id: str, votes: Sequence[Vote]) -> List[bytes]:
    """`[v.sign_bytes(chain_id) for v in votes]`, byte for byte. The
    votes of a commit share type, height, round and (all but a few)
    block id: what stands before the fixed64 timestamp is encoded once
    for each run of votes that share it, the chain id after it once,
    and both are spliced around each vote's timestamp. A vote for nil
    or for another block id starts a run, and so a prefix, of its own."""
    tail = codec.t_string(6, chain_id)
    stamp_tag = codec.tag(5, codec.WIRE_FIXED64)
    out = []
    shared = head = None
    for v in votes:
        fields = (v.type, v.height, v.round, v.block_id)
        if fields != shared:
            shared = fields
            head = (codec.t_uvarint(1, v.type) + codec.t_fixed64(2, v.height)
                    + codec.t_fixed64(3, v.round)
                    + codec.t_message(4, v.block_id.encode()))
        ts = v.timestamp
        out.append(head + stamp_tag + _FIXED64(ts & _U64) + tail if ts
                   else head + tail)
    return out


def votes_encode(votes: Sequence[Optional[Vote]]) -> List[bytes]:
    """`[v.encode() for v in votes]`, byte for byte, with an absent vote
    (None) as b"" (the leaves of Commit.hash). As in votes_sign_bytes,
    the fields a run of votes shares are encoded once a run."""
    uvarint = codec.uvarint
    addr_tag = codec.tag(1, codec.WIRE_BYTES)
    index_tag = codec.tag(2, codec.WIRE_VARINT)
    stamp_tag = codec.tag(5, codec.WIRE_FIXED64)
    sig_tag = codec.tag(8, codec.WIRE_BYTES)
    out = []
    shared = where = what = None
    for v in votes:
        if v is None:
            out.append(b"")
            continue
        fields = (v.type, v.height, v.round, v.block_id)
        if fields != shared:
            shared = fields
            where = codec.t_fixed64(3, v.height) + codec.t_fixed64(4, v.round)
            what = (codec.t_uvarint(6, v.type)
                    + codec.t_message(7, v.block_id.encode()))
        addr, sig = v.validator_address, v.signature
        index, ts = v.validator_index + 1, v.timestamp
        out.append(
            (addr_tag + uvarint(len(addr)) + addr if addr else b"")
            + (index_tag + uvarint(index) if index else b"")
            + where
            + (stamp_tag + _FIXED64(ts & _U64) if ts else b"")
            + what
            + (sig_tag + uvarint(len(sig)) + sig if sig else b""))
    return out


@dataclass
class Proposal:
    """Block proposal (reference types/proposal.go)."""

    height: int
    round: int
    block_parts_header: PartSetHeader
    pol_round: int  # -1 when no proof-of-lock
    pol_block_id: BlockID
    timestamp: int
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical_proposal_sign_bytes(
            chain_id,
            self.height,
            self.round,
            self.block_parts_header,
            self.pol_round,
            self.pol_block_id,
            self.timestamp,
        )

    def __str__(self):
        return f"Proposal{{{self.height}/{self.round} {self.block_parts_header} pol={self.pol_round}}}"
