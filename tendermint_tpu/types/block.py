"""Block, Header, Data, Commit (reference types/block.go).

Hashes: header hash is a merkle tree over the encoded fields (reference
Header.Hash :403-426 uses a simple map hasher; we use an ordered field
list — deterministic and proof-friendly); data/evidence/commit hashes are
merkle roots over item encodings.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import List, Optional

from .. import codec
from ..crypto import merkle, tmhash
from .basic import (VOTE_TYPE_PRECOMMIT, BlockID, PartSetHeader, Vote,
                    votes_encode)

MAX_BLOCK_SIZE_BYTES = 104857600  # reference types/params.go MaxBlockSizeBytes


@dataclass
class Header:
    chain_id: str = ""
    height: int = 0
    time: int = 0  # unix ns
    num_txs: int = 0
    total_txs: int = 0
    last_block_id: BlockID = dc_field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    next_validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    proposer_address: bytes = b""

    def hash(self) -> Optional[bytes]:
        """Merkle root over encoded fields; None until validators_hash is
        populated (reference Header.Hash returns nil likewise)."""
        if not self.validators_hash:
            return None
        fields = [
            codec.t_string(1, self.chain_id),
            codec.t_fixed64(1, self.height),
            codec.t_fixed64(1, self.time),
            codec.t_fixed64(1, self.num_txs),
            codec.t_fixed64(1, self.total_txs),
            self.last_block_id.encode(),
            self.last_commit_hash,
            self.data_hash,
            self.validators_hash,
            self.next_validators_hash,
            self.consensus_hash,
            self.app_hash,
            self.last_results_hash,
            self.evidence_hash,
            self.proposer_address,
        ]
        return merkle.hash_from_byte_slices(fields)

    def __str__(self):
        return f"Header{{{self.chain_id}/{self.height} t:{self.time}}}"


@dataclass
class Data:
    txs: List[bytes] = dc_field(default_factory=list)

    def hash(self) -> bytes:
        return merkle.hash_from_byte_slices(self.txs)


def tx_hash(tx: bytes) -> bytes:
    return tmhash.sum(tx)


@dataclass
class Commit:
    """+2/3 precommits for a block (reference types/block.go:480-490).
    precommits[i] corresponds to validator i of the set; None = absent."""

    block_id: BlockID
    precommits: List[Optional[Vote]]
    # Not part of the commit: the bytes the block store saved it as
    # (serde.encode_commit), so that the LastCommit fast sync saves as
    # SC:h is not packed again as C:h a height later. Nothing writes to
    # block_id or precommits once a commit is built; whoever does works
    # on a commit it has just decoded, before any store has seen it.
    saved_as: Optional[bytes] = dc_field(default=None, repr=False,
                                         compare=False)

    def height(self) -> int:
        for v in self.precommits:
            if v is not None:
                return v.height
        return 0

    def round(self) -> int:
        for v in self.precommits:
            if v is not None:
                return v.round
        return 0

    def size(self) -> int:
        return len(self.precommits)

    def is_commit(self) -> bool:
        return len(self.precommits) > 0

    def bit_array(self):
        from ..libs.bit_array import BitArray

        return BitArray.from_bools([v is not None for v in self.precommits])

    def validate_basic(self) -> None:
        if self.block_id.is_zero():
            raise ValueError("commit has zero block id")
        if not self.precommits:
            raise ValueError("commit has no precommits")
        h, r = self.height(), self.round()
        for v in self.precommits:
            if v is None:
                continue
            if v.type != VOTE_TYPE_PRECOMMIT:
                raise ValueError("commit contains non-precommit vote")
            if v.height != h or v.round != r:
                raise ValueError("commit contains vote from wrong height/round")

    def hash(self) -> bytes:
        """Merkle root over each precommit's encode() (b"" where absent)."""
        return merkle.hash_from_byte_slices(votes_encode(self.precommits))

    def __str__(self):
        n = sum(1 for v in self.precommits if v is not None)
        return f"Commit{{{self.height()}/{self.round()} {n}/{len(self.precommits)} {self.block_id}}}"


@dataclass
class AggregateCommit:
    """O(1) commit certificate for BLS12-381-keyed validator sets: the
    signer bitmap plus ONE 96-byte aggregate signature (no reference
    equivalent; the aggregate-signature fast lane's wire/store form).

    Every signer's precommit for (height, round, block_id) covers
    identical sign-bytes — BLS-lane votes carry timestamp 0 (see
    MIGRATION.md) — so the certificate verifies with one
    fast_aggregate_verify over the bitmap-selected pubkeys, replacing
    N per-vote signature checks AND N×64 wire bytes with
    ceil(N/8) + 96. Duck-types the Commit query surface (height/round/
    size/bit_array/validate_basic/hash) used by stores, gossip, and
    verification; it deliberately has NO .precommits — every consumer
    branches explicitly so the plain per-vote path stays byte-for-byte
    untouched."""

    block_id: BlockID
    agg_height: int
    agg_round: int
    signers: "object"  # libs.bit_array.BitArray
    agg_sig: bytes  # 96-byte compressed G2 aggregate

    def height(self) -> int:
        return self.agg_height

    def round(self) -> int:
        return self.agg_round

    def size(self) -> int:
        return self.signers.size()

    def is_commit(self) -> bool:
        return self.signers.num_true() > 0

    def bit_array(self):
        return self.signers.copy()

    def num_signers(self) -> int:
        return self.signers.num_true()

    def num_absent(self) -> int:
        return self.signers.size() - self.signers.num_true()

    def sign_bytes(self, chain_id: str) -> bytes:
        """The single message every signer covered (precommit canonical
        sign-bytes with timestamp 0)."""
        from .basic import canonical_vote_sign_bytes

        return canonical_vote_sign_bytes(
            chain_id, VOTE_TYPE_PRECOMMIT, self.agg_height, self.agg_round,
            self.block_id, 0,
        )

    def validate_basic(self) -> None:
        if self.block_id.is_zero():
            raise ValueError("aggregate commit has zero block id")
        if self.signers.size() == 0 or self.signers.num_true() == 0:
            raise ValueError("aggregate commit has no signers")
        if len(self.agg_sig) != 96:
            raise ValueError("aggregate commit signature must be 96 bytes")
        if self.agg_height <= 0:
            raise ValueError("aggregate commit height must be positive")
        if self.agg_round < 0:
            raise ValueError("aggregate commit round must be non-negative")

    def encode(self) -> bytes:
        return (
            codec.t_message(1, self.block_id.encode())
            + codec.t_fixed64(2, self.agg_height)
            + codec.t_fixed64(3, self.agg_round)
            + codec.t_uvarint(4, self.signers.size())
            + codec.t_bytes(5, self.signers.to_bytes())
            + codec.t_bytes(6, self.agg_sig)
        )

    def size_bytes(self) -> int:
        """Certificate wire size — the constant-vs-64×N story the
        agg_commit_size_bytes gauge reports."""
        return len(self.encode())

    def hash(self) -> bytes:
        return tmhash.sum(self.encode())

    def __str__(self):
        return (
            f"AggregateCommit{{{self.agg_height}/{self.agg_round} "
            f"{self.num_signers()}/{self.size()} {self.block_id}}}"
        )


@dataclass
class EvidenceData:
    evidence: list = dc_field(default_factory=list)

    def hash(self) -> bytes:
        return merkle.hash_from_byte_slices([e.encode() for e in self.evidence])


@dataclass
class Block:
    header: Header
    data: Data
    evidence: EvidenceData
    last_commit: Optional[Commit]
    # Not part of the block: the bytes a block_response carried for it,
    # kept by the blockchain reactor that decoded them so that fast sync
    # cuts the part set from them and does not encode 10,000 precommits
    # again (serde is deterministic: the bytes are encode()'s).
    arrived_as: Optional[bytes] = dc_field(default=None, repr=False,
                                           compare=False)

    @classmethod
    def make(
        cls,
        height: int,
        txs: List[bytes],
        last_commit: Optional[Commit],
        evidence: list,
    ) -> "Block":
        """Reference types/block.go MakeBlock — header is only partially
        filled; fill_header + the proposer complete it."""
        block = cls(
            header=Header(height=height, num_txs=len(txs)),
            data=Data(txs=list(txs)),
            evidence=EvidenceData(evidence=list(evidence)),
            last_commit=last_commit,
        )
        block.fill_header()
        return block

    def fill_header(self) -> None:
        h = self.header
        if not h.last_commit_hash and self.last_commit is not None:
            h.last_commit_hash = self.last_commit.hash()
        if not h.data_hash:
            h.data_hash = self.data.hash()
        if not h.evidence_hash:
            h.evidence_hash = self.evidence.hash()

    def hash(self) -> Optional[bytes]:
        if self.header is None or self.last_commit is None and self.header.height != 1:
            return None
        self.fill_header()
        return self.header.hash()

    def validate_basic(self) -> None:
        if self.header.height < 1:
            raise ValueError(f"invalid block height {self.header.height}")
        if self.header.height > 1:
            if self.last_commit is None:
                raise ValueError("nil last_commit for height > 1")
            self.last_commit.validate_basic()
            if self.header.last_commit_hash != self.last_commit.hash():
                raise ValueError("last_commit_hash mismatch")
        if self.header.num_txs != len(self.data.txs):
            raise ValueError("num_txs mismatch")
        if self.header.data_hash != self.data.hash():
            raise ValueError("data_hash mismatch")
        if self.header.evidence_hash != self.evidence.hash():
            raise ValueError("evidence_hash mismatch")

    def encode(self) -> bytes:
        """Deterministic encoding for PartSet chunking / storage."""
        from . import serde

        return serde.encode_block(self)

    def __str__(self):
        return f"Block{{{self.header} txs:{len(self.data.txs)}}}"


@dataclass
class BlockMeta:
    """Header + BlockID summary stored per height (reference
    types/block_meta.go)."""

    block_id: BlockID
    header: Header

    @classmethod
    def from_block(cls, block: Block, part_set) -> "BlockMeta":
        return cls(
            block_id=BlockID(block.hash(), part_set.header()),
            header=block.header,
        )


def make_part_set(block: Block, part_size: int = 65536):
    from .part_set import PartSet

    return PartSet.from_data(block.encode(), part_size)
