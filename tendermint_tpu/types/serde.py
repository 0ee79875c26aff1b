"""Structural serialization for storage / WAL / p2p (msgpack, list-shaped).

Deterministic: every type encodes as a fixed-order list (never a map), so
identical values yield identical bytes — required because the block's
part-set hash commits to these bytes. Distinct from the codec module,
which produces the minimal canonical encodings used for sign-bytes and
merkle leaves only.
"""

from __future__ import annotations

from typing import Optional

import msgpack

from ..crypto import batch, merkle, pubkey_from_bytes, pubkey_to_bytes
from .basic import BlockID, PartSetHeader, Proposal, Vote
from .block import Block, Commit, Data, EvidenceData, Header
from .part_set import Part
from .validator_set import Validator, ValidatorSet


def pack(obj) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def unpack(data: bytes):
    return msgpack.unpackb(data, raw=False, strict_map_key=False)


class Packed(bytes):
    """Bytes that are msgpack already: pack_list splices them in where
    the object they were packed from would stand."""


def pack_list(items) -> bytes:
    """pack(list(items)) with every Packed item standing for its object:
    an array header and its elements' own packings, one after another,
    are the bytes msgpack gives the nested list."""
    return b"".join([msgpack.Packer().pack_array_header(len(items))] + [
        i if isinstance(i, Packed) else pack(i) for i in items])


def _count_encoding(kind: str) -> None:
    m = batch.get_metrics()
    if m is not None:
        m.store_encodings.with_labels(kind).inc()


# --- to_obj / from_obj -----------------------------------------------------


def psh_obj(p: PartSetHeader):
    return [p.total, p.hash]


def psh_from(o) -> PartSetHeader:
    return PartSetHeader(total=o[0], hash=o[1])


def block_id_obj(b: BlockID):
    return [b.hash, psh_obj(b.parts_header)]


def block_id_from(o) -> BlockID:
    return BlockID(hash=o[0], parts_header=psh_from(o[1]))


def vote_obj(v: Optional[Vote]):
    if v is None:
        return None
    return [
        v.validator_address,
        v.validator_index,
        v.height,
        v.round,
        v.timestamp,
        v.type,
        block_id_obj(v.block_id),
        v.signature,
    ]


def vote_from(o) -> Optional[Vote]:
    if o is None:
        return None
    return Vote(
        validator_address=o[0],
        validator_index=o[1],
        height=o[2],
        round=o[3],
        timestamp=o[4],
        type=o[5],
        block_id=block_id_from(o[6]),
        signature=o[7],
    )


def proposal_obj(p: Proposal):
    return [
        p.height,
        p.round,
        psh_obj(p.block_parts_header),
        p.pol_round,
        block_id_obj(p.pol_block_id),
        p.timestamp,
        p.signature,
    ]


def proposal_from(o) -> Proposal:
    return Proposal(
        height=o[0],
        round=o[1],
        block_parts_header=psh_from(o[2]),
        pol_round=o[3],
        pol_block_id=block_id_from(o[4]),
        timestamp=o[5],
        signature=o[6],
    )


def commit_obj(c):
    if c is None:
        return None
    from .block import AggregateCommit

    if isinstance(c, AggregateCommit):
        # tagged form: a plain Commit's first element is a block-id obj
        # (a list), so the string tag is unambiguous on decode
        return ["AGG", block_id_obj(c.block_id), c.agg_height, c.agg_round,
                c.signers.size(), c.signers.to_bytes(), c.agg_sig]
    return [block_id_obj(c.block_id), [vote_obj(v) for v in c.precommits]]


def commit_from(o):
    if o is None:
        return None
    if isinstance(o[0], str) and o[0] == "AGG":
        from ..libs.bit_array import BitArray
        from .block import AggregateCommit

        return AggregateCommit(
            block_id=block_id_from(o[1]), agg_height=o[2], agg_round=o[3],
            signers=BitArray.from_bytes_size(o[5], o[4]), agg_sig=o[6],
        )
    # a vote for the commit's own block id (all but a few) shares the
    # commit's BlockID object: it is immutable, and 10,000 copies of it
    # are most of what decoding a large commit builds
    for_block, block_id = o[0], block_id_from(o[0])
    return Commit(block_id=block_id, precommits=[
        None if v is None else Vote(
            v[0], v[1], v[2], v[3], v[4], v[5],
            block_id if v[6] == for_block else block_id_from(v[6]), v[7])
        for v in o[1]])


def header_obj(h: Header):
    return [
        h.chain_id,
        h.height,
        h.time,
        h.num_txs,
        h.total_txs,
        block_id_obj(h.last_block_id),
        h.last_commit_hash,
        h.data_hash,
        h.validators_hash,
        h.next_validators_hash,
        h.consensus_hash,
        h.app_hash,
        h.last_results_hash,
        h.evidence_hash,
        h.proposer_address,
    ]


def header_from(o) -> Header:
    return Header(
        chain_id=o[0],
        height=o[1],
        time=o[2],
        num_txs=o[3],
        total_txs=o[4],
        last_block_id=block_id_from(o[5]),
        last_commit_hash=o[6],
        data_hash=o[7],
        validators_hash=o[8],
        next_validators_hash=o[9],
        consensus_hash=o[10],
        app_hash=o[11],
        last_results_hash=o[12],
        evidence_hash=o[13],
        proposer_address=o[14],
    )


def evidence_obj(e):
    from .evidence import evidence_to_obj

    return evidence_to_obj(e)


def block_obj(b: Block):
    return [
        header_obj(b.header),
        [bytes(t) for t in b.data.txs],
        [evidence_obj(e) for e in b.evidence.evidence],
        commit_obj(b.last_commit),
    ]


def block_from(o) -> Block:
    from .evidence import evidence_from_obj

    return Block(
        header=header_from(o[0]),
        data=Data(txs=list(o[1])),
        evidence=EvidenceData(evidence=[evidence_from_obj(e) for e in o[2]]),
        last_commit=commit_from(o[3]),
    )


def encode_block(b: Block) -> bytes:
    return pack(block_obj(b))


def decode_block(data: bytes) -> Block:
    return block_from(unpack(data))


def encode_vote(v: Vote) -> bytes:
    return pack(vote_obj(v))


def decode_vote(data: bytes) -> Vote:
    return vote_from(unpack(data))


def encode_commit(c: Commit) -> bytes:
    """pack(commit_obj(c)) for the block store, kept on a Commit: fast
    sync saves block h+1's LastCommit as SC:h and, one height later, the
    same object as C:h."""
    if not isinstance(c, Commit):  # an AggregateCommit: a bitmap and 96 bytes
        return pack(commit_obj(c))
    if c.saved_as is None:
        c.saved_as = pack(commit_obj(c))
        _count_encoding("commit")
    return c.saved_as


def decode_commit(data: bytes) -> Commit:
    return commit_from(unpack(data))


def validator_obj(v: Validator):
    # element 4 (proof of possession) is optional on the wire: older
    # peers / previously persisted valsets serialized 4-element lists
    return [v.address, pubkey_to_bytes(v.pub_key), v.voting_power,
            v.proposer_priority, v.pop]


def validator_from(o) -> Validator:
    return Validator(
        address=o[0],
        pub_key=pubkey_from_bytes(o[1]),
        voting_power=o[2],
        proposer_priority=o[3],
        pop=bytes(o[4]) if len(o) > 4 and o[4] else b"",
    )


def valset_obj(vs: ValidatorSet):
    prop = vs.proposer.address if vs.proposer else b""
    return [[validator_obj(v) for v in vs.validators], prop]


def encode_valset(vs: ValidatorSet) -> Packed:
    """pack(valset_obj(vs)) for the state store, kept on the set until
    something writes to it (ValidatorSet._packed_memo)."""
    memo = vs._packed_memo
    if memo is None:
        memo = vs._packed_memo = Packed(pack(valset_obj(vs)))
        _count_encoding("valset")
    return memo


def valset_from(o) -> ValidatorSet:
    vs = ValidatorSet.__new__(ValidatorSet)
    vs.validators = [validator_from(v) for v in o[0]]
    # __new__ skips __init__'s sort/rotation on purpose (persisted sets
    # carry their exact order + priorities) but its invariants must
    # still hold: statesync feeds wire bytes through here. A repeated
    # entry would double-count that validator's power in every tally
    # downstream (lite aggregate trusting path included), and an
    # unsorted set would hide members from get_by_address's search
    addrs = [v.address for v in vs.validators]
    if any(a >= b for a, b in zip(addrs, addrs[1:])):
        raise ValueError("duplicate validator address"
                         if len(set(addrs)) != len(addrs)
                         else "validators not sorted by address")
    vs._total = None
    _, vs.proposer = vs.get_by_address(o[1])
    return vs


def proof_obj(p: merkle.SimpleProof):
    return [p.total, p.index, p.leaf_hash, list(p.aunts)]


def proof_from(o) -> merkle.SimpleProof:
    return merkle.SimpleProof(total=o[0], index=o[1], leaf_hash=o[2], aunts=list(o[3]))


def part_obj(p: Part):
    return [p.index, p.bytes, proof_obj(p.proof)]


def part_from(o) -> Part:
    return Part(index=o[0], bytes=o[1], proof=proof_from(o[2]))
