"""Seeded serving chains: the traffic of the fast-sync cells.

Blocks are the program's wire types (the joiner has to decode them);
what goes into them is decided here: a committee of keys drawn from the
seed, kv transactions over a fixed key space, precommits of every
validator signed by OpenSSL in worker processes, and header fields
(app hash, results hash) taken from the plain reference, not from the
program's app. A joiner applies block h+1 only if its own app hash
after h equals the reference's, so a chain that syncs has proved them
equal at every height.
"""

from __future__ import annotations

import multiprocessing
import struct
import time

import numpy as np

from . import signer
from .reference import KVReference, verify_one

VOTE_TYPE_PRECOMMIT = 2


class SignerPool:
    """Worker processes that hold the committee's keys. They never
    import JAX, so they can run beside the process that owns the chip."""

    def __init__(self, seeds: list, workers: int):
        self.n = len(seeds)
        self.workers = max(1, min(workers, self.n))
        ctx = multiprocessing.get_context("spawn")
        self.pool = ctx.Pool(self.workers, initializer=signer.init_worker,
                             initargs=(seeds,))
        step = -(-self.n // self.workers)
        self.ranges = [(lo, min(self.n, lo + step))
                       for lo in range(0, self.n, step)]

    def sign_spliced(self, prefix: bytes, suffix: bytes, stamps: list) -> list:
        jobs = [(lo, hi, prefix, suffix, stamps[lo:hi]) for lo, hi in self.ranges]
        blob = b"".join(self.pool.map(signer.sign_spliced, jobs, chunksize=1))
        return [blob[i:i + 64] for i in range(0, len(blob), 64)]

    def sign_messages(self, pairs: list) -> list:
        step = -(-len(pairs) // self.workers)
        jobs = [pairs[lo:lo + step] for lo in range(0, len(pairs), step)]
        blob = b"".join(self.pool.map(signer.sign_messages, jobs, chunksize=1))
        return [blob[i:i + 64] for i in range(0, len(blob), 64)]

    def close(self) -> None:
        self.pool.close()
        self.pool.join()


def flip_bit(sig: bytes, bit: int) -> bytes:
    b = bytearray(sig)
    b[bit // 8] ^= 1 << (bit % 8)
    return bytes(b)


def _splice_parts(chain_id: str, height: int, block_id):
    """(prefix, suffix) of a precommit's sign-bytes around its fixed64
    timestamp, found by encoding two timestamps with the program's own
    canonical encoder."""
    from tendermint_tpu.types.basic import canonical_vote_sign_bytes

    a = canonical_vote_sign_bytes(chain_id, VOTE_TYPE_PRECOMMIT, height, 0,
                                  block_id, 0x0101010101010101)
    b = canonical_vote_sign_bytes(chain_id, VOTE_TYPE_PRECOMMIT, height, 0,
                                  block_id, 0x0202020202020202)
    diff = [i for i in range(len(a)) if a[i] != b[i]]
    if len(a) != len(b) or diff != list(range(diff[0], diff[0] + 8)):
        raise RuntimeError("precommit sign-bytes are not a fixed64 splice")
    return a[:diff[0]], a[diff[0] + 8:]


class Chain:
    """What the serving peer holds: encoded block_response messages by
    height, and what the oracle compares: block hash and the reference's
    app hash after each height."""

    def __init__(self):
        self.chain_id = ""
        self.genesis = None          # GenesisDoc
        self.messages: list = []     # [h-1] -> encoded block_response
        self.block_hash: list = []   # [h-1] -> block hash
        self.txs: list = []          # [h-1] -> the block's txs
        self.app_hash: list = []     # [h-1] -> reference app hash AFTER h
        self.pubkeys: list = []      # validator order
        self.seeds: list = []
        self.build_s = 0.0

    def __len__(self) -> int:
        return len(self.messages)


def build_chain(*, seed: int, validators: int, blocks: int, txs_per_block: int,
                tx_bytes: int, key_space: int, workers: int,
                genesis_time_ns: int = 1_700_000_000_000_000_000) -> Chain:
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.state import state_from_genesis_doc
    from tendermint_tpu.state.execution import ABCIResponses
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, Vote, serde
    from tendermint_tpu.types.basic import BlockID
    from tendermint_tpu.types.block import (Block, Commit, Data, EvidenceData,
                                            Header, make_part_set)

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    out = Chain()
    out.chain_id = chain_id = f"bench-sync-{seed}"
    tag = b"bench-%d-val" % seed
    seeds = [signer.seed_of(tag, i) for i in range(validators)]
    pubs = [signer.public_key(s) for s in seeds]
    doc = GenesisDoc(
        chain_id=chain_id, genesis_time=genesis_time_ns,
        validators=[GenesisValidator(PubKeyEd25519(p), 10, f"v{i}")
                    for i, p in enumerate(pubs)])
    genesis = state_from_genesis_doc(doc)
    vals = genesis.validators.validators  # address-sorted
    by_pub = {p: i for i, p in enumerate(pubs)}
    order = [by_pub[v.pub_key.bytes()] for v in vals]
    out.genesis = doc
    out.seeds = [seeds[i] for i in order]
    out.pubkeys = [pubs[i] for i in order]
    addresses = [v.address for v in vals]
    vals_hash = genesis.validators.hash()
    next_vals_hash = genesis.next_validators.hash()
    cons_hash = genesis.consensus_params.hash()
    proposer = addresses[0]

    # every seed writes the same number of txs of the same size over the
    # same key space, in another order and with other values
    perm = rng.permutation(key_space)
    key_w = len(str(key_space - 1))
    results_hash = ABCIResponses(
        [abci.ResponseDeliverTx(code=0)] * txs_per_block, None).results_hash()

    pool = SignerPool(out.seeds, workers)
    try:
        ref = KVReference()
        last_id, last_commit, last_time = BlockID(), None, genesis_time_ns
        total_txs, app_hash, last_results = 0, doc.app_hash, b""
        for h in range(1, blocks + 1):
            txs = []
            for i in range(txs_per_block):
                k = int(perm[((h - 1) * txs_per_block + i) % key_space])
                head = b"k%0*d=" % (key_w, k)
                txs.append(head + rng.bytes(tx_bytes - len(head)))
            if last_commit is None:
                when = genesis_time_ns
            else:  # the median of equal-power votes stamped base + index
                when = sorted(v.timestamp for v in last_commit.precommits)[
                    len(vals) // 2]
            total_txs += len(txs)
            block = Block(
                header=Header(
                    chain_id=chain_id, height=h, time=when, num_txs=len(txs),
                    total_txs=total_txs, last_block_id=last_id,
                    validators_hash=vals_hash,
                    next_validators_hash=next_vals_hash,
                    consensus_hash=cons_hash, app_hash=app_hash,
                    last_results_hash=last_results, proposer_address=proposer),
                data=Data(txs=txs), evidence=EvidenceData(evidence=[]),
                last_commit=last_commit)
            block.fill_header()
            parts = make_part_set(block)
            block_id = BlockID(hash=block.hash(), parts_header=parts.header())
            out.messages.append(
                serde.pack(["block_response", serde.block_obj(block)]))
            out.block_hash.append(block_id.hash)
            out.txs.append(txs)

            base = max(last_time, when) + 1_000_000_000
            stamps = [base + i for i in range(len(vals))]
            prefix, suffix = _splice_parts(chain_id, h, block_id)
            sigs = pool.sign_spliced(prefix, suffix, stamps)
            votes = [Vote(addresses[i], i, h, 0, stamps[i], VOTE_TYPE_PRECOMMIT,
                          block_id, sigs[i]) for i in range(len(vals))]
            if h == 1:  # the splice against the program's own encoding
                for i in (0, len(vals) - 1):
                    want = votes[i].sign_bytes(chain_id)
                    got = prefix + struct.pack("<Q", stamps[i]) + suffix
                    if want != got:
                        raise RuntimeError("spliced sign-bytes differ")
            for tx in txs:
                ref.deliver(tx)
            app_hash = ref.commit()
            out.app_hash.append(app_hash)
            last_results = results_hash
            last_id, last_commit, last_time = block_id, Commit(block_id, votes), when
    finally:
        pool.close()
    out.build_s = time.monotonic() - t0
    return out


def poisoned_message(chain: Chain, height: int, rng, ranges: list) -> tuple:
    """Block `height` as a dishonest peer would serve it: in its
    LastCommit (the votes for height-1) one precommit in each of `ranges`
    has one signature bit flipped, and the header's last_commit_hash is
    recomputed, so the block is self-consistent and only the signature
    check can refuse it. Returns (encoded block_response, the corrupted
    validators' indices)."""
    from tendermint_tpu.types import serde

    block = serde.block_from(serde.unpack(chain.messages[height - 1])[1])
    where = []
    for lo, hi in ranges:
        idx = int(rng.integers(lo, hi))
        vote = block.last_commit.precommits[idx].copy()
        msg, pub = vote.sign_bytes(chain.chain_id), chain.pubkeys[idx]
        good = vote.signature
        vote.signature = flip_bit(good, int(rng.integers(0, 512)))
        # the oracle's word on both, before the joiner is asked
        if not verify_one(msg, good, pub) or verify_one(msg, vote.signature, pub):
            raise RuntimeError(f"OpenSSL disagrees about validator {idx}'s vote")
        block.last_commit.precommits[idx] = vote
        where.append(idx)
    block.header.last_commit_hash = b""
    block.fill_header()
    return serde.pack(["block_response", serde.block_obj(block)]), where
