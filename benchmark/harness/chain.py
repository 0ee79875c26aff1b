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
    import JAX, so they can run beside the process that owns the chip.
    Each has a pipe of its own that the caller's thread writes and reads
    itself: a `multiprocessing.Pool` hands its jobs over through threads
    of the caller's process, which wait for the interpreter lock while
    the caller works, and the caller works while the committee signs."""

    def __init__(self, seeds: list, workers: int):
        self.n = len(seeds)
        self.workers = max(1, min(workers, self.n))
        step = -(-self.n // self.workers)
        self.ranges = [(lo, min(self.n, lo + step))
                       for lo in range(0, self.n, step)]
        ctx = multiprocessing.get_context("spawn")
        self.conns, self.procs = [], []
        for _ in range(self.workers):
            ours, theirs = ctx.Pipe()
            proc = ctx.Process(target=signer.serve, args=(theirs, seeds),
                               daemon=True)
            proc.start()
            theirs.close()
            self.conns.append(ours)
            self.procs.append(proc)

    def _start(self, kind: str, jobs: list):
        """Sends one job to each of the first workers; returns the call
        that waits for their signatures, in the jobs' order."""
        conns = self.conns[:len(jobs)]
        for conn, job in zip(conns, jobs):
            conn.send((kind, job))

        def wait() -> list:
            blob = b"".join(conn.recv_bytes() for conn in conns)
            return [blob[i:i + 64] for i in range(0, len(blob), 64)]

        return wait

    def sign_spliced(self, prefix: bytes, suffix: bytes, stamps: list):
        """Starts the committee signing; returns the call that waits for
        the signatures, so that the caller can work meanwhile."""
        return self._start("spliced", [(lo, hi, prefix, suffix, stamps[lo:hi])
                                       for lo, hi in self.ranges])

    def sign_messages(self, pairs: list) -> list:
        step = max(1, -(-len(pairs) // self.workers))
        return self._start("messages", [pairs[lo:lo + step] for lo in
                                        range(0, len(pairs), step)])()

    def close(self) -> None:
        for conn in self.conns:
            conn.close()  # a worker's read ends, and the worker with it
        for proc in self.procs:
            proc.join(10)
            if proc.is_alive():
                proc.terminate()
                proc.join()


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
        self.seed = 0
        self.state0 = None           # the genesis State
        self.build_s = 0.0

    def __len__(self) -> int:
        return len(self.messages)


def committee(*, seed: int, validators: int,
              genesis_time_ns: int = 1_700_000_000_000_000_000) -> Chain:
    """The chain's keys and genesis, drawn from the seed: what a joiner
    needs before it is started. No block yet (`sign_blocks`)."""
    from tendermint_tpu.crypto.keys import PubKeyEd25519
    from tendermint_tpu.state import state_from_genesis_doc
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    out = Chain()
    out.chain_id = f"bench-sync-{seed}"
    out.seed = seed
    tag = b"bench-%d-val" % seed
    seeds = [signer.seed_of(tag, i) for i in range(validators)]
    pubs = [signer.public_key(s) for s in seeds]
    out.genesis = GenesisDoc(
        chain_id=out.chain_id, genesis_time=genesis_time_ns,
        validators=[GenesisValidator(PubKeyEd25519(p), 10, f"v{i}")
                    for i, p in enumerate(pubs)])
    out.state0 = state_from_genesis_doc(out.genesis)
    by_pub = {p: i for i, p in enumerate(pubs)}
    order = [by_pub[v.pub_key.bytes()]  # address-sorted, as the set is
             for v in out.state0.validators.validators]
    out.seeds = [seeds[i] for i in order]
    out.pubkeys = [pubs[i] for i in order]
    return out


def sign_blocks(out: Chain, *, blocks: int, txs_per_block: int, tx_bytes: int,
                key_space: int, workers: int) -> Chain:
    """Builds and signs the committee's chain, block by block (a block's
    hash covers the signatures under the block before it)."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.state.execution import ABCIResponses
    from tendermint_tpu.types import Vote, serde
    from tendermint_tpu.types.basic import BlockID
    from tendermint_tpu.types.block import (Block, Commit, Data, EvidenceData,
                                            Header)
    from tendermint_tpu.types.part_set import PartSet

    t0 = time.monotonic()
    rng = np.random.default_rng(out.seed)
    chain_id, doc, genesis = out.chain_id, out.genesis, out.state0
    genesis_time_ns = doc.genesis_time
    vals = genesis.validators.validators
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

    # a block_response is the array ["block_response", block]: this head,
    # then the block's own encoding, which the part set is cut from too
    response_head = serde.pack(["block_response", None])[:-1]
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
                when = stamps[len(vals) // 2]
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
            encoded = block.encode()  # once: the parts and the message
            block_id = BlockID(hash=block.hash(),
                               parts_header=PartSet.from_data(encoded).header())
            base = max(last_time, when) + 1_000_000_000
            stamps = [base + i for i in range(len(vals))]
            prefix, suffix = _splice_parts(chain_id, h, block_id)
            signed = pool.sign_spliced(prefix, suffix, stamps)
            # while the workers sign: what no signature feeds
            out.messages.append(response_head + encoded)
            out.block_hash.append(block_id.hash)
            out.txs.append(txs)
            for tx in txs:
                ref.deliver(tx)
            app_hash = ref.commit()
            out.app_hash.append(app_hash)
            sigs = signed()
            votes = [Vote(addresses[i], i, h, 0, stamps[i], VOTE_TYPE_PRECOMMIT,
                          block_id, sigs[i]) for i in range(len(vals))]
            if h == 1:  # the splices against the program's own encodings
                for i in (0, len(vals) - 1):
                    want = votes[i].sign_bytes(chain_id)
                    got = prefix + struct.pack("<Q", stamps[i]) + suffix
                    if want != got:
                        raise RuntimeError("spliced sign-bytes differ")
                if out.messages[0] != serde.pack(
                        ["block_response", serde.block_obj(block)]):
                    raise RuntimeError("spliced block_response differs")
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
