"""Seeded chains as a live network makes them: the traffic of the
consensus-gossip cells (`hub150-follow`).

A fast-sync joiner is served blocks and checks each block's LastCommit;
`chain.sign_blocks` builds that, with one proposer stamped on every
header. A node that follows a chain through consensus gossip sees every
message of every height, so this module builds, for each height h:

- the block, with the round-0 proposer of the program's own rotation
  (`ValidatorSet.get_proposer`, then `increment_proposer_priority(1)` a
  height, as `state.update_state` does with no validator change) in its
  header;
- the `Proposal` for it, signed by that proposer;
- every validator's prevote and precommit, signed by OpenSSL in worker
  processes over sign-bytes spliced around each vote's fixed64
  timestamp (as `chain._splice_parts` does for precommits, here for
  both types); the precommits are byte for byte the LastCommit of block
  h+1, as the network's are;
- each of those as the consensus wire message a peer sends (0x21
  `ProposalMessage`, `BlockPartMessage`; 0x22 `VoteMessage`), encoded in
  set-up, and an order for each height's prevotes and its precommits
  drawn from the seed.

Header fields the node checks (app hash, results hash) come from the
plain reference, as in `chain.py`. The block loop is `chain.sign_blocks`'s
with the proposer, the prevotes and the proposal added; `chain.py` is
not edited, so that every cell it serves keeps its bytes (README-hub150.md).
"""

from __future__ import annotations

import time

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from . import chain as chainlib
from .reference import KVReference, verify_one

VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT = 1, 2


def splice_parts(chain_id: str, vote_type: int, height: int, block_id):
    """(prefix, suffix) of a vote's sign-bytes around its fixed64
    timestamp, found by encoding two timestamps with the program's own
    canonical encoder."""
    from tendermint_tpu.types.basic import canonical_vote_sign_bytes

    a = canonical_vote_sign_bytes(chain_id, vote_type, height, 0, block_id,
                                  0x0101010101010101)
    b = canonical_vote_sign_bytes(chain_id, vote_type, height, 0, block_id,
                                  0x0202020202020202)
    diff = [i for i in range(len(a)) if a[i] != b[i]]
    if len(a) != len(b) or diff != list(range(diff[0], diff[0] + 8)):
        raise RuntimeError("vote sign-bytes are not a fixed64 splice")
    return a[:diff[0]], a[diff[0] + 8:]


class Rounds(chainlib.Chain):
    """A `Chain` (committee, genesis, block hashes, txs and the
    reference's app hashes, which `_check_applied` reads) and, by height
    (index h-1), what a peer ahead of the node sends once the node has
    entered h: the proposal's message, the parts' messages, every
    validator's prevote and precommit message in validator order, and
    the order each type is sent in."""

    def __init__(self):
        super().__init__()
        self.proposal_msg: list = []     # [h-1] -> encoded ProposalMessage
        self.part_msgs: list = []        # [h-1] -> [encoded BlockPartMessage]
        self.prevote_msgs: list = []     # [h-1] -> [encoded VoteMessage] by index
        self.precommit_msgs: list = []   # [h-1] -> [encoded VoteMessage] by index
        self.orders: list = []           # [h-1] -> (prevote order, precommit order)
        self.warm_triples: list = []     # fresh vote-sized triples for warming

    def __len__(self) -> int:
        return len(self.proposal_msg)


def committee(*, seed: int, validators: int) -> Rounds:
    """`chain.committee`'s keys and genesis, in a `Rounds`."""
    out = Rounds()
    out.__dict__.update(chainlib.committee(seed=seed, validators=validators).__dict__)
    return out


def build(out: Rounds, *, heights: int, txs_per_block: int, tx_bytes: int,
          key_space: int, workers: int, warm_lanes: int = 0) -> Rounds:
    """Builds and signs `heights` heights of the committee's chain, and
    `warm_lanes` fresh signatures over vote-sized messages that the
    traffic never sends (to make kernel shapes ready in set-up)."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.consensus.messages import (BlockPartMessage,
                                                   ProposalMessage,
                                                   VoteMessage)
    from tendermint_tpu.consensus.reactor import encode_msg
    from tendermint_tpu.state.execution import ABCIResponses
    from tendermint_tpu.types import Vote
    from tendermint_tpu.types.basic import BlockID, Proposal
    from tendermint_tpu.types.block import (Block, Commit, Data, EvidenceData,
                                            Header)
    from tendermint_tpu.types.part_set import PartSet

    t0 = time.monotonic()
    rng = np.random.default_rng(out.seed)
    chain_id, doc, genesis = out.chain_id, out.genesis, out.state0
    vals = genesis.validators.validators
    n = len(vals)
    addresses = [v.address for v in vals]
    vals_hash = genesis.validators.hash()
    next_vals_hash = genesis.next_validators.hash()
    cons_hash = genesis.consensus_params.hash()
    index_of = {a: i for i, a in enumerate(addresses)}
    keys = [Ed25519PrivateKey.from_private_bytes(s) for s in out.seeds]
    # the program's rotation: validators at h, and next_validators
    cur, nxt = genesis.validators.copy(), genesis.next_validators.copy()

    perm = rng.permutation(key_space)
    key_w = len(str(key_space - 1))
    results_hash = ABCIResponses(
        [abci.ResponseDeliverTx(code=0)] * txs_per_block, None).results_hash()

    pool = chainlib.SignerPool(out.seeds, workers)
    try:
        ref = KVReference()
        last_id, last_commit, last_time = BlockID(), None, doc.genesis_time
        total_txs, app_hash, last_results = 0, doc.app_hash, b""
        for h in range(1, heights + 1):
            txs = []
            for i in range(txs_per_block):
                k = int(perm[((h - 1) * txs_per_block + i) % key_space])
                head = b"k%0*d=" % (key_w, k)
                txs.append(head + rng.bytes(tx_bytes - len(head)))
            # the median of equal-power precommits stamped base + index
            when = doc.genesis_time if last_commit is None else stamps[n // 2]
            proposer = index_of[cur.get_proposer().address]
            total_txs += len(txs)
            block = Block(
                header=Header(
                    chain_id=chain_id, height=h, time=when, num_txs=len(txs),
                    total_txs=total_txs, last_block_id=last_id,
                    validators_hash=vals_hash,
                    next_validators_hash=next_vals_hash,
                    consensus_hash=cons_hash, app_hash=app_hash,
                    last_results_hash=last_results,
                    proposer_address=addresses[proposer]),
                data=Data(txs=txs), evidence=EvidenceData(evidence=[]),
                last_commit=last_commit)
            block.fill_header()
            parts = PartSet.from_data(block.encode())
            block_id = BlockID(hash=block.hash(), parts_header=parts.header())
            base = max(last_time, when) + 1_000_000_000
            pre_stamps = [base - 500_000_000 + i for i in range(n)]
            stamps = [base + i for i in range(n)]
            pv = splice_parts(chain_id, VOTE_TYPE_PREVOTE, h, block_id)
            pc = splice_parts(chain_id, VOTE_TYPE_PRECOMMIT, h, block_id)
            # one reply a worker a job, in the order the jobs went out
            signed_pv = pool.sign_spliced(*pv, pre_stamps)
            signed_pc = pool.sign_spliced(*pc, stamps)
            # while the workers sign: what no vote signature feeds
            proposal = Proposal(height=h, round=0,
                                block_parts_header=parts.header(),
                                pol_round=-1, pol_block_id=BlockID(),
                                timestamp=when)
            proposal.signature = keys[proposer].sign(proposal.sign_bytes(chain_id))
            out.proposal_msg.append(encode_msg(ProposalMessage(proposal)))
            out.part_msgs.append([
                encode_msg(BlockPartMessage(h, 0, parts.get_part(i)))
                for i in range(parts.total())])
            out.orders.append((rng.permutation(n), rng.permutation(n)))
            out.block_hash.append(block_id.hash)
            out.txs.append(txs)
            for tx in txs:
                ref.deliver(tx)
            app_hash = ref.commit()
            out.app_hash.append(app_hash)
            by_type = {}
            for vote_type, signed, ts in ((VOTE_TYPE_PREVOTE, signed_pv, pre_stamps),
                                          (VOTE_TYPE_PRECOMMIT, signed_pc, stamps)):
                sigs = signed()
                by_type[vote_type] = [
                    Vote(addresses[i], i, h, 0, ts[i], vote_type, block_id,
                         sigs[i]) for i in range(n)]
            out.prevote_msgs.append([encode_msg(VoteMessage(v))
                                     for v in by_type[VOTE_TYPE_PREVOTE]])
            out.precommit_msgs.append([encode_msg(VoteMessage(v))
                                       for v in by_type[VOTE_TYPE_PRECOMMIT]])
            if h == 1:  # the splices and the proposer against the program
                for v in (by_type[VOTE_TYPE_PREVOTE][0],
                          by_type[VOTE_TYPE_PRECOMMIT][n - 1]):
                    if not verify_one(v.sign_bytes(chain_id), v.signature,
                                      out.pubkeys[v.validator_index]):
                        raise RuntimeError("spliced sign-bytes differ")
                if not cur.get_proposer().pub_key.verify_bytes(
                        proposal.sign_bytes(chain_id), proposal.signature):
                    raise RuntimeError("the proposal is not the proposer's")
            last_results = results_hash
            last_id, last_time = block_id, when
            last_commit = Commit(block_id, by_type[VOTE_TYPE_PRECOMMIT])
            cur, nxt = nxt, nxt.copy()
            nxt.increment_proposer_priority(1)
        # vote-sized messages (a prevote's sign-bytes with another stamp)
        # no height ever sends: a kernel bucket warmed with them leaves
        # nothing of the traffic in the verified-signature cache
        for i in range(warm_lanes):
            msg = pv[0] + (2**63 + i).to_bytes(8, "little") + pv[1]
            out.warm_triples.append((msg, keys[i % n].sign(msg),
                                     out.pubkeys[i % n]))
    finally:
        pool.close()
    out.build_s = time.monotonic() - t0
    return out


def corrupted(out: Rounds, height: int, rng, lo: int, hi: int,
              before: int) -> tuple:
    """Height `height`'s prevotes and precommits with one prevote and one
    precommit, of validators drawn from [lo, hi), carrying one flipped
    signature bit; the corrupted precommit is put among the first
    `before` of its height's order (ahead of the quorum, so a node that
    took it would keep it in its seen commit). Returns (prevote
    messages, precommit messages, precommit order, [(type, validator,
    corrupted signature)])."""
    from tendermint_tpu.consensus.messages import VoteMessage
    from tendermint_tpu.consensus.reactor import decode_msg, encode_msg

    msgs = {VOTE_TYPE_PREVOTE: list(out.prevote_msgs[height - 1]),
            VOTE_TYPE_PRECOMMIT: list(out.precommit_msgs[height - 1])}
    where = []
    for vote_type in (VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT):
        idx = int(rng.integers(lo, hi))
        vote = decode_msg(msgs[vote_type][idx]).vote
        good, msg = vote.signature, vote.sign_bytes(out.chain_id)
        vote.signature = chainlib.flip_bit(good, int(rng.integers(0, 512)))
        # the oracle's word on both, before the node is asked
        pub = out.pubkeys[idx]
        if not verify_one(msg, good, pub) or verify_one(msg, vote.signature, pub):
            raise RuntimeError(f"OpenSSL disagrees about validator {idx}'s vote")
        msgs[vote_type][idx] = encode_msg(VoteMessage(vote))
        where.append((vote_type, idx, vote.signature))
    order = list(out.orders[height - 1][1])
    at, to = order.index(where[1][1]), int(rng.integers(0, before))
    order[at], order[to] = order[to], order[at]
    return msgs[VOTE_TYPE_PREVOTE], msgs[VOTE_TYPE_PRECOMMIT], order, where
