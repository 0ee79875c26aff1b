"""A full node that follows a chain through consensus gossip alone: every
proposal, block part, prevote and precommit of ten heights of a
7-validator chain reaches it through `ConsensusReactor.receive`, pushed
the way a peer ahead of it would (a height's proposal, its parts, its
prevotes, then its precommits, each in an order drawn from a seed, once
the node has entered that height). The batched live vote path (async
dispatch, the verified-signature cache, a drained run verified as one
batch) has to end where the serial oracle does (`[crypto]
async_dispatch = false`, one vote a run, one verify a vote): the same
blocks, app hash and seen commits. At one height a prevote and a
precommit carry a flipped signature bit; each is refused alone and the
height still commits. The vote path's counters say what was sent and
what was refused, and its histogram holds one sample a drained run."""

import os
import random
import threading
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

from tendermint_tpu import state as sm
from tendermint_tpu.abci.example.kvstore import KVStoreApplication
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config import ConsensusConfig
from tendermint_tpu.consensus import ConsensusState
from tendermint_tpu.consensus import state as cs_state
from tendermint_tpu.consensus.messages import (BlockPartMessage,
                                               ProposalMessage, VoteMessage)
from tendermint_tpu.consensus.reactor import (DATA_CHANNEL, VOTE_CHANNEL,
                                              ConsensusReactor, encode_msg)
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.crypto import PrivKeyEd25519
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.sigcache import SigCache
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.types.basic import (VOTE_TYPE_PRECOMMIT,
                                        VOTE_TYPE_PREVOTE, BlockID, Proposal,
                                        Vote)
from tendermint_tpu.types.block import Commit, make_part_set
from tendermint_tpu.types.event_bus import EventBus

CHAIN_ID = "follow-test"
N_VALS, HEIGHTS, BAD_H = 7, 10, 6
GENESIS_NS = 1_700_000_000_000_000_000


def _flip(sig: bytes, bit: int) -> bytes:
    b = bytearray(sig)
    b[bit // 8] ^= 1 << (bit % 8)
    return bytes(b)


def _genesis():
    keys = [PrivKeyEd25519.gen_from_secret(b"follow-%d" % i)
            for i in range(N_VALS)]
    doc = GenesisDoc(chain_id=CHAIN_ID, genesis_time=GENESIS_NS,
                     validators=[GenesisValidator(k.pub_key(), 10)
                                 for k in keys])
    return doc, {k.pub_key().address(): k for k in keys}


def _app():
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    return conns


class Chain:
    """Ten heights as the network made them: the round-0 proposer by the
    program's own rotation, a signed proposal, every validator's prevote
    and precommit; block h+1's LastCommit holds the precommits of h, byte
    for byte. `pushes[h]` is what a peer ahead sends once the node has
    entered h; at BAD_H one prevote and one precommit of the upper half
    of the committee carry a flipped signature bit."""

    def __init__(self, seed: int):
        rng = random.Random(seed)
        self.doc, key_of = _genesis()
        conns = _app()
        state = sm.load_state_from_db_or_genesis(MemDB(), self.doc)
        exec_ = sm.BlockExecutor(MemDB(), conns.consensus)
        self.block_hash, self.pushes, self.bad = {}, {}, []
        last_commit = None
        try:
            for h in range(1, HEIGHTS + 1):
                proposer = state.validators.get_proposer().address
                when = (state.last_block_time if h == 1 else
                        sm.state.median_time(last_commit, state.last_validators))
                txs = [b"k%d=%d-%d" % (i, h, seed) for i in range(3)]
                block = state.make_block(h, txs, last_commit, [], proposer,
                                         time_ns=when)
                if h == 1:
                    block.last_commit = None
                parts = make_part_set(block, part_size=1024)
                bid = BlockID(block.hash(), parts.header())
                proposal = Proposal(h, 0, parts.header(), -1, BlockID(),
                                    when + 1)
                proposal.signature = key_of[proposer].sign(
                    proposal.sign_bytes(CHAIN_ID))
                votes = {}
                for type_ in (VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT):
                    votes[type_] = []
                    for i, val in enumerate(state.validators.validators):
                        v = Vote(val.address, i, h, 0, when + 10**9 + i,
                                 type_, bid)
                        v.signature = key_of[val.address].sign(
                            v.sign_bytes(CHAIN_ID))
                        votes[type_].append(v)
                msgs = [(DATA_CHANNEL, ProposalMessage(proposal))]
                msgs += [(DATA_CHANNEL, BlockPartMessage(h, 0, parts.get_part(i)))
                         for i in range(parts.total())]
                for type_ in (VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT):
                    sent = list(votes[type_])
                    if h == BAD_H:
                        i = rng.randrange(N_VALS // 2 + 1, N_VALS)
                        bad = sent[i].copy()
                        bad.signature = _flip(bad.signature, rng.randrange(512))
                        sent[i] = bad
                        self.bad.append(bad)
                    rng.shuffle(sent)
                    msgs += [(VOTE_CHANNEL, VoteMessage(v)) for v in sent]
                self.pushes[h] = msgs
                self.block_hash[h] = bid.hash
                state = exec_.apply_block(state, bid, block)
                last_commit = Commit(bid, votes[VOTE_TYPE_PRECOMMIT])
            self.app_hash = state.app_hash
        finally:
            conns.stop()


class _Peer:
    """What `receive` reads of the peer that sent a message."""

    id = "ab" * 20

    def __init__(self):
        self._kv = {}

    def get(self, key):
        return self._kv.get(key)

    def set(self, key, value):
        self._kv[key] = value

    def is_running(self) -> bool:
        return False


def _until(done, what: str, deadline_s: float = 10.0) -> None:
    end = time.monotonic() + deadline_s
    while not done():
        if time.monotonic() > end:
            raise AssertionError(f"the follower never {what}")
        time.sleep(0.002)


def _follow(chain: Chain, tmp_path, monkeypatch, batched: bool) -> dict:
    """One follower fed the chain's pushes, height by height. Each
    height's votes are held back from the receive loop until every one is
    queued, so a batched run is one drained run a height."""
    with monkeypatch.context() as m:
        m.setattr(crypto_batch, "_default_name", "cpu")
        m.setattr(crypto_batch, "_async_enabled", batched)
        m.setattr(crypto_batch, "_sig_cache", SigCache(4096) if batched else None)
        if not batched:
            m.setattr(cs_state, "MAX_VOTE_BATCH", 1)
        conns, bus = _app(), EventBus()
        bus.start()
        db = MemDB()
        state = sm.load_state_from_db_or_genesis(db, chain.doc)
        store = BlockStore(MemDB())
        metrics = prometheus_metrics().consensus
        cs = ConsensusState(ConsensusConfig(), state,
                            sm.BlockExecutor(db, conns.consensus, event_bus=bus),
                            store, event_bus=bus,
                            wal=WAL(str(tmp_path / f"wal-{batched}"),
                                    records_counter=metrics.wal_records),
                            metrics=metrics)
        reactor = ConsensusReactor(cs)
        peer = _Peer()
        reactor.init_peer(peer)
        gate, runs = threading.Event(), []
        real_run = cs._vote_run

        def vote_run(first):
            gate.wait(10)
            runs.append(first)
            real_run(first)

        cs._vote_run = vote_run
        added = metrics.votes
        cs.start()
        try:
            for h in range(1, HEIGHTS + 1):
                _until(lambda: cs.rs.height == h, f"entered height {h}")
                gate.clear()
                for ch, msg in chain.pushes[h]:
                    reactor.receive(ch, peer, encode_msg(msg))
                gate.set()
            _until(lambda: sum(added._vals.values()) == 2 * N_VALS * HEIGHTS,
                   "tallied every vote")
            _until(lambda: cs.state.last_block_height == HEIGHTS,
                   f"applied height {HEIGHTS}")
        finally:
            cs.stop()
            bus.stop()
            conns.stop()
        hist = metrics.vote_run_size
        logged = WAL(cs.wal.group.head_path)
        wal_votes = [m for m in logged.iter_messages()
                     if isinstance(m, tuple) and isinstance(m[1], VoteMessage)]
        logged.group.close()
        return {
            "hashes": {h: store.load_block_meta(h).block_id.hash
                       for h in range(1, HEIGHTS + 1)},
            "app_hash": cs.state.app_hash,
            "seen": {h: [None if v is None else (v.validator_index, v.signature)
                         for v in store.load_seen_commit(h).precommits]
                     for h in range(1, HEIGHTS + 1)},
            "seen_votes": {h: [v for v in store.load_seen_commit(h).precommits
                               if v is not None]
                           for h in range(1, HEIGHTS + 1)},
            "received": dict(metrics.votes_received._vals),
            "results": dict(added._vals),
            "runs": len(runs),
            "run_count": hist._totals.get((), 0),
            "run_sum": hist._sums.get((), 0.0),
            "wal_records": dict(metrics.wal_records._vals),
            "wal_votes": wal_votes,
        }


@pytest.fixture(scope="module")
def chain():
    return Chain(seed=2**31 + 41)


def test_the_batched_follower_ends_where_the_serial_oracle_does(
        chain, tmp_path, monkeypatch):
    got = _follow(chain, tmp_path, monkeypatch, batched=True)
    want = _follow(chain, tmp_path, monkeypatch, batched=False)
    assert got["hashes"] == want["hashes"] == chain.block_hash
    assert got["app_hash"] == want["app_hash"] == chain.app_hash
    assert got["seen"] == want["seen"]
    # one drained run a height, against one vote a run
    assert (got["runs"], want["runs"]) == (HEIGHTS, 2 * N_VALS * HEIGHTS)


def test_a_corrupted_vote_is_refused_alone_and_its_height_commits(
        chain, tmp_path, monkeypatch):
    got = _follow(chain, tmp_path, monkeypatch, batched=True)
    bad = {(v.validator_index, v.signature) for v in chain.bad}
    assert len(bad) == 2 and got["hashes"][BAD_H] == chain.block_hash[BAD_H]
    seen = got["seen"][BAD_H]
    assert not bad & set(seen)
    # the quorum stands without the refused precommit
    assert sum(v is not None for v in seen) * 3 > 2 * N_VALS
    # every signature the node keeps in a seen commit verifies
    pubs = {v.address: v.pub_key for v in
            sm.load_state_from_db_or_genesis(MemDB(), chain.doc)
            .validators.validators}
    for h, votes in got["seen_votes"].items():
        for v in votes:
            assert pubs[v.validator_address].verify_bytes(
                v.sign_bytes(CHAIN_ID), v.signature), (h, v.validator_index)


@pytest.mark.parametrize("batched", [True, False])
def test_the_vote_path_counts_what_was_sent_and_refused(
        chain, tmp_path, monkeypatch, batched):
    got = _follow(chain, tmp_path, monkeypatch, batched=batched)
    n = N_VALS * HEIGHTS
    assert got["received"] == {("prevote",): n, ("precommit",): n}
    assert got["results"] == {("added",): 2 * n - 2, ("bad_signature",): 2}
    assert got["run_count"] == got["runs"]
    assert got["run_sum"] == 2 * n


@pytest.mark.parametrize("batched", [True, False])
def test_every_peer_vote_is_logged_from_the_bytes_it_arrived_as(
        chain, tmp_path, monkeypatch, batched):
    got = _follow(chain, tmp_path, monkeypatch, batched=batched)
    pushed = [m for h in range(1, HEIGHTS + 1) for _, m in chain.pushes[h]]
    sent = [(_Peer.id, m) for m in pushed if isinstance(m, VoteMessage)]
    # the proposals and block parts are encoded again
    assert got["wal_records"] == {("arrived",): len(sent),
                                  ("encoded",): len(pushed) - len(sent)}
    # in the order received, and each replays as the vote that was sent
    assert got["wal_votes"] == sent
