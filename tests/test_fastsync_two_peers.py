"""A joiner that draws on two peers, one of which turns dishonest.

A real BlockchainReactor behind a real switch fast-syncs a seeded
kvstore chain (8-16 validators) from two serving switches over
encrypted connections on loopback, and is held to the plain reference
(benchmark/harness/reference.py: a dict, its Merkle root, OpenSSL one
signature at a time): an honest catch-up takes blocks from both peers
and ends with the chain's hashes, the reference's app hash and its
values; a block refused two past the honest tip costs the peer that
sent it and nobody else, and the honest copies of that height and the
next are applied from the peer that is left (reactor.go:318-330:
RedoRequest for both blocks of the pair; `sync-10kval-2peer`'s fourth
guarantee), in the serial and the pipelined loop, whichever block of
the pair was the altered one.
"""

import os
import sys
import time
import types

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from test_committee_scale import _chain, _wire  # noqa: E402
from test_save_once import _counted  # noqa: E402

from benchmark.harness.peer import make_serving_switch  # noqa: E402
from benchmark.harness.reference import KVReference, verify_one  # noqa: E402
from tendermint_tpu import state as sm
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.example.kvstore import KVStoreApplication
from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.p2p import (MultiplexTransport, NodeInfo, NodeKey,
                                ProtocolVersion, Switch)
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.types import serde

N_BLOCKS, TIP = 30, 20  # the chain; the honest tip T of the dishonest runs
RATE = 5_120_000


@pytest.fixture(scope="module")
def chain():
    return _chain(2**31 + 36, N_BLOCKS)


class Net:
    """The joiner (switch, reactor, kvstore app, stores in memory) and
    two serving switches of the benchmark's harness, dialled in."""

    def __init__(self, genesis, messages):
        self.app = KVStoreApplication()
        conns = AppConns(local_client_creator(self.app))
        conns.start()
        self.store = BlockStore(MemDB())
        self.reactor = BlockchainReactor(
            genesis.copy(), sm.BlockExecutor(MemDB(), conns.consensus),
            self.store, fast_sync=True)
        nk = NodeKey(PrivKeyEd25519.generate())
        info = NodeInfo(protocol_version=ProtocolVersion(), id=nk.id,
                        listen_addr="", network=genesis.chain_id,
                        version="dev", channels=bytes([0x40]), moniker="joiner")
        tr = MultiplexTransport(info, nk)
        tr.listen("127.0.0.1:0")
        info.listen_addr = tr.listen_addr
        self.joiner = Switch(tr)
        self.joiner.add_reactor("BLOCKCHAIN", self.reactor)
        served = types.SimpleNamespace(chain_id=genesis.chain_id,
                                       messages=messages)
        self.switches, self.peers = zip(*(
            make_serving_switch(served, RATE, []) for _ in range(2)))
        self.joiner_id, self.addr = nk.id, tr.listen_addr

    def start(self) -> None:
        self.joiner.start()
        for sw in self.switches:
            sw.start()
            assert sw.dial_peer(self.addr, expect_id=self.joiner_id) is not None

    def stop(self) -> None:
        for sw in (*self.switches, self.joiner):
            sw.stop()

    def applied(self) -> int:
        """The height the loop has applied (the store is a step ahead
        of it while a block is being applied)."""
        return self.reactor.state.last_block_height

    def wait(self, done, what: str, seconds: float = 15.0) -> None:
        end = time.monotonic() + seconds
        while not done():
            assert time.monotonic() < end, (
                f"{what}: store at {self.store.height()}, served "
                f"{[p.served for p in self.peers]}, dropped "
                f"{[p.drop_reason for p in self.peers]}")
            time.sleep(0.01)


def _held_to_the_reference(net, blocks, txs_at, upto: int) -> None:
    ref = KVReference()
    for h in range(1, upto + 1):
        for tx in txs_at[h]:
            ref.deliver(tx)
        assert net.store.load_block_meta(h).block_id.hash == blocks[h].hash()
        assert net.store.load_block(h).encode() == blocks[h].encode()
        # header h+1 carries the app hash after h
        assert blocks[h + 1].header.app_hash == ref.commit()
    assert net.reactor.state.last_block_height == upto
    assert net.reactor.state.app_hash == ref.commit()
    assert ref.kv, "the chain wrote nothing"
    for key, value in ref.kv.items():
        assert net.app.query(abci.RequestQuery(data=key)).value == value


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_an_honest_catch_up_draws_on_both_peers(chain, loop):
    crypto_batch.set_async_enabled(loop == "pipelined")
    genesis, blocks, txs_at = chain
    m = prometheus_metrics("t_2p")
    crypto_batch.set_metrics(m.crypto)
    net = Net(genesis, [_wire(blocks[h]) for h in sorted(blocks)])
    try:
        for p in net.peers:
            p.tip = N_BLOCKS  # told to the joiner when it is dialled
        net.start()
        # block N-1 is applied once block N has brought its commit
        net.wait(lambda: net.applied() == N_BLOCKS - 1, "catch-up")
    finally:
        net.stop()
        crypto_batch.set_metrics(None)
    assert not any(p.dropped.is_set() and "bad block" in str(p.drop_reason)
                   for p in net.peers)
    assert all(p.served > 0 for p in net.peers)
    assert sum(p.served for p in net.peers) == N_BLOCKS
    _held_to_the_reference(net, blocks, txs_at, N_BLOCKS - 1)
    # the pool's counters say the same, by slot and never by id
    received = _counted(m, "t_2p_blockchain_pool_blocks_received_total")
    assert set(received) == {'{slot="0"}', '{slot="1"}'}
    assert sorted(received.values()) == sorted(
        float(p.served) for p in net.peers)
    assert _counted(m, "t_2p_blockchain_pool_requests_total") == received
    assert _counted(m, "t_2p_blockchain_redo_heights_total") == {"": 0.0}


def _altered(blocks, genesis, place: str):
    """(height, the block as the dishonest peer serves it). `second`:
    block T+2 with one signature bit flipped in its LastCommit, so the
    pair (T+1, T+2) fails on its second block; `first`: block T+1 with
    another time and its own LastCommit intact, so it passes as the
    second of (T, T+1) and fails as the first of (T+1, T+2). Both are
    self-consistent: only the commit check can refuse them."""
    if place == "first":
        bad = serde.decode_block(blocks[TIP + 1].encode())
        bad.header.time += 1
        assert bad.hash() != blocks[TIP + 1].hash()
        return TIP + 1, bad
    bad = serde.decode_block(blocks[TIP + 2].encode())
    where = max(i for i, v in enumerate(bad.last_commit.precommits)
                if v is not None)
    vote = bad.last_commit.precommits[where]
    good = vote.signature
    vote.signature = good[:-1] + bytes([good[-1] ^ 1])
    # OpenSSL's word on both, before the joiner is asked
    msg = vote.sign_bytes(genesis.chain_id)
    pub = genesis.validators.validators[where].pub_key.bytes()
    assert verify_one(msg, good, pub) and not verify_one(msg, vote.signature, pub)
    bad.header.last_commit_hash = bad.last_commit.hash()
    return TIP + 2, bad


@pytest.mark.parametrize("place", ["first", "second"])
@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_one_dishonest_peer_does_not_stop_the_catch_up(chain, loop, place):
    crypto_batch.set_async_enabled(loop == "pipelined")
    genesis, blocks, txs_at = chain
    m = prometheus_metrics("t_2p")
    crypto_batch.set_metrics(m.crypto)
    net = Net(genesis, [_wire(blocks[h]) for h in sorted(blocks)])
    a, b = net.peers
    bad_h, bad = _altered(blocks, genesis, place)
    a.poison[bad_h] = _wire(bad)
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        # A alone reports T+2, so A delivers T+1 and T+2; B stands at T
        a.tip, b.tip = TIP + 2, TIP
        net.start()
        net.wait(a.dropped.is_set, "the dishonest peer is dropped")
        net.wait(lambda: net.applied() == TIP, "the honest prefix")
        time.sleep(0.2)  # nothing of A's pair may follow
        assert net.store.height() == TIP and not b.dropped.is_set()
        # the honest copies: B reports T+3, which proves T+2
        b.advertise(TIP + 3)
        net.wait(lambda: net.applied() == TIP + 2, "the honest copies")
        assert not b.dropped.is_set()
        spans = tracer.events()
    finally:
        net.stop()
        crypto_batch.set_metrics(None)
        if not was_on:
            tracer.disable()
    _held_to_the_reference(net, blocks, txs_at, TIP + 2)
    # the spans say what was redone and whose block each was
    redo = [r.args for r in spans if r.name == "fastsync.redo"]
    assert redo == [{"height": TIP + 1, "dropped": 2, "peers": 1}]
    ids = {sw.transport.node_info.id[:8] for sw in net.switches}
    came = [r.args["peer"] for r in spans if r.name == "p2p.recvBlock"]
    assert len(came) == TIP + 2 + 3 and set(came) == ids
    assert net.store.load_block_meta(bad_h).block_id.hash != bad.hash()
    redone = _counted(m, "t_2p_blockchain_redo_heights_total")[""]
    assert redone == 2.0  # T+1 and T+2, A's two deliveries still in the pool
