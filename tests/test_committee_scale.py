"""What a commit of 10,000 precommits costs a height is per-validator
Python, and each routine that was made to do that work once instead of
twice (or once a commit instead of once a vote) has to equal its plain
definition on commits with absent votes, nil votes, a vote for another
block id and mixed timestamps. Small sizes, CPU, seeded.
"""

import os
import random
import sys
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from test_state import make_genesis, sign_commit  # noqa: E402

from benchmark.harness.reference import KVReference, merkle_root  # noqa: E402
from tendermint_tpu import state as sm
from tendermint_tpu.abci.example.kvstore import KVStoreApplication
from tendermint_tpu.blockchain.pool import _Requester
from tendermint_tpu.blockchain.reactor import (
    BLOCKCHAIN_CHANNEL,
    BlockchainReactor,
    _part_set,
)
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.sigcache import SigCache
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.flowrate import Monitor
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.state.state import State
from tendermint_tpu.state.store import load_state, load_validators, save_state
from tendermint_tpu.types import BlockID, serde
from tendermint_tpu.types.basic import (
    VOTE_TYPE_PRECOMMIT,
    PartSetHeader,
    Vote,
    votes_encode,
    votes_sign_bytes,
)
from tendermint_tpu.types.block import Commit, make_part_set
from tendermint_tpu.types.validator_set import random_validator_set

SEEDS = [11, 2**31 + 5, 940000077, 3]
CHAIN_ID = "committee-scale"


def _block_id(rng) -> BlockID:
    return BlockID(rng.randbytes(32),
                   PartSetHeader(rng.randint(1, 40), rng.randbytes(32)))


def _mixed_commit(seed: int) -> Commit:
    """8-64 precommits for one block id, with (at seeded places) absent
    votes, votes for nil, one vote for another block id, a zero and a
    repeated timestamp; signatures are random bytes."""
    rng = random.Random(seed)
    n = rng.randint(8, 64)
    block_id, other = _block_id(rng), _block_id(rng)
    height, round_ = rng.randint(1, 2**40), rng.choice([0, 0, 1, 300])
    base = 1_700_000_000_000_000_000 + rng.randint(0, 10**12)
    votes = []
    for i in range(n):
        votes.append(Vote(rng.randbytes(20), i, height, round_,
                          base + rng.choice([0, 1, i, 10**9 + i]),
                          VOTE_TYPE_PRECOMMIT, block_id, rng.randbytes(64)))
    places = rng.sample(range(n), 5)
    votes[places[0]] = None
    votes[places[1]] = None
    votes[places[2]].block_id = BlockID()
    votes[places[3]].block_id = other
    votes[places[4]].timestamp = 0
    return Commit(block_id, votes)


def _split_rule_root(items) -> bytes:
    """The tree as crypto/merkle.py defines it: split at the largest
    power of two below the count."""
    if not items:
        return merkle._sha256(b"")
    if len(items) == 1:
        return merkle.leaf_hash(items[0])
    k = merkle._split_point(len(items))
    return merkle.inner_hash(_split_rule_root(items[:k]),
                             _split_rule_root(items[k:]))


# --- the vote passes ----------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_spliced_sign_bytes_equal_each_votes_own(seed):
    votes = [v for v in _mixed_commit(seed).precommits if v is not None]
    assert votes_sign_bytes(CHAIN_ID, votes) == [
        v.sign_bytes(CHAIN_ID) for v in votes]
    assert votes_sign_bytes(CHAIN_ID, []) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_vote_encodings_in_one_pass_equal_each_votes_own(seed):
    commit = _mixed_commit(seed)
    votes = commit.precommits + [Vote(b"", -1, 0, 0, 0, 0, BlockID(), b"")]
    assert votes_encode(votes) == [
        b"" if v is None else v.encode() for v in votes]


@pytest.mark.parametrize("seed", SEEDS)
def test_commit_hash_is_the_root_over_vote_encodings(seed):
    commit = _mixed_commit(seed)
    leaves = [b"" if v is None else v.encode() for v in commit.precommits]
    assert commit.hash() == _split_rule_root(leaves) == merkle_root(leaves)


@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 8, 13, 64, 65, 100, 257])
def test_level_walk_equals_the_split_rule(n):
    rng = random.Random(n)
    items = [rng.randbytes(rng.randint(0, 40)) for _ in range(n)]
    assert merkle.hash_from_byte_slices(items) == _split_rule_root(items)
    if n:
        root, proofs = merkle.proofs_from_byte_slices(items)
        assert root == _split_rule_root(items)
        assert all(p.verify(root, it) for p, it in zip(proofs, items))


# --- commit verification -------------------------------------------------


def _signed_commit(seed: int):
    """A validator set of 8-64 and a commit over it: most sign the block,
    some are absent, one signs nil and one another block; every
    signature is genuine."""
    rng = random.Random(seed)
    n = rng.randint(8, 64)
    vals, keys = random_validator_set(n, 10)
    block_id, other = _block_id(rng), _block_id(rng)
    height = rng.randint(2, 10**6)
    places = rng.sample(range(n), 4)
    votes = []
    for i, key in enumerate(keys):
        if i in places[:2]:
            votes.append(None)
            continue
        bid = {places[2]: BlockID(), places[3]: other}.get(i, block_id)
        v = Vote(vals.validators[i].address, i, height, 0,
                 1_700_000_000_000_000_000 + rng.choice([0, i, 10**9]),
                 VOTE_TYPE_PRECOMMIT, bid)
        v.signature = key.sign(v.sign_bytes(CHAIN_ID))
        votes.append(v)
    return vals, Commit(block_id, votes), block_id, height


@pytest.mark.parametrize("seed", SEEDS)
def test_batch_holds_each_present_votes_own_triple(seed):
    vals, commit, block_id, height = _signed_commit(seed)
    bv, entries = vals._prepare_commit_verify(CHAIN_ID, block_id, height,
                                              commit)
    present = [(i, v) for i, v in enumerate(commit.precommits)
               if v is not None]
    assert [(i, v) for i, v, _ in entries] == present
    assert bv._items == [
        (v.sign_bytes(CHAIN_ID), v.signature,
         vals.validators[i].pub_key.bytes()) for i, v in present]
    assert all(val is vals.validators[i] for i, _, val in entries)
    vals.verify_commit(CHAIN_ID, block_id, height, commit)  # +2/3: passes


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_one_flipped_bit_is_refused_wherever_it_sits(seed):
    vals, commit, block_id, height = _signed_commit(seed)
    last = max(i for i, v in enumerate(commit.precommits) if v is not None)
    v = commit.precommits[last]
    v.signature = v.signature[:-1] + bytes([v.signature[-1] ^ 0x40])
    with pytest.raises(Exception, match=f"validator {last}"):
        vals.verify_commit(CHAIN_ID, block_id, height, commit)


def test_the_sig_cache_serves_a_commit_verified_before():
    """The funnel's verdicts with the verified-signature cache on: the
    batch built from spliced sign-bytes hits the entries the same
    commit left."""
    vals, commit, block_id, height = _signed_commit(SEEDS[0])
    cache = SigCache(4096)
    crypto_batch.set_sig_cache(cache)
    try:
        bv, entries = vals._prepare_commit_verify(CHAIN_ID, block_id, height,
                                                  commit)
        router = crypto_batch.AdaptiveBatchVerifier(
            crypto_batch.CPUBatchVerifier, min_device_batch=1)
        for item in bv._items:
            router.add(*item)
        assert router.verify() == [True] * len(entries)
        assert router.verify() == [True] * len(entries)  # all hits now
        assert cache.hits == len(entries)
        for v, (msg, sig, pk) in zip((v for _, v, _ in entries), bv._items):
            assert cache.peek(cache.key(v.sign_bytes(CHAIN_ID), sig, pk))
    finally:
        crypto_batch.set_sig_cache(None)


# --- validator sets and the state ----------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_address_search_finds_members_and_no_stranger(seed):
    rng = random.Random(seed)
    vals, _ = random_validator_set(rng.randint(8, 64), 10)
    for i, v in enumerate(vals.validators):
        assert vals.get_by_address(v.address) == (i, v)
        assert vals.has_address(v.address)
    for stranger in (b"", b"\x00" * 20, b"\xff" * 20, rng.randbytes(20)):
        assert vals.get_by_address(stranger) == (-1, None)
        assert not vals.has_address(stranger)
    cp = vals.copy()
    assert cp.proposer is not vals.proposer
    assert cp.proposer.address == vals.proposer.address
    assert cp.proposer is cp.validators[
        cp.get_by_address(cp.proposer.address)[0]]


def test_a_set_out_of_order_is_refused_on_decode():
    vals, _ = random_validator_set(9, 10)
    obj = serde.valset_obj(vals)
    assert serde.valset_from(obj).hash() == vals.hash()
    obj[0][2], obj[0][5] = obj[0][5], obj[0][2]
    with pytest.raises(ValueError, match="not sorted"):
        serde.valset_from(obj)
    obj = serde.valset_obj(vals)
    obj[0][3] = obj[0][2]
    with pytest.raises(ValueError, match="duplicate"):
        serde.valset_from(obj)


@pytest.mark.parametrize("seed", SEEDS)
def test_state_bytes_and_the_stores_read_back_are_unchanged(seed):
    rng = random.Random(seed)
    doc, _ = make_genesis(rng.randint(8, 64))
    db = MemDB()
    state = sm.load_state_from_db_or_genesis(db, doc)
    state.next_validators.increment_proposer_priority(rng.randint(1, 5))
    raw = state.to_bytes()
    # the plain definition: every validator's five fields, set by set
    assert raw == serde.pack(state.to_obj())
    assert serde.unpack(raw)[6][0] == [
        [v.address, b"\x01" + v.pub_key.bytes(), v.voting_power,
         v.proposer_priority, b""] for v in state.validators.validators]
    back = State.from_bytes(raw)
    assert back.to_bytes() == raw and back.equals(state)
    assert back.validators.proposer.address == state.validators.proposer.address
    save_state(db, state)
    assert load_state(db).to_bytes() == raw
    assert load_validators(db, 1).hash() == state.validators.hash()
    assert [v.proposer_priority for v in load_validators(db, 2).validators] \
        != [0] * len(state.validators)


def test_a_committee_of_10000_is_one_batch_of_10240():
    from tendermint_tpu.crypto.jaxed25519.verify import _bucket

    assert _bucket(10000) == 10240
    assert _bucket(500) == 512 and _bucket(10240) == 10240


# --- blocks, part sets, the stores ---------------------------------------


def _executor():
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    return sm.BlockExecutor(MemDB(), conns.consensus)


def _chain(seed: int, n_blocks: int):
    """A seeded kvstore chain of 8-16 validators, each block applied by
    a generating executor so that every header field holds; some
    validators sit out of some commits. -> (genesis state, {h: block},
    {h: txs})."""
    rng = random.Random(seed)
    doc, keys = make_genesis(rng.randint(8, 16))
    state = sm.load_state_from_db_or_genesis(MemDB(), doc)
    genesis, executor, blocks, txs_at = state.copy(), _executor(), {}, {}
    for h in range(1, n_blocks + 1):
        commit = None
        if h > 1:
            absent = rng.sample(range(len(keys)), rng.randint(0, len(keys) // 5))
            signers = [k for i, k in enumerate(keys) if i not in absent]
            commit = sign_commit(
                type("S", (), {"validators": state.last_validators,
                               "chain_id": state.chain_id}),
                state.last_block_id, h - 1, 0, signers,
                time_ns=1_700_000_100_000_000_000 + 1000 * h)
            for v in commit.precommits:
                if v is not None:  # mixed timestamps, signed as such
                    v.timestamp += rng.randint(0, 999)
                    key = keys[v.validator_index]
                    v.signature = key.sign(v.sign_bytes(state.chain_id))
        txs = [b"k%03d=%s" % (rng.randint(0, 40), rng.randbytes(12).hex().encode())
               for _ in range(rng.randint(0, 6))]
        when = (sm.state.median_time(commit, state.last_validators)
                if commit is not None else state.last_block_time)
        block = state.make_block(h, txs, commit, [],
                                 state.validators.get_proposer().address,
                                 time_ns=when)
        block_id = BlockID(block.hash(), make_part_set(block).header())
        blocks[h], txs_at[h] = block, txs
        state = executor.apply_block(state, block_id, block)
    return genesis, blocks, txs_at


def _wire(block) -> bytes:
    return serde.pack(["block_response", serde.block_obj(block)])


@pytest.fixture(scope="module")
def chain():
    return _chain(SEEDS[1], 8)


@pytest.mark.parametrize("height", [1, 2, 5, 8])
def test_a_block_decoded_from_the_wire_encodes_to_the_same_bytes(chain, height):
    """serde is deterministic: what a block_response carries after its
    head is the block's own encoding, so a part set cut from it is
    make_part_set(block), header and every part."""
    _, blocks, _ = chain
    msg = _wire(blocks[height])
    head = serde.pack(["block_response", None])[:-1]
    assert msg.startswith(head)
    decoded = serde.block_from(serde.unpack(msg)[1])
    assert decoded.encode() == msg[len(head):] == blocks[height].encode()
    decoded.arrived_as = msg[len(head):]
    cut, plain = _part_set(decoded), make_part_set(blocks[height])
    assert cut.header() == plain.header()
    for i in range(plain.total()):
        a, b = cut.get_part(i), plain.get_part(i)
        assert (a.index, a.bytes, a.proof) == (b.index, b.bytes, b.proof)
    # a block that did not come off the wire is encoded
    assert _part_set(blocks[height]).header() == plain.header()
    if decoded.last_commit is not None:
        shared = [v for v in decoded.last_commit.precommits if v is not None]
        assert all(v.block_id is decoded.last_commit.block_id for v in shared)
        assert decoded.last_commit.hash() == blocks[height].last_commit.hash()


def test_the_store_reads_back_what_fast_sync_saved(chain):
    _, blocks, _ = chain
    store = BlockStore(MemDB())
    for h in range(1, 8):
        store.save_block(blocks[h], _part_set(blocks[h]),
                         blocks[h + 1].last_commit)
    for h in range(1, 8):
        want = serde.encode_commit(blocks[h + 1].last_commit)
        assert serde.encode_commit(store.load_seen_commit(h)) == want
        if h < 7:
            assert serde.encode_commit(store.load_block_commit(h)) == want
        assert store.load_block(h).encode() == blocks[h].encode()
        assert store.load_block_meta(h).block_id.parts_header == \
            make_part_set(blocks[h]).header()


# --- the link's limiter --------------------------------------------------


def test_the_limiter_counts_the_seconds_it_slept():
    mon = Monitor()
    assert mon.limit(1000, 0) == 1000 and mon.throttled_s == 0.0
    mon.update(mon.limit(100_000, 1_000_000))  # inside the idle credit
    assert mon.throttled_s == 0.0
    t0 = time.monotonic()
    for _ in range(40):
        mon.update(5_000)
        mon.limit(5_000, 1_000_000)
    assert 0.0 < mon.throttled_s <= time.monotonic() - t0


def test_a_throttled_connection_counts_and_records_its_stretches():
    import socket
    import threading

    from tendermint_tpu.p2p.base_reactor import ChannelDescriptor
    from tendermint_tpu.p2p.conn.connection import MConnConfig, MConnection

    class Plain:
        """A connection with no encryption: the limiter is under test."""

        def __init__(self, sock):
            self.sock = sock

        def write(self, data):
            self.sock.sendall(data)

        def read_exact(self, n):
            buf = b""
            while len(buf) < n:
                chunk = self.sock.recv(n - len(buf))
                if not chunk:
                    raise ConnectionError("closed")
                buf += chunk
            return buf

        def close(self):
            self.sock.close()

    m = prometheus_metrics("t_thr")
    a, b = socket.socketpair()
    got = threading.Event()
    desc = [ChannelDescriptor(id=0x40, priority=1, send_queue_capacity=4,
                              recv_message_capacity=1 << 20)]
    slow = MConnConfig(send_rate=400_000, recv_rate=200_000)
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    tracer.clear()
    sender = MConnection(Plain(a), desc, lambda c, msg: None,
                         lambda e: None, slow, metrics=m.p2p)
    receiver = MConnection(Plain(b), desc, lambda c, msg: got.set(),
                           lambda e: None, slow, metrics=m.p2p)
    try:
        sender.start()
        receiver.start()
        # idle credit is one second of the rate: send well past it
        assert sender.send(0x40, os.urandom(360_000))
        assert got.wait(20)
        names = {r.name for r in tracer.events()}
    finally:
        sender.stop()
        receiver.stop()
        if not was_on:
            tracer.disable()
    assert "p2p.recvThrottle" in names
    text = m.registry.render()
    line = next(ln for ln in text.splitlines() if ln.startswith(
        't_thr_p2p_throttled_seconds_total{direction="recv"}'))
    assert 0.1 < float(line.split()[-1]) < 20


# --- a fast sync through the reactor, against the plain reference --------


class _Peer:
    id = "p1"

    def __init__(self):
        self.sent = []

    def is_running(self):
        return False

    def try_send(self, ch_id, msg):
        self.sent.append(serde.unpack(msg))
        return True


def _joiner(genesis, n_blocks):
    executor = _executor()
    store = BlockStore(MemDB())
    reactor = BlockchainReactor(genesis, executor, store, fast_sync=False)
    for h in range(1, n_blocks + 1):
        req = _Requester(h)
        req.peer_id = "p1"
        reactor.pool._requesters[h] = req
    reactor.pool.height = 1
    return reactor, store


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_fast_sync_off_the_wire_equals_the_reference(chain, loop):
    """Blocks handed to the reactor as the bytes a peer sends: every
    applied height has the served chain's block hash and the plain
    reference's app hash, and a commit with one flipped signature bit
    is refused at its height."""
    crypto_batch.set_async_enabled(loop == "pipelined")
    genesis, blocks, txs_at = chain
    bad = serde.decode_block(blocks[6].encode())  # carries the commit for 5
    where = max(i for i, v in enumerate(bad.last_commit.precommits)
                if v is not None)  # the upper half of the batch
    v = bad.last_commit.precommits[where]
    v.signature = v.signature[:-1] + bytes([v.signature[-1] ^ 1])
    bad.header.last_commit_hash = bad.last_commit.hash()
    served = dict(blocks)
    served[6] = bad

    reactor, store = _joiner(genesis, len(blocks))
    peer = _Peer()
    for h in sorted(served):
        reactor.receive(BLOCKCHAIN_CHANNEL, peer, _wire(served[h]))
    assert all(reactor.pool._requesters[h].block.arrived_as is not None
               for h in served)
    assert reactor._try_sync_batch() is True

    assert store.height() == 4 == reactor.state.last_block_height
    # 5, 6 and everything else their peer delivered is asked for again
    assert all(reactor.pool._requesters[h].block is None
               and reactor.pool._requesters[h].peer_id is None
               for h in range(5, len(blocks) + 1))
    ref = KVReference()
    for h in range(1, 5):
        for tx in txs_at[h]:
            ref.deliver(tx)
        meta = store.load_block_meta(h)
        assert meta.block_id.hash == blocks[h].hash()
        assert meta.block_id.parts_header == make_part_set(blocks[h]).header()
        assert store.load_block(h).encode() == blocks[h].encode()
        # header h+1 carries the app hash after h
        assert blocks[h + 1].header.app_hash == ref.commit()
    assert reactor.state.app_hash == ref.commit()

    # the honest copies come back from another peer: the rest applies
    peer.id = "p2"
    for h in range(5, len(blocks) + 1):
        reactor.pool._requesters[h].peer_id = "p2"
        reactor.receive(BLOCKCHAIN_CHANNEL, peer, _wire(blocks[h]))
    assert reactor._try_sync_batch() is True
    assert store.height() == 7
    for h in range(5, 8):
        for tx in txs_at[h]:
            ref.deliver(tx)
    assert reactor.state.app_hash == ref.commit()
