"""One node on a four-chip host: the path a default node takes there
(funnel -> JAXBatchVerifier -> verify_batch over a 1-D `dp` mesh) held
to the plain reference, OpenSSL one signature at a time
(benchmark/harness/reference.py, which shares no code with the program),
on four of the conftest's virtual CPU devices. Seeded; nothing here is a
time.

- the chips' shares add up: a corrupted signature in any chip's share,
  on either side of any share boundary, or in the last real lane before
  the padding is the one lane the mask refuses;
- a toy joiner whose verifier sees four devices applies an honest chain
  to the reference's app hashes and refuses a flipped signature bit at
  the validator the reference names, in whichever chip's quarter;
- how many chips a batch gets is one function of its bucket, and the
  warm-up readies exactly the shapes a live batch then asks for;
- the warm-up compiles the psum commit step where a node can reach it
  and nowhere else;
- the spans and counters say how many chips a batch ran on.
"""

import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from benchmark.harness.reference import KVReference, verify_one  # noqa: E402
from tendermint_tpu import state as sm
from tendermint_tpu.abci.example.kvstore import KVStoreApplication
from tendermint_tpu.blockchain.pool import _Requester
from tendermint_tpu.blockchain.reactor import BLOCKCHAIN_CHANNEL, BlockchainReactor
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import kernel_cache
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.crypto.sigcache import SigCache
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.types import BlockID, GenesisDoc, GenesisValidator, serde
from tendermint_tpu.types.basic import VOTE_TYPE_PRECOMMIT, Vote
from tendermint_tpu.types.block import Commit, make_part_set
from tendermint_tpu.types.validator_set import ErrInvalidCommitSignatures

CHIPS = 4
SEED = 2**31 + 33


def _flip(sig: bytes, bit: int) -> bytes:
    return sig[:bit // 8] + bytes([sig[bit // 8] ^ (1 << bit % 8)]) + sig[bit // 8 + 1:]


# --- the chips' shares add up ---------------------------------------------


def _triples(n: int):
    rng = random.Random(SEED + n)
    keys = [PrivKeyEd25519.gen_from_secret(b"four-chip-%d-%d" % (n, i))
            for i in range(n)]
    msgs = [rng.randbytes(110) for _ in range(n)]  # a vote's sign-bytes: nb 2
    return (msgs, [k.sign(m) for k, m in zip(keys, msgs)],
            [k.pub_key().bytes() for k in keys])


def _places(n: int, bucket: int) -> list:
    """A lane inside every chip's share, the lanes on each side of every
    share boundary, and the last real lane before the padding."""
    share = bucket // CHIPS
    places = {n - 1}
    for chip in range(CHIPS):
        places.add(chip * share + share // 2)
        if chip:
            places |= {chip * share - 1, chip * share}
    return sorted(p for p in places if p < n)


@pytest.mark.parametrize("n,bucket", [(7, 8), (100, 128), (1000, 1024)])
def test_the_chips_shares_add_up_to_the_references_mask(n, bucket):
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    msgs, sigs, pks = _triples(n)
    assert V._padded_bucket(n, CHIPS) == bucket == V._padded_bucket(n, 1)
    places = _places(n, bucket)
    share = bucket // CHIPS
    # every chip with a real lane has a place of its own; padding lanes,
    # where there are any, lie in the last chip's share
    assert {p // share for p in places} == set(range((n - 1) // share + 1))
    assert n == bucket or n > bucket - share
    rng = random.Random(SEED)
    bad = {p: _flip(sigs[p], rng.randrange(512)) for p in places}
    assert not any(verify_one(msgs[p], bad[p], pks[p]) for p in places)
    for p in places[:2] + places[-1:]:
        assert verify_one(msgs[p], sigs[p], pks[p])

    # all at once: four chips, one chip and the reference give one mask
    all_bad = [bad.get(i, s) for i, s in enumerate(sigs)]
    want = [i not in bad for i in range(n)]
    assert V.verify_batch(msgs, all_bad, pks, devices=CHIPS) == want
    assert V.verify_batch(msgs, all_bad, pks, devices=1) == want
    # in turn: one corrupted lane is the one lane refused (the largest
    # committee takes each chip's own lane and the last lane in turn,
    # its boundary lanes above)
    in_turn = places if n <= 128 else sorted(
        {c * share + share // 2 for c in range(CHIPS)} & set(places)
        | {n - 1})
    for p in in_turn:
        one = list(sigs)
        one[p] = bad[p]
        mask = V.verify_batch(msgs, one, pks, devices=CHIPS)
        assert [i for i, ok in enumerate(mask) if not ok] == [p]


# --- a toy joiner whose verifier sees four devices ------------------------

N_VALS = 512  # the smallest bucket a four-chip node cuts four ways: 128 a chip


def _chain(n_blocks: int):
    """A seeded kvstore chain of 512 validators, every block applied by a
    generating executor (host verifier) so that every header field
    holds. -> (genesis state, {h: block}, {h: txs})."""
    rng = random.Random(SEED)
    keys = [PrivKeyEd25519.gen_from_secret(b"four-chip-val-%d" % i)
            for i in range(N_VALS)]
    doc = GenesisDoc(
        chain_id="four-chip", genesis_time=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pub_key=k.pub_key(), power=10) for k in keys])
    state = sm.load_state_from_db_or_genesis(MemDB(), doc)
    by_addr = {k.pub_key().address(): k for k in keys}
    genesis, executor, blocks, txs_at = state.copy(), _executor(), {}, {}
    for h in range(1, n_blocks + 1):
        commit = None
        if h > 1:
            vals = state.last_validators
            votes = []
            for i in range(len(vals)):
                addr, _ = vals.get_by_index(i)
                v = Vote(validator_address=addr, validator_index=i, height=h - 1,
                         round=0, timestamp=1_700_000_100_000_000_000 + 1000 * h + i,
                         type=VOTE_TYPE_PRECOMMIT, block_id=state.last_block_id)
                v.signature = by_addr[addr].sign(v.sign_bytes(state.chain_id))
                votes.append(v)
            commit = Commit(block_id=state.last_block_id, precommits=votes)
        txs = [b"k%03d=%s" % (rng.randint(0, 40), rng.randbytes(12).hex().encode())
               for _ in range(rng.randint(1, 5))]
        when = (sm.state.median_time(commit, state.last_validators)
                if commit is not None else state.last_block_time)
        block = state.make_block(h, txs, commit, [],
                                 state.validators.get_proposer().address,
                                 time_ns=when)
        blocks[h], txs_at[h] = block, txs
        state = executor.apply_block(
            state, BlockID(block.hash(), make_part_set(block).header()), block)
    return genesis, blocks, txs_at


def _executor():
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    return sm.BlockExecutor(MemDB(), conns.consensus)


@pytest.fixture(scope="module")
def chain():
    prev = crypto_batch.default_backend_name()
    crypto_batch.set_default_backend("cpu")  # the generator is not under test
    try:
        return _chain(7)
    finally:
        crypto_batch.set_default_backend(prev)


def _see(monkeypatch, n: int) -> None:
    """`n` of the conftest's virtual devices are all the program sees."""
    import jax

    visible = jax.devices()[:n]
    monkeypatch.setattr(jax, "devices", lambda *a: visible)


@pytest.fixture
def four_chip_node(monkeypatch):
    """What a default node is on a four-chip host: the device backend,
    async dispatch on, a sig cache installed, four devices visible."""
    _see(monkeypatch, CHIPS)
    prev = (crypto_batch.default_backend_name(), crypto_batch.async_enabled(),
            crypto_batch.get_sig_cache())
    crypto_batch.set_default_backend("jax")
    crypto_batch.set_async_enabled(True)
    crypto_batch.set_sig_cache(SigCache(4096))
    m = prometheus_metrics("t_four")
    crypto_batch.set_metrics(m.crypto)
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        yield m
    finally:
        if not was_on:
            tracer.disable()
        crypto_batch.set_metrics(None)
        crypto_batch.set_default_backend(prev[0])
        crypto_batch.set_async_enabled(prev[1])
        crypto_batch.set_sig_cache(prev[2])
        crypto_batch.shutdown_dispatchers()


class _Peer:
    id = "p1"

    def is_running(self):
        return False

    def try_send(self, ch_id, msg):
        return True


def _wire(block) -> bytes:
    return serde.pack(["block_response", serde.block_obj(block)])


def _reference_names(chain_id: str, validators, commit) -> list:
    """The validators whose precommit the reference refuses."""
    return [i for i, v in enumerate(commit.precommits)
            if not verify_one(v.sign_bytes(chain_id), v.signature,
                              validators.get_by_index(i)[1].pub_key.bytes())]


@pytest.mark.parametrize("quarter", range(CHIPS))
def test_a_joiner_on_four_devices_keeps_to_the_reference(chain, four_chip_node,
                                                         quarter):
    genesis, blocks, txs_at = chain
    rng = random.Random(SEED + quarter)
    where = quarter * (N_VALS // CHIPS) + rng.randrange(N_VALS // CHIPS)
    bad = serde.decode_block(blocks[6].encode())  # carries the commit for 5
    v = bad.last_commit.precommits[where]
    v.signature = _flip(v.signature, rng.randrange(512))
    bad.header.last_commit_hash = bad.last_commit.hash()
    assert _reference_names("four-chip", genesis.validators, bad.last_commit) == [where]
    assert _reference_names("four-chip", genesis.validators, blocks[6].last_commit) == []

    store = BlockStore(MemDB())
    reactor = BlockchainReactor(genesis, _executor(), store, fast_sync=False)
    peer = _Peer()
    for h in sorted(blocks):
        req = _Requester(h)
        req.peer_id = "p1"
        reactor.pool._requesters[h] = req
    reactor.pool.height = 1
    for h in sorted(blocks):
        reactor.receive(BLOCKCHAIN_CHANNEL, peer, _wire(bad if h == 6 else blocks[h]))
    assert reactor._try_sync_batch() is True

    # every honest height applied, to the reference's app hash; the
    # corrupted commit refused at its height
    assert store.height() == 4 == reactor.state.last_block_height
    assert reactor.pool._requesters[5].block is None
    ref = KVReference()
    for h in range(1, 5):
        for tx in txs_at[h]:
            ref.deliver(tx)
        assert store.load_block_meta(h).block_id.hash == blocks[h].hash()
        assert blocks[h + 1].header.app_hash == ref.commit()
    assert reactor.state.app_hash == ref.commit()
    # ... at the validator the reference names
    block5 = blocks[5]
    block_id = BlockID(block5.hash(), make_part_set(block5).header())
    with pytest.raises(ErrInvalidCommitSignatures,
                       match=f"from validator {where} "):
        genesis.validators.begin_verify_commit(
            "four-chip", block_id, 5, bad.last_commit).result()

    # the batches ran over the four devices, and the node says so
    m = four_chip_node
    spans = [e for e in tracing.get_tracer().events()
             if e.name == "crypto.batchVerify"]
    assert spans and all(e.args["backend"] == "jax" and e.args["ndev"] == CHIPS
                         for e in spans)
    for name in ("verify.pack", "verify.h2d", "verify.launch", "verify.wait"):
        under = [e for e in tracing.get_tracer().events() if e.name == name]
        assert len(under) == len(spans)
        assert all(e.args["ndev"] == CHIPS and e.args["bucket"] == N_VALS
                   for e in under)
    text = m.registry.render()
    count = next(float(ln.split()[-1]) for ln in text.splitlines() if ln.startswith(
        't_four_crypto_batch_lanes_per_device_count{ndev="4"}'))
    total = next(float(ln.split()[-1]) for ln in text.splitlines() if ln.startswith(
        't_four_crypto_batch_lanes_per_device_sum{ndev="4"}'))
    assert count == len(spans) and total == count * N_VALS // CHIPS
    assert crypto_batch.devices_used() >= CHIPS


# --- how many chips a batch gets, and what the warm-up readies -----------


@pytest.fixture
def chips(monkeypatch, request):
    """`request.param` of the conftest's virtual devices are visible."""
    _see(monkeypatch, request.param)
    return request.param


@pytest.mark.parametrize("chips", [1, 4], indirect=True)
def test_chips_a_batch_gets_by_its_bucket(chips):
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    assert V.MULTI_CHIP_MIN_LANES == 512
    table = {n: V.batch_devices(n) for n in
             (1, 8, 9, 64, 65, 256, 257, 500, 512, 513, 2048, 10000)}
    many = chips if chips > 1 else 1
    assert table == {1: 1, 8: 1, 9: 1, 64: 1, 65: 1, 256: 1,  # buckets under 512
                     257: many, 500: many, 512: many, 513: many, 2048: many,
                     10000: many}
    # every chip gets the same number of lanes, whatever the choice
    for n, ndev in table.items():
        assert V._padded_bucket(n, ndev) % ndev == 0
    assert V._padded_bucket(10000, many) // many == 10240 // many


@pytest.mark.parametrize("chips", [4], indirect=True)
def test_the_warm_up_readies_the_shapes_live_batches_ask_for(chips, monkeypatch):
    """A four-chip node warms bucket 8 for one chip and bucket 512 for
    four; a first live batch of either size then asks for exactly those
    shapes: nothing is loaded or compiled for it."""
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    asked: list = []
    real = V._jitted_packed

    def spy(nb, mrows, bpad, ndev):
        asked.append((nb, mrows, bpad, ndev))
        return real(nb, mrows, bpad, ndev)

    monkeypatch.setattr(V, "_jitted_packed", spy)
    prev = crypto_batch.get_sig_cache()
    crypto_batch.set_sig_cache(SigCache(64))  # a default node: no psum step
    try:
        V.warmup(buckets=(8, 512), calibrate=False)
    finally:
        crypto_batch.set_sig_cache(prev)
    warmed = list(asked)
    assert warmed == [(2, 32, 8, 1), (2, 32, 512, 4)]
    ready = kernel_cache.status()
    assert not [k for k in ready["kernels"] if k["kernel"] == "ed25519_commit_step"]
    for n in (8, 512):
        msgs, sigs, pks = _triples(n)
        assert V.verify_batch(msgs, sigs, pks) == [True] * n
    assert asked[len(warmed):] == warmed
    after = kernel_cache.status()
    assert after["compiles"] == ready["compiles"]
    assert after["kernels"] == ready["kernels"]


# --- the warm-up compiles what this node can reach ------------------------


@pytest.mark.parametrize("chips,sig_cache,compiled", [
    (4, False, True),    # a synchronous verify_commit takes the psum step
    (4, True, False),    # a default node: the funnel's cache is in the way
    (1, False, False),   # one chip: there is no psum step
    (1, True, False),
], indirect=["chips"])
def test_the_warm_up_compiles_the_commit_step_only_where_it_is_reached(
        monkeypatch, chips, sig_cache, compiled):
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    made: list = []

    def packed(nb, mrows, bpad, ndev):
        made.append(("ed25519_packed", bpad, ndev))
        return lambda buf: np.zeros((bpad,), bool)

    def commit_fn(ndev):
        made.append(("ed25519_commit_step", ndev))
        return lambda *args: None

    monkeypatch.setattr(V, "_jitted_packed", packed)
    monkeypatch.setattr(V, "_sharded_commit_fn", commit_fn)
    prev = crypto_batch.get_sig_cache()
    crypto_batch.set_sig_cache(SigCache(64) if sig_cache else None)
    try:
        V.warmup(buckets=(8, 512), calibrate=False)
        # what ValidatorSet._run_batch_verify asks before it takes the step
        reachable = chips > 1 and crypto_batch.get_sig_cache() is None
    finally:
        crypto_batch.set_sig_cache(prev)
    assert reachable is compiled
    assert [m for m in made if m[0] == "ed25519_packed"] == [
        ("ed25519_packed", 8, 1), ("ed25519_packed", 512, chips)]
    assert [m for m in made if m[0] == "ed25519_commit_step"] == (
        [("ed25519_commit_step", chips)] * 2 if compiled else [])


# --- the compile-once layer's spans name the chips ------------------------


def test_kernel_spans_carry_the_number_of_chips():
    import jax
    import jax.numpy as jnp

    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        fn = kernel_cache.aot_wrap("four_chip_probe", (SEED,),
                                   jax.jit(lambda x: x + 1), ndev=CHIPS)
        assert int(fn(jnp.zeros((), jnp.int32))) == 1
        spans = [e for e in tracer.events()
                 if e.name in ("kernel.load", "kernel.compile")]
    finally:
        if not was_on:
            tracer.disable()
    # a compile in a checkout's first run, a load from the store after
    assert spans and spans[-1].name in ("kernel.load", "kernel.compile")
    assert all(e.args["ndev"] == CHIPS and e.args["kernel"] == "four_chip_probe"
               for e in spans)
