"""A height is encoded once on its way to disk (PR 35).

A commit keeps the bytes the block store saved it as
(serde.encode_commit, Commit.saved_as), a validator set the bytes the
state store saved it as (serde.encode_valset, ValidatorSet._packed_memo),
and update_state hands the two sets it does not write to down a slot
instead of copying them. The stored bytes are the parent's, byte for
byte: what is held here is that the kept bytes are never stale and that
the counters tell a packing from a kept one.
"""

import os

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

from tendermint_tpu import state as sm
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.example.kvstore import KVStoreApplication
from tendermint_tpu.blockchain import store as block_store
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import PrivKeyEd25519, pubkey_to_bytes
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.state import store as state_store
from tendermint_tpu.state.execution import update_state
from tendermint_tpu.types import (
    VOTE_TYPE_PRECOMMIT,
    BlockID,
    GenesisDoc,
    GenesisValidator,
    Vote,
    serde,
)
from tendermint_tpu.types.block import Commit, make_part_set
from tendermint_tpu.types.validator_set import (
    Validator,
    random_validator_set,
)

CHAIN_ID = "save-once"
UPDATE_AT = 3  # the height whose EndBlock changes the committee


class _RotatingApp(KVStoreApplication):
    """A kvstore whose EndBlock at UPDATE_AT repowers one validator,
    brings one in and sends one away."""

    def __init__(self, updates):
        super().__init__()
        self._updates = updates

    def end_block(self, req):
        res = super().end_block(req)
        if req.height == UPDATE_AT:
            res.validator_updates = list(self._updates)
        return res


def _genesis(n=4):
    vs, keys = random_validator_set(n, 10)
    doc = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pub_key=v.pub_key, power=v.voting_power)
                    for v in vs.validators],
    )
    return doc, keys


def _rotation(keys):
    """(updates, keys by address with the joiner's): keys[0] repowered,
    a newcomer, keys[-1] leaving."""
    joiner = PrivKeyEd25519.generate()
    updates = [
        abci.ValidatorUpdate(pub_key=pubkey_to_bytes(keys[0].pub_key()), power=15),
        abci.ValidatorUpdate(pub_key=pubkey_to_bytes(joiner.pub_key()), power=5),
        abci.ValidatorUpdate(pub_key=pubkey_to_bytes(keys[-1].pub_key()), power=0),
    ]
    by_addr = {k.pub_key().address(): k for k in [*keys, joiner]}
    return updates, by_addr


def _executor(db, doc, updates=()):
    state = sm.load_state_from_db_or_genesis(db, doc)
    conns = AppConns(local_client_creator(_RotatingApp(updates)))
    conns.start()
    return state, sm.BlockExecutor(db, conns.consensus)


def _commit_for(vals, by_addr, block_id, height):
    """The commit for `block_id`, signed by every member of `vals`."""
    precommits = []
    for idx, val in enumerate(vals.validators):
        vote = Vote(
            validator_address=val.address, validator_index=idx, height=height,
            round=0, timestamp=1_700_000_100_000_000_000 + height,
            type=VOTE_TYPE_PRECOMMIT, block_id=block_id)
        vote.signature = by_addr[val.address].sign(vote.sign_bytes(CHAIN_ID))
        precommits.append(vote)
    return Commit(block_id=block_id, precommits=precommits)


def _next_block(state, last_commit, by_addr):
    """(block, parts, block_id, the commit for it) at the state's next
    height: what fast sync holds when it saves and applies a height."""
    height = state.last_block_height + 1
    time_ns = (sm.state.median_time(last_commit, state.last_validators)
               if last_commit is not None else state.last_block_time)
    block = state.make_block(
        height, [b"k%d=v%d" % (height, height)], last_commit, [],
        state.validators.get_proposer().address, time_ns=time_ns)
    parts = make_part_set(block)
    block_id = BlockID(block.hash(), parts.header())
    return block, parts, block_id, _commit_for(
        state.validators, by_addr, block_id, height)


def _plain_state(state) -> bytes:
    return serde.pack(state.to_obj())


def _plain_commit(commit) -> bytes:
    return serde.pack(serde.commit_obj(commit))


def _snapshot(vs):
    return ([(v.address, v.voting_power, v.proposer_priority)
             for v in vs.validators],
            vs.proposer.address if vs.proposer else None)


# --- (a) the stored bytes are the plain packing, at every height -----------


def test_every_height_is_stored_as_the_plain_packing():
    """Eight heights through apply_block and save_block with a power
    change, a joiner and a leaver in the middle: with every memo warm,
    State.to_bytes(), stateKey, validatorsKey:h, C:h and SC:h are what
    serde.pack gives the plain objects."""
    doc, keys = _genesis()
    updates, by_addr = _rotation(keys)
    db, blocks_db = MemDB(), MemDB()
    state, executor = _executor(db, doc, updates)
    store = BlockStore(blocks_db)
    assert state.to_bytes() == _plain_state(state)
    powers, commit = [], None
    for height in range(1, 9):
        block, parts, block_id, seen = _next_block(state, commit, by_addr)
        store.save_block(block, parts, seen)
        state = executor.apply_block(state, block_id, block)
        commit = seen

        assert state.to_bytes() == _plain_state(state)  # memos warm
        assert db.get(state_store._STATE_KEY) == _plain_state(state)
        assert sm.load_state(db).equals(state)
        assert serde.encode_commit(seen) == _plain_commit(seen)
        assert blocks_db.get(block_store._seen_commit_key(height)) == _plain_commit(seen)
        if height > 1:
            assert (blocks_db.get(block_store._commit_key(height - 1))
                    == _plain_commit(block.last_commit))
        # validatorsKey of the height this state makes effective
        changed = state.last_height_validators_changed
        record = [changed, serde.valset_obj(state.next_validators)
                  if changed == height + 2 else None]
        assert db.get(state_store._vals_key(height + 2)) == serde.pack(record)
        powers.append(sorted(v.voting_power for v in state.validators.validators))
    # the rotation is in `validators` from the state after UPDATE_AT + 1 on
    assert powers == ([[10] * 4] * UPDATE_AT
                      + [[5, 10, 10, 15]] * (8 - UPDATE_AT))
    assert state.last_height_validators_changed == UPDATE_AT + 2
    for h in range(1, 11):
        sm.load_validators(db, h)  # every record loads


# --- (b) every writer drops the set's kept bytes ---------------------------


def _uncentre(vs):
    for i, v in enumerate(vs.validators):
        v.proposer_priority = 1000 * (i + 1)
    vs._packed_memo = None  # the test's own write, outside the set's methods


def _rotate(vs):
    vs.increment_proposer_priority(1)


def _update(vs):
    vs.update_with_changes([
        Validator.new(PrivKeyEd25519.generate().pub_key(), 7)])


def _rescale(vs):
    vs._rescale_priorities(1)


def _shift(vs):
    vs._shift_by_avg_priority()


def _change_proposer(vs):
    vs.proposer = next(v for v in vs.validators if v is not vs.proposer)


@pytest.mark.parametrize("write", [
    _rotate, _update, _rescale, _shift, _change_proposer,
], ids=lambda f: f.__name__.lstrip("_"))
def test_a_writer_drops_the_kept_bytes(write):
    vs, _ = random_validator_set(5, 10)
    _uncentre(vs)
    kept = serde.encode_valset(vs)
    assert vs._packed_memo is kept
    assert serde.encode_valset(vs) is kept
    assert vs.copy()._packed_memo is kept  # a copy says the same
    write(vs)
    assert vs._packed_memo is None
    again = serde.encode_valset(vs)
    assert again == serde.pack(serde.valset_obj(vs))
    assert again != kept


def test_a_copy_keeps_and_drops_its_own():
    vs, _ = random_validator_set(4, 10)
    kept = serde.encode_valset(vs)
    cp = vs.copy()
    cp.increment_proposer_priority(1)
    assert vs._packed_memo is kept and cp._packed_memo is None
    assert serde.encode_valset(vs) == serde.pack(serde.valset_obj(vs))
    assert serde.encode_valset(cp) == serde.pack(serde.valset_obj(cp))


# --- (c) sets handed down a slot --------------------------------------------


def test_update_state_hands_two_sets_down_and_copies_the_third():
    doc, keys = _genesis()
    state, _ = _executor(MemDB(), doc)
    block, _, block_id, _ = _next_block(
        state, None, {k.pub_key().address(): k for k in keys})
    responses = sm.execution.ABCIResponses(
        [abci.ResponseDeliverTx() for _ in block.data.txs],
        abci.ResponseEndBlock())
    new = update_state(state, block_id, block.header, responses)
    assert new.validators is state.next_validators
    assert new.last_validators is state.validators
    assert new.next_validators is not state.next_validators
    before = _snapshot(new.validators), _snapshot(new.last_validators)
    old_next = _snapshot(state.next_validators)
    new.next_validators.increment_proposer_priority(3)
    assert (_snapshot(new.validators), _snapshot(new.last_validators)) == before
    assert _snapshot(state.next_validators) == old_next
    # and State.copy() still takes its own
    cp = new.copy()
    assert cp.validators is not new.validators
    assert cp.next_validators is not new.next_validators


# --- (d) a store the parent wrote --------------------------------------------


def test_a_store_written_with_plain_pack_loads_and_saves_back_the_same():
    doc, keys = _genesis()
    by_addr = {k.pub_key().address(): k for k in keys}
    state, executor = _executor(MemDB(), doc)
    commit = None
    for _ in range(3):
        block, _, block_id, seen = _next_block(state, commit, by_addr)
        state, commit = executor.apply_block(state, block_id, block), seen

    # the parent's code path: plain serde.pack of the plain objects
    db, blocks_db = MemDB(), MemDB()
    raw_state = serde.pack(state.to_obj())
    raw_vals = serde.pack([4, serde.valset_obj(state.next_validators)])
    raw_commit = serde.pack(serde.commit_obj(commit))
    db.set(state_store._STATE_KEY, raw_state)
    db.set(state_store._vals_key(4), raw_vals)
    blocks_db.set(block_store._seen_commit_key(3), raw_commit)

    loaded = sm.load_state(db)
    assert loaded.to_bytes() == raw_state
    assert loaded.equals(state)
    resaved = MemDB()
    state_store.save_validators_info(
        resaved, 4, 4, state_store.load_validators(db, 4))
    assert resaved.get(state_store._vals_key(4)) == raw_vals
    state_store.save_state(resaved, loaded)
    assert resaved.get(state_store._STATE_KEY) == raw_state
    again = BlockStore(blocks_db).load_seen_commit(3)
    assert again.saved_as is None and again == commit
    assert serde.encode_commit(again) == raw_commit


# --- (e) the counters tell a packing from kept bytes ------------------------


def _counted(metrics, family):
    out = {}
    for line in metrics.registry.render().splitlines():
        if line.startswith(family):
            name, value = line.rsplit(" ", 1)
            out[name[len(family):]] = float(value)
    return out


def test_fast_sync_packs_one_commit_and_one_set_a_height():
    """A joiner's loop over a served chain: each height saves C:h-1 and
    SC:h and a State of three sets, and packs one commit (the other is
    the object it saved a height ago) and one set (next_validators)."""
    from tendermint_tpu.blockchain.pool import _Requester
    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    doc, keys = _genesis()
    by_addr = {k.pub_key().address(): k for k in keys}
    state, executor = _executor(MemDB(), doc)
    served, commit = [], None
    for _ in range(5):
        block, _, block_id, seen = _next_block(state, commit, by_addr)
        state, commit = executor.apply_block(state, block_id, block), seen
        # as it comes off the wire: objects of its own
        served.append(serde.decode_block(block.encode()))

    m = prometheus_metrics("t_so")
    crypto_batch.set_metrics(m.crypto)
    try:
        joiner, joiner_exec = _executor(MemDB(), doc)
        store = BlockStore(MemDB())
        reactor = BlockchainReactor(joiner, joiner_exec, store, fast_sync=False)
        for block in served:
            req = _Requester(block.header.height)
            req.peer_id, req.block = "p1", block
            reactor.pool._requesters[block.header.height] = req
        reactor.pool.height = 1
        at_start = _counted(m, "t_so_store_")
        assert reactor._try_sync_batch() is True
        assert store.height() == 4
        gained = {k: v - at_start.get(k, 0.0)
                  for k, v in _counted(m, "t_so_store_").items()}
    finally:
        crypto_batch.set_metrics(None)
    assert gained == {
        "heights_saved_total": 4.0,
        'encodings_total{kind="commit"}': 4.0,
        'encodings_total{kind="valset"}': 4.0,
    }
    assert reactor.state.to_bytes() == _plain_state(reactor.state)
    for h in range(1, 4):
        assert store.load_block_commit(h) == store.load_seen_commit(h)
