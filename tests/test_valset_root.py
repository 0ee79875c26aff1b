"""A validator set knows its own Merkle root (types/validator_set.py
hash() memo), and fast sync hands the commit it verified down to
validate_block keyed by that root (state/validation.py VerifiedCommit,
blockchain/reactor.py _apply_verified).
"""

import os
import sys
from types import SimpleNamespace

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_state import make_genesis, sign_commit  # noqa: E402

from tendermint_tpu import state as sm
from tendermint_tpu.abci.example.kvstore import PersistentKVStoreApplication
from tendermint_tpu.blockchain.pool import _Requester
from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import merkle, pubkey_to_bytes
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.proxy import AppConns, local_client_creator
from tendermint_tpu.state.validation import VerifiedCommit
from tendermint_tpu.types import BlockID, serde
from tendermint_tpu.types.block import Commit, make_part_set
from tendermint_tpu.types.validator_set import (
    ErrInvalidCommit,
    Validator,
    ValidatorSet,
    random_validator_set,
)


# --- (a) the memo is never stale ---------------------------------------


def _root(vs) -> bytes:
    return merkle.hash_from_byte_slices(
        [v.hash_bytes() for v in vs.validators])


def _newcomer(power=7):
    return Validator.new(PrivKeyEd25519.generate().pub_key(), power)


def _add(vs):
    vs.update_with_changes([_newcomer()])
    return vs


def _remove(vs):
    gone = vs.validators[1]
    vs.update_with_changes([Validator(gone.address, gone.pub_key, 0)])
    return vs


def _repower(vs):
    v = vs.validators[2]
    vs.update_with_changes([Validator(v.address, v.pub_key, 99)])
    return vs


def _update_a_copy(vs):
    cp = vs.copy()
    cp.update_with_changes([_newcomer()])
    # the copy dropped the root it was given; the original keeps its own
    assert cp.hash() == _root(cp) != vs.hash() == _root(vs)
    return cp


def _increment(vs):
    vs.increment_proposer_priority(3)
    return vs


def _rescale_shift(vs):
    vs.validators[0].proposer_priority = 10**9
    vs._rescale_priorities(2 * vs.total_voting_power())
    vs._shift_by_avg_priority()
    return vs


_PATHS = {
    "init": lambda vs: ValidatorSet(vs.validators),
    "copy": lambda vs: vs.copy(),
    "serde": lambda vs: serde.valset_from(
        serde.unpack(serde.pack(serde.valset_obj(vs)))),
    "add": _add,
    "remove": _remove,
    "repower": _repower,
    "update_a_copy": _update_a_copy,
    "increment_proposer_priority": _increment,
    "rescale_shift": _rescale_shift,
}
_CHANGES_ROOT = {"add", "remove", "repower", "update_a_copy"}


@pytest.mark.parametrize("path", sorted(_PATHS))
@pytest.mark.parametrize("asked_before", [True, False])
def test_hash_equals_the_root_recomputed(path, asked_before):
    """Asked both before and after every way a set is built or changed,
    hash() is the root over [v.hash_bytes() ...] recomputed from
    scratch; asking first plants the memo a faulty path would leave
    stale."""
    vs, _ = random_validator_set(5, 10)
    before = _root(vs)
    if asked_before:
        assert vs.hash() == before
    out = _PATHS[path](vs)
    assert out.hash() == _root(out)
    assert out.hash() == _root(out)  # and the remembered one too
    assert (out.hash() != before) == (path in _CHANGES_ROOT)


def test_copy_carries_the_memo_and_counters_tell():
    m = prometheus_metrics("t_vh")
    crypto_batch.set_metrics(m.crypto)
    try:
        vs, _ = random_validator_set(4, 10)
        vs.hash()
        cp = vs.copy()
        cp.increment_proposer_priority(1)
        assert cp.hash() == vs.hash()
        cp.update_with_changes([_newcomer()])
        cp.hash()
    finally:
        crypto_batch.set_metrics(None)
    text = m.registry.render()
    assert 't_vh_types_valset_hash_total{result="computed"} 2' in text
    assert 't_vh_types_valset_hash_total{result="memo"} 2' in text


def test_a_set_built_through_new_has_no_memo():
    vs, _ = random_validator_set(3, 10)
    bare = ValidatorSet.__new__(ValidatorSet)
    bare.validators = [v.copy() for v in vs.validators]
    bare._total = None
    bare.proposer = None
    assert bare.hash() == vs.hash() == _root(bare)


# --- (b) validate_block and the record ---------------------------------


def _executor(metrics=None):
    conns = AppConns(local_client_creator(
        PersistentKVStoreApplication(MemDB())))
    conns.start()
    return sm.BlockExecutor(MemDB(), conns.consensus, metrics=metrics)


def _genesis_state(n=4):
    doc, keys = make_genesis(n)
    return sm.load_state_from_db_or_genesis(MemDB(), doc), keys


def _make_block(state, keys, flip_signature=False, txs=()):
    """The next block on `state`, its LastCommit signed by every key
    that sits in the set of that height; flip_signature corrupts one
    precommit BEFORE the header takes the commit's hash, so only
    verify_commit can refuse the block."""
    height = state.last_block_height + 1
    commit = None
    if height > 1:
        last = SimpleNamespace(validators=state.last_validators,
                               chain_id=state.chain_id)
        commit = sign_commit(
            last, state.last_block_id, height - 1, 0,
            [k for k in keys
             if state.last_validators.has_address(k.pub_key().address())],
            time_ns=1_700_000_100_000_000_000 + height)
        if flip_signature:
            v = commit.precommits[1]
            v.signature = bytes([v.signature[0] ^ 1]) + v.signature[1:]
    time_ns = (sm.state.median_time(commit, state.last_validators)
               if commit is not None else state.last_block_time)
    block = state.make_block(height, list(txs), commit, [],
                             state.validators.get_proposer().address,
                             time_ns=time_ns)
    return block, BlockID(block.hash(), make_part_set(block).header())


def _state_at_height_1():
    state, keys = _genesis_state()
    block, block_id = _make_block(state, keys)
    return _executor().apply_block(state, block_id, block), keys


def _record(state, block, **other) -> VerifiedCommit:
    rec = VerifiedCommit(block.last_commit, state.last_validators.hash(),
                         state.chain_id, state.last_block_id,
                         block.header.height - 1)
    return rec._replace(**other)


def _copy_of(commit) -> Commit:
    return Commit(commit.block_id, list(commit.precommits))


_MISMATCHES = {
    "another_commit_object": lambda s, b: _record(
        s, b, commit=_copy_of(b.last_commit)),
    "another_sets_root": lambda s, b: _record(
        s, b, valset_root=random_validator_set(4, 10)[0].hash()),
    "another_block_id": lambda s, b: _record(
        s, b, block_id=BlockID(b"\x07" * 32, s.last_block_id.parts_header)),
    "another_height": lambda s, b: _record(s, b, height=b.header.height),
    "another_chain_id": lambda s, b: _record(s, b, chain_id="elsewhere"),
    "no_record": lambda s, b: None,
}


@pytest.mark.parametrize("case", sorted(_MISMATCHES))
def test_a_record_that_does_not_match_verifies_in_full(case):
    state, keys = _state_at_height_1()
    bad, _ = _make_block(state, keys, flip_signature=True)
    with pytest.raises(ErrInvalidCommit):
        sm.validate_block(state, bad,
                          verified_last_commit=_MISMATCHES[case](state, bad))
    good, _ = _make_block(state, keys)
    assert sm.validate_block(
        state, good,
        verified_last_commit=_MISMATCHES[case](state, good)) == "verified"


def test_the_matching_record_skips_verify_commit(monkeypatch):
    state, keys = _state_at_height_1()
    block, _ = _make_block(state, keys)
    calls = []
    real = ValidatorSet.verify_commit
    monkeypatch.setattr(
        ValidatorSet, "verify_commit",
        lambda self, *a: (calls.append(a), real(self, *a))[1])
    assert sm.validate_block(state, block) == "verified"
    assert len(calls) == 1
    assert sm.validate_block(
        state, block,
        verified_last_commit=_record(state, block)) == "handed_down"
    assert len(calls) == 1


def test_the_structural_checks_stay_with_a_matching_record():
    state, keys = _state_at_height_1()
    block, _ = _make_block(state, keys)
    block.header.time += 1  # no longer the median of the commit's times
    with pytest.raises(sm.ErrInvalidBlock, match="median"):
        sm.validate_block(state, block,
                          verified_last_commit=_record(state, block))
    block, _ = _make_block(state, keys)
    block.last_commit.precommits.pop()
    with pytest.raises(Exception):  # last_commit_hash, then the size
        sm.validate_block(state, block,
                          verified_last_commit=_record(state, block))


def test_first_block_has_no_last_commit_to_check():
    state, keys = _genesis_state()
    block, _ = _make_block(state, keys)
    assert sm.validate_block(state, block) is None


def test_apply_block_takes_the_record_once():
    """The executor's verified_last_commit serves the next apply_block
    only, and is counted."""
    m = prometheus_metrics("t_lc")
    state, keys = _genesis_state()
    executor = _executor(m.state)
    b1, id1 = _make_block(state, keys)
    state = executor.apply_block(state, id1, b1)
    b2, id2 = _make_block(state, keys)
    executor.verified_last_commit = _record(state, b2)
    state = executor.apply_block(state, id2, b2)
    assert executor.verified_last_commit is None
    b3, id3 = _make_block(state, keys)
    executor.apply_block(state, id3, b3)
    text = m.registry.render()
    assert 't_lc_state_last_commit_check_total{result="handed_down"} 1' in text
    assert 't_lc_state_last_commit_check_total{result="verified"} 1' in text


# --- (c) a toy fast sync with one corrupted commit ---------------------


def _chain(n, txs_at=None):
    """Blocks 1..n of a 4-validator kvstore chain, each applied by a
    generating executor so every header field holds. -> (genesis state,
    {height: block}, state after n)."""
    state, keys = _genesis_state()
    genesis, executor, blocks = state.copy(), _executor(), {}
    txs_at = txs_at or {}
    for h in range(1, n + 1):
        block, block_id = _make_block(state, keys + txs_at.get("keys", []),
                                      txs=txs_at.get(h, ()))
        blocks[h] = block
        state = executor.apply_block(state, block_id, block)
    return genesis, blocks, state


def _with_corrupted_last_commit(block):
    """A copy of `block` whose LastCommit has one flipped signature bit
    and whose header names that commit's hash, so only the signature
    check can refuse it."""
    bad = serde.decode_block(block.encode())
    v = bad.last_commit.precommits[2]
    v.signature = bytes([v.signature[0] ^ 1]) + v.signature[1:]
    bad.header.last_commit_hash = bad.last_commit.hash()
    return bad


def _joiner(genesis, blocks):
    executor = _executor()
    checked = {}
    real = executor.validate_block

    def spy(state, block, **kw):
        checked[block.header.height] = real(state, block, **kw)
        return checked[block.header.height]

    executor.validate_block = spy
    store = BlockStore(MemDB())
    reactor = BlockchainReactor(genesis, executor, store, fast_sync=False)
    for h, b in blocks.items():
        req = _Requester(h)
        req.peer_id = "p1"
        req.block = b
        reactor.pool._requesters[h] = req
    reactor.pool.height = 1
    return reactor, store, checked


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_corrupted_commit_refused_and_redone_height_fully_verified(loop):
    crypto_batch.set_async_enabled(loop == "pipelined")
    genesis, honest, _ = _chain(7)
    served = dict(honest)
    served[4] = _with_corrupted_last_commit(honest[4])  # the commit for 3
    reactor, store, checked = _joiner(genesis, served)

    assert reactor._try_sync_batch() is True
    assert store.height() == 2 and reactor.state.last_block_height == 2
    assert checked == {1: None, 2: "handed_down"}
    assert reactor.pool.height == 3
    # 3, 4 and everything else their peer delivered is asked for again
    assert all(reactor.pool._requesters[h].block is None for h in range(3, 8))

    # the very same copy of 3 comes back, and the honest rest behind it
    for h in range(3, 8):
        reactor.pool._requesters[h].block = honest[h]
        reactor.pool._requesters[h].peer_id = "p2"
    assert reactor._try_sync_batch() is True
    assert store.height() == 6 and reactor.state.last_block_height == 6
    assert checked == {1: None, 2: "handed_down", 3: "verified",
                       4: "handed_down", 5: "handed_down",
                       6: "handed_down"}


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_a_set_that_changes_mid_sync_still_verifies_every_commit(loop):
    """After a validator update the roots of state.validators and
    state.last_validators part for a height; the record follows the set
    the commit was verified under, so every block is still handed down
    and a corrupted commit behind the change is still refused."""
    crypto_batch.set_async_enabled(loop == "pipelined")
    extra = PrivKeyEd25519.generate()
    join = b"val:%s!10" % pubkey_to_bytes(extra.pub_key()).hex().encode()
    genesis, blocks, end = _chain(8, {2: [join], "keys": [extra]})
    assert len(end.validators) == 5 and len(genesis.validators) == 4

    served = dict(blocks)
    served[7] = _with_corrupted_last_commit(blocks[7])  # the commit for 6
    reactor, store, checked = _joiner(genesis, served)
    assert reactor._try_sync_batch() is True
    assert store.height() == 5
    assert checked == {1: None, 2: "handed_down", 3: "handed_down",
                       4: "handed_down", 5: "handed_down"}
