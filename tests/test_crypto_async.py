"""Async verification pipeline (crypto/batch.py verify_async /
VerifyFuture / dispatchers) and the fast-sync two-stage pipeline
(blockchain/reactor.py _try_sync_batch_pipelined,
types/validator_set.py begin_verify_commit).
"""

import os
import threading
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import PrivKeyEd25519


def _triple(i=0, valid=True):
    sk = PrivKeyEd25519.gen_from_secret(b"async-%d" % i)
    msg = b"amsg-%d" % i
    sig = sk.sign(msg)
    if not valid:
        sig = bytes([sig[0] ^ 1]) + sig[1:]
    return (msg, sig, sk.pub_key().bytes())


class TestVerifyFuture:
    def test_async_matches_sync_in_add_order(self):
        items = [_triple(i, valid=(i % 3 != 0)) for i in range(10)]
        want = crypto_batch.batch_verify(items, backend="cpu")
        bv = crypto_batch.CPUBatchVerifier()
        for t in items:
            bv.add(*t)
        fut = bv.verify_async()
        assert fut.result(timeout=30) == want
        assert fut.done()
        # result() is idempotent
        assert fut.result(timeout=1) == want

    def test_each_future_gets_its_own_mask(self):
        """Several batches in flight on one dispatcher: every future
        resolves to ITS batch's mask, in its own add order."""
        futs, wants = [], []
        for k in range(6):
            items = [_triple(100 + 10 * k + j, valid=(j % 2 == 0))
                     for j in range(k + 1)]
            wants.append(crypto_batch.batch_verify(items, backend="cpu"))
            bv = crypto_batch.CPUBatchVerifier()
            for t in items:
                bv.add(*t)
            futs.append(bv.verify_async())
        for fut, want in zip(futs, wants):
            assert fut.result(timeout=30) == want

    def test_backend_exception_surfaces_at_result(self):
        """A backend raise must arrive at .result() — and must NOT kill
        the dispatch thread, which keeps serving later batches."""

        class Exploding(crypto_batch.BatchVerifier):
            BACKEND = "exploding-test"

            def _verify(self):
                raise RuntimeError("kernel on fire")

        bv = Exploding()
        bv.add(b"m", b"s" * 64, b"p" * 32)
        fut = bv.verify_async()
        with pytest.raises(RuntimeError, match="kernel on fire"):
            fut.result(timeout=30)
        with pytest.raises(RuntimeError, match="kernel on fire"):
            fut.result(timeout=1)  # replayed, not swallowed

        class Fine(crypto_batch.BatchVerifier):
            BACKEND = "exploding-test"  # same dispatcher thread

            def _verify(self):
                return [True] * len(self._items)

        bv2 = Fine()
        bv2.add(b"m", b"s" * 64, b"p" * 32)
        assert bv2.verify_async().result(timeout=30) == [True]

    def test_result_timeout_then_completion(self):
        release = threading.Event()

        class Slow(crypto_batch.BatchVerifier):
            BACKEND = "slow-test"

            def _verify(self):
                release.wait(30)
                return [True] * len(self._items)

        bv = Slow()
        bv.add(b"m", b"s" * 64, b"p" * 32)
        fut = bv.verify_async()
        with pytest.raises(TimeoutError):
            fut.result(timeout=0.01)
        release.set()
        assert fut.result(timeout=30) == [True]

    def test_overlap_histogram_records_hidden_wall_time(self):
        from tendermint_tpu.metrics import prometheus_metrics

        m = prometheus_metrics("t_async")
        crypto_batch.set_metrics(m.crypto)
        try:
            bv = crypto_batch.CPUBatchVerifier()
            bv.add(*_triple(900))
            fut = bv.verify_async()
            time.sleep(0.005)  # caller "works" while the batch runs
            assert fut.result(timeout=30) == [True]
        finally:
            crypto_batch.set_metrics(None)
        out = m.registry.render()
        assert "t_async_crypto_pipeline_overlap_seconds_count 1" in out


class TestDispatcherLifecycle:
    def test_shutdown_joins_threads_and_completes_inflight(self):
        class Slowish(crypto_batch.BatchVerifier):
            BACKEND = "slowish-test"

            def _verify(self):
                time.sleep(0.02)
                return [True] * len(self._items)

        futs = []
        for _ in range(3):
            bv = Slowish()
            bv.add(b"m", b"s" * 64, b"p" * 32)
            futs.append(bv.verify_async())
        crypto_batch.shutdown_dispatchers()
        # queued futures completed BEFORE the thread exited
        for fut in futs:
            assert fut.result(timeout=1) == [True]
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("crypto-dispatch") and t.is_alive()
        ]
        # a later verify_async lazily respawns a fresh dispatcher
        bv = crypto_batch.CPUBatchVerifier()
        bv.add(*_triple(901))
        assert bv.verify_async().result(timeout=30) == [True]

    def test_submit_racing_stop_still_resolves(self):
        """A submit that catches a dispatcher mid-shutdown (another
        node's stop) must not strand its future behind the sentinel —
        it runs inline and resolves."""
        d = crypto_batch._dispatcher("race-test")
        d.stop()
        bv = crypto_batch.CPUBatchVerifier()
        bv.add(*_triple(903))
        fut = d.submit(bv.verify)  # stopped dispatcher object directly
        assert fut.result(timeout=5) == [True]

    def test_config_of_an_older_node_still_loads(self):
        """A config.toml written by an older node may name [crypto] keys
        that have since gone (PR 31 took two): it loads, those keys are
        skipped, the keys beside them are read."""
        from tendermint_tpu.config import Config

        c = Config.from_toml(
            "[crypto]\nasync_dispatch = false\na_key_that_went = 5.0\n"
            "sig_cache_size = 7\n")
        assert c.crypto.async_dispatch is False
        assert c.crypto.sig_cache_size == 7
        assert not hasattr(c.crypto, "a_key_that_went")
        assert "a_key_that_went" not in c.to_toml()

    def test_node_stop_shuts_down_dispatch_threads(self, tmp_path):
        """Node.stop must leave no crypto-dispatch threads behind (the
        clean-shutdown guarantee the conftest teardown enforces for
        every test)."""
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from test_node import init_files, make_config

        from tendermint_tpu.node import default_new_node

        c = make_config(tmp_path, "async0")
        init_files(c)
        node = default_new_node(c)
        node.start()
        try:
            # the node's [crypto] defaults are live process-wide
            assert crypto_batch.async_enabled()
            assert crypto_batch.get_sig_cache() is not None
            bv = crypto_batch.CPUBatchVerifier()
            bv.add(*_triple(902))
            assert bv.verify_async().result(timeout=30) == [True]
            assert any(t.name.startswith("crypto-dispatch")
                       for t in threading.enumerate())
        finally:
            node.stop()
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("crypto-dispatch") and t.is_alive()
        ]
        # and the node uninstalled its own sig cache on the way out
        assert crypto_batch.get_sig_cache() is None


# --- fast-sync pipeline -------------------------------------------------


def _build_chain(state, keys, nblocks, corrupt_height=None,
                 resign_from=None, resign_keys=None, resign_vals=None):
    """Blocks 1..nblocks+1 with real signed commits: block h+1 carries
    the commit for block h. corrupt_height flips one signature in THAT
    block's commit; resign_from/resign_* sign commits for heights >=
    resign_from with a different validator set (valset-change case)."""
    from tendermint_tpu.types.basic import VOTE_TYPE_PRECOMMIT, BlockID, Vote
    from tendermint_tpu.types.block import Commit, make_part_set

    def commit_for(block, h):
        vals, ks = state.validators, keys
        if resign_from is not None and h >= resign_from:
            vals, ks = resign_vals, resign_keys
        parts = make_part_set(block)
        bid = BlockID(block.hash(), parts.header())
        pre = []
        for i in range(len(vals)):
            addr, _ = vals.get_by_index(i)
            v = Vote(
                validator_address=addr,
                validator_index=i,
                height=h,
                round=0,
                timestamp=1_700_000_000_000_000_000 + i,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=bid,
            )
            v.signature = ks[i].sign(v.sign_bytes(state.chain_id))
            pre.append(v)
        if corrupt_height == h:
            pre[1].signature = (bytes([pre[1].signature[0] ^ 1])
                                + pre[1].signature[1:])
        return Commit(bid, pre)

    blocks = {}
    prev_commit = None
    proposer = state.validators.validators[0].address
    for h in range(1, nblocks + 2):
        b = state.make_block(h, [], prev_commit if h > 1 else None, [],
                             proposer, time_ns=1_700_000_000_000_000_000 + h)
        if h == 1:
            b.last_commit = None
        blocks[h] = b
        prev_commit = commit_for(b, h)
    return blocks


class _FakeExec:
    """apply_block stand-in: records heights, bumps the state height,
    and optionally swaps in a new validator set at a given height."""

    def __init__(self, new_vals_at=None, new_vals=None):
        self.applied = []
        self._new_vals_at = new_vals_at
        self._new_vals = new_vals

    def apply_block(self, state, block_id, block):
        self.applied.append(block.header.height)
        ns = state.copy()
        ns.last_block_height = block.header.height
        if self._new_vals_at == block.header.height:
            ns.validators = self._new_vals
        return ns


def _make_reactor(nblocks, **chain_kw):
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from tendermint_tpu import state as sm
    from tendermint_tpu.blockchain.pool import _Requester
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.validator_set import random_validator_set

    vs, keys = random_validator_set(4, 10)
    doc = GenesisDoc(
        chain_id="fs-pipe",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(v.pub_key, v.voting_power)
                    for v in vs.validators],
    )
    state = sm.load_state_from_db_or_genesis(MemDB(), doc)
    blocks = _build_chain(state, keys, nblocks, **chain_kw)
    exec_ = _FakeExec()
    store = BlockStore(MemDB())
    reactor = BlockchainReactor(state, exec_, store, fast_sync=False)
    for h, b in blocks.items():
        req = _Requester(h)
        req.peer_id = "p1"
        req.block = b
        reactor.pool._requesters[h] = req
    reactor.pool.height = 1
    return reactor, exec_, store, state, keys


class TestFastSyncPipeline:
    def test_pipelined_sync_applies_all_blocks_with_overlap(self):
        from tendermint_tpu.metrics import prometheus_metrics

        crypto_batch.set_async_enabled(True)
        m = prometheus_metrics("t_fs")
        crypto_batch.set_metrics(m.crypto)
        try:
            reactor, exec_, store, _, _ = _make_reactor(nblocks=6)
            assert reactor._try_sync_batch() is True
        finally:
            crypto_batch.set_metrics(None)
        assert exec_.applied == [1, 2, 3, 4, 5, 6]
        assert store.height() == 6
        assert reactor.state.last_block_height == 6
        # verify(k+1) genuinely overlapped apply(k): the pipeline-overlap
        # histogram recorded samples
        assert ("t_fs_crypto_pipeline_overlap_seconds_count" in
                m.registry.render())
        counts = [
            line for line in m.registry.render().splitlines()
            if line.startswith("t_fs_crypto_pipeline_overlap_seconds_count")
        ]
        assert counts and float(counts[0].split()[-1]) > 0

    def test_verify_failure_mid_pipeline_stops_cleanly(self):
        """Block 3's commit is corrupt: blocks 1-2 (already verified)
        apply; 3 is redone; nothing after 3 is saved or applied."""
        crypto_batch.set_async_enabled(True)
        reactor, exec_, store, _, _ = _make_reactor(
            nblocks=6, corrupt_height=3)
        assert reactor._try_sync_batch() is True
        assert exec_.applied == [1, 2]
        assert store.height() == 2
        assert reactor.state.last_block_height == 2
        # the pool rewound to re-request height 3
        assert reactor.pool.height == 3
        req = reactor.pool._requesters.get(3)
        assert req is not None and req.block is None

    def test_serial_and_pipelined_paths_agree(self):
        crypto_batch.set_async_enabled(False)  # forces the serial loop
        reactor_s, exec_s, store_s, _, _ = _make_reactor(nblocks=5)
        assert reactor_s._try_sync_batch() is True

        crypto_batch.set_async_enabled(True)
        reactor_p, exec_p, store_p, _, _ = _make_reactor(nblocks=5)
        assert reactor_p._try_sync_batch() is True

        assert exec_s.applied == exec_p.applied == [1, 2, 3, 4, 5]
        assert store_s.height() == store_p.height() == 5

    def test_validator_change_mid_pipeline_reverifies(self):
        """apply(k) swaps the validator set; the speculative verify of
        k+1 (dispatched under the OLD set) must be discarded and the
        commit re-verified against the new set — here the new set signed
        it, so sync proceeds."""
        from tendermint_tpu.types.validator_set import random_validator_set

        new_vs, new_keys = random_validator_set(4, 10)
        crypto_batch.set_async_enabled(True)
        reactor, exec_, store, state, keys = _make_reactor(nblocks=4)
        # rebuild the chain: commits for heights >= 3 signed by new_vs
        blocks = _build_chain(state, keys, 4, resign_from=3,
                              resign_keys=new_keys, resign_vals=new_vs)
        from tendermint_tpu.blockchain.pool import _Requester

        reactor.pool._requesters.clear()
        for h, b in blocks.items():
            req = _Requester(h)
            req.peer_id = "p1"
            req.block = b
            reactor.pool._requesters[h] = req
        reactor.pool.height = 1
        exec_.applied.clear()
        exec_._new_vals_at = 2
        exec_._new_vals = new_vs

        assert reactor._try_sync_batch() is True
        assert exec_.applied == [1, 2, 3, 4]
        assert reactor.state.last_block_height == 4
