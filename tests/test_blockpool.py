"""Fast-sync BlockPool scheduler (reference blockchain/pool_test.go):
request-window fill, ordered hand-off, peer removal re-dispatch,
bad-block redo + peer punishment, caught-up detection — plus the
HeightVoteSet round bookkeeping (consensus/types/height_vote_set_test.go)
and BitArray ops (libs/common/bit_array_test.go) that ride the same
gossip paths."""

import os
import threading
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest
from test_save_once import _counted

from tendermint_tpu.blockchain import pool as pool_mod
from tendermint_tpu.blockchain.pool import BlockPool, _Requester
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.libs.bit_array import BitArray
from tendermint_tpu.metrics import prometheus_metrics


class _FakeBlock:
    class header:
        pass

    def __init__(self, h):
        self.header = type("H", (), {"height": h})()


class PoolHarness:
    def __init__(self, start=1):
        self.requests = []  # (peer, height)
        self.errors = []
        self._cv = threading.Condition()
        self.pool = BlockPool(start, self._request, self._error)

    def _request(self, peer, height):
        with self._cv:
            self.requests.append((peer, height))
            self._cv.notify_all()

    def _error(self, peer, reason):
        self.errors.append((peer, reason))

    def wait_requests(self, n, timeout=10.0):
        deadline = time.time() + timeout
        with self._cv:
            while len(self.requests) < n:
                left = deadline - time.time()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True


class TestBlockPool:
    def test_requests_flow_and_ordered_handoff(self):
        h = PoolHarness(start=1)
        h.pool.start()
        try:
            h.pool.set_peer_height("p1", 5)
            assert h.wait_requests(5), f"only {len(h.requests)} requests"
            heights = sorted(hh for _, hh in h.requests[:5])
            assert heights == [1, 2, 3, 4, 5]

            # serve out of order: 2 first, then 1
            h.pool.add_block("p1", _FakeBlock(2), 100)
            first, second = h.pool.peek_two_blocks()
            assert first is None  # height 1 not here yet: no hand-off
            h.pool.add_block("p1", _FakeBlock(1), 100)
            first, second = h.pool.peek_two_blocks()
            assert first.header.height == 1 and second.header.height == 2
            h.pool.pop_request()
            assert h.pool.height == 2
            first, _ = h.pool.peek_two_blocks()
            assert first.header.height == 2
        finally:
            h.pool.stop()

    def test_unsolicited_and_wrong_peer_blocks_ignored(self):
        h = PoolHarness(start=1)
        h.pool.start()
        try:
            h.pool.set_peer_height("p1", 3)
            assert h.wait_requests(3)
            # block from a peer that was never asked for that height
            h.pool.add_block("intruder", _FakeBlock(1), 100)
            first, _ = h.pool.peek_two_blocks()
            assert first is None
        finally:
            h.pool.stop()

    def test_remove_peer_redispatches_to_survivor(self):
        h = PoolHarness(start=1)
        h.pool.start()
        try:
            h.pool.set_peer_height("p1", 2)
            h.pool.set_peer_height("p2", 2)
            assert h.wait_requests(2)
            victims = {hh for p, hh in h.requests if p == "p1"}
            h.pool.remove_peer("p1")
            if victims:
                deadline = time.time() + 10
                while time.time() < deadline:
                    redone = {hh for p, hh in h.requests if p == "p2"}
                    if victims <= redone:
                        break
                    time.sleep(0.05)
                assert victims <= {hh for p, hh in h.requests if p == "p2"}
        finally:
            h.pool.stop()

    def test_redo_request_punishes_and_rerequests(self):
        h = PoolHarness(start=1)
        h.pool.start()
        try:
            h.pool.set_peer_height("bad", 1)
            h.pool.set_peer_height("good", 1)
            assert h.wait_requests(1)
            peer0, _ = h.requests[0]
            h.pool.add_block(peer0, _FakeBlock(1), 100)
            h.pool.redo_request(1)  # validation failed upstream
            assert h.errors and h.errors[0][0] == peer0
            other = "good" if peer0 == "bad" else "bad"
            deadline = time.time() + 10
            while time.time() < deadline:
                if any(p == other and hh == 1 for p, hh in h.requests):
                    break
                time.sleep(0.05)
            assert any(p == other and hh == 1 for p, hh in h.requests), (
                "height 1 never re-requested from the surviving peer")
        finally:
            h.pool.stop()

    def test_caught_up(self):
        h = PoolHarness(start=5)
        h.pool.start()
        try:
            assert not h.pool.is_caught_up()  # no peers yet
            h.pool.set_peer_height("p1", 5)
            assert h.pool.is_caught_up()  # already at max peer height
            h.pool.set_peer_height("p2", 9)
            assert not h.pool.is_caught_up()
            assert h.pool.max_peer_height() == 9
        finally:
            h.pool.stop()


# --- a refused commit (pool.go RedoRequest x 2 + removePeer) ---------------


def _planned_pool(plan: dict, monkeypatch) -> PoolHarness:
    """A pool, not started, whose requester for each height of `plan`
    went to the peer the plan names (the draw is scripted); the peers
    are heard of in sorted order, so "a" holds slot 0."""
    h = PoolHarness(start=1)
    for peer in sorted(set(plan.values())):
        h.pool.set_peer_height(peer, max(plan))
    for height, peer in sorted(plan.items()):
        monkeypatch.setattr(
            pool_mod.random, "choice",
            lambda among, peer=peer: next(p for p in among if p.id == peer))
        h.pool._requesters[height] = _Requester(height)
        h.pool._dispatch(height)
    monkeypatch.undo()
    assert h.requests == [(plan[k], k) for k in sorted(plan)]
    return h


REFUSED = {
    # plan, delivered, peer that timed out first, -> reported, dropped
    "the pair came from one peer": (
        {1: "a", 2: "a", 3: "b", 4: "a", 5: "b"}, {1, 2, 3, 4, 5}, None,
        ["a"], {1, 2, 4}),
    "the pair came from two peers": (
        {1: "a", 2: "b", 3: "c", 4: "a", 5: "b", 6: "c"}, {1, 2, 3, 4, 5, 6},
        None, ["a", "b"], {1, 2, 4, 5}),
    "a reported peer's open requests move too": (
        {1: "a", 2: "a", 3: "a", 4: "b"}, {1, 2, 4}, None, ["a"], {1, 2}),
    "a peer that only timed out keeps what it delivered": (
        {1: "a", 2: "a", 3: "t", 4: "t", 5: "b"}, {1, 2, 3, 5}, "t",
        ["a"], {1, 2}),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_height_drops_the_pair_and_what_its_peers_delivered(
        case, monkeypatch):
    plan, delivered, slow, reported, dropped = REFUSED[case]
    m = prometheus_metrics("t_bp")
    crypto_batch.set_metrics(m.crypto)
    try:
        h = _planned_pool(plan, monkeypatch)
        pool = h.pool
        # whatever is asked again goes to the last of those that remain
        monkeypatch.setattr(pool_mod.random, "choice",
                            lambda among: max(among, key=lambda p: p.id))
        for height in sorted(delivered):
            pool.add_block(plan[height], _FakeBlock(height), 100)
        pool.add_block("stranger", _FakeBlock(max(plan)), 100)  # not counted
        if slow is not None:
            pool._peers[slow].timeout_at = time.monotonic() - 1
            pool._check_peer_timeouts()
            assert h.errors == [(slow, "block request timed out")]
            assert slow not in pool._peers
        asked_before = len(h.requests)
        assert pool.redo_request(1) == (len(dropped), reported)

        assert h.errors[-len(reported):] == [
            (p, "bad block at height 1") for p in reported]
        gone = set(reported) | ({slow} if slow else set())
        assert set(pool._peers) == set(plan.values()) - gone
        kept = delivered - dropped
        for height, req in pool._requesters.items():
            if height in kept:  # the delivered blocks of everyone else
                assert req.block.header.height == height
                assert req.peer_id == plan[height]
            else:  # asked again, of a peer that remains
                assert req.block is None and req.peer_id in pool._peers
        again = h.requests[asked_before:]
        open_of_reported = {k for k, p in plan.items()
                            if p in reported and k not in delivered}
        assert {k for _, k in again} == dropped | open_of_reported
        assert all(p in pool._peers for p, _ in again)
        # the pool hands on what the survivors send for the dropped pair
        for height in (1, 2):
            pool.add_block(pool._requesters[height].peer_id,
                           _FakeBlock(height), 100)
        first, second = pool.peek_two_blocks()
        assert (first.header.height, second.header.height) == (1, 2)

        requests = _counted(m, "t_bp_blockchain_pool_requests_total")
        received = _counted(m, "t_bp_blockchain_pool_blocks_received_total")
        slots = {'{slot="%d"}' % i for i in range(len(set(plan.values())))}
        assert set(requests) <= slots and set(received) <= slots
        assert sum(requests.values()) == len(h.requests)
        assert sum(received.values()) == len(delivered) + 2
        assert _counted(m, "t_bp_blockchain_redo_heights_total") == {
            "": float(len(dropped))}
        by_slot = {'{slot="%d"}' % i: p
                   for i, p in enumerate(sorted(set(plan.values())))}
        before_redo = [p for p, _ in h.requests[:asked_before]]
        for label, peer in by_slot.items():
            if peer in pool._peers:
                continue  # a survivor's slot also counts what was asked again
            assert requests.get(label, 0.0) == before_redo.count(peer)
    finally:
        crypto_batch.set_metrics(None)


def test_a_slot_is_the_lowest_one_free_so_the_labels_stay_few():
    h = PoolHarness(start=1)
    pool = h.pool
    for peer in ("a", "b", "c"):
        pool.set_peer_height(peer, 3)
    assert [pool._peers[p].slot for p in "abc"] == ["0", "1", "2"]
    pool.remove_peer("b")
    pool.set_peer_height("d", 3)
    pool.set_peer_height("a", 9)  # a peer heard of again keeps its slot
    assert {p.id: p.slot for p in pool._peers.values()} == {
        "a": "0", "c": "2", "d": "1"}
    for i in range(50):  # churn: one peer at a time comes and goes
        pool.set_peer_height(f"x{i}", 3)
        assert pool._peers[f"x{i}"].slot == "3"
        pool.remove_peer(f"x{i}")


class TestHeightVoteSet:
    def _mk(self):
        from tendermint_tpu.consensus.cstypes import HeightVoteSet
        from tendermint_tpu.types.validator_set import random_validator_set

        vals, keys = random_validator_set(4, 10)
        return HeightVoteSet("hvs-test", 1, vals), vals, keys

    def _vote(self, vals, keys, i, round_, type_, block_id):
        from tendermint_tpu.types import Vote
        from tendermint_tpu.types.basic import (
            VOTE_TYPE_PRECOMMIT,
            VOTE_TYPE_PREVOTE,
        )

        addr, _ = vals.get_by_index(i)
        v = Vote(
            validator_address=addr, validator_index=i, height=1,
            round=round_, timestamp=1_700_000_000_000_000_000,
            type=type_, block_id=block_id,
        )
        v.signature = keys[i].sign(v.sign_bytes("hvs-test"))
        return v

    def test_rounds_created_on_demand_and_pol_info(self):
        from tendermint_tpu.types.basic import (
            VOTE_TYPE_PREVOTE,
            BlockID,
            PartSetHeader,
        )

        hvs, vals, keys = self._mk()
        b = BlockID(hash=b"\x01" * 32,
                    parts_header=PartSetHeader(1, b"\x01" * 32))
        assert hvs.pol_info() == (-1, BlockID()) or hvs.pol_info()[0] == -1
        # votes for a FUTURE round are accepted from peers (hvs tracks
        # round 0..round+1 plus peer-supplied rounds)
        for i in range(3):
            hvs.add_vote(self._vote(vals, keys, i, 0, VOTE_TYPE_PREVOTE, b),
                         peer_id=f"p{i}")
        assert hvs.prevotes(0).has_two_thirds_majority()
        pol_round, pol_bid = hvs.pol_info()
        assert pol_round == 0 and pol_bid == b

    def test_set_round_advances_window(self):
        from tendermint_tpu.types.basic import VOTE_TYPE_PREVOTE, BlockID

        hvs, vals, keys = self._mk()
        hvs.set_round(3)
        assert hvs.prevotes(3) is not None
        assert hvs.prevotes(4) is not None  # round+1 pre-created
        v = self._vote(vals, keys, 0, 3, VOTE_TYPE_PREVOTE, BlockID())
        assert hvs.add_vote(v)
        assert hvs.prevotes(3).bit_array().num_true() == 1


class TestBitArray:
    def test_ops(self):
        a = BitArray.from_bools([1, 0, 1, 0, 1, 0, 0, 0, 1])
        b = BitArray.from_bools([1, 1, 0, 0, 1, 0, 0, 0, 0])
        assert a.num_true() == 4
        assert a.or_(b).num_true() == 5  # union {0,1,2,4,8}
        assert a.and_(b).num_true() == 2
        assert a.sub(b).num_true() == 2  # in a, not in b: idx 2, 8
        assert a.not_().num_true() == 9 - 4
        assert not a.is_empty() and not a.is_full()
        assert BitArray.from_bools([1, 1]).is_full()
        assert BitArray(5).is_empty()

    def test_roundtrip_bytes_and_pick(self):
        a = BitArray.from_bools([0, 1, 0, 0, 0, 0, 0, 0, 0, 1, 1])
        back = BitArray.from_bytes_size(a.to_bytes(), a.size())
        assert back == a
        picks = {a.pick_random() for _ in range(50)}
        assert picks <= {1, 9, 10}
        assert {1, 9, 10} <= picks  # all true bits reachable

    def test_set_out_of_range(self):
        a = BitArray(4)
        assert not a.set_index(9, True)
        assert not a.get_index(9)
