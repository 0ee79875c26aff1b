"""The node as one validator of a live chain: it holds one key of a
7-validator committee and runs consensus with the other six, phantoms of
the benchmark's committee (`benchmark/harness/committee.py`, v0.27's
honest rules, round 0 every height) behind one p2p connection on
loopback, with `skip_timeout_commit` on so that every height waits for
the node's precommit. Twelve heights, one or two of them the node's
turn, are then held to the plain reference
(`benchmark/harness/reference_live.py`: OpenSSL one signature at a
time, a dict for the kvstore): the committee's block hashes and the
reference's app hashes at every height, one prevote and one precommit
signed a height, valid and never signed twice over other bytes, every
LastCommit holding all seven precommits, and the node's own block
committed at its turns. The committee begins no height before the
node's precommit of the last has reached it, and the node's own
messages wait behind no queued peer message."""

import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.drivers import consensus_live as live  # noqa: E402
from benchmark.harness import chain as chainlib  # noqa: E402
from benchmark.harness import node as nodelib  # noqa: E402
from benchmark.harness import reference_live as reflive  # noqa: E402
from benchmark.harness.committee import Committee  # noqa: E402

N_VALS, HEIGHTS, WARM, SEED = 7, 12, 5, 2**31 + 4412
DELAY_MS = 1.0
CONFIG = {"node": {"base.proxy_app": "kvstore", "base.fast_sync": False,
                   "consensus.skip_timeout_commit": True}}


@pytest.fixture(scope="module")
def chain_run():
    """Twelve heights of the node among six phantoms; what it committed,
    wrote and sent, read after it stopped."""
    chain = chainlib.committee(seed=SEED, validators=N_VALS)
    me = live.node_place(chain, WARM, np.random.default_rng(SEED))
    home = tempfile.mkdtemp(prefix="live_validator_")
    node = committee = None
    try:
        live.write_key(home, chain.seeds[me])
        node = nodelib.build_node(home, chain.chain_id, CONFIG,
                                  genesis_json=chain.genesis.to_json(),
                                  trace=False)
        node.start()
        committee = Committee(
            seed=SEED, validators=N_VALS, node_index=me, txs_per_block=4,
            tx_bytes=64, key_space=32, delay_ms=DELAY_MS, workers=1,
            rate=5_120_000,
            other_channels=[[d.id, d.priority] for d in node.sw.ch_descs
                            if d.id not in live.CONSENSUS],
            tail_heights=0, max_heights=HEIGHTS + 8, deadline_s=30,
            node_id=node.node_key.id, node_addr=node.transport.listen_addr)
        committee.dial()
        store = node.block_store
        end = time.monotonic() + 60
        while store.height() < HEIGHTS and time.monotonic() < end:
            if committee.error is not None:
                break
            time.sleep(0.02)
        committee.close()
        committee.thread.join(10)
        assert not committee.thread.is_alive()
        time.sleep(0.2)
        report = committee.report()
        final = min(store.height(), len(report["heights"]))
        stored = live._stored(chain, store, report["heights"], final, me)
        commits = {h: store.load_block_commit(h) for h in range(1, final)}
        wal_path = node.config.consensus.wal_file(node.config.root_dir)
        node.stop()
        node.wait(30)
        node = None
        own = live._own_votes(wal_path, me, chain.chain_id)
        yield dict(chain=chain, me=me, report=report, final=final,
                   stored=stored, own=own, commits=commits)
    finally:
        if committee is not None:
            committee.stop()
        if node is not None:
            node.stop()
            node.wait(30)
        shutil.rmtree(home, ignore_errors=True)


def _check(r) -> dict:
    received = [(h, rd, t, bytes.fromhex(sb), bytes.fromhex(sig))
                for h, rd, t, sb, sig, _ in r["report"]["node_votes"]]
    return reflive.check(
        final=r["final"], heights=r["report"]["heights"],
        app_hash0=r["chain"].genesis.app_hash, stored=r["stored"],
        own_votes=r["own"], received=received,
        proposals=r["report"]["proposals"], node_index=r["me"],
        pubkeys=r["chain"].pubkeys)


def test_twelve_heights_commit_with_the_committee(chain_run):
    assert chain_run["report"]["error"] is None
    assert chain_run["report"]["dropped"] is None
    assert chain_run["final"] >= HEIGHTS


def test_the_chain_is_the_committees_and_the_references(chain_run):
    out = _check(chain_run)
    assert out["chain_differs"] == 0


def test_one_prevote_and_one_precommit_a_height_never_signed_twice(chain_run):
    out = _check(chain_run)
    assert out["own_votes_wrong"] == 0
    by = {}
    for h, rd, t, sb, sig in chain_run["own"]:
        by.setdefault((h, rd, t), set()).add((sb, sig))
    for h in range(1, chain_run["final"] + 1):
        assert len(by[(h, 0, reflive.PREVOTE)]) == 1
        assert len(by[(h, 0, reflive.PRECOMMIT)]) == 1


def test_every_last_commit_holds_all_seven(chain_run):
    for h, commit in chain_run["commits"].items():
        assert len(commit.precommits) == N_VALS
        assert all(v is not None for v in commit.precommits), h
        me = commit.precommits[chain_run["me"]]
        assert me.validator_index == chain_run["me"] and me.height == h


def test_the_nodes_own_block_commits_at_its_turn(chain_run):
    out = _check(chain_run)
    turns = [r["h"] for r in chain_run["report"]["heights"][:chain_run["final"]]
             if r["own"]]
    assert out["own_turns"] == len(turns) >= 1
    assert out["own_turns_wrong"] == 0
    for h in turns:
        assert chain_run["stored"][h]["block_hash"] == bytes.fromhex(
            chain_run["report"]["heights"][h - 1]["block_hash"])


def test_the_committee_waits_for_the_nodes_precommit(chain_run):
    """Each height after the first began because the node's precommit of
    the last arrived, and its first message left no sooner than the
    injected delay after it."""
    heights = chain_run["report"]["heights"][1:chain_run["final"]]
    assert heights
    for r in heights:
        assert r["after"] == "precommit", r["h"]
        assert r["lag_ms"] >= DELAY_MS, r["h"]
    assert all(r["own_precommit"] for r in
               chain_run["report"]["heights"][:chain_run["final"]])


def test_weighted_median_is_the_stamp_past_half_the_power():
    assert reflive.weighted_median([(5, 10), (1, 10), (3, 10)]) == 3
    assert reflive.weighted_median([(1, 10), (2, 10)]) == 2
    assert reflive.weighted_median([(1, 30), (2, 10), (3, 10)]) == 1


def test_own_messages_are_taken_before_queued_peer_messages():
    """An own message (its vote, its proposal) waits behind no peer
    message already queued: the receive loop empties the internal queue
    first, as v0.27 serves internalMsgQueue beside peerMsgQueue."""
    import threading

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_consensus import make_consensus

    cs, bus, *_ = make_consensus(1)
    seen = []
    cs._handle_item = seen.append
    peers = [("msg", (f"peer{i}", object())) for i in range(5)]
    for item in peers:
        cs._queue.put(item)
    cs._send_internal("own")
    loop = threading.Thread(target=cs._receive_routine, daemon=True)
    loop.start()
    end = time.monotonic() + 10
    while len(seen) < 7 and time.monotonic() < end:
        time.sleep(0.01)
    cs._done.set()
    loop.join(5)
    bus.stop()
    assert not loop.is_alive()
    assert seen[0] == ("msg", ("", "own"))
    assert [x for x in seen if x[0] == "msg"][1:] == peers
    assert seen[-1][0] == "wake"  # the token that ended the wait, dropped
