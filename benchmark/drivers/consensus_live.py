"""Driver kind `consensus_live`: the node is one validator of the chain's
committee, signing its prevote and precommit every height, and the
other validators are the committee of `harness/committee.py`, a process
of its own behind one real p2p connection on loopback.

Set-up draws the committee from the seed (`chain.committee`) and the
node's place in it: one of the proposers of heights 1..`warmup_heights`
by the program's rotation, so that its first turn falls in the warm-up
and the next every `validators` heights. The node's key is written into
its home as the `FilePV` file before `build_node` (`init` keeps a key
that is there), the node is started, the committee process is started
beside it (JAX held to the CPU), the node's verifier warms and every
kernel bucket a drained vote run can reach is made ready
(`warm_buckets`), and then the committee dials the node.

The loop is closed: a height begins when the committee holds every
precommit of the last (the node's included) and the node has stepped
into it. The window opens when the node's block store reaches
`warmup_heights` and lasts `seconds` on the height poller's clock
(`fastsync_from_peer.HeightPoller`); the rate is the heights the store
gained in it over its seconds. At the close the committee is told, and
the next `tail_heights` heights each carry a corrupted phantom prevote
and precommit (`harness/committee.py`); then it sends nothing more.

What the node committed, wrote in its WAL and sent is then held to the
plain reference (`harness/reference_live.py`, guarantees 1-5 of
`configs/hub-150val-live.json`). Every wait ends by a deadline, and a
node or committee that stalls ends the run with a line that is not
correct.
"""

from __future__ import annotations

import os
import shutil
import statistics
import tempfile
import time

import numpy as np

from ..harness import chain as chainlib
from ..harness import node as nodelib
from ..harness import reference_live as reflive
from ..harness.committee import CommitteeProcess
from ..harness.reference import verify_one
from ..harness.rpcclient import Rpc
from .consensus_follow import (STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL,
                               VOTE_SET_BITS_CHANNEL, _NoTip, _wait_height,
                               warm_vote_buckets)
from .fastsync_from_peer import HeightPoller, _check_applied, say

TAIL_HEIGHTS = 4
TAIL_DEADLINE_S = 60
CONSENSUS = (STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL)


def node_place(chain, warm: int, rng) -> int:
    """The node's validator index: one of the round-0 proposers of
    heights 1..warm by the program's rotation, drawn from the seed."""
    cur, nxt = chain.state0.validators.copy(), chain.state0.next_validators.copy()
    index_of = {v.address: i for i, v in enumerate(cur.validators)}
    proposers = []
    for _ in range(warm):
        proposers.append(index_of[cur.get_proposer().address])
        cur, nxt = nxt, nxt.copy()
        nxt.increment_proposer_priority(1)
    return proposers[int(rng.integers(0, len(proposers)))]


def write_key(home: str, seed: bytes) -> None:
    """The node's key as the program's `FilePV` file, before `init`."""
    from tendermint_tpu.cmd import main as cli
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.privval import FilePV

    path = cli._load_config(home).base.priv_validator_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    FilePV(PrivKeyEd25519.from_seed(seed), path).save()


def warm_triples(chain, lanes: int) -> list:
    """Fresh vote-sized signatures no height sends (a prevote's
    sign-bytes of height 0), for the kernel buckets."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    from tendermint_tpu.types.basic import BlockID

    from ..harness.rounds import splice_parts

    pre, post = splice_parts(chain.chain_id, 1, 0, BlockID(hash=b"\x01" * 32))
    keys = [Ed25519PrivateKey.from_private_bytes(s) for s in chain.seeds]
    out = []
    for i in range(lanes):
        msg = pre + (2**63 + i).to_bytes(8, "little") + post
        k = i % len(keys)
        out.append((msg, keys[k].sign(msg), chain.pubkeys[k]))
    return out


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    traffic, cfg = cell.traffic, cell.config
    n_vals = cfg["validators"]
    warm = traffic["warmup_heights"]
    deadline = traffic.get("deadline_s", 120)
    tail_deadline = min(TAIL_DEADLINE_S, deadline)
    rng = np.random.default_rng(seed)

    chain = chainlib.committee(seed=seed, validators=n_vals)
    me = node_place(chain, warm, rng)
    triples = warm_triples(chain, sum(traffic["warm_buckets"]))
    home = tempfile.mkdtemp(prefix="bench_home_")
    node = poller = committee = None
    try:
        write_key(home, chain.seeds[me])
        node = nodelib.build_node(home, chain.chain_id, cfg,
                                  genesis_json=chain.genesis.to_json(),
                                  trace=ctx.trace_on)
        if node.priv_validator.get_pub_key().bytes() != chain.pubkeys[me]:
            raise RuntimeError("the node did not take the key written for it")
        ctx.install(node)
        node.start()
        committee = CommitteeProcess(dict(
            seed=seed, validators=n_vals, node_index=me,
            txs_per_block=traffic["txs_per_block"],
            tx_bytes=traffic["tx_bytes"], key_space=traffic["key_space"],
            delay_ms=traffic["delay_ms"], workers=traffic["committee_workers"],
            rate=cfg["p2p_rate_bytes_per_s"],
            other_channels=[[d.id, d.priority] for d in node.sw.ch_descs
                            if d.id not in CONSENSUS],
            tail_heights=TAIL_HEIGHTS,
            max_heights=traffic.get("max_heights", 5000),
            deadline_s=deadline, node_id=node.node_key.id,
            node_addr=node.transport.listen_addr))
        surf = nodelib.Surfaces(node)
        verifier = surf.wait_verifier(traffic.get("verifier_deadline_s", 1100))
        say("node verifier:", verifier)
        if str(verifier.get("warmup")).startswith("error"):
            raise RuntimeError(f"verify warm-up failed: {verifier}")
        warm_vote_buckets(triples, verifier, traffic["warm_buckets"])

        close: dict = {}

        def at_close() -> None:
            """The window's last instant, on the poller's thread: the
            committee finishes its height and waits for the tail."""
            committee.call("hold")
            close["early_drop"] = bool(committee.call("status")["dropped"])

        store = node.block_store
        poller = HeightPoller(store, _NoTip(), 0, 0, at_close)
        poller.start()
        committee.call("dial")
        say(f"committee of {n_vals - 1} phantoms dialled; the node is "
            f"validator {me}")

        numbers: dict = {}
        t_open = _wait_height(poller, warm, deadline)
        if t_open is None:  # the chain does not move: the line says so
            numbers["warmup_not_committed"] = (warm - store.height(), 0)
            t_open = time.monotonic()
            say(f"the node reached height {store.height()} of {warm} "
                f"warm-up heights in {deadline}s: {committee.call('status')}")
        ctx.window_opens(t_open, surf)
        poller.t_close = t_close = ctx.t_close
        ctx.wait_until(t_close)
        peak = ctx.window_closes()
        if not poller.closed.wait(10):
            raise RuntimeError("the height poller did not close the window")
        if poller.error is not None:
            raise poller.error
        h_open, h_end = poller.height_at(t_open), poller.height_at(t_close)
        blocks = h_end - h_open
        say(f"window: heights {h_open}..{h_end} in {seconds:.3f}s "
            f"({blocks / seconds:.4f} heights/s)")
        ctx.trace_stop()  # holds the node's process up for seconds: no height runs

        # --- the tail: TAIL_HEIGHTS heights with corrupted votes --------
        committee.call("close")
        end = time.monotonic() + tail_deadline
        while time.monotonic() < end:
            st = committee.call("status")
            if st["done"] or st["dropped"]:
                if not st["tail"] or store.height() >= st["tail"][-1]:
                    break
            time.sleep(0.05)
        time.sleep(0.3)  # anything it still does shows here
        report = committee.call("report")
        final = store.height()
        tail = [r["h"] for r in report["heights"] if r["corrupt"]]
        say(f"corrupted heights {tail}, node at {final}"
            + (f", committee: {report['error']}" if report["error"] else "")
            + (f", dropped: {report['dropped']}" if report["dropped"] else ""))
        first = tail[0] if tail else final + 1
        numbers["height_not_committed"] = (
            max(0, first + TAIL_HEIGHTS - 1 - final), 0)
        numbers["bad_vote_in_seen_commit"] = (
            _bad_in_seen_commits(chain, store, report["heights"], final), 0)
        numbers["honest_peer_dropped"] = (1 if report["dropped"] else 0, 0)
        # the committee stops itself after the tail; any other end is a fault
        numbers["committee_failed"] = (1 if report["error"] else 0, 0)
        final = min(final, len(report["heights"]))
        stored = _stored(chain, store, report["heights"], final, me)
        state_of = node.consensus_state
        end = time.monotonic() + tail_deadline
        while state_of.state.last_block_height < final and time.monotonic() < end:
            time.sleep(0.01)
        applied = reflive.Applied(report["heights"][:final])
        numbers.update(_check_applied(applied, Rpc(surf.rpc_addr), final, rng,
                                      traffic["check_heights"],
                                      traffic["check_keys"]))
        wal_path = node.config.consensus.wal_file(node.config.root_dir)
        node.stop()  # the WAL is read closed
        node.wait(60)
        node = None
        own = _own_votes(wal_path, me, chain.chain_id)
        received = [(h, r, t, bytes.fromhex(sb), bytes.fromhex(sig))
                    for h, r, t, sb, sig, _ in report["node_votes"]]
        ref = reflive.check(
            final=final, heights=report["heights"],
            app_hash0=chain.genesis.app_hash, stored=stored, own_votes=own,
            received=received, proposals=report["proposals"], node_index=me,
            pubkeys=chain.pubkeys)
        for k in ("chain_differs", "own_votes_wrong", "own_turns_wrong"):
            numbers[k] = (ref[k], 0)
            if ref[k]:
                say(f"{k} at heights {ref['heights'][k]}")
        numbers["own_turn_missing"] = (0 if ref["own_turns"] else 1, 0)

        window = [r for r in report["heights"] if h_open < r["h"] <= h_end]
        facts = {
            "blocks": blocks, "window_s": seconds,
            "votes_per_height": 2 * n_vals,
            "height_open": h_open, "height_end": h_end,
            "node_index": me,
            "committee_ms_per_height": (statistics.fmean(r["ms"] for r in window)
                                        if window else None),
            "own_proposals": ref["own_turns"],
            "own_votes_signed": sum(1 for v in own if h_open < v[0] <= h_end),
            # of them, those that reached the committee: the vote gossip
            # sends a height's prevote only while node and committee are
            # both at that height
            "own_prevotes_sent": sum(1 for v in received
                                     if h_open < v[0] <= h_end
                                     and v[2] == reflive.PREVOTE),
            "own_precommits_sent": sum(1 for v in received
                                       if h_open < v[0] <= h_end
                                       and v[2] == reflive.PRECOMMIT),
            # heights whose next block was built without the node's precommit
            "own_precommits_missed": sum(
                1 for r in report["heights"][:final - 1]
                if not r.get("own_precommit")),
            "votes_relayed_by_node": report["relayed"],
            "corrupted_heights": tail,
            "corrupted_votes": [[r["h"], t, i] for r in report["heights"]
                                for t, i, _ in r["corrupt"]],
        }
        return {
            "end_to_end": {"sync_blocks_per_s": blocks / seconds},
            "attempted": blocks + (1 if close.get("early_drop") else 0),
            "failed": 1 if close.get("early_drop") else 0,
            "numbers": numbers, "facts": facts, "peak": peak,
        }
    finally:
        if poller is not None:
            poller.stop()
        if committee is not None:
            committee.close()
        if node is not None:
            node.stop()
            node.wait(60)
        shutil.rmtree(home, ignore_errors=True)


def _stored(chain, store, heights: list, final: int, me: int) -> dict:
    """What the node committed, as plain values: by height its block
    hash, its header's app hash and its LastCommit (every precommit at
    the node's turns, the node's own elsewhere), (sign_bytes, sig)."""
    out = {}
    for h in range(1, final + 1):
        meta = store.load_block_meta(h)
        entry = {"block_hash": meta.block_id.hash,
                 "app_hash": meta.header.app_hash, "last_commit": None}
        if h > 1:
            commit = store.load_block_commit(h - 1)
            want = (range(len(commit.precommits)) if heights[h - 1]["own"]
                    else [me])
            last = [None] * len(commit.precommits)
            for i in want:
                v = commit.precommits[i]
                if v is not None:
                    last[i] = (v.sign_bytes(chain.chain_id), v.signature)
            entry["last_commit"] = last
        out[h] = entry
    return out


def _own_votes(wal_path: str, me: int, chain_id: str) -> list:
    """Every vote of the node's its WAL holds (internal messages), as
    (height, round, type, sign_bytes, signature)."""
    from tendermint_tpu.consensus.messages import VoteMessage
    from tendermint_tpu.consensus.wal import WAL

    out = []
    for msg in WAL(wal_path).iter_messages():
        if (isinstance(msg, tuple) and msg[0] == ""
                and isinstance(msg[1], VoteMessage)
                and msg[1].vote.validator_index == me):
            v = msg[1].vote
            out.append((v.height, v.round, v.type, v.sign_bytes(chain_id),
                        v.signature))
    return out


def _bad_in_seen_commits(chain, store, heights: list, final: int) -> int:
    """Corrupted heights the node committed whose stored seen commit
    holds the corrupted precommit, or any signature OpenSSL refuses."""
    bad = 0
    for r in heights:
        if not r["corrupt"] or r["h"] > final:
            continue
        seen = store.load_seen_commit(r["h"])
        sigs = {(i, bytes.fromhex(s)) for t, i, s in r["corrupt"]
                if t == reflive.PRECOMMIT}
        held = [v for v in seen.precommits if v is not None]
        if any((v.validator_index, v.signature) in sigs for v in held) or any(
                not verify_one(v.sign_bytes(chain.chain_id), v.signature,
                               chain.pubkeys[v.validator_index])
                for v in held):
            bad += 1
    return bad
