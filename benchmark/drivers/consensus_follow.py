"""Driver kind `consensus_follow`: a full node (no validator key in the
committee, fast sync off) follows a chain through consensus gossip from
one peer ahead of it, over a real p2p connection on loopback.

Set-up draws the committee from the seed and builds the chain as the
network makes it (`harness/rounds.py`: proposer rotation, a signed
proposal, every prevote and precommit a height), starts the node and the
peer (a switch of the benchmark's own, in the node's process), waits for
the node's verifier and makes ready each kernel bucket a drained vote
run can reach (`warm_buckets`, with vote-sized signatures the traffic
never sends), then dials the node.

The peer announces its own round state, a `NewRoundStep` at the chain's
tip, as a live peer ahead of the node would. Each time the node's
`NewRoundStep` shows it has entered a height H, the peer sends, back to
back: H's `ProposalMessage`, its `BlockPartMessage`s, the committee's
prevotes, then its precommits, each type in the order drawn for H. The
node sets the pace; nothing is sent for a height the node has not
entered. The window opens when the node's block store reaches
`warmup_heights` and lasts `seconds` on the height poller's clock
(`fastsync_from_peer.HeightPoller`): the rate is the heights the store
gained in it, over its seconds.

After the window the next `TAIL_HEIGHTS` heights the node enters each
carry one prevote and one precommit with one flipped signature bit, of
validators drawn from the upper half of the committee (the rest still
make +2/3); then the peer sends nothing more. The node has to commit
each of them, keep neither corrupted vote and keep its peer. Every wait
ends by a deadline, and a node that commits nothing still ends the run
with a line that is not correct.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
import time

import numpy as np

from ..harness import node as nodelib
from ..harness import rounds as roundslib
from ..harness.reference import verify_one
from ..harness.rpcclient import Rpc
from .fastsync_from_peer import HeightPoller, _check_applied, say

STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL = (
    0x20, 0x21, 0x22, 0x23)
TAIL_HEIGHTS = 4
TAIL_DEADLINE_S = 60


class _NoTip:
    """The poller moves no tip here: the peer answers the node's steps."""

    def advertise(self, tip: int) -> None:
        pass


def make_gossip_switch(rounds, rate: int, other_channels: list, last: int):
    """A switch that speaks the consensus channels: it announces its
    round state at the chain's tip and pushes each height the node
    enters (`Gossip._push_loop`). `last` is the last height it pushes
    before the window closes; after the close (`Gossip.close`) the next
    TAIL_HEIGHTS heights are pushed with the votes `Gossip.corrupt`
    gives, then nothing."""
    from tendermint_tpu.consensus.messages import NewRoundStepMessage
    from tendermint_tpu.consensus.reactor import decode_msg, encode_msg
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.p2p import (MultiplexTransport, NodeInfo, NodeKey,
                                    ProtocolVersion, Switch)
    from tendermint_tpu.p2p.base_reactor import ChannelDescriptor, Reactor
    from tendermint_tpu.p2p.conn.connection import MConnConfig

    tip = len(rounds)
    step_head = encode_msg(NewRoundStepMessage(1, 0, 1, 0, 0))[:16]

    class Gossip(Reactor):
        def __init__(self):
            super().__init__("BenchGossip")
            self.peer = None
            self.entered = self.pushed = 0
            self.closed = False          # the window is over: corrupt
            self.corrupt = None          # height -> (prevotes, precommits, order)
            self.corrupted: list = []    # heights pushed corrupted
            self.dropped = threading.Event()
            self.drop_reason = self.dropped_at = None
            self._cond = threading.Condition()
            self._halt = False
            self._thread = None

        def get_channels(self):
            return [
                ChannelDescriptor(id=STATE_CHANNEL, priority=5,
                                  send_queue_capacity=100),
                ChannelDescriptor(id=DATA_CHANNEL, priority=10,
                                  send_queue_capacity=1000,
                                  recv_message_capacity=1048576),
                ChannelDescriptor(id=VOTE_CHANNEL, priority=5,
                                  send_queue_capacity=4000,
                                  recv_message_capacity=100 * 1024),
                ChannelDescriptor(id=VOTE_SET_BITS_CHANNEL, priority=1,
                                  send_queue_capacity=2,
                                  recv_message_capacity=1024),
            ]

        def start(self) -> None:
            self._thread = threading.Thread(target=self._push_loop,
                                            name="bench-gossip", daemon=True)
            self._thread.start()

        def stop(self) -> None:
            with self._cond:
                self._halt = True
                self._cond.notify_all()
            if self._thread is not None:
                self._thread.join(5)

        def add_peer(self, peer) -> None:
            self.peer = peer
            # a peer ahead of the node: at its tip, waiting for a proposal
            peer.send(STATE_CHANNEL, encode_msg(NewRoundStepMessage(
                height=tip + 1, round=0, step=1, seconds_since_start_time=0,
                last_commit_round=0)))

        def remove_peer(self, peer, reason) -> None:
            self.drop_reason = reason
            self.dropped_at = time.monotonic()
            self.dropped.set()

        def receive(self, ch_id, peer, msg_bytes) -> None:
            # the node's HasVote and step messages: only its steps matter,
            # and only a new height is news
            if ch_id != STATE_CHANNEL or not msg_bytes.startswith(step_head):
                return
            h = decode_msg(msg_bytes).height
            if h > self.entered:
                with self._cond:
                    self.peer = peer
                    self.entered = max(self.entered, h)
                    self._cond.notify_all()

        def close(self) -> None:
            with self._cond:
                self.closed = True
                self._cond.notify_all()

        def _messages(self, h: int):
            i = h - 1
            pre, pc = rounds.prevote_msgs[i], rounds.precommit_msgs[i]
            pre_order, pc_order = rounds.orders[i]
            if self.closed:
                pre, pc, pc_order = self.corrupt(h)
            yield DATA_CHANNEL, rounds.proposal_msg[i]
            for m in rounds.part_msgs[i]:
                yield DATA_CHANNEL, m
            for j in pre_order:
                yield VOTE_CHANNEL, pre[j]
            for j in pc_order:
                yield VOTE_CHANNEL, pc[j]

        def _push_loop(self) -> None:
            while True:
                with self._cond:
                    while not self._halt and self.entered <= self.pushed:
                        self._cond.wait(0.5)
                    if self._halt:
                        return
                    h = self.entered
                    closed = self.closed
                if h > tip or (not closed and h > last) or (
                        closed and len(self.corrupted) >= TAIL_HEIGHTS):
                    with self._cond:  # nothing to push until the close
                        self._cond.wait(0.05)
                    continue
                if closed:
                    self.corrupted.append(h)
                for ch, m in self._messages(h):
                    if not self.peer.send(ch, m):
                        break  # the connection is gone or stuck
                self.pushed = h

    class Sink(Reactor):
        """Owns the node's other channels and drops what arrives."""

        def __init__(self):
            super().__init__("BenchSink")

        def get_channels(self):
            return [ChannelDescriptor(id=d.id, priority=d.priority)
                    for d in other_channels]

    nk = NodeKey(PrivKeyEd25519.generate())
    ni = NodeInfo(protocol_version=ProtocolVersion(), id=nk.id, listen_addr="",
                  network=rounds.chain_id, version="dev",
                  channels=bytes([STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL,
                                  VOTE_SET_BITS_CHANNEL]
                                 + [d.id for d in other_channels]),
                  moniker="bench-gossip")
    tr = MultiplexTransport(ni, nk)
    tr.listen("127.0.0.1:0")
    ni.listen_addr = tr.listen_addr
    sw = Switch(tr, mconfig=MConnConfig(send_rate=rate, recv_rate=rate))
    gossip = Gossip()
    sw.add_reactor("CONSENSUS", gossip)
    sw.add_reactor("SINK", Sink())
    return sw, gossip


def warm_vote_buckets(triples: list, verifier: dict, buckets: list) -> None:
    """Every bucket a drained run can reach goes once through the device
    backend by name (below the calibrated cutoff `adaptive` would send
    it to the host and warm nothing), with fresh vote-sized triples: the
    node warms 8, 16 and 64 itself, and any other bucket would compile or
    load in the window."""
    if verifier["backend"] == "cpu":
        return
    from tendermint_tpu.crypto import batch as crypto_batch

    lo = 0
    for b in buckets:
        t = time.monotonic()
        chunk = triples[lo:lo + b]
        lo += b
        if len(chunk) < b:
            raise RuntimeError("not enough warm-up triples for the buckets")
        if not all(crypto_batch.batch_verify(chunk, backend="jax")):
            raise RuntimeError(f"warm-up batch of {b} votes did not verify")
        say(f"vote bucket {b} ready in {time.monotonic() - t:.1f}s")


def _wait_height(poller, h: int, deadline_s: float):
    """The stamped instant the store reached `h`, or None at the deadline."""
    try:
        return poller.wait_height(h, deadline_s)
    except RuntimeError:
        return None


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    traffic, cfg = cell.traffic, cell.config
    n_vals = cfg["validators"]
    warm = traffic["warmup_heights"]
    # honest heights, then TAIL_HEIGHTS that only the tail can reach
    last = warm + int(traffic["chain_heights_per_s"] * seconds + 0.999)
    n_heights = last + TAIL_HEIGHTS
    deadline = traffic.get("deadline_s", 120)
    tail_deadline = min(TAIL_DEADLINE_S, deadline)
    rng = np.random.default_rng(seed)

    rounds = roundslib.committee(seed=seed, validators=n_vals)
    roundslib.build(rounds, heights=n_heights,
                    txs_per_block=traffic["txs_per_block"],
                    tx_bytes=traffic["tx_bytes"],
                    key_space=traffic["key_space"], workers=ctx.workers,
                    warm_lanes=sum(traffic["warm_buckets"]))
    say(f"chain of {n_heights} heights x {n_vals} validators (a proposal, "
        f"{2 * n_vals} votes a height) built in {rounds.build_s:.1f}s")
    home = tempfile.mkdtemp(prefix="bench_home_")
    node = poller = sw = None
    try:
        node = nodelib.build_node(home, rounds.chain_id, cfg,
                                  genesis_json=rounds.genesis.to_json(),
                                  trace=ctx.trace_on)
        others = [d for d in node.sw.ch_descs
                  if d.id not in (STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL,
                                  VOTE_SET_BITS_CHANNEL)]
        sw, gossip = make_gossip_switch(rounds, cfg["p2p_rate_bytes_per_s"],
                                        others, last)
        where: dict = {}

        def corrupt(h: int):
            pre, pc, order, bad = roundslib.corrupted(
                rounds, h, rng, n_vals // 2, n_vals, 2 * n_vals // 3)
            where[h] = bad
            return pre, pc, order

        gossip.corrupt = corrupt
        ctx.install(node)
        node.start()
        sw.start()
        surf = nodelib.Surfaces(node)
        verifier = surf.wait_verifier(traffic.get("verifier_deadline_s", 1100))
        say("node verifier:", verifier)
        if str(verifier.get("warmup")).startswith("error"):
            raise RuntimeError(f"verify warm-up failed: {verifier}")
        warm_vote_buckets(rounds.warm_triples, verifier, traffic["warm_buckets"])

        close: dict = {}

        def at_close() -> None:
            """The window's last instant, on the poller's thread: from
            here the peer pushes corrupted heights."""
            close["height"] = poller.height_at(poller.t_close)
            close["early_drop"] = (gossip.dropped.is_set()
                                   and gossip.dropped_at <= poller.t_close)
            close["caught_up"] = close["height"] >= last
            gossip.close()

        store = node.block_store
        poller = HeightPoller(store, _NoTip(), 0, n_heights, at_close)
        poller.start()
        addr = node.transport.listen_addr
        if sw.dial_peer(addr, expect_id=node.node_key.id) is None:
            raise RuntimeError(f"the gossip peer could not dial {addr}")

        numbers: dict = {}
        t_open = _wait_height(poller, warm, deadline)
        if t_open is None:  # the node does not commit: the line says so
            numbers["warmup_not_committed"] = (warm - store.height(), 0)
            t_open = time.monotonic()
            say(f"the node reached height {store.height()} of {warm} "
                f"warm-up heights in {deadline}s")
        ctx.window_opens(t_open, surf)
        poller.t_close = t_close = ctx.t_close
        ctx.wait_until(t_close)
        peak = ctx.window_closes()
        if not poller.closed.wait(10):
            raise RuntimeError("the height poller did not close the window")
        if poller.error is not None:
            raise poller.error
        h_open, h_end = poller.height_at(t_open), poller.height_at(t_close)
        window_s = seconds
        if close["caught_up"]:  # the chain ran out: the rate is over the time it had work
            window_s = next(t for t, h in poller.marks if h >= last) - t_open
            say(f"the node committed the {last} honest heights "
                f"{window_s:.2f}s into a {seconds}s window")
        blocks = h_end - h_open
        used = 100.0 * (h_end - warm) / (last - warm)
        say(f"window: heights {h_open}..{h_end} in {window_s:.3f}s "
            f"({blocks / window_s:.4f} heights/s), {used:.1f}% of the chain "
            f"used")
        ctx.trace_stop()

        # --- the tail: TAIL_HEIGHTS heights with corrupted votes --------
        end = time.monotonic() + tail_deadline
        while (len(gossip.corrupted) < TAIL_HEIGHTS
               or store.height() < gossip.corrupted[-1]) \
                and time.monotonic() < end and not gossip.dropped.is_set():
            time.sleep(0.01)
        time.sleep(0.3)  # anything it still does shows here
        final = store.height()
        tail = list(gossip.corrupted)
        say(f"corrupted heights pushed {tail}, node at {final}"
            + (f", peer dropped: {gossip.drop_reason}"
               if gossip.dropped.is_set() else ""))
        # a corrupted height never pushed, because the node did not get
        # that far, counts as not committed too
        first = tail[0] if tail else (gossip.entered or final + 1)
        numbers["height_not_committed"] = (
            max(0, first + TAIL_HEIGHTS - 1 - final), 0)
        numbers["bad_vote_in_seen_commit"] = (
            _bad_in_seen_commits(rounds, store, where, final), 0)
        numbers["honest_peer_dropped"] = (1 if gossip.dropped.is_set() else 0, 0)
        # a height is in the store a step before the app has it
        state_of = node.consensus_state
        end = time.monotonic() + tail_deadline
        while state_of.state.last_block_height < final and time.monotonic() < end:
            time.sleep(0.01)
        numbers.update(_check_applied(rounds, Rpc(surf.rpc_addr), final, rng,
                                      traffic["check_heights"],
                                      traffic["check_keys"]))
        facts = {
            "blocks": blocks, "window_s": window_s,
            "votes_per_height": 2 * n_vals,
            "height_open": h_open, "height_end": h_end,
            # how near the node came to the end of the honest chain
            "chain_used_pct": used,
            "corrupted_heights": tail,
            "corrupted_votes": [[h, t, i] for h, bad in where.items()
                                for t, i, _ in bad],
        }
        return {
            "end_to_end": {"sync_blocks_per_s": blocks / window_s},
            "attempted": blocks + (1 if close["early_drop"] else 0),
            "failed": 1 if close["early_drop"] else 0,
            "numbers": numbers, "facts": facts, "peak": peak,
        }
    finally:
        if poller is not None:
            poller.stop()
        if sw is not None:
            sw.stop()
        if node is not None:
            node.stop()
            node.wait(60)
        shutil.rmtree(home, ignore_errors=True)


def _bad_in_seen_commits(rounds, store, where: dict, final: int) -> int:
    """Corrupted heights the node committed whose stored seen commit
    holds the corrupted precommit, or any signature OpenSSL refuses."""
    bad = 0
    for h, votes in where.items():
        if h > final:
            continue
        seen = store.load_seen_commit(h)
        sigs = {(i, s) for t, i, s in votes if t == roundslib.VOTE_TYPE_PRECOMMIT}
        held = [v for v in seen.precommits if v is not None]
        if any((v.validator_index, v.signature) in sigs for v in held) or any(
                not verify_one(v.sign_bytes(rounds.chain_id), v.signature,
                               rounds.pubkeys[v.validator_index])
                for v in held):
            bad += 1
    return bad
