"""The committee of the live-consensus cell (`hub150-live`): every
validator of a chain but the node, as one peer in a process of its own.

The node under test holds one key of the committee and votes; the other
validators are phantoms that this process speaks for over one real p2p
connection on loopback (the four consensus channels). They follow
Tendermint v0.27's honest rules, round 0 of every height:

- At a phantom's turn the block of height h is built once the committee
  holds every precommit of h-1 (the node's included: `skip_timeout_commit`
  with `has_all`), or `timeout_commit` after it held +2/3 of them. Its
  LastCommit holds every precommit it holds, its txs are kv writes over a
  fixed key space, its app hash is the plain reference's and its time the
  weighted median of the LastCommit's stamps; the proposer signs the
  proposal. Then the proposal and its parts go out, and once the last
  part has left (a phantom votes once it holds the block), the
  phantoms' prevotes and then their precommits, each type in an order
  drawn from the seed, every vote its own `VoteMessage`.
- At the node's turn the committee waits for the node's proposal and
  parts, checks the block against its own view (header, app hash, time,
  a LastCommit holding the precommits it holds), then sends the
  phantoms' votes for it.
- A message sent because of one received (the node's precommit, its
  step into a new height, its block's last part) leaves `delay_ms` after
  it arrived.
- Phantom signatures come from `signer.py` worker processes; a vote's
  stamp is v0.27's `voteTime`: the later of the clock and the block's
  time plus `blocktime_iota`.

Nothing is sent for a height the node has not entered (its
`NewRoundStep`): a proposal for a height ahead of it would be dropped.
A block's LastCommit holds the node's precommit if it has come by the
time the block is built. After `hold` the committee begins no height
until `close`; after `close` the next `tail_heights` heights each carry one phantom
prevote and one phantom precommit with a flipped signature bit: nobody
holds all of that height's precommits, so the committee goes on after
`timeout_commit`, and the LastCommit it builds leaves the corrupted
phantom out. The committee records its own time a height (from what let
the height start to its last vote handed to the connection, less any
wait for the node), every vote of the node's it received and the
node's proposals.

`drivers/consensus_live.py` runs this module as
`python -m benchmark.harness.committee` with `JAX_PLATFORMS=cpu`, so it
never opens the chip and its work takes none of the node's interpreter
lock, and talks to it through `CommitteeProcess`: one JSON object a line
each way over the child's stdin and stdout (`start` builds the
committee and its signing workers, `dial` connects and begins the
heights, then `hold`, `close`, `status`, `report`, `stop`).
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

from . import chain as chainlib
from .reference import KVReference, verify_one
from .reference_live import weighted_median

STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL = (
    0x20, 0x21, 0x22, 0x23)
PREVOTE, PRECOMMIT = 1, 2
STEP_PROPOSE = 3
ANSWER_S = 120.0  # the most a call on the committee process may take
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class CommitteeError(RuntimeError):
    pass


class Committee:
    """The phantoms' honest rules over one connection to the node."""

    def __init__(self, *, seed: int, validators: int, node_index: int,
                 txs_per_block: int, tx_bytes: int, key_space: int,
                 delay_ms: float, workers: int, rate: int,
                 other_channels: list, tail_heights: int, max_heights: int,
                 deadline_s: float, node_id: str, node_addr: str):
        from tendermint_tpu.config import ConsensusConfig

        self.chain = chainlib.committee(seed=seed, validators=validators)
        self.n, self.me = validators, node_index
        self.node_pub = self.chain.pubkeys[node_index]
        self.txs_per_block, self.tx_bytes = txs_per_block, tx_bytes
        self.key_space = key_space
        self.delay = delay_ms / 1000.0
        self.tail_heights, self.max_heights = tail_heights, max_heights
        self.deadline = deadline_s
        self.node_id, self.node_addr = node_id, node_addr
        cc = ConsensusConfig()  # the deployment's defaults, as the node's
        self.iota, self.timeout_commit = cc.blocktime_iota, cc.timeout_commit
        # the traffic's draws: another stream than the committee's keys
        self.rng = np.random.default_rng([seed, 44])
        # the node's key stays out of this process: its slot signs with a
        # key of no validator, and that signature is never sent
        seeds = list(self.chain.seeds)
        seeds[node_index] = hashlib.sha256(b"not-a-validator").digest()
        self.pool = chainlib.SignerPool(seeds, workers)
        from cryptography.hazmat.primitives.asymmetric.ed25519 import \
            Ed25519PrivateKey

        self.keys = [Ed25519PrivateKey.from_private_bytes(s) for s in seeds]
        self.sw, self.reactor = _switch(self, rate, other_channels)

        self.cond = threading.Condition()
        self.node_height = 0
        self.entered_at: dict = {}       # h -> when the node stepped into h
        self.node_precommit: dict = {}   # h -> (Vote, arrival)
        self.node_proposal: dict = {}    # h -> Proposal whose signature verified
        self.node_parts: dict = {}       # h -> PartSet
        self.node_block: dict = {}       # h -> (Block, arrival of its last part)
        self.node_votes: list = []       # every vote of the node's received
        self.proposals: list = []        # [h, verified] for each of the node's
        self.relayed = 0                 # votes of others the node sent
        self.closed = self.held = False
        self.heights: list = []          # one record a height the committee began
        self.error = None
        self.dropped = None
        self.halt = threading.Event()
        self.thread = None

    # --- control ------------------------------------------------------

    def dial(self) -> dict:
        """Connects to the node and starts the heights."""
        self.sw.start()
        if self.sw.dial_peer(self.node_addr, expect_id=self.node_id) is None:
            raise CommitteeError(f"could not dial the node at {self.node_addr}")
        end = time.monotonic() + self.deadline
        while self.reactor.peer is None:
            if time.monotonic() > end:
                raise CommitteeError("the node's connection never came up")
            time.sleep(0.01)
        self.thread = threading.Thread(target=self._run, name="committee",
                                       daemon=True)
        self.thread.start()
        return {"node_index": self.me}

    def hold(self) -> dict:
        """The window is over: the committee finishes the height it is
        in and begins no other until `close` (a traced run's profiler
        stops meanwhile, which holds up the node's process)."""
        with self.cond:
            self.held = True
            self.cond.notify_all()
        return {}

    def close(self) -> dict:
        """The next `tail_heights` heights the committee begins are
        corrupted; it begins them now."""
        with self.cond:
            self.closed, self.held = True, False
            self.cond.notify_all()
        return {}

    def status(self) -> dict:
        return {"tail": [r["h"] for r in self.heights if r["corrupt"]],
                "done": self.thread is not None and not self.thread.is_alive(),
                "dropped": self.dropped, "error": self.error}

    def report(self) -> dict:
        return {"heights": self.heights, "node_votes": self.node_votes,
                "proposals": self.proposals, "relayed": self.relayed,
                "dropped": self.dropped, "error": self.error}

    def stop(self) -> dict:
        self.halt.set()
        with self.cond:
            self.cond.notify_all()
        if self.thread is not None:
            self.thread.join(10)
        self.sw.stop()
        self.pool.close()
        return {}

    # --- what arrives (the connection's receive thread) ----------------

    def on_step(self, height: int, now: float) -> None:
        with self.cond:
            if height > self.node_height:
                self.node_height = height
                self.entered_at[height] = now
                self.cond.notify_all()

    def on_vote(self, vote, now: float) -> None:
        if vote.validator_index != self.me:
            self.relayed += 1
            return
        sign_bytes = vote.sign_bytes(self.chain.chain_id)
        ok = verify_one(sign_bytes, vote.signature, self.node_pub)
        self.node_votes.append([vote.height, vote.round, vote.type,
                                sign_bytes.hex(), vote.signature.hex(), ok])
        if ok and vote.type == PRECOMMIT and vote.round == 0:
            with self.cond:
                self.node_precommit.setdefault(vote.height, (vote, now))
                self.cond.notify_all()

    def on_proposal(self, proposal) -> None:
        from tendermint_tpu.types.part_set import PartSet

        ok = verify_one(proposal.sign_bytes(self.chain.chain_id),
                        proposal.signature, self.node_pub)
        self.proposals.append([proposal.height, ok])
        if ok and proposal.round == 0 and proposal.height not in self.node_proposal:
            with self.cond:
                self.node_proposal[proposal.height] = proposal
                self.node_parts[proposal.height] = PartSet(
                    proposal.block_parts_header)

    def on_part(self, msg, now: float) -> None:
        from tendermint_tpu.types import serde

        parts = self.node_parts.get(msg.height)
        if parts is None or parts.is_complete():
            return
        try:
            parts.add_part(msg.part)
        except ValueError:
            return
        if parts.is_complete():
            block = serde.decode_block(parts.assemble())
            with self.cond:
                self.node_block[msg.height] = (block, now)
                self.cond.notify_all()

    # --- the heights ----------------------------------------------------

    def _wait(self, ready, until: float | None = None):
        """Waits under the condition until `ready()` is true (returns
        True) or `until` passes (False); the run's deadline, a stop or a
        lost connection raise."""
        end = time.monotonic() + self.deadline
        with self.cond:
            while not ready():
                now = time.monotonic()
                if self.halt.is_set():
                    raise CommitteeError("stopped")
                if self.dropped is not None:
                    raise CommitteeError(f"the node dropped the committee: "
                                         f"{self.dropped}")
                if until is not None and now >= until:
                    return False
                if now > end:
                    raise CommitteeError("the node went quiet")
                self.cond.wait(min(0.05, max(0.0, (until or end) - now)))
        return True

    def _send(self, ch: int, msg: bytes) -> None:
        if not self.reactor.peer.send(ch, msg):
            raise CommitteeError("the connection to the node is gone")

    def _run(self) -> None:
        try:
            self._heights()
        except CommitteeError as e:
            if not self.halt.is_set():
                self.error = str(e)
        except Exception:  # `consensus_live` reads it in the report
            self.error = traceback.format_exc()

    def _heights(self) -> None:
        from tendermint_tpu.abci import types as abci
        from tendermint_tpu.consensus.messages import NewRoundStepMessage
        from tendermint_tpu.consensus.reactor import encode_msg
        from tendermint_tpu.state.execution import ABCIResponses
        from tendermint_tpu.types.basic import BlockID

        chain, n = self.chain, self.n
        doc, genesis = chain.genesis, chain.state0
        vals = genesis.validators.validators
        self.addresses = [v.address for v in vals]
        self.powers = [v.voting_power for v in vals]
        index_of = {a: i for i, a in enumerate(self.addresses)}
        cur, nxt = genesis.validators.copy(), genesis.next_validators.copy()
        perm = self.rng.permutation(self.key_space)
        ref = KVReference()
        view = {"app_hash": doc.app_hash, "last_id": BlockID(),
                "last_commit": None, "last_time": doc.genesis_time,
                "total_txs": 0, "last_results": b"", "n_txs": 0}
        ready_at = time.monotonic()  # what let height 1 start: the dial
        after = "dial"
        tail = 0
        for h in range(1, self.max_heights + 1):
            if self.held:
                # the chain stood still: the last height gets its
                # timeout_commit again, from here, for the node's precommit
                self._wait(lambda: not self.held)
                self._wait(lambda: h - 1 in self.node_precommit,
                           time.monotonic() + self.timeout_commit)
            if self.closed and tail >= self.tail_heights:
                return  # the tail is out: nothing more is sent
            self._wait(lambda: self.node_height >= h)
            corrupt = self.closed
            tail += corrupt
            trigger = max(ready_at, self.entered_at.get(h, ready_at))
            proposer = index_of[cur.get_proposer().address]
            rec = {"h": h, "proposer": proposer, "own": proposer == self.me,
                   "corrupt": [], "after": after}
            self._at(trigger + self.delay)
            self._send(STATE_CHANNEL, encode_msg(NewRoundStepMessage(
                height=h, round=0, step=STEP_PROPOSE,
                seconds_since_start_time=0, last_commit_round=0)))
            # from what let the height begin to its first message out
            rec["lag_ms"] = 1000.0 * (time.monotonic() - ready_at)
            busy = time.monotonic() - trigger
            if rec["own"]:
                self._wait(lambda: h in self.node_block)
                block, t_block = self.node_block[h]
                self._last_commit(h, view)
                self._check_node_block(h, block, view)
                trigger = t_block
                block_id = BlockID(
                    hash=block.hash(),
                    parts_header=self.node_proposal[h].block_parts_header)
                signed = self._sign(h, block, block_id)
                self._at(trigger + self.delay)
                txs = list(block.data.txs)
            else:
                txs = self._txs(h, perm)
                self._last_commit(h, view)
                block, parts = self._block(h, txs, proposer, view)
                block_id = BlockID(hash=block.hash(), parts_header=parts.header())
                signed = self._sign(h, block, block_id)  # while the block leaves
                self._propose(h, block, parts, proposer)
            bad, precommits = self._votes(h, block_id, signed, corrupt)
            busy += time.monotonic() - trigger
            rec.update(block_hash=block_id.hash.hex(),
                       txs=[tx.hex() for tx in txs], ms=1000.0 * busy,
                       corrupt=[[t, i, s.hex()] for t, i, s in bad])
            self.heights.append(rec)
            # the phantoms hold +2/3 precommits now: the height waits for
            # all of them (the node's), or timeout_commit where a
            # corrupted one is missing or the node's does not come
            t_commit = time.monotonic()
            until = t_commit + self.timeout_commit
            if not corrupt and self._wait(lambda: h in self.node_precommit,
                                          until):
                ready_at, after = self.node_precommit[h][1], "precommit"
            else:
                self._wait(lambda: False, until)
                ready_at, after = until, "timeout_commit"
            bad_ix = {i for t, i, _ in bad if t == PRECOMMIT}
            commit = [None if i in bad_ix else v
                      for i, v in enumerate(precommits)]
            commit[self.me] = None  # the node's, when it comes: _last_commit
            rec["own_precommit"] = self._own_precommit(h, block_id) is not None
            for tx in txs:
                ref.deliver(tx)
            view.update(
                app_hash=ref.commit(), last_id=block_id, last_rec=rec,
                last_commit=commit, last_time=block.header.time,
                total_txs=view["total_txs"] + len(txs),
                last_results=ABCIResponses(
                    [abci.ResponseDeliverTx(code=0)] * len(txs),
                    None).results_hash(),
                n_txs=len(txs))
            cur, nxt = nxt, nxt.copy()
            nxt.increment_proposer_priority(1)
        raise CommitteeError(f"{self.max_heights} heights and no close")

    def _own_precommit(self, h: int, block_id):
        """The node's round-0 precommit of h for `block_id`, if it came."""
        own = self.node_precommit.get(h)
        return own[0] if own is not None and own[0].block_id == block_id \
            else None

    def _last_commit(self, h: int, view: dict) -> None:
        """The precommits of h-1 as the committee holds them now that it
        builds or checks block h: a proposer includes what it has, so a
        precommit of the node's that came after timeout_commit (a node
        held up) is in."""
        if h == 1:
            return
        own = self._own_precommit(h - 1, view["last_id"])
        view["last_commit"][self.me] = own
        view["last_rec"]["own_precommit"] = own is not None

    def _at(self, when: float) -> None:
        """Holds the next send until `when` (the injected delay)."""
        wait = when - time.monotonic()
        if wait > 0:
            time.sleep(wait)

    def _txs(self, h: int, perm) -> list:
        key_w = len(str(self.key_space - 1))
        out = []
        for i in range(self.txs_per_block):
            k = int(perm[((h - 1) * self.txs_per_block + i) % self.key_space])
            head = b"k%0*d=" % (key_w, k)
            out.append(head + self.rng.bytes(self.tx_bytes - len(head)))
        return out

    def _header_time(self, h: int, view: dict) -> int:
        if h == 1:
            return self.chain.genesis.genesis_time
        return weighted_median([(v.timestamp, p) for v, p in
                                zip(view["last_commit"], self.powers)
                                if v is not None])

    def _block(self, h: int, txs: list, proposer: int, view: dict):
        from tendermint_tpu.types.block import (Block, Commit, Data,
                                                EvidenceData, Header)
        from tendermint_tpu.types.part_set import PartSet

        genesis = self.chain.state0
        last_commit = (None if h == 1 else
                       Commit(view["last_id"], list(view["last_commit"])))
        block = Block(
            header=Header(
                chain_id=self.chain.chain_id, height=h,
                time=self._header_time(h, view), num_txs=len(txs),
                total_txs=view["total_txs"] + len(txs),
                last_block_id=view["last_id"],
                validators_hash=genesis.validators.hash(),
                next_validators_hash=genesis.next_validators.hash(),
                consensus_hash=genesis.consensus_params.hash(),
                app_hash=view["app_hash"],
                last_results_hash=view["last_results"],
                proposer_address=self.addresses[proposer]),
            data=Data(txs=txs), evidence=EvidenceData(evidence=[]),
            last_commit=last_commit)
        block.fill_header()
        return block, PartSet.from_data(block.encode())

    def _propose(self, h: int, block, parts, proposer: int) -> None:
        from tendermint_tpu.consensus.messages import (BlockPartMessage,
                                                       ProposalMessage)
        from tendermint_tpu.consensus.reactor import encode_msg
        from tendermint_tpu.types.basic import BlockID, Proposal

        proposal = Proposal(height=h, round=0,
                            block_parts_header=parts.header(), pol_round=-1,
                            pol_block_id=BlockID(), timestamp=block.header.time)
        proposal.signature = self.keys[proposer].sign(
            proposal.sign_bytes(self.chain.chain_id))
        self._send(DATA_CHANNEL, encode_msg(ProposalMessage(proposal)))
        for i in range(parts.total()):
            self._send(DATA_CHANNEL, encode_msg(
                BlockPartMessage(h, 0, parts.get_part(i))))
        # a phantom votes once it holds the block: no vote of theirs may
        # reach the node ahead of the block's last part, which the
        # connection would otherwise interleave with the votes by channel
        # priority
        channel = self.reactor.peer.mconn.channels[DATA_CHANNEL]
        end = time.monotonic() + self.deadline
        while channel.is_send_pending():
            if time.monotonic() > end:
                raise CommitteeError("the block's parts never left")
            time.sleep(0.0002)

    def _check_node_block(self, h: int, block, view: dict) -> None:
        """What an honest phantom checks before it prevotes the node's
        block: a LastCommit holding every precommit the committee holds,
        signature for signature (the node's own, if the committee has
        not had it yet, taken from the block once OpenSSL accepts it),
        and the header the committee's view then implies."""
        wrong = []
        if h > 1:
            got = block.last_commit.precommits
            held = view["last_commit"]
            mine = got[self.me] if len(got) == self.n else None
            if (held[self.me] is None and mine is not None
                    and mine.block_id == view["last_id"]
                    and verify_one(mine.sign_bytes(self.chain.chain_id),
                                   mine.signature, self.node_pub)):
                held[self.me] = mine
                view["last_rec"]["own_precommit"] = True
            if len(got) != self.n or any(
                    (a is None) != (b is None)
                    or (a is not None and a.signature != b.signature)
                    for a, b in zip(got, held)):
                wrong.append("last_commit")
        hd = block.header
        want = {"height": h, "last_block_id": view["last_id"],
                "app_hash": view["app_hash"],
                "last_results_hash": view["last_results"],
                "total_txs": view["total_txs"] + len(block.data.txs),
                "proposer_address": self.addresses[self.me],
                "time": self._header_time(h, view)}
        wrong += [k for k, v in want.items() if getattr(hd, k) != v]
        if wrong:
            raise CommitteeError(f"the node's block {h} differs in {wrong}")

    def _sign(self, h: int, block, block_id) -> tuple:
        """Starts the workers on the phantoms' prevotes and precommits
        for the block; returns (stamps, the calls that wait for them)."""
        from .rounds import splice_parts

        base = max(time.time_ns(), block.header.time + self.iota)
        stamps = [base + i for i in range(self.n)]
        return stamps, {t: self.pool.sign_spliced(
            *splice_parts(self.chain.chain_id, t, h, block_id), stamps)
            for t in (PREVOTE, PRECOMMIT)}

    def _votes(self, h: int, block_id, signed: tuple, corrupt: bool) -> tuple:
        """Sends the phantoms' prevotes, then their precommits, each in
        an order drawn for the height; at a corrupted height one of each,
        of a phantom drawn from the upper half of the committee, carries
        a flipped bit (the precommit among the first two thirds sent).
        Returns the corrupted [(type, index, signature)] and the
        precommits in validator order, for the next LastCommit."""
        from tendermint_tpu.consensus.messages import VoteMessage
        from tendermint_tpu.consensus.reactor import encode_msg
        from tendermint_tpu.types import Vote

        n, me = self.n, self.me
        stamps, waits = signed
        bad = []
        for t in (PREVOTE, PRECOMMIT):
            order = [int(i) for i in self.rng.permutation(n) if i != me]
            sigs = waits[t]()
            votes = [Vote(self.addresses[i], i, h, 0, stamps[i], t, block_id,
                          sigs[i]) for i in range(n)]
            if corrupt:
                i = me
                while i == me:
                    i = int(self.rng.integers(n // 2, n))
                sig = chainlib.flip_bit(sigs[i], int(self.rng.integers(0, 512)))
                bad.append((t, i, sig))
                at, to = order.index(i), int(self.rng.integers(0, 2 * n // 3))
                order[at], order[to] = order[to], order[at]
                votes[i] = Vote(self.addresses[i], i, h, 0, stamps[i], t,
                                block_id, sig)
            for i in order:
                self._send(VOTE_CHANNEL, encode_msg(VoteMessage(votes[i])))
        return bad, votes


def _switch(committee: Committee, rate: int, other_channels: list):
    """A switch that speaks the consensus channels for the committee and
    owns the node's other channels (dropping what arrives on them)."""
    from tendermint_tpu.consensus.messages import (BlockPartMessage,
                                                   NewRoundStepMessage,
                                                   ProposalMessage,
                                                   VoteMessage)
    from tendermint_tpu.consensus.reactor import decode_msg, encode_msg
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.p2p import (MultiplexTransport, NodeInfo, NodeKey,
                                    ProtocolVersion, Switch)
    from tendermint_tpu.p2p.base_reactor import ChannelDescriptor, Reactor
    from tendermint_tpu.p2p.conn.connection import MConnConfig

    step_head = encode_msg(NewRoundStepMessage(1, 0, 1, 0, 0))[:16]

    class Phantoms(Reactor):
        def __init__(self):
            super().__init__("BenchCommittee")
            self.peer = None

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

        def add_peer(self, peer) -> None:
            self.peer = peer

        def remove_peer(self, peer, reason) -> None:
            with committee.cond:
                committee.dropped = str(reason)
                committee.cond.notify_all()

        def receive(self, ch_id, peer, msg_bytes) -> None:
            now = time.monotonic()
            if ch_id == STATE_CHANNEL:
                # the node's HasVote messages are most of this channel:
                # only its steps are decoded
                if msg_bytes.startswith(step_head):
                    msg = decode_msg(msg_bytes)
                    if isinstance(msg, NewRoundStepMessage):
                        committee.on_step(msg.height, now)
            elif ch_id == VOTE_CHANNEL:
                msg = decode_msg(msg_bytes)
                if isinstance(msg, VoteMessage):
                    committee.on_vote(msg.vote, now)
            elif ch_id == DATA_CHANNEL:
                msg = decode_msg(msg_bytes)
                if isinstance(msg, ProposalMessage):
                    committee.on_proposal(msg.proposal)
                elif isinstance(msg, BlockPartMessage):
                    committee.on_part(msg, now)

    class Sink(Reactor):
        def __init__(self):
            super().__init__("BenchSink")

        def get_channels(self):
            return [ChannelDescriptor(id=i, priority=p)
                    for i, p in other_channels]

    nk = NodeKey(PrivKeyEd25519.generate())
    ni = NodeInfo(protocol_version=ProtocolVersion(), id=nk.id, listen_addr="",
                  network=committee.chain.chain_id, version="dev",
                  channels=bytes([STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL,
                                  VOTE_SET_BITS_CHANNEL]
                                 + [i for i, _ in other_channels]),
                  moniker="bench-committee")
    tr = MultiplexTransport(ni, nk)
    tr.listen("127.0.0.1:0")
    ni.listen_addr = tr.listen_addr
    sw = Switch(tr, mconfig=MConnConfig(send_rate=rate, recv_rate=rate))
    reactor = Phantoms()
    sw.add_reactor("CONSENSUS", reactor)
    sw.add_reactor("SINK", Sink())
    return sw, reactor


class CommitteeProcess:
    """`consensus_live`'s handle: starts the committee in a process of its own
    (JAX held to the CPU) and calls it, one JSON line each way."""

    def __init__(self, params: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu", TM_TPU_CRYPTO_BACKEND="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.harness.committee"], cwd=ROOT,
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._lock = threading.Lock()  # the poller's thread calls too
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, name="committee-out",
                         daemon=True).start()
        self.call("start", **params)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def call(self, cmd: str, **params) -> dict:
        with self._lock:
            self.proc.stdin.write(json.dumps(dict(params, cmd=cmd)) + "\n")
            self.proc.stdin.flush()
            try:
                line = self._lines.get(timeout=ANSWER_S)
            except queue.Empty:
                raise RuntimeError(f"the committee did not answer {cmd!r} "
                                   f"in {ANSWER_S:.0f}s") from None
        if line is None:
            raise RuntimeError(f"the committee exited on {cmd!r} "
                               f"(code {self.proc.poll()})")
        out = json.loads(line)
        if "failed" in out:
            raise RuntimeError(f"the committee failed {cmd!r}: {out['failed']}")
        return out

    def close(self) -> None:
        """Stops the committee and its process, whatever state it is in."""
        try:
            if self.proc.poll() is None:
                self.call("stop")
        except (RuntimeError, OSError, ValueError):
            pass
        finally:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5)


def main() -> int:
    # the protocol owns the real stdout; anything the program prints goes
    # to stderr with its logs
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    committee = None
    for line in sys.stdin:
        req = json.loads(line)
        cmd = req.pop("cmd")
        try:
            if cmd == "start":
                committee = Committee(**req)
                out = {}
            else:
                out = getattr(committee, cmd)()
        except Exception:
            out = {"failed": traceback.format_exc()}
        proto.write(json.dumps(out) + "\n")
        proto.flush()
        if cmd == "stop":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
