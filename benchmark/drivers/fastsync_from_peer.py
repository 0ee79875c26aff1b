"""Driver kind `fastsync_from_peer`: a joiner node catches up from one
serving peer over a real p2p connection on loopback.

Set-up draws the committee from the seed and signs its chain, starts
the serving switch and the joiner, waits for the joiner's verifier, has
the joiner's validator set
verify the chain's first commit through the call the sync loop makes
(which loads, or in a checkout's first run compiles, the cell's one
kernel shape while no peer is connected), connects the peer and lets
the joiner apply the cell's warm-up blocks. The window opens
at the instant the joiner's block store reaches the warm-up height and
lasts `seconds` on the height poller's clock, in every kind of run: the
rate is the heights the store gained from `t_open` to `t_open + seconds`
over `seconds`. The peer's advertised tip stays `lookahead` blocks ahead
of the joiner's store, as a live chain's does, and stands still from
`t_close` on, so what is downloaded ahead is bounded, the chain needs no
block for what follows the window, and the check after it is short.

After the window the peer turns dishonest: two blocks past its tip it
serves a block whose LastCommit has one flipped signature bit, in the
upper half of the committee. The joiner has to stop below it. The thread
that closes the window makes that offer, in the same breath: a joiner
left at a tip that stands still goes on to consensus within a second.
Only then are the closing readings taken and the profiler stopped
(benchmark/README.md, "The window and the profiler").
"""

from __future__ import annotations

import base64
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import chain as chainlib
from ..harness import node as nodelib
from ..harness import peer as peerlib
from ..harness.reference import KVReference
from ..harness.rpcclient import Rpc


def say(*parts) -> None:
    print("benchmark:", *parts, file=sys.stderr, flush=True)


class HeightPoller(threading.Thread):
    """Reads the joiner's block store every 2 ms, stamps every change and
    moves the serving peer's tip with it. It owns the window's close: at
    the first reading past `t_close` it freezes the advertised tip and
    calls `at_close` (the driver's offer of the corrupted commit), so
    whatever holds the main thread then stretches neither the window nor
    the chain the joiner is offered, and costs no run its `correct`."""

    def __init__(self, store, serving, lookahead: int, last: int, at_close):
        super().__init__(name="bench-height", daemon=True)
        self.store, self.serving = store, serving
        self.lookahead, self.last = lookahead, last
        self.at_close = at_close
        self.t_close = None
        self.frozen = False
        self.closed = threading.Event()  # set when at_close has returned
        self.error = None
        self.marks: list = []  # (monotonic, height) at each change
        self._halt = threading.Event()

    def run(self) -> None:
        seen = -1
        while not self._halt.is_set():
            h, now = self.store.height(), time.monotonic()
            if (self.t_close is not None and now > self.t_close
                    and not self.frozen):
                self.frozen = True  # a height stamped past t_close moves no tip
                try:
                    self.at_close()
                except Exception as e:  # the main thread raises it
                    self.error = e
                self.closed.set()
            if h != seen:
                seen = h
                self.marks.append((now, h))
                if not self.frozen:
                    self.serving.advertise(min(self.last, h + self.lookahead))
            time.sleep(0.002)

    def height_at(self, t: float) -> int:
        h = 0
        for when, height in self.marks:
            if when > t:
                break
            h = height
        return h

    def wait_height(self, h: int, deadline_s: float) -> float:
        """The stamped instant at which the store reached `h`."""
        end = time.monotonic() + deadline_s
        while True:
            for when, height in self.marks:
                if height >= h:
                    return when
            if time.monotonic() > end:
                raise RuntimeError(
                    f"joiner did not reach height {h} in {deadline_s:.0f}s "
                    f"(at {self.marks[-1][1] if self.marks else 0})")
            time.sleep(0.005)

    def stop(self) -> None:
        self._halt.set()
        self.join(5)


def warm_commit_shape(node, chain) -> float:
    """The joiner's validator set verifies the commit of block 1 (carried
    by block 2) through `begin_verify_commit`, as `_try_sync_batch_pipelined`
    does: the cell's one kernel shape is ready when this returns. It is
    done before the peer is dialled because a checkout's first run compiles
    here for a minute and a half, and a joiner that compiles inside its
    first `verify_commit` with block requests outstanding takes no block
    for the pool's 15 s peer timeout and drops the honest peer ("block
    request timed out"; PERF.md, Findings, PR 25)."""
    from tendermint_tpu.types import serde
    from tendermint_tpu.types.basic import BlockID
    from tendermint_tpu.types.block import make_part_set

    first, second = (serde.block_from(serde.unpack(m)[1])
                     for m in chain.messages[:2])
    block_id = BlockID(hash=first.hash(),
                       parts_header=make_part_set(first).header())
    state = node.blockchain_reactor.state
    t0 = time.monotonic()
    state.validators.begin_verify_commit(
        state.chain_id, block_id, first.header.height,
        second.last_commit).result()  # raises if the commit is refused
    return time.monotonic() - t0


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    traffic, cfg = cell.traffic, cell.config
    n_vals = cfg["validators"]
    warm = traffic["warmup_blocks"]
    lookahead = traffic["lookahead_blocks"]
    n_blocks = (warm + lookahead + 4
                + int(traffic["chain_blocks_per_s"] * seconds + 0.999))
    rng = np.random.default_rng(seed)

    chain = chainlib.committee(seed=seed, validators=n_vals)
    # signed before the joiner exists: nothing of the program runs beside
    # the twelve workers, so `kernel_ready_s` reads the node alone and
    # whatever a later PR moves into the node's start shows in `setup_s`
    chainlib.sign_blocks(
        chain, blocks=n_blocks, txs_per_block=traffic["txs_per_block"],
        tx_bytes=traffic["tx_bytes"], key_space=traffic["key_space"],
        workers=ctx.workers)
    say(f"chain of {n_blocks} blocks x {n_vals} precommits built in "
        f"{chain.build_s:.1f}s; block {n_blocks} is "
        f"{len(chain.messages[-1])} bytes, all "
        f"{sum(map(len, chain.messages))}")
    home = tempfile.mkdtemp(prefix="bench_home_")
    node = poller = sw = None
    try:
        node = nodelib.build_node(home, chain.chain_id, cfg,
                                  genesis_json=chain.genesis.to_json(),
                                  trace=ctx.trace_on)
        sw, serving = peerlib.make_serving_switch(
            chain, cfg["p2p_rate_bytes_per_s"],
            [d for d in node.sw.ch_descs if d.id != peerlib.BLOCKCHAIN_CHANNEL])
        ctx.install(node)
        node.start()
        sw.start()
        surf = nodelib.Surfaces(node)
        verifier = surf.wait_verifier(traffic.get("deadline_s", 1100))
        say("joiner verifier:", verifier)
        if str(verifier.get("warmup")).startswith("error"):
            raise RuntimeError(f"verify warm-up failed: {verifier}")
        say(f"commit of {n_vals} precommits verified in "
            f"{warm_commit_shape(node, chain):.1f}s before the peer is dialled")

        close: dict = {}  # what the poller's thread found and did at t_close

        def at_close() -> None:
            """The window's last instant, on the poller's thread: the tip
            is frozen, and two blocks past it the corrupted commit goes on
            offer before anything else can keep the joiner waiting."""
            t_close = poller.t_close
            close["tip"] = tip = serving.tip
            close["early_drop"] = (serving.dropped.is_set()
                                   and serving.dropped_at <= t_close)
            close["caught_up"] = poller.height_at(t_close) >= n_blocks - 1
            if close["early_drop"] or close["caught_up"]:
                return
            # one corrupted precommit, in the upper half of the committee:
            # a verifier that stops part-way through a batch lets it pass.
            # (One attempt only: the joiner keeps the refused block in its
            # pool and asks no peer for it again, PERF.md Open questions.)
            close["bad_h"] = bad_h = min(n_blocks, tip + 2)
            msg, close["where"] = chainlib.poisoned_message(
                chain, bad_h, rng, [(n_vals // 2, n_vals)])
            serving.poison[bad_h] = msg
            serving.advertise(bad_h)
            close["offered"] = time.monotonic()

        poller = HeightPoller(node.block_store, serving, lookahead, n_blocks,
                              at_close)
        poller.start()
        addr = node.transport.listen_addr
        if sw.dial_peer(addr, expect_id=node.node_key.id) is None:
            raise RuntimeError(f"the serving peer could not dial {addr}")

        t_open = poller.wait_height(warm, traffic.get("deadline_s", 1100))
        ctx.window_opens(t_open, surf)
        poller.t_close = t_close = ctx.t_close
        ctx.wait_until(t_close)
        # the window is over on the poller's clock, whatever kept this
        # thread: the height stamped last before t_close, the tip frozen,
        # the corrupted commit on offer. The closing readings meanwhile.
        peak = ctx.window_closes()
        if not poller.closed.wait(10):
            raise RuntimeError("the height poller did not close the window")
        if poller.error is not None:
            raise poller.error
        h_open, h_end = poller.height_at(t_open), poller.height_at(t_close)
        tip, window_s = close["tip"], seconds
        early_drop, caught_up = close["early_drop"], close["caught_up"]
        bad_h = close.get("bad_h")
        if caught_up:  # the chain ran out: the rate is over the time it had work
            window_s = next(t for t, h in poller.marks if h >= n_blocks - 1) - t_open
            say(f"the joiner caught up with the {n_blocks}-block chain "
                f"{window_s:.2f}s into a {seconds}s window")
        blocks = h_end - h_open
        used = 100.0 * (h_end - warm) / (n_blocks - 1 - warm)
        say(f"window: heights {h_open}..{h_end} in {window_s:.3f}s "
            f"({blocks / window_s:.4f} blocks/s), tip frozen at {tip}, "
            f"{used:.1f}% of the chain used")
        if not caught_up and n_blocks - (tip + 2) < lookahead:
            say(f"WARNING: {n_blocks - (tip + 2)} blocks of the chain lie past "
                f"the corrupted commit's place: a joiner {lookahead} heights "
                f"faster cannot be offered it and its run is not correct "
                f"(chain_blocks_per_s, benchmark/README.md)")

        # --- the dishonest tail ------------------------------------------
        numbers: dict = {}
        if early_drop:
            say(f"the joiner dropped the honest peer: {serving.drop_reason}")
            numbers["honest_blocks_refused"] = (1, 0)
        elif caught_up:
            # fast sync is over (the joiner went on to consensus), so the
            # corrupted commit cannot be offered: the cell needs a longer
            # chain (chain_blocks_per_s) before it can say `correct`
            numbers["bad_commit_not_offered"] = (1, 0)
        else:
            numbers["honest_blocks_refused"] = (0, 0)
            say(f"corrupted precommit of validator {close['where']} offered in "
                f"block {bad_h}, {close['offered'] - t_close:.3f}s after the "
                f"window")
        ctx.trace_stop()  # seconds, in which the joiner walks to the tip
        if early_drop:
            # it still applies what it had downloaded: let it finish, so
            # that the read-back below is of a store that stands still
            quiet, h = time.monotonic(), node.block_store.height()
            while time.monotonic() - quiet < 1.5:
                time.sleep(0.1)
                if node.block_store.height() != h:
                    quiet, h = time.monotonic(), node.block_store.height()
        elif bad_h is not None:
            if not serving.dropped.wait(traffic.get("deadline_s", 60)):
                say("the joiner never dropped the dishonest peer")
            time.sleep(0.3)  # anything it still applies shows here
            final = node.block_store.height()
            say(f"joiner at {final}, peer dropped: {serving.drop_reason}")
            numbers["height_past_bad_commit"] = (final - (bad_h - 2), 0)
            numbers["stopped_short_of_bad_commit"] = ((bad_h - 2) - final, 0)
        final = node.block_store.height()
        numbers.update(_check_applied(chain, Rpc(surf.rpc_addr), final, rng,
                                      traffic["check_heights"],
                                      traffic["check_keys"]))
        facts = {
            "blocks": blocks, "window_s": window_s,
            "signatures_per_block": n_vals,
            "height_open": h_open, "height_end": h_end, "tip_at_close": tip,
            # how near the joiner came to the end of the chain it could use
            "chain_used_pct": used,
        }
        return {
            "end_to_end": {"sync_blocks_per_s": blocks / window_s},
            "attempted": blocks + (1 if early_drop else 0),
            "failed": 1 if early_drop else 0,
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


def _check_applied(chain, rpc, final: int, rng, n_heights: int, n_keys: int) -> dict:
    """What the joiner serves for what it applied, against the serving
    chain and the plain reference: block hash and app hash at a seeded
    sample of heights (the last always), and the values of a seeded
    sample of keys read back through abci_query."""
    if final < 2:
        return {"applied_heights": (final, None)}
    heights = sorted({final, *(int(h) for h in rng.integers(1, final + 1,
                                                            n_heights))})
    wrong = 0
    for h in heights:
        got = rpc.call("block", {"height": h})
        if bytes.fromhex(got["block_meta"]["block_id"]["hash"]) != chain.block_hash[h - 1]:
            wrong += 1
        # header h carries the app hash after h-1
        if h >= 2 and bytes.fromhex(got["block"]["header"]["app_hash"]) \
                != chain.app_hash[h - 2]:
            wrong += 1
    info = rpc.call("abci_info")["response"]
    if base64.b64decode(info["last_block_app_hash"]) != chain.app_hash[final - 1] \
            or int(info["last_block_height"]) != final:
        wrong += 1
    ref = KVReference()
    for txs in chain.txs[:final]:
        for tx in txs:
            ref.deliver(tx)
    keys = sorted(ref.kv)
    stale = 0
    for i in rng.integers(0, len(keys), n_keys):
        key = keys[int(i)]
        got = rpc.call("abci_query", {"path": "", "data": key.hex()})["response"]
        if base64.b64decode(got.get("value") or "") != ref.kv[key]:
            stale += 1
    return {"hash_mismatches": (wrong, 0), "keys_read_back_wrong": (stale, 0)}
