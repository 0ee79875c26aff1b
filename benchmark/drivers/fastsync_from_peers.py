"""Driver kind `fastsync_from_peers`: a joiner node catches up from
`peers` serving peers at once, each over a p2p connection of its own on
loopback, and one of them turns dishonest when the window has closed.

Set-up and window are `fastsync_from_peer`'s (its `HeightPoller`,
`warm_commit_shape` and `_check_applied` are used as they are): the
chain is signed before the joiner exists, every serving switch (its own
node key, its own send and receive limiter at the configuration's rate
a connection) is dialled before the warm-up blocks, one poller moves
every peer's tip `lookahead` ahead of the joiner's store, and the rate
is the heights the store gained in `[t_open, t_open + seconds]`.

After the window, on the poller's thread and in the same breath as the
close: every tip is frozen at T, and the peer drawn from the seed (A)
puts a block T+2 on offer whose LastCommit has one flipped signature
bit, and alone advertises T+2, so A delivers T+1 and T+2. The joiner
has to stop at T and drop A. Then the honest peers advertise T+3: the
joiner has to keep them and apply the honest copies of T+1 and T+2 from
them (the configuration's fourth guarantee). Five numbers, limit 0 each:
`height_past_bad_commit`, `stopped_short_of_bad_commit` (as the one-peer
driver reads them), `dishonest_peer_not_dropped`, `honest_peer_dropped`
(at any time), `honest_copy_not_applied` ((T+2) less the store's height
once it stands still). Every wait of the tail ends by a deadline of at
most 60 s, and a run that misses one still prints its line, not correct.
"""

from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from ..harness import chain as chainlib
from ..harness import node as nodelib
from ..harness import peer as peerlib
from ..harness.rpcclient import Rpc
from .fastsync_from_peer import (HeightPoller, _check_applied, say,
                                 warm_commit_shape)

TAIL_DEADLINE_S = 60
PAST_BAD_COMMIT = 3  # T+2 the corrupted commit, T+3 what proves T+2 honest


class Tips:
    """What the one poller moves: every serving peer's advertised tip."""

    def __init__(self, servings: list):
        self.servings = servings

    def advertise(self, tip: int) -> None:
        for serving in self.servings:
            serving.advertise(tip)


def _until(done, deadline_s: float) -> bool:
    end = time.monotonic() + deadline_s
    while not done():
        if time.monotonic() > end:
            return False
        time.sleep(0.01)
    return True


def _stands_still(store, quiet_s: float, deadline_s: float) -> int:
    """The store's height once it has not moved for `quiet_s`."""
    end = time.monotonic() + deadline_s
    since, h = time.monotonic(), store.height()
    while time.monotonic() - since < quiet_s and time.monotonic() < end:
        time.sleep(0.05)
        if store.height() != h:
            since, h = time.monotonic(), store.height()
    return h


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    traffic, cfg = cell.traffic, cell.config
    n_vals, n_peers = cfg["validators"], cfg["peers"]
    warm = traffic["warmup_blocks"]
    lookahead = traffic["lookahead_blocks"]
    n_blocks = (warm + lookahead + 4
                + int(traffic["chain_blocks_per_s"] * seconds + 0.999))
    last = n_blocks - PAST_BAD_COMMIT  # the furthest any tip goes in the window
    setup_deadline = traffic.get("deadline_s", 1100)
    tail_deadline = min(TAIL_DEADLINE_S, traffic.get("deadline_s", TAIL_DEADLINE_S))
    rng = np.random.default_rng(seed)
    dishonest = int(rng.integers(0, n_peers))

    chain = chainlib.committee(seed=seed, validators=n_vals)
    chainlib.sign_blocks(
        chain, blocks=n_blocks, txs_per_block=traffic["txs_per_block"],
        tx_bytes=traffic["tx_bytes"], key_space=traffic["key_space"],
        workers=ctx.workers)
    say(f"chain of {n_blocks} blocks x {n_vals} precommits built in "
        f"{chain.build_s:.1f}s; block {n_blocks} is "
        f"{len(chain.messages[-1])} bytes, all "
        f"{sum(map(len, chain.messages))}")
    home = tempfile.mkdtemp(prefix="bench_home_")
    node = poller = None
    switches: list = []
    try:
        node = nodelib.build_node(home, chain.chain_id, cfg,
                                  genesis_json=chain.genesis.to_json(),
                                  trace=ctx.trace_on)
        others = [d for d in node.sw.ch_descs
                  if d.id != peerlib.BLOCKCHAIN_CHANNEL]
        servings = []
        for _ in range(n_peers):
            sw, serving = peerlib.make_serving_switch(
                chain, cfg["p2p_rate_bytes_per_s"], others)
            switches.append(sw)
            servings.append(serving)
        ctx.install(node)
        node.start()
        for sw in switches:
            sw.start()
        surf = nodelib.Surfaces(node)
        verifier = surf.wait_verifier(setup_deadline)
        say("joiner verifier:", verifier)
        if str(verifier.get("warmup")).startswith("error"):
            raise RuntimeError(f"verify warm-up failed: {verifier}")
        say(f"commit of {n_vals} precommits verified in "
            f"{warm_commit_shape(node, chain):.1f}s before a peer is dialled")

        close: dict = {}  # what the poller's thread found and did at t_close

        def at_close() -> None:
            """The window's last instant, on the poller's thread: every
            tip is frozen, and peer A alone offers two blocks more, the
            second with the corrupted commit."""
            t_close = poller.t_close
            close["tip"] = tip = servings[0].tip
            close["served"] = [s.served for s in servings]
            close["early_drop"] = [
                i for i, s in enumerate(servings)
                if s.dropped.is_set() and s.dropped_at <= t_close]
            close["caught_up"] = poller.height_at(t_close) >= last - 1
            if close["early_drop"] or close["caught_up"]:
                return
            msg, close["where"] = chainlib.poisoned_message(
                chain, tip + 2, rng, [(n_vals // 2, n_vals)])
            servings[dishonest].poison[tip + 2] = msg
            servings[dishonest].advertise(tip + 2)
            close["offered"] = time.monotonic()

        poller = HeightPoller(node.block_store, Tips(servings), lookahead,
                              last, at_close)
        poller.start()
        addr = node.transport.listen_addr
        for sw in switches:
            if sw.dial_peer(addr, expect_id=node.node_key.id) is None:
                raise RuntimeError(f"a serving peer could not dial {addr}")

        t_open = poller.wait_height(warm, setup_deadline)
        served_open = [s.served for s in servings]
        ctx.window_opens(t_open, surf)
        poller.t_close = t_close = ctx.t_close
        ctx.wait_until(t_close)
        peak = ctx.window_closes()
        if not poller.closed.wait(10):
            raise RuntimeError("the height poller did not close the window")
        if poller.error is not None:
            raise poller.error
        h_open, h_end = poller.height_at(t_open), poller.height_at(t_close)
        tip, window_s = close["tip"], seconds
        early_drop, caught_up = close["early_drop"], close["caught_up"]
        if caught_up:  # the chain ran out: the rate is over the time it had work
            window_s = next(t for t, h in poller.marks if h >= last - 1) - t_open
            say(f"the joiner caught up with the {last} blocks it was offered "
                f"{window_s:.2f}s into a {seconds}s window")
        blocks = h_end - h_open
        used = 100.0 * (h_end - warm) / (last - 1 - warm)
        from_peer = [b - a for a, b in zip(served_open, close["served"])]
        say(f"window: heights {h_open}..{h_end} in {window_s:.3f}s "
            f"({blocks / window_s:.4f} blocks/s), tips frozen at {tip}, "
            f"{used:.1f}% of the chain used, blocks served by each peer "
            f"{from_peer}")
        if not caught_up and last - tip < lookahead:
            say(f"WARNING: {last - tip} blocks of the chain lie past the "
                f"frozen tip: a joiner {lookahead} heights faster cannot be "
                f"offered the corrupted commit and its run is not correct "
                f"(chain_blocks_per_s, benchmark/README.md)")

        # --- the dishonest tail ------------------------------------------
        ctx.trace_stop()  # seconds, in which the joiner walks to the tip
        numbers: dict = {}
        store = node.block_store
        if early_drop:
            say(f"the joiner dropped honest peers {early_drop} in the window: "
                f"{[servings[i].drop_reason for i in early_drop]}")
            numbers["honest_blocks_refused"] = (1, 0)
            _stands_still(store, 1.5, tail_deadline)
        elif caught_up:
            numbers["bad_commit_not_offered"] = (1, 0)
        else:
            numbers["honest_blocks_refused"] = (0, 0)
            say(f"corrupted precommit of validator {close['where']} offered by "
                f"peer {dishonest} in block {tip + 2}, "
                f"{close['offered'] - t_close:.3f}s after the window")
            a = servings[dishonest]
            honest = [s for s in servings if s is not a]
            a_dropped = a.dropped.wait(tail_deadline)
            time.sleep(0.3)  # anything it still applies shows here
            at_bad = store.height()
            say(f"joiner at {at_bad}, dishonest peer dropped: {a.drop_reason}")
            numbers["dishonest_peer_not_dropped"] = (0 if a_dropped else 1, 0)
            numbers["height_past_bad_commit"] = (at_bad - tip, 0)
            numbers["stopped_short_of_bad_commit"] = (tip - at_bad, 0)
            # the honest copies: T+3 proves T+2, so the store ends at T+2
            for s in honest:
                s.advertise(tip + PAST_BAD_COMMIT)
            _until(lambda: store.height() >= tip + 2
                   or all(s.dropped.is_set() for s in honest), tail_deadline)
            at_end = _stands_still(store, 0.3, tail_deadline)
            lost = [s.drop_reason for s in honest if s.dropped.is_set()]
            say(f"joiner at {at_end} after the honest copies"
                + (f", honest peers dropped: {lost}" if lost else ""))
            numbers["honest_copy_not_applied"] = (tip + 2 - at_end, 0)
            numbers["honest_peer_dropped"] = (len(lost), 0)
        # a height is in the store a step before the app has it
        final = store.height()
        _until(lambda: node.blockchain_reactor.state.last_block_height >= final,
               tail_deadline)
        numbers.update(_check_applied(chain, Rpc(surf.rpc_addr), final, rng,
                                      traffic["check_heights"],
                                      traffic["check_keys"]))
        facts = {
            "blocks": blocks, "window_s": window_s,
            "signatures_per_block": n_vals,
            "height_open": h_open, "height_end": h_end, "tip_at_close": tip,
            # how near the joiner came to the end of the chain it could use
            "chain_used_pct": used,
            # block requests each peer answered inside the window
            "blocks_from_peer": from_peer,
            "dishonest_peer": dishonest,
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
        for sw in switches:
            sw.stop()
        if node is not None:
            node.stop()
            node.wait(60)
        shutil.rmtree(home, ignore_errors=True)
