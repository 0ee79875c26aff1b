"""BlockPool — parallel block download for fast sync.

Reference parity: blockchain/pool.go.  Per-height requesters ask peers
for blocks (bounded in-flight window), time out slow peers, and hand
blocks to the reactor in strict height order via peek_two_blocks /
pop_request (:62-105,328).  A refused commit drops both blocks of the
pair and everything their peers delivered (redo_request).  The
reference's receive-rate floor (:129 minRecvRate) is not implemented:
a slow peer is removed by PEER_TIMEOUT alone.

Counters, through the process-wide sink (crypto/batch.get_metrics()):
pool_requests{slot} a request sent, pool_blocks_received{slot} a block
taken, redo_heights a delivered block dropped and asked again. A
peer's slot is the lowest number no live peer of this pool holds when
the pool first hears of it: bounded by the peer limit, never an id.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..crypto import batch as crypto_batch

LOG = logging.getLogger("blockchain.pool")

REQUEST_INTERVAL = 0.01  # pool.go:36 requestIntervalMS
MAX_TOTAL_REQUESTERS = 600  # pool.go:37
MAX_PENDING_REQUESTS = 600  # pool.go:38
MAX_PENDING_REQUESTS_PER_PEER = 20  # pool.go:39
PEER_TIMEOUT = 15.0  # pool.go:41


class _PoolPeer:
    def __init__(self, peer_id: str, height: int, slot: str):
        self.id = peer_id
        self.height = height
        self.slot = slot  # the label of this peer's counters
        self.num_pending = 0
        self.timeout_at: Optional[float] = None
        self.did_timeout = False

    def touch(self) -> None:
        """(re)arm the response timer (pool.go:516-540)."""
        self.timeout_at = time.monotonic() + PEER_TIMEOUT

    def disarm(self) -> None:
        self.timeout_at = None


class _Requester:
    """One outstanding height (pool.go:560-687); retries on timeout or
    peer removal by picking a new peer."""

    def __init__(self, height: int):
        self.height = height
        self.peer_id: Optional[str] = None
        self.block = None


class BlockPool:
    def __init__(
        self,
        start_height: int,
        request_fn: Callable[[str, int], None],
        error_fn: Callable[[str, str], None],
    ):
        self.height = start_height  # next height to process
        self._request_fn = request_fn  # (peer_id, height) -> send request
        self._error_fn = error_fn  # (peer_id, reason) -> punish peer
        self._lock = threading.RLock()
        self._peers: Dict[str, _PoolPeer] = {}
        self._requesters: Dict[int, _Requester] = {}
        self._max_peer_height = 0
        self._started_at = time.monotonic()
        self._num_received = 0
        self._running = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        self._running.set()
        self._thread = threading.Thread(target=self._make_requesters_routine, name="pool", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running.clear()

    def is_running(self) -> bool:
        return self._running.is_set()

    def _make_requesters_routine(self) -> None:
        """pool.go:105-150: keep the request window full; check timeouts."""
        while self._running.is_set():
            self._check_peer_timeouts()
            with self._lock:
                n_pending = sum(1 for r in self._requesters.values() if r.block is None)
                total = len(self._requesters)
                next_height = self.height + total
                make = (
                    n_pending < MAX_PENDING_REQUESTS
                    and total < MAX_TOTAL_REQUESTERS
                    and next_height <= self._max_peer_height
                )
                # requesters that couldn't get a peer earlier retry here
                # (the reference requester goroutine loops on redo)
                orphans = [
                    r.height
                    for r in self._requesters.values()
                    if r.peer_id is None and r.block is None
                ]
                if make:
                    self._requesters[next_height] = _Requester(next_height)
            for h in orphans:
                self._dispatch(h)
            if make:
                self._dispatch(next_height)
            else:
                time.sleep(REQUEST_INTERVAL)

    def _dispatch(self, height: int) -> None:
        """Assign a peer to the requester and fire the request."""
        with self._lock:
            req = self._requesters.get(height)
            if req is None or req.block is not None:
                return
            candidates = [
                p
                for p in self._peers.values()
                if not p.did_timeout
                and p.num_pending < MAX_PENDING_REQUESTS_PER_PEER
                and p.height >= height
            ]
            if not candidates:
                req.peer_id = None
                return
            peer = random.choice(candidates)
            peer.num_pending += 1
            if peer.num_pending == 1:
                peer.touch()
            req.peer_id = peer.id
        m = crypto_batch.get_metrics()
        if m is not None:
            m.pool_requests.with_labels(peer.slot).inc()
        self._request_fn(peer.id, height)

    def _check_peer_timeouts(self) -> None:
        with self._lock:
            now = time.monotonic()
            timed_out = [
                p for p in self._peers.values() if p.timeout_at and now > p.timeout_at
            ]
        for p in timed_out:
            self._error_fn(p.id, "block request timed out")
            self.remove_peer(p.id)

    # -- peer management -----------------------------------------------

    def set_peer_height(self, peer_id: str, height: int) -> None:
        """pool.go:224-241 SetPeerHeight (from StatusResponse)."""
        with self._lock:
            p = self._peers.get(peer_id)
            if p is None:
                taken = {q.slot for q in self._peers.values()}
                slot = next(s for s in map(str, range(len(taken) + 1))
                            if s not in taken)
                p = _PoolPeer(peer_id, height, slot)
                self._peers[peer_id] = p
            else:
                p.height = max(p.height, height)
            self._max_peer_height = max(self._max_peer_height, height)

    def remove_peer(self, peer_id: str) -> None:
        """pool.go:243-266: re-dispatch its outstanding requests. The
        blocks it delivered stay (a peer that timed out or hung up sent
        nothing wrong; one whose block is refused goes through
        redo_request, which drops them)."""
        redo: List[int] = []
        with self._lock:
            self._peers.pop(peer_id, None)
            for r in self._requesters.values():
                if r.peer_id == peer_id and r.block is None:
                    r.peer_id = None
                    redo.append(r.height)
        for h in redo:
            self._dispatch(h)

    # -- block intake --------------------------------------------------

    def add_block(self, peer_id: str, block, block_size: int) -> None:
        """pool.go:291-324."""
        with self._lock:
            req = self._requesters.get(block.header.height)
            if req is None or req.peer_id != peer_id or req.block is not None:
                # unsolicited or duplicate; reference just ignores
                return
            req.block = block
            self._num_received += 1
            p = self._peers.get(peer_id)
            if p is None:
                return
            p.num_pending = max(0, p.num_pending - 1)
            if p.num_pending == 0:
                p.disarm()
            else:
                p.touch()
        m = crypto_batch.get_metrics()
        if m is not None:
            m.pool_blocks_received.with_labels(p.slot).inc()

    def redo_request(self, height: int) -> Tuple[int, List[str]]:
        """The commit for block `height`, carried by block height+1,
        was refused (reactor.go:318-330: RedoRequest(first),
        RedoRequest(second), both peers stopped; pool.go removePeer
        redoes every requester of a removed peer). Either block may be
        the altered one, so both are dropped, the peers that delivered
        them are reported and removed, and every other block those
        peers delivered that is still in the pool goes with them: all
        are asked again of the peers that remain. Returns (blocks
        dropped, peers reported)."""
        dropped = 0
        redo: List[int] = []
        with self._lock:
            bad: List[str] = []
            for h in (height, height + 1):
                req = self._requesters.get(h)
                if (req is not None and req.block is not None
                        and req.peer_id and req.peer_id not in bad):
                    bad.append(req.peer_id)
            for req in self._requesters.values():
                if req.peer_id in bad:
                    if req.block is not None:
                        dropped += 1
                        req.block = None
                    req.peer_id = None
                    redo.append(req.height)
            for peer_id in bad:
                self._peers.pop(peer_id, None)
        m = crypto_batch.get_metrics()
        if m is not None and dropped:
            m.redo_heights.inc(dropped)
        for peer_id in bad:
            self._error_fn(peer_id, f"bad block at height {height}")
        for h in redo:
            self._dispatch(h)
        return dropped, bad

    # -- ordered hand-off ----------------------------------------------

    def peek_two_blocks(self):
        """pool.go:204-215: blocks at height and height+1 (or None)."""
        with self._lock:
            r1 = self._requesters.get(self.height)
            r2 = self._requesters.get(self.height + 1)
            return (r1.block if r1 else None, r2.block if r2 else None)

    def peek_window(self, k: int):
        """Contiguous run of downloaded blocks starting at the pool
        head, up to k blocks (ours: the aggregate-certificate
        pre-verification window — BLS catch-up batches the whole run's
        commit checks into one multi-pair product check)."""
        with self._lock:
            out = []
            for h in range(self.height, self.height + k):
                r = self._requesters.get(h)
                if r is None or r.block is None:
                    break
                out.append(r.block)
            return out

    def pop_request(self) -> None:
        """pool.go:217-222: first block verified — advance."""
        with self._lock:
            self._requesters.pop(self.height, None)
            self.height += 1

    # -- status --------------------------------------------------------

    def is_caught_up(self) -> bool:
        """pool.go:170-183."""
        with self._lock:
            if not self._peers:
                return False
            return self.height >= self._max_peer_height

    def max_peer_height(self) -> int:
        with self._lock:
            return self._max_peer_height

    def get_status(self):
        with self._lock:
            n_pending = sum(1 for r in self._requesters.values() if r.block is None)
            return self.height, n_pending, len(self._requesters)
