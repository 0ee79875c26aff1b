"""Peer — a connected, authenticated remote node (reference p2p/peer.go).

Wraps the MConnection; carries the peer's NodeInfo and a per-peer data
dict used by reactors (e.g. ConsensusReactor stores PeerState here).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

from .conn.connection import MConnConfig, MConnection
from .node_info import NodeInfo


class Peer:
    def __init__(
        self,
        secret_conn,
        node_info: NodeInfo,
        ch_descs: List,
        on_receive: Callable[[int, "Peer", bytes], None],
        on_error: Callable[["Peer", Exception], None],
        outbound: bool,
        persistent: bool = False,
        mconfig: Optional[MConnConfig] = None,
        socket_addr: str = "",
        metrics=None,
    ):
        from ..metrics import P2PMetrics

        self.metrics = metrics if metrics is not None else P2PMetrics()
        self.node_info = node_info
        self.outbound = outbound
        self.persistent = persistent
        self.socket_addr = socket_addr  # "host:port" we dialed / accepted from
        self.data: Dict[str, object] = {}  # reactor scratch (peer.Set/Get)
        self._running = threading.Event()
        self.mconn = MConnection(
            secret_conn,
            ch_descs,
            on_receive=lambda ch_id, msg: on_receive(ch_id, self, msg),
            on_error=lambda err: on_error(self, err),
            config=mconfig,
            metrics=self.metrics,
            peer_id=node_info.id,
        )

    @property
    def id(self) -> str:
        return self.node_info.id

    def is_running(self) -> bool:
        return self._running.is_set()

    def start(self) -> None:
        self._running.set()
        self.mconn.start()

    def stop(self) -> None:
        self._running.clear()
        self.mconn.stop()

    def send(self, ch_id: int, msg_bytes: bytes) -> bool:
        if not self.is_running():
            return False
        ok = self.mconn.send(ch_id, msg_bytes)
        if ok:
            self.metrics.peer_send_bytes_total.with_labels(
                self.id, f"{ch_id:#04x}").inc(len(msg_bytes))
        return ok

    def try_send(self, ch_id: int, msg_bytes: bytes) -> bool:
        if not self.is_running():
            return False
        ok = self.mconn.try_send(ch_id, msg_bytes)
        if ok:
            self.metrics.peer_send_bytes_total.with_labels(
                self.id, f"{ch_id:#04x}").inc(len(msg_bytes))
        return ok

    def set(self, key: str, value) -> None:
        self.data[key] = value

    def get(self, key: str):
        return self.data.get(key)

    def status(self) -> dict:
        return self.mconn.status()

    def __repr__(self) -> str:
        arrow = "out" if self.outbound else "in"
        return f"Peer{{{self.id[:12]} {arrow} {self.socket_addr}}}"


class PeerSet:
    """Thread-safe set of peers keyed by ID (reference p2p/peer_set.go)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_id: Dict[str, Peer] = {}

    def add(self, peer: Peer) -> None:
        with self._lock:
            if peer.id in self._by_id:
                raise KeyError(f"duplicate peer {peer.id}")
            self._by_id[peer.id] = peer

    def has(self, peer_id: str) -> bool:
        with self._lock:
            return peer_id in self._by_id

    def get(self, peer_id: str) -> Optional[Peer]:
        with self._lock:
            return self._by_id.get(peer_id)

    def remove(self, peer: Peer) -> bool:
        with self._lock:
            return self._by_id.pop(peer.id, None) is not None

    def size(self) -> int:
        with self._lock:
            return len(self._by_id)

    def list(self) -> List[Peer]:
        with self._lock:
            return list(self._by_id.values())
