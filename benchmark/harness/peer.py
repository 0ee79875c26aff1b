"""The serving peer of the fast-sync cells: a p2p switch of the
benchmark's own that speaks the blockchain channel and serves a chain
held in memory as encoded messages, so that serving costs the joiner's
process next to nothing. It advertises a tip that the harness moves."""

from __future__ import annotations

import threading
import time

BLOCKCHAIN_CHANNEL = 0x40


def make_serving_switch(chain, send_rate: int, other_channels: list):
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.p2p import (MultiplexTransport, NodeInfo, NodeKey,
                                    ProtocolVersion, Switch)
    from tendermint_tpu.p2p.base_reactor import ChannelDescriptor, Reactor
    from tendermint_tpu.p2p.conn.connection import MConnConfig
    from tendermint_tpu.types import serde

    class Serving(Reactor):
        def __init__(self):
            super().__init__("BenchServing")
            self.tip = 0
            self.poison: dict = {}
            self.served = 0
            self.max_requested = 0
            self.dropped = threading.Event()
            self.drop_reason = None
            self.dropped_at = None
            self._lock = threading.Lock()

        def get_channels(self):
            return [ChannelDescriptor(id=BLOCKCHAIN_CHANNEL, priority=10,
                                      send_queue_capacity=1000,
                                      recv_message_capacity=10 * 1024 * 1024)]

        def _status(self) -> bytes:
            return serde.pack(["status_response", self.tip])

        def add_peer(self, peer) -> None:
            peer.try_send(BLOCKCHAIN_CHANNEL, self._status())

        def remove_peer(self, peer, reason) -> None:
            self.drop_reason = reason
            self.dropped_at = time.monotonic()
            self.dropped.set()

        def advertise(self, tip: int) -> None:
            with self._lock:
                if tip <= self.tip:
                    return
                self.tip = tip
            self.switch.broadcast(BLOCKCHAIN_CHANNEL, self._status())

        def receive(self, ch_id, peer, msg_bytes) -> None:
            obj = serde.unpack(msg_bytes)
            kind = obj[0]
            if kind == "block_request":
                h = obj[1]
                if 1 <= h <= self.tip:
                    self.max_requested = max(self.max_requested, h)
                    self.served += 1
                    peer.send(BLOCKCHAIN_CHANNEL,
                              self.poison.get(h) or chain.messages[h - 1])
                else:
                    peer.try_send(BLOCKCHAIN_CHANNEL,
                                  serde.pack(["no_block_response", h]))
            elif kind == "status_request":
                peer.try_send(BLOCKCHAIN_CHANNEL, self._status())

    class Sink(Reactor):
        """Owns the node's other channels (consensus, mempool, evidence,
        pex...) and drops what arrives: a peer that only serves blocks."""

        def __init__(self):
            super().__init__("BenchSink")

        def get_channels(self):
            return [ChannelDescriptor(id=d.id, priority=d.priority)
                    for d in other_channels]

    nk = NodeKey(PrivKeyEd25519.generate())
    ni = NodeInfo(protocol_version=ProtocolVersion(), id=nk.id, listen_addr="",
                  network=chain.chain_id, version="dev",
                  channels=bytes([BLOCKCHAIN_CHANNEL]
                                 + [d.id for d in other_channels]),
                  moniker="bench-serving")
    tr = MultiplexTransport(ni, nk)
    tr.listen("127.0.0.1:0")
    ni.listen_addr = tr.listen_addr
    sw = Switch(tr, mconfig=MConnConfig(send_rate=send_rate, recv_rate=send_rate))
    reactor = Serving()
    sw.add_reactor("BLOCKCHAIN", reactor)
    sw.add_reactor("SINK", Sink())
    return sw, reactor
