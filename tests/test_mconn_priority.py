"""Deterministic MConnection channel-scheduling tests (reference
p2p/conn/connection.go:448-486 sendSomePacketMsgs: pick the channel with
the least recently_sent/priority ratio, batch of 10, decay after).

No sockets/threads: a dummy conn that decodes the packets of each
conn.write drives _send_some_packets directly.
"""

import struct

import msgpack

from tendermint_tpu.p2p.base_reactor import ChannelDescriptor
from tendermint_tpu.p2p.conn.connection import (
    NUM_BATCH_PACKET_MSGS,
    MConnConfig,
    MConnection,
)


class _DummyConn:
    def __init__(self):
        self.sent = []  # every packet written: [type, ch, eof, chunk]

    def write(self, b):
        pos = 0
        while pos < len(b):
            (n,) = struct.unpack_from("<I", b, pos)
            self.sent.append(msgpack.unpackb(b[pos + 4 : pos + 4 + n], raw=False))
            pos += 4 + n
        assert pos == len(b), "a write is a whole number of packets"

    def read_exact(self, n):  # pragma: no cover
        raise AssertionError("dummy conn must not be read")

    def close(self):
        pass


def _mconn(descs, **cfg_kw):
    cfg = MConnConfig(send_rate=10**12, **cfg_kw)  # no rate limiting
    conn = _DummyConn()
    m = MConnection(conn, descs, lambda ch, b: None, lambda e: None, cfg)
    return m, conn.sent


def _fill(m, ch_id, nbytes):
    # one queued message; packetizer splits it into ~nbytes/1024 packets
    m.channels[ch_id].send_queue.put(b"\xaa" * nbytes)


def test_high_priority_channel_dominates_batch():
    """Both channels saturated: the priority-10 channel should win the
    overwhelming share of the first batch (votes before txs)."""
    descs = [
        ChannelDescriptor(id=0x22, priority=10),  # votes
        ChannelDescriptor(id=0x30, priority=1),  # mempool
    ]
    m, sent = _mconn(descs)
    _fill(m, 0x22, 64 * 1024)
    _fill(m, 0x30, 64 * 1024)
    assert m._send_some_packets()
    assert len(sent) == NUM_BATCH_PACKET_MSGS
    by_ch = {0x22: 0, 0x30: 0}
    for _, ch, _, chunk in sent:
        by_ch[ch] += 1
    assert by_ch[0x22] >= NUM_BATCH_PACKET_MSGS - 2, by_ch
    # the ratio rule still lets the low-priority channel through
    # eventually: drain more batches and check it is not starved forever
    for _ in range(20):
        if not m._send_some_packets():
            break
    by_ch = {0x22: 0, 0x30: 0}
    for _, ch, _, chunk in sent:
        by_ch[ch] += 1
    assert by_ch[0x30] > 0, "low-priority channel fully starved"


def test_equal_priorities_share_evenly():
    descs = [
        ChannelDescriptor(id=0x01, priority=5),
        ChannelDescriptor(id=0x02, priority=5),
    ]
    m, sent = _mconn(descs)
    _fill(m, 0x01, 32 * 1024)
    _fill(m, 0x02, 32 * 1024)
    for _ in range(4):
        m._send_some_packets()
    by_ch = {0x01: 0, 0x02: 0}
    for _, ch, _, chunk in sent:
        by_ch[ch] += 1
    assert abs(by_ch[0x01] - by_ch[0x02]) <= 2, by_ch


def test_idle_connection_sends_nothing():
    descs = [ChannelDescriptor(id=0x01, priority=1)]
    m, sent = _mconn(descs)
    assert not m._send_some_packets()
    assert sent == []


def test_recently_sent_decays_between_batches():
    """After a batch, recently_sent decays (×0.8) so a long-idle
    channel's counter shrinks toward zero and priorities re-assert."""
    descs = [ChannelDescriptor(id=0x01, priority=1)]
    m, sent = _mconn(descs)
    _fill(m, 0x01, 8 * 1024)
    m._send_some_packets()
    after_first = m.channels[0x01].recently_sent
    assert after_first > 0
    for _ in range(30):
        m._send_some_packets()  # queue empties; decay keeps applying
    assert m.channels[0x01].recently_sent < after_first // 10
