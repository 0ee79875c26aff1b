"""SecretConnection + MConnection tests (reference p2p/conn/*_test.go)."""

import os
import socket
import struct
import sys
import threading
import time

import msgpack
import pytest

from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.libs.flowrate import Monitor
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.p2p.base_reactor import ChannelDescriptor
from tendermint_tpu.p2p.conn.connection import (
    _PKT_MSG,
    _PKT_PING,
    NUM_BATCH_PACKET_MSGS,
    MConnConfig,
    MConnection,
    _packet,
)
from tendermint_tpu.p2p.conn.secret_connection import (
    DATA_MAX_SIZE,
    RECV_CHUNK_SIZE,
    SEALED_FRAME_SIZE,
    TOTAL_FRAME_SIZE,
    AuthError,
    SecretConnection,
)
from tendermint_tpu.p2p.key import node_id


def _socket_pair():
    a, b = socket.socketpair()
    return a, b


def _make_secret_pair(k1=None, k2=None):
    k1 = k1 or PrivKeyEd25519.generate()
    k2 = k2 or PrivKeyEd25519.generate()
    s1, s2 = _socket_pair()
    out = {}

    def server():
        out["sc2"] = SecretConnection(s2, k2)

    t = threading.Thread(target=server)
    t.start()
    sc1 = SecretConnection(s1, k1)
    t.join(timeout=5)
    return sc1, out["sc2"], k1, k2


class TestSecretConnection:
    def test_handshake_authenticates_remote_key(self):
        sc1, sc2, k1, k2 = _make_secret_pair()
        assert sc1.remote_pub_key().bytes() == k2.pub_key().bytes()
        assert sc2.remote_pub_key().bytes() == k1.pub_key().bytes()

    def test_roundtrip_small(self):
        sc1, sc2, _, _ = _make_secret_pair()
        sc1.write(b"hello world")
        assert sc2.read_exact(11) == b"hello world"
        sc2.write(b"pong")
        assert sc1.read_exact(4) == b"pong"

    def test_roundtrip_multi_frame(self):
        sc1, sc2, _, _ = _make_secret_pair()
        blob = bytes(range(256)) * 40  # 10240B > 1024-frame payload
        done = {}

        def rx():
            done["got"] = sc2.read_exact(len(blob))

        t = threading.Thread(target=rx)
        t.start()
        sc1.write(blob)
        t.join(timeout=5)
        assert done["got"] == blob

    def test_ciphertext_differs_from_plaintext(self):
        """The raw socket must never carry plaintext."""
        a, b = _socket_pair()
        k1, k2 = PrivKeyEd25519.generate(), PrivKeyEd25519.generate()
        captured = []

        class Tap:
            def __init__(self, s):
                self.s = s

            def sendall(self, data):
                captured.append(bytes(data))
                self.s.sendall(data)

            def recv(self, n):
                return self.s.recv(n)

            def settimeout(self, t):
                self.s.settimeout(t)

            def close(self):
                self.s.close()

            def shutdown(self, how):
                self.s.shutdown(how)

        out = {}
        t = threading.Thread(target=lambda: out.update(sc=SecretConnection(b, k2)))
        t.start()
        sc1 = SecretConnection(Tap(a), k1)
        t.join(timeout=5)
        secret = b"SUPER-SECRET-PLAINTEXT"
        sc1.write(secret)
        out["sc"].read_exact(len(secret))
        assert all(secret not in c for c in captured)

    def test_tampered_frame_fails(self):
        a, b = _socket_pair()
        k1, k2 = PrivKeyEd25519.generate(), PrivKeyEd25519.generate()

        out, errs = {}, []

        def server():
            try:
                sc = SecretConnection(b, k2)
                out["sc"] = sc
                sc.read_exact(5)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=server)
        t.start()
        sc1 = SecretConnection(a, k1)
        # flip a bit in the next sealed frame by writing garbage directly
        a.sendall(b"\x00" * (1028 + 16))
        t.join(timeout=5)
        assert errs, "tampered frame must not decrypt"


class _Wire:
    """A socket under a SecretConnection that the test can see and
    steer: every sendall is recorded; with `hold` set it goes nowhere
    but the record; `script`, while it has bytes, is what recv serves,
    at most `step` a call (how a peer's bytes happened to arrive)."""

    def __init__(self, sock):
        self.sock = sock
        self.sent = []
        self.hold = False
        self.script = bytearray()
        self.step = RECV_CHUNK_SIZE
        self.recvs = 0

    def sendall(self, data):
        self.sent.append(bytes(data))
        if not self.hold:
            self.sock.sendall(data)

    def recv(self, n):
        self.recvs += 1
        if self.script:
            n = min(n, self.step)
            out = bytes(self.script[:n])
            del self.script[:n]
            return out
        return self.sock.recv(n)

    def settimeout(self, t):
        self.sock.settimeout(t)

    def shutdown(self, how):
        self.sock.shutdown(how)

    def close(self):
        self.sock.close()


def _wired_pair():
    """-> (sc1, wire1, sc2, wire2): a handshaken pair on a socketpair,
    each side's socket a _Wire."""
    a, b = _socket_pair()
    a.settimeout(5)
    b.settimeout(5)
    w1, w2 = _Wire(a), _Wire(b)
    out = {}
    t = threading.Thread(
        target=lambda: out.update(sc=SecretConnection(w2, PrivKeyEd25519.generate())))
    t.start()
    sc1 = SecretConnection(w1, PrivKeyEd25519.generate())
    t.join(timeout=5)
    return sc1, w1, out["sc"], w2


def _sealed_by(sc1, w1, data) -> bytes:
    """What sc1.write(data) puts on the wire, kept off the socket."""
    w1.hold, before = True, len(w1.sent)
    sc1.write(data)
    w1.hold = False
    assert len(w1.sent) == before + 1, "one write, one sendall"
    return w1.sent[-1]


class TestSecretConnectionBatches:
    """One socket call a batch of sealed frames, the sealed stream
    unchanged frame for frame."""

    def test_write_is_one_sendall_of_all_its_frames(self):
        sc1, w1, sc2, _ = _wired_pair()
        data = os.urandom(10 * 1035)  # ten mconn packets
        n_sent, frames, calls = len(w1.sent), sc1.frames_sent, sc1.send_calls
        assert sc1.write(data) == len(data)
        assert len(w1.sent) == n_sent + 1
        assert len(w1.sent[-1]) == 11 * SEALED_FRAME_SIZE
        assert (sc1.frames_sent - frames, sc1.send_calls - calls) == (11, 1)
        assert sc2.read_exact(len(data)) == data
        assert sc1.write(b"") == 0 and len(w1.sent) == n_sent + 1

    def test_sealed_stream_is_the_per_frame_loops(self):
        """Same key, same nonces, same bytes as a seal and a send a
        frame: the loop the connection had, literally."""
        sc1, w1, sc2, w2 = _wired_pair()
        data = os.urandom(10 * 1035)
        nonce = sc1._send_nonce
        expected = b""
        for off in range(0, len(data), DATA_MAX_SIZE):
            chunk = data[off : off + DATA_MAX_SIZE]
            frame = struct.pack("<I", len(chunk)) + chunk
            frame += b"\x00" * (TOTAL_FRAME_SIZE - len(frame))
            expected += sc1._send_aead.encrypt(
                nonce.to_bytes(12, "little"), frame, None)
            nonce += 1
        assert _sealed_by(sc1, w1, data) == expected
        assert sc1._send_nonce == nonce
        w2.script += expected
        assert sc2.read_exact(len(data)) == data

    @pytest.mark.parametrize("step", [1, 1043, 1045, RECV_CHUNK_SIZE])
    def test_reads_back_however_the_bytes_arrive(self, step):
        """A peer that dribbles a byte at a time, one that splits every
        frame, one that delivers 64 KB at once."""
        sc1, w1, sc2, w2 = _wired_pair()
        data = os.urandom(70_000)  # 69 frames: more than one 64 KB recv
        w2.script += _sealed_by(sc1, w1, data)
        w2.step = step
        frames, calls, w2.recvs = sc2.frames_recv, sc2.recv_calls, 0
        got = bytearray()
        for n in (1, 4, 1031, 4, 5000):
            got += sc2.read_exact(n)
        got += sc2.read_exact(len(data) - len(got))
        assert bytes(got) == data
        assert sc2.frames_recv - frames == 69
        assert sc2.recv_calls - calls == w2.recvs
        if step == RECV_CHUNK_SIZE:
            assert w2.recvs == 2  # 72,036 sealed bytes

    def test_timeout_in_mid_frame_loses_nothing(self):
        sc1, w1, sc2, w2 = _wired_pair()
        sealed = _sealed_by(sc1, w1, b"x" * 1500)  # two frames
        sc2.settimeout(0.05)
        w1.sock.sendall(sealed[:SEALED_FRAME_SIZE + 500])
        assert sc2.read_exact(1024) == b"x" * 1024
        with pytest.raises(socket.timeout):
            sc2.read(1)
        with pytest.raises(socket.timeout):
            sc2.read(1)
        w1.sock.sendall(sealed[SEALED_FRAME_SIZE + 500:])
        assert sc2.read_exact(476) == b"x" * 476

    def test_flipped_bit_in_third_frame_of_one_recv(self):
        sc1, w1, sc2, w2 = _wired_pair()
        data = os.urandom(4096)
        sealed = bytearray(_sealed_by(sc1, w1, data))
        sealed[2 * SEALED_FRAME_SIZE + 10] ^= 0x04
        w2.script += sealed
        w2.recvs = 0
        assert sc2.read_exact(2048) == data[:2048]
        assert w2.recvs == 1  # all four frames came in that one recv
        with pytest.raises(Exception) as err:
            sc2.read(1)
        assert not isinstance(err.value, (socket.timeout, AssertionError))
        assert sc2._recv_buffer == b""  # nothing of that frame handed up

    def test_frame_length_over_1024_is_refused(self):
        sc1, w1, sc2, _ = _wired_pair()
        frame = struct.pack("<I", DATA_MAX_SIZE + 1) + b"\x00" * DATA_MAX_SIZE
        w1.sock.sendall(sc1._seal(frame))
        with pytest.raises(ConnectionError, match="frame length 1025"):
            sc2.read(1)
        assert sc2._recv_buffer == b""

    def test_handshake_with_auth_frame_in_the_key_segment(self):
        """The peer's ephemeral key and its sealed auth frame arrive in
        one segment: the 32-byte key read takes 32 bytes and no more."""
        a, b = _socket_pair()
        a.settimeout(5)
        b.settimeout(5)
        k1, k2 = PrivKeyEd25519.generate(), PrivKeyEd25519.generate()

        class KeyAndAuthTogether(_Wire):
            def sendall(self, data):
                self.sent.append(bytes(data))
                if len(self.sent) == 2:
                    self.sock.sendall(b"".join(self.sent))
                elif len(self.sent) > 2:
                    self.sock.sendall(data)

        out = {}
        t = threading.Thread(target=lambda: out.update(
            sc=SecretConnection(KeyAndAuthTogether(b), k2)))
        t.start()
        sc1 = SecretConnection(a, k1)
        t.join(timeout=5)
        assert sc1.remote_pub_key().bytes() == k2.pub_key().bytes()
        assert out["sc"].remote_pub_key().bytes() == k1.pub_key().bytes()
        out["sc"].write(b"after")
        assert sc1.read_exact(5) == b"after"


def _packets(stream: bytes) -> list:
    """Decode a byte stream as whole length-prefixed mconn packets."""
    out, pos = [], 0
    while pos < len(stream):
        (n,) = struct.unpack_from("<I", stream, pos)
        assert pos + 4 + n <= len(stream), "torn packet"
        out.append(msgpack.unpackb(stream[pos + 4 : pos + 4 + n], raw=False))
        pos += 4 + n
    return out


class _RecordingConn:
    def __init__(self):
        self.writes = []

    def write(self, data):
        self.writes.append(bytes(data))

    def close(self):
        pass


class _TwoHalvesConn:
    """Lays each write down in two halves and lets other threads run
    between them: a write not under the lock would tear."""

    def __init__(self):
        self.stream = bytearray()

    def write(self, data):
        half = len(data) // 2
        self.stream += data[:half]
        time.sleep(0)
        self.stream += data[half:]

    def close(self):
        pass


class TestMConnectionBatches:
    def test_saturated_channel_batch_is_one_write_of_ten_packets(self):
        conn = _RecordingConn()
        m = MConnection(conn, [ChannelDescriptor(id=0x40, priority=1)],
                        lambda ch, b: None, lambda e: None,
                        MConnConfig(send_rate=10**12))
        blob = os.urandom(64 * 1024)
        m.channels[0x40].send_queue.put(blob)
        assert m._send_some_packets()
        assert len(conn.writes) == 1
        assert len(conn.writes[0]) == 10 * 1035
        pkts = _packets(conn.writes[0])
        assert len(pkts) == NUM_BATCH_PACKET_MSGS
        assert all(p[:3] == [_PKT_MSG, 0x40, False] for p in pkts)
        assert b"".join(p[3] for p in pkts) == blob[: 10 * 1024]

    def test_ping_beside_a_batch_never_tears_a_packet(self):
        rounds = 1000
        conn = _TwoHalvesConn()
        m = MConnection(conn, [ChannelDescriptor(id=0x40, priority=1)],
                        lambda ch, b: None, lambda e: None,
                        MConnConfig(send_rate=10**12))
        blob = os.urandom(4000)

        def batches():
            for _ in range(rounds):
                m.channels[0x40].send_queue.put(blob)
                assert m._send_some_packets()

        def pings():
            for _ in range(rounds):
                m._write_packets(_packet([_PKT_PING]))  # _ping_routine's

        threads = [threading.Thread(target=batches), threading.Thread(target=pings)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        pkts = _packets(bytes(conn.stream))
        assert sum(p == [_PKT_PING] for p in pkts) == rounds
        msgs = [p for p in pkts if p[0] == _PKT_MSG]
        assert len(msgs) == 4 * rounds
        assert b"".join(p[3] for p in msgs) == blob * rounds

    def test_link_counters_published(self):
        """p2p_frames_total and p2p_socket_calls_total{direction} are the
        connections' own counts; a batch is fewer calls than frames."""
        pm = prometheus_metrics("t_link")
        sc1, sc2, _, _ = _make_secret_pair()
        got = threading.Event()
        desc = [ChannelDescriptor(id=0x40, priority=1,
                                  recv_message_capacity=1 << 20)]
        cfg = MConnConfig(send_rate=10**9, recv_rate=10**9)
        sender = MConnection(sc1, desc, lambda c, b: None, lambda e: None, cfg)
        receiver = MConnection(sc2, desc, lambda c, b: got.set(),
                               lambda e: None, cfg, metrics=pm.p2p)
        sender.start()
        receiver.start()
        try:
            assert sender.send(0x40, os.urandom(300_000))
            assert got.wait(20)
        finally:
            sender.stop()
            receiver.stop()

        def value(family, direction):
            line = next(ln for ln in pm.registry.render().splitlines()
                        if ln.startswith(f't_link_p2p_{family}{{direction="{direction}"}}'))
            return float(line.split()[-1])

        # the handshake's frames came before the MConnection was there:
        # they are counted with the first packet's
        assert value("frames_total", "recv") == sc2.frames_recv >= 296
        assert value("socket_calls_total", "recv") == sc2.recv_calls
        assert sc2.recv_calls < sc2.frames_recv
        assert sc1.send_calls * 5 < sc1.frames_sent  # ten packets a call


def _mconn_pair(descs, cfg=None):
    sc1, sc2, _, _ = _make_secret_pair()
    rx1, rx2 = [], []
    ev1, ev2 = threading.Event(), threading.Event()
    m1 = MConnection(
        sc1, descs, lambda ch, b: (rx1.append((ch, b)), ev1.set()), lambda e: None, cfg
    )
    m2 = MConnection(
        sc2, descs, lambda ch, b: (rx2.append((ch, b)), ev2.set()), lambda e: None, cfg
    )
    m1.start()
    m2.start()
    return m1, m2, rx1, rx2, ev1, ev2


class TestMConnection:
    def test_send_receive(self):
        descs = [ChannelDescriptor(id=0x20, priority=5), ChannelDescriptor(id=0x30, priority=1)]
        m1, m2, rx1, rx2, ev1, ev2 = _mconn_pair(descs)
        try:
            assert m1.send(0x20, b"vote-data")
            assert ev2.wait(5)
            assert rx2 == [(0x20, b"vote-data")]
            ev2.clear()
            assert m2.send(0x30, b"tx-data")
            assert ev1.wait(5)
            assert rx1 == [(0x30, b"tx-data")]
        finally:
            m1.stop()
            m2.stop()

    def test_large_message_packetized(self):
        descs = [ChannelDescriptor(id=0x40, priority=1)]
        m1, m2, _, rx2, _, ev2 = _mconn_pair(descs)
        try:
            blob = b"\xab" * 5000  # > 4 packets
            assert m1.send(0x40, blob)
            assert ev2.wait(5)
            assert rx2 == [(0x40, blob)]
        finally:
            m1.stop()
            m2.stop()

    def test_unknown_channel_rejected(self):
        descs = [ChannelDescriptor(id=0x20, priority=1)]
        m1, m2, *_ = _mconn_pair(descs)
        try:
            assert not m1.send(0x99, b"x")
        finally:
            m1.stop()
            m2.stop()

    def test_ping_pong(self):
        descs = [ChannelDescriptor(id=0x20, priority=1)]
        cfg = MConnConfig(ping_interval=0.1, pong_timeout=2.0)
        m1, m2, *_ = _mconn_pair(descs, cfg)
        try:
            t0 = m1._last_pong
            time.sleep(0.5)
            assert m1._last_pong > t0, "pongs should have arrived"
        finally:
            m1.stop()
            m2.stop()


class TestFlowrate:
    def test_monitor_tracks_total(self):
        m = Monitor()
        m.update(1000)
        m.update(500)
        assert m.total == 1500

    def test_limit_throttles(self):
        m = Monitor()
        t0 = time.monotonic()
        moved = 0
        while moved < 3000:
            n = m.limit(1000, 10000)  # 10KB/s
            m.update(n)
            moved += n
        assert time.monotonic() - t0 > 0.2  # 3KB at 10KB/s ≳ 0.3s


class TestNodeID:
    def test_id_is_pubkey_address_hex(self):
        k = PrivKeyEd25519.generate()
        assert node_id(k.pub_key()) == k.pub_key().address().hex()
        assert len(node_id(k.pub_key())) == 40
