"""MConnection — multiplexed priority channels over one SecretConnection.

Reference parity: p2p/conn/connection.go.  One MConnection per peer:
byte-ID'd channels with priorities and bounded send queues; messages are
packetized (≤1024B payload, :21), the send loop picks the channel with
the least recently_sent/priority ratio (:464-486) and sends batches of
10 packets (:23, :448-462), each batch in one conn.write (the
reference's bufio writer, flushed after sendSomePacketMsgs); both
directions are flow-rate limited (:370,504); ping/pong liveness with a
pong timeout (:38-40).

on_receive(ch_id, msg_bytes) fires when a packet with EOF completes a
message; on_error(err) fires once on connection failure.
"""

from __future__ import annotations

import logging
import queue
import struct
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import msgpack

from ...libs import tracing
from ...libs.flowrate import Monitor

LOG = logging.getLogger("p2p.conn")

MAX_PACKET_MSG_PAYLOAD_SIZE = 1024  # connection.go:21
NUM_BATCH_PACKET_MSGS = 10  # connection.go:23

# p2p.sendThrottle / p2p.recvThrottle: a stretch in which the flow-rate
# limiter kept putting the connection to sleep. Sleeps closer together
# than the first are one stretch (a binding limiter sleeps a fraction of
# a millisecond a packet), a stretch is cut and recorded at the second
# so that a snapshot loses little of one still open, and one shorter
# than the third gets no span (the counter still has its seconds).
THROTTLE_JOIN_NS = 10_000_000
THROTTLE_CUT_NS = 100_000_000
THROTTLE_SPAN_FLOOR_NS = 1_000_000

_PKT_PING = 0
_PKT_PONG = 1
_PKT_MSG = 2


def _packet(obj) -> bytes:
    """One length-prefixed packet as it goes on the connection."""
    body = msgpack.packb(obj, use_bin_type=True)
    return struct.pack("<I", len(body)) + body


@dataclass
class MConnConfig:
    """connection.go:30-40 defaults (flush throttle, rates, ping)."""

    send_rate: int = 512000
    recv_rate: int = 512000
    max_packet_msg_payload_size: int = MAX_PACKET_MSG_PAYLOAD_SIZE
    flush_throttle: float = 0.1
    ping_interval: float = 60.0
    pong_timeout: float = 45.0
    send_queue_capacity: int = 1
    recv_message_capacity: int = 22020096  # 21MB


class _Channel:
    """connection.go:570-680: bounded send queue + packetizer +
    reassembly buffer, with a recently-sent counter for scheduling."""

    def __init__(self, desc, config: MConnConfig):
        self.desc = desc
        cap = desc.send_queue_capacity or config.send_queue_capacity
        self.send_queue: "queue.Queue[bytes]" = queue.Queue(maxsize=cap)
        self.sending: Optional[bytes] = None
        self.sent_pos = 0
        self.recently_sent = 0
        self.recv_msg_capacity = desc.recv_message_capacity or config.recv_message_capacity
        self.recving = bytearray()
        self.max_payload = config.max_packet_msg_payload_size

    def is_send_pending(self) -> bool:
        return self.sending is not None or not self.send_queue.empty()

    def next_packet(self):
        """-> (eof, payload) for the next outbound packet."""
        if self.sending is None:
            self.sending = self.send_queue.get_nowait()
            self.sent_pos = 0
        chunk = self.sending[self.sent_pos : self.sent_pos + self.max_payload]
        self.sent_pos += len(chunk)
        eof = self.sent_pos >= len(self.sending)
        if eof:
            self.sending = None
        self.recently_sent += len(chunk)
        return eof, chunk

    def recv_packet(self, eof: bool, data: bytes) -> Optional[bytes]:
        """Reassemble; returns the full message on EOF."""
        if len(self.recving) + len(data) > self.recv_msg_capacity:
            raise ConnectionError(
                f"recv msg exceeds capacity {self.recv_msg_capacity} on ch {self.desc.id}"
            )
        self.recving.extend(data)
        if eof:
            msg = bytes(self.recving)
            self.recving = bytearray()
            return msg
        return None


class MConnection:
    """The multiplexed connection (connection.go:70)."""

    def __init__(
        self,
        conn,  # SecretConnection-like: write/read_exact/close
        ch_descs: List,
        on_receive: Callable[[int, bytes], None],
        on_error: Callable[[Exception], None],
        config: Optional[MConnConfig] = None,
        metrics=None,  # P2PMetrics
        peer_id: str = "",  # whose connection: names the throttle spans
    ):
        from ...metrics import P2PMetrics

        self.metrics = metrics if metrics is not None else P2PMetrics()
        self.conn = conn
        self.config = config or MConnConfig()
        self.channels: Dict[int, _Channel] = {
            d.id: _Channel(d, self.config) for d in ch_descs
        }
        self.on_receive = on_receive
        self.on_error = on_error
        self.send_monitor = Monitor()
        self.recv_monitor = Monitor()
        self._throttled: Dict[str, Optional[List[int]]] = {}
        self._span_peer = peer_id[:8]
        # per direction: [a count the conn keeps, the counter it feeds,
        # the count last published]
        frames, calls = self.metrics.frames, self.metrics.socket_calls
        self._link = {
            "send": [["frames_sent", frames.with_labels("send"), 0],
                     ["send_calls", calls.with_labels("send"), 0]],
            "recv": [["frames_recv", frames.with_labels("recv"), 0],
                     ["recv_calls", calls.with_labels("recv"), 0]],
        }
        # wall clock of the last fully received packet (any kind);
        # 0.0 until the first one lands. The peer-reachability probe
        # (consensus stall classification, monitor [PARTITIONED?] tag)
        # reads this instead of the flowrate EWMA — the EWMA takes ~10s
        # to decay below any threshold after a link goes dark, silence
        # since the last packet is visible immediately.
        self.last_recv_time = 0.0
        self._send_signal = threading.Event()
        self._pong_pending = threading.Event()
        self._pong_received = threading.Event()
        self._last_pong = time.monotonic()
        self._wlock = threading.Lock()
        self._errored = False
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        for fn, name in (
            (self._send_routine, "mconn-send"),
            (self._recv_routine, "mconn-recv"),
            (self._ping_routine, "mconn-ping"),
        ):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        self._send_signal.set()
        try:
            self.conn.close()
        except Exception:
            pass

    def _error(self, err: Exception) -> None:
        if self._errored or self._stop.is_set():
            return
        self._errored = True
        self.stop()
        try:
            self.on_error(err)
        except Exception:
            LOG.exception("on_error callback failed")

    # -- sending -------------------------------------------------------

    def send(self, ch_id: int, msg_bytes: bytes, timeout: float = 10.0) -> bool:
        """Blocking enqueue (connection.go Send, defaultSendTimeout 10s)."""
        ch = self.channels.get(ch_id)
        if ch is None or self._stop.is_set():
            return False
        try:
            ch.send_queue.put(msg_bytes, timeout=timeout)
        except queue.Full:
            return False
        self._send_signal.set()
        return True

    def try_send(self, ch_id: int, msg_bytes: bytes) -> bool:
        """Non-blocking enqueue."""
        ch = self.channels.get(ch_id)
        if ch is None or self._stop.is_set():
            return False
        try:
            ch.send_queue.put_nowait(msg_bytes)
        except queue.Full:
            return False
        self._send_signal.set()
        return True

    def can_send(self, ch_id: int) -> bool:
        ch = self.channels.get(ch_id)
        return ch is not None and not ch.send_queue.full()

    def _write_packets(self, packets: bytes) -> None:
        """One conn.write of whole packets under the write lock: the
        send and ping threads never tear one another's packets."""
        with self._wlock:
            self.conn.write(packets)
            self._publish_link("send")

    def _publish_link(self, direction: str) -> None:
        """What the conn's frame and socket-call counts (the plain
        integers a SecretConnection keeps; a conn without them counts
        nothing) gained since the last call, into p2p_frames_total and
        p2p_socket_calls_total{direction}. Once a batch sent, once a
        packet received; one caller at a time per direction."""
        for entry in self._link[direction]:
            attr, counter, seen = entry
            now = getattr(self.conn, attr, 0)
            if now != seen:
                counter.inc(now - seen)
                entry[2] = now

    def _send_routine(self) -> None:
        try:
            while not self._stop.is_set():
                if self._pong_pending.is_set():
                    self._pong_pending.clear()
                    self._write_packets(_packet([_PKT_PONG]))
                if not self._send_some_packets():
                    # nothing pending: wait for a signal (bounded so the
                    # pong/ping path stays responsive)
                    self._send_signal.wait(timeout=self.config.flush_throttle)
                    self._send_signal.clear()
        except Exception as e:
            self._error(e)

    def _throttle(self, direction: str, monitor: Monitor, want: int,
                  rate: int) -> None:
        """monitor.limit(), with the seconds it slept counted in
        p2p_throttled_seconds_total{direction} and its stretches of
        sleeping recorded, when they end, as spans."""
        t0 = time.perf_counter_ns()
        slept = monitor.throttled_s
        monitor.limit(want, rate)
        slept = monitor.throttled_s - slept
        open_ = self._throttled.get(direction)  # [start_ns, end_ns]
        if open_ is not None and (t0 - open_[1] > THROTTLE_JOIN_NS
                                  or t0 - open_[0] > THROTTLE_CUT_NS):
            if open_[1] - open_[0] >= THROTTLE_SPAN_FLOOR_NS:
                tracing.get_tracer().record(
                    f"p2p.{direction}Throttle", open_[0], open_[1], "p2p",
                    peer=self._span_peer)
            open_ = self._throttled[direction] = None
        if slept > 0:
            self.metrics.throttled_seconds.with_labels(direction).inc(slept)
            t1 = time.perf_counter_ns()
            if open_ is None:
                self._throttled[direction] = [t0, t1]
            else:
                open_[1] = t1

    def _send_some_packets(self) -> bool:
        """Send up to a batch of packets, gathered without waiting and
        written at once in one conn.write; True if any were sent
        (connection.go:448-486)."""
        # rate-limit on the monitor before a batch
        self._throttle(
            "send", self.send_monitor,
            NUM_BATCH_PACKET_MSGS * self.config.max_packet_msg_payload_size,
            self.config.send_rate,
        )
        batch = []
        for _ in range(NUM_BATCH_PACKET_MSGS):
            best, least_ratio = None, float("inf")
            for ch in self.channels.values():
                if not ch.is_send_pending():
                    continue
                ratio = ch.recently_sent / ch.desc.priority
                if ratio < least_ratio:
                    least_ratio, best = ratio, ch
            if best is None:
                break
            try:
                eof, chunk = best.next_packet()
            except queue.Empty:
                continue
            batch.append(_packet([_PKT_MSG, best.desc.id, eof, chunk]))
            self.send_monitor.update(len(chunk))
        if batch:
            self._write_packets(b"".join(batch))
        # decay recently_sent so priorities re-assert over time
        for ch in self.channels.values():
            ch.recently_sent = int(ch.recently_sent * 0.8)
        return bool(batch)

    # -- receiving -----------------------------------------------------

    def _recv_routine(self) -> None:
        # a packet is msgpack of [type, ch, eof, <=max_payload chunk];
        # cap well under that bound so a malicious 4-byte header can't
        # force a multi-MB allocation (reference maxPacketMsgSize)
        max_packet = self.config.max_packet_msg_payload_size + 128
        try:
            while not self._stop.is_set():
                hdr = self.conn.read_exact(4)
                (length,) = struct.unpack("<I", hdr)
                if length > max_packet:
                    raise ConnectionError(f"packet too large: {length}")
                body = self.conn.read_exact(length)
                self._publish_link("recv")
                self.last_recv_time = time.monotonic()
                self.recv_monitor.update(len(body))
                self._throttle("recv", self.recv_monitor, len(body),
                               self.config.recv_rate)
                pkt = msgpack.unpackb(body, raw=False)
                kind = pkt[0]
                if kind == _PKT_PING:
                    self._pong_pending.set()
                    self._send_signal.set()
                elif kind == _PKT_PONG:
                    self._last_pong = time.monotonic()
                    self._pong_received.set()
                elif kind == _PKT_MSG:
                    _, ch_id, eof, data = pkt
                    ch = self.channels.get(ch_id)
                    if ch is None:
                        raise ConnectionError(f"unknown channel {ch_id:#x}")
                    msg = ch.recv_packet(eof, bytes(data))
                    if msg is not None:
                        self.on_receive(ch_id, msg)
                else:
                    raise ConnectionError(f"unknown packet type {kind}")
        except Exception as e:
            self._error(e)

    # -- liveness ------------------------------------------------------

    def _ping_routine(self) -> None:
        try:
            while not self._stop.wait(timeout=self.config.ping_interval):
                self._pong_received.clear()
                self._write_packets(_packet([_PKT_PING]))
                # the recv routine sets _pong_received; an early pong
                # ends the wait so the period stays ~ping_interval
                if not self._pong_received.wait(timeout=self.config.pong_timeout):
                    if self._stop.is_set():
                        return
                    raise ConnectionError("pong timeout")
        except Exception as e:
            self._error(e)

    # -- introspection -------------------------------------------------

    @staticmethod
    def _monitor_status(mon: Monitor) -> dict:
        """flowrate.Status field names (libs/flowrate/flowrate.go)."""
        st = mon.status()
        return {
            "Duration": st["duration"],
            "Bytes": st["bytes"],
            "Samples": st["samples"],
            "InstRate": st["cur_rate"],
            "CurRate": st["cur_rate"],
            "AvgRate": st["avg_rate"],
            "PeakRate": st["peak_rate"],
        }

    def status(self) -> dict:
        """p2p.ConnectionStatus shape (reference conn/connection.go
        Status + p2p/peer.go Status): flowrate monitors for both
        directions plus per-channel queue depths — the per-peer network
        telemetry net_info and the node watchdog report from."""
        return {
            "Duration": time.monotonic() - self.send_monitor.start,
            "SendMonitor": self._monitor_status(self.send_monitor),
            "RecvMonitor": self._monitor_status(self.recv_monitor),
            "Channels": [
                {
                    "ID": ch.desc.id,
                    "SendQueueCapacity": ch.send_queue.maxsize,
                    "SendQueueSize": ch.send_queue.qsize(),
                    "Priority": ch.desc.priority,
                    "RecentlySent": ch.recently_sent,
                }
                for ch in self.channels.values()
            ],
        }
