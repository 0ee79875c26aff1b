"""Consensus write-ahead log (reference consensus/wal.go).

Every message the consensus machine receives (and every timeout it acts
on) is logged BEFORE processing, so a crashed node replays to exactly
where it left off. Records are crc32(4) + len(4) + msgpack payload
(reference WALEncoder :218-241 uses crc32c+amino); `#ENDHEIGHT: H`
markers delimit heights for catchup replay (SearchForEndHeight :159).
"""

from __future__ import annotations

import binascii
import logging
import struct
import time
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

from ..libs import tracing
from ..libs.autofile import Group
from ..types import serde

LOG = logging.getLogger("consensus.wal")

MAX_MSG_SIZE = 1048576  # 1MB (reference wal.go:32)


@dataclass
class TimedWALMessage:
    """reference wal.go:37-40"""

    time: float  # unix seconds
    msg: object  # wal message object (see messages.py to_obj shapes)


@dataclass
class EndHeightMessage:
    """Height H is complete (reference wal.go:43-46)."""

    height: int


class WALCorruptionError(Exception):
    pass


def _encode_record(payload: bytes) -> bytes:
    if len(payload) > MAX_MSG_SIZE:
        raise ValueError(f"WAL message too big: {len(payload)}")
    crc = binascii.crc32(payload) & 0xFFFFFFFF
    return struct.pack(">II", crc, len(payload)) + payload


class WAL:
    """File-backed WAL over a rotating Group (reference baseWAL :69).

    `corrupted_counter` is a Counter-like sink (metrics
    wal_corrupted_records_total) bumped when iter_messages drops a
    CORRUPT record — bad CRC, absurd length, undecodable payload —
    as opposed to the expected truncated crash tail."""

    def __init__(self, path: str, corrupted_counter=None,
                 records_counter=None):
        from ..metrics import NOP

        self.group = Group(path)
        self._started = False
        self._corrupted_counter = (corrupted_counter
                                   if corrupted_counter is not None else NOP)
        # write_run's records of peers' messages by source (metrics
        # wal_records_total): spliced from the bytes a message arrived
        # as, or encoded
        records = records_counter if records_counter is not None else NOP
        self._count_arrived = records.with_labels("arrived").inc
        self._count_encoded = records.with_labels("encoded").inc
        self._corruption_warned = False
        # plain process-local count mirroring the metric — the
        # /debug/recovery provider reads it without a registry scrape
        self.corrupted_records = 0
        # the height consensus is writing for (ConsensusState keeps it
        # current): the request id of the wal.* spans, 0 before the first
        self.height = 0

    def _request(self):
        return ("block", self.height) if self.height else None

    def start(self) -> None:
        self._started = True
        # an empty WAL gets an ENDHEIGHT-0 marker so replay for height 1
        # can find its messages after a crash (reference baseWAL.OnStart)
        if not any(True for _ in self.iter_messages()):
            self.write_sync(EndHeightMessage(0))

    def stop(self) -> None:
        if self._started:
            self.group.sync()
            self.group.close()
            self._started = False

    # --- write --------------------------------------------------------------

    def write(self, msg) -> None:
        """Log a message (no fsync; reference Save → Write)."""
        with tracing.span("wal.write", cat="wal", request=self._request()):
            payload = serde.pack(_msg_obj(msg))
            self.group.write(_encode_record(payload))

    def write_sync(self, msg) -> None:
        """Log + fsync — used for self-originated messages and EndHeight
        (reference consensus/state.go:609,1280)."""
        with tracing.span("wal.writeSync", cat="wal",
                          request=self._request()):
            self.write(msg)
            self.group.sync()

    def write_run(self, msgs) -> None:
        """Log received (peer_id, message) pairs in queue order: one
        message, or a run the receive loop drained. A peer's message
        becomes a record like write()'s, its message
        spliced from the bytes it arrived as where it carries them
        (`arrived_as`), and the records go down in one write under one
        `wal.write` span (no fsync; reference Save → Write). An internal
        message (peer_id "") is write_sync()'d by itself, after the
        records queued ahead of it."""
        records, arrived = [], 0
        for peer_id, msg in msgs:
            if peer_id == "":
                self._write_records(records, arrived)
                records, arrived = [], 0
                self.write_sync((peer_id, msg))
                continue
            raw = getattr(msg, "arrived_as", None)
            if raw is None:
                payload = serde.pack(_msg_obj((peer_id, msg)))
            else:
                payload = serde.pack_list(
                    ["msg_info", peer_id, serde.Packed(raw)])
                arrived += 1
            records.append(_encode_record(payload))
        self._write_records(records, arrived)

    def _write_records(self, records: list, arrived: int) -> None:
        if not records:
            return
        with tracing.span("wal.write", cat="wal", request=self._request(),
                          n=len(records), arrived=arrived):
            self.group.write(b"".join(records))
        if arrived:
            self._count_arrived(arrived)
        if arrived < len(records):
            self._count_encoded(len(records) - arrived)

    def flush(self) -> None:
        self.group.flush()

    def write_end_height(self, height: int) -> None:
        self.write_sync(EndHeightMessage(height))
        self.group.maybe_rotate()

    # --- read ---------------------------------------------------------------

    def _note_corruption(self, offset: int, why: str) -> None:
        """Count + one-shot warn: the WAL tolerates a bad record (replay
        stops there, the crash-recovery contract), but silently eaten
        records used to be invisible to operators."""
        self._corrupted_counter.inc()
        self.corrupted_records += 1
        if not self._corruption_warned:
            self._corruption_warned = True
            LOG.warning(
                "WAL corruption at byte offset %d: %s; replay stops here "
                "(records beyond this point are lost). Check the disk.",
                offset, why)

    def iter_messages(self) -> Iterator[object]:
        """All decodable messages oldest → newest; stops at the first
        corrupt/truncated record. A short read at the very end is the
        expected crash tail; a CRC/length/decode failure is disk
        corruption and is counted + warned (wal_corrupted_records_total)."""
        r = self.group.reader()
        offset = 0
        try:
            while True:
                hdr = r.read(8)
                if len(hdr) < 8:
                    return  # clean EOF or truncated crash tail
                crc, ln = struct.unpack(">II", hdr)
                if ln > MAX_MSG_SIZE:
                    self._note_corruption(
                        offset, f"record length {ln} exceeds "
                                f"{MAX_MSG_SIZE} (garbage header)")
                    return
                payload = r.read(ln)
                if len(payload) < ln:
                    return  # truncated crash tail
                if binascii.crc32(payload) & 0xFFFFFFFF != crc:
                    self._note_corruption(offset, "CRC mismatch")
                    return
                try:
                    msg = _msg_from(serde.unpack(payload))
                except (ValueError, TypeError, IndexError) as e:
                    self._note_corruption(
                        offset, f"undecodable payload ({e})")
                    return
                offset += 8 + ln
                yield msg
        finally:
            r.close()

    def search_for_end_height(self, height: int) -> Optional[list]:
        """Messages logged AFTER `#ENDHEIGHT height` (i.e. height+1's
        traffic), or None if the marker is absent (reference
        SearchForEndHeight :159-216). Returns a list for replay."""
        found = False
        out: list = []
        for msg in self.iter_messages():
            if isinstance(msg, EndHeightMessage):
                if msg.height == height:
                    found = True
                    out = []
                continue
            if found:
                out.append(msg)
        return out if found else None


class NilWAL:
    """No-op WAL (reference wal.go:322)."""

    def start(self) -> None: ...
    def stop(self) -> None: ...
    def write(self, msg) -> None: ...
    def write_sync(self, msg) -> None: ...
    def write_run(self, msgs) -> None: ...
    def flush(self) -> None: ...
    def write_end_height(self, height: int) -> None: ...
    def iter_messages(self):
        return iter(())
    def search_for_end_height(self, height: int):
        return None


# --- message serde -----------------------------------------------------------
# WAL messages: EndHeight, TimeoutInfo, and msg_info (peer_id + consensus
# message). Consensus messages themselves are (kind, obj) pairs from
# messages.py.


def _msg_obj(msg):
    from .messages import message_to_obj
    from .ticker import TimeoutInfo

    if isinstance(msg, EndHeightMessage):
        return ["end_height", msg.height]
    if isinstance(msg, TimedWALMessage):
        return ["timed", msg.time, _msg_obj(msg.msg)]
    if isinstance(msg, TimeoutInfo):
        return ["timeout", msg.duration, msg.height, msg.round, msg.step]
    if isinstance(msg, tuple) and len(msg) == 2:  # (peer_id, ConsensusMessage)
        peer_id, m = msg
        return ["msg_info", peer_id, message_to_obj(m)]
    raise TypeError(f"cannot WAL-encode {type(msg)}")


def _msg_from(o):
    from .messages import message_from_obj
    from .ticker import TimeoutInfo

    kind = o[0]
    if kind == "end_height":
        return EndHeightMessage(o[1])
    if kind == "timed":
        return TimedWALMessage(o[1], _msg_from(o[2]))
    if kind == "timeout":
        return TimeoutInfo(duration=o[1], height=o[2], round=o[3], step=o[4])
    if kind == "msg_info":
        return (o[1], message_from_obj(o[2]))
    raise ValueError(f"unknown WAL message kind {kind!r}")
