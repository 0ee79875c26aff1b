"""Deterministic canonical binary codec.

Replaces the reference's go-amino (types/wire.go, types/canonical.go) with a
minimal proto3-style wire format that is byte-deterministic by construction:
fields are always emitted in ascending tag order, zero values are emitted
explicitly where signedness matters for sign-bytes (height/round are
fixed64, like amino's "binary:fixed64" annotations at types/vote.go), and
maps never appear. This codec is ONLY used for hashing and sign-bytes —
inter-node wire messages use msgpack with explicit schemas (p2p layer).
"""

from __future__ import annotations

WIRE_VARINT = 0
WIRE_FIXED64 = 1
WIRE_BYTES = 2


_ONE_BYTE = tuple(bytes([i]) for i in range(0x80))


def uvarint(n: int) -> bytes:
    if n < 0x80:  # tags, lengths and small counts: most calls
        if n < 0:
            raise ValueError("uvarint of negative")
        return _ONE_BYTE[n]
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def read_uvarint(data: bytes, pos: int = 0):
    shift = 0
    result = 0
    while True:
        if pos >= len(data):
            raise ValueError("truncated uvarint")
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("uvarint too long")


def svarint(n: int) -> bytes:
    """ZigZag-encoded signed varint."""
    return uvarint((n << 1) ^ (n >> 63) if n < 0 else n << 1)


def read_svarint(data: bytes, pos: int = 0):
    u, pos = read_uvarint(data, pos)
    return (u >> 1) ^ -(u & 1), pos


def tag(field: int, wire: int) -> bytes:
    return uvarint((field << 3) | wire)


def t_uvarint(field: int, n: int) -> bytes:
    """Tagged varint; zero is skipped (proto3 default-elision)."""
    if n == 0:
        return b""
    return tag(field, WIRE_VARINT) + uvarint(n)


def t_fixed64(field: int, n: int) -> bytes:
    """Tagged fixed64 (always 8 bytes little-endian); zero skipped."""
    if n == 0:
        return b""
    return tag(field, WIRE_FIXED64) + (n & (2**64 - 1)).to_bytes(8, "little")


def t_bytes(field: int, b: bytes) -> bytes:
    if not b:
        return b""
    return tag(field, WIRE_BYTES) + uvarint(len(b)) + b


def t_string(field: int, s: str) -> bytes:
    return t_bytes(field, s.encode())


def t_message(field: int, body: bytes) -> bytes:
    """Tagged nested message. Unlike scalars, an empty message is still
    emitted (presence is meaningful, e.g. nil vs empty BlockID)."""
    return tag(field, WIRE_BYTES) + uvarint(len(body)) + body
