"""Signing worker for seeded chains and envelopes.

Imports nothing of the program and never JAX: a pool of these runs
beside the process that holds the chip. A worker holds the key objects
of its slice of the committee and signs what it is sent.
"""

from __future__ import annotations

import hashlib
import struct

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

_KEYS: list = []


def seed_of(tag: bytes, i: int) -> bytes:
    """The 32-byte Ed25519 seed of key `i` of the family `tag`."""
    return hashlib.sha256(tag + b"-%d" % i).digest()


def public_key(seed: bytes) -> bytes:
    return Ed25519PrivateKey.from_private_bytes(seed).public_key().public_bytes_raw()


def init_worker(seeds: list) -> None:
    global _KEYS
    _KEYS = [Ed25519PrivateKey.from_private_bytes(s) for s in seeds]


def sign_spliced(job) -> bytes:
    """job = (lo, hi, prefix, suffix, timestamps): key i signs
    prefix + fixed64(timestamps[i - lo]) + suffix. Returns the
    signatures concatenated (64 bytes each)."""
    lo, hi, prefix, suffix, stamps = job
    out = bytearray()
    for i in range(lo, hi):
        out += _KEYS[i].sign(prefix + struct.pack("<Q", stamps[i - lo]) + suffix)
    return bytes(out)


def sign_messages(job) -> bytes:
    """job = [(key index, message)]: the signatures concatenated."""
    out = bytearray()
    for i, msg in job:
        out += _KEYS[i].sign(msg)
    return bytes(out)


def serve(conn, seeds: list) -> None:
    """A worker's life: (kind, job) in, the signatures out as bytes,
    until the other end of the pipe is closed."""
    init_worker(seeds)
    kinds = {"spliced": sign_spliced, "messages": sign_messages}
    while True:
        try:
            kind, job = conn.recv()
        except EOFError:
            return
        conn.send_bytes(kinds[kind](job))
