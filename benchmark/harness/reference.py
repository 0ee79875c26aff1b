"""The plain reference: the semantics the configurations guarantee,
written straight and sharing no code with the program.

- `KVReference`: the kvstore state machine (key=value writes, the app
  hash after each block as a Merkle root over the sorted pairs and the
  count of writes);
- `verify_one`: one Ed25519 signature through OpenSSL, one at a time.
"""

from __future__ import annotations

import hashlib
import struct

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkle_root(items: list) -> bytes:
    """RFC 6962 style root with 0x00/0x01 domain separation and the
    largest-power-of-two split; the empty tree hashes the empty string."""
    n = len(items)
    if n == 0:
        return _sha(b"")
    level = [_sha(b"\x00" + it) for it in items]

    def fold(lo: int, hi: int) -> bytes:
        if hi - lo == 1:
            return level[lo]
        k = 1
        while k * 2 < hi - lo:
            k *= 2
        return _sha(b"\x01" + fold(lo, lo + k) + fold(lo + k, hi))

    return fold(0, n)


class KVReference:
    """key=value store; a tx without '=' writes itself under itself."""

    def __init__(self) -> None:
        self.kv: dict = {}
        self.size = 0

    def deliver(self, tx: bytes) -> None:
        key, sep, value = tx.partition(b"=")
        self.kv[key] = value if sep else tx
        self.size += 1

    def commit(self) -> bytes:
        items = [b"kv:" + k + b"\x00" + self.kv[k] for k in sorted(self.kv)]
        return merkle_root(items) + struct.pack(">Q", self.size)


def verify_one(msg: bytes, sig: bytes, pub: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True
