"""Merkle trees and inclusion proofs (RFC-6962 style).

Capability parity with the reference's crypto/merkle/simple_tree.go:23
(SimpleHashFromByteSlices), simple_proof.go:70 (SimpleProof.Verify), and
proof.go (ProofOperators for ABCI query proofs). We use domain-separated
leaf/inner hashing (0x00 / 0x01 prefixes) and the same largest-power-of-two
split rule, so proofs are position-binding.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Sequence

LEAF_PREFIX = b"\x00"
INNER_PREFIX = b"\x01"


def _sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def leaf_hash(leaf: bytes) -> bytes:
    return _sha256(LEAF_PREFIX + leaf)


def inner_hash(left: bytes, right: bytes) -> bytes:
    return _sha256(INNER_PREFIX + left + right)


def _split_point(n: int) -> int:
    """Largest power of two strictly less than n."""
    k = 1
    while k * 2 < n:
        k *= 2
    return k


def hash_from_byte_slices(items: Sequence[bytes]) -> bytes:
    """Root hash of the simple tree over items. Empty tree hashes to
    SHA256 of the empty string, matching an unambiguous fixed value.

    Walked level by level: adjacent nodes pair up and an odd last node
    rises unchanged, which is the tree the largest-power-of-two split
    rule (_split_point) defines, without its recursion and slicing
    (tests/test_committee_scale.py holds the two equal). A commit or a
    validator set of 10,000 is 20,000 hashes a root."""
    if not items:
        return _sha256(b"")
    sha = hashlib.sha256
    level = [sha(LEAF_PREFIX + item).digest() for item in items]
    while len(level) > 1:
        paired = [sha(INNER_PREFIX + level[i] + level[i + 1]).digest()
                  for i in range(0, len(level) - 1, 2)]
        if len(level) & 1:
            paired.append(level[-1])
        level = paired
    return level[0]


def hash_from_map(m: Dict[str, bytes]) -> bytes:
    """Deterministic root over a str->bytes map (sorted by key), used for
    header app-level maps (reference types/block.go Header.Hash uses a
    simple map hasher)."""
    kvs = []
    for key in sorted(m):
        kvs.append(leaf_hash(key.encode()) + leaf_hash(m[key]))
    return hash_from_byte_slices(kvs)


@dataclass
class SimpleProof:
    """Inclusion proof for item `index` of `total` leaves.

    aunts are sibling hashes from leaf level up to the root.
    """

    total: int
    index: int
    leaf_hash: bytes
    aunts: List[bytes] = field(default_factory=list)

    def compute_root(self) -> bytes:
        return _compute_from_aunts(self.index, self.total, self.leaf_hash, self.aunts)

    def verify(self, root: bytes, leaf: bytes) -> bool:
        if self.total <= 0 or not (0 <= self.index < self.total):
            return False
        if leaf_hash(leaf) != self.leaf_hash:
            return False
        computed = self.compute_root()
        return computed is not None and computed == root


def _compute_from_aunts(index, total, leaf, aunts):
    if index >= total or index < 0 or total <= 0:
        return None
    if total == 1:
        return leaf if not aunts else None
    if not aunts:
        return None
    k = _split_point(total)
    if index < k:
        left = _compute_from_aunts(index, k, leaf, aunts[:-1])
        if left is None:
            return None
        return inner_hash(left, aunts[-1])
    right = _compute_from_aunts(index - k, total - k, leaf, aunts[:-1])
    if right is None:
        return None
    return inner_hash(aunts[-1], right)


def proofs_from_byte_slices(items: Sequence[bytes]):
    """Returns (root, [SimpleProof per item])."""
    trails, root_node = _trails_from_byte_slices(list(items))
    root = root_node.hash if root_node else _sha256(b"")
    proofs = []
    for i, trail in enumerate(trails):
        proofs.append(
            SimpleProof(
                total=len(items),
                index=i,
                leaf_hash=trail.hash,
                aunts=trail.flatten_aunts(),
            )
        )
    return root, proofs


class _Node:
    __slots__ = ("hash", "parent", "left", "right")

    def __init__(self, h):
        self.hash = h
        self.parent = None
        self.left = None  # sibling on the left
        self.right = None  # sibling on the right

    def flatten_aunts(self):
        aunts = []
        node = self
        while node is not None:
            if node.left is not None:
                aunts.append(node.left.hash)
            elif node.right is not None:
                aunts.append(node.right.hash)
            node = node.parent
        return aunts


def _trails_from_byte_slices(items):
    n = len(items)
    if n == 0:
        return [], None
    if n == 1:
        node = _Node(leaf_hash(items[0]))
        return [node], node
    k = _split_point(n)
    lefts, left_root = _trails_from_byte_slices(items[:k])
    rights, right_root = _trails_from_byte_slices(items[k:])
    root = _Node(inner_hash(left_root.hash, right_root.hash))
    left_root.parent = root
    left_root.right = right_root
    right_root.parent = root
    right_root.left = left_root
    return lefts + rights, root


# --- proof operators (ABCI query proof chaining) ---------------------------


class ProofOp:
    """One verification step: takes child value(s), returns parent value."""

    type: str = ""

    def run(self, values: List[bytes]) -> List[bytes]:
        raise NotImplementedError

    def get_key(self) -> bytes:
        return b""


@dataclass
class SimpleValueOp(ProofOp):
    """Proves value at key is included in a simple tree with given root."""

    key: bytes
    proof: SimpleProof
    type: str = "simple:v"

    def run(self, values: List[bytes]) -> List[bytes]:
        if len(values) != 1:
            raise ValueError("SimpleValueOp expects one value")
        vhash = _sha256(values[0])
        # leaf is encoded as key/value-hash pair
        kv = _encode_lenprefixed(self.key) + _encode_lenprefixed(vhash)
        if leaf_hash(kv) != self.proof.leaf_hash:
            raise ValueError("leaf hash mismatch")
        root = self.proof.compute_root()
        if root is None:
            raise ValueError("bad proof")
        return [root]

    def get_key(self) -> bytes:
        return self.key


def _encode_lenprefixed(b: bytes) -> bytes:
    out = bytearray()
    n = len(b)
    while True:
        bb = n & 0x7F
        n >>= 7
        if n:
            out.append(bb | 0x80)
        else:
            out.append(bb)
            break
    return bytes(out) + b


class ProofOperators(list):
    def verify_value(self, root: bytes, keypath: List[bytes], value: bytes) -> bool:
        return self.verify(root, keypath, [value])

    def verify(self, root: bytes, keypath: List[bytes], args: List[bytes]) -> bool:
        keys = list(keypath)
        for op in self:
            key = op.get_key()
            if key:
                if not keys or keys[-1] != key:
                    return False
                keys = keys[:-1]
            try:
                args = op.run(args)
            except ValueError:
                return False
        return bool(args) and args[0] == root and not keys
