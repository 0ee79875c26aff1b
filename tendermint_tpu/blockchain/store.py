"""BlockStore — persistent blocks/parts/commits keyed by height.

Reference parity: blockchain/store.go. Layout:
  H:<height>        -> BlockMeta (block_id + header)
  P:<height>:<idx>  -> block part bytes
  C:<height>        -> commit FOR block at height (from block height+1's
                       LastCommit)
  SC:<height>       -> "seen commit" (the local +2/3 precommits)
  blockStore        -> json {"height": N, "base": B}

`base` is the lowest height with a full block still on disk (0 when the
store is empty). It moves up via prune(retain_height) — long-running
producers drop history they no longer serve — and is set past `height`
by seed_anchor(), the state-sync bootstrap that installs only the
anchor commit at H so fast sync can resume at H+1 without blocks 1..H
ever existing locally.
"""

from __future__ import annotations

import json
import struct
import threading
from typing import Optional

from ..crypto import batch as crypto_batch
from ..libs import tracing
from ..libs.db import DB
from ..types import serde
from ..types.basic import BlockID
from ..types.block import Block, BlockMeta, Commit
from ..types.part_set import Part, PartSet

_STORE_KEY = b"blockStore"


def _h(height: int) -> bytes:
    return struct.pack(">Q", height)


def _meta_key(height: int) -> bytes:
    return b"H:" + _h(height)


def _part_key(height: int, index: int) -> bytes:
    return b"P:" + _h(height) + b":" + struct.pack(">I", index)


def _commit_key(height: int) -> bytes:
    return b"C:" + _h(height)


def _seen_commit_key(height: int) -> bytes:
    return b"SC:" + _h(height)


class BlockStore:
    """Stores the chain: metas, parts, and commits (reference
    blockchain/store.go:24-47 contract)."""

    def __init__(self, db: DB):
        self._db = db
        self._lock = threading.RLock()
        raw = db.get(_STORE_KEY)
        if raw:
            o = json.loads(raw)
            self._height = o["height"]
            # stores written before base-tracking hold full history
            self._base = o.get("base", 1 if self._height > 0 else 0)
        else:
            self._height = 0
            self._base = 0

    def height(self) -> int:
        with self._lock:
            return self._height

    def base(self) -> int:
        """Lowest height with a full block available (0 = empty store;
        reference blockchain/store.go Base, v0.33+)."""
        with self._lock:
            return self._base

    def _persist_meta_locked(self) -> None:
        self._db.set_sync(
            _STORE_KEY,
            json.dumps({"height": self._height, "base": self._base}).encode(),
        )

    # --- save ---------------------------------------------------------------

    def save_block(self, block: Block, part_set: PartSet, seen_commit: Commit) -> None:
        """Persist block at height == base+1 with its parts and the
        locally-seen commit (reference store.go SaveBlock:148-183)."""
        if block is None:
            raise ValueError("cannot save nil block")
        height = block.header.height
        with tracing.span("store.saveBlock", cat="store",
                          request=("block", height), height=height,
                          parts=part_set.total()), self._lock:
            if height != self._height + 1:
                raise ValueError(
                    f"cannot save block at height {height}; expected {self._height + 1}"
                )
            if not part_set.is_complete():
                raise ValueError("cannot save block with incomplete part set")
            meta = BlockMeta.from_block(block, part_set)
            self._db.set(_meta_key(height), serde.pack(_meta_obj(meta)))
            for i in range(part_set.total()):
                part = part_set.get_part(i)
                self._db.set(_part_key(height, i), serde.pack(serde.part_obj(part)))
            # each commit is packed once: in fast sync this LastCommit
            # is the object saved as SC:height-1 a call ago, and brings
            # its bytes (serde.encode_commit)
            if block.last_commit is not None:
                self._db.set(
                    _commit_key(height - 1), serde.encode_commit(block.last_commit)
                )
            self._db.set(_seen_commit_key(height), serde.encode_commit(seen_commit))
            self._height = height
            if self._base == 0:
                self._base = height
            self._persist_meta_locked()
            m = crypto_batch.get_metrics()
            if m is not None:
                m.store_heights_saved.inc()

    def seed_anchor(self, height: int, commit: Commit) -> None:
        """State-sync bootstrap (no reference equivalent; upstream v0.34
        statesync stores only the seen commit too): record the
        light-verified commit FOR `height` in an EMPTY store and move
        height there, with base = height+1 — no block bytes exist below
        it. Fast sync then resumes at height+1 and consensus can
        reconstruct LastCommit from the seen commit."""
        if commit is None:
            raise ValueError("cannot seed anchor with nil commit")
        with self._lock:
            if self._height != 0:
                raise ValueError(
                    f"cannot seed anchor at {height}: store already at "
                    f"height {self._height}")
            self._db.set(_seen_commit_key(height), serde.encode_commit(commit))
            self._db.set(_commit_key(height), serde.encode_commit(commit))
            self._height = height
            self._base = height + 1
            self._persist_meta_locked()

    def prune(self, retain_height: int) -> int:
        """Drop all blocks below `retain_height` (reference
        blockchain/store.go PruneBlocks, v0.33+): metas, parts and
        commits for heights [base, retain_height) are deleted and base
        moves up. Returns the number of blocks pruned. The commit FOR
        retain_height-1 (C:) is kept — block retain_height's LastCommit
        validation and RPC /commit still need it."""
        with self._lock:
            if retain_height <= 0:
                raise ValueError(f"retain height must be positive, got {retain_height}")
            if retain_height > self._height + 1:
                raise ValueError(
                    f"cannot retain beyond store height+1 "
                    f"({retain_height} > {self._height + 1})")
            pruned = 0
            for h in range(max(self._base, 1), retain_height):
                meta = self.load_block_meta(h)
                if meta is not None:
                    for i in range(meta.block_id.parts_header.total):
                        self._db.delete(_part_key(h, i))
                    self._db.delete(_meta_key(h))
                    pruned += 1
                self._db.delete(_seen_commit_key(h))
                if h < retain_height - 1:
                    self._db.delete(_commit_key(h))
            if retain_height > self._base:
                self._base = retain_height
                self._persist_meta_locked()
            return pruned

    # --- load ---------------------------------------------------------------

    def load_block_meta(self, height: int) -> Optional[BlockMeta]:
        raw = self._db.get(_meta_key(height))
        return _meta_from(serde.unpack(raw)) if raw else None

    def load_block(self, height: int) -> Optional[Block]:
        meta = self.load_block_meta(height)
        if meta is None:
            return None
        chunks = []
        for i in range(meta.block_id.parts_header.total):
            part = self.load_block_part(height, i)
            if part is None:
                return None
            chunks.append(part.bytes)
        return serde.decode_block(b"".join(chunks))

    def load_block_part(self, height: int, index: int) -> Optional[Part]:
        raw = self._db.get(_part_key(height, index))
        return serde.part_from(serde.unpack(raw)) if raw else None

    def load_block_commit(self, height: int) -> Optional[Commit]:
        """The canonical commit for block at `height` (stored once block
        height+1 is saved)."""
        raw = self._db.get(_commit_key(height))
        return serde.decode_commit(raw) if raw else None

    def load_seen_commit(self, height: int) -> Optional[Commit]:
        raw = self._db.get(_seen_commit_key(height))
        return serde.decode_commit(raw) if raw else None


def _meta_obj(m: BlockMeta):
    return [serde.block_id_obj(m.block_id), serde.header_obj(m.header)]


def _meta_from(o) -> BlockMeta:
    return BlockMeta(block_id=serde.block_id_from(o[0]), header=serde.header_from(o[1]))
