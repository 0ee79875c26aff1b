"""Verified-signature cache — sharded, bounded LRU over verify verdicts.

The same (msg, sig, pubkey) triple is verified up to three times in a
vote's lifetime — live pre-verification in the consensus receive loop,
commit reconstruction via VoteSet.add_votes, and
ValidatorSet.verify_commit — plus once more per duplicate gossip
delivery. Ed25519 verification is a pure function of the triple, so the
verdict can be memoized: BatchVerifier.verify() consults this cache and
only dispatches the cache-miss subset to the backend (arXiv:2302.00418
measures exactly this redundant re-verification as a first-order cost
in committee consensus).

Design notes:
- Keyed by sha256(msg ‖ sig ‖ pubkey). sig (64B) and pubkey (32B) are
  fixed length and form the suffix, so the concatenation is injective
  even though msg is variable length. Storing the 32-byte digest rather
  than the triple bounds memory at ~100B/entry regardless of message
  size.
- BOTH verdicts are cached. A False verdict is as deterministic as a
  True one, and caching it means a replayed bad signature costs one
  dict lookup instead of one device dispatch (cheap DoS resistance).
  An invalid signature can therefore never be cached as valid — the
  stored verdict is exactly what the backend returned for that triple.
- Sharded: the key's first byte picks a shard, each with its own lock
  and LRU (OrderedDict), so the consensus receive loop, fast-sync pool
  thread, and async dispatch threads don't serialize on one mutex.
- Bounded: per-shard capacity = capacity // shards; least-recently-used
  entries are evicted on insert. Hit/miss counters are maintained under
  the shard locks (exact, cheap) for bench/metrics reporting.
- Once a batch: a batch of triples meets the cache once. keys() builds
  one digest a triple, get_many() looks the whole batch up and
  put_many() stores its verdicts, each taking a shard's lock once a
  batch and not once a key (a 10,000-vote commit: 10,000 digests and at
  most 2 x shards lock takes, where a key at a time cost 20,000 and
  30,000). BatchVerifier.verify() is their caller; the adaptive
  router's look IS the counted look, handed on to the leaf verifier and
  not made again. get(), peek() and put() stay for a single key.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import List, Optional

DEFAULT_SHARDS = 8


class SigCache:
    def __init__(self, capacity: int, shards: int = DEFAULT_SHARDS):
        if capacity < 1:
            raise ValueError("SigCache capacity must be >= 1")
        shards = max(1, min(int(shards), int(capacity)))
        self._per_shard_cap = max(1, int(capacity) // shards)
        self._shards: List[OrderedDict] = [OrderedDict() for _ in range(shards)]
        self._locks = [threading.Lock() for _ in range(shards)]
        self._hits = [0] * shards
        self._misses = [0] * shards

    @property
    def capacity(self) -> int:
        return self._per_shard_cap * len(self._shards)

    @staticmethod
    def key(msg: bytes, sig: bytes, pk: bytes) -> bytes:
        """Digest of the triple. sig+pk are a fixed-length (96B) suffix,
        so msg ‖ sig ‖ pk is an injective encoding."""
        return hashlib.sha256(msg + sig + pk).digest()

    @staticmethod
    def keys(items) -> List[bytes]:
        """key() of every (msg, sig, pk) triple of a batch, in order."""
        sha256 = hashlib.sha256
        return [sha256(b"".join(triple)).digest() for triple in items]

    def _idx(self, key: bytes) -> int:
        return key[0] % len(self._shards)

    def _by_shard(self, keys):
        """(shard, positions in `keys` that fall to it) for every shard
        the batch touches; positions keep the batch's order."""
        n = len(self._shards)
        groups: List[list] = [[] for _ in range(n)]
        for i, k in enumerate(keys):
            groups[k[0] % n].append(i)
        return [(s, g) for s, g in enumerate(groups) if g]

    def get(self, key: bytes) -> Optional[bool]:
        """Cached verdict for `key`, or None on miss. A hit refreshes
        the entry's LRU position."""
        i = self._idx(key)
        with self._locks[i]:
            shard = self._shards[i]
            v = shard.get(key)
            if v is None:
                self._misses[i] += 1
                return None
            shard.move_to_end(key)
            self._hits[i] += 1
            return v

    def peek(self, key: bytes) -> Optional[bool]:
        """Like get(), but stats-neutral: no hit/miss counting and no
        LRU refresh. For callers that only need to KNOW whether a triple
        is cached (e.g. the adaptive router sizing the miss subset)
        without double-counting the lookup the verify template will do."""
        i = self._idx(key)
        with self._locks[i]:
            return self._shards[i].get(key)

    def get_many(self, keys) -> List[Optional[bool]]:
        """get() of every key, in order, under one take of each shard's
        lock: the same verdicts, LRU refreshes and hit counts as a get()
        a key. A key that misses counts one miss however often the
        batch repeats it (its repeats read None too): the batch verifies
        it once."""
        out: List[Optional[bool]] = [None] * len(keys)
        for s, positions in self._by_shard(keys):
            with self._locks[s]:
                shard = self._shards[s]
                get, refresh = shard.get, shard.move_to_end
                missed = set()
                hits = 0
                for i in positions:
                    k = keys[i]
                    v = get(k)
                    if v is None:
                        missed.add(k)
                    else:
                        refresh(k)
                        out[i] = v
                        hits += 1
                self._hits[s] += hits
                self._misses[s] += len(missed)
        return out

    def put(self, key: bytes, verdict: bool) -> None:
        i = self._idx(key)
        with self._locks[i]:
            shard = self._shards[i]
            shard[key] = bool(verdict)
            shard.move_to_end(key)
            while len(shard) > self._per_shard_cap:
                shard.popitem(last=False)

    def put_many(self, keys, verdicts) -> None:
        """put() of every (key, verdict) pair, in order, under one take
        of each shard's lock: the same entries, LRU order and evictions
        as a put() a key."""
        cap = self._per_shard_cap
        for s, positions in self._by_shard(keys):
            with self._locks[s]:
                shard = self._shards[s]
                refresh = shard.move_to_end
                for i in positions:
                    k = keys[i]
                    shard[k] = bool(verdicts[i])
                    refresh(k)
                    while len(shard) > cap:
                        shard.popitem(last=False)

    def __len__(self) -> int:
        return sum(len(s) for s in self._shards)

    @property
    def hits(self) -> int:
        return sum(self._hits)

    @property
    def misses(self) -> int:
        return sum(self._misses)

    def clear(self) -> None:
        for i, lock in enumerate(self._locks):
            with lock:
                self._shards[i].clear()
