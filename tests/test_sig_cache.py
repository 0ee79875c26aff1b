"""Verified-signature cache (crypto/sigcache.py + the BatchVerifier
template wiring in crypto/batch.py).

The invariants that matter: a cached verdict is ALWAYS identical to a
fresh verify (the cache is a pure memo of a pure function), an invalid
signature is never cached as valid, capacity is bounded under eviction,
and concurrent verifiers sharing the cache stay correct.
"""

import random
import threading

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import PrivKeyEd25519
from tendermint_tpu.crypto.sigcache import SigCache


def _mk_triples(n, seed, invalid_rate=0.3):
    """n distinct (msg, sig, pk) triples with ~invalid_rate corrupted
    signatures; returns (triples, expected_mask)."""
    rnd = random.Random(seed)
    triples, want = [], []
    for i in range(n):
        sk = PrivKeyEd25519.gen_from_secret(b"sigcache-%d-%d" % (seed, i))
        msg = b"msg-%d-%d" % (seed, i)
        sig = sk.sign(msg)
        ok = True
        if rnd.random() < invalid_rate:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
            ok = False
        triples.append((msg, sig, sk.pub_key().bytes()))
        want.append(ok)
    return triples, want


def test_cached_verdict_equals_fresh_randomized():
    """Property: with a TINY cache (constant eviction) and randomized
    mixed-validity batches full of repeats, every verify returns exactly
    what a fresh, uncached verify would."""
    pool, want = _mk_triples(40, seed=1)
    crypto_batch.set_sig_cache(SigCache(8))
    rnd = random.Random(2)
    for _ in range(25):
        idxs = [rnd.randrange(len(pool)) for _ in range(rnd.randrange(1, 20))]
        got = crypto_batch.batch_verify([pool[i] for i in idxs], backend="cpu")
        assert got == [want[i] for i in idxs]
    cache = crypto_batch.get_sig_cache()
    assert cache.hits > 0 and cache.misses > 0  # both paths exercised


def test_invalid_signature_never_cached_valid():
    sk = PrivKeyEd25519.gen_from_secret(b"sigcache-bad")
    msg = b"m"
    pk = sk.pub_key().bytes()
    good = sk.sign(msg)
    bad = bytes([good[0] ^ 1]) + good[1:]

    cache = SigCache(64)
    crypto_batch.set_sig_cache(cache)
    for _ in range(3):  # repeated delivery: hit path after the first
        assert crypto_batch.batch_verify([(msg, bad, pk)], backend="cpu") == [False]
    # the stored verdict for the bad triple is False, never True
    assert cache.get(cache.key(msg, bad, pk)) is False
    # the valid triple caches True under its own (distinct) key
    assert crypto_batch.batch_verify([(msg, good, pk)], backend="cpu") == [True]
    assert cache.get(cache.key(msg, good, pk)) is True


def test_eviction_keeps_cache_bounded():
    cache = SigCache(16, shards=4)
    for i in range(200):
        cache.put(cache.key(b"m%d" % i, b"s" * 64, b"p" * 32), True)
    assert len(cache) <= cache.capacity
    # LRU: a recently-refreshed entry survives a burst of inserts to
    # its shard while untouched ones are evicted
    k = cache.key(b"keepme", b"s" * 64, b"p" * 32)
    cache.put(k, True)
    for i in range(1000):
        cache.get(k)  # keep refreshing
        cache.put(cache.key(b"churn%d" % i, b"s" * 64, b"p" * 32), False)
    assert cache.get(k) is True


def test_thread_safety_concurrent_add_verify():
    pool, want = _mk_triples(60, seed=3)
    crypto_batch.set_sig_cache(SigCache(32))
    errs = []

    def worker(seed):
        rnd = random.Random(seed)
        try:
            for _ in range(20):
                idxs = [rnd.randrange(len(pool)) for _ in range(8)]
                got = crypto_batch.batch_verify(
                    [pool[i] for i in idxs], backend="cpu")
                assert got == [want[i] for i in idxs]
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs, errs


def test_intra_batch_duplicates_dispatched_once():
    calls = []

    class Counting(crypto_batch.CPUBatchVerifier):
        def _verify(self):
            calls.append(len(self._items))
            return super()._verify()

    sk = PrivKeyEd25519.gen_from_secret(b"sigcache-dup")
    msg = b"dup"
    triple = (msg, sk.sign(msg), sk.pub_key().bytes())

    crypto_batch.set_sig_cache(SigCache(64))
    v = Counting()
    for _ in range(5):
        v.add(*triple)
    assert v.verify() == [True] * 5
    assert calls == [1]  # one unique triple reached the backend
    # second delivery: pure cache hit, nothing dispatched
    v2 = Counting()
    v2.add(*triple)
    assert v2.verify() == [True]
    assert calls == [1]


def test_adaptive_routes_on_cache_miss_count():
    """A mostly-cached batch must not pay a device dispatch for the
    straggler misses: the adaptive router sizes on the miss subset."""
    calls = []

    class FakeDevice(crypto_batch.BatchVerifier):
        def verify(self):
            calls.append(len(self._items))
            return [True] * len(self._items)

    cache = SigCache(64)
    crypto_batch.set_sig_cache(cache)
    triples = []
    for i in range(6):
        sk = PrivKeyEd25519.gen_from_secret(b"adapt-%d" % i)
        msg = b"am-%d" % i
        triples.append((msg, sk.sign(msg), sk.pub_key().bytes()))
    for t in triples[:5]:
        cache.put(cache.key(*t), True)

    bv = crypto_batch.AdaptiveBatchVerifier(FakeDevice, min_device_batch=4)
    for t in triples:
        bv.add(*t)
    # batch of 6 but only 1 miss < cutoff 4: routed to cpu, device idle
    assert bv.verify() == [True] * 6
    assert calls == []

    # with the cache cold, the same batch still rides the device
    cache.clear()
    bv2 = crypto_batch.AdaptiveBatchVerifier(FakeDevice, min_device_batch=4)
    for t in triples:
        bv2.add(*t)
    assert bv2.verify() == [True] * 6
    assert calls == [6]


def test_duplicate_vote_set_delivery_hits_cache():
    """The duplicate-gossip scenario the cache exists for: the SAME vote
    set delivered twice (two VoteSet instances, as two peers would
    trigger) — the second delivery is served from cache, visible in both
    the SigCache stats and the CryptoMetrics counters."""
    from tendermint_tpu.metrics import prometheus_metrics
    from tendermint_tpu.types.basic import (
        VOTE_TYPE_PREVOTE,
        BlockID,
        PartSetHeader,
        Vote,
    )
    from tendermint_tpu.types.validator_set import random_validator_set
    from tendermint_tpu.types.vote_set import VoteSet

    chain = "sigcache-votes"
    vals, keys = random_validator_set(6, 10)
    bid = BlockID(b"\x0b" * 20, PartSetHeader(1, b"\x0c" * 20))
    votes = []
    for i in range(6):
        addr, _ = vals.get_by_index(i)
        v = Vote(
            validator_address=addr,
            validator_index=i,
            height=1,
            round=0,
            timestamp=1_700_000_000_000_000_000 + i,
            type=VOTE_TYPE_PREVOTE,
            block_id=bid,
        )
        v.signature = keys[i].sign(v.sign_bytes(chain))
        votes.append(v)

    cache = SigCache(4096)
    crypto_batch.set_sig_cache(cache)
    m = prometheus_metrics("t_sigcache")
    crypto_batch.set_metrics(m.crypto)
    try:
        vs1 = VoteSet(chain, 1, 0, VOTE_TYPE_PREVOTE, vals)
        assert vs1.add_votes(votes) == [True] * 6
        hits_before = cache.hits

        vs2 = VoteSet(chain, 1, 0, VOTE_TYPE_PREVOTE, vals)
        assert vs2.add_votes(votes) == [True] * 6  # identical re-delivery
        assert cache.hits - hits_before >= len(votes)
    finally:
        crypto_batch.set_metrics(None)

    out = m.registry.render()
    hit_lines = [
        line for line in out.splitlines()
        if line.startswith("t_sigcache_crypto_sig_cache_hits_total ")
    ]
    assert hit_lines and float(hit_lines[0].split()[-1]) > 0, out


# --- the funnel: a batch meets the cache once -------------------------


class _Stub(crypto_batch.BatchVerifier):
    """A leaf whose backend says True unless the signature starts with
    a zero byte."""

    BACKEND = "stub"

    def _verify(self):
        return [sig[:1] != b"\x00" for _, sig, _ in self._items]


def _fake_triples(n, tag=b"t"):
    return [(b"msg-%s-%d" % (tag, i), b"\x01" + b"%063d" % i, b"%032d" % i)
            for i in range(n)]


def _count_sha256(monkeypatch):
    import hashlib

    from tendermint_tpu.crypto import sigcache

    real, built = hashlib.sha256, []

    class _Hashlib:
        @staticmethod
        def sha256(data=b""):
            built.append(1)
            return real(data)

    monkeypatch.setattr(sigcache, "hashlib", _Hashlib)
    return built


@pytest.mark.parametrize("n", [1, 7, 300])
@pytest.mark.parametrize("enter", ["adaptive", "leaf"])
def test_one_key_a_triple_cold_and_cached(monkeypatch, n, enter):
    """(a) n distinct triples build exactly n digests, whether the cache
    holds none of them or all, through the router or a leaf directly;
    the counter and the span's source agree."""
    from tendermint_tpu.metrics import prometheus_metrics

    built = _count_sha256(monkeypatch)
    cache = SigCache(4096)
    crypto_batch.set_sig_cache(cache)
    m = prometheus_metrics("t_funnel")
    crypto_batch.set_metrics(m.crypto)
    triples = _fake_triples(n)
    try:
        for round_, (hits, misses) in enumerate([(0, n), (n, n)]):
            bv = (crypto_batch.AdaptiveBatchVerifier(_Stub, min_device_batch=1)
                  if enter == "adaptive" else _Stub())
            for t in triples:
                bv.add(*t)
            assert bv.verify() == [True] * n
            assert len(built) == n * (round_ + 1)
            assert (cache.hits, cache.misses) == (hits, misses)
    finally:
        crypto_batch.set_metrics(None)
    out = m.registry.render()

    def total(family):
        return float([line for line in out.splitlines()
                      if line.startswith(family + " ")][0].split()[-1])

    assert total("t_funnel_crypto_sig_cache_key_hashes_total") == 2 * n
    assert total("t_funnel_crypto_sig_cache_hits_total") == n
    assert total("t_funnel_crypto_sig_cache_misses_total") == n


def _apply_single(cache, op, keys, verdicts=None):
    """The single-key methods over a batch: what get_many / put_many
    must equal (a key that missed earlier in the batch is not looked up
    again, as in the verify template)."""
    if op == "put":
        for k, v in zip(keys, verdicts):
            cache.put(k, v)
        return None
    out, missed = [], set()
    for k in keys:
        if k in missed:
            out.append(None)
            continue
        v = cache.get(k)
        if v is None:
            missed.add(k)
        out.append(v)
    return out


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("capacity,shards", [(16, 4), (64, 8), (5, 1)])
def test_batch_forms_equal_single_key_methods(seed, capacity, shards):
    """(b) randomized interleavings of get_many / put_many against the
    single-key methods: same verdicts, same hits and misses, same LRU
    order in every shard and so the same evicted keys at capacity."""
    rnd = random.Random(seed)
    universe = [SigCache.key(b"m%d" % i, b"s" * 64, b"p" * 32)
                for i in range(3 * capacity)]
    one, many = SigCache(capacity, shards), SigCache(capacity, shards)
    for _ in range(60):
        keys = [rnd.choice(universe) for _ in range(rnd.randrange(0, 12))]
        if rnd.random() < 0.5:
            verdicts = [rnd.random() < 0.7 for _ in keys]
            _apply_single(one, "put", keys, verdicts)
            many.put_many(keys, verdicts)
        else:
            assert many.get_many(keys) == _apply_single(one, "get", keys)
        assert (many.hits, many.misses) == (one.hits, one.misses)
        assert [list(s.items()) for s in many._shards] == \
            [list(s.items()) for s in one._shards]
        assert len(many) <= many.capacity
    assert one.hits and one.misses and len(one) == one.capacity


class _CountingLock:
    def __init__(self):
        self._lock = threading.Lock()
        self.takes = 0

    def __enter__(self):
        self.takes += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


@pytest.mark.parametrize("n", [1, 64, 2000])
def test_a_shard_lock_is_taken_once_a_batch(n):
    """(c) get_many and put_many take each shard's lock at most once,
    and so does a whole verify(): one pass in, one out."""
    cache = SigCache(8192)
    cache._locks = [_CountingLock() for _ in cache._locks]
    keys = SigCache.keys(_fake_triples(n))

    def takes():
        got = [lk.takes for lk in cache._locks]
        for lk in cache._locks:
            lk.takes = 0
        return got

    assert cache.get_many(keys) == [None] * n
    assert max(takes()) == 1
    cache.put_many(keys, [True] * n)
    assert max(takes()) == 1
    assert cache.get_many(keys + keys) == [True] * (2 * n)
    assert max(takes()) == 1

    crypto_batch.set_sig_cache(cache)
    bv = crypto_batch.AdaptiveBatchVerifier(_Stub, min_device_batch=1)
    for t in _fake_triples(n, tag=b"u"):
        bv.add(*t)
    assert bv.verify() == [True] * n
    assert max(takes()) == 2  # one look, one store


@pytest.mark.parametrize("enter", ["adaptive-device", "adaptive-cpu", "leaf"])
def test_mixed_batch_mask_in_add_order(enter):
    """(d) hits, misses, in-batch duplicates of a miss and one invalid
    signature: the mask comes back in add order, the backend sees each
    missing triple once, and the invalid one is cached False."""
    good, want = _mk_triples(8, seed=11, invalid_rate=0.0)
    sk = PrivKeyEd25519.gen_from_secret(b"funnel-bad")
    sig = sk.sign(b"bad")
    bad = (b"bad", bytes([sig[0] ^ 1]) + sig[1:], sk.pub_key().bytes())
    cache = SigCache(256)
    crypto_batch.set_sig_cache(cache)
    for t in good[:3]:
        cache.put(cache.key(*t), True)
    asked = []

    class Leaf(crypto_batch.CPUBatchVerifier):
        def _verify(self):
            asked.append(list(self._items))
            return super()._verify()

    #        hit      miss     dup-miss hit      bad  miss     dup-bad dup-hit
    batch = [good[0], good[4], good[4], good[1], bad, good[5], bad, good[0]]
    if enter == "leaf":
        bv = Leaf()
    else:
        # three distinct misses: the device route at a cutoff of 3, the
        # cpu route at 4 (where the router builds its own leaf)
        bv = crypto_batch.AdaptiveBatchVerifier(
            Leaf, min_device_batch=3 if enter == "adaptive-device" else 4)
    for t in batch:
        bv.add(*t)
    hits, misses = cache.hits, cache.misses
    assert bv.verify() == [True, True, True, True, False, True, False, True]
    if enter != "adaptive-cpu":
        assert asked == [[good[4], bad, good[5]]]
    assert (cache.hits - hits, cache.misses - misses) == (3, 3)
    assert cache.peek(cache.key(*bad)) is False
    assert cache.peek(cache.key(*good[4])) is True
    assert cache.peek(cache.key(*good[6])) is None
    assert bv._items == batch  # still the list of triples that was added


def test_leaf_overriding_verify_under_the_router():
    """(e) a leaf that overrides verify() wholesale never reads what the
    router hands it: it gets the whole batch as (msg, sig, pk) triples
    and its answer is the router's."""
    seen = []

    class Wholesale(crypto_batch.BatchVerifier):
        def verify(self):
            seen.append(list(self._items))
            return [sig[:1] != b"\x00" for _, sig, _ in self._items]

    cache = SigCache(64)
    crypto_batch.set_sig_cache(cache)
    triples = _fake_triples(6)
    triples[2] = (b"m", b"\x00" * 64, b"p" * 32)
    cache.put(cache.key(*triples[0]), True)
    want = [True, True, False, True, True, True]
    for _ in range(2):  # nothing it answers is cached: asked both times
        bv = crypto_batch.AdaptiveBatchVerifier(Wholesale, min_device_batch=4)
        for t in triples:
            bv.add(*t)
        assert bv.verify() == want
        assert bv.verify_async().result(10) == want
    assert seen == [triples] * 4
    assert cache.peek(cache.key(*triples[1])) is None
    # the router's look is the counted one: five misses, one hit a batch
    assert (cache.hits, cache.misses) == (4, 20)


def test_batch_verify_span_says_how_many_keys_were_built():
    """crypto.batchVerify carries key_hashes: the digests built for the
    batch it came from, hits included; a batch that met no cache has no
    such arg."""
    from tendermint_tpu.libs import tracing

    tracer = tracing.get_tracer()
    triples = _fake_triples(9)
    cache = SigCache(64)
    cache.put_many(SigCache.keys(triples[:4]), [True] * 4)
    tracer.enable()
    try:
        tracer.clear()
        for installed in (cache, None):
            crypto_batch.set_sig_cache(installed)
            bv = crypto_batch.AdaptiveBatchVerifier(_Stub, min_device_batch=1)
            for t in triples:
                bv.add(*t)
            assert bv.verify() == [True] * 9
        spans = [e for e in tracer.events() if e.name == "crypto.batchVerify"]
    finally:
        tracer.disable()
        tracer.clear()
    assert [s.args for s in spans] == [
        {"backend": "stub", "n": 5, "route": "device", "cache_hits": 4,
         "key_hashes": 9},
        {"backend": "stub", "n": 9, "route": "device", "cache_hits": 0},
    ]


def test_batch_forms_under_contention():
    """More threads than cores on a short switch interval, all through
    get_many / put_many on one small cache: every look-up is counted
    once (a lost update under a shard's lock would drop one), a verdict
    read is the verdict its key was stored with, and capacity holds."""
    import sys

    cache = SigCache(64, shards=4)
    universe = SigCache.keys(_fake_triples(200))
    truth = {k: i % 3 != 0 for i, k in enumerate(universe)}
    looked, errs = [], []

    def worker(seed):
        rnd = random.Random(seed)
        n = 0
        try:
            for _ in range(300):
                keys = rnd.sample(universe, rnd.randrange(1, 24))
                got = cache.get_many(keys)
                n += len(keys)
                assert all(v is None or v is truth[k]
                           for k, v in zip(keys, got))
                cache.put_many(keys, [truth[k] for k in keys])
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)
        looked.append(n)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not errs, errs
    assert not any(t.is_alive() for t in threads) and len(looked) == 24
    assert cache.hits + cache.misses == sum(looked)
    assert len(cache) == cache.capacity
