"""BatchVerifier — the pluggable bulk-verification engine (the north star).

The reference verifies every vote/commit signature serially
(types/validator_set.go:345-371, types/vote_set.go:189 →
crypto/ed25519/ed25519.go:151-157). Here every bulk call site —
ValidatorSet.verify_commit, fast-sync block validation, VoteSet batching —
routes through this registry instead, and per-item validity masks come back
(mixed valid/invalid batches are first-class; no all-or-nothing batch
equations).

Backends:
  "cpu"      — per-signature verify via OpenSSL (always available; baseline)
  "jax"      — vectorized Ed25519 verify (decompress → SHA-512 → double
               scalar mult) under vmap/jit; shards across every visible
               device with shard_map when more than one is present.
  "adaptive" — (default when jax is importable) routes batches below
               TM_TPU_BATCH_MIN to "cpu" and the rest to "jax": the
               latency-shaped live vote path stays serial when traffic is
               light and rides the device exactly when batching pays.

Select with set_default_backend() or the TM_TPU_CRYPTO_BACKEND env var.

Two cross-cutting layers sit in front of every backend:

- Verified-signature cache (sigcache.SigCache, installed process-wide
  via set_sig_cache / configure): verify() consults it first and only
  the cache-miss subset reaches the backend; the per-item mask is
  re-interleaved in add order. Duplicate triples within one batch are
  dispatched once. A batch meets the cache once: one digest a triple,
  one locked pass a shard in (the adaptive router's look is that pass,
  handed to the leaf) and one out.
- Async dispatch: verify_async() runs the exact verify() pipeline on a
  dedicated per-backend dispatch thread and returns a VerifyFuture, so
  callers overlap verification with other work (fast-sync applies block
  k while block k+1's commit verifies; the consensus receive loop WALs
  a vote run while its batch is on the device). Backend exceptions
  surface at .result(), never in the dispatch thread.
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from ..libs import tracing

LOG = logging.getLogger("crypto.batch")

Triple = Tuple[bytes, bytes, bytes]  # (message, signature, pubkey)

# Process-wide CryptoMetrics sink (tendermint_tpu.metrics.CryptoMetrics).
# None (the default) costs one load+is-check per verify() call; a Node
# with instrumentation on wires its live metric set here so EVERY call
# site — VoteSet, ValidatorSet.verify_commit, fast-sync, lite client —
# is measured without plumbing a metrics object through each of them.
_metrics = None
_metrics_lock = threading.Lock()


def set_metrics(metrics) -> None:
    """Install (or, with None, remove) the process-wide CryptoMetrics."""
    global _metrics
    with _metrics_lock:
        _metrics = metrics


def get_metrics():
    return _metrics


# the largest number of chips one device batch has been cut over in
# this process (/debug/crypto `verifier.devices_used`, beside the
# `device_count` the backend sees)
_devices_used = 0


def note_device_batch(lanes: int, ndev: int) -> None:
    """One device batch of `lanes` padded lanes shared by `ndev` chips,
    reported by the device backend once it has chosen both."""
    global _devices_used
    if ndev > _devices_used:
        _devices_used = ndev
    m = _metrics
    if m is not None:
        m.batch_lanes_per_device.with_labels(str(ndev)).observe(lanes // ndev)


def devices_used() -> int:
    return _devices_used


# --- process-wide [crypto] configuration (sig cache + async flag) ------
#
# Like the metrics sink above, these are process-global so every call
# site — VoteSet, ValidatorSet.verify_commit, fast-sync, consensus —
# picks them up without plumbing. node.Node wires them from the
# config.py [crypto] section; library users call the setters directly.

_sig_cache = None  # sigcache.SigCache or None (cache disabled)
_async_enabled = True  # gates the PIPELINED call sites, not verify_async


def set_sig_cache(cache) -> None:
    """Install (or, with None, remove) the process-wide verified-
    signature cache consulted by every BatchVerifier.verify()."""
    global _sig_cache
    _sig_cache = cache


def get_sig_cache():
    return _sig_cache


def set_async_enabled(on: bool) -> None:
    global _async_enabled
    _async_enabled = bool(on)


def async_enabled() -> bool:
    """Whether pipelined call sites (fast-sync verify/apply overlap, the
    consensus WAL/dispatch overlap) should use verify_async. The
    verify_async API itself always works regardless."""
    return _async_enabled


def configure(async_dispatch: Optional[bool] = None,
              sig_cache_size: Optional[int] = None) -> None:
    """Apply the [crypto] config section (config.CryptoConfig)."""
    if async_dispatch is not None:
        set_async_enabled(async_dispatch)
    if sig_cache_size is not None:
        if sig_cache_size > 0:
            from .sigcache import SigCache

            set_sig_cache(SigCache(sig_cache_size))
        else:
            set_sig_cache(None)


# --- async dispatch ----------------------------------------------------


class VerifyFuture:
    """Handle for one verify_async() call. result() returns exactly what
    verify() would have (per-item mask in add order) or re-raises the
    backend exception — errors never die in the dispatch thread."""

    __slots__ = ("_event", "_mask", "_exc", "_t_submit", "_t_done",
                 "_overlap_recorded", "_cause")

    def __init__(self):
        self._event = threading.Event()
        self._mask: Optional[List[bool]] = None
        self._exc: Optional[BaseException] = None
        self._t_submit = time.perf_counter()
        # the submitting thread's open span: crypto.batchVerify on the
        # dispatch thread names it as parent, and crypto.dispatchWait
        # is the measured gap between the two
        self._cause = tracing.cause()
        self._t_done: Optional[float] = None
        self._overlap_recorded = False

    def done(self) -> bool:
        return self._event.is_set()

    def _set_result(self, mask) -> None:
        self._t_done = time.perf_counter()
        self._mask = mask
        self._event.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._t_done = time.perf_counter()
        self._exc = exc
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> List[bool]:
        t_ask = time.perf_counter()
        if not self._event.wait(timeout):
            raise TimeoutError("verify_async result not ready")
        if not self._overlap_recorded:
            # pipeline overlap = wall time the caller spent elsewhere
            # while the batch was in flight: submit -> first result()
            # call, capped at completion (waiting inside result() is not
            # overlap). One sample per future.
            self._overlap_recorded = True
            m = _metrics
            if m is not None:
                overlap = max(0.0, min(t_ask, self._t_done) - self._t_submit)
                m.pipeline_overlap_seconds.observe(overlap)
        if self._exc is not None:
            raise self._exc
        return self._mask


# live async-batch count, readable without a metrics registry — the
# consensus stall watchdog includes it in /debug/consensus bundles (a
# stall with batches in flight points at the device, not the network)
_inflight = 0
_inflight_lock = threading.Lock()


def _inflight_add(d: int) -> None:
    global _inflight
    with _inflight_lock:
        _inflight += d


def inflight_count() -> int:
    """Async verify batches dispatched and not yet completed."""
    return _inflight


class _Dispatcher:
    """One daemon thread draining verify jobs for one backend name.
    stop() enqueues a sentinel, so queued jobs complete (their futures
    always resolve) before the thread exits."""

    def __init__(self, name: str):
        self.name = name
        self._q: "_queue.Queue" = _queue.Queue()
        # guards the stopping flag so a submit racing stop() can never
        # land behind the sentinel (its future would never resolve and
        # result() callers block forever) — it runs inline instead
        self._stop_lock = threading.Lock()
        self._stopping = False
        self._thread = threading.Thread(
            target=self._run, name=f"crypto-dispatch-{name}", daemon=True
        )
        self._thread.start()

    def submit(self, fn: Callable[[], List[bool]]) -> VerifyFuture:
        fut = VerifyFuture()
        # capture the metrics sink ONCE: increment and decrement must hit
        # the same gauge even if set_metrics re-wires the process-wide
        # sink while this batch is in flight
        m = _metrics
        _inflight_add(1)
        if m is not None:
            m.inflight_batches.add(1)
        with self._stop_lock:
            if not self._stopping:
                self._q.put((fn, fut, m))
                return fut
        self._execute(fn, fut, m)  # stopping: run inline, future resolves
        return fut

    @staticmethod
    def _execute(fn, fut: VerifyFuture, m) -> None:
        _dispatched.fut = fut
        try:
            fut._set_result(fn())
        except BaseException as e:  # noqa: BLE001 - surfaces at result()
            fut._set_exception(e)
        finally:
            _dispatched.fut = None
            _inflight_add(-1)
            if m is not None:
                m.inflight_batches.add(-1)

    def _run(self) -> None:
        while True:
            task = self._q.get()
            if task is None:
                return
            self._execute(*task)

    def stop(self, timeout: float = 10.0) -> None:
        with self._stop_lock:
            if not self._stopping:
                self._stopping = True
                self._q.put(None)
        self._thread.join(timeout)

    def alive(self) -> bool:
        return self._thread.is_alive()


_dispatchers: dict = {}
_dispatchers_lock = threading.Lock()

# the future a dispatch thread is running, until the verify it carries
# opens its crypto.batchVerify span (a fully cached batch opens none)
_dispatched = threading.local()


def _dispatcher(name: str) -> _Dispatcher:
    with _dispatchers_lock:
        d = _dispatchers.get(name)
        if d is None or not d.alive():
            d = _Dispatcher(name)
            _dispatchers[name] = d
        return d


def shutdown_dispatchers(timeout: float = 10.0) -> None:
    """Stop every dispatch thread after draining its queue: in-flight
    futures complete, then the threads join. Called by Node.stop; a
    verify_async() issued afterwards lazily spawns a fresh dispatcher,
    so concurrent nodes in one process stay correct (at worst a thread
    respawn)."""
    with _dispatchers_lock:
        ds = list(_dispatchers.values())
        _dispatchers.clear()
    for d in ds:
        d.stop(timeout)


def _look_up(cache, items):
    """The one meeting of a batch with the cache on its way in: a key a
    triple, built here and nowhere else, and one counted get_many().
    Returns (keys, verdicts, miss_idx): verdicts holds None where the
    backend has to answer, miss_idx the position of every such triple's
    first occurrence in the batch. The pass is one `crypto.cacheLook`
    span: on a dispatch thread it names the span that called
    verify_async, as crypto.batchVerify does, and lies inside
    crypto.dispatchWait."""
    tracer = tracing.get_tracer()
    # read, not cleared: _verify_instrumented still takes it
    fut = getattr(_dispatched, "fut", None) if tracer.enabled else None
    with tracer.span("crypto.cacheLook", "crypto",
                     cause=fut._cause if fut is not None else None,
                     n=len(items)) as sp:
        keys = cache.keys(items)
        verdicts = cache.get_many(keys)
        seen = set()
        miss_idx = []
        for i, v in enumerate(verdicts):
            if v is None and keys[i] not in seen:
                seen.add(keys[i])
                miss_idx.append(i)
        sp.set(misses=len(miss_idx))
    m = _metrics
    if m is not None:
        m.sig_cache_key_hashes.inc(len(keys))
        hits = len(keys) - verdicts.count(None)
        if hits:
            m.sig_cache_hits.inc(hits)
        if miss_idx:
            m.sig_cache_misses.inc(len(miss_idx))
    return keys, verdicts, miss_idx


class BatchVerifier:
    """Accumulate (msg, sig, pubkey) triples, then verify all at once.

    Backends implement _verify(); the public verify() wraps it with
    latency/batch-size/validity telemetry (no-op until set_metrics) and
    a tracing span. Subclasses may still override verify() wholesale
    (test fakes do) — they just opt out of the built-in telemetry."""

    BACKEND = "unknown"
    # how the batch got to this verifier: "direct", or the adaptive
    # router's decision ("device" | "cpu") on the verifier it built
    _route = "direct"
    # chips the last _verify() cut its batch over: a device backend
    # sets it, and crypto.batchVerify then carries it as `ndev`
    ndev = None
    # what the adaptive router hands the verifier it built, beside the
    # batch itself: the (keys, verdicts) of its look at the cache, which
    # verify() then does not make again, and that every pubkey of the
    # batch is 32 bytes long. A verifier that overrides verify()
    # wholesale never reads either and verifies the whole of _items.
    _looked = None
    _ed25519_only = False

    def __init__(self):
        self._items: List[Triple] = []

    def add(self, msg: bytes, sig: bytes, pubkey: bytes) -> None:
        self._items.append((msg, sig, pubkey))

    def __len__(self) -> int:
        return len(self._items)

    def _verify(self) -> List[bool]:
        raise NotImplementedError

    def verify(self) -> List[bool]:
        """Returns one validity flag per added triple, in add order.

        The batch meets the process-wide verified-signature cache once:
        one key a triple and one locked look a shard (the adaptive
        router's own, when it built this verifier: its look and the
        counted look are one), then one locked pass to store what the
        backend answered. Cached triples never reach the backend,
        duplicate triples within the batch are dispatched once, and
        only the cache-miss subset runs _verify(); the mask is
        re-interleaved in add order."""
        cache = _sig_cache
        looked, self._looked = self._looked, None
        if cache is None or not self._items:
            return self._verify_instrumented()
        items = self._items
        all_keys, verdicts, miss_idx = looked or _look_up(cache, items)
        if not miss_idx:
            return verdicts
        all_missed = len(miss_idx) == len(items)
        if all_missed:
            keys = all_keys  # the miss subset is the list: nothing copied
        else:
            # _verify() reads self._items; narrow it to the miss subset
            # for the dispatch (single-caller contract, like add/verify)
            self._items = [items[i] for i in miss_idx]
            keys = [all_keys[i] for i in miss_idx]
        try:
            submask = self._verify_instrumented(
                cache_hits=len(items) - verdicts.count(None),
                key_hashes=len(all_keys))
        finally:
            self._items = items
        answered = list(map(bool, submask))
        cache.put_many(keys, answered)
        if all_missed:
            return answered
        # hits keep their verdict; a miss, and every in-batch duplicate
        # of it, takes what the backend said of its first occurrence
        first = dict(zip(keys, answered))
        return [first[k] if v is None else v
                for k, v in zip(all_keys, verdicts)]

    def _verify_instrumented(self, cache_hits: int = 0,
                             key_hashes: Optional[int] = None) -> List[bool]:
        """_verify() wrapped with latency/size/validity telemetry: the
        histogram and the crypto.batchVerify span share two clock reads."""
        m = _metrics
        tracer = tracing.get_tracer()
        if m is None and not tracer.enabled:
            return self._verify()
        n = len(self._items)
        fut = cause = request = None
        if tracer.enabled:
            fut = getattr(_dispatched, "fut", None)
            _dispatched.fut = None
            cause = fut._cause if fut is not None else tracer.cause()
            request = (cause and cause[1]) or tracer.request("batch")
        with tracer.timed("crypto.batchVerify", cat="crypto", cause=cause,
                          request=request, backend=self.BACKEND, n=n,
                          route=self._route, cache_hits=cache_hits) as sp:
            if fut is not None:
                tracer.record("crypto.dispatchWait",
                              int(fut._t_submit * 1e9), sp.start_ns,
                              "crypto", backend=self.BACKEND, n=n)
            if key_hashes is not None:  # the batch went by the cache
                sp.set(key_hashes=key_hashes)
            mask = self._verify()
            if self.ndev is not None:
                sp.set(ndev=self.ndev)
        if m is not None:
            m.batch_verify_seconds.with_labels(self.BACKEND).observe(sp.seconds)
            m.batch_size.with_labels(self.BACKEND).observe(n)
            ok = sum(mask)
            if ok:
                m.signatures_verified.inc(ok)
            if n - ok:
                m.signatures_invalid.inc(n - ok)
        return mask

    def verify_async(self) -> VerifyFuture:
        """Dispatch verify() of the CURRENT items on this backend's
        dedicated dispatch thread. The caller must not add() to this
        verifier while the future is in flight; result() returns the
        per-item mask (add order) or re-raises the backend error."""
        return _dispatcher(self.BACKEND).submit(self.verify)

    def verify_all(self) -> bool:
        return all(self.verify())


class CPUBatchVerifier(BatchVerifier):
    """Serial per-signature verification — the reference semantics.

    Key type is dispatched on pubkey length: 32 bytes = Ed25519,
    48 bytes = BLS12-381 (the aggregate fast lane's INDIVIDUAL votes —
    live gossip still delivers one precommit at a time; the O(1)
    certificate path is ValidatorSet.verify_commit_aggregate)."""

    BACKEND = "cpu"

    def _verify(self) -> List[bool]:
        from .keys import PubKeyEd25519

        out = []
        for msg, sig, pk in self._items:
            try:
                if len(pk) == 48:
                    from .bls import PubKeyBLS12381

                    out.append(PubKeyBLS12381(pk).verify_bytes(msg, sig))
                else:
                    out.append(PubKeyEd25519(pk).verify_bytes(msg, sig))
            except ValueError:
                out.append(False)
        return out


class AdaptiveBatchVerifier(BatchVerifier):
    """Latency-shaped dispatch: device batch verification pays a fixed
    dispatch cost per call, so tiny batches (the live add_vote path when
    traffic is light) run the serial CPU path and only batches of
    >= min_device_batch ride the device kernel. The threshold is the
    crossover point between per-sig CPU cost (~100µs) and device
    dispatch overhead; tune with TM_TPU_BATCH_MIN."""

    BACKEND = "adaptive"

    def __init__(self, device_factory: Callable[[], BatchVerifier],
                 min_device_batch: int | None = None):
        super().__init__()
        self._device_factory = device_factory
        if min_device_batch is None:
            min_device_batch = effective_batch_min()
        self._min = min_device_batch

    def verify(self) -> List[bool]:
        # overrides verify() (not _verify) on purpose: the inner
        # verifier's own verify() records the latency/size telemetry
        # under its leaf backend label — a template here would double
        # count every batch. Adaptive only adds the routing decision,
        # and hands the leaf its batch whole: the list, not n add()s.
        items = self._items
        n = len(items)
        if any(len(pk) != 32 for _, _, pk in items):
            # non-Ed25519 triples (BLS fast lane): the jax kernel is
            # Ed25519-specific — route straight to the CPU dispatcher
            inner = CPUBatchVerifier()
            inner._items = items
            return inner.verify()
        cache = _sig_cache
        looked = None
        if cache is not None and n:
            # route on the CACHE-MISS count: the leaf verifier will only
            # dispatch the misses, so a mostly-cached batch must not pay
            # the fixed device dispatch for a handful of stragglers.
            # This is the batch's one counted look; the leaf gets it
            looked = _look_up(cache, items)
            n = len(looked[2])
        use_device = n >= self._min
        m = _metrics
        if m is not None:
            m.routing_decisions.with_labels(
                "device" if use_device else "cpu").inc()
        inner = self._device_factory() if use_device else CPUBatchVerifier()
        inner._route = "device" if use_device else "cpu"
        inner._items = items
        inner._looked = looked
        inner._ed25519_only = True
        return inner.verify()


_registry: dict[str, Callable[[], BatchVerifier]] = {}
_default_lock = threading.Lock()
_default_name: str | None = None
_calibrated_min: int | None = None


def set_calibrated_batch_min(n: int) -> None:
    """Record the MEASURED device break-even (verify.warmup calibrates:
    one compiled-dispatch round trip vs the serial per-signature cost on
    the hardware actually attached). Consulted whenever TM_TPU_BATCH_MIN
    is not explicitly set, so the device is only used where it wins."""
    global _calibrated_min
    with _default_lock:
        _calibrated_min = max(1, int(n))


def calibrated_batch_min() -> int | None:
    with _default_lock:
        return _calibrated_min


def effective_batch_min(default: int = 16) -> int:
    """The adaptive cutoff: explicit TM_TPU_BATCH_MIN wins, then the
    warmup-measured calibration, then the static default."""
    env = os.environ.get("TM_TPU_BATCH_MIN")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass  # malformed env must never take down verification
    with _default_lock:
        if _calibrated_min is not None:
            return _calibrated_min
    return default


def register_backend(name: str, factory: Callable[[], BatchVerifier]) -> None:
    _registry[name] = factory


def backends() -> List[str]:
    return sorted(_registry)


def set_default_backend(name: str) -> None:
    global _default_name
    if name not in _registry:
        raise KeyError(f"unknown batch-verify backend {name!r}; have {backends()}")
    with _default_lock:
        _default_name = name


def default_backend_name() -> str:
    global _default_name
    with _default_lock:
        if _default_name is None:
            env = os.environ.get("TM_TPU_CRYPTO_BACKEND")
            if env:
                if env not in _registry:
                    raise ValueError(
                        f"TM_TPU_CRYPTO_BACKEND={env!r} names no "
                        f"batch-verify backend; have {backends()}")
                _default_name = env
            elif "adaptive" in _registry:
                _default_name = "adaptive"
            elif "jax" in _registry:
                _default_name = "jax"
            else:
                _default_name = "cpu"
        return _default_name


def new_batch_verifier(name: str | None = None) -> BatchVerifier:
    if name is None:
        name = default_backend_name()
    try:
        factory = _registry[name]
    except KeyError:
        raise KeyError(f"unknown batch-verify backend {name!r}; have {backends()}")
    return factory()


def batch_verify(
    triples: Sequence[Triple], backend: str | None = None
) -> List[bool]:
    bv = new_batch_verifier(backend)
    for msg, sig, pk in triples:
        bv.add(msg, sig, pk)
    return bv.verify()


register_backend("cpu", CPUBatchVerifier)


def _register_jax_backend():
    """Deferred so importing tendermint_tpu.crypto never forces jax init."""
    try:
        from .jaxed25519.verify import JAXBatchVerifier
    except ImportError as e:
        LOG.warning("jax batch-verify backend unavailable (%s): the "
                    "default backend is the serial host path", e)
        return
    register_backend("jax", JAXBatchVerifier)
    register_backend(
        "adaptive", lambda: AdaptiveBatchVerifier(JAXBatchVerifier)
    )


_register_jax_backend()
