"""Compile-once kernel layer: persistent XLA cache + AOT artifact store.

XLA compilation is the dominant tax on the verify hot path: the packed
Ed25519 kernel costs multi-second compiles per (shape, device) key, the
BLS jax-MSM kernel minutes — and every PROCESS used to pay it again.
This module makes kernels compile once per MACHINE:

1. The persistent XLA compilation cache. Where ``JAX_COMPILATION_CACHE_DIR``
   is set, jax itself reads it and this module sets no directory; where
   it is not, the cache lives at ONE fixed path inside the checkout
   (``DEFAULT_CACHE_DIR``, git-ignored) — the path is part of XLA's
   cache key, so a directory that moves never hits.
2. An AOT artifact store beneath it: known kernels are
   ``.lower().compile()``d once, serialized with
   ``jax.experimental.serialize_executable``, and written (atomically)
   under ``<cache_dir>/aot/``. A later process deserializes the native
   executable in milliseconds — no tracing, no XLA compile at all.

Artifacts are keyed by (jax version, backend platform, device kind,
device count, a digest of the kernel source files, kernel name, static
key, argument avals) — the digest keeps two checkouts that share one
cache directory from running each other's executables. A corrupted,
truncated, or foreign-keyed artifact is IGNORED (fresh compile + miss
counter), never a crash. Writes go through a same-directory
tempfile + ``os.replace`` so concurrent processes racing one entry
cannot corrupt it — last writer wins, both end up with a valid file.

Trust model: artifacts deserialize via pickle, the same local-user
trust boundary as XLA's own persistent cache directory — do not point
``JAX_COMPILATION_CACHE_DIR`` at an untrusted location.

A failure of the STORE (unreadable artifact, unwritable directory) only
costs the cache and is logged at WARNING; a failure to COMPILE a kernel
is the kernel's failure and propagates. The module never imports jax at
import time (mirroring crypto/batch's deferred-registration idiom).
"""

from __future__ import annotations

import functools
import glob
import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
import time
import weakref
from typing import Callable, Optional

from ..libs import tracing

LOG = logging.getLogger("crypto.kernel_cache")

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the one cache location when ENV_CACHE_DIR is unset: fixed, inside the
# checkout, git-ignored — never ~, a temp name, a pid or a time
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# artifact header: magic + one json metadata line, then the pickled
# serialize_executable payload
_MAGIC = b"TMTPU-AOT1 "

_lock = threading.RLock()
_dir: Optional[str] = None  # resolved cache dir; None = not yet configured
_unusable = False  # the resolved dir could not be created: store is off
_stats = {"hits": 0, "misses": 0, "compiles": 0, "load_errors": 0}
# in-progress compiles: unique token -> (kernel, perf_counter() start);
# tokens (not kernel names) so two shapes of one kernel compiling
# concurrently both stay visible until each finishes
_compiling: dict = {}
_compile_seq = 0
# one record per kernel shape made ready in this process (status()
# "kernels"): name, static key, seconds, and whether it was compiled or
# loaded from the store — bounded by the number of distinct shapes
_ready_log: list = []
# weakrefs to every live aot_wrap in-memory cache (clear_memory's only
# purpose); weak so an aot_wrap dropped by its caller (e.g. lru_cache
# eviction of a kernel shape) actually frees its loaded executables
_wrapper_caches: list = []


# Set on the compiling thread when jax served a compile from its own
# persistent cache (its monitoring event; jax calls listeners on the
# thread that compiles). An XLA:CPU executable loaded that way
# serializes without its kernels' functions: the artifact loads in the
# next process and fails at its first run ("Function ... not found"),
# so such an executable is used and not stored. TPU executables
# serialize whole either way.
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_from_jax_cache = threading.local()
_listening = False  # the listener is registered once a process


def _on_jax_event(event: str, **_kw) -> None:
    if event == _JAX_CACHE_HIT:
        _from_jax_cache.seen = True


class _WrapperCache(dict):
    """A dict that supports weak references (plain dicts don't)."""

    __slots__ = ("__weakref__",)


def _metrics():
    """The process-wide CryptoMetrics sink, if one is installed
    (crypto/batch.set_metrics). Imported lazily: batch imports the jax
    verify module which imports us — a top-level import would cycle."""
    from . import batch as _batch

    return _batch.get_metrics()


def ensure_configured() -> Optional[str]:
    """Resolve the cache root once per process and return it.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already keeps XLA's cache
    there (it reads the variable itself), the AOT store goes to
    ``<dir>/aot`` and no directory is set in code. Unset: both layers
    live at DEFAULT_CACHE_DIR and jax is pointed there. Safe before or
    after backend init. An uncreatable directory turns the store off
    (WARNING) — kernels then compile per process."""
    global _dir, _unusable, _listening
    with _lock:
        if _dir is not None or _unusable:
            return _dir
        env = os.environ.get(ENV_CACHE_DIR)
        resolved = os.path.abspath(env or DEFAULT_CACHE_DIR)
        try:
            os.makedirs(os.path.join(resolved, "aot"), exist_ok=True)
        except OSError as e:
            LOG.warning("compile cache dir %s unusable, kernels compile "
                        "per process: %s", resolved, e)
            _unusable = True
            return None
        _dir = resolved
        _prune_tempfiles(resolved)
        import jax
        import jax.monitoring

        if not _listening:
            jax.monitoring.register_event_listener(_on_jax_event)
            _listening = True
        if not env:
            jax.config.update("jax_compilation_cache_dir", resolved)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        return _dir


_TMP_MAX_AGE_S = 24 * 3600.0  # crashed writers' tempfiles age out


def _prune_tempfiles(root: str) -> None:
    """Remove day-old tempfiles crashed writers left in aot/."""
    aot = os.path.join(root, "aot")
    now = time.time()
    for name in os.listdir(aot):
        if not name.startswith(".tmp-aot-"):
            continue
        path = os.path.join(aot, name)
        try:
            if now - os.path.getmtime(path) > _TMP_MAX_AGE_S:
                os.unlink(path)
        except OSError:
            continue  # racing process: it won the unlink, fine


def unconfigure() -> None:
    """Return to the never-configured state (test fixtures): the next
    ensure_configured() re-reads the environment."""
    global _dir, _unusable
    with _lock:
        _dir = None
        _unusable = False


def cache_dir() -> Optional[str]:
    return _dir


def stats() -> dict:
    with _lock:
        return dict(_stats)


def status() -> dict:
    """Bundle for /debug/crypto: store state, counters, and any compile
    currently in progress (a node stuck compiling at boot shows up here
    as {"kernel": elapsed_seconds})."""
    now = time.perf_counter()
    with _lock:
        compiling: dict = {}
        for kernel, t in _compiling.values():
            elapsed = round(now - t, 1)
            # several shapes of one kernel: report the longest-running
            compiling[kernel] = max(elapsed, compiling.get(kernel, 0.0))
        return {
            "dir": _dir,
            "enabled": _dir is not None,
            **_stats,
            "compiling": compiling,
            "kernels": [dict(r) for r in _ready_log],
        }


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0
        _ready_log.clear()


def clear_memory() -> None:
    """Drop every aot_wrap in-memory compiled-kernel reference, so the
    next call re-loads from disk — a fresh process, simulated in-process
    (warm-path tests use this to assert load-without-recompile)."""
    with _lock:
        live = []
        for ref in _wrapper_caches:
            c = ref()
            if c is not None:
                c.clear()
                live.append(ref)
        _wrapper_caches[:] = live  # prune dead wrappers while here


def _bump(key: str, n: int = 1) -> None:
    with _lock:
        _stats[key] += n


def _aval_part(a) -> tuple:
    """Stable key component for one argument: (shape, dtype) for
    anything array-like, a type tag for python scalars."""
    import numpy as np

    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return ("arr", tuple(int(s) for s in a.shape), str(a.dtype))
    if isinstance(a, bool):
        return ("pybool",)
    if isinstance(a, int):
        return ("pyint",)
    if isinstance(a, float):
        return ("pyfloat",)
    return ("other", str(np.asarray(a).shape), str(np.asarray(a).dtype))


@functools.lru_cache(maxsize=1)
def _code_digest() -> str:
    """sha256 over the kernel source files (crypto/jaxed25519/*.py +
    crypto/bls/msm.py), once per process: the program's component of
    the artifact key."""
    here = os.path.dirname(os.path.abspath(__file__))
    paths = glob.glob(os.path.join(here, "jaxed25519", "*.py"))
    paths.append(os.path.join(here, "bls", "msm.py"))
    h = hashlib.sha256()
    for path in sorted(paths):
        h.update(os.path.relpath(path, here).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read() + b"\0")
    return h.hexdigest()[:16]


def _full_key(kernel: str, static_key: tuple, args) -> str:
    import jax

    devs = jax.devices()
    return json.dumps([jax.__version__, devs[0].platform,
                       devs[0].device_kind, len(devs), _code_digest(),
                       kernel, list(static_key),
                       [list(_aval_part(a)) for a in args]],
                      sort_keys=True)


def _artifact_path(kernel: str, key: str) -> Optional[str]:
    if _dir is None:
        return None
    h = hashlib.sha256(key.encode()).hexdigest()[:24]
    return os.path.join(_dir, "aot", f"{kernel}-{h}.aot")


def _try_load(kernel: str, key: str, path: str):
    """Deserialize a stored executable onto the devices it was compiled
    for; None on ANY mismatch/corruption (counted, logged — the fresh
    compile takes over and rewrites the artifact)."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None  # plain miss: not on disk yet
    try:
        if not blob.startswith(_MAGIC):
            raise ValueError("bad magic")
        rest = blob[len(_MAGIC):]
        nl = rest.index(b"\n")
        meta = json.loads(rest[:nl].decode())
        if meta.get("key") != key:
            raise ValueError("key mismatch (different jax/backend/code/shape)")
        payload = pickle.loads(rest[nl + 1:])
        import jax
        from jax.experimental import serialize_executable as _se

        by_id = {d.id: d for d in jax.devices()}
        return _se.deserialize_and_load(
            *payload, execution_devices=[by_id[i] for i in meta["devices"]])
    except Exception as e:  # noqa: BLE001 - corrupt/foreign artifact
        _bump("load_errors")
        LOG.warning("ignoring unusable AOT artifact %s: %s", path, e)
        return None


def _try_store(kernel: str, key: str, path: str, compiled) -> None:
    """Serialize + atomic write-rename; failures only cost the cache."""
    try:
        from jax.experimental import serialize_executable as _se

        payload = pickle.dumps(_se.serialize(compiled))
        devices = [d.id for d in
                   compiled._executable.xla_executable.local_devices()]
        meta = json.dumps({"key": key, "kernel": kernel,
                           "devices": devices}).encode()
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".tmp-aot-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC + meta + b"\n" + payload)
            os.replace(tmp, path)  # atomic: racing writers both stay valid
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception as e:  # noqa: BLE001 - store is best-effort
        LOG.warning("could not persist AOT artifact for %s: %s", kernel, e)


def _timed_compile(kernel: str, jitted, args):
    """lower().compile() with the compile-seconds metric and the
    in-progress marker /debug/crypto surfaces."""
    global _compile_seq
    t0 = time.perf_counter()
    with _lock:
        _compile_seq += 1
        token = _compile_seq
        _compiling[token] = (kernel, t0)
    _from_jax_cache.seen = False
    try:
        compiled = jitted.lower(*args).compile()
    finally:
        with _lock:
            _compiling.pop(token, None)
    dt = time.perf_counter() - t0
    _bump("compiles")
    m = _metrics()
    if m is not None:
        m.compile_seconds.with_labels(kernel).observe(dt)
    return compiled


def load_or_compile(kernel: str, static_key: tuple, jitted, args,
                    ndev: int = 1):
    """One kernel instance: AOT-load from disk if a matching artifact
    exists, else lower+compile from `args` (concrete arrays or
    jax.ShapeDtypeStruct) and write the artifact back. A compile error
    propagates: it is the kernel's, not the cache's. `ndev`, the chips
    the program runs across, is carried by the kernel.load and
    kernel.compile spans."""
    ensure_configured()
    m = _metrics()
    key = _full_key(kernel, static_key, args)
    path = _artifact_path(kernel, key)
    span_key = str(list(static_key))
    t0 = time.perf_counter()
    if path is not None:
        with tracing.span("kernel.load", cat="crypto", kernel=kernel,
                          key=span_key, ndev=ndev) as sp:
            compiled = _try_load(kernel, key, path)
            sp.set(hit=compiled is not None)
        if compiled is not None:
            _bump("hits")
            if m is not None:
                m.compile_cache_hits.inc()
            _note_ready(kernel, static_key, args, t0, "aot-store")
            return compiled
        _bump("misses")
        if m is not None:
            m.compile_cache_misses.inc()
    with tracing.span("kernel.compile", cat="crypto", kernel=kernel,
                      key=span_key, ndev=ndev):
        compiled = _timed_compile(kernel, jitted, args)
    _note_ready(kernel, static_key, args, t0, "compiled")
    if path is not None and not (_from_jax_cache.seen and _on_cpu()):
        _try_store(kernel, key, path, compiled)
    return compiled


def _on_cpu() -> bool:
    import jax

    return jax.devices()[0].platform == "cpu"


def _note_ready(kernel: str, static_key: tuple, args, t0: float,
                source: str) -> None:
    rec = {"kernel": kernel, "static_key": list(static_key),
           "arg0_shape": list(getattr(args[0], "shape", ())),
           "seconds": round(time.perf_counter() - t0, 3), "source": source}
    LOG.info("kernel %s %s %s ready in %.1fs (%s)", kernel,
             rec["static_key"], rec["arg0_shape"], rec["seconds"], source)
    with _lock:
        _ready_log.append(rec)


def aot_wrap(kernel: str, static_key: tuple, jitted,
             ndev: int = 1) -> Callable:
    """Wrap a jitted function with the compile-once layer: the first
    call for each argument-shape signature loads the stored executable
    (or compiles and stores it); later calls dispatch the executable
    directly. Drop-in for the jit callable at every existing call site.
    """
    cache = _WrapperCache()
    lock = threading.Lock()
    with _lock:
        _wrapper_caches.append(weakref.ref(cache))

    def call(*args):
        k = tuple(_aval_part(a) for a in args)
        fn = cache.get(k)
        if fn is None:
            with lock:
                fn = cache.get(k)
                if fn is None:
                    fn = load_or_compile(kernel, static_key, jitted, args,
                                         ndev)
                    cache[k] = fn
        return fn(*args)

    call.kernel_name = kernel
    call.jitted = jitted  # for lowering without running (tests, rehearsals)
    return call
