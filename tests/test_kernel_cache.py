"""Compile-once kernel layer (crypto/kernel_cache.py).

The kernel-cache tests drive the AOT artifact store with TINY jitted
kernels (millisecond compiles) so integrity properties — corrupted
artifacts fall back, foreign keys are ignored, racing writers never
corrupt an entry, cached ≡ fresh results — run in tier-1 time. The
real verify kernels route through exactly the same aot_wrap layer
(tests/test_jax_ed25519.py exercises them end to end, warm via the
conftest session cache).
"""

import os
import threading

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import numpy as np
import pytest

from tendermint_tpu.crypto import kernel_cache

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture
def cache_dir(tmp_path):
    """Place the store from outside, the way a deployment does: export
    JAX_COMPILATION_CACHE_DIR and let the module resolve it. Restores
    the session's location afterwards."""
    d = str(tmp_path / "kc")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(kernel_cache.ENV_CACHE_DIR, d)
        kernel_cache.unconfigure()
        assert kernel_cache.ensure_configured() == d
        kernel_cache.reset_stats()
        yield d
    kernel_cache.unconfigure()
    kernel_cache.ensure_configured()
    kernel_cache.reset_stats()


_KERNEL_SEQ = [0]


def _tiny_kernel(c: int = 3):
    """A fresh aot_wrap'ed trivial kernel (unique name per call so tests
    never share artifacts)."""
    _KERNEL_SEQ[0] += 1
    name = f"test_tiny_{_KERNEL_SEQ[0]}"
    return kernel_cache.aot_wrap(name, (c,), jax.jit(lambda x: x * c + 1))


def _artifacts(d):
    aot = os.path.join(d, "aot")
    return sorted(os.path.join(aot, f) for f in os.listdir(aot)
                  if f.endswith(".aot"))


class TestAOTStore:
    def test_cold_compile_then_warm_load(self, cache_dir):
        """First call compiles + persists; dropping the in-memory
        executable reloads from disk WITHOUT recompiling, and the
        loaded executable computes the same result (cached ≡ fresh)."""
        fn = _tiny_kernel()
        x = np.arange(8, dtype=np.int32)
        fresh = np.asarray(fn(x))
        s = kernel_cache.stats()
        assert s["compiles"] == 1 and s["misses"] == 1 and s["hits"] == 0
        assert len(_artifacts(cache_dir)) == 1

        kernel_cache.clear_memory()  # simulate a fresh process
        warm = np.asarray(fn(x))
        s = kernel_cache.stats()
        assert s["compiles"] == 1, "warm load must not recompile"
        assert s["hits"] == 1
        np.testing.assert_array_equal(fresh, warm)

    def test_a_compile_jax_served_from_its_own_cache_is_not_stored(self, cache_dir):
        """On the CPU an executable that jax loaded from its persistent
        cache serializes without its kernels' functions: stored, it
        would load in the next process and fail at its first run. It is
        used and not stored; a compile jax really made is stored."""
        import jax.monitoring

        class _ServedFromJaxCache:
            """A jitted function whose compile jax reports as a hit."""

            def __init__(self, jitted):
                self.jitted = jitted

            def lower(self, *args):
                return self

            def compile(self):
                jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
                return self.jitted.lower(np.arange(8, dtype=np.int32)).compile()

        x = np.arange(8, dtype=np.int32)
        jitted = jax.jit(lambda x: x * 5 + 1)
        served = kernel_cache.aot_wrap("test_served_from_cache", (5,),
                                       _ServedFromJaxCache(jitted))
        np.testing.assert_array_equal(np.asarray(served(x)), x * 5 + 1)
        assert kernel_cache.stats()["compiles"] == 1
        assert _artifacts(cache_dir) == []
        # the flag is the compile's own: the next one, made, is stored
        made = kernel_cache.aot_wrap("test_really_compiled", (5,), jitted)
        np.testing.assert_array_equal(np.asarray(made(x)), x * 5 + 1)
        assert len(_artifacts(cache_dir)) == 1

    def test_crashed_writer_tempfiles_pruned(self, cache_dir):
        """Resolving the store GCs day-old crashed-writer tempfiles in
        aot/; live artifacts survive and still warm-load."""
        fn = _tiny_kernel()
        x = np.arange(4, dtype=np.int32)
        want = np.asarray(fn(x))
        live = os.path.basename(_artifacts(cache_dir)[0])

        aot = os.path.join(cache_dir, "aot")
        stale_tmp = os.path.join(aot, ".tmp-aot-crashed")
        open(stale_tmp, "wb").close()
        os.utime(stale_tmp, (1, 1))

        kernel_cache.unconfigure()
        kernel_cache.ensure_configured()
        names = os.listdir(aot)
        assert live in names and ".tmp-aot-crashed" not in names

        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        np.testing.assert_array_equal(want, np.asarray(fn(x)))
        assert kernel_cache.stats()["compiles"] == 0  # still warm

    def test_distinct_shapes_distinct_artifacts(self, cache_dir):
        fn = _tiny_kernel()
        fn(np.arange(8, dtype=np.int32))
        fn(np.arange(16, dtype=np.int32))
        assert kernel_cache.stats()["compiles"] == 2
        assert len(_artifacts(cache_dir)) == 2
        # both signatures warm-load independently
        kernel_cache.clear_memory()
        fn(np.arange(16, dtype=np.int32))
        fn(np.arange(8, dtype=np.int32))
        s = kernel_cache.stats()
        assert s["compiles"] == 2 and s["hits"] == 2

    def test_truncated_artifact_falls_back_to_fresh_compile(self, cache_dir):
        fn = _tiny_kernel()
        x = np.arange(8, dtype=np.int32)
        want = np.asarray(fn(x))
        path = _artifacts(cache_dir)[0]
        with open(path, "r+b") as f:
            f.truncate(10)
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        got = np.asarray(fn(x))  # no crash, no wrong verdicts
        np.testing.assert_array_equal(want, got)
        s = kernel_cache.stats()
        assert s["load_errors"] == 1 and s["misses"] == 1
        assert s["compiles"] == 1  # fresh compile replaced the artifact
        # ...and the rewritten artifact is valid again
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        np.testing.assert_array_equal(want, np.asarray(fn(x)))
        assert kernel_cache.stats()["hits"] == 1

    def test_garbage_payload_falls_back(self, cache_dir):
        fn = _tiny_kernel()
        x = np.arange(8, dtype=np.int32)
        want = np.asarray(fn(x))
        path = _artifacts(cache_dir)[0]
        with open(path, "rb") as f:
            blob = f.read()
        head, _, _ = blob.partition(b"\n")  # keep magic+meta, trash payload
        with open(path, "wb") as f:
            f.write(head + b"\n" + os.urandom(256))
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        np.testing.assert_array_equal(want, np.asarray(fn(x)))
        s = kernel_cache.stats()
        assert s["load_errors"] == 1 and s["compiles"] == 1

    def test_foreign_key_ignored(self, cache_dir):
        """An artifact whose embedded key names a different jax version
        / backend string is ignored (fresh compile), never trusted."""
        import json

        fn = _tiny_kernel()
        x = np.arange(8, dtype=np.int32)
        want = np.asarray(fn(x))
        path = _artifacts(cache_dir)[0]
        with open(path, "rb") as f:
            blob = f.read()
        magic = blob[:len(b"TMTPU-AOT1 ")]
        rest = blob[len(magic):]
        meta_raw, _, payload = rest.partition(b"\n")
        meta = json.loads(meta_raw.decode())
        key = json.loads(meta["key"])
        key[0] = "0.0.0-other-jax"  # jax version field of the key
        meta["key"] = json.dumps(key, sort_keys=True)
        with open(path, "wb") as f:
            f.write(magic + json.dumps(meta).encode() + b"\n" + payload)
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        np.testing.assert_array_equal(want, np.asarray(fn(x)))
        s = kernel_cache.stats()
        assert s["load_errors"] == 1 and s["hits"] == 0
        assert s["compiles"] == 1

    def test_concurrent_writers_never_corrupt(self, cache_dir):
        """Threads racing load-or-compile on the SAME entry (the
        process-race analogue; os.replace atomicity is identical):
        every caller gets correct results and the surviving artifact
        file is loadable."""
        fn = _tiny_kernel()
        x = np.arange(8, dtype=np.int32)
        want = list(range(1, 25, 3))
        results, errs = [], []

        def worker():
            try:
                results.append(np.asarray(fn(x)).tolist())
            except Exception as e:  # noqa: BLE001 - fail the test below
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs
        assert all(r == want for r in results)
        # the entry on disk is valid: a "fresh process" warm-loads it
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        assert np.asarray(fn(x)).tolist() == want
        s = kernel_cache.stats()
        assert s["hits"] == 1 and s["load_errors"] == 0

    def test_stale_tempfile_is_harmless(self, cache_dir):
        aot = os.path.join(cache_dir, "aot")
        with open(os.path.join(aot, ".tmp-aot-crashed"), "wb") as f:
            f.write(b"a writer died here")
        fn = _tiny_kernel()
        assert np.asarray(fn(np.arange(4, dtype=np.int32))).tolist() \
            == [1, 4, 7, 10]

    def test_different_code_digest_is_a_miss_never_a_load(
            self, cache_dir, monkeypatch):
        """Two checkouts sharing one cache directory: same kernel name,
        same shapes, different kernel source -> the second one compiles
        its own executable, it never loads the first one's."""
        name = "test_shared_name"
        x = np.arange(8, dtype=np.int32)
        monkeypatch.setattr(kernel_cache, "_code_digest", lambda: "aaaa")
        parent = kernel_cache.aot_wrap(name, (), jax.jit(lambda v: v + 1))
        assert np.asarray(parent(x)).tolist() == list(range(1, 9))
        assert len(_artifacts(cache_dir)) == 1

        kernel_cache.reset_stats()
        monkeypatch.setattr(kernel_cache, "_code_digest", lambda: "bbbb")
        change = kernel_cache.aot_wrap(name, (), jax.jit(lambda v: v + 2))
        assert np.asarray(change(x)).tolist() == list(range(2, 10))
        s = kernel_cache.stats()
        assert s["hits"] == 0 and s["misses"] == 1 and s["compiles"] == 1
        assert s["load_errors"] == 0  # a clean miss, not a rejected load
        assert len(_artifacts(cache_dir)) == 2  # neither evicts the other

    def test_code_digest_covers_kernel_sources(self):
        """The digest is taken over the kernel source files and is
        stable within a process."""
        d = kernel_cache._code_digest()
        assert d == kernel_cache._code_digest() and len(d) == 16
        assert d in kernel_cache._full_key("k", (), [])

    def test_loads_onto_the_devices_it_was_compiled_for(self, cache_dir):
        """A single-device kernel reloads as a single-device executable
        on a multi-device backend (jax 0.9.0's deserialize_and_load
        defaults to EVERY device, which made the warm run raise 'expected
        8 shards, got [1]'); a mesh kernel reloads over its mesh."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        ndev = len(jax.devices())
        assert ndev > 1, "conftest pins 8 virtual cpu devices"
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        sh = NamedSharding(mesh, P("dp"))
        meshed = kernel_cache.aot_wrap(
            "test_meshed", (ndev,),
            jax.jit(lambda v: v * 2, in_shardings=sh, out_shardings=sh))
        x = jax.device_put(np.arange(ndev * 2, dtype=np.int32), sh)
        want = np.asarray(meshed(x))
        kernel_cache.clear_memory()
        kernel_cache.reset_stats()
        got = meshed(x)
        np.testing.assert_array_equal(want, np.asarray(got))
        assert len(got.sharding.device_set) == ndev
        s = kernel_cache.stats()
        assert s["hits"] == 1 and s["compiles"] == 0

    def test_status_bundle_shape(self, cache_dir):
        fn = _tiny_kernel()
        fn(np.arange(4, dtype=np.int32))
        st = kernel_cache.status()
        assert st["enabled"] and st["dir"] == cache_dir
        assert st["compiles"] == 1 and st["compiling"] == {}

    def test_wrapper_cache_weakly_held(self, cache_dir):
        """An aot_wrap dropped by its caller (lru_cache eviction) must
        free its executables — the registry holds them weakly."""
        import gc

        fn = _tiny_kernel()
        fn(np.arange(4, dtype=np.int32))
        live_before = sum(1 for r in kernel_cache._wrapper_caches
                          if r() is not None)
        del fn
        gc.collect()
        kernel_cache.clear_memory()  # also prunes dead refs
        live_after = sum(1 for r in kernel_cache._wrapper_caches
                         if r() is not None)
        assert live_after < live_before


class TestCacheRule:
    """Where the compile cache lives: JAX_COMPILATION_CACHE_DIR when
    set (and then no code sets jax_compilation_cache_dir), else one
    fixed git-ignored path inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)
        kernel_cache.unconfigure()
        kernel_cache.ensure_configured()

    def test_env_dir_honoured_and_not_overwritten(self, tmp_path,
                                                  monkeypatch):
        d = str(tmp_path / "placed")
        monkeypatch.setenv(kernel_cache.ENV_CACHE_DIR, d)
        # what jax would hold had the variable been set at start-up
        jax.config.update("jax_compilation_cache_dir", d)
        updates = []
        real = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real(k, v))[1])
        kernel_cache.unconfigure()
        assert kernel_cache.ensure_configured() == d
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(os.path.join(d, "aot"))

    def test_unset_means_fixed_path_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv(kernel_cache.ENV_CACHE_DIR, raising=False)
        kernel_cache.unconfigure()
        got = kernel_cache.ensure_configured()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestObservability:
    def test_node_crypto_status_bundle(self, cache_dir):
        """The /debug/crypto provider bundle: kernel-cache state +
        inflight count, JSON-serializable."""
        import json

        from tendermint_tpu.node.node import Node

        class _Stub:  # the bundle reads module state + this one field
            _verifier = {"backend": "cpu", "warmup": "disabled"}

        out = Node._crypto_status(_Stub)
        json.dumps(out)
        assert out["dir"] == cache_dir and out["enabled"]
        assert "compiling" in out
        assert out["kernels"] == [] and out["inflight_batches"] == 0
        assert out["verifier"]["backend"] == "cpu"
        assert out["verifier"]["batch_cutoff"] >= 1

    def test_monitor_surfaces_compiling_node(self):
        """A node stuck compiling at boot is visible in the monitor
        snapshot (compiling kernel -> elapsed seconds) and the view
        resets when the debug endpoint goes away."""
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from tendermint_tpu.tools.monitor import Monitor

        payload = {
            "dwell_s": 0.1, "threshold_s": 30.0, "stalls_total": 0,
            "stalls": [], "live": {"peers": []},
            # the same stub answers every /debug route; crypto keys:
            "hits": 3, "misses": 1,
            "compiling": {"ed25519_packed": 42.5},
        }

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = _json.dumps(payload).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        daddr = "%s:%d" % srv.server_address[:2]
        try:
            mon = Monitor(["rpc-addr"], debug_addrs=[daddr])
            ns = mon.nodes["rpc-addr"]
            ns.mark_online()
            mon._poll_debug(ns, daddr)
            assert ns.compiling == {"ed25519_packed": 42.5}
            assert ns.compile_cache_hits == 3
            snap = mon.snapshot()
            assert snap["nodes"][0]["compiling"] == {"ed25519_packed": 42.5}
            assert snap["nodes"][0]["compile_cache_misses"] == 1
            ns.clear_debug_view()
            assert ns.compiling == {} and ns.compile_cache_hits == 0
        finally:
            srv.shutdown()
            srv.server_close()
