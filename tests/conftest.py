"""Test configuration.

Tests run JAX on a virtual 8-device CPU platform so multi-chip sharding
paths (shard_map over a Mesh) are exercised without TPU hardware. The
platform is pinned in the jax config as well as by the exported
JAX_PLATFORMS=cpu, so a bare `pytest` on a machine with a chip does not
take it.

The compile cache follows the production rule (crypto/kernel_cache):
JAX_COMPILATION_CACHE_DIR when set, else the git-ignored `.jax_cache/`
inside the checkout — so the XLA-compile burners (verify warmup
calibration, the jax_ed25519 suites, jax-MSM equivalence) pay their
compiles once per checkout instead of per run.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest

# Named thread families a test must not leak (PR-11 generalization of
# the crypto-dispatch check): each prefix is a worker family with an
# owning stop()/shutdown path, so anything still alive after teardown
# means a lifecycle bug — exactly what check_concurrency's CC-THREAD
# rule enforces statically. Families whose teardown is asynchronous get
# a short grace join before the assert so shutdown races don't flake.
_THREAD_FAMILIES = (
    "crypto-dispatch",    # per-backend verify dispatchers
    "mempool-ingest",     # batched CheckTx ingest worker
    "ws-writer",          # per-client websocket writer (PR-9 fan-out)
    "rpc-cache-inval",    # RPC response-cache invalidation drainer
    "cs-watchdog",        # consensus stall watchdog ticker
    "replica-telemetry",  # replica-mode telemetry ticker
    "lockdep",            # lockdep reporter/debug threads (PR-11)
    "tx-indexer",         # indexer service drainer (joined on stop)
    "bc-tip-announce",    # push-based tip announcer (PR-13; joined by
                          # BlockchainReactor.stop)
    "exec-lane",          # parallel block-execution lane workers (PR-12;
                          # joined per segment by state/parallel.py)
    "exec-spec",          # speculative block execution (PR-12; settled
                          # by BlockExecutor.stop / _take_speculation)
)

# Daemons allowed to outlive a test: process-wide singletons that are
# deliberately not per-test (none today — add entries HERE with a
# reason, not by widening the family list).
_KNOWN_DAEMON_ALLOWLIST: frozenset = frozenset()


def _leaked_family_threads():
    import threading

    return [
        t for t in threading.enumerate()
        if t.is_alive()
        and t.name not in _KNOWN_DAEMON_ALLOWLIST
        and any(t.name.startswith(p) for p in _THREAD_FAMILIES)
    ]


@pytest.fixture(autouse=True)
def _thread_hygiene():
    """Thread + process-global hygiene after every test: no NEW thread
    from ANY named worker family may outlive the test that created it
    (grace-joined first so in-flight shutdowns can finish), the crypto
    dispatch/cache globals are reset, and lockdep never stays patched
    into threading. Delta-based on purpose: module-scoped node
    fixtures (test_rpc_fanout's fanout_node and friends) legitimately
    keep their worker families alive across the module — those threads
    are in the baseline, so only threads the TEST spawned and lost can
    fail it."""
    # strong refs to the Thread OBJECTS, not idents: CPython reuses
    # idents after a thread exits, which could mask a leaked thread
    # that recycled a baseline ident; holding the objects pins their
    # identity for the test's duration
    baseline = set(_leaked_family_threads())
    yield
    import time

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.libs import lockdep

    crypto_batch.shutdown_dispatchers()
    crypto_batch.set_sig_cache(None)
    crypto_batch.set_async_enabled(True)
    # a test that enabled lockdep and failed before disable() would
    # leave threading.Lock patched for every later test
    if lockdep.is_enabled():
        lockdep.disable()
        lockdep.reset()

    def new_leaks():
        return [t for t in _leaked_family_threads()
                if t not in baseline]

    leaked = new_leaks()
    deadline = time.monotonic() + 2.0
    while leaked and time.monotonic() < deadline:
        for t in leaked:
            t.join(timeout=0.2)
        leaked = new_leaks()
    assert not leaked, (
        f"leaked worker threads (family list in conftest): {leaked}")
