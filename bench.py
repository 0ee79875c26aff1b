"""Benchmark: the north-star hot path — VerifyCommit at 10k validators.

Default run = BASELINE.json config 5: "10k-validator mega-commit
VerifyCommit on TPU, mixed valid/invalid sigs". Baseline stand-in for the
reference's serial Go ed25519 path (types/validator_set.go:345-371): a
serial OpenSSL verify loop (measured on a subset, extrapolated linearly —
per-signature cost is constant).

The other BASELINE.json configs map to modes:
  1 "VerifyCommit on a 4-validator genesis commit"  -> `bench.py commit4`
  2 "1k random triples, serial vs JAX-CPU backend"  -> no mode: an
        XLA:CPU kernel time is not a device number (`bench.py 1000`
        runs the same 1k triples on the chip)
  3 "150-validator prevote+precommit round replay"  -> `bench.py votes`
  4 "fast-sync block validation, 500-val commits"   -> `bench.py fastsync`
  5 "10k-validator mega-commit, mixed validity"     -> default

Async/cache modes (PR 2):
  `bench.py fastsync --pipeline` — two-stage pipeline: verify(k+1)
        dispatched async while apply(k) runs; reports serial AND
        pipelined wall plus the pipeline-overlap histogram count
  `bench.py cache` — duplicate-heavy deliveries through the verified-
        signature cache; reports hit rate and wall vs the uncached run

Mega mode (PR 8):
  `bench.py mega` — the default verify-commit benchmark at the
        100k-signature mega-committee point (10k validators x many
        heights in flight); `bench.py 100000` spelled as a mode

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N}
vs_baseline > 1 means faster than the serial baseline.

Device modes (default/mega, votes, fastsync, cache, statesync) need
the chip: they exit non-zero, naming the cause, unless
jax.default_backend() is "tpu", and every line they print carries the
platform, device_kind and device count it ran on. The host modes pin
the cpu batch backend and never initialise a device. Any failure is a
traceback and a non-zero exit code, never a line with a placeholder
value. A chip belongs to one process: this script starts no child that
needs it.
"""

import json
import os
import secrets
import subprocess
import sys
import time

VOTES_MODE = "votes" in sys.argv[1:]  # BASELINE.json config 3
FASTSYNC_MODE = "fastsync" in sys.argv[1:]  # BASELINE.json config 4 (scaled)
COMMIT4_MODE = "commit4" in sys.argv[1:]  # BASELINE.json config 1
CACHE_MODE = "cache" in sys.argv[1:]  # duplicate-heavy sig-cache mode
STATESYNC_MODE = "statesync" in sys.argv[1:]  # restore vs replay (PR 4)
CHAOS_MODE = "chaos" in sys.argv[1:]  # ABCI reconnect recovery (PR 5)
LOAD_MODE = "load" in sys.argv[1:]  # sustained-TPS mempool localnet (PR 6)
PREVERIFY_MODE = "preverify" in sys.argv[1:]  # batched vs serial CheckTx
AGGVERIFY_MODE = "aggverify" in sys.argv[1:]  # BLS aggregate cert (PR 7)
RPCLOAD_MODE = "rpcload" in sys.argv[1:]  # RPC fan-out serving (PR 9)
MEGA_MODE = "mega" in sys.argv[1:]  # 100k-sig mega-committee batch point
CHAOSNET_MODE = "chaosnet" in sys.argv[1:]  # partition-heal recovery (PR 10)
CRASHREC_MODE = "crashrecovery" in sys.argv[1:]  # kill->committing (PR 14)
DETCHECK_MODE = "detcheck" in sys.argv[1:]  # replay-divergence oracle (PR 15)
PROPTRACE_MODE = "proptrace" in sys.argv[1:]  # fleet causal tracing (PR 16)
INCIDENT_MODE = "incident" in sys.argv[1:]  # incident MTTD/MTTR (PR 18)
HANDEL_MODE = "handel" in sys.argv[1:]  # aggregation overlay (PR 19)
FLEET_MODE = "fleet" in sys.argv[1:]  # replica fan-out serving (PR 20)
PIPELINE_FLAG = "--pipeline" in sys.argv[1:]  # fastsync: 2-stage pipeline
PARALLEL_FLAG = "--parallel" in sys.argv[1:]  # load: parallel exec lanes
_args = [a for a in sys.argv[1:]
         if a not in ("votes", "fastsync", "commit4", "cache",
                      "statesync", "chaos", "load", "preverify",
                      "aggverify", "mega", "chaosnet",
                      "crashrecovery", "detcheck", "proptrace",
                      "incident", "handel", "fleet",
                      "--pipeline", "--parallel")]
try:
    METRIC_N = int(_args[0]) if _args else (100000 if MEGA_MODE else 10000)
except ValueError:
    METRIC_N = 100000 if MEGA_MODE else 10000

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


# mode scales + metric names, shared by the success and failure paths so
# they cannot diverge when the scale constants change. The fastsync
# scale is env-overridable (metric names track the actual values) so
# hosts without OpenSSL — where the serial stand-in runs the ~7.5ms/sig
# pure-Python fallback — can still exercise the mode end-to-end.
VOTES_NVAL = 150
VOTES_METRIC = f"voteset_replay_{VOTES_NVAL}val_2rounds_wall_ms"
FS_NVAL = _env_int("TM_TPU_BENCH_FS_NVAL", 500)
FS_NBLOCKS = _env_int("TM_TPU_BENCH_FS_BLOCKS", 20)
FS_METRIC = f"fastsync_{FS_NBLOCKS}x{FS_NVAL}val_wall_ms"
FS_PIPE_METRIC = f"fastsync_pipeline_{FS_NBLOCKS}x{FS_NVAL}val_wall_ms"
COMMIT4_METRIC = "verify_commit_4val_wall_ms"
CACHE_NVAL, CACHE_DUPS = 500, 3
CACHE_METRIC = f"sig_cache_{CACHE_DUPS}x{CACHE_NVAL}dup_wall_ms"
SS_NBLOCKS = _env_int("TM_TPU_BENCH_SS_BLOCKS", 20)
SS_NVAL = _env_int("TM_TPU_BENCH_SS_NVAL", 100)
SS_METRIC = f"statesync_restore_vs_replay_{SS_NBLOCKS}x{SS_NVAL}val_wall_ms"
CHAOS_ROUNDS = _env_int("TM_TPU_BENCH_CHAOS_ROUNDS", 10)
CHAOS_METRIC = f"abci_reconnect_recovery_{CHAOS_ROUNDS}rounds_ms"
LOAD_TPS = _env_int("TM_TPU_BENCH_LOAD_TPS", 200)
LOAD_SECS = _env_int("TM_TPU_BENCH_LOAD_SECS", 5)
LOAD_METRIC = f"mempool_load_{LOAD_TPS}tps_{LOAD_SECS}s_p99_commit_ms"
# parallel-execution load mode (`bench.py load --parallel`, PR 12):
# the same single-validator localnet drives a sharded kvstore app with
# EXEC_IO_US of simulated per-tx backend latency (storage/remote-call
# wait — the GIL-released stall parallel lanes overlap) twice: serial
# execution ([execution] defaults, the committed baseline) and then
# EXEC_LANES optimistic lanes + speculative execution
EXEC_IO_US = _env_int("TM_TPU_BENCH_EXEC_IO_US", 10000)
EXEC_LANES = _env_int("TM_TPU_BENCH_EXEC_LANES", 64)
EXEC_SERIAL_TPS = _env_int("TM_TPU_BENCH_EXEC_SERIAL_TPS", 300)
EXEC_PAR_TPS = _env_int("TM_TPU_BENCH_EXEC_PAR_TPS", 4000)
EXEC_SECS = _env_int("TM_TPU_BENCH_EXEC_SECS", 4)
EXEC_METRIC = (f"exec_parallel_{EXEC_LANES}lanes_"
               f"{EXEC_IO_US}us_committed_tps")
# high-conflict legs (PR 17): EXEC_CONFLICT_PCT percent of txs carry a
# LYING access hint and actually touch one of EXEC_HOT_KEYS shared
# keys, so the planner spreads them across lanes and the merge sees
# real read/write overlap. Run once on the PR-16 engine (segment
# re-run + whole-block serial fallback) and once on the retry-DAG +
# lane-pool engine; the ratio is the conflict-path speedup.
EXEC_HC_TPS = _env_int("TM_TPU_BENCH_EXEC_HC_TPS", 800)
EXEC_HC_SECS = _env_int("TM_TPU_BENCH_EXEC_HC_SECS", 3)
EXEC_CONFLICT_PCT = _env_int("TM_TPU_BENCH_EXEC_CONFLICT_PCT", 30)
EXEC_HOT_KEYS = _env_int("TM_TPU_BENCH_EXEC_HOT_KEYS", 16)
EXEC_RETRY_ROUNDS = _env_int("TM_TPU_BENCH_EXEC_RETRY_ROUNDS", 3)
PREVERIFY_N = _env_int("TM_TPU_BENCH_PREVERIFY_N", 2000)
PREVERIFY_METRIC = f"mempool_preverify_{PREVERIFY_N}tx_wall_ms"
AGG_NVAL = _env_int("TM_TPU_BENCH_AGG_NVAL", 10000)
AGG_METRIC = f"aggverify_{AGG_NVAL}val_commit_wall_ms"
RPC_SUBS = _env_int("TM_TPU_BENCH_RPC_SUBS", 100)
RPC_QUERIES = _env_int("TM_TPU_BENCH_RPC_QUERIES", 2000)
RPC_THREADS = _env_int("TM_TPU_BENCH_RPC_THREADS", 4)
RPCLOAD_METRIC = f"rpc_serving_{RPC_SUBS}subs_hot_status_p50_ms"
CHAOSNET_NVAL = _env_int("TM_TPU_BENCH_CHAOSNET_NVAL", 4)
CHAOSNET_SEED = _env_int("TM_TPU_BENCH_CHAOSNET_SEED", 1)
CHAOSNET_METRIC = (
    f"chaosnet_partition_heal_{CHAOSNET_NVAL}node_recovery_ms")
CRASHREC_ROUNDS = _env_int("TM_TPU_BENCH_CRASHREC_ROUNDS", 3)
CRASHREC_METRIC = (
    f"crash_recovery_kill_to_committing_{CRASHREC_ROUNDS}rounds_ms")
DETCHECK_BLOCKS = _env_int("TM_TPU_BENCH_DETCHECK_BLOCKS", 10)
DETCHECK_METRIC = f"detcheck_oracle_{DETCHECK_BLOCKS}blocks_wall_ms"
PROPTRACE_NVAL = _env_int("TM_TPU_BENCH_PROPTRACE_NVAL", 4)
PROPTRACE_SEED = _env_int("TM_TPU_BENCH_PROPTRACE_SEED", 8)
PROPTRACE_METRIC = (
    f"proptrace_{PROPTRACE_NVAL}node_commit_attribution_coverage_pct")
INCIDENT_NVAL = _env_int("TM_TPU_BENCH_INCIDENT_NVAL", 4)
INCIDENT_SEED = _env_int("TM_TPU_BENCH_INCIDENT_SEED", 9)
INCIDENT_METRIC = (
    f"incident_{INCIDENT_NVAL}node_composed_mttr_p50_ms")
HANDEL_NVAL = _env_int("TM_TPU_BENCH_HANDEL_NVAL", 1024)
HANDEL_METRIC = f"handel_overlay_{HANDEL_NVAL}val_per_node_verify_ops"
# replica fan-out tree serving (PR 20): N in-process replicas behind
# one validator, tiered via [replica] prefer_replicas, answering a
# round-robin read load while tailing live
FLEET_REPLICAS = _env_int("TM_TPU_BENCH_FLEET_REPLICAS", 4)
FLEET_SECS = _env_int("TM_TPU_BENCH_FLEET_SECS", 6)
FLEET_CLIENTS = _env_int("TM_TPU_BENCH_FLEET_CLIENTS", 8)
FLEET_METRIC = f"fleet_serve_{FLEET_REPLICAS}replica_tree_rpc_p50_ms"


def _best_of(fn, reps: int) -> float:
    """Best-of-N wall time in ms (same outlier discipline for serial
    baselines and batch paths, so vs_baseline compares like with like)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1000)
    return best


_DEVICE = None  # set by _require_chip() in device modes


def _require_chip() -> None:
    """Device modes only: fail (non-zero exit, cause named) unless the
    default jax backend is a TPU; remember the device for _emit."""
    global _DEVICE
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py: this mode measures the accelerator and jax "
                 f"came up on {backend!r} (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); no number is "
                 f"reported from a CPU run")
    dev = jax.devices()[0]
    _DEVICE = {"platform": dev.platform, "kind": dev.device_kind,
               "count": len(jax.devices())}


def _emit(out: dict) -> None:
    """Print the one JSON line; device modes name the device."""
    if _DEVICE is not None:
        out["device"] = _DEVICE
    print(json.dumps(out))


def _signed_vote(chain_id, keys_list, vals, idx, height, round_, type_, block_id):
    from tendermint_tpu.types import Vote

    addr, _ = vals.get_by_index(idx)
    v = Vote(
        validator_address=addr,
        validator_index=idx,
        height=height,
        round=round_,
        timestamp=1_700_000_000_000_000_000 + idx,
        type=type_,
        block_id=block_id,
    )
    v.signature = keys_list[idx].sign(v.sign_bytes(chain_id))
    return v


def votes_main():
    """BASELINE.json config 3: a 150-validator prevote+precommit round
    replayed through VoteSet.add_votes (the live batched tally path).
    Baseline stand-in: per-vote serial add_vote (one OpenSSL verify per
    vote), the reference's one-at-a-time types/vote_set.go:189 flow."""
    from tendermint_tpu.types import (
        VOTE_TYPE_PRECOMMIT,
        VOTE_TYPE_PREVOTE,
        BlockID,
    )
    from tendermint_tpu.types.basic import PartSetHeader
    from tendermint_tpu.types.validator_set import random_validator_set
    from tendermint_tpu.types.vote_set import VoteSet

    chain = "bench-votes"
    nval = VOTES_NVAL
    vals, keys_list = random_validator_set(nval, 10)
    bid = BlockID(b"\x0b" * 20, PartSetHeader(1, b"\x0c" * 20))
    rounds = [
        (VOTE_TYPE_PREVOTE, [
            _signed_vote(chain, keys_list, vals, i, 1, 0, VOTE_TYPE_PREVOTE, bid)
            for i in range(nval)
        ]),
        (VOTE_TYPE_PRECOMMIT, [
            _signed_vote(chain, keys_list, vals, i, 1, 0, VOTE_TYPE_PRECOMMIT, bid)
            for i in range(nval)
        ]),
    ]

    # serial baseline: add_vote one at a time (fresh sets), same
    # best-of-N outlier discipline as the batch path
    def serial():
        for type_, votes in rounds:
            vs = VoteSet(chain, 1, 0, type_, vals)
            for v in votes:
                vs.add_vote(v)
            assert vs.has_two_thirds_majority()

    serial_ms = _best_of(serial, 3)

    # production flow: warmup compiles the bucket this batch uses AND
    # calibrates the adaptive cutoff to the measured dispatch-vs-serial
    # break-even — where one dispatch costs more than 150 serial
    # verifies the batch correctly DECLINES the device
    from tendermint_tpu.crypto.jaxed25519.verify import warmup

    warmup(buckets=(nval,))

    # batched path (warm once, then best of N)
    def run():
        for type_, votes in rounds:
            vs = VoteSet(chain, 1, 0, type_, vals)
            vs.add_votes(votes)
            assert vs.has_two_thirds_majority()

    run()
    best = _best_of(run, 5)

    out = {
        "metric": VOTES_METRIC,
        "value": round(best, 3),
        "unit": "ms",
        "vs_baseline": round(serial_ms / best, 2),
    }
    from tendermint_tpu.crypto import batch as crypto_batch

    # effective_batch_min already folds in env-override precedence, so
    # the reported cutoff always matches the actual routing decision
    eff = crypto_batch.effective_batch_min()
    out["batch_cutoff"] = eff
    if nval < eff:
        out["note"] = "calibrated cutoff routed this batch to host CPU"
    _emit(out)


def _hist_count(registry, name: str) -> int:
    """Sample count of a label-less histogram in a metrics Registry."""
    for line in registry.render().splitlines():
        if line.startswith(name + "_count"):
            try:
                return int(float(line.rsplit(" ", 1)[1]))
            except ValueError:
                return 0
    return 0


def fastsync_pipeline_main(chain, vs, commits, serial_extrap_ms,
                           warm_wall_ms):
    """`bench.py fastsync --pipeline` — the two-stage fast-sync pipeline
    (blockchain/reactor._try_sync_batch_pipelined shape): block k's
    apply runs on the host while block k+1's commit batch is already
    dispatched (begin_verify_commit -> verify_async). The apply stand-in
    is a sleep sized to the measured per-block verify cost — the
    'comparable verify/apply cost' regime of the acceptance criterion,
    where pipelining approaches 2x. Reports BOTH modes (serial_ms vs
    value) plus the pipeline-overlap histogram count."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.metrics import prometheus_metrics

    nblocks = len(commits)
    verify_ms = warm_wall_ms / nblocks  # measured per-block verify wall
    apply_s = verify_ms / 1000.0

    def serial_run():
        for h, bid, commit in commits:
            vs.verify_commit(chain, bid, h, commit)
            time.sleep(apply_s)  # apply(k) stand-in

    def pipelined_run():
        h0, bid0, commit0 = commits[0]
        pend = vs.begin_verify_commit(chain, bid0, h0, commit0)
        for i in range(nblocks):
            pend.result()  # verify(k) must complete before apply(k)
            nxt = None
            if i + 1 < nblocks:
                h, bid, commit = commits[i + 1]
                nxt = vs.begin_verify_commit(chain, bid, h, commit)
            time.sleep(apply_s)  # apply(k) overlaps verify(k+1)
            pend = nxt

    m = prometheus_metrics("bench")
    crypto_batch.set_metrics(m.crypto)
    prev_async = crypto_batch.async_enabled()
    crypto_batch.set_async_enabled(True)
    try:
        pipelined_run()  # warm the dispatcher
        serial_wall = _best_of(serial_run, 3)
        pipe_wall = _best_of(pipelined_run, 3)
    finally:
        crypto_batch.set_metrics(None)
        crypto_batch.set_async_enabled(prev_async)
        crypto_batch.shutdown_dispatchers()

    overlap_n = _hist_count(m.registry,
                            "bench_crypto_pipeline_overlap_seconds")
    out = {
        "metric": FS_PIPE_METRIC,
        "value": round(pipe_wall, 3),
        "unit": "ms",
        # headline ratio: pipelined vs the serial verify+apply loop
        "vs_baseline": round(serial_wall / pipe_wall, 2),
        "serial_ms": round(serial_wall, 3),
        "per_block_ms": round(pipe_wall / nblocks, 3),
        "apply_stub_ms": round(verify_ms, 3),
        "overlap_samples": overlap_n,
        "vs_serial_openssl": round(
            (serial_extrap_ms + nblocks * verify_ms) / pipe_wall, 2),
    }
    _emit(out)


def cache_main():
    """`bench.py cache` — duplicate-heavy verification: CACHE_NVAL
    unique vote-sized triples (with ~1% invalid) delivered CACHE_DUPS
    times, the gossip re-delivery pattern. Baseline: same deliveries
    with the verified-signature cache off (every delivery re-dispatches
    to the backend). Reports hit rate alongside wall-ms in the standard
    BENCH schema."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import keys as ck
    from tendermint_tpu.crypto.sigcache import SigCache

    nval, dups = CACHE_NVAL, CACHE_DUPS
    sks = [ck.PrivKeyEd25519.gen_from_secret(b"cache-%d" % i)
           for i in range(nval)]
    triples = []
    for i, sk in enumerate(sks):
        msg = b"vote-%d-" % i + b"\x00" * 100
        sig = sk.sign(msg)
        if i % 100 == 37:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        triples.append((msg, sig, sk.pub_key().bytes()))
    deliveries = [list(triples) for _ in range(dups)]

    def run_all():
        for d in deliveries:
            crypto_batch.batch_verify(d)

    crypto_batch.set_sig_cache(None)
    run_all()  # warm (compile, key tables)
    nocache_ms = _best_of(run_all, 3)

    last_cache = [None]

    def run_cached():
        # fresh cache per rep: hits come from the duplicate deliveries
        # within one run, exactly the per-block gossip pattern
        cache = SigCache(4 * nval)
        last_cache[0] = cache
        crypto_batch.set_sig_cache(cache)
        run_all()

    try:
        run_cached()
        cached_ms = _best_of(run_cached, 3)
        cache = last_cache[0]
        hit_rate = cache.hits / max(1, cache.hits + cache.misses)
    finally:
        crypto_batch.set_sig_cache(None)

    _emit({
        "metric": CACHE_METRIC,
        "value": round(cached_ms, 3),
        "unit": "ms",
        "vs_baseline": round(nocache_ms / cached_ms, 2),
        "nocache_ms": round(nocache_ms, 3),
        "hit_rate": round(hit_rate, 4),
    })


def fastsync_main():
    """BASELINE.json config 4 (scaled to this box): fast-sync block
    validation — sequential verify_commit of 20 blocks x 500-validator
    commits (10k signatures), the blockchain/reactor.go:310 loop.
    Baseline stand-in: serial OpenSSL verifies extrapolated. With
    --pipeline, additionally measures the two-stage verify/apply
    pipeline (fastsync_pipeline_main)."""
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.basic import PartSetHeader

    chain = "bench-fastsync"
    nval, nblocks = FS_NVAL, FS_NBLOCKS
    vs, sorted_sks = _build_valset(nval, b"fs")

    commits = []
    for h in range(1, nblocks + 1):
        bid = BlockID(bytes([h % 256]) * 20, PartSetHeader(1, b"\x0c" * 20))
        commits.append((h, bid, _build_commit(chain, vs, sorted_sks, h, bid)))

    # serial baseline (subset of 300 verifies, extrapolated to all sigs;
    # best-of-3 like the batch path)
    sub = 300

    def serial():
        h, bid, commit = commits[0]
        for i in range(sub):
            v = commit.precommits[i % nval]
            vs.validators[v.validator_index].pub_key.verify_bytes(
                v.sign_bytes(chain), v.signature)

    serial_ms = _best_of(serial, 3) / sub * nval * nblocks

    def run():
        for h, bid, commit in commits:
            vs.verify_commit(chain, bid, h, commit)

    run()  # warm the 512-bucket compile
    best = _best_of(run, 3)

    if PIPELINE_FLAG:
        return fastsync_pipeline_main(chain, vs, commits, serial_ms, best)

    out = {
        "metric": FS_METRIC,
        "value": round(best, 3),
        "unit": "ms",
        "vs_baseline": round(serial_ms / best, 2),
        "per_block_ms": round(best / nblocks, 2),
    }
    _emit(out)


def statesync_main():
    """`bench.py statesync` — bootstrap-cost comparison: restoring a
    fresh node from a chunked snapshot at height N (light-verify the
    anchor via DynamicVerifier — a handful of batched verify_commits —
    then hash-check + apply chunks) vs replaying blocks 1..N (one
    verify_commit per block plus tx re-execution). This is the whole
    point of the subsystem: replay cost grows linearly in chain height,
    restore cost doesn't."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import merkle
    from tendermint_tpu.lite import (
        DynamicVerifier,
        FullCommit,
        MemProvider,
        SignedHeader,
    )
    from tendermint_tpu.statesync import chunker
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from tendermint_tpu.types.block import Header

    chain = "bench-statesync"
    nval, nblocks = SS_NVAL, SS_NBLOCKS
    txs_per_block = 10
    chunk_size = 4096
    vs, sorted_sks = _build_valset(nval, b"ss")

    # the sig cache would let the restore path ride verifications the
    # replay path already paid for — disable it for a fair comparison
    crypto_batch.set_sig_cache(None)

    def _header(h):
        return Header(
            chain_id=chain, height=h,
            time=1_700_000_000_000_000_000 + h,
            num_txs=txs_per_block, total_txs=txs_per_block * h,
            last_commit_hash=b"\x02" * 32,
            data_hash=merkle.hash_from_byte_slices([]),
            validators_hash=vs.hash(), next_validators_hash=vs.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"",
            last_results_hash=b"", evidence_hash=b"",
            proposer_address=vs.validators[0].address,
        )

    # synthetic chain: header+commit per height, same valset throughout
    commits, source = [], MemProvider()
    for h in range(1, nblocks + 1):
        hdr = _header(h)
        bid = BlockID(hdr.hash(), PartSetHeader(1, b"\x0c" * 20))
        commit = _build_commit(chain, vs, sorted_sks, h, bid)
        commits.append((h, bid, commit))
        source.save_full_commit(FullCommit(
            signed_header=SignedHeader(header=hdr, commit=commit),
            validators=vs, next_validators=vs))

    block_txs = [[b"k%d-%d=v" % (h, i) for i in range(txs_per_block)]
                 for h in range(1, nblocks + 1)]

    # producer app at height N, snapshotted
    producer = KVStoreApplication()
    producer.snapshot_interval = nblocks
    producer.snapshot_chunk_size = chunk_size
    for txs in block_txs:
        for tx in txs:
            producer.deliver_tx(tx)
        producer.commit()
    snap = producer.list_snapshots(abci.RequestListSnapshots()).snapshots[-1]

    def replay_run():
        app = KVStoreApplication()
        for (h, bid, commit), txs in zip(commits, block_txs):
            vs.verify_commit(chain, bid, h, commit)  # fast-sync's check
            for tx in txs:
                app.deliver_tx(tx)
            app.commit()
        return app

    def restore_run():
        verifier = DynamicVerifier(chain, MemProvider(), source)
        verifier.init_trust(source.latest_full_commit(chain, 1))
        # the real restore light-verifies headers H and H+1 (the anchor
        # pair); each is one batched verify_commit
        for h in (nblocks - 1, nblocks):
            verifier.verify(
                source.latest_full_commit(chain, h).signed_header)
        app = KVStoreApplication()
        res = app.offer_snapshot(abci.RequestOfferSnapshot(
            snapshot=snap, app_hash=producer.app_hash))
        assert res.result == abci.OFFER_ACCEPT
        for i in range(snap.chunks):
            data = producer.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(
                    height=snap.height, format=snap.format, chunk=i)).chunk
            assert chunker.verify_chunk(data, i, snap.chunk_hashes)
            r = app.apply_snapshot_chunk(abci.RequestApplySnapshotChunk(
                index=i, chunk=data, sender="bench"))
            assert r.result == abci.APPLY_ACCEPT
        return app

    # warm (compiles, key tables), then sanity: both paths land on the
    # producer's app hash
    assert replay_run().app_hash == producer.app_hash
    assert restore_run().app_hash == producer.app_hash

    replay_ms = _best_of(replay_run, 3)
    restore_ms = _best_of(restore_run, 3)

    _emit({
        "metric": SS_METRIC,
        "value": round(restore_ms, 3),
        "unit": "ms",
        "vs_baseline": round(replay_ms / restore_ms, 2),
        "replay_ms": round(replay_ms, 3),
        "chunks": snap.chunks,
        "note": "baseline = fast-sync replay of the same height range",
    })


def _build_valset(nval: int, seed: bytes):
    """(validator_set, secret keys aligned to address-sorted order) —
    fixture shared by the commit4 and fastsync modes."""
    from tendermint_tpu.crypto import keys as ck
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    sks = [ck.PrivKeyEd25519.gen_from_secret(seed + b"-%d" % i)
           for i in range(nval)]
    vs = ValidatorSet([Validator.new(sk.pub_key(), 10) for sk in sks])
    by_addr = {sk.pub_key().address(): sk for sk in sks}
    return vs, [by_addr[v.address] for v in vs.validators]


def _build_commit(chain: str, vs, sorted_sks, height: int, bid):
    """A full commit for `bid` at `height`, every validator signing."""
    from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT
    from tendermint_tpu.types.block import Commit

    pre = [
        _signed_vote(chain, sorted_sks, vs, i, height, 0,
                     VOTE_TYPE_PRECOMMIT, bid)
        for i in range(len(sorted_sks))
    ]
    return Commit(bid, pre)


def commit4_main():
    """BASELINE.json config 1: VerifyCommit on a 4-validator genesis-style
    commit. At 4 signatures the serial CPU path is the point — this
    measures the small-commit common case every block pays, not the
    batch kernel. The cpu backend is FORCED so no env tuning
    (TM_TPU_BATCH_MIN, TM_TPU_CRYPTO_BACKEND=jax) can route the
    benchmarked call onto a device: this is a host mode."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.basic import PartSetHeader

    crypto_batch.set_default_backend("cpu")
    chain = "bench-commit4"
    bid = BlockID(b"\x04" * 20, PartSetHeader(1, b"\x0c" * 20))
    vs, sorted_sks = _build_valset(4, b"c4")
    commit = _build_commit(chain, vs, sorted_sks, 1, bid)

    def run():
        vs.verify_commit(chain, bid, 1, commit)

    run()
    reps = 50
    best = _best_of(lambda: [run() for _ in range(reps)], 3) / reps
    print(json.dumps({
        "metric": COMMIT4_METRIC,
        "value": round(best, 3),
        "unit": "ms",
        "vs_baseline": 1.0,
        "note": "serial CPU path forced by design at 4 sigs",
    }))


class _NullApp:
    """Zero-cost app stand-in: isolates the mempool's own ingest cost
    (signature verification, locks, batching) from app logic."""

    def check_tx(self, tx):
        from tendermint_tpu.abci import types as abci_types

        return abci_types.ResponseCheckTx(code=0, gas_wanted=1)

    def flush(self):
        pass


def preverify_main():
    """`bench.py preverify` — batched CheckTx signature pre-verification
    (the ingest queue draining into ONE crypto/batch call riding the
    verified-signature cache) vs the serial per-tx verify path, same
    txs, same app. The cache is warmed first — the batched path's win
    is exactly the PR-2 vote trick applied to tx ingest: a warm cache
    turns the whole signature batch into sha256 lookups while the
    serial path re-verifies every tx. cpu backend forced: a host mode
    never initialises a device."""
    from tendermint_tpu import config as cfg
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import keys
    from tendermint_tpu.crypto.sigcache import SigCache
    from tendermint_tpu.mempool import Mempool, make_signed_tx

    crypto_batch.set_default_backend("cpu")
    crypto_batch.set_sig_cache(SigCache(4 * PREVERIFY_N))
    sks = [keys.PrivKeyEd25519.generate() for _ in range(32)]
    txs = [make_signed_tx(sks[i % len(sks)], b"load-%06d" % i,
                          priority=i % 4)
           for i in range(PREVERIFY_N)]

    def serial_run():
        # the serial baseline is the REFERENCE semantics: one full
        # Ed25519 verify per tx, no cache (the serial mempool path
        # itself rides the sig cache when installed — uninstall it for
        # the baseline so the measured contrast is architectural)
        cache = crypto_batch.get_sig_cache()
        crypto_batch.set_sig_cache(None)
        try:
            mp = Mempool(cfg.MempoolConfig(size=PREVERIFY_N + 1), _NullApp())
            for tx in txs:
                assert mp.check_tx(tx).code == 0
            return mp
        finally:
            crypto_batch.set_sig_cache(cache)

    def batched_run():
        mp = Mempool(
            cfg.MempoolConfig(size=PREVERIFY_N + 1, preverify_batch=True,
                              preverify_batch_max=256,
                              ingest_queue_size=2 * PREVERIFY_N),
            _NullApp())
        futs = [mp.check_tx_nowait(tx) for tx in txs]
        for f in futs:
            assert f.result(timeout=60).code == 0
        mp.stop()
        return mp

    batched_run()  # warm: fills the verified-signature cache
    serial_ms = _best_of(serial_run, 3)
    batched_ms = _best_of(batched_run, 3)
    crypto_batch.shutdown_dispatchers()
    crypto_batch.set_sig_cache(None)
    print(json.dumps({
        "metric": PREVERIFY_METRIC,
        "value": round(batched_ms, 3),
        "unit": "ms",
        "vs_baseline": round(serial_ms / batched_ms, 2),
        "serial_ms": round(serial_ms, 3),
        "note": ("batched ingest (one verify_async per drain, warm sig "
                 "cache) vs serial per-tx Ed25519 verify; cpu backend"),
    }))
    return 0


def load_main():
    """`bench.py load` — sustained-load harness: drive an in-process
    single-validator localnet at a target TPS through the batched
    ingest path and report accepted TPS plus p50/p99 commit latency
    (submit -> the NewBlock event carrying the tx). Pure host path."""
    import hashlib
    import threading

    from tendermint_tpu import config as cfg
    from tendermint_tpu import state as sm
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.consensus import ConsensusState
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import keys
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.mempool import Mempool, make_signed_tx
    from tendermint_tpu.privval import FilePV
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK, EventBus, query_for_event)
    from tendermint_tpu.types.validator_set import random_validator_set

    crypto_batch.set_default_backend("cpu")
    vs, vkeys = random_validator_set(1, 10)
    doc = GenesisDoc(
        chain_id="bench-load",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(v.pub_key, v.voting_power)
                    for v in vs.validators],
    )
    db = MemDB()
    state = sm.load_state_from_db_or_genesis(db, doc)
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    mp = Mempool(
        cfg.MempoolConfig(size=50000, lanes=2, preverify_batch=True,
                          ingest_queue_size=50000, recheck=False),
        conns.mempool)
    bus = EventBus()
    bus.start()
    block_exec = sm.BlockExecutor(db, conns.consensus, mempool=mp,
                                  event_bus=bus)
    ccfg = cfg.test_config().consensus
    cs = ConsensusState(
        ccfg, state, block_exec, BlockStore(MemDB()),
        mempool=mp, event_bus=bus, priv_validator=FilePV(vkeys[0], None),
    )
    sub = bus.subscribe("bench-load", query_for_event(EVENT_NEW_BLOCK), 4096)
    cs.start()

    sk = keys.PrivKeyEd25519.generate()
    submit_at = {}
    latencies_ms = []
    committed = set()

    def _drain(timeout):
        msg = sub.get(timeout=timeout)
        if msg is None:
            return
        now = time.perf_counter()
        for tx in msg.data["block"].data.txs:
            k = hashlib.sha256(tx).digest()
            t0 = submit_at.get(k)
            if t0 is not None and k not in committed:
                committed.add(k)
                latencies_ms.append((now - t0) * 1000)

    # pre-generate OUTSIDE the timed window: pure-Python Ed25519
    # signing costs ~ms/tx on fallback-crypto hosts and was previously
    # billed to the submit loop, understating the node's own ceiling
    n_target = LOAD_TPS * LOAD_SECS
    txs = [make_signed_tx(sk, b"bench-load-%08d" % i, priority=i % 2)
           for i in range(n_target)]

    futs = []
    t_start = time.perf_counter()
    for i, tx in enumerate(txs):
        k = hashlib.sha256(tx).digest()
        submit_at[k] = time.perf_counter()
        futs.append(mp.check_tx_nowait(tx))
        # pace to the target, absorbing drain time into the schedule
        next_t = t_start + (i + 1) / LOAD_TPS
        while time.perf_counter() < next_t:
            _drain(timeout=max(0.0, next_t - time.perf_counter()))
    accepted = 0
    for f in futs:
        try:
            if f.result(timeout=30).code == 0:
                accepted += 1
        except Exception:  # noqa: BLE001 - full pool counts as rejected
            pass
    # grace: let the tail commit
    deadline = time.time() + max(10.0, 2 * LOAD_SECS)
    while len(committed) < accepted and time.time() < deadline:
        _drain(timeout=0.25)
    wall_s = time.perf_counter() - t_start

    cs.stop()
    bus.stop()
    mp.stop()
    conns.stop()
    crypto_batch.shutdown_dispatchers()

    lat = sorted(latencies_ms)

    def _pct(p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else -1.0

    accepted_tps = accepted / max(wall_s, 1e-9)
    loop_ms, batch_ms = _socket_deliver_measure()
    print(json.dumps({
        "metric": LOAD_METRIC,
        "value": round(_pct(0.99), 3),
        "unit": "ms",
        "vs_baseline": round(accepted_tps / LOAD_TPS, 2),
        "target_tps": LOAD_TPS,
        "accepted_tps": round(accepted_tps, 1),
        "committed": len(committed),
        "p50_ms": round(_pct(0.50), 3),
        "p99_ms": round(_pct(0.99), 3),
        # the DeliverTx socket-pipelining micro-point (batch-written
        # request frames vs one round trip per tx, same app):
        "socket_deliver_loop_ms": round(loop_ms, 2),
        "socket_deliver_batch_ms": round(batch_ms, 2),
        "socket_deliver_speedup": round(loop_ms / max(batch_ms, 1e-9), 2),
        "note": ("single-validator in-process localnet, batched ingest, "
                 "2 lanes, txs pre-generated outside the timed window; "
                 "vs_baseline = accepted/target TPS"),
    }))
    return 0


def _socket_deliver_measure(n: int = 256):
    """Satellite micro-point: DeliverTx over a REAL ABCI socket, per-tx
    round-trip loop vs the batch-written pipeline (deliver_tx_batch).
    Returns (loop_ms, batch_ms)."""
    from tendermint_tpu.abci.client import SocketClient
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.abci.server import ABCIServer

    srv = ABCIServer("tcp://127.0.0.1:0", KVStoreApplication())
    srv.start()
    try:
        addr = f"tcp://127.0.0.1:{srv.local_port()}"
        txs = [b"sock-%05d=v" % i for i in range(n)]
        c = SocketClient(addr)
        try:
            c.deliver_tx(b"warm=1")
            t0 = time.perf_counter()
            for tx in txs:
                c.deliver_tx(tx)
            loop_ms = (time.perf_counter() - t0) * 1000
            t0 = time.perf_counter()
            c.deliver_tx_batch(txs)
            batch_ms = (time.perf_counter() - t0) * 1000
        finally:
            c.close()
    finally:
        srv.stop()
    return loop_ms, batch_ms


def _exec_load_leg(app_addr: str, exec_cfg, target_tps: int, secs: int,
                   mp_size: int = 200000, conflict_pct: int = 0,
                   hot_keys: int = EXEC_HOT_KEYS):
    """One parallel-exec load leg: a single-validator in-process
    localnet against `app_addr`, plain `k=v` txs (footprints come from
    the app's inference — no signing/verify on the measurement path),
    paced at target_tps for secs. conflict_pct > 0 swaps that share of
    the stream for signed txs with LYING access hints that really
    touch one of `hot_keys` shared keys (alternating writers and
    readers), so the planner schedules them concurrently and the merge
    observes genuine conflicts. Returns a stats dict."""
    import hashlib

    from tendermint_tpu import config as cfg
    from tendermint_tpu import state as sm
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.consensus import ConsensusState
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.mempool import Mempool
    from tendermint_tpu.privval import FilePV
    from tendermint_tpu.proxy import AppConns, default_client_creator
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK, EventBus, query_for_event)
    from tendermint_tpu.types.validator_set import random_validator_set

    crypto_batch.set_default_backend("cpu")
    vs, vkeys = random_validator_set(1, 10)
    doc = GenesisDoc(
        chain_id="bench-exec",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(v.pub_key, v.voting_power)
                    for v in vs.validators],
    )
    db = MemDB()
    state = sm.load_state_from_db_or_genesis(db, doc)
    conns = AppConns(default_client_creator(app_addr))
    conns.start()
    mp = Mempool(
        cfg.MempoolConfig(size=mp_size, lanes=2, preverify_batch=True,
                          ingest_queue_size=mp_size, recheck=False),
        conns.mempool)
    bus = EventBus()
    bus.start()

    class _Ctr:  # counting stub so the leg can report exec counters
        def __init__(self):
            self.value = 0

        def inc(self, n=1):
            self.value += n

        def set(self, v):
            self.value = v

        def observe(self, v):
            pass

    from tendermint_tpu.metrics import StateMetrics
    st_metrics = StateMetrics(
        block_processing_time=_Ctr(), validator_updates=_Ctr(),
        valset_changes=_Ctr(), exec_parallel_lanes=_Ctr(),
        exec_conflicts=_Ctr(), exec_speculation_hits=_Ctr(),
        exec_speculation_wasted=_Ctr())
    # fresh flight-recorder rings so the leg's wakeup percentiles and
    # busy ratios describe THIS leg only (serial legs record nothing —
    # the inline path is not instrumented)
    from tendermint_tpu.state.parallel import get_flight_recorder
    recorder = get_flight_recorder()
    recorder.reset()
    block_exec = sm.BlockExecutor(db, conns.consensus, mempool=mp,
                                  event_bus=bus, exec_config=exec_cfg,
                                  metrics=st_metrics)
    # a real kv tx indexer rides the run so the commit-stage breakdown
    # covers the index stage (block-at-a-time ingest, like a node)
    from tendermint_tpu.state.txindex import IndexerService, KVTxIndexer
    indexer = KVTxIndexer(MemDB())
    indexer_svc = IndexerService(indexer, bus,
                                 stage_profile=block_exec.stage_profile)
    indexer_svc.start()
    ccfg = cfg.test_config().consensus
    cs = ConsensusState(
        ccfg, state, block_exec, BlockStore(MemDB()),
        mempool=mp, event_bus=bus, priv_validator=FilePV(vkeys[0], None),
    )
    sub = bus.subscribe("bench-exec", query_for_event(EVENT_NEW_BLOCK), 4096)
    cs.start()

    n = target_tps * secs
    if conflict_pct > 0:
        from tendermint_tpu.crypto.keys import PrivKeyEd25519
        from tendermint_tpu.mempool.preverify import make_signed_tx
        signer = PrivKeyEd25519.gen_from_secret(b"bench-exec-conflict")
        txs = []
        j = 0  # running conflict-tx index; j//3 numbers the triple
        for i in range(n):
            if i % 100 >= conflict_pct:
                txs.append(b"bench-exec-%08d=v" % i)
                continue
            # conflict triples with LYING hints, all landing in
            # different groups: (A) points p_t at a hot key, (B) an
            # indirect write THROUGH p_t — its re-run retargets to the
            # hot key, a write that only appears on re-execution — and
            # (C) an honest-looking read OF that hot key. On the PR-16
            # engine B's re-run invalidates clean C → whole-block
            # serial fallback; the retry DAG converges in two rounds
            # re-running only the cone.
            t, role = j // 3, j % 3
            hot = b"h%02d" % (t % hot_keys)
            if role == 0:
                txs.append(make_signed_tx(
                    signer, b"p%05d=" % t + hot,
                    hints=[b"kv:a%05d" % t]))
            elif role == 1:
                txs.append(make_signed_tx(
                    signer, b"ind:p%05d:V%05d" % (t, t),
                    hints=[b"kv:b%05d" % t]))
            else:
                txs.append(make_signed_tx(
                    signer, b"cp:" + hot + b":c%05d" % t,
                    hints=[b"kv:c%05d" % t]))
            j += 1
    else:
        txs = [b"bench-exec-%08d=v" % i for i in range(n)]
    submit_at = {}
    latencies_ms = []
    committed = set()
    blocks = [0]

    def _drain(timeout):
        msg = sub.get(timeout=timeout)
        if msg is None:
            return
        blocks[0] += 1
        now = time.perf_counter()
        for tx in msg.data["block"].data.txs:
            k = hashlib.sha256(tx).digest()
            t0 = submit_at.get(k)
            if t0 is not None and k not in committed:
                committed.add(k)
                latencies_ms.append((now - t0) * 1000)

    futs = []
    t_start = time.perf_counter()
    for i, tx in enumerate(txs):
        submit_at[hashlib.sha256(tx).digest()] = time.perf_counter()
        futs.append(mp.check_tx_nowait(tx))
        next_t = t_start + (i + 1) / target_tps
        while time.perf_counter() < next_t:
            _drain(timeout=max(0.0, next_t - time.perf_counter()))
    accepted = 0
    for f in futs:
        try:
            if f.result(timeout=60).code == 0:
                accepted += 1
        except Exception:  # noqa: BLE001 - full pool counts as rejected
            pass
    deadline = time.time() + max(30.0, 6 * secs)
    while len(committed) < accepted and time.time() < deadline:
        _drain(timeout=0.25)
    wall_s = time.perf_counter() - t_start

    cs.stop()
    indexer_svc.stop()
    bus.stop()
    mp.stop()
    conns.stop()
    crypto_batch.shutdown_dispatchers()

    lat = sorted(latencies_ms)

    def _pct(p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else -1.0

    m = block_exec.metrics
    # exec-lane flight-recorder summary for the leg (PR 16): wakeup
    # percentiles across lanes plus per-lane busy ratios. Serial legs
    # report count=0 — the inline path records nothing.
    wake = recorder.wakeup_percentiles()
    disp = recorder.dispatch_percentiles()
    full_report = recorder.report()
    lane_report = full_report["lanes"]
    rstats = recorder.retry_stats()
    return {
        "target_tps": target_tps,
        "accepted": accepted,
        "committed": len(committed),
        "committed_tps": round(len(committed) / max(wall_s, 1e-9), 1),
        "blocks": blocks[0],
        "p50_ms": round(_pct(0.50), 1),
        "p99_ms": round(_pct(0.99), 1),
        "conflict_reruns": m.exec_conflicts.value,
        # observed-conflict rate over the committed stream, plus the
        # PR-17 engine counters (all zero when retry/pool are off)
        "conflict_rate": round(
            m.exec_conflicts.value / max(len(committed), 1), 4),
        "retry_rounds_p99": rstats["retry_rounds_p99"],
        "retried_txs": rstats["retried_txs"],
        "steals": rstats["steals"],
        "steal_ratio": rstats["steal_ratio"],
        "serial_fallbacks": full_report["blocks"]["serial_fallbacks"],
        "speculation_hits": m.exec_speculation_hits.value,
        "speculation_wasted": m.exec_speculation_wasted.value,
        # the commit-path profiler's per-stage breakdown (the PR-13
        # point: the ceiling is attributable, not anecdotal)
        "stages": block_exec.stage_profile.snapshot(),
        "indexed_height": indexer.indexed_height(),
        "lane_wakeup_samples": wake["count"],
        "lane_wakeup_p50_us": round(wake["p50_s"] * 1e6, 3),
        "lane_wakeup_p99_us": round(wake["p99_s"] * 1e6, 3),
        # per-run critical-path lane-launch cost (PR 17): the wall time
        # the submitter spends getting all lanes going — serialized
        # blocking t.start() calls on the spawn engine vs non-blocking
        # pokes on the pool. This is the convoy number the two engines
        # can be compared on; per-lane wakeup samples can't be, because
        # t.start() blocks until the thread runs and so hides the spawn
        # convoy inside the submit loop.
        "dispatch_samples": disp["count"],
        "dispatch_p50_us": round(disp["p50_s"] * 1e6, 3),
        "dispatch_p99_us": round(disp["p99_s"] * 1e6, 3),
        "lane_busy_ratio": {
            lane: rep["busy_ratio"] for lane, rep in lane_report.items()},
    }


def load_parallel_main():
    """`bench.py load --parallel` — the PR-12 tentpole point, extended
    by PR 17: the same sharded kvstore workload (EXEC_IO_US of
    simulated per-tx backend latency) executed serially ([execution]
    defaults — the committed baseline, BENCH_LOAD_SERIAL.json), with
    the PR-16 spawn-per-block engine, and with the PR-17 persistent
    lane pool + retry DAG. Two extra high-conflict legs
    (EXEC_CONFLICT_PCT% of txs carrying lying hints over EXEC_HOT_KEYS
    shared keys) compare the old conflict path (segment re-run /
    whole-block serial fallback) against the conflict-cone retry
    engine. vs_baseline is pooled-parallel/serial committed TPS, both
    measured in THIS run so the ratio is like-for-like on the box."""
    from tendermint_tpu.config import ExecutionConfig

    app = f"sharded_kvstore:shards=64,io_us={EXEC_IO_US}"
    spawn_cfg = dict(parallel_lanes=EXEC_LANES, speculative=True)
    pool_cfg = dict(parallel_lanes=EXEC_LANES, speculative=True,
                    lane_pool=True, retry_max_rounds=EXEC_RETRY_ROUNDS)
    serial = _exec_load_leg(app, ExecutionConfig(), EXEC_SERIAL_TPS,
                            EXEC_SECS)
    spawn = _exec_load_leg(app, ExecutionConfig(**spawn_cfg),
                           EXEC_PAR_TPS, EXEC_SECS)
    pooled = _exec_load_leg(app, ExecutionConfig(**pool_cfg),
                            EXEC_PAR_TPS, EXEC_SECS)
    hc_spawn = _exec_load_leg(app, ExecutionConfig(**spawn_cfg),
                              EXEC_HC_TPS, EXEC_HC_SECS,
                              conflict_pct=EXEC_CONFLICT_PCT)
    hc_retry = _exec_load_leg(app, ExecutionConfig(**pool_cfg),
                              EXEC_HC_TPS, EXEC_HC_SECS,
                              conflict_pct=EXEC_CONFLICT_PCT)
    s_tps = max(serial["committed_tps"], 1e-9)
    print(json.dumps({
        "metric": EXEC_METRIC,
        "value": pooled["committed_tps"],
        "unit": "tps",
        "vs_baseline": round(pooled["committed_tps"] / s_tps, 2),
        # exec-lane flight recorder (PR 16/17): the wakeup convoy is
        # compared on the per-run DISPATCH span — the submitter-side
        # critical path of getting every lane going. On the spawn
        # engine that is n_lanes serialized blocking t.start() calls;
        # on the pool it is the non-blocking per-lane poke loop.
        # (Per-lane wakeup samples are reported per leg but are NOT
        # comparable across engines: t.start() blocks until the new
        # thread runs, so the spawn path's per-thread samples hide the
        # convoy the submit loop pays.)
        "lane_wakeup_p50_us": pooled["lane_wakeup_p50_us"],
        "lane_wakeup_p99_us": pooled["lane_wakeup_p99_us"],
        "lane_wakeup_samples": pooled["lane_wakeup_samples"],
        "dispatch_p99_us": pooled["dispatch_p99_us"],
        "spawn_dispatch_p99_us": spawn["dispatch_p99_us"],
        "wakeup_p99_speedup": round(
            spawn["dispatch_p99_us"]
            / max(pooled["dispatch_p99_us"], 1e-9), 2),
        # PR-17 conflict-path summary (from the retry-DAG high-conflict
        # leg; hc_speedup = retry-DAG tps / PR-16-engine tps on the
        # identical lying-hint stream)
        "conflict_rate": hc_retry["conflict_rate"],
        "retry_rounds_p99": hc_retry["retry_rounds_p99"],
        "steal_ratio": hc_retry["steal_ratio"],
        "hc_speedup": round(
            hc_retry["committed_tps"]
            / max(hc_spawn["committed_tps"], 1e-9), 2),
        "serial": serial,
        "parallel": spawn,
        "pooled": pooled,
        "hc_spawn": hc_spawn,
        "hc_retry": hc_retry,
        "io_us": EXEC_IO_US,
        "lanes": EXEC_LANES,
        "conflict_pct": EXEC_CONFLICT_PCT,
        "hot_keys": EXEC_HOT_KEYS,
        "retry_rounds": EXEC_RETRY_ROUNDS,
        "note": ("single-validator in-process localnet, sharded_kvstore "
                 f"with {EXEC_IO_US}us simulated per-tx backend latency "
                 "(GIL-released stall), plain k=v txs partitioned via "
                 "app footprint inference; serial leg = [execution] "
                 "defaults (the conformance oracle), parallel legs = "
                 f"{EXEC_LANES} lanes + speculative execution, spawn-"
                 "per-block vs persistent work-stealing lane pool + "
                 f"retry DAG; hc_* legs add {EXEC_CONFLICT_PCT}% lying-"
                 f"hint txs over {EXEC_HOT_KEYS} hot keys; vs_baseline "
                 "= pooled/serial committed TPS; wakeup_p99_speedup = "
                 "spawn/pooled per-run lane-launch (dispatch) p99 — "
                 "the submit-side convoy, comparable across engines"),
    }))
    return 0


def rpcload_main():
    """`bench.py rpcload` — RPC serving at fan-out scale: a single-
    validator in-process node answers a concurrent mixed read load
    (status/block/validators) through the serving layer twice — once
    with the height/generation byte cache on, once bypassed — and then
    fans NewBlock events out to RPC_SUBS live websocket subscribers,
    reporting the render-once funnel (renders vs frames delivered).
    Pure host path; the JSON line is the hot-status p50 with
    vs_baseline = uncached_p50 / cached_p50."""
    import tempfile
    import threading

    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu import config as cfg
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.rpc import core as rpc_core
    from tendermint_tpu.rpc.client import WSClient
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK, query_for_event)

    with tempfile.TemporaryDirectory(prefix="bench_rpcload_") as root:
        c = cfg.test_config()
        c.set_root(root)
        c.base.proxy_app = "kvstore"
        c.base.moniker = "bench-rpcload"
        c.rpc.laddr = "tcp://127.0.0.1:0"
        c.rpc.cache_bytes = 32 << 20
        c.rpc.ws_send_queue = 512
        c.p2p.laddr = "tcp://127.0.0.1:0"
        # a slow-ish cadence leaves clear gaps between blocks, so the
        # fan-out phase can align its counting window to the block
        # schedule and compare renders vs deliveries exactly
        c.consensus.create_empty_blocks_interval = 0.6
        cfg.ensure_root(root)
        NodeKey.load_or_gen(c.base.node_key_path())
        pv = load_or_gen_file_pv(c.base.priv_validator_path())
        GenesisDoc(
            chain_id="bench-rpcload",
            genesis_time=time.time_ns() - 10**9,
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        ).save(c.base.genesis_path())
        node = default_new_node(c)
        sub = node.event_bus.subscribe(
            "bench-rpcload", query_for_event(EVENT_NEW_BLOCK), 64)
        node.start()
        try:
            deadline = time.time() + 60
            while node.block_store.height() < 2 and time.time() < deadline:
                sub.get(timeout=0.5)
            if node.block_store.height() < 2:
                raise RuntimeError("node never committed 2 blocks")
            srv = node._rpc_server

            queries = [("status", {}), ("block", {"height": 1}),
                       ("validators", {})]

            def run_load():
                """RPC_QUERIES mixed calls across RPC_THREADS threads
                through the serving layer; returns {method: [ms...]}."""
                lats = {m: [] for m, _ in queries}
                lock = threading.Lock()
                per_thread = RPC_QUERIES // RPC_THREADS

                def worker():
                    local = {m: [] for m, _ in queries}
                    for i in range(per_thread):
                        m, p = queries[i % len(queries)]
                        t0 = time.perf_counter()
                        srv.call_bytes(m, p)
                        local[m].append(
                            (time.perf_counter() - t0) * 1000)
                    with lock:
                        for m in local:
                            lats[m].extend(local[m])

                ts = [threading.Thread(target=worker)
                      for _ in range(RPC_THREADS)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                return lats

            def _pct(samples, p):
                s = sorted(samples)
                return s[min(len(s) - 1, int(p * len(s)))] if s else -1.0

            # warm the cache, then the cached run; then bypass the
            # cache entirely for the baseline (same handlers, full
            # render + encode per call — today's serving path)
            for m, p in queries:
                srv.call_bytes(m, p)
            cached = run_load()
            saved_cache, srv.cache = srv.cache, None
            try:
                uncached = run_load()
            finally:
                srv.cache = saved_cache

            # fan-out: RPC_SUBS real websocket subscribers, NewBlock
            clients = []
            for _ in range(RPC_SUBS):
                w = WSClient(node.rpc_listen_addr)
                w.connect(timeout=10.0)
                w.subscribe("tm.event = 'NewBlock'")
                clients.append(w)

            delivered = {}  # height -> frames read

            def drain_all(record=True) -> int:
                got = 0
                for w in clients:
                    while True:
                        ev = w.next_event(timeout=0)
                        if ev is None:
                            break
                        got += 1
                        if record:
                            try:
                                h = (ev["data"]["value"]["block"]
                                     ["header"]["height"])
                            except (KeyError, TypeError):
                                continue
                            delivered[h] = delivered.get(h, 0) + 1
                return got

            def settle():
                """Align to the block schedule: wait for the next
                NewBlock on the node bus (render + delivery start at
                that instant), give its frames a beat to reach every
                client reader, and drain them — the next block is then
                a comfortable fraction of the 0.6s interval away, so a
                snapshot taken now sits in quiet air with nothing in
                flight between renderer, queues, and clients."""
                while sub.get(timeout=0.0) is not None:
                    pass  # clear bus backlog
                if sub.get(timeout=10.0) is None:
                    raise RuntimeError("chain stopped producing blocks")
                time.sleep(0.2)
                drain_all()

            # discard the connect-phase boundary (clients subscribed
            # at different instants), then count a clean window
            settle()
            delivered.clear()
            renders0 = rpc_core.events_rendered_count()
            t0 = time.perf_counter()
            window_s = 3.0
            end = time.perf_counter() + window_s
            while time.perf_counter() < end:
                drain_all()
                time.sleep(0.02)
            settle()
            renders = rpc_core.events_rendered_count() - renders0
            frames = sum(delivered.values())
            for w in clients:
                w.close()
            fanout_s = time.perf_counter() - t0

            cached_p50 = _pct(cached["status"], 0.50)
            uncached_p50 = _pct(uncached["status"], 0.50)
            print(json.dumps({
                "metric": RPCLOAD_METRIC,
                "value": round(cached_p50, 4),
                "unit": "ms",
                "vs_baseline": round(uncached_p50 / max(cached_p50, 1e-9),
                                     2),
                "status_p50_ms": round(cached_p50, 4),
                "status_p99_ms": round(_pct(cached["status"], 0.99), 4),
                "status_uncached_p50_ms": round(uncached_p50, 4),
                "status_uncached_p99_ms": round(
                    _pct(uncached["status"], 0.99), 4),
                "block_p50_ms": round(_pct(cached["block"], 0.50), 4),
                "block_uncached_p50_ms": round(
                    _pct(uncached["block"], 0.50), 4),
                "validators_p50_ms": round(
                    _pct(cached["validators"], 0.50), 4),
                "validators_uncached_p50_ms": round(
                    _pct(uncached["validators"], 0.50), 4),
                "cache_hit_rate": srv.cache.stats()["hit_rate"],
                "subscribers": RPC_SUBS,
                "fanout_events": len(delivered),
                "fanout_renders": renders,
                "fanout_frames_delivered": frames,
                "renders_per_event": round(
                    renders / max(len(delivered), 1), 2),
                "fanout_window_s": round(fanout_s, 2),
                "note": ("in-process node; mixed status/block/validators"
                         f" x{RPC_QUERIES} over {RPC_THREADS} threads, "
                         "cached (pre-encoded bytes) vs uncached "
                         "(handler+encode); render-once websocket "
                         "fan-out — renders advance per event, frames "
                         "per (event x subscriber)"),
            }))
        finally:
            node.stop()
    return 0


def aggverify_main():
    """`bench.py aggverify` — the aggregate-signature fast lane: ONE
    BLS commit certificate (signer bitmap + 96-byte aggregate) verified
    with one pubkey aggregation + one 2-pairing product check, against
    the Ed25519 batch path (verify_commit over N per-vote signatures)
    at the same committee size. cpu backend forced (pure host path —
    a host mode never initialises a device); the BLS pubkey
    MSM runs the registry default (python unless TM_TPU_BLS_MSM=jax).

    Fixture note: the BLS committee uses consecutive secret scalars so
    the 10k pubkeys come from incremental generator additions, and the
    aggregate signature is [sum sk_i] H(m) — mathematically identical
    to aggregating per-validator signatures, without 10k G2 scalar
    multiplications of fixture setup."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import bls
    from tendermint_tpu.crypto.bls import curve as bc
    from tendermint_tpu.crypto.bls.fields import R_ORDER
    from tendermint_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from tendermint_tpu.libs.bit_array import BitArray
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.basic import PartSetHeader
    from tendermint_tpu.types.block import AggregateCommit
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    crypto_batch.set_default_backend("cpu")
    crypto_batch.set_sig_cache(None)  # the certificate never hits the
    # sig cache anyway; the ed25519 baseline must not either
    chain = "bench-aggverify"
    nval = AGG_NVAL
    bid = BlockID(b"\x07" * 20, PartSetHeader(1, b"\x0c" * 20))

    # --- BLS committee: pk_i = [s0 + i] G1, built incrementally -------
    s0 = 7_777_777
    pt = bc.g1_mul(bc.G1_GEN, s0)
    jac_points = []
    for _ in range(nval):
        jac_points.append(pt)
        pt = bc.g1_add(pt, bc.G1_GEN)
    # batch-normalize via one shared inversion chain (affine pubkeys)
    from tendermint_tpu.crypto.bls.fields import P as _P, fp_inv

    zs = [p[2] for p in jac_points]
    prefix, acc = [], 1
    for z in zs:
        prefix.append(acc)
        acc = acc * z % _P
    inv = fp_inv(acc)
    pubs = [None] * nval
    for i in range(nval - 1, -1, -1):
        zi = inv * prefix[i] % _P
        inv = inv * zs[i] % _P
        zi2 = zi * zi % _P
        X, Y, _ = jac_points[i]
        pubs[i] = bls.PubKeyBLS12381(
            bc.g1_compress((X * zi2 % _P, Y * zi2 * zi % _P, 1)))
    vals_bls = ValidatorSet([Validator.new(pk, 10) for pk in pubs])

    signers = BitArray(nval)
    for i in range(nval):
        signers.set_index(i, True)
    cert = AggregateCommit(block_id=bid, agg_height=1, agg_round=0,
                           signers=signers, agg_sig=b"\x00" * 96)
    sum_sk = sum(s0 + i for i in range(nval)) % R_ORDER
    hm = hash_to_g2(cert.sign_bytes(chain), bls.DST_SIG)
    cert.agg_sig = bc.g2_compress(bc.g2_mul(hm, sum_sk))

    def bls_run():
        vals_bls.verify_commit(chain, bid, 1, cert)

    # --- Ed25519 baseline: the existing batch path, same size ---------
    vs_ed, sorted_sks = _build_valset(nval, b"agg-ed")
    commit_ed = _build_commit(chain, vs_ed, sorted_sks, 1, bid)

    def ed_run():
        vs_ed.verify_commit(chain, bid, 1, commit_ed)

    bls_run()  # warm (point parse caches)
    bls_ms = _best_of(bls_run, 3)
    ed_ms = _best_of(ed_run, 2)

    cert_bytes = cert.size_bytes()
    print(json.dumps({
        "metric": AGG_METRIC,
        "value": round(bls_ms, 3),
        "unit": "ms",
        "vs_baseline": round(ed_ms / bls_ms, 2),
        "ed25519_batch_ms": round(ed_ms, 3),
        "cert_bytes": cert_bytes,
        "signature_bytes_ed25519": 64 * nval,
        "msm_backend": __import__(
            "tendermint_tpu.crypto.bls.msm", fromlist=["msm"]
        ).default_msm_backend(),
        "note": ("one fast_aggregate_verify (bitmap MSM + 2-pairing "
                 "check) vs verify_commit over %d per-vote Ed25519 "
                 "signatures; cpu backend forced" % nval),
    }))
    return 0


def handel_main():
    """`bench.py handel` — the Handel aggregation overlay vs the flat
    per-vote lane at committee size HANDEL_NVAL (default 1024): run the
    REAL per-session state machine for every committee member (actual
    binomial-tree routing, windowed sends, wire-encoded contribution
    messages, real G2 aggregation) and count what one node pays to
    assemble a full-committee certificate.

    The verify_fn is a counting stub — per-item pairing work is what
    the mode MEASURES, and correctness is enforced end-to-end by the
    oracle instead: every session's final certificate must byte-equal
    the flat-lane aggregate [sum sk_i]H(m) for the same vote set, or
    the metric value is -1. Signature fixtures use consecutive scalars
    (sig_i = [s0+i]H(m), built by incremental G2 adds) so setup stays
    O(n) adds instead of n scalar multiplications."""
    from tendermint_tpu.consensus.handel import HandelSession, num_levels
    from tendermint_tpu.consensus.messages import (
        HandelContributionMessage,
        VoteMessage,
    )
    from tendermint_tpu.consensus.reactor import encode_msg
    from tendermint_tpu.crypto import bls
    from tendermint_tpu.crypto.bls import curve as bc
    from tendermint_tpu.crypto.bls.fields import R_ORDER
    from tendermint_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from tendermint_tpu.types.basic import (
        VOTE_TYPE_PRECOMMIT,
        BlockID,
        PartSetHeader,
        Vote,
        canonical_vote_sign_bytes,
    )

    n = HANDEL_NVAL
    chain = "bench-handel"
    bid = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x0c" * 32))
    msg = canonical_vote_sign_bytes(
        chain, VOTE_TYPE_PRECOMMIT, 1, 0, bid, 0)
    hm = hash_to_g2(msg, bls.DST_SIG)

    # per-validator precommit signatures sig_i = [s0+i] H(m)
    s0 = 424_242
    pts, pt = [], bc.g2_mul(hm, s0)
    for _ in range(n):
        pts.append(pt)
        pt = bc.g2_add(pt, hm)
    sigs = [bc.g2_compress(p) for p in pts]
    # flat-lane reference certificate over the same vote set
    sum_sk = sum(s0 + i for i in range(n)) % R_ORDER
    flat_cert = bc.g2_compress(bc.g2_mul(hm, sum_sk))

    counters = {"calls": 0, "items": 0}

    def verify_fn(items):
        counters["calls"] += 1
        counters["items"] += len(items)
        return [True] * len(items)

    t0 = time.perf_counter()
    sessions = [
        HandelSession(
            n, i, [1] * n, sigs[i], verify_fn=verify_fn,
            parse_fn=bls._parse_signature_point, add_fn=bc.g2_add,
            compress_fn=bc.g2_compress, seed=1, height=1, round_=0,
            window=4, level_timeout_s=1e9, resend_ticks=2,
            reshuffle_ticks=8)
        for i in range(n)
    ]
    sent_bytes = 0
    inboxes = [[] for _ in range(n)]
    certs = {}
    now = 0.0
    rounds = 0
    max_rounds = 6 * num_levels(n) + 8
    while rounds < max_rounds:
        rounds += 1
        now += 0.05
        for i, s in enumerate(sessions):
            for target, level, bits, sig in s.tick(now):
                sent_bytes += len(encode_msg(HandelContributionMessage(
                    1, 0, level, i, bid, bits, sig)))
                inboxes[target].append((i, level, bits, sig))
        for i, s in enumerate(sessions):
            if inboxes[i]:
                s.add_contributions(inboxes[i], now)
                inboxes[i] = []
            c = s.take_certificate()
            if c is not None:
                certs[i] = c
        if len(certs) == n and all(
                b.num_true() == n for b, _ in certs.values()):
            break
    wall_ms = (time.perf_counter() - t0) * 1000

    byte_equal = len(certs) == n and all(
        bits.num_true() == n and sig == flat_cert
        for bits, sig in certs.values())

    # per-node accounting. Overlay: measured from the run (verify items
    # feed ONE multi-pair check per absorb batch -> items + calls
    # Miller loops). Flat lane: every node verifies n-1 individual
    # precommits (2 pairings each) and receives n-1 wire votes.
    ov_verify = counters["items"] / n
    ov_pairings = (counters["items"] + counters["calls"]) / n
    ov_bytes = sent_bytes / n
    flat_verify = n - 1
    flat_pairings = 2 * (n - 1)
    vote_wire = len(encode_msg(VoteMessage(Vote(
        b"\x01" * 20, 0, 1, 0, 0, VOTE_TYPE_PRECOMMIT, bid, sigs[0]))))
    flat_bytes = (n - 1) * vote_wire

    print(json.dumps({
        "metric": HANDEL_METRIC,
        "value": round(ov_verify, 2) if byte_equal else -1,
        "unit": "aggregate verifications/node/round",
        "oracle_cert_byte_equal": byte_equal,
        "converged_sessions": len(certs),
        "rounds": rounds,
        "wall_ms": round(wall_ms, 1),
        "flat_verify_ops": flat_verify,
        "verify_ops_ratio": round(flat_verify / max(ov_verify, 1e-9), 1),
        "overlay_pairings": round(ov_pairings, 2),
        "flat_pairings": flat_pairings,
        "pairings_ratio": round(flat_pairings / max(ov_pairings, 1e-9), 1),
        "overlay_gossip_bytes": round(ov_bytes),
        "flat_gossip_bytes": flat_bytes,
        "gossip_bytes_ratio": round(flat_bytes / max(ov_bytes, 1e-9), 1),
        "note": ("%d real HandelSessions to full-committee certificate; "
                 "flat lane = n-1 per-vote verifies (2 pairings each) + "
                 "n-1 wire votes (%dB each) per node; value -1 unless "
                 "every overlay certificate byte-equals the flat "
                 "aggregate" % (n, vote_wire)),
    }))

    # -- satellite line: verify_aggregates_many batching at k=8 --------
    # (the Handel level-verify workhorse: one 2k-pair Miller loop vs k
    # sequential fast_aggregate_verify calls, REAL pairings both ways)
    k, m = 8, 8
    t0sk = 31_337
    g1pts, gp = [], bc.g1_mul(bc.G1_GEN, t0sk)
    for _ in range(m):
        g1pts.append(gp)
        gp = bc.g1_add(gp, bc.G1_GEN)
    pks = [bc.g1_compress(p) for p in g1pts]
    sum_pk_sk = sum(t0sk + i for i in range(m)) % R_ORDER
    items = []
    for j in range(k):
        mj = b"bench-handel-batch-%d" % j
        sj = bc.g2_compress(bc.g2_mul(
            hash_to_g2(mj, bls.DST_SIG), sum_pk_sk))
        items.append((pks, mj, sj))

    def batched():
        assert all(bls.verify_aggregates_many(items))

    def sequential():
        for pk_list, mj, sj in items:
            assert bls.fast_aggregate_verify(
                pk_list, mj, sj, require_pop=False)

    batched()  # warm point-parse caches for both paths
    batch_ms = _best_of(batched, 3)
    seq_ms = _best_of(sequential, 3)
    print(json.dumps({
        "metric": f"verify_aggregates_many_k{k}_wall_ms",
        "value": round(batch_ms, 3),
        "unit": "ms",
        "vs_baseline": round(seq_ms / batch_ms, 2),
        "sequential_ms": round(seq_ms, 3),
        "note": (f"{k} aggregate certificates ({m} signers each) in one "
                 "RLC multi-pair check vs sequential 2-pairing "
                 "fast_aggregate_verify calls"),
    }))
    return 0 if byte_equal else 1


def chaos_main():
    """`bench.py chaos` — ABCI reconnect recovery latency: a real
    kvstore socket app, a ResilientClient(retry) supervising the
    connection, and a ChaosClient injecting a hard disconnect each
    round. Measures wall from the failed in-flight call to the first
    call served on the redialed connection (the window in which a
    mempool/query conn fails soft). Pure host path: no TPU."""
    import threading

    from tendermint_tpu.abci import types as abci_types
    from tendermint_tpu.abci.chaos import ChaosClient, ChaosRule
    from tendermint_tpu.abci.client import ABCIClientError, SocketClient
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.abci.server import ABCIServer
    from tendermint_tpu.proxy.resilient import ResilientClient

    srv = ABCIServer("tcp://127.0.0.1:0", KVStoreApplication())
    srv.start()
    addr = f"tcp://127.0.0.1:{srv.local_port()}"

    chaos_handle = []

    def creator():
        c = ChaosClient(SocketClient(addr, request_timeout=2.0), seed=7)
        chaos_handle.append(c)
        return c

    client = ResilientClient(
        "bench", creator, policy="retry",
        backoff_base_s=0.005, backoff_max_s=0.05, retry_budget=5)
    client.start()

    recoveries_ms = []
    try:
        for round_i in range(CHAOS_ROUNDS):
            # healthy steady state
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    client.check_tx(b"k%d=v" % round_i)
                    break
                except ABCIClientError:
                    time.sleep(0.002)
            else:
                raise RuntimeError("conn never became healthy")
            # one-shot hard disconnect on the CURRENT transport
            chaos_handle[-1].rules.append(
                ChaosRule("disconnect", methods=("echo",), max_fires=1))
            t0 = time.perf_counter()
            try:
                client.echo("boom")
            except ABCIClientError:
                pass  # the in-flight call fails soft by design
            while True:
                try:
                    client.echo("recovered?")
                    break
                except ABCIClientError:
                    time.sleep(0.001)
            recoveries_ms.append((time.perf_counter() - t0) * 1000)
    finally:
        client.close()
        srv.stop()

    mean_ms = sum(recoveries_ms) / len(recoveries_ms)
    print(json.dumps({
        "metric": CHAOS_METRIC,
        "value": round(mean_ms, 3),
        "unit": "ms",
        "vs_baseline": 1.0,
        "note": ("mean wall from injected disconnect to first call on "
                 "the redialed conn; best %.3f worst %.3f over %d rounds"
                 % (min(recoveries_ms), max(recoveries_ms),
                    len(recoveries_ms))),
        "reconnects": client.reconnects,
    }))
    return 0


def chaosnet_main():
    """`bench.py chaosnet` — network-partition recovery latency: the
    partition_heal scenario (tools/scenarios.py) on an in-process
    localnet, reporting wall ms from fault removal to the first NEW
    height committed and agreed by every node. Pure host path: no TPU.
    The scenario's oracle gates the number: a run that fails to
    converge, violates safety, or misclassifies its stall emits
    value -1 instead of a fake latency."""
    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu.tools import scenarios

    res = scenarios.run("partition_heal", seed=CHAOSNET_SEED,
                        n=CHAOSNET_NVAL)
    ok = bool(res.get("ok"))
    recovery_ms = (round(res["recovery_s"] * 1000, 1)
                   if ok and res.get("recovery_s") is not None else -1)
    print(json.dumps({
        "metric": CHAOSNET_METRIC,
        "value": recovery_ms,
        "unit": "ms",
        "vs_baseline": 1.0,
        "seed": CHAOSNET_SEED,
        "converged": res.get("converged"),
        "safety_ok": res.get("safety_ok"),
        "classified_ok": res.get("classified_ok"),
        "stall_reasons": sorted(set(res.get("stall_reasons", []))),
        "note": ("wall from partition heal to first new agreed height; "
                 "fault timeline replayable from seed "
                 f"{CHAOSNET_SEED} (netchaos FaultPlan)"),
    }))
    return 0 if ok else 1


def crashrecovery_main():
    """`bench.py crashrecovery` — kill -> recovered-and-committing
    latency: the crash-matrix harness (tools/crashmatrix.py) warms a
    FileDB-backed single-validator node, kills it in-process at
    ApplyBlock.AfterCommit (app committed, chain state unsaved — the
    stored-responses handshake path, the most intricate replay case),
    restarts from disk, and measures wall from the kill to the first
    NEW committed block. The recovery oracle gates the number: any
    failing clause (handshake, double-sign guard, index convergence,
    app-hash-vs-uncrashed-replay) emits value -1 instead of a fake
    latency. Pure host path: no TPU."""
    import shutil
    import tempfile

    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu.tools import crashmatrix

    root = tempfile.mkdtemp(prefix="bench_crashrec_")
    recoveries_ms = []
    oracle_ok = True
    results = []
    try:
        for i in range(CRASHREC_ROUNDS):
            # one matrix cell per round: run_case owns the warm/kill/
            # restart sequence AND the full recovery oracle (handshake,
            # progression, double-sign guard vs the release ledger,
            # index convergence, app-hash-vs-uncrashed-replay), so the
            # published latency can never outlive the oracle's rigor
            res = crashmatrix.run_case(
                os.path.join(root, f"round{i}"),
                "ApplyBlock.AfterCommit", mode="clean", nth=1,
                timeout=60)
            ok = bool(res.get("ok"))
            oracle_ok = oracle_ok and ok
            if ok and res.get("recommit_s"):
                recoveries_ms.append(res["recommit_s"] * 1000)
            results.append({"round": i,
                            "crash_height": res.get("crash_height"),
                            "oracle_ok": ok})
    finally:
        shutil.rmtree(root, ignore_errors=True)

    mean_ms = (sum(recoveries_ms) / len(recoveries_ms)
               if recoveries_ms else -1)
    print(json.dumps({
        "metric": CRASHREC_METRIC,
        "value": round(mean_ms, 1) if oracle_ok and recoveries_ms else -1,
        "unit": "ms",
        "vs_baseline": 1.0,
        "rounds": results,
        "note": ("wall from in-process kill at ApplyBlock.AfterCommit "
                 "to the first NEW committed block after restart; "
                 "best %.1f worst %.1f over %d rounds"
                 % (min(recoveries_ms), max(recoveries_ms),
                    len(recoveries_ms))) if recoveries_ms else
                "no recovery completed",
    }))
    return 0 if oracle_ok else 1


def detcheck_main():
    """`bench.py detcheck` — the replay-divergence oracle as a gated
    BENCH line: the churn+sharded workload executed under serial,
    parallel(2), parallel(4), speculative, and two cross-PYTHONHASHSEED
    subprocess engines, every consensus-visible surface (app hashes,
    DeliverTx results, event stream, tx-index rows, durable FileDB
    image) diffed byte-for-byte. Any divergence gates the metric to -1:
    a wall time is only worth publishing for a matrix that agrees.
    Pure host path: no TPU."""
    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu.tools import detcheck

    t0 = time.perf_counter()
    rep = detcheck.run_oracle(n_blocks=DETCHECK_BLOCKS)
    wall_ms = (time.perf_counter() - t0) * 1000
    ok = not rep["divergences"]
    print(json.dumps({
        "metric": DETCHECK_METRIC,
        "value": round(wall_ms, 1) if ok else -1,
        "unit": "ms",
        "vs_baseline": 1.0 if ok else 0.0,
        "engines": rep["engines"],
        "divergences": rep["divergences"],
        "app_hash": rep["app_hash"][:16],
        "note": ("serial==parallel(2,4)==speculative==cross-hashseed "
                 "subprocesses on app_hashes/results/events/index/image"
                 if ok else "DIVERGENT — see divergences"),
    }))
    return 0 if ok else 1


def proptrace_main():
    """`bench.py proptrace` — fleet causal tracing as a gated BENCH
    line: the proptrace scenario (tools/scenarios.py) runs a 4-node
    in-process localnet with ±0.5s synthetic clock skew, probes each
    node's /debug/clock over real HTTP (NTP-style min-RTT offset
    estimation), stitches per-height propagation trees and the
    proposal→commit stage waterfall from the nodes' rebased timelines,
    and reports the MINIMUM attributed-coverage fraction across the
    traced heights as a percentage. The scenario's oracle gates the
    number: offsets recovered worse than the tolerance, missing
    heights, or coverage under 95% emit value -1. Pure host path:
    no TPU."""
    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu.tools import scenarios

    res = scenarios.run("proptrace", seed=PROPTRACE_SEED,
                        n=PROPTRACE_NVAL)
    ok = bool(res.get("ok"))
    coverage_min = res.get("coverage_min")
    value = (round(coverage_min * 100, 2)
             if ok and coverage_min is not None else -1)
    print(json.dumps({
        "metric": PROPTRACE_METRIC,
        "value": value,
        "unit": "pct",
        "vs_baseline": 1.0 if ok else 0.0,
        "seed": PROPTRACE_SEED,
        "offset_error_ms": res.get("offset_error_ms"),
        "offset_tol_ms": res.get("offset_tol_ms"),
        "offsets_ok": res.get("offsets_ok"),
        "coverages": res.get("coverages"),
        "coverage_ok": res.get("coverage_ok"),
        "stitched_heights": res.get("stitched_heights"),
        "max_hop": res.get("max_hop"),
        "converged": res.get("converged"),
        "safety_ok": res.get("safety_ok"),
        "note": ("min share of proposal->commit wall attributed to a "
                 "named waterfall stage across traced heights; clock "
                 "offsets recovered via /debug/clock min-RTT probes "
                 "against ±0.5s synthetic skew"
                 if ok else "ORACLE FAILED — see offsets/coverages"),
    }))
    return 0 if ok else 1


def incident_main():
    """`bench.py incident` — the incident observatory as a gated BENCH
    line: the incident scenario (tools/scenarios.py) composes a seeded
    netchaos partition with a seeded torn-WAL crash on a 4-node
    subprocess localnet, scrapes every node's /debug/incidents, stitches
    the fleet incident report (tools/fleettrace.py) with the
    orchestrator's kill stamp merged in, and reports the p50 MTTR
    (heal -> first fresh-height commit) in ms, with p50 MTTD alongside.
    The scenario's oracle gates the number: every injected phase must be
    detected AND classified correctly (partition stall reasons for the
    net phase, unclean_shutdown for the crash), zero double-commits, and
    each survivor's seeded ledger projection byte-identical to the
    plan-derived prediction — otherwise value -1. Pure host path:
    no TPU."""
    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu.tools import scenarios

    res = scenarios.run("incident", seed=INCIDENT_SEED, n=INCIDENT_NVAL)
    ok = bool(res.get("ok"))
    mttr_p50 = res.get("mttr_p50_s")
    mttd_p50 = res.get("mttd_p50_s")
    value = (round(mttr_p50 * 1000, 1)
             if ok and mttr_p50 is not None else -1)
    print(json.dumps({
        "metric": INCIDENT_METRIC,
        "value": value,
        "unit": "ms",
        "vs_baseline": 1.0 if ok else 0.0,
        "seed": INCIDENT_SEED,
        "mttd_p50_ms": (round(mttd_p50 * 1000, 1)
                        if mttd_p50 is not None else -1),
        "total_phases": res.get("total_phases"),
        "attribution": res.get("attribution"),
        "replay_identical": res.get("replay_identical"),
        "safety_ok": res.get("safety_ok"),
        "classified_ok": res.get("classified_ok"),
        "recovered_ok": res.get("recovered_ok"),
        "note": ("p50 heal->fresh-commit MTTR across a composed "
                 "partition + torn-WAL timeline; fault ledger "
                 f"replayable from seed {INCIDENT_SEED} "
                 "(canonical projection byte-checked per survivor)"
                 if ok else "ORACLE FAILED — see attribution/replay"),
    }))
    return 0 if ok else 1


def fleet_main():
    """`bench.py fleet` — the replica fan-out tree as a serving
    benchmark: FLEET_REPLICAS in-process replicas tier up behind ONE
    validator ([replica] prefer_replicas: deeper replicas tail other
    replicas, never the validator), then FLEET_CLIENTS round-robin
    clients hammer the replicas' RPC serving layer for FLEET_SECS while
    the tree keeps tailing live blocks. The BENCH value is the hot
    /status p50 across the round-robin load; the oracle gates it on
    ZERO stale tips (every replica within lag_budget_blocks of the
    validator tip at the end), every replica parented, and the
    validator carrying only O(fan-in) peer connections — the point of
    the tree. Pure host path: no TPU."""
    import tempfile
    import threading

    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    from tendermint_tpu import config as cfg
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    n = max(2, FLEET_REPLICAS)
    tier1_n = min(2, n)

    def _mk_config(root, name, mode):
        c = cfg.test_config()
        c.set_root(os.path.join(root, name))
        c.base.proxy_app = "kvstore"
        c.base.moniker = name
        c.base.mode = mode
        c.rpc.laddr = "tcp://127.0.0.1:0"
        c.p2p.laddr = "tcp://127.0.0.1:0"
        c.p2p.pex = False
        c.consensus.create_empty_blocks_interval = 0.5
        c.statesync.enable = False
        c.statesync.snapshot_interval = 0
        c.replica.prefer_replicas = True
        c.replica.lag_budget_blocks = 8
        c.replica.silence_budget_s = 5.0
        cfg.ensure_root(c.root_dir)
        NodeKey.load_or_gen(c.base.node_key_path())
        return c

    started = []
    with tempfile.TemporaryDirectory(prefix="bench_fleet_") as root:
        vc = _mk_config(root, "fleet-val", "full")
        pv = load_or_gen_file_pv(vc.base.priv_validator_path())
        genesis = GenesisDoc(
            chain_id="bench-fleet",
            genesis_time=time.time_ns() - 10**9,
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        )
        genesis.save(vc.base.genesis_path())
        validator = default_new_node(vc)
        validator.start()
        started.append(validator)
        try:
            deadline = time.time() + 60
            while validator.block_store.height() < 2 \
                    and time.time() < deadline:
                time.sleep(0.1)
            if validator.block_store.height() < 2:
                raise RuntimeError("validator never warmed")
            val_peer = (f"{validator.node_key.id}@"
                        f"{validator.transport.listen_addr}")

            # tier-1 replicas dial the validator; deeper replicas dial
            # ONLY the tier-1 replicas (prefer_replicas then keeps them
            # parented inside the tree)
            replicas = []
            for i in range(n):
                c = _mk_config(root, f"fleet-rep{i}", "replica")
                load_or_gen_file_pv(c.base.priv_validator_path())
                genesis.save(c.base.genesis_path())
                if i < tier1_n:
                    c.p2p.persistent_peers = val_peer
                else:
                    c.p2p.persistent_peers = ",".join(
                        f"{r.node_key.id}@{r.transport.listen_addr}"
                        for r in replicas[:tier1_n])
                node = default_new_node(c)
                node.start()
                started.append(node)
                replicas.append(node)

            # the tree settles: every replica parented + tailing near
            # the validator tip
            deadline = time.time() + 90
            settled = False
            while time.time() < deadline:
                sts = [r.replica_tree.status() for r in replicas]
                vh = validator.block_store.height()
                if (all(not s["orphaned"] for s in sts)
                        and all(vh - r.block_store.height() <= 3
                                for r in replicas)):
                    settled = True
                    break
                time.sleep(0.2)
            if not settled:
                raise RuntimeError(
                    "fleet tree never settled: " + json.dumps(
                        [{"parent": s["parent"][:8],
                          "lag": s["lag_blocks"]}
                         for s in (r.replica_tree.status()
                                   for r in replicas)]))

            # round-robin read load across the replicas' serving layers
            servers = [r._rpc_server for r in replicas]
            lats = []
            lock = threading.Lock()
            stop_at = time.time() + FLEET_SECS

            def client(k):
                local = []
                j = k
                while time.time() < stop_at:
                    t0 = time.perf_counter()
                    servers[j % len(servers)].call_bytes("status", {})
                    local.append((time.perf_counter() - t0) * 1000)
                    servers[(j + 1) % len(servers)].call_bytes(
                        "block", {"height": 1})
                    j += 1
                with lock:
                    lats.extend(local)

            ts = [threading.Thread(target=client, args=(k,))
                  for k in range(FLEET_CLIENTS)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

            sts = [r.replica_tree.status() for r in replicas]
            budget = sts[0]["lag_budget_blocks"]
            vh = validator.block_store.height()
            lags = [max(0, vh - r.block_store.height())
                    for r in replicas]
            stale = sum(1 for lag in lags if lag > budget)
            orphans = sum(1 for s in sts if s["orphaned"])
            out_p, in_p, _ = validator.sw.num_peers()
            val_conns = out_p + in_p
            # per-node subscriber ceiling: children each upstream serves
            children = {r.node_key.id: 0 for r in replicas}
            children[validator.node_key.id] = 0
            for s in sts:
                if s["parent"] in children:
                    children[s["parent"]] += 1
            max_children = max(children.values())
            depths = [s["depth"] for s in sts]

            s_lats = sorted(lats)
            p50 = s_lats[len(s_lats) // 2] if s_lats else -1.0
            p99 = (s_lats[min(len(s_lats) - 1, int(0.99 * len(s_lats)))]
                   if s_lats else -1.0)
            ok = bool(stale == 0 and orphans == 0 and s_lats
                      and val_conns <= tier1_n
                      and (n <= tier1_n or max(depths) >= 2))
            _emit({
                "metric": FLEET_METRIC,
                "value": round(p50, 3) if ok else -1,
                "unit": "ms",
                "vs_baseline": 1.0 if ok else 0.0,
                "p99_ms": round(p99, 3),
                "queries": 2 * len(lats),
                "qps": round(2 * len(lats) / FLEET_SECS, 1),
                "replicas": n,
                "clients": FLEET_CLIENTS,
                "depths": depths,
                "validator_conns": val_conns,
                "tier1": tier1_n,
                "max_children": max_children,
                "lag_blocks": lags,
                "lag_budget_blocks": budget,
                "stale_tips": stale,
                "orphaned": orphans,
                "note": ("hot /status p50 over a round-robin read load "
                         f"across {n} tree replicas; validator serves "
                         f"{val_conns} conns (O(fan-in), not O(N))"
                         if ok else "ORACLE FAILED — see stale_tips/"
                                    "orphaned/validator_conns"),
            })
            return 0 if ok else 1
        finally:
            for node in reversed(started):
                try:
                    node.stop()
                except Exception:
                    pass


def main():
    n = METRIC_N
    if COMMIT4_MODE:
        # pure host path: never touches the device
        return commit4_main()
    if DETCHECK_MODE:
        # in-process + subprocess oracle: pure host path
        return detcheck_main()
    if PROPTRACE_MODE:
        # in-process localnet + loopback HTTP: pure host path, no TPU
        return proptrace_main()
    if INCIDENT_MODE:
        # subprocess localnet + loopback HTTP: pure host path, no TPU
        return incident_main()
    if CHAOS_MODE:
        return chaos_main()
    if CHAOSNET_MODE:
        # in-process localnet: pure host path
        return chaosnet_main()
    if CRASHREC_MODE:
        # crash-matrix harness: pure host path
        return crashrecovery_main()
    if LOAD_MODE:
        if PARALLEL_FLAG:
            return load_parallel_main()
        return load_main()
    if PREVERIFY_MODE:
        return preverify_main()
    if AGGVERIFY_MODE:
        # pure host path like commit4/preverify
        return aggverify_main()
    if HANDEL_MODE:
        # in-process overlay simulation: pure host path
        return handel_main()
    if FLEET_MODE:
        # in-process replica tree + serving layer: pure host, no TPU
        return fleet_main()
    if RPCLOAD_MODE:
        # pure host serving path
        return rpcload_main()
    # everything below measures the accelerator
    _require_chip()
    if VOTES_MODE:
        return votes_main()
    if FASTSYNC_MODE:
        return fastsync_main()
    if CACHE_MODE:
        return cache_main()
    if STATESYNC_MODE:
        return statesync_main()

    from tendermint_tpu.crypto import keys
    from tendermint_tpu.crypto.jaxed25519.verify import verify_batch

    # build a synthetic 10k-validator commit: distinct keys, vote-sized
    # messages (~110B canonical sign-bytes), ~1% corrupted signatures
    sks = [keys.PrivKeyEd25519.generate() for _ in range(min(n, 2000))]
    msgs, sigs, pks, want = [], [], [], []
    for i in range(n):
        sk = sks[i % len(sks)]
        msg = secrets.token_bytes(110)
        sig = sk.sign(msg)
        if i % 100 == 37:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
            want.append(False)
        else:
            want.append(True)
        msgs.append(msg)
        sigs.append(sig)
        pks.append(sk.pub_key().bytes())

    # serial CPU baseline (subset of 300, extrapolated)
    sub = min(300, n)
    t0 = time.perf_counter()
    for i in range(sub):
        keys.PubKeyEd25519(pks[i]).verify_bytes(msgs[i], sigs[i])
    serial_ms = (time.perf_counter() - t0) / sub * n * 1000

    # batch path: one warmup (compile; persistent cache warms later runs),
    # then timed runs of the COMPLETE verify
    got = verify_batch(msgs, sigs, pks)
    assert got == want, "batch verify mask mismatch vs expected"
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        verify_batch(msgs, sigs, pks)
        times.append((time.perf_counter() - t0) * 1000)
    batch_ms = min(times)

    _emit({
        "metric": f"verify_commit_{n}_sigs_wall_ms",
        "value": round(batch_ms, 3),
        "unit": "ms",
        "vs_baseline": round(serial_ms / batch_ms, 2),
        # device_ms = slope over back-to-back dispatches on resident data
        # (host clock; the profiler-trace number is ROADMAP S0's)
        "device_ms": round(_device_ms(msgs, sigs, pks), 1),
    })


def _device_ms(msgs, sigs, pks, k: int = 6) -> float:
    """Device-only time of the verify kernel: slope of k back-to-back
    dispatches on resident data (removes dispatch latency + transfer)."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto.jaxed25519 import verify as V

    n = len(msgs)
    sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
    pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32)
    buf, nb, mrows, bpad = V.pack_buffer(msgs, sig_arr, pk_arr, 1)
    fn = V._jitted_packed(nb, mrows, bpad, 1)
    d = jax.device_put(buf)

    def run(reps):
        out = None
        for _ in range(reps):
            out = fn(d)
        np.asarray(out)

    run(1)
    t0 = time.perf_counter(); run(1); t1 = time.perf_counter() - t0
    t0 = time.perf_counter(); run(k); tk = time.perf_counter() - t0
    return (tk - t1) / (k - 1) * 1000


if __name__ == "__main__":
    sys.exit(main())
