"""Per-subsystem metrics (reference consensus/metrics.go,
p2p/metrics.go, mempool/metrics.go, state/metrics.go; wired by the
MetricsProvider in node/node.go:100-113).

`prometheus_metrics(namespace)` builds live metric sets over one
Registry; `nop_metrics()` builds no-op sets (NopMetrics in each
reference metrics.go) so instrumented code never branches.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .libs.metrics import ProcessCPU, Registry


class _Nop:
    """Absorbs inc/set/add/observe/with_labels calls. Each absorbed
    method is memoized onto the instance on first access — hot paths
    (mempool admission, exec lanes) hit these per tx, and rebuilding a
    lambda per call showed up in profiles."""

    def __getattr__(self, item):
        if item == "with_labels":
            fn = lambda *a: self  # noqa: E731
        else:
            fn = lambda *a, **k: None  # noqa: E731
        object.__setattr__(self, item, fn)
        return fn


NOP = _Nop()


@dataclass
class ConsensusMetrics:
    """consensus/metrics.go:12-57 (+ step_duration, ours: wall time of
    each step-machine transition, labeled step=new_round|propose|...)"""

    height: object = NOP
    rounds: object = NOP
    validators: object = NOP
    validators_power: object = NOP
    missing_validators: object = NOP
    byzantine_validators: object = NOP
    block_interval_seconds: object = NOP
    num_txs: object = NOP
    block_size_bytes: object = NOP
    total_txs: object = NOP
    committed_height: object = NOP
    step_duration: object = NOP
    # stall watchdog (consensus/state.py StallWatchdog): wall seconds the
    # machine has dwelt in the current (height, round), refreshed each
    # watchdog tick, and stalls past the threshold labeled by diagnosis
    round_dwell: object = NOP
    stalls: object = NOP
    # WAL records dropped as corrupt (bad CRC / absurd length / decode
    # failure) by consensus/wal.py iter_messages — an operator signal
    # that the disk is eating records, not a code path that can recover
    wal_corrupted: object = NOP
    # Handel-lite lane: gossiped aggregate precommit certificates that
    # verified and advanced our running aggregate (merged)
    agg_gossip_merges: object = NOP
    # the live vote path (consensus/reactor.py receive, consensus/
    # state.py's drain): VoteMessages received, labeled type=prevote|
    # precommit; what the tally made of each, labeled result=added|
    # bad_signature|duplicate|unmapped; votes in one drained run
    votes_received: object = NOP
    votes: object = NOP
    vote_run_size: object = NOP
    # the WAL records the receive loop wrote for peers' messages
    # (consensus/wal.py write_run), labeled source=arrived (spliced from
    # the bytes a vote was received as) | encoded (built from the message)
    wal_records: object = NOP


@dataclass
class CryptoMetrics:
    """Batch-verify engine telemetry (crypto/batch.py — the north-star
    hot path; no reference equivalent). Every BatchVerifier.verify()
    call reports here once batch.set_metrics() is wired."""

    # wall time of one verify() call, labeled by the backend that ran it
    batch_verify_seconds: object = NOP
    # signatures per verify() call, labeled like batch_verify_seconds
    batch_size: object = NOP
    # padded lanes each chip got of one device batch, labeled by the
    # number of chips that shared it (crypto/jaxed25519 verify_batch)
    batch_lanes_per_device: object = NOP
    signatures_verified: object = NOP
    signatures_invalid: object = NOP
    # adaptive router choices, labeled route=cpu|device
    routing_decisions: object = NOP
    # verified-signature cache (crypto/sigcache.py): triples served from
    # cache vs dispatched to a backend
    sig_cache_hits: object = NOP
    sig_cache_misses: object = NOP
    # digests built to key triples for that cache: one a triple looked
    # up, whoever looks (router or leaf verifier)
    sig_cache_key_hashes: object = NOP
    # async dispatch (verify_async): batches submitted but not completed
    inflight_batches: object = NOP
    # wall time a caller overlapped with an in-flight async batch
    # (submit -> first result() call, capped at batch completion)
    pipeline_overlap_seconds: object = NOP
    # BLS aggregate fast lane (crypto/bls): wall time of one
    # fast_aggregate_verify (MSM + pairing check) and signers per call
    agg_verify_seconds: object = NOP
    agg_signers: object = NOP
    # wire size of the last aggregate commit certificate seen/produced
    # (constant bitmap+96B vs 64B x N — the fast lane's bandwidth story)
    agg_commit_size_bytes: object = NOP
    # compile-once layer (crypto/kernel_cache.py): wall time of each
    # XLA lower+compile (labeled by kernel — a node stuck compiling at
    # boot shows up here), and AOT artifact store hit/miss counters
    compile_seconds: object = NOP
    compile_cache_hits: object = NOP
    compile_cache_misses: object = NOP
    # ValidatorSet.hash() calls, labeled result=memo|computed: a Merkle
    # walk of the whole committee against a 32-byte read (types/
    # validator_set.py reports through this process-wide sink like
    # verify_commit does)
    valset_hash: object = NOP
    # what a height costs on its way to disk (types/serde.py,
    # blockchain/store.py, through the same sink): a commit or a
    # validator set packed for a save, labeled kind=commit|valset (one
    # kept from an earlier save counts nothing), beside the heights
    # the block store saved
    store_encodings: object = NOP
    store_heights_saved: object = NOP
    # fast sync's block pool (blockchain/pool.py, through the same
    # sink): requests sent and blocks taken, labeled by the peer's slot
    # in the pool (a small number, never an id), and the delivered
    # blocks a refused commit made the pool drop and ask again
    pool_requests: object = NOP
    pool_blocks_received: object = NOP
    redo_heights: object = NOP


@dataclass
class P2PMetrics:
    """p2p/metrics.go:12-28, grown per-peer/per-channel: byte counters
    are labeled (peer_id, chID), received messages additionally by
    decoded msg_type, and gauges track each peer's flow rates, pending
    send queue, and consensus height lag. Every peer-labeled family is
    pruned on disconnect (prune_peer_series) so churn can't leak series."""

    peers: object = NOP
    peer_receive_bytes_total: object = NOP  # (peer_id, chID)
    peer_send_bytes_total: object = NOP  # (peer_id, chID)
    peer_msg_recv_total: object = NOP  # (peer_id, chID, msg_type)
    peer_send_rate: object = NOP  # (peer_id) flowrate EWMA, bytes/s
    peer_recv_rate: object = NOP  # (peer_id)
    peer_pending_send: object = NOP  # (peer_id) msgs queued across chans
    peer_lag_blocks: object = NOP  # (peer_id) our height - peer height
    # reconnect storm hygiene (switch._schedule_reconnect): dial attempts
    # at a dropped persistent peer, pruned on removal like the rest
    reconnect_attempts: object = NOP  # (peer_id)
    # seconds MConnection spent blocked in its flow-rate limiter, by
    # direction (send|recv): tells "the link's cap binds" from "the
    # host is slow" when a sync nears send_rate/recv_rate
    throttled_seconds: object = NOP  # (direction)
    # sealed frames a SecretConnection sent or opened, and the socket
    # calls that carried them (send: one a sendall; recv: one a recv
    # that returned bytes): calls per frame is what a batch saves
    frames: object = NOP  # (direction)
    socket_calls: object = NOP  # (direction)
    # network-fault engine (p2p/netchaos.py): faults actually injected,
    # by kind (drop|delay|throttle|disconnect), and the rules currently
    # active in the installed fault plan (0 when no controller/phase)
    chaos_injected: object = NOP  # (kind)
    chaos_active_rules: object = NOP


# the P2PMetrics families carrying a peer_id label; prune_peer_series
# walks exactly these on peer removal
_P2P_PEER_LABELED = (
    "peer_receive_bytes_total",
    "peer_send_bytes_total",
    "peer_msg_recv_total",
    "peer_send_rate",
    "peer_recv_rate",
    "peer_pending_send",
    "peer_lag_blocks",
    "reconnect_attempts",
)


def prune_peer_series(p2p: P2PMetrics, peer_id: str) -> int:
    """Drop every series labeled with a disconnected peer's id; returns
    the number removed (0 for nop metrics). Called from the switch's
    peer-removal paths — without it labeled families keep series for
    every peer that ever connected (unbounded cardinality under churn)."""
    removed = 0
    for fname in _P2P_PEER_LABELED:
        m = getattr(p2p, fname, NOP)
        removed += int(m.remove_labels(peer_id=peer_id) or 0)
    return removed


@dataclass
class StateSyncMetrics:
    """State-sync telemetry (statesync/ — no reference equivalent):
    producer-side snapshot inventory + chunk serving, restore-side
    chunk intake and per-phase durations."""

    # local snapshots currently advertisable / newest snapshot height
    snapshots: object = NOP
    snapshot_height: object = NOP
    # chunk flow: served to peers / received and verified / rejected
    # (reason=hash_mismatch|timeout)
    chunks_served: object = NOP
    chunks_received: object = NOP
    chunks_rejected: object = NOP
    # restore progress + per-phase wall time
    # (phase=discover|verify|fetch|apply|finalize)
    restore_chunks_applied: object = NOP
    restore_phase_seconds: object = NOP


@dataclass
class ABCIMetrics:
    """App-connection resilience telemetry (proxy/resilient.py; no
    reference equivalent — the reference's app conns have no deadlines,
    no reconnect, and no health model). Every request through a
    supervised conn reports here."""

    # wall time of one ABCI request, labeled (conn, method)
    request_duration: object = NOP
    # requests that tripped [abci] request_timeout_s, (conn, method)
    request_timeouts: object = NOP
    # successful redials, labeled conn
    reconnects: object = NOP
    # 2=healthy 1=degraded 0=down, labeled conn
    conn_state: object = NOP


@dataclass
class MempoolMetrics:
    """mempool/metrics.go:12-25 (+ recheck_failures, ours: recheck/flush
    app errors that previously vanished silently; + the throughput-path
    families: lane depths, CheckTx ingest batching, signature
    pre-verification, and incremental-recheck skip accounting)"""

    size: object = NOP
    tx_size_bytes: object = NOP
    failed_txs: object = NOP
    recheck_times: object = NOP
    # post-commit recheck (or commit-path flush) calls the app refused
    # at the TRANSPORT level — a failing/app-down signal, distinct from
    # failed_txs (txs the app rejected by code)
    recheck_failures: object = NOP
    # pending txs per priority lane, labeled (lane)
    lane_depth: object = NOP
    # txs drained per ingest round (the batched-preverify batch size)
    checktx_batch_size: object = NOP
    # submit -> drain wait inside the ingest queue
    ingest_queue_wait: object = NOP
    # serial-path envelope verifications served from the verified-sig
    # cache (gossip duplicates, replays: a sha256 instead of a full
    # Ed25519 verify). Batched-ingest hits are counted by the crypto
    # layer: crypto_sig_cache_hits_total.
    preverify_cache_hits: object = NOP
    # enveloped txs rejected for a bad signature BEFORE the app's
    # CheckTx ever ran (distinct from failed_txs: app verdicts)
    preverify_rejected: object = NOP
    # incremental recheck: pending txs that skipped the post-commit app
    # round trip because the committed set couldn't have invalidated
    # them (recheck_times counts the ones actually re-run)
    recheck_skipped: object = NOP


@dataclass
class RPCMetrics:
    """Fan-out serving telemetry (rpc/cache.py + rpc/server.py; no
    reference equivalent — the reference re-marshals every response and
    renders every event per subscriber)."""

    # height/generation response cache: requests served from cached
    # pre-encoded bytes vs. run through a handler + encoder, and the
    # bytes currently resident against [rpc] cache_bytes
    cache_hits: object = NOP
    cache_misses: object = NOP
    cache_bytes: object = NOP
    # live websocket subscriptions across all clients
    ws_subscribers: object = NOP
    # event frames shed (or connections cut) by the slow-client policy,
    # labeled policy=drop|disconnect
    ws_dropped: object = NOP
    # events rendered to wire bytes — with render-once fan-out this
    # advances once per event, not once per (event x subscriber)
    events_rendered: object = NOP


@dataclass
class LockdepMetrics:
    """Runtime lock-discipline telemetry (libs/lockdep.py; no reference
    equivalent). Families are registered unconditionally — declaration
    presence is the check_metrics contract — but record samples only
    while [instrumentation] lockdep is on."""

    # wall time a lock was held, by creation site (file.py:line)
    hold_seconds: object = NOP
    # distinct lock-order inversions (A->B observed after B->A) —
    # latent deadlocks; the chaos-under-lockdep oracle requires zero
    inversions: object = NOP


@dataclass
class StateMetrics:
    """state/metrics.go:10-22 (+ the churn families, ours: EndBlock
    validator-update batches applied by update_state — the first-class
    validator-rotation workload's primary counters)"""

    block_processing_time: object = NOP
    # individual validator updates applied (adds + removes + repowers)
    validator_updates: object = NOP
    # blocks whose EndBlock carried at least one validator update
    valset_changes: object = NOP
    # parallel-execution lane count the executor is configured with
    # (1 = serial oracle path)
    exec_parallel_lanes: object = NOP
    # txs re-run serially after an observed read/write conflict across
    # concurrently executed groups
    exec_conflicts: object = NOP
    # speculative block executions adopted at commit / discarded
    exec_speculation_hits: object = NOP
    exec_speculation_wasted: object = NOP
    # commit-path stage breakdown (state/execution.CommitStageProfile):
    # wall seconds per commit-path stage, labeled
    # stage=execute|app_commit|events|index|mempool_update|wal — the
    # profiler that
    # makes the post-executor pipeline ceiling attributable
    commit_stage: object = NOP
    # exec-lane flight recorder (state/parallel.FlightRecorder): lane
    # spawn->first-instruction latency — the thread-wakeup convoy the
    # Block-STM retry-DAG work regresses against
    exec_lane_wakeup: object = NOP
    # fraction of a lane's lifetime spent executing txs (1.0 = no
    # scheduling overhead), labeled by lane index
    exec_lane_busy: object = NOP
    # conflict-cone retry engine: txs re-executed in retry rounds
    # (per-lane attribution lives in the flight recorder report)
    exec_lane_retries: object = NOP
    # work-stealing lane pool: groups a lane stole from a sibling's
    # deque tail (nonzero = the pool is actually load-balancing)
    exec_lane_steals: object = NOP
    # apply-time LastCommit checks, labeled result=handed_down|verified:
    # the fast-sync loop verified this very commit one iteration earlier
    # and handed the proof down, against a full verify_commit
    last_commit_check: object = NOP


@dataclass
class RecoveryMetrics:
    """Crash-recovery telemetry (ours): what a restart had to repair.
    Samples flow only on a boot that actually replayed/recovered, and
    under armed storage-fault injection ([storage] fault_plan) — the
    crash matrix's acceptance surface."""

    # blocks re-driven through the app by the boot handshake (ABCI
    # replay decision table) — nonzero exactly when a crash left the
    # app behind the chain
    replayed_blocks: object = NOP
    # wall seconds of the whole boot recovery (handshake + index
    # convergence), observed once per boot
    recovery_time: object = NOP
    # storage faults injected by the crash-consistency engine, by kind
    storage_faults: object = NOP


@dataclass
class DeterminismMetrics:
    """Determinism-gate telemetry (ours; no reference equivalent):
    the static analyzer's finding counts and the replay-divergence
    oracle's run/divergence counters (tools/detcheck.py). Families are
    registered unconditionally — declaration presence is the
    check_metrics contract — and record samples only when a lint or
    oracle run is driven in-process (tests, bench.py detcheck, the
    scenario runner)."""

    # static-gate findings observed per lint run, by DT-* class
    lint_findings: object = NOP
    # replay-divergence oracle executions completed
    oracle_runs: object = NOP
    # byte-level divergences between execution engines, by surface
    # (app_hashes|results|events|index|image) — any nonzero value is a
    # chain-splitting bug; tools/monitor.py degrades health on it
    oracle_divergence: object = NOP


@dataclass
class IncidentMetrics:
    """Incident-observatory telemetry (ours; libs/incident.py): how
    fast this node notices and outlives injected faults. Samples flow
    only when the ledger pairs events — a fault-free node records
    nothing, which is the healthy signal."""

    # injection -> correct watchdog stall classification, by the
    # INJECTED fault's kind (MTTD)
    detection: object = NOP
    # fault heal -> first commit at a fresh height, by kind (MTTR)
    recovery: object = NOP
    # incidents currently open on this node (injected, not yet closed
    # by a fresh-height commit)
    open: object = NOP


@dataclass
class HandelMetrics:
    """Handel aggregation overlay telemetry (ours; consensus/handel.py).
    All families stay silent on Ed25519 chains and when [handel] is
    off — absence is the disabled signal."""

    # current session's per-level fill fraction (0..1 of the
    # complementary group covered by the best verified aggregate)
    level: object = NOP
    # incoming contributions by verdict (verified | rejected)
    contributions: object = NOP
    # wall seconds per contribution verification batch (one multi-pair
    # aggregate check per drained run)
    verify_seconds: object = NOP
    # candidates pruned after exhausting their garbage fail budget
    pruned_peers: object = NOP


@dataclass
class ReplicaMetrics:
    """Replica fan-out tree telemetry (ours;
    blockchain/replica_tree.py). All families stay silent on full
    nodes and on replicas without a tree manager — absence is the
    flat-topology signal."""

    # this replica's current tree depth (0 while orphaned; validators
    # and full nodes are depth 0 by definition)
    tree_depth: object = NOP
    # parent re-adoptions, by reason
    # (attach | peer_down | silence | lag_budget)
    parent_switches_total: object = NOP
    # tip age: best fleet tip this replica can see minus its own
    # store height
    lag_blocks: object = NOP


@dataclass
class RuntimeMetrics:
    """The interpreter under the node (ours; libs/tracing.py "the
    frozen heap")."""

    # objects in the collector's permanent generation after this node's
    # gc.freeze() at the end of its start; 0 before it and after stop()
    gc_frozen_objects: object = NOP


@dataclass
class PrivvalMetrics:
    """The validator's key (ours; privval/file_pv.py)."""

    # signatures made and their last-sign state persisted, labeled
    # type=prevote|precommit|proposal; a re-sign that hands out the
    # last signature again is not counted
    signatures: object = NOP


@dataclass
class NodeMetrics:
    consensus: ConsensusMetrics = field(default_factory=ConsensusMetrics)
    p2p: P2PMetrics = field(default_factory=P2PMetrics)
    abci: ABCIMetrics = field(default_factory=ABCIMetrics)
    mempool: MempoolMetrics = field(default_factory=MempoolMetrics)
    state: StateMetrics = field(default_factory=StateMetrics)
    crypto: CryptoMetrics = field(default_factory=CryptoMetrics)
    statesync: StateSyncMetrics = field(default_factory=StateSyncMetrics)
    rpc: RPCMetrics = field(default_factory=RPCMetrics)
    lockdep: LockdepMetrics = field(default_factory=LockdepMetrics)
    recovery: RecoveryMetrics = field(default_factory=RecoveryMetrics)
    determinism: DeterminismMetrics = field(
        default_factory=DeterminismMetrics)
    incident: IncidentMetrics = field(default_factory=IncidentMetrics)
    handel: HandelMetrics = field(default_factory=HandelMetrics)
    replica: ReplicaMetrics = field(default_factory=ReplicaMetrics)
    runtime: RuntimeMetrics = field(default_factory=RuntimeMetrics)
    privval: PrivvalMetrics = field(default_factory=PrivvalMetrics)
    registry: Optional[Registry] = None


def nop_metrics() -> NodeMetrics:
    return NodeMetrics()


def prometheus_metrics(namespace: str = "tendermint") -> NodeMetrics:
    """DefaultMetricsProvider (each reference metrics.go
    PrometheusMetrics constructor)."""
    r = Registry()
    r.register(ProcessCPU())
    ns = namespace
    cons = ConsensusMetrics(
        height=r.gauge(f"{ns}_consensus_height",
                       "Height of the chain."),
        rounds=r.gauge(f"{ns}_consensus_rounds",
                       "Number of rounds at the latest height."),
        validators=r.gauge(f"{ns}_consensus_validators",
                           "Number of validators."),
        validators_power=r.gauge(f"{ns}_consensus_validators_power",
                                 "Total voting power of validators."),
        missing_validators=r.gauge(
            f"{ns}_consensus_missing_validators",
            "Validators missing from the last commit."),
        byzantine_validators=r.gauge(
            f"{ns}_consensus_byzantine_validators",
            "Validators with evidence against them."),
        block_interval_seconds=r.histogram(
            f"{ns}_consensus_block_interval_seconds",
            "Time between this and the last block.",
            buckets=(0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60)),
        num_txs=r.gauge(f"{ns}_consensus_num_txs",
                        "Number of transactions in the latest block."),
        block_size_bytes=r.gauge(f"{ns}_consensus_block_size_bytes",
                                 "Size of the latest block."),
        total_txs=r.gauge(f"{ns}_consensus_total_txs",
                          "Total transactions committed."),
        committed_height=r.gauge(f"{ns}_consensus_latest_block_height",
                                 "Latest committed block height."),
        step_duration=r.histogram(
            f"{ns}_consensus_step_duration_seconds",
            "Wall time of each consensus step transition.",
            ("step",),
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                     0.5, 1, 5)),
        round_dwell=r.gauge(
            f"{ns}_consensus_round_dwell_seconds",
            "Seconds spent in the current consensus (height, round)."),
        stalls=r.counter(
            f"{ns}_consensus_stalls_total",
            "Rounds that dwelt past the stall threshold, by diagnosis.",
            ("reason",)),
        wal_corrupted=r.counter(
            f"{ns}_wal_corrupted_records_total",
            "WAL records dropped due to corruption (bad CRC/length/"
            "decode)."),
        agg_gossip_merges=r.counter(
            f"{ns}_consensus_agg_gossip_merges_total",
            "Gossiped aggregate precommit certificates merged into the "
            "running aggregate (BLS fast lane)."),
        votes_received=r.counter(
            f"{ns}_consensus_votes_received_total",
            "Vote messages received from peers, by vote type.",
            ("type",)),
        votes=r.counter(
            f"{ns}_consensus_votes_total",
            "Votes the tally took or refused: added, bad_signature, "
            "duplicate, unmapped (no height, round or validator to hold "
            "it).", ("result",)),
        vote_run_size=r.histogram(
            f"{ns}_consensus_vote_run_size",
            "Votes in one run the receive loop drained and verified as "
            "one batch.",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)),
        wal_records=r.counter(
            f"{ns}_consensus_wal_records_total",
            "WAL records written for peers' messages: arrived (spliced "
            "from the bytes a vote was received as) or encoded.",
            ("source",)),
    )
    p2p = P2PMetrics(
        peers=r.gauge(f"{ns}_p2p_peers", "Number of connected peers."),
        peer_receive_bytes_total=r.counter(
            f"{ns}_p2p_peer_receive_bytes_total",
            "Bytes received from peers, per channel.",
            ("peer_id", "chID")),
        peer_send_bytes_total=r.counter(
            f"{ns}_p2p_peer_send_bytes_total",
            "Bytes sent to peers, per channel.", ("peer_id", "chID")),
        peer_msg_recv_total=r.counter(
            f"{ns}_p2p_peer_msg_recv_total",
            "Messages received from peers, by channel and decoded type.",
            ("peer_id", "chID", "msg_type")),
        peer_send_rate=r.gauge(
            f"{ns}_p2p_peer_send_rate_bytes",
            "Current send rate to the peer (flowrate EWMA, bytes/s).",
            ("peer_id",)),
        peer_recv_rate=r.gauge(
            f"{ns}_p2p_peer_recv_rate_bytes",
            "Current receive rate from the peer (flowrate EWMA, bytes/s).",
            ("peer_id",)),
        peer_pending_send=r.gauge(
            f"{ns}_p2p_peer_pending_send_msgs",
            "Messages queued to the peer across all channels.",
            ("peer_id",)),
        peer_lag_blocks=r.gauge(
            f"{ns}_p2p_peer_lag_blocks",
            "Blocks the peer's consensus height trails ours.",
            ("peer_id",)),
        reconnect_attempts=r.counter(
            f"{ns}_p2p_reconnect_attempts_total",
            "Dial attempts at a dropped persistent peer (reconnect "
            "loops; pruned with the peer's other series on removal).",
            ("peer_id",)),
        throttled_seconds=r.counter(
            f"{ns}_p2p_throttled_seconds_total",
            "Seconds connections spent blocked by the [p2p] send_rate/"
            "recv_rate limiter, by direction.", ("direction",)),
        frames=r.counter(
            f"{ns}_p2p_frames_total",
            "Sealed frames connections sent or opened, by direction.",
            ("direction",)),
        socket_calls=r.counter(
            f"{ns}_p2p_socket_calls_total",
            "Socket calls that carried sealed frames, by direction "
            "(send: one a sendall; recv: one a recv that returned "
            "bytes).", ("direction",)),
        chaos_injected=r.counter(
            f"{ns}_chaos_injected_total",
            "Network faults injected by the netchaos engine, by kind.",
            ("kind",)),
        chaos_active_rules=r.gauge(
            f"{ns}_chaos_active_rules",
            "Link rules currently active in the installed fault plan."),
    )
    abci_m = ABCIMetrics(
        request_duration=r.histogram(
            f"{ns}_abci_request_duration_seconds",
            "Wall time of one ABCI request, by connection and method.",
            ("conn", "method"),
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                     0.5, 1, 5, 30)),
        request_timeouts=r.counter(
            f"{ns}_abci_request_timeouts_total",
            "ABCI requests that exceeded the configured request "
            "deadline.", ("conn", "method")),
        reconnects=r.counter(
            f"{ns}_abci_reconnects_total",
            "Successful app-connection redials.", ("conn",)),
        conn_state=r.gauge(
            f"{ns}_abci_conn_state",
            "App-connection health (2=healthy 1=degraded 0=down).",
            ("conn",)),
    )
    mem = MempoolMetrics(
        size=r.gauge(f"{ns}_mempool_size",
                     "Number of uncommitted transactions."),
        tx_size_bytes=r.histogram(
            f"{ns}_mempool_tx_size_bytes", "Tx sizes in bytes.",
            buckets=(32, 128, 512, 2048, 8192, 32768, 131072)),
        failed_txs=r.counter(f"{ns}_mempool_failed_txs",
                             "Transactions that failed CheckTx."),
        recheck_times=r.counter(f"{ns}_mempool_recheck_times",
                                "Times transactions were rechecked."),
        recheck_failures=r.counter(
            f"{ns}_mempool_recheck_failures_total",
            "Recheck/flush app calls that failed at the transport "
            "level (app down or erroring)."),
        lane_depth=r.gauge(
            f"{ns}_mempool_lane_depth",
            "Pending transactions per priority lane.", ("lane",)),
        checktx_batch_size=r.histogram(
            f"{ns}_mempool_checktx_batch_size",
            "Transactions drained per batched-CheckTx ingest round.",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256)),
        ingest_queue_wait=r.histogram(
            f"{ns}_mempool_ingest_queue_wait_seconds",
            "Wait between tx submission and ingest-batch drain (s).",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5)),
        preverify_cache_hits=r.counter(
            f"{ns}_mempool_preverify_cache_hits_total",
            "Serial-path tx signature checks served from the verified-"
            "signature cache (batched-ingest hits land in "
            "crypto_sig_cache_hits_total)."),
        preverify_rejected=r.counter(
            f"{ns}_mempool_preverify_rejected_total",
            "Transactions rejected for a bad signature before the "
            "app's CheckTx ran."),
        recheck_skipped=r.counter(
            f"{ns}_mempool_recheck_skipped_total",
            "Pending transactions that skipped the post-commit recheck "
            "(incremental mode: sender untouched by the committed set)."),
    )
    state = StateMetrics(
        block_processing_time=r.histogram(
            f"{ns}_state_block_processing_time",
            "Time spent processing a block (s).",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5)),
        validator_updates=r.counter(
            f"{ns}_churn_validator_updates_total",
            "Individual validator updates (add/remove/repower) applied "
            "from EndBlock responses."),
        valset_changes=r.counter(
            f"{ns}_churn_valset_changes_total",
            "Blocks whose EndBlock carried at least one validator "
            "update."),
        exec_parallel_lanes=r.gauge(
            f"{ns}_exec_parallel_lanes",
            "Configured parallel execution lanes (1 = serial)."),
        exec_conflicts=r.counter(
            f"{ns}_exec_conflicts_total",
            "Transactions re-run serially after an observed read/write "
            "conflict between concurrently executed groups."),
        exec_speculation_hits=r.counter(
            f"{ns}_exec_speculation_hits_total",
            "Speculative block executions adopted at commit."),
        exec_speculation_wasted=r.counter(
            f"{ns}_exec_speculation_wasted_total",
            "Speculative block executions discarded (decided block or "
            "base state did not match)."),
        commit_stage=r.histogram(
            f"{ns}_commit_stage_seconds",
            "Wall time of each commit-path stage per block "
            "(execute/app_commit/events/index/mempool_update/wal).",
            ("stage",),
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                     0.5, 1, 5)),
        exec_lane_wakeup=r.histogram(
            f"{ns}_exec_lane_wakeup_seconds",
            "Exec-lane thread wakeup latency: spawn to first "
            "instruction (flight recorder, threaded path only).",
            buckets=(0.00001, 0.000025, 0.00005, 0.0001, 0.00025,
                     0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05)),
        exec_lane_busy=r.gauge(
            f"{ns}_exec_lane_busy_ratio",
            "Fraction of an exec lane's lifetime spent executing txs "
            "(1.0 = zero scheduling overhead).",
            ("lane",)),
        exec_lane_retries=r.counter(
            f"{ns}_exec_lane_retries_total",
            "Transactions re-executed by the conflict-cone retry "
            "engine (Block-STM fixpoint rounds)."),
        exec_lane_steals=r.counter(
            f"{ns}_exec_lane_steals_total",
            "Groups stolen from a sibling lane's deque by the "
            "persistent work-stealing pool."),
        last_commit_check=r.counter(
            f"{ns}_state_last_commit_check_total",
            "Apply-time LastCommit checks, by result: handed_down (fast "
            "sync verified this commit one block earlier) or verified "
            "(a full verify_commit).",
            ("result",)),
    )
    crypto = CryptoMetrics(
        batch_verify_seconds=r.histogram(
            f"{ns}_crypto_batch_verify_seconds",
            "Wall time of one batch-verify call, by backend.",
            ("backend",),
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                     0.01, 0.025, 0.05, 0.1, 0.25, 1)),
        batch_size=r.histogram(
            f"{ns}_crypto_batch_size",
            "Signatures per batch-verify call, by backend.",
            ("backend",),
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                     4096)),
        batch_lanes_per_device=r.histogram(
            f"{ns}_crypto_batch_lanes_per_device",
            "Padded lanes a chip of one device batch: the bucket over "
            "the chips that shared it, by their number.",
            ("ndev",),
            buckets=(2, 8, 32, 128, 512, 2048, 8192)),
        signatures_verified=r.counter(
            f"{ns}_crypto_signatures_verified_total",
            "Signatures that verified valid."),
        signatures_invalid=r.counter(
            f"{ns}_crypto_signatures_invalid_total",
            "Signatures that failed verification."),
        routing_decisions=r.counter(
            f"{ns}_crypto_batch_routing_total",
            "Adaptive batch-verify routing decisions.", ("route",)),
        sig_cache_hits=r.counter(
            f"{ns}_crypto_sig_cache_hits_total",
            "Triples served from the verified-signature cache."),
        sig_cache_misses=r.counter(
            f"{ns}_crypto_sig_cache_misses_total",
            "Triples that missed the cache and reached a backend."),
        sig_cache_key_hashes=r.counter(
            f"{ns}_crypto_sig_cache_key_hashes_total",
            "Digests built to key triples for the verified-signature "
            "cache: one for every triple looked up."),
        inflight_batches=r.gauge(
            f"{ns}_crypto_inflight_batches",
            "Async verify batches dispatched and not yet completed."),
        pipeline_overlap_seconds=r.histogram(
            f"{ns}_crypto_pipeline_overlap_seconds",
            "Wall time callers overlapped with an in-flight async batch.",
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 1)),
        agg_verify_seconds=r.histogram(
            f"{ns}_crypto_agg_verify_seconds",
            "Wall time of one BLS fast_aggregate_verify (bitmap MSM + "
            "pairing check).",
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5)),
        agg_signers=r.histogram(
            f"{ns}_crypto_agg_signers",
            "Signers covered by one BLS aggregate verification.",
            buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384)),
        agg_commit_size_bytes=r.gauge(
            f"{ns}_agg_commit_size_bytes",
            "Wire size of the latest aggregate commit certificate "
            "(signer bitmap + one 96-byte signature)."),
        compile_seconds=r.histogram(
            f"{ns}_crypto_compile_seconds",
            "Wall time of one XLA kernel lower+compile, by kernel.",
            ("kernel",),
            buckets=(0.5, 1, 2.5, 5, 10, 30, 60, 120, 300, 600)),
        compile_cache_hits=r.counter(
            f"{ns}_crypto_compile_cache_hits_total",
            "Kernel executables loaded from the AOT artifact store "
            "(no XLA compile paid)."),
        compile_cache_misses=r.counter(
            f"{ns}_crypto_compile_cache_misses_total",
            "Kernel signatures that missed the AOT artifact store and "
            "paid a fresh XLA compile."),
        valset_hash=r.counter(
            f"{ns}_types_valset_hash_total",
            "ValidatorSet.hash() calls, by result: memo (the remembered "
            "root) or computed (a Merkle walk of the whole set).",
            ("result",)),
        store_encodings=r.counter(
            f"{ns}_store_encodings_total",
            "Commits and validator sets packed for a save, by kind "
            "(commit, valset); one that kept the bytes of an earlier "
            "save is not counted.",
            ("kind",)),
        store_heights_saved=r.counter(
            f"{ns}_store_heights_saved_total",
            "Heights the block store saved (save_block calls)."),
        pool_requests=r.counter(
            f"{ns}_blockchain_pool_requests_total",
            "Block requests fast sync's pool sent, by the peer's slot "
            "in the pool.",
            ("slot",)),
        pool_blocks_received=r.counter(
            f"{ns}_blockchain_pool_blocks_received_total",
            "Blocks fast sync's pool took from the peer it had asked, "
            "by the peer's slot in the pool.",
            ("slot",)),
        redo_heights=r.counter(
            f"{ns}_blockchain_redo_heights_total",
            "Delivered blocks the pool dropped and asked again after a "
            "refused commit."),
    )
    statesync = StateSyncMetrics(
        snapshots=r.gauge(
            f"{ns}_statesync_snapshots",
            "Local snapshots available to serve."),
        snapshot_height=r.gauge(
            f"{ns}_statesync_snapshot_height",
            "Height of the newest local snapshot."),
        chunks_served=r.counter(
            f"{ns}_statesync_chunks_served_total",
            "Snapshot chunks served to peers."),
        chunks_received=r.counter(
            f"{ns}_statesync_chunks_received_total",
            "Snapshot chunks received and hash-verified during restore."),
        chunks_rejected=r.counter(
            f"{ns}_statesync_chunks_rejected_total",
            "Snapshot chunk requests that failed, by reason.",
            ("reason",)),
        restore_chunks_applied=r.gauge(
            f"{ns}_statesync_restore_chunks_applied",
            "Chunks applied through ABCI in the current restore."),
        restore_phase_seconds=r.histogram(
            f"{ns}_statesync_restore_phase_seconds",
            "Wall time of each state-sync restore phase.",
            ("phase",),
            buckets=(0.01, 0.05, 0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 300)),
    )
    rpc = RPCMetrics(
        cache_hits=r.counter(
            f"{ns}_rpc_cache_hits_total",
            "RPC requests served from the pre-encoded response cache."),
        cache_misses=r.counter(
            f"{ns}_rpc_cache_misses_total",
            "Cache-eligible RPC requests that ran the handler and "
            "encoder."),
        cache_bytes=r.gauge(
            f"{ns}_rpc_cache_bytes",
            "Bytes resident in the RPC response cache."),
        ws_subscribers=r.gauge(
            f"{ns}_rpc_ws_subscribers",
            "Live websocket event subscriptions across all clients."),
        ws_dropped=r.counter(
            f"{ns}_rpc_ws_dropped_total",
            "Event frames shed (drop) or connections cut (disconnect) "
            "by the slow-websocket-client policy.", ("policy",)),
        events_rendered=r.counter(
            f"{ns}_rpc_events_rendered_total",
            "Events rendered to wire bytes (once per event under "
            "render-once fan-out, regardless of subscriber count)."),
    )
    lockdep = LockdepMetrics(
        hold_seconds=r.histogram(
            f"{ns}_lockdep_hold_seconds",
            "Wall time locks were held, by creation site (records only "
            "under [instrumentation] lockdep).",
            ("site",),
            buckets=(0.000001, 0.00001, 0.0001, 0.001, 0.01, 0.1, 1,
                     10)),
        inversions=r.counter(
            f"{ns}_lockdep_inversions_total",
            "Distinct lock-order inversions observed at runtime "
            "(latent deadlocks; records only under [instrumentation] "
            "lockdep)."),
    )
    recovery = RecoveryMetrics(
        replayed_blocks=r.counter(
            f"{ns}_recovery_replayed_blocks_total",
            "Blocks re-driven through the app by the boot handshake "
            "(nonzero exactly when a crash left the app behind)."),
        recovery_time=r.histogram(
            f"{ns}_recovery_time_seconds",
            "Wall time of boot recovery (ABCI handshake replay + tx "
            "index convergence), one observation per boot.",
            buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 15, 60, 300)),
        storage_faults=r.counter(
            f"{ns}_storage_faults_injected_total",
            "Storage faults injected by the crash-consistency engine, "
            "by kind.", ("kind",)),
    )
    determinism = DeterminismMetrics(
        lint_findings=r.counter(
            f"{ns}_detlint_findings_total",
            "check_determinism findings observed per in-process lint "
            "run, by DT-* class (allowlisted findings included).",
            ("cls",)),
        oracle_runs=r.counter(
            f"{ns}_detcheck_runs_total",
            "Replay-divergence oracle executions completed "
            "(tools/detcheck.py)."),
        oracle_divergence=r.counter(
            f"{ns}_detcheck_divergence_total",
            "Byte-level divergences between execution engines, by "
            "surface — any nonzero value is a chain-splitting bug.",
            ("surface",)),
    )
    incident = IncidentMetrics(
        detection=r.histogram(
            f"{ns}_incident_detection_seconds",
            "Fault injection to correct watchdog stall classification "
            "(MTTD), by injected fault kind.", ("kind",),
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 300)),
        recovery=r.histogram(
            f"{ns}_incident_recovery_seconds",
            "Fault heal to the first commit at a fresh height (MTTR), "
            "by injected fault kind.", ("kind",),
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 300)),
        open=r.gauge(
            f"{ns}_incident_open",
            "Incidents currently open on this node (fault injected, "
            "no fresh-height commit yet)."),
    )
    handel = HandelMetrics(
        level=r.gauge(
            f"{ns}_handel_level",
            "Current Handel session's per-level fill fraction (best "
            "verified aggregate coverage of the complementary group).",
            ("level",)),
        contributions=r.counter(
            f"{ns}_handel_contributions_total",
            "Incoming Handel level contributions, by verdict.",
            ("verdict",)),
        verify_seconds=r.histogram(
            f"{ns}_handel_verify_seconds",
            "Wall seconds per Handel contribution verification batch "
            "(one multi-pair aggregate check per drained run).",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1, 2.5)),
        pruned_peers=r.counter(
            f"{ns}_handel_pruned_peers_total",
            "Handel candidates pruned after exhausting their garbage "
            "fail budget."),
    )
    replica = ReplicaMetrics(
        tree_depth=r.gauge(
            f"{ns}_replica_tree_depth",
            "This replica's current fan-out tree depth (0 while "
            "orphaned; validators are depth 0)."),
        parent_switches_total=r.counter(
            f"{ns}_replica_parent_switches_total",
            "Replica parent re-adoptions, by reason.", ("reason",)),
        lag_blocks=r.gauge(
            f"{ns}_replica_lag_blocks",
            "Tip age: best fleet tip this replica can see minus its "
            "own store height."),
    )
    runtime = RuntimeMetrics(
        gc_frozen_objects=r.gauge(
            f"{ns}_runtime_gc_frozen_objects",
            "Objects the node's start left in the collector's permanent "
            "generation (gc.freeze), which no collection walks; 0 until "
            "the verify warm-up has ended and after stop()."),
    )
    privval = PrivvalMetrics(
        signatures=r.counter(
            f"{ns}_privval_signatures_total",
            "Signatures the validator's key made, its last-sign state "
            "persisted first: prevote, precommit or proposal.", ("type",)),
    )
    return NodeMetrics(consensus=cons, p2p=p2p, abci=abci_m, mempool=mem,
                       state=state, crypto=crypto, statesync=statesync,
                       rpc=rpc, lockdep=lockdep, recovery=recovery,
                       determinism=determinism, incident=incident,
                       handel=handel, replica=replica, runtime=runtime,
                       privval=privval, registry=r)
