"""Configuration — all 8 sections of the reference config
(config/config.go:50-60): Base, RPC, P2P, Mempool, Consensus, TxIndex,
Instrumentation (+ privval paths in Base), plus our [crypto] section
for the batch-verification engine. TOML-persisted (config/toml.go);
tests use in-memory defaults via TestConfig.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


@dataclass
class BaseConfig:
    """reference config/config.go:127-260"""

    root_dir: str = ""
    chain_id: str = ""
    moniker: str = "anonymous"
    # "full" (default: the reference node — consensus + serving) or
    # "replica": a non-validating read node that bootstraps via state
    # sync, permanently tails blocks through the fast-sync reactor
    # (never starts consensus), and serves the full RPC/subscription
    # surface — read traffic scales horizontally by adding replicas
    mode: str = "full"
    fast_sync: bool = True
    db_backend: str = "filedb"  # memdb | filedb | native
    db_dir: str = "data"
    # "module:level,*:level" list or a bare level (reference
    # libs/cli/flags/log_level.go); format "plain"|"json" (config.go:18-21)
    log_level: str = "info"
    log_format: str = "plain"
    genesis_file: str = "config/genesis.json"
    priv_validator_file: str = "config/priv_validator.json"
    priv_validator_laddr: str = ""  # remote signer listen addr
    node_key_file: str = "config/node_key.json"
    abci: str = "socket"  # socket | grpc
    proxy_app: str = "tcp://127.0.0.1:26658"  # or kvstore/counter/noop
    prof_laddr: str = ""
    filter_peers: bool = False

    def genesis_path(self) -> str:
        return os.path.join(self.root_dir, self.genesis_file)

    def priv_validator_path(self) -> str:
        return os.path.join(self.root_dir, self.priv_validator_file)

    def node_key_path(self) -> str:
        return os.path.join(self.root_dir, self.node_key_file)

    def db_path(self) -> str:
        return os.path.join(self.root_dir, self.db_dir)


@dataclass
class RPCConfig:
    """reference config/config.go:262-347 (+ the fan-out-scale serving
    knobs, ours: response caching, websocket backpressure, and the
    broadcast_tx_commit wait).

    cache_bytes: byte budget for the height/generation response cache
    (rpc/cache.py) serving pre-encoded JSON for hot read endpoints
    (block/commit/block_results/validators/blockchain at a fixed
    height; status and latest-height variants per block generation).
    0 (default) disables caching — every request runs its handler.
    ws_send_queue: bounded per-websocket-client event queue drained by
    a writer thread; a slow client backs up only its own queue.
    ws_slow_policy: what happens when that queue is full — "drop"
    sheds the event with a counter (rpc_ws_dropped_total), keeping the
    connection; "disconnect" hangs up so the client's reconnect logic
    resubscribes from live state.
    timeout_broadcast_tx_commit: seconds broadcast_tx_commit waits for
    the DeliverTx event (the reference hard-codes 10s)."""

    laddr: str = "tcp://0.0.0.0:26657"
    grpc_laddr: str = ""
    grpc_max_open_connections: int = 900
    unsafe: bool = False
    max_open_connections: int = 900
    cache_bytes: int = 0
    ws_send_queue: int = 256
    ws_slow_policy: str = "drop"  # drop | disconnect
    timeout_broadcast_tx_commit: float = 10.0


@dataclass
class P2PConfig:
    """reference config/config.go:349-484"""

    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""
    persistent_peers: str = ""
    upnp: bool = False
    addr_book_file: str = "config/addrbook.json"
    addr_book_strict: bool = True
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    flush_throttle_timeout: float = 0.1  # seconds (reference: 100ms)
    max_packet_msg_payload_size: int = 1024
    send_rate: int = 5120000  # 5MB/s
    recv_rate: int = 5120000
    pex: bool = True
    seed_mode: bool = False
    private_peer_ids: str = ""
    allow_duplicate_ip: bool = True
    handshake_timeout: float = 20.0
    dial_timeout: float = 3.0
    # fuzz testing (reference config/config.go:485-530): with test_fuzz
    # on, every peer connection is wrapped in a FuzzedConnection
    # (p2p/fuzz.py) built from these knobs. test_fuzz_seed != 0 makes
    # each connection's op sequence deterministic (per-instance RNG).
    test_fuzz: bool = False
    test_fuzz_mode: str = "drop"  # drop | delay
    test_fuzz_prob_drop_rw: float = 0.2
    test_fuzz_delay_ms: int = 250
    test_fuzz_seed: int = 0


@dataclass
class MempoolConfig:
    """reference config/config.go:508-560 (+ the throughput knobs, ours:
    lanes/preverify/recheck_mode — every default reproduces the
    reference's single-lane, synchronous, full-recheck behavior)"""

    recheck: bool = True
    broadcast: bool = True
    wal_path: str = ""  # empty = no mempool WAL
    size: int = 5000
    cache_size: int = 10000
    # priority/fee lanes: the pool splits into `lanes` independent FIFO
    # shards (per-lane locks + gossip cursors). Reap order is ALWAYS
    # (priority desc, arrival asc) regardless of lane count — identical
    # to the reference FIFO while every tx has the default priority 0
    # (plain txs always do; only signed envelopes carry priorities).
    # 1 = the reference's single list.
    lanes: int = 1
    # recognize the signed-tx envelope (mempool/preverify.py MAGIC):
    # enveloped txs are signature-checked by the node (serially, or in
    # batches with preverify_batch) and carry priority/sender. Off, the
    # magic is just opaque app bytes — the escape hatch for an app
    # whose own tx format could collide with the 5-byte prefix.
    envelopes: bool = True
    # batched CheckTx signature pre-verification: an ingest queue drains
    # waiting txs into one crypto/batch verify_async call (riding the
    # sig cache + dispatch threads) before the per-tx ABCI CheckTx.
    # False = today's synchronous per-tx path.
    preverify_batch: bool = False
    preverify_batch_max: int = 256  # max txs drained per verify batch
    ingest_queue_size: int = 10000  # submit() fails ErrMempoolIsFull past this
    # post-commit recheck scope: "full" re-runs CheckTx on every pending
    # tx (reference Update :526); "incremental" rechecks only txs whose
    # sender was touched by the committed set (unsigned txs, which carry
    # no sender, are always rechecked)
    recheck_mode: str = "full"


@dataclass
class ConsensusConfig:
    """reference config/config.go:564-720. Timeouts in seconds; each
    timeout grows by its delta per round (accessors below mirror
    Propose(round) etc. used at consensus/state.go:823,1016,1144)."""

    wal_path: str = "data/cs.wal/wal"
    timeout_propose: float = 3.0
    timeout_propose_delta: float = 0.5
    timeout_prevote: float = 1.0
    timeout_prevote_delta: float = 0.5
    timeout_precommit: float = 1.0
    timeout_precommit_delta: float = 0.5
    timeout_commit: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval: float = 0.0
    peer_gossip_sleep_duration: float = 0.1
    peer_query_maj23_sleep_duration: float = 2.0
    blocktime_iota: int = 1_000_000_000  # 1s in ns (min time between blocks)

    def propose(self, round_: int) -> float:
        return self.timeout_propose + self.timeout_propose_delta * round_

    def prevote(self, round_: int) -> float:
        return self.timeout_prevote + self.timeout_prevote_delta * round_

    def precommit(self, round_: int) -> float:
        return self.timeout_precommit + self.timeout_precommit_delta * round_

    def commit_time(self, t: float) -> float:
        """Wall-clock at which to start the next height (reference
        Commit(t))."""
        return t + self.timeout_commit

    def wal_file(self, root: str) -> str:
        return os.path.join(root, self.wal_path)


@dataclass
class ABCIConfig:
    """[abci] — app-connection resilience knobs (ours; the reference has
    a single blocking socket with no deadlines or reconnect).

    request_timeout_s: per-request deadline on the socket/gRPC clients;
    a wedged app trips ABCITimeoutError instead of hanging consensus.
    0 keeps the legacy block-forever behavior. dial_timeout_s: TOTAL
    budget (attempts + backoff) for establishing an app connection at
    boot — a late-starting app delays boot, it no longer aborts it.
    retry_backoff_base_s/_max_s: the bounded exponential backoff every
    redial shares. retry_budget: consecutive failed reconnect attempts
    before the consensus conn gives up (and mempool/query conns report
    state "down" — they keep retrying in the background regardless).
    on_failure: what the CONSENSUS conn does when its in-flight request
    dies with the app process — "halt" stops the node cleanly (the
    legacy fatal behavior, default), "handshake" redials and re-runs the
    handshake replay to re-sync the app, then re-drives the in-flight
    block from scratch (never resumes mid-block)."""

    request_timeout_s: float = 0.0
    dial_timeout_s: float = 10.0
    retry_backoff_base_s: float = 0.1
    retry_backoff_max_s: float = 2.0
    retry_budget: int = 5
    on_failure: str = "halt"  # halt | handshake


@dataclass
class ExecutionConfig:
    """[execution] — deterministic parallel block execution (ours; the
    reference drives DeliverTx strictly serially).

    parallel_lanes: max concurrent execution lanes for footprint-
    disjoint tx groups (state/parallel.py) against an app that supports
    exec sessions (abci/example/sharded_kvstore.py). 1 (default) keeps
    the exact serial DeliverTx loop — the conformance oracle. Apps
    without the exec-session surface always run serial regardless.
    speculative: execute the proposed block during the prevote/
    precommit window on a background thread; the result is adopted at
    commit only if the decided block matches (hash + base app state),
    discarded otherwise — speculative state is never visible in state,
    WAL, or RPC before finalize. Defaults off."""

    parallel_lanes: int = 1
    speculative: bool = False
    # block-scoped event publish: apply_block hands the whole block's
    # tx events to the event bus in one publish_batch call (query
    # matching per distinct tag-shape, one subscriber-buffer lock per
    # block). Subscriber-observed event sequences are identical to the
    # per-tx loop (property-tested); False restores the per-tx publish
    # calls for bisecting.
    event_batch: bool = True
    # persistent work-stealing lane pool (state/lanepool.py): lanes
    # become long-lived workers created at node start instead of
    # threads spawned per block — kills the per-block wakeup convoy
    # the flight recorder measures. Default off = per-block spawning
    # (the PR 12–16 behavior). Only meaningful with parallel_lanes > 1.
    lane_pool: bool = False
    # Block-STM conflict-cone retry: > 0 arms the fixpoint engine that
    # re-executes only invalidated dependency cones in parallel rounds
    # (at most this many) instead of one serial re-run pass; falls back
    # to serial-through-overlay beyond the bound. 0 (default) keeps the
    # legacy conflict path.
    retry_max_rounds: int = 0
    # cross-height speculation chain depth: 1 (default) speculates only
    # on the committed base (the PR 12 behavior); >= 2 lets height h+1
    # execute speculatively on h's still-un-promoted overlay, chained
    # promote-or-discard at commit. Requires speculative = true.
    speculate_depth: int = 1


@dataclass
class CryptoConfig:
    """[crypto] — batch-verification engine knobs (ours; the reference
    has no crypto section). async_dispatch gates the PIPELINED call
    sites — fast-sync overlapping verify(k+1) with apply(k), and the
    consensus receive loop overlapping a vote run's WAL write with its
    device dispatch; BatchVerifier.verify() itself stays synchronous
    either way. sig_cache_size bounds the verified-signature LRU
    (crypto/sigcache.py) in entries; 0 disables the cache.

    key_type selects the validator key algorithm when a NEW private
    validator is generated ("ed25519" | "bls12381"); an existing
    priv_validator.json keeps its key. bls12381 opts the chain into the
    aggregate-signature fast lane (O(1) commit certificates) — every
    genesis validator must use it, with proofs of possession in the
    genesis doc (MIGRATION.md)."""

    async_dispatch: bool = True
    sig_cache_size: int = 65536
    key_type: str = "ed25519"


@dataclass
class StateSyncConfig:
    """[statesync] — snapshot production + light-verified bootstrap
    (ours; upstream only grew state sync in v0.34).

    enable: bootstrap a FRESH node (state at genesis) from a peer
    snapshot instead of replaying from height 1; falls back to fast
    sync when no usable snapshot is offered. snapshot_interval: take an
    app snapshot every N heights (0 = don't produce; pushed to the app
    via ABCI SetOption). chunk_size: snapshot chunk bytes.
    trust_height/trust_hash: optional operator pin — the header at
    trust_height must hash to trust_hash (hex); when unset, trust roots
    at the LOCAL genesis validator set over the height-1 commit.
    discovery_time_s: how long to keep collecting peer offers once the
    first one lands (more peers offering = parallel chunk sources).
    restore_timeout_s: overall restore budget before falling back.
    chunk_send_rate: serve-side flowrate ceiling, bytes/s."""

    enable: bool = False
    snapshot_interval: int = 0
    chunk_size: int = 65536
    # snapshots the app retains; must cover a restorer's discover->fetch
    # window in block-intervals or the chosen snapshot is evicted
    # mid-download on a fast chain
    snapshot_keep: int = 4
    trust_height: int = 0
    trust_hash: str = ""
    discovery_time_s: float = 5.0
    restore_timeout_s: float = 60.0
    chunk_send_rate: int = 5120000


@dataclass
class StorageConfig:
    """[storage] — the crash-consistency fault engine
    (libs/storagechaos.py; ours, the durability counterpart of [chaos]).

    fault_plan: path to a StorageFaultPlan JSON file
    ({"seed": N, "faults": [[target, kind, at_op], ...]}). When set,
    node boot installs a StorageFaultInjector and wraps every node DB
    and the consensus WAL in fault-injecting shims: the named target's
    at_op'th mutating operation injects the fault (torn_write /
    partial_batch / lost_tail / bit_flip) and kills the process —
    crash states become replayable experiments. Empty (default) = no
    wrapping, zero overhead.
    fault_seed: overrides the plan file's seed when != 0 (sweep one
    plan shape across seeds without rewriting the file)."""

    fault_plan: str = ""
    fault_seed: int = 0


@dataclass
class ChaosConfig:
    """[chaos] — the deterministic network-fault engine (p2p/netchaos.py;
    ours, no reference equivalent — the reference's only fault tool is
    the per-connection fuzz wrapper).

    enable: install a process-wide NetChaosController at node boot;
    every peer link's outbound path then runs the plan's rules.
    seed: the fault plan's RNG seed — same seed, same fault timeline.
    plan: path to a FaultPlan JSON file (FaultPlan.to_json shape:
    {"seed": N, "phases": [[at_s, until_s, rule], ...]}); empty = an
    empty plan (the engine idles until one is installed in-process,
    which is how the scenario runner drives it)."""

    enable: bool = False
    seed: int = 0
    plan: str = ""


@dataclass
class HandelConfig:
    """[handel] — the Handel aggregation overlay (consensus/handel.py,
    arXiv:1906.05132; ours, no reference equivalent). Only meaningful
    on BLS validator sets; default OFF, which keeps gossip
    byte-identical to the flat certificate lane.

    enable: run per-(height, round) binomial-tree aggregation sessions
    and open the HANDEL p2p channel (0x24).
    window: candidate peers contacted per level per tick.
    tick_ms: overlay gossip tick cadence.
    level_timeout_ms: a level incomplete past this stops gating higher
    levels, and a stuck frontier re-enables flat certificate gossip
    (byzantine-silent subtrees cost latency, never liveness).
    fail_budget: garbage contributions a peer may send at a level
    before it is pruned from the candidate set.
    resend_ticks: ticks between re-contacts of a silent candidate.
    reshuffle_ticks: ticks between deterministic candidate-window
    reshuffles.
    seed: the candidate-shuffle RNG seed — same seed, same walk (the
    scoring/pruning determinism story; see tests/test_handel.py)."""

    enable: bool = False
    window: int = 4
    tick_ms: int = 50
    level_timeout_ms: int = 1000
    fail_budget: int = 8
    resend_ticks: int = 4
    reshuffle_ticks: int = 8
    seed: int = 0


@dataclass
class ReplicaConfig:
    """[replica] — the self-healing replica fan-out tree
    (blockchain/replica_tree.py; ours, no reference equivalent). Only
    meaningful with [base] mode = "replica"; full nodes ignore it.

    prefer_replicas: statesync-boot from and tail OTHER REPLICAS when
    any are reachable, falling back to validators only when no replica
    peer qualifies — validators then serve O(fan-in) tier-1 replicas
    instead of O(subscribers). Off (default) keeps the flat PR-9
    topology where every replica hangs off the validators.
    max_depth: deepest tree position this replica will accept (our
    depth = chosen parent's depth + 1; validators/full nodes are depth
    0). A candidate whose adoption would exceed this is ineligible.
    lag_budget_blocks: tip age (best fleet tip minus parent tip, via
    the PR-13 push announce) past which the parent is declared lagging
    and abandoned. Also the oracle bound chaos scenarios assert on.
    silence_budget_s: seconds without any status/delivery from the
    parent before it is scored dead (SIGKILL shows up as silence long
    before the TCP session dies).
    reparent_backoff_base_s/_max_s: bounded exponential backoff
    between re-parenting attempts — the same discipline as [abci]
    redials, so a flapping fleet cannot make an orphan thrash."""

    prefer_replicas: bool = False
    max_depth: int = 4
    lag_budget_blocks: int = 8
    silence_budget_s: float = 10.0
    reparent_backoff_base_s: float = 0.5
    reparent_backoff_max_s: float = 8.0


@dataclass
class TxIndexConfig:
    """reference config/config.go:723-760"""

    indexer: str = "kv"  # kv | null
    index_tags: str = ""
    index_all_tags: bool = False
    # block-at-a-time ingest (ours): the IndexerService drains its
    # event subscription in batches and writes ONE DB write-batch (and
    # one index_generation bump) per block instead of per tx. Search
    # and get results are identical to per-tx indexing
    # (property-tested); False restores the per-tx index() path.
    batch: bool = True


@dataclass
class InstrumentationConfig:
    """reference config/config.go:767-800 (+ tracing, ours: the
    libs/tracing.py span recorder behind /debug/trace on prof_laddr)"""

    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    max_open_connections: int = 3
    namespace: str = "tendermint"
    # ring-buffered span tracing of the consensus/crypto/WAL hot path;
    # exported as chrome://tracing JSON from the prof server
    tracing: bool = False
    tracing_buffer_size: int = 131072
    # consensus stall watchdog (ours): a round dwelling past this many
    # seconds increments consensus_stalls_total{reason} and snapshots a
    # diagnostic bundle served at /debug/consensus on prof_laddr;
    # 0 disables detection (the dwell gauge still updates)
    stall_threshold_s: float = 30.0
    # per-height lifecycle timelines (libs/timeline.py) kept for the
    # newest N heights, served at /debug/timeline?height=N; 0 disables
    timeline_heights: int = 64
    # runtime lock-discipline checker (libs/lockdep.py): wraps every
    # threading.Lock/RLock created after boot with acquisition-order
    # tracking (lock-order-inversion detection), per-site hold-time
    # histograms, and the /debug/lockdep report on prof_laddr. Debug
    # mode: ~5us per acquire/release pair on a throttled CPU — leave
    # off in production (see README "Correctness tooling")
    lockdep: bool = False
    # exec-lane flight recorder (state/parallel.py): per-lane bounded
    # ring of (wakeup latency, run span, txs, conflict outcome) samples
    # taken on the THREADED parallel-exec path only; served at
    # /debug/exec and as exec_lane_* metric families. Default-on: with
    # parallel_lanes <= 1 the threaded path never runs, so the recorder
    # is structurally zero-cost
    flight_recorder: bool = True
    flight_recorder_samples: int = 512
    # synthetic wall-clock offset applied to timeline marks and
    # /debug/clock (test/chaos knob: lets an in-process localnet, which
    # shares one real clock, present skewed per-node clocks for
    # tools/fleettrace.py offset recovery to find). Leave 0 in
    # production
    clock_skew_s: float = 0.0


@dataclass
class Config:
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    abci: ABCIConfig = field(default_factory=ABCIConfig)
    execution: ExecutionConfig = field(default_factory=ExecutionConfig)
    crypto: CryptoConfig = field(default_factory=CryptoConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    handel: HandelConfig = field(default_factory=HandelConfig)
    replica: ReplicaConfig = field(default_factory=ReplicaConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)

    def set_root(self, root: str) -> "Config":
        self.base.root_dir = root
        return self

    @property
    def root_dir(self) -> str:
        return self.base.root_dir

    # --- TOML ---------------------------------------------------------------

    def to_toml(self) -> str:
        def emit(name, obj, skip=()):
            lines = [f"[{name}]"] if name else []
            for k, v in vars(obj).items():
                if k in skip:
                    continue
                if isinstance(v, bool):
                    val = "true" if v else "false"
                elif isinstance(v, (int, float)):
                    val = str(v)
                else:
                    val = '"%s"' % str(v).replace("\\", "\\\\").replace('"', '\\"')
                lines.append(f"{k} = {val}")
            return "\n".join(lines)

        # the transport selector lives in code as base.abci (reference
        # config keeps a top-level `abci` key), but TOML cannot hold both
        # a top-level `abci` value and an `[abci]` table — emit it inside
        # the section as `transport`; from_toml accepts either spelling
        abci_section = emit("abci", self.abci).replace(
            "[abci]", f'[abci]\ntransport = "{self.base.abci}"', 1)
        parts = [
            emit("", self.base, skip=("root_dir", "abci")),
            emit("rpc", self.rpc),
            emit("p2p", self.p2p),
            emit("mempool", self.mempool),
            emit("consensus", self.consensus),
            abci_section,
            emit("execution", self.execution),
            emit("crypto", self.crypto),
            emit("statesync", self.statesync),
            emit("chaos", self.chaos),
            emit("handel", self.handel),
            emit("replica", self.replica),
            emit("storage", self.storage),
            emit("tx_index", self.tx_index),
            emit("instrumentation", self.instrumentation),
        ]
        return "\n\n".join(parts) + "\n"

    @classmethod
    def from_toml(cls, text: str) -> "Config":
        try:
            import tomllib
        except ImportError:  # Python < 3.11: the vendored backport
            import tomli as tomllib

        o = tomllib.loads(text)
        cfg = cls()
        sections = {
            "rpc": cfg.rpc,
            "p2p": cfg.p2p,
            "mempool": cfg.mempool,
            "consensus": cfg.consensus,
            "execution": cfg.execution,
            "crypto": cfg.crypto,
            "statesync": cfg.statesync,
            "chaos": cfg.chaos,
            "handel": cfg.handel,
            "replica": cfg.replica,
            "storage": cfg.storage,
            "tx_index": cfg.tx_index,
            "instrumentation": cfg.instrumentation,
        }
        for k, v in o.items():
            if k == "abci" and isinstance(v, dict):
                # our [abci] section: `transport` is base.abci, the rest
                # are ABCIConfig resilience knobs
                for kk, vv in v.items():
                    if kk == "transport":
                        cfg.base.abci = vv
                    elif hasattr(cfg.abci, kk):
                        setattr(cfg.abci, kk, vv)
            elif k in sections:
                for kk, vv in v.items():
                    if hasattr(sections[k], kk):
                        setattr(sections[k], kk, vv)
            elif hasattr(cfg.base, k):
                # includes the reference's top-level `abci = "socket"`
                setattr(cfg.base, k, v)
        return cfg

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_toml())

    @classmethod
    def load(cls, path: str) -> "Config":
        with open(path) as f:
            return cls.from_toml(f.read())


def default_config() -> Config:
    return Config()


def test_config() -> Config:
    """Fast timeouts for in-process tests (reference config.TestConfig,
    config/config.go:90-99 + 612-629)."""
    cfg = Config()
    cfg.base.db_backend = "memdb"
    cfg.consensus.timeout_propose = 0.4
    cfg.consensus.timeout_propose_delta = 0.002
    cfg.consensus.timeout_prevote = 0.1
    cfg.consensus.timeout_prevote_delta = 0.002
    cfg.consensus.timeout_precommit = 0.1
    cfg.consensus.timeout_precommit_delta = 0.002
    cfg.consensus.timeout_commit = 0.02
    cfg.consensus.skip_timeout_commit = True
    cfg.consensus.peer_gossip_sleep_duration = 0.005
    cfg.consensus.peer_query_maj23_sleep_duration = 0.25
    cfg.consensus.blocktime_iota = 10_000_000  # 10ms
    return cfg


def ensure_root(root: str) -> None:
    """Create the standard directory skeleton (reference config/toml.go
    EnsureRoot)."""
    for d in ("config", "data"):
        os.makedirs(os.path.join(root, d), exist_ok=True)
