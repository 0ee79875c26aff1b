"""Node — the composition root.

Reference parity: node/node.go. `NewNode` (node/node.go:152-501) wires
DBs, state, the proxy app + ABCI handshake, mempool/evidence/consensus/
blockchain reactors, the p2p switch, event bus and tx indexer;
`OnStart` (node/node.go:504-562) brings up the event bus, RPC, the
transport listener, the switch (all reactors), and dials persistent
peers. `DefaultNewNode` (node/node.go:83) loads node key + file priv
validator from the config root.

TPU-first notes: the hot verification path (vote/commit Ed25519) runs
through the pluggable crypto BatchVerifier configured process-wide
(crypto/batch.py); the node itself is plain host-side composition and
stays framework-agnostic.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Optional

from .. import config as cfg
from .. import state as sm
from ..blockchain.reactor import BlockchainReactor
from ..blockchain.store import BlockStore
from ..consensus import ConsensusState
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import Handshaker
from ..consensus.wal import WAL
from ..evidence.pool import EvidencePool
from ..evidence.reactor import EvidenceReactor
from ..evidence.store import EvidenceStore
from ..libs.db import DB, FileDB, MemDB
from ..mempool import Mempool
from ..mempool.reactor import MempoolReactor
from ..p2p import (
    MConnConfig,
    MultiplexTransport,
    NodeInfo,
    NodeKey,
    ProtocolVersion,
    Switch,
)
from ..privval import FilePV, load_or_gen_file_pv
from ..proxy import AppConns, default_client_creator
from ..state.txindex import IndexerService, KVTxIndexer, NullTxIndexer
from ..types import GenesisDoc
from ..types.event_bus import EventBus

LOG = logging.getLogger("node")

# p2p channel ids advertised in NodeInfo (reference node/node.go:795-800,
# + our state-sync channels 0x60/0x61); the PEX channel 0x00 is appended
# only when PEX is enabled
NODE_CHANNELS = bytes([0x40, 0x20, 0x21, 0x22, 0x23, 0x30, 0x38,
                       0x60, 0x61])


def db_provider(name: str, backend: str, db_dir: str) -> DB:
    """DBProvider (reference node/node.go:60-66): one KV store per
    subsystem (blockstore / state / evidence / tx_index)."""
    if backend == "memdb":
        return MemDB()
    if backend == "native":
        from ..libs.nativedb import NativeDB

        return NativeDB(os.path.join(db_dir, name + ".ndb"))
    if backend == "remotedb":
        # gRPC-served stores (reference libs/db/remotedb): the node's
        # DBs live on a RemoteDBServer at TM_REMOTEDB_ADDR
        from ..libs.remotedb import RemoteDB

        addr = os.environ.get("TM_REMOTEDB_ADDR")
        if not addr:
            raise ValueError("db_backend=remotedb requires TM_REMOTEDB_ADDR")
        return RemoteDB(
            addr, name=name,
            backend=os.environ.get("TM_REMOTEDB_BACKEND", "memdb"))
    return FileDB(os.path.join(db_dir, name + ".db"))


def _split_addr(laddr: str) -> str:
    """tcp://host:port -> host:port"""
    return laddr.split("://", 1)[-1]


class _TelemetryTicker:
    """Replica-mode stand-in for the StallWatchdog's tick: runs the
    node's per-peer gauge refresh on a fixed cadence (there is no
    consensus machine to watch, but flow rates and peer lag still
    matter to operators of a read fleet)."""

    def __init__(self, fn, interval: float = 2.0):
        self._fn = fn
        self.interval = interval
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="replica-telemetry", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self._fn()
            except Exception:  # noqa: BLE001 - telemetry must not die
                LOG.exception("replica telemetry tick failed")


class Node:
    """A full Tendermint node (reference node/node.go:118-150 struct)."""

    def __init__(
        self,
        config: cfg.Config,
        priv_validator: FilePV,
        node_key: NodeKey,
        client_creator: Callable,
        genesis_doc: GenesisDoc,
    ):
        self.config = config
        self.genesis_doc = genesis_doc
        self.priv_validator = priv_validator
        self.node_key = node_key
        # [base] mode: "full" runs consensus; "replica" is a read node
        # that tails blocks through the fast-sync reactor forever and
        # never instantiates a ConsensusState
        self.mode = config.base.mode or "full"
        if self.mode not in ("full", "replica"):
            raise ValueError(
                f"[base] mode must be 'full' or 'replica', got "
                f"{self.mode!r}")

        root = config.root_dir
        db_dir = config.base.db_path()
        backend = config.base.db_backend
        if backend != "memdb":
            os.makedirs(db_dir, exist_ok=True)

        # MetricsProvider (node/node.go:100-113): live Prometheus
        # metrics when instrumentation is on, no-ops otherwise
        from ..metrics import nop_metrics, prometheus_metrics

        if config.instrumentation.prometheus:
            self.metrics = prometheus_metrics(
                config.instrumentation.namespace)
        else:
            self.metrics = nop_metrics()
        self._metrics_server = None
        if hasattr(priv_validator, "signatures"):  # a FilePV, not a socket
            priv_validator.signatures = self.metrics.privval.signatures

        # observability plumbing (ours; the reference's MetricsProvider
        # stops at per-reactor metrics): the crypto BatchVerifier sink
        # is process-global so every call site — VoteSet, verify_commit,
        # fast-sync, lite — reports without threading a metrics object
        # through each, and the span tracer feeds /debug/trace on the
        # prof server. Both are unwired/disabled again in stop().
        from ..crypto import batch as crypto_batch
        from ..libs import tracing

        from ..rpc import core as rpc_core

        if config.instrumentation.prometheus:
            crypto_batch.set_metrics(self.metrics.crypto)
            # the websocket event renderer is process-global the same
            # way the crypto sink is (render-once fan-out memoizes on
            # the Message, not per server)
            rpc_core.set_metrics(self.metrics.rpc)
        # [crypto] section: async dispatch flag + verified-signature
        # cache, process-wide like the metrics sink (every BatchVerifier
        # call site picks them up). The cache object is remembered so
        # stop() only uninstalls OUR cache — a second node in the same
        # process may have re-wired it since.
        crypto_batch.configure(
            async_dispatch=config.crypto.async_dispatch,
            sig_cache_size=config.crypto.sig_cache_size,
        )
        self._installed_sig_cache = crypto_batch.get_sig_cache()
        # which verifier this node got: filled in by the warm-up thread
        # once the backend is up, served at /debug/crypto
        self._verifier = {
            "backend": crypto_batch.default_backend_name(),
            "platform": None, "device_kind": None, "device_count": 0,
            "fused_kernel": None, "warmup": "pending", "gc_frozen": 0,
        }
        # this start's hold on the frozen heap (libs/tracing.FrozenHeap):
        # taken by the warm-up thread once the services are up, dropped
        # by stop() or by a start() that fails
        self._heap: Optional[tracing.FrozenHeap] = None
        self._services_up = threading.Event()
        self._enabled_tracing = False
        if config.instrumentation.tracing:
            tracer = tracing.get_tracer()
            # the first enabler owns the global tracer; a node that finds
            # it already on leaves it alone in stop() too
            self._enabled_tracing = not tracer.enabled
            tracer.enable(config.instrumentation.tracing_buffer_size)
            if self._enabled_tracing and config.instrumentation.clock_skew_s:
                # only the enabling owner may skew the process-global
                # tracer (in-process localnets share it; per-node skew
                # there comes from the per-instance Timeline instead)
                tracer.set_skew(config.instrumentation.clock_skew_s)
        # runtime lock-discipline checker ([instrumentation] lockdep):
        # enabled HERE, before any subsystem constructs its locks, so
        # the whole threaded stack below gets wrapped primitives. Same
        # first-enabler-owns contract as the tracer; the metrics sink is
        # process-global like crypto_batch's (families declared either
        # way, samples only in debug mode).
        from ..libs import lockdep

        self._enabled_lockdep = False
        if config.instrumentation.lockdep:
            self._enabled_lockdep = lockdep.enable()
        if config.instrumentation.prometheus:
            lockdep.set_metrics(self.metrics.lockdep)
            # determinism-gate telemetry sink (tools/detcheck.py):
            # process-global like the lockdep/crypto sinks — families
            # declared unconditionally, samples only when a lint/oracle
            # run is driven
            from ..tools import detcheck

            detcheck.set_metrics(self.metrics.determinism)

        # exec-lane flight recorder ([instrumentation] flight_recorder):
        # process-global bounded rings, default-on (structurally free at
        # parallel_lanes=1 — the threaded exec path never runs); the
        # metrics sink rides on BlockExecutor, this only sizes/arms it
        from ..state import parallel as _parallel

        _parallel.get_flight_recorder().configure(
            enabled=config.instrumentation.flight_recorder,
            samples=config.instrumentation.flight_recorder_samples)

        # incident ledger (libs/incident.py): one per node, fed by the
        # chaos engines (injections/heals), the stall watchdog
        # (detections) and the commit path (recoveries); served at
        # /debug/incidents. Wall stamps share the synthetic
        # [instrumentation] clock_skew_s with timeline marks and
        # /debug/clock so fleettrace rebases all three with one offset
        from ..libs import incident as incident_mod

        self.incidents = incident_mod.IncidentLedger(
            skew_s=config.instrumentation.clock_skew_s)
        self.incidents.set_metrics(self.metrics.incident)

        # --- storage (node/node.go:162-171) --------------------------
        # crash-consistency fault engine ([storage] fault_plan, ours):
        # when armed, every node DB and the consensus WAL are wrapped in
        # seeded fault-injecting shims (libs/storagechaos.py) — the
        # storage-layer counterpart of the [chaos] network engine
        from ..libs import storagechaos

        self.fault_injector = None
        if config.storage.fault_plan:
            with open(os.path.join(root, config.storage.fault_plan)
                      if not os.path.isabs(config.storage.fault_plan)
                      else config.storage.fault_plan) as f:
                plan = storagechaos.StorageFaultPlan.from_json(f.read())
            if config.storage.fault_seed:
                plan.seed = config.storage.fault_seed
            self.fault_injector = storagechaos.StorageFaultInjector(
                plan, exit_process=True)
            self.fault_injector.set_metrics(
                self.metrics.recovery.storage_faults)
            self.fault_injector.set_incidents(self.incidents)

        def _db(name: str):
            d = db_provider(name, backend, db_dir)
            if self.fault_injector is not None:
                d = storagechaos.FaultyDB(d, self.fault_injector,
                                          "db:" + name)
            return d

        self._db = _db
        self.block_store_db = _db("blockstore")
        self.state_db = _db("state")
        self.block_store = BlockStore(self.block_store_db)

        state = sm.load_state_from_db_or_genesis(self.state_db, genesis_doc)

        # --- proxy app + handshake (node/node.go:193-206) ------------
        # every conn rides a ResilientClient supervisor ([abci] config):
        # request deadlines + duration metrics, backoff redial, and the
        # consensus-conn failure policy (halt cleanly, or re-run the
        # handshake replay on reconnect and re-drive the in-flight block)
        self.proxy_app = AppConns(
            client_creator, config=config.abci, metrics=self.metrics.abci,
            on_fatal=self._on_abci_fatal)
        self.proxy_app.start()
        self.proxy_app.set_consensus_resync(self._resync_app)
        self.event_bus = EventBus()
        import time as _time

        _recovery_t0 = _time.monotonic()
        handshaker = Handshaker(
            self.state_db, state, self.block_store, genesis_doc, self.event_bus
        )
        handshaker.handshake(self.proxy_app)
        # recovery telemetry (/debug/recovery + recovery_* families):
        # what this boot had to repair — completed below once the tx
        # index has converged too
        self._recovery = {
            "handshake_outcome": "ok",
            "replayed_blocks": handshaker.n_blocks,
            "replay_from": handshaker.replay_from,
            "replay_to": handshaker.replay_to,
            "reindexed_blocks": 0,
            "recovery_time_s": 0.0,
        }
        if handshaker.n_blocks:
            self.metrics.recovery.replayed_blocks.inc(handshaker.n_blocks)
        # reload: handshake may have advanced state via replay
        state = sm.load_state_from_db_or_genesis(self.state_db, genesis_doc)

        # incident view of the boot: fresh heights start beyond the tip
        # we restarted with. An unclean shutdown is discovered either by
        # the handshake having blocks to replay OR by the dirty-boot
        # marker a clean stop() would have removed — a crash between two
        # heights leaves app and chain state equal (nothing to replay)
        # but still skips the marker cleanup. Ledger it (injection) and
        # mark the replay completion (heal); the first commit at a fresh
        # height closes it with the node-local MTTR.
        self._dirty_marker = (os.path.join(db_dir, "dirty")
                              if backend != "memdb" else None)
        unclean_boot = (self._dirty_marker is not None
                        and os.path.exists(self._dirty_marker))
        self.incidents.set_height(state.last_block_height)
        if handshaker.n_blocks or unclean_boot:
            # uid carries the moniker so an orchestrator-side kill
            # record (fleettrace extra_injections) merges with the
            # reboot's own view of the same incident
            _crash_uid = f"crash:{config.base.moniker}"
            self.incidents.open_incident(
                _crash_uid, "crash",
                replayed_blocks=handshaker.n_blocks,
                replay_from=handshaker.replay_from,
                replay_to=handshaker.replay_to)
            # the recovery handshake IS the crash detector: a stall
            # watchdog can't classify a dead process, but the reboot
            # classifying its own unclean shutdown can — and against an
            # orchestrator-side kill stamp (fleettrace extra_injections)
            # this detection carries the fleet-level MTTD
            self.incidents.note_detection(
                "unclean_shutdown", height=state.last_block_height,
                replayed_blocks=handshaker.n_blocks)
            self.incidents.note_heal(
                _crash_uid, replayed_blocks=handshaker.n_blocks)

        # fast-sync only makes sense with peers to sync from; a sole
        # validator skips it (reference node/node.go:240-246). A replica
        # ALWAYS fast-syncs — tailing blocks is its whole job
        fast_sync = config.base.fast_sync
        if self.mode == "replica":
            fast_sync = True
        elif len(state.validators) == 1 and priv_validator is not None:
            addr = priv_validator.get_address()
            if state.validators.has_address(addr):
                fast_sync = False

        # state-sync bootstrap: only a FRESH node (state still at
        # genesis) restores from a snapshot; anyone else already has
        # history and fast-syncs the difference
        state_sync = (config.statesync.enable
                      and state.last_block_height == 0
                      and fast_sync)

        # --- mempool (node/node.go:255-271) --------------------------
        self.mempool = Mempool(
            config.mempool,
            self.proxy_app.mempool,
            height=state.last_block_height,
            metrics=self.metrics.mempool,
        )
        if config.mempool.wal_path:
            self.mempool.init_wal(os.path.join(root, config.mempool.wal_path))
        self.mempool_reactor = MempoolReactor(config.mempool, self.mempool)

        # --- evidence (node/node.go:273-291) -------------------------
        self.evidence_db = _db("evidence")
        evidence_store = EvidenceStore(self.evidence_db)
        self.evidence_pool = EvidencePool(
            evidence_store,
            state,
            load_validators=lambda h: sm.load_validators(self.state_db, h),
        )
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)

        # --- block executor + blockchain reactor (node/node.go:293-307)
        self.block_exec = sm.BlockExecutor(
            self.state_db,
            self.proxy_app.consensus,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            metrics=self.metrics.state,
            exec_config=config.execution,
        )

        # --- consensus (node/node.go:309-326) ------------------------
        # replica mode builds NO consensus machinery at all: the
        # blockchain reactor tails blocks forever and a channel
        # absorber keeps the p2p protocol intact for validator peers
        self._consensus_absorber = None
        self.replica_tree = None
        if self.mode == "full":
            wal = None
            if config.consensus.wal_path:
                wal_path = config.consensus.wal_file(root)
                os.makedirs(os.path.dirname(wal_path), exist_ok=True)
                wal = WAL(wal_path,
                          corrupted_counter=self.metrics.consensus.wal_corrupted,
                          records_counter=self.metrics.consensus.wal_records)
                if self.fault_injector is not None:
                    from ..libs.storagechaos import wrap_wal

                    wrap_wal(wal, self.fault_injector)
            self.consensus_state = ConsensusState(
                config.consensus,
                state,
                self.block_exec,
                self.block_store,
                mempool=self.mempool,
                evpool=self.evidence_pool,
                event_bus=self.event_bus,
                priv_validator=priv_validator,
                wal=wal,
                metrics=self.metrics.consensus,
                handel_cfg=config.handel,
            )
            if self.consensus_state.handel is not None:
                self.consensus_state.handel.set_metrics(self.metrics.handel)
            # per-height lifecycle timelines (libs/timeline.py): the
            # recorder lives on the ConsensusState (per-node, not
            # process-global); marks are a dict write per consensus
            # event, so this defaults on
            if config.instrumentation.timeline_heights > 0:
                self.consensus_state.timeline.enable(
                    config.instrumentation.timeline_heights)
            if config.instrumentation.clock_skew_s:
                # synthetic skew (chaos/fleettrace testing): marks and
                # /debug/clock shift together so offset recovery sees a
                # consistent per-node clock
                self.consensus_state.timeline.set_skew(
                    config.instrumentation.clock_skew_s)
            self.consensus_state.incidents = self.incidents
            # while state sync runs, consensus must stay parked
            # (fast_sync mode) and the blockchain pool must NOT start at
            # height 1 — resume_fast_sync re-arms it at the restored
            # height
            self.consensus_reactor = ConsensusReactor(
                self.consensus_state, fast_sync=fast_sync or state_sync
            )
            self.blockchain_reactor = BlockchainReactor(
                state,
                self.block_exec,
                self.block_store,
                fast_sync and not state_sync,
                consensus_reactor=self.consensus_reactor,
            )
        else:
            from ..consensus.reactor import ReplicaConsensusAbsorber

            self.consensus_state = None
            self.consensus_reactor = None
            self._consensus_absorber = ReplicaConsensusAbsorber(
                handel=config.handel.enable)
            self.blockchain_reactor = BlockchainReactor(
                state,
                self.block_exec,
                self.block_store,
                fast_sync and not state_sync,
                tail_forever=True,
            )
            # the self-healing fan-out tree (blockchain/replica_tree.py):
            # scores upstream candidates from the status exchange, gates
            # the pool to exactly one parent, and re-parents on
            # death / partition / blown lag budget
            from ..blockchain.replica_tree import ReplicaTreeManager

            self.replica_tree = ReplicaTreeManager(
                config.replica, node_key.id, config.base.moniker,
                self.block_store.height, self.block_store.base,
                metrics=self.metrics.replica, ledger=self.incidents)
            self.blockchain_reactor.attach_tree(self.replica_tree)

        # --- tx indexer (node/node.go:329-349) -----------------------
        if config.tx_index.indexer == "kv":
            self.tx_index_db = _db("tx_index")
            tags = [
                t.strip()
                for t in config.tx_index.index_tags.split(",")
                if t.strip()
            ]
            self.tx_indexer = KVTxIndexer(
                self.tx_index_db,
                index_tags=tags,
                index_all_tags=config.tx_index.index_all_tags,
            )
        else:
            self.tx_indexer = NullTxIndexer()
        # index convergence: re-ingest committed blocks the crashed
        # process never durably indexed (torn ingest batch, events lost
        # before the service subscribed, handshake-replayed blocks) —
        # after this, the index holds exactly the committed txs
        from ..state.txindex import recover_index

        self._recovery["reindexed_blocks"] = recover_index(
            self.tx_indexer, self.block_store, self.state_db, logger=LOG)
        self._recovery["recovery_time_s"] = round(
            _time.monotonic() - _recovery_t0, 6)
        self.metrics.recovery.recovery_time.observe(
            self._recovery["recovery_time_s"])
        self.indexer_service = IndexerService(
            self.tx_indexer, self.event_bus,
            batch=config.tx_index.batch,
            stage_profile=self.block_exec.stage_profile,
        )
        # push-based tip announcement: peers (tailing replicas above
        # all) learn a committed height in one RTT instead of waiting
        # out their status poll
        self.blockchain_reactor.enable_tip_announce(self.event_bus)

        # --- p2p (node/node.go:366-464) ------------------------------
        channels = NODE_CHANNELS + (b"\x00" if config.p2p.pex else b"")
        if config.handel.enable:
            # Handel overlay channel: advertised only when [handel] is
            # on, so a default build's handshake stays byte-identical
            channels += bytes([0x24])
        node_info = NodeInfo(
            protocol_version=ProtocolVersion(),
            id=node_key.id,
            listen_addr=_split_addr(config.p2p.laddr),
            network=genesis_doc.chain_id,
            version="tendermint-tpu",
            channels=channels,
            moniker=config.base.moniker,
        )
        mconfig = MConnConfig(
            send_rate=config.p2p.send_rate,
            recv_rate=config.p2p.recv_rate,
            max_packet_msg_payload_size=config.p2p.max_packet_msg_payload_size,
            flush_throttle=config.p2p.flush_throttle_timeout,
        )
        # ABCI-query-based peer filters (reference node/node.go:378-416):
        # when filter_peers is set the app vets every connection via
        # /p2p/filter/addr/<addr> (pre-handshake) and /p2p/filter/id/<id>
        # (post-handshake); a non-zero response code rejects the peer
        conn_filters = []
        peer_filters = []
        if config.base.filter_peers:
            from ..abci.types import RequestQuery
            from ..p2p.transport import RejectedError

            def _abci_addr_filter(_conn, remote: str) -> None:
                res = self.proxy_app.query.query(
                    RequestQuery(path=f"/p2p/filter/addr/{remote}"))
                if res.code != 0:
                    raise RejectedError(
                        f"app rejected addr {remote}: code {res.code}")

            def _abci_id_filter(their_info) -> None:
                res = self.proxy_app.query.query(
                    RequestQuery(path=f"/p2p/filter/id/{their_info.id}"))
                if res.code != 0:
                    raise RejectedError(
                        f"app rejected id {their_info.id[:8]}: code {res.code}")

            conn_filters.append(_abci_addr_filter)
            peer_filters.append(_abci_id_filter)

        # legacy single-connection fuzz mode ([p2p] test_fuzz*): every
        # peer socket is wrapped in a FuzzedConnection built from TOML —
        # previously the config keys existed but nothing consumed them
        fuzz_wrap = None
        if config.p2p.test_fuzz:
            from ..p2p.fuzz import FuzzConnConfig, FuzzedConnection

            fuzz_cfg = FuzzConnConfig(
                mode=config.p2p.test_fuzz_mode,
                max_delay=config.p2p.test_fuzz_delay_ms / 1000.0,
                prob_drop_rw=config.p2p.test_fuzz_prob_drop_rw,
                seed=config.p2p.test_fuzz_seed,
            )
            fuzz_wrap = lambda conn: FuzzedConnection(conn, fuzz_cfg)  # noqa: E731

        # network-fault engine ([chaos]): install the process-wide
        # controller BEFORE the switch exists so every peer link it
        # creates runs through the plan's rules
        self._chaos_installed = False
        if config.chaos.enable:
            from ..p2p import netchaos

            if config.chaos.plan:
                with open(os.path.join(root, config.chaos.plan)
                          if not os.path.isabs(config.chaos.plan)
                          else config.chaos.plan) as f:
                    plan = netchaos.FaultPlan.from_json(f.read())
                plan.seed = config.chaos.seed or plan.seed
            else:
                plan = netchaos.FaultPlan(seed=config.chaos.seed)
            ctrl = netchaos.NetChaosController(
                plan, metrics=self.metrics.p2p)
            ctrl.set_incidents(self.incidents)
            netchaos.install(ctrl)
            self._chaos_installed = True

        self.transport = MultiplexTransport(
            node_info, node_key, conn_filters=conn_filters,
            fuzz_wrap=fuzz_wrap)
        # peer trust scoring (p2p/trust.py; reference p2p/trust/store.go):
        # persisted per-peer metrics the switch consults on admission and
        # persistent-peer reconnects
        from ..p2p.trust import TrustMetricStore

        self.trust_store = TrustMetricStore(
            db=_db("trust_history")
        )
        self.sw = Switch(
            self.transport,
            mconfig=mconfig,
            max_inbound=config.p2p.max_num_inbound_peers,
            max_outbound=config.p2p.max_num_outbound_peers,
            metrics=self.metrics.p2p,
            trust_store=self.trust_store,
            peer_filters=peer_filters,
        )
        self.sw.add_reactor("MEMPOOL", self.mempool_reactor)
        self.sw.add_reactor("BLOCKCHAIN", self.blockchain_reactor)
        self.sw.add_reactor(
            "CONSENSUS",
            self.consensus_reactor if self.consensus_reactor is not None
            else self._consensus_absorber)
        self.sw.add_reactor("EVIDENCE", self.evidence_reactor)

        # --- state sync (statesync/; upstream v0.34 leapfrog) --------
        # the snapshot reactor always serves (discovery + chunks);
        # the StateSyncer restore pipeline only exists on a fresh node
        # that opted in via [statesync] enable
        from ..statesync.reactor import SnapshotReactor
        from ..statesync.store import SnapshotStore

        self.statesync_db = _db("statesync")
        self.snapshot_store = SnapshotStore(
            self.statesync_db, self.proxy_app.query,
            metrics=self.metrics.statesync)
        self.snapshot_reactor = SnapshotReactor(
            self.snapshot_store, self.block_store, self.state_db,
            chunk_send_rate=config.statesync.chunk_send_rate,
            metrics=self.metrics.statesync)
        self.sw.add_reactor("STATESYNC", self.snapshot_reactor)
        self._boot_state = state
        self.state_syncer = None
        if state_sync:
            from ..statesync.restore import StateSyncer

            # [replica] prefer_replicas: boot from replica-served
            # snapshots (the tree manager knows which peers advertised
            # replica mode), falling back to validators only when no
            # replica qualifies
            prefer = None
            if (self.replica_tree is not None
                    and config.replica.prefer_replicas):
                prefer = self.replica_tree.is_replica_peer
            self.state_syncer = StateSyncer(
                self.snapshot_reactor, genesis_doc, self.state_db,
                self.block_store, self.proxy_app.query,
                config.statesync, metrics=self.metrics.statesync,
                on_complete=self._on_statesync_complete,
                peer_preference=prefer)

        # PEX reactor + address book (node/node.go:417-464)
        self.pex_reactor = None
        self.addr_book = None
        if config.p2p.pex:
            from ..p2p.pex import AddrBook, PEXReactor

            addr_book_path = os.path.join(root, config.p2p.addr_book_file)
            os.makedirs(os.path.dirname(addr_book_path) or ".", exist_ok=True)
            self.addr_book = AddrBook(
                addr_book_path, strict=config.p2p.addr_book_strict
            )
            self.addr_book.add_our_address(node_info.listen_addr, node_key.id)
            seeds = [s.strip() for s in config.p2p.seeds.split(",") if s.strip()]
            self.pex_reactor = PEXReactor(
                self.addr_book,
                seeds=seeds,
                seed_mode=config.p2p.seed_mode,
            )
            self.sw.add_reactor("PEX", self.pex_reactor)

        # consensus stall watchdog (consensus/state.py StallWatchdog):
        # publishes round dwell, trips on threshold with a diagnostic
        # bundle at /debug/consensus, and carries the per-peer network
        # telemetry refresh (flow rates, queue depth, height lag) on its
        # tick so peer gauges update even between scrapes
        self.watchdog = None
        self._telemetry_ticker = None
        if self.consensus_state is not None:
            from ..consensus.state import StallWatchdog

            self.watchdog = StallWatchdog(
                self.consensus_state,
                threshold_s=config.instrumentation.stall_threshold_s,
                switch=self.sw,
            )
            self.watchdog.on_tick.append(self._refresh_peer_telemetry)
        else:
            # replicas have no watchdog (nothing to stall) but the
            # per-peer network gauges still need a cadence
            self._telemetry_ticker = _TelemetryTicker(
                self._refresh_peer_telemetry)

        self._rpc_server = None
        self._grpc_server = None
        self._prof_server = None
        self._running = False
        self._stopped = threading.Event()

    # --- lifecycle (node/node.go:504-607) ----------------------------

    def start(self) -> None:
        self._running = True
        self._stopped.clear()
        from ..libs import tracing

        # set when the services below are up, or have failed: the
        # warm-up thread freezes the heap no earlier
        self._services_up = up = threading.Event()
        self._heap = tracing.FrozenHeap(self._publish_frozen)
        try:
            self._start_services()
        except BaseException:
            self._heap.drop()
            raise
        finally:
            up.set()

    def _publish_frozen(self, frozen: int) -> None:
        self._verifier["gc_frozen"] = frozen
        self.metrics.runtime.gc_frozen_objects.set(frozen)

    def _start_services(self) -> None:
        # dirty-boot marker: exists for exactly the running lifetime of
        # the node; a boot that finds one knows the previous run never
        # reached its clean stop() (see the incident block in __init__)
        if self._dirty_marker is not None:
            try:
                with open(self._dirty_marker, "w"):
                    pass
            except OSError:
                LOG.warning("could not write dirty-boot marker %s",
                            self._dirty_marker)
        self.event_bus.start()
        self.indexer_service.start()
        self._start_verify_warmup()

        if self.config.rpc.laddr:
            self._start_rpc()
        if self.config.base.prof_laddr:
            self._start_prof()
        if (self.config.instrumentation.prometheus
                and self.metrics.registry is not None):
            from ..libs.metrics import MetricsServer

            addr = self.config.instrumentation.prometheus_listen_addr
            host, _, port = addr.rpartition(":")
            self._metrics_server = MetricsServer(
                self.metrics.registry, host or "0.0.0.0", int(port))
            self._metrics_server.start()

        laddr = _split_addr(self.config.p2p.laddr)
        self.transport.listen(laddr)
        # rewrite advertised addr with the bound port (useful for :0)
        self.transport.node_info.listen_addr = self.transport.listen_addr
        self.sw.start()

        peers = [
            p.strip()
            for p in self.config.p2p.persistent_peers.split(",")
            if p.strip()
        ]
        if peers:
            self.sw.dial_peers_async(peers, persistent=True)
        if self.watchdog is not None:
            self.watchdog.start()
        if self._telemetry_ticker is not None:
            self._telemetry_ticker.start()

        # snapshot production: push the [statesync] producer knobs to
        # the app over ABCI SetOption (works for in-proc and remote
        # apps alike); the app snapshots at commit() on its own
        if self.config.statesync.snapshot_interval > 0:
            from ..abci.types import RequestSetOption

            for key, value in (
                ("snapshot_interval",
                 self.config.statesync.snapshot_interval),
                ("snapshot_chunk_size", self.config.statesync.chunk_size),
                ("snapshot_keep", self.config.statesync.snapshot_keep),
            ):
                try:
                    res = self.proxy_app.query.set_option(
                        RequestSetOption(key=key, value=str(value)))
                    if res.code != 0:
                        LOG.warning("app refused %s=%s: %s",
                                    key, value, res.log)
                except Exception:  # noqa: BLE001 - optional capability
                    LOG.warning("app does not accept %s; snapshots "
                                "disabled app-side", key)
        if self.state_syncer is not None:
            self.state_syncer.start()

    def _on_abci_fatal(self, exc: Exception) -> None:
        """The consensus app connection is unrecoverable ([abci]
        on_failure = "halt", or a failed handshake re-sync): stop the
        node cleanly — WALs sync, stores close, peers get hangups —
        instead of wedging with a dead app. Runs on a separate thread:
        the failure surfaces inside the consensus thread, and stop()
        joins reactors that may be waiting on that very thread."""
        LOG.error("consensus app connection unrecoverable: %s; "
                  "halting node cleanly", exc)
        threading.Thread(target=self.stop, name="abci-fatal-stop",
                         daemon=True).start()

    def _resync_app(self, client) -> None:
        """on_failure = "handshake": re-sync a restarted app (app-only
        replay against the RAW reconnected client; chain state is never
        touched — the in-flight block re-drives itself afterwards)."""
        from ..consensus.replay import resync_app

        state = sm.load_state_from_db_or_genesis(
            self.state_db, self.genesis_doc)
        resync_app(client, state, self.block_store, self.state_db,
                   self.genesis_doc)

    def _on_statesync_complete(self, state) -> None:
        """Restore finished (state holds the snapshot-height State) or
        gave up (None): either way fast sync takes over — from the
        anchor height or, on fallback, from genesis."""
        if state is None:
            LOG.warning("state sync did not complete; fast-syncing the "
                        "whole chain instead")
            state = self._boot_state
        self.blockchain_reactor.resume_fast_sync(state)

    def _refresh_peer_telemetry(self) -> None:
        """Per-peer network gauges, refreshed each watchdog tick: the
        MConnection flowrate monitors (send/recv EWMA), pending send
        queue depth, and consensus height lag from PeerState."""
        m = self.metrics.p2p
        our_height = (self.consensus_state.rs.height
                      if self.consensus_state is not None
                      else self.block_store.height())
        for p in self.sw.peers.list():
            if not p.is_running():
                # racing removal: writing now would re-create series the
                # removal path just pruned
                continue
            try:
                st = p.status()
            except Exception:  # noqa: BLE001 - peer may be tearing down
                continue
            m.peer_send_rate.with_labels(p.id).set(
                st["SendMonitor"]["CurRate"])
            m.peer_recv_rate.with_labels(p.id).set(
                st["RecvMonitor"]["CurRate"])
            m.peer_pending_send.with_labels(p.id).set(
                sum(ch["SendQueueSize"] for ch in st["Channels"]))
            ps = p.get("consensus_peer_state")
            if ps is not None:
                peer_h = ps.get_height()
                if peer_h > 0:
                    m.peer_lag_blocks.with_labels(p.id).set(
                        max(0, our_height - peer_h))
        if self.replica_tree is not None:
            # the fan-out tree's budget enforcement (lag/silence) and
            # orphan re-attach ride the same telemetry cadence
            self.replica_tree.evaluate()

    def _start_rpc(self) -> None:
        from ..rpc.cache import RPCCache
        from ..rpc.core import RPCEnvironment
        from ..rpc.server import RPCServer

        env = RPCEnvironment(self)
        addr = _split_addr(self.config.rpc.laddr)
        host, _, port = addr.rpartition(":")
        host = host or "127.0.0.1"
        self._rpc_server = RPCServer(
            env, host, int(port), unsafe=self.config.rpc.unsafe,
            max_open_connections=self.config.rpc.max_open_connections,
            cache=RPCCache(self.config.rpc.cache_bytes,
                           metrics=self.metrics.rpc),
            ws_send_queue=self.config.rpc.ws_send_queue,
            ws_slow_policy=self.config.rpc.ws_slow_policy,
            metrics=self.metrics.rpc,
        )
        self._rpc_server.start()
        if self.config.rpc.grpc_laddr:
            from ..rpc.grpc_api import BroadcastAPIServer

            gaddr = _split_addr(self.config.rpc.grpc_laddr)
            ghost, _, gport = gaddr.rpartition(":")
            self._grpc_server = BroadcastAPIServer(env, ghost or "127.0.0.1", int(gport))
            self._grpc_server.start()

    def _start_verify_warmup(self) -> None:
        """Resolve the batch verifier once, on a daemon thread, and say
        which device it got: one log line plus the /debug/crypto fields
        (backend, platform, device_kind, device_count, fused_kernel,
        warmup, batch_cutoff). With a device backend ("jax"/"adaptive")
        the thread then pre-compiles the hot verify-kernel bucket shapes
        and calibrates the adaptive cutoff (crypto/jaxed25519/
        verify.warmup), so the first-compile cost never lands inside the
        live vote path; a warm-up that fails — backend init, a shape the
        compiler refuses — is an ERROR here, not a surprise in the first
        live batch. The host OpenSSL backend ("cpu") never touches jax.
        TM_TPU_WARMUP=0 skips the compile, not the report. Whatever the
        outcome, the heap as the start left it — the services start()
        built, jax, the kernels loaded here — is then collected once and
        frozen (libs/tracing "the frozen heap"), before `warmup` says
        so: whoever waits for the warm-up's end starts on a frozen
        heap."""
        from ..crypto import batch as crypto_batch

        info = self._verifier
        up, heap = self._services_up, self._heap

        def _go():
            outcome = _warm()
            up.wait()
            heap.take()
            info["warmup"] = outcome

        def _warm() -> str:
            if info["backend"] == "cpu":
                LOG.info("crypto verifier: backend=cpu (host OpenSSL); "
                         "no device initialised")
                return "disabled"
            try:
                import jax

                from ..crypto.jaxed25519 import verify as jv

                dev = jax.devices()[0]
                use_pallas, interp = jv._pallas_flags()
                info.update(
                    platform=dev.platform, device_kind=dev.device_kind,
                    device_count=len(jax.devices()),
                    fused_kernel=("interpret" if interp else "compiled")
                    if use_pallas else "off")
                LOG.info(
                    "crypto verifier: backend=%s platform=%s device_kind=%r "
                    "devices=%d fused_kernel=%s", info["backend"],
                    info["platform"], info["device_kind"],
                    info["device_count"], info["fused_kernel"])
                if os.environ.get("TM_TPU_WARMUP", "1") == "0":
                    return "disabled"
                env = os.environ.get("TM_TPU_WARMUP_BUCKETS")
                buckets = (tuple(int(x) for x in env.split(",") if x)
                           if env else (8, 16, 64))
                jv.warmup(buckets=buckets)
                LOG.info("verify warm-up ok: buckets=%s, adaptive batch "
                         "cutoff %d", list(buckets),
                         crypto_batch.effective_batch_min())
                return "ok"
            except Exception as e:  # noqa: BLE001 - thread boundary
                LOG.exception("verify warm-up FAILED with the %s backend: "
                              "the first live batch will meet the same "
                              "error", info["backend"])
                return f"error: {type(e).__name__}: {e}"

        t = threading.Thread(target=_go, name="verify-warmup", daemon=True)
        t.start()
        self._verify_warmup_thread = t

    def _start_prof(self) -> None:
        """pprof-equivalent profile endpoint (reference node/node.go:468-474)
        plus the node-scoped debug routes: /debug/consensus (stall
        watchdog bundle) rides here next to /debug/trace and
        /debug/timeline."""
        from ..rpc.prof import ProfServer

        addr = _split_addr(self.config.base.prof_laddr)
        host, _, port = addr.rpartition(":")
        self._prof_server = ProfServer(
            host or "127.0.0.1", int(port),
            timeline=(self.consensus_state.timeline
                      if self.consensus_state is not None else None),
            providers={
                "/debug/consensus": lambda q: self._consensus_status(),
                "/debug/statesync": lambda q: self._statesync_status(),
                "/debug/abci": lambda q: self.proxy_app.status(),
                "/debug/mempool": lambda q: self.mempool.status(),
                "/debug/crypto": lambda q: self._crypto_status(),
                "/debug/rpc": lambda q: self._rpc_status(),
                "/debug/lockdep": lambda q: self._lockdep_status(),
                "/debug/recovery": lambda q: self._recovery_status(),
                "/debug/determinism": lambda q: self._determinism_status(),
                "/debug/exec": lambda q: self._exec_status(),
                "/debug/incidents": lambda q: self._incidents_status(),
                "/debug/handel": lambda q: self._handel_status(),
                "/debug/replica": lambda q: self._replica_status(),
            },
            identity={"node_id": self.node_key.id,
                      "moniker": self.config.base.moniker},
            clock_skew_s=self.config.instrumentation.clock_skew_s,
        )
        self._prof_server.start()

    def _handel_status(self) -> dict:
        """/debug/handel: per-session Handel overlay state (level fill,
        frontier, stuck level, contribution counters). Registered in
        BOTH validator and replica modes — the fleettrace provider
        contract requires an identical route surface — and reports
        {"enabled": false} wherever the overlay is off or absent."""
        if self.consensus_state is None:
            return {"enabled": False, "mode": "replica"}
        return self.consensus_state.handel_status()

    def _replica_status(self) -> dict:
        """/debug/replica: the fan-out tree view (parent, depth, lag,
        switch history, candidate scores). Registered in BOTH modes —
        the fleettrace provider contract requires an identical route
        surface — and reports {"enabled": false} on full nodes."""
        if self.replica_tree is None:
            return {"enabled": False, "mode": self.mode}
        return self.replica_tree.status()

    def _incidents_status(self) -> dict:
        """/debug/incidents: the incident ledger (libs/incident.py).
        Poking the chaos controller's status first lets phase
        expirations on a QUIET network (a healed partition with no
        traffic yet) be observed by the scrape itself."""
        from ..p2p import netchaos

        ctrl = netchaos.get_controller()
        if ctrl is not None:
            ctrl.status()  # side effect: observe phase transitions
        return self.incidents.status()

    def _exec_status(self) -> dict:
        """/debug/exec: the exec-lane flight recorder report (per-lane
        wakeup/busy plus retry-round and work-steal attribution) and
        the executor's configured lane count — empty-but-stable shape
        on a lanes=1 or replica node (the threaded path never runs
        there)."""
        from ..state import parallel as par

        rec = par.get_flight_recorder()
        report = rec.report()
        report["retry"] = rec.retry_stats()
        exec_cfg = (self.block_exec.exec_config
                    if self.block_exec is not None else None)
        report["parallel_lanes"] = (
            exec_cfg.parallel_lanes if exec_cfg is not None else 1)
        report["lane_pool"] = bool(
            exec_cfg is not None and getattr(exec_cfg, "lane_pool", False))
        return report

    def _consensus_status(self) -> dict:
        """/debug/consensus: the watchdog bundle on a full node; a
        minimal never-stalled shape on a replica so monitors scraping a
        mixed fleet keep one code path."""
        if self.watchdog is not None:
            return self.watchdog.status()
        return {
            "mode": "replica",
            "height": self.block_store.height(),
            "dwell_s": 0.0, "threshold_s": 0.0,
            "stalls_total": 0, "stalls": [],
            "live": {"peers": [], "absorbed_consensus_msgs":
                     (self._consensus_absorber.absorbed
                      if self._consensus_absorber is not None else 0)},
        }

    def _recovery_status(self) -> dict:
        """/debug/recovery: what this boot repaired (handshake outcome,
        replayed-block span, re-indexed blocks) plus the LIVE WAL
        corruption count and, when the fault engine is armed, its
        injection ledger — tm-monitor tags [REPLAYED h..h'] and
        degrades health on corruption from this."""
        out = dict(self._recovery)
        wal_corrupted = 0
        if self.consensus_state is not None:
            wal_corrupted = getattr(self.consensus_state.wal,
                                    "corrupted_records", 0)
        out["wal_corrupted_records"] = wal_corrupted
        if self.fault_injector is not None:
            out["fault_engine"] = self.fault_injector.status()
        return out

    def _rpc_status(self) -> dict:
        """/debug/rpc: response-cache pressure + websocket fan-out
        state (queue occupancy, drops, render-once counter)."""
        if self._rpc_server is None:
            return {"enabled": False}
        return self._rpc_server.debug_status()

    def _crypto_status(self) -> dict:
        """The /debug/crypto bundle: which verifier the node resolved at
        start-up (backend, platform, device_kind, device_count, the most
        chips a device batch has been cut over, fused_kernel, warm-up
        outcome, the adaptive cutoff in force),
        compile-once layer state (cache dir, AOT hit/miss counters, any
        compile in progress — a node wedged compiling at boot shows up
        here), plus the live async-batch count."""
        from ..crypto import batch as crypto_batch
        from ..crypto import kernel_cache

        out = kernel_cache.status()
        out["inflight_batches"] = crypto_batch.inflight_count()
        out["verifier"] = dict(
            self._verifier,
            devices_used=crypto_batch.devices_used(),
            batch_cutoff=crypto_batch.effective_batch_min())
        return out

    def _lockdep_status(self) -> dict:
        """/debug/lockdep: the acquisition graph, inversion witnesses,
        and per-site hold stats (empty shells when the mode is off)."""
        from ..libs import lockdep

        return lockdep.report()

    def _determinism_status(self) -> dict:
        """/debug/determinism: the determinism gate's runtime view —
        last static-lint summary plus the replay-divergence oracle's
        run/divergence counters (zero-shells until a run is driven)."""
        from ..tools import detcheck

        return detcheck.report()

    def _statesync_status(self) -> dict:
        """The /debug/statesync bundle: serve-side snapshot inventory +
        chunk counters, plus restore progress when this node is (or
        was) bootstrapping."""
        out = self.snapshot_reactor.status()
        if self.state_syncer is not None:
            out["restore"] = self.state_syncer.status()
        return out

    @property
    def rpc_listen_addr(self) -> Optional[str]:
        return self._rpc_server.listen_addr if self._rpc_server else None

    def stop(self) -> None:
        if not self._running:
            return
        self._running = False
        if self.state_syncer is not None:
            self.state_syncer.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._telemetry_ticker is not None:
            self._telemetry_ticker.stop()
        for srv in (self._rpc_server, self._grpc_server, self._prof_server,
                    self._metrics_server):
            if srv is not None:
                srv.stop()
        # unwire the process-global observability hooks this node set up
        # so back-to-back nodes (tests) don't report into a dead registry.
        # Only if the installed sink is still OURS — a second instrumented
        # node in the same process may have re-wired them since.
        from ..crypto import batch as crypto_batch

        if self.config.instrumentation.prometheus:
            if crypto_batch.get_metrics() is self.metrics.crypto:
                crypto_batch.set_metrics(None)
            from ..rpc import core as rpc_core

            if rpc_core.get_metrics() is self.metrics.rpc:
                rpc_core.set_metrics(None)
        if (self._installed_sig_cache is not None
                and crypto_batch.get_sig_cache() is self._installed_sig_cache):
            crypto_batch.set_sig_cache(None)
        if self._heap is not None:
            self._heap.drop()
        if self._enabled_tracing:
            from ..libs import tracing

            tracing.get_tracer().disable()
        from ..libs import lockdep

        if self._enabled_lockdep:
            lockdep.disable()
        if lockdep.get_metrics() is self.metrics.lockdep:
            lockdep.set_metrics(None)
        from ..tools import detcheck

        if detcheck.get_metrics() is self.metrics.determinism:
            detcheck.set_metrics(None)
        self.sw.stop()
        # settle any in-flight speculative execution (exec-spec thread +
        # overlay session) before the app conns go away
        self.block_exec.stop()
        if self._chaos_installed:
            # only the installer tears the process-wide controller down
            # (scenario runs install their own outside any node)
            from ..p2p import netchaos

            netchaos.uninstall()
            self._chaos_installed = False
        # drain the mempool ingest worker BEFORE the crypto dispatchers:
        # its queued batches verify_async, and a drain after dispatcher
        # shutdown would respawn a dispatcher thread post-stop
        self.mempool.stop()
        # join the async verify dispatch threads AFTER the reactors are
        # down (queued batches drain first; futures always complete). A
        # concurrently running node respawns its dispatcher lazily.
        crypto_batch.shutdown_dispatchers()
        if self.addr_book is not None:
            self.addr_book.save()
        self.trust_store.save()
        self.indexer_service.stop()
        self.event_bus.stop()
        self.proxy_app.stop()
        # remote signer (SocketPV) holds a conn + listener; hang up so
        # the signer process sees EOF and the laddr can be re-bound
        if hasattr(self.priv_validator, "close"):
            self.priv_validator.close()
        # the last act of a clean stop: the next boot of this home dir
        # must not ledger a crash incident
        if self._dirty_marker is not None:
            try:
                os.unlink(self._dirty_marker)
            except OSError:
                pass
        self._stopped.set()

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until stop() completes (reference node runner blocks)."""
        self._stopped.wait(timeout)


def default_new_node(config: cfg.Config) -> Node:
    """Load node key, priv validator and genesis from the config root
    and construct a Node (reference node/node.go:83-98)."""
    cfg.ensure_root(config.root_dir)
    node_key = NodeKey.load_or_gen(config.base.node_key_path())
    if config.base.priv_validator_laddr:
        # external signing process dials in (node/node.go:228-236)
        from ..privval.remote import SocketPV

        pv = SocketPV(config.base.priv_validator_laddr)
        pv.listen()
        LOG.info("waiting for remote signer on %s", pv.listen_addr)
        pv.accept()
    else:
        pv = load_or_gen_file_pv(config.base.priv_validator_path(),
                                 key_type=config.crypto.key_type)
    genesis_doc = GenesisDoc.load(config.base.genesis_path())
    creator = default_client_creator(
        config.base.proxy_app, config.base.abci,
        request_timeout=config.abci.request_timeout_s,
        dial_timeout=config.abci.dial_timeout_s)
    return Node(config, pv, node_key, creator, genesis_doc)
