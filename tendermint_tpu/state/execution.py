"""BlockExecutor — validate, execute against the ABCI app, commit.

Reference parity: state/execution.go. apply_block (reference :89-152) is
the single chokepoint where a validated block mutates chain state;
exec_block_on_proxy_app (:209-274) is the BeginBlock → DeliverTx loop →
EndBlock pipeline across the app process boundary; commit (:160-202)
locks the mempool around the app Commit + recheck.
"""

from __future__ import annotations

import logging
from typing import List, Optional

from ..abci import types as abci
from ..crypto import merkle, pubkey_from_bytes
from ..libs import fail, tracing
from ..libs.db import DB
from ..types import serde
from ..types.basic import BlockID
from ..types.block import Block
from ..types.validator_set import Validator
from .state import VALSET_CHANGE_DELAY, State
from .store import save_abci_responses, save_state
from .validation import ErrInvalidBlock, validate_block


class ABCIResponses:
    """Results of exec_block_on_proxy_app, persisted per height for
    replay-crash-recovery and last_results_hash (reference
    state/store.go:109-135)."""

    def __init__(self, deliver_tx: List[abci.ResponseDeliverTx], end_block: Optional[abci.ResponseEndBlock]):
        self.deliver_tx = deliver_tx
        self.end_block = end_block
        self.begin_block: Optional[abci.ResponseBeginBlock] = None

    def results_hash(self) -> bytes:
        """Merkle root over (code, data) of each DeliverTx (reference
        types/results.go ABCIResults.Hash)."""
        from .. import codec

        leaves = [
            codec.t_uvarint(1, r.code) + codec.t_bytes(2, r.data)
            for r in self.deliver_tx
        ]
        return merkle.hash_from_byte_slices(leaves)

    def to_bytes(self) -> bytes:
        return serde.pack(
            [
                [[r.code, r.data, r.log, r.gas_wanted, r.gas_used,
                  _tags_obj(r.tags)] for r in self.deliver_tx],
                [
                    [[u.pub_key, u.power, u.pop]
                     for u in self.end_block.validator_updates],
                    _params_obj(self.end_block.consensus_param_updates),
                    _tags_obj(self.end_block.tags),
                ]
                if self.end_block
                else None,
                _tags_obj(self.begin_block.tags) if self.begin_block else None,
            ]
        )

    @classmethod
    def from_bytes(cls, data: bytes) -> "ABCIResponses":
        o = serde.unpack(data)
        dtxs = [
            abci.ResponseDeliverTx(
                code=r[0], data=r[1], log=r[2], gas_wanted=r[3], gas_used=r[4],
                tags=_tags_from(r[5]),
            )
            for r in o[0]
        ]
        eb = None
        if o[1] is not None:
            eb = abci.ResponseEndBlock(
                validator_updates=[
                    abci.ValidatorUpdate(u[0], u[1],
                                         pop=u[2] if len(u) > 2 else b"")
                    for u in o[1][0]],
                consensus_param_updates=_params_from(o[1][1]),
                tags=_tags_from(o[1][2]) if len(o[1]) > 2 else [],
            )
        res = cls(dtxs, eb)
        if len(o) > 2 and o[2] is not None:
            res.begin_block = abci.ResponseBeginBlock(tags=_tags_from(o[2]))
        return res


def _tags_obj(tags):
    return [[kv.key, kv.value] for kv in (tags or [])]


def _tags_from(o):
    return [abci.KVPair(k, v) for k, v in (o or [])]


def _params_obj(p):
    if p is None:
        return None
    return [
        [p.block_size.max_bytes, p.block_size.max_gas] if p.block_size else None,
        [p.evidence.max_age] if p.evidence else None,
    ]


def _params_from(o):
    if o is None:
        return None
    return abci.ConsensusParamUpdates(
        block_size=abci.BlockSizeParams(o[0][0], o[0][1]) if o[0] else None,
        evidence=abci.EvidenceParams(o[1][0]) if o[1] else None,
    )


class CommitStageProfile:
    """Per-stage commit-path timer: every per-block cost between block
    execution and the RPC edge reports here, labeled
    stage=execute|app_commit|events|index|mempool_update|wal.
    Observations land in
    the commit_stage_seconds{stage} metric family AND an in-process
    accumulator, so the pipeline ceiling is attributable from a live
    scrape, a tracer timeline, or a bench run's stage table — not
    anecdotal. Writers: BlockExecutor (execute/app_commit/events/
    mempool_update),
    ConsensusState (wal), IndexerService (index)."""

    def __init__(self, metrics=None):
        import threading

        self._metric = getattr(metrics, "commit_stage", None)
        self._lock = threading.Lock()
        self._totals: dict = {}  # stage -> [count, total_seconds]

    def observe(self, stage: str, seconds: float) -> None:
        if self._metric is not None:
            self._metric.with_labels(stage).observe(seconds)
        with self._lock:
            ent = self._totals.get(stage)
            if ent is None:
                self._totals[stage] = [1, seconds]
            else:
                ent[0] += 1
                ent[1] += seconds

    def snapshot(self) -> dict:
        """{stage: {count, total_ms, avg_ms}} — the bench/debug view."""
        with self._lock:
            return {
                stage: {
                    "count": n,
                    "total_ms": round(total * 1000, 2),
                    "avg_ms": round(total * 1000 / max(n, 1), 3),
                }
                for stage, (n, total) in sorted(self._totals.items())
            }


class BlockExecutor:
    """Reference state/execution.go:22-39. Handles block validation +
    execution; the ONLY writer of State past genesis."""

    def __init__(
        self,
        db: DB,
        proxy_app,  # AppConnConsensus-shaped client
        mempool=None,
        evidence_pool=None,
        event_bus=None,
        logger: Optional[logging.Logger] = None,
        metrics=None,
        exec_config=None,
    ):
        import threading

        from ..config import ExecutionConfig
        from ..metrics import StateMetrics

        self.db = db
        self.proxy_app = proxy_app
        self.mempool = mempool
        self.evidence_pool = evidence_pool
        self.event_bus = event_bus
        self.logger = logger or logging.getLogger("state.BlockExecutor")
        self.metrics = metrics if metrics is not None else StateMetrics()
        self.exec_config = (exec_config if exec_config is not None
                            else ExecutionConfig())
        self.metrics.exec_parallel_lanes.set(self.exec_config.parallel_lanes)
        # the commit-path profiler: shared with ConsensusState (wal
        # stage) and the node's IndexerService (index stage)
        self.stage_profile = CommitStageProfile(self.metrics)
        # a validation.VerifiedCommit for the next apply_block's
        # LastCommit, set by a caller that verified that commit itself
        # (fast sync, one block earlier) just before it calls
        # apply_block, which takes it and clears it. An attribute and
        # not a parameter of apply_block, so the call keeps its three
        # arguments for every stand-in that replaces it.
        self.verified_last_commit = None
        # exec-lane flight recorder: process-global (state/parallel.py);
        # the executor only hands it a metrics sink when the parallel
        # path can actually run, so a lanes=1 node never touches it
        if self.exec_config.parallel_lanes > 1:
            from . import parallel as par

            par.get_flight_recorder().set_metrics(self.metrics)
        # persistent work-stealing lane pool ([execution] lane_pool):
        # workers live from here to stop() — blocks are handed off by
        # condition notify instead of per-block thread spawns
        self._lane_pool = None
        if (self.exec_config.parallel_lanes > 1
                and getattr(self.exec_config, "lane_pool", False)):
            from .lanepool import LanePool

            self._lane_pool = LanePool(self.exec_config.parallel_lanes)
            self._lane_pool.start()
        # speculation slots, ascending height (> 1 entry only while a
        # cross-height chained child is in flight): written by the
        # consensus/sync thread, workers only fill their own slot
        # objects (state/parallel.py)
        self._spec_lock = threading.Lock()
        self._spec_slots: list = []
        self._spec_threads: list = []  # live exec-spec threads for stop()
        # identity of the last overlay session promoted into the app —
        # the adoption gate for chained slots (a child is only valid on
        # the EXACT parent overlay it executed against)
        self._last_promoted_session = None
        # next-block hint from the sync reactors (stage_next_block):
        # consumed by _exec_block to launch cross-height speculation
        self._staged_next = None
        self._warned_no_parallel_app = False

    def set_event_bus(self, event_bus) -> None:
        self.event_bus = event_bus

    @property
    def speculation_enabled(self) -> bool:
        return bool(self.exec_config.speculative)

    def stop(self) -> None:
        """Settle any in-flight speculation and drain the persistent
        lane pool so no exec thread (or undiscarded overlay session)
        outlives the executor's owner."""
        with self._spec_lock:
            slots, self._spec_slots = self._spec_slots, []
            threads, self._spec_threads = list(self._spec_threads), []
        # children first: a chained child must detach from its parent's
        # overlay before the parent's sessions are released
        for slot in reversed(slots):
            slot.abandon()
        # stopping the pool unblocks any worker mid-run (its caller —
        # an exec-spec thread — sees a RuntimeError and discards), so
        # the pool goes down BEFORE the spec-thread joins
        if self._lane_pool is not None:
            self._lane_pool.stop()
        for t in threads:
            t.join(timeout=10)
        # uninstall only OUR metrics sink from the process-global flight
        # recorder (same identity contract as crypto_batch.set_metrics)
        from . import parallel as par

        rec = par.get_flight_recorder()
        if rec.get_metrics() is self.metrics:
            rec.set_metrics(None)

    def validate_block(self, state: State, block: Block,
                       decided: bool = False, verified_last_commit=None):
        return validate_block(state, block, self.evidence_pool,
                              decided=decided,
                              verified_last_commit=verified_last_commit)

    def apply_block(self, state: State, block_id: BlockID, block: Block) -> State:
        """Validate → exec against app → update state → commit app →
        fire events. Returns the new State (reference execution.go:89-152)."""
        import time as _time

        from ..abci.client import ABCIAppRestartedError

        _t0 = _time.monotonic()
        with tracing.span("state.applyBlock", cat="state",
                          request=("block", block.header.height),
                          height=block.header.height,
                          txs=len(block.data.txs)):
            try:
                return self._apply_block_inner(state, block_id, block, _t0)
            except ABCIAppRestartedError as e:
                # the resilient consensus conn reconnected to a restarted
                # app and re-synced it to the LAST COMMITTED height (the
                # in-flight execution died with the old process, nothing
                # was half-kept) — re-drive the whole block from scratch;
                # never resume mid-block, so nothing can apply twice
                self.logger.warning(
                    "app restarted mid-block at height %d (%s); "
                    "re-driving the full block", block.header.height, e)
                return self._apply_block_inner(state, block_id, block, _t0)

    def _apply_block_inner(self, state: State, block_id: BlockID,
                           block: Block, _t0: float) -> State:
        """Every stage is a child span of state.applyBlock (README
        "Spans"); where a CommitStageProfile stage brackets the same
        lines it is observed from the span's own two clock reads."""
        import time as _time

        height = block.header.height
        # apply-time blocks are DECIDED (commit apply, replay, fast
        # sync) — proposal-only checks like the aggregate-lane clock
        # drift bound must not reject them
        handed, self.verified_last_commit = self.verified_last_commit, None
        with tracing.span("state.validateBlock", cat="state",
                          height=height) as sp:
            checked = self.validate_block(state, block, decided=True,
                                          verified_last_commit=handed)
            if checked is not None:
                sp.set(last_commit=checked)
                self.metrics.last_commit_check.with_labels(checked).inc()

        with tracing.timed("commit.execute", cat="state",
                           height=height) as sp:
            abci_responses = self._exec_block(state, block)
        self.stage_profile.observe("execute", sp.seconds)

        fail.fail_point("ApplyBlock.SaveABCIResponses")  # execution.go:103
        with tracing.span("state.saveResponses", cat="state", height=height):
            save_abci_responses(self.db, height, abci_responses)
            # durability barrier: the app Commit below makes the app's
            # state ahead of the chain's — recoverable ONLY through the
            # stored responses (the app==store handshake path). If this
            # record can vanish with an un-synced page-cache tail, that
            # crash window is unrecoverable (found by the crash matrix:
            # ApplyBlock.AfterCommit x state_torn), so fsync it FIRST.
            sync = getattr(self.db, "sync", None)
            if sync is not None:
                sync()
        fail.fail_point("ApplyBlock.AfterSaveABCIResponses")  # execution.go:108

        with tracing.span("state.updateState", cat="state", height=height):
            val_updates = _abci_validator_updates(abci_responses)
            if val_updates:
                self.logger.info("updates to validators: %d",
                                 len(val_updates))
                self.metrics.validator_updates.inc(len(val_updates))
                self.metrics.valset_changes.inc()

            state = update_state(state, block_id, block.header,
                                 abci_responses)

        # lock mempool, commit app state, update mempool (execution.go:130-135)
        app_hash = self.commit(state, block)

        fail.fail_point("ApplyBlock.AfterCommit")  # execution.go:139

        with tracing.span("state.saveState", cat="state", height=height):
            if self.evidence_pool is not None:
                self.evidence_pool.update(block, state)

            state.app_hash = app_hash
            save_state(self.db, state)

        fail.fail_point("ApplyBlock.AfterSaveState")  # execution.go:145

        self.metrics.block_processing_time.observe(_time.monotonic() - _t0)
        with tracing.timed("commit.events", cat="state", height=height) as sp:
            self._fire_events(block, abci_responses, val_updates)
        self.stage_profile.observe("events", sp.seconds)
        return state

    def commit(self, state: State, block: Block) -> bytes:
        """App Commit under mempool lock; then mempool Update/recheck
        (reference execution.go:160-202). Returns the new app hash."""
        height = block.header.height
        if self.mempool is not None:
            self.mempool.lock()
        try:
            if self.mempool is not None:
                self.mempool.flush_app_conn()
            with tracing.timed("commit.appCommit", cat="state",
                               height=height) as sp:
                res = self.proxy_app.commit()
            self.stage_profile.observe("app_commit", sp.seconds)
            self.logger.debug(
                "committed state: height=%d app_hash=%s",
                height,
                res.data.hex()[:16],
            )
            if self.mempool is not None:
                with tracing.timed("commit.mempool_update", cat="state",
                                   height=height) as sp:
                    self.mempool.update(
                        height,
                        block.data.txs,
                        pre_check=_tx_pre_check(state),
                    )
                self.stage_profile.observe("mempool_update", sp.seconds)
            return res.data
        finally:
            if self.mempool is not None:
                self.mempool.unlock()

    def _begin_block_request(self, state: State,
                             block: Block) -> abci.RequestBeginBlock:
        commit_info = _last_commit_info(state, block)
        byz_vals = [
            abci.Evidence(
                type="duplicate/vote",
                validator_address=ev.address(),
                height=ev.height(),
                time=block.header.time,
            )
            for ev in block.evidence.evidence
        ]
        return abci.RequestBeginBlock(
            hash=block.hash() or b"",
            header=block.header,
            last_commit_info=commit_info,
            byzantine_validators=byz_vals,
        )

    def exec_block_on_proxy_app(self, state: State, block: Block) -> ABCIResponses:
        """BeginBlock → DeliverTx× → EndBlock (reference execution.go:209-274).
        DeliverTx requests ARE pipelined: deliver_tx_batch batch-writes
        frames ahead of the response drain on the socket transport (a
        bounded in-flight window keeps the per-request deadline
        semantics), and degrades to the per-tx loop everywhere else.
        This is the serial conformance oracle the parallel lane
        (state/parallel.py) is property-tested against."""
        res_begin = self.proxy_app.begin_block(
            self._begin_block_request(state, block))

        txs = list(block.data.txs)
        batch = getattr(self.proxy_app, "deliver_tx_batch", None)
        if batch is not None:
            deliver_txs = list(batch(txs))
        else:  # foreign/stub app conns without the batched entry point
            deliver_txs = [self.proxy_app.deliver_tx(tx) for tx in txs]
        invalid_count = sum(1 for r in deliver_txs if not r.is_ok)

        res_end = self.proxy_app.end_block(abci.RequestEndBlock(height=block.header.height))

        self.logger.info(
            "executed block height=%d valid_txs=%d invalid_txs=%d",
            block.header.height,
            len(deliver_txs) - invalid_count,
            invalid_count,
        )
        responses = ABCIResponses(deliver_txs, res_end)
        responses.begin_block = res_begin
        return responses

    # --- parallel / speculative execution (state/parallel.py) ---------

    def _exec_block(self, state: State, block: Block) -> ABCIResponses:
        """Execution dispatch: adopt a matching speculative run, else
        run the optimistic parallel lane (capable app + lanes > 1),
        else the serial oracle. Every path yields an ABCIResponses that
        is byte-identical to the serial loop (property-tested)."""
        from . import parallel as par

        run = self._take_speculation(state, block)
        if run is not None:
            # chain BEFORE promote: the staged next block must execute
            # against this block's genuinely un-promoted overlay (the
            # cross-height speculation contract)
            self._launch_chained(state, block, run)
            # promote through the session's OWN app handle: re-unwrapping
            # the proxy here could yield None mid-reconnect (the
            # ResilientClient swaps _client), and the session is bound to
            # the app object it executed against anyway
            run.session.app.exec_promote(run.session)
            self._last_promoted_session = run.session
            # crash here = speculative writes promoted into the app's
            # working state but NOTHING committed (no app Commit, no
            # chain-state save): recovery must re-execute the block and
            # land on the same app hash — speculation leaves zero trace
            fail.fail_point("Exec.AfterSpeculationAdopt")
            self.metrics.exec_speculation_hits.inc()
            return self._finish_run(run, block)
        if self.exec_config.parallel_lanes > 1:
            app = par.unwrap_parallel_app(self.proxy_app)
            if app is None:
                if not self._warned_no_parallel_app:
                    self._warned_no_parallel_app = True
                    self.logger.warning(
                        "[execution] parallel_lanes=%d but the app "
                        "connection has no exec-session surface; "
                        "executing serially",
                        self.exec_config.parallel_lanes)
            else:
                run = par.run_block(
                    app, block.data.txs,
                    self._begin_block_request(state, block),
                    abci.RequestEndBlock(height=block.header.height),
                    lanes=self.exec_config.parallel_lanes,
                    logger=self.logger,
                    pool=self._lane_pool,
                    retry_rounds=getattr(self.exec_config,
                                         "retry_max_rounds", 0))
                self._launch_chained(state, block, run)
                app.exec_promote(run.session)
                self._last_promoted_session = run.session
                return self._finish_run(run, block)
        self._staged_next = None
        return self.exec_block_on_proxy_app(state, block)

    def stage_next_block(self, block) -> None:
        """Sync-reactor hint: `block` is the block that will be applied
        AFTER the one currently being applied. With [execution]
        speculate_depth >= 2, _exec_block launches it speculatively on
        the current block's un-promoted overlay. Cheap no-op otherwise
        (the hint is dropped at the next dispatch)."""
        if (self.speculation_enabled
                and getattr(self.exec_config, "speculate_depth", 1) >= 2):
            self._staged_next = block

    def _launch_chained(self, state: State, block: Block, run) -> None:
        """Launch the staged next block speculatively on `run`'s
        still-un-promoted overlay (chained SpeculationSlot). `state` is
        the PRE-apply state of `block`: the post-apply state's
        last_validators — what the next block's LastCommitInfo is built
        from — is exactly state.validators (update_state's shift)."""
        nxt, self._staged_next = self._staged_next, None
        if (nxt is None or not self.speculation_enabled
                or getattr(self.exec_config, "speculate_depth", 1) < 2):
            return
        if nxt.header.height != block.header.height + 1:
            return
        from . import parallel as par

        app = par.unwrap_parallel_app(self.proxy_app)
        if app is None or app is not run.session.app:
            return
        breq = abci.RequestBeginBlock(
            hash=nxt.hash() or b"",
            header=nxt.header,
            last_commit_info=make_last_commit_info(state.validators, nxt),
            byzantine_validators=[
                abci.Evidence(
                    type="duplicate/vote",
                    validator_address=ev.address(),
                    height=ev.height(),
                    time=nxt.header.time,
                )
                for ev in nxt.evidence.evidence
            ],
        )
        slot = par.SpeculationSlot(
            app, nxt.header.height, nxt.hash() or b"", b"",
            parent_session=run.session)
        slot.start(list(nxt.data.txs), breq,
                   abci.RequestEndBlock(height=nxt.header.height),
                   lanes=max(1, self.exec_config.parallel_lanes),
                   pool=self._lane_pool,
                   retry_rounds=getattr(self.exec_config,
                                        "retry_max_rounds", 0))
        # crash here = a speculative child is executing against an
        # un-promoted parent overlay; NOTHING is durable (both sessions
        # are memory-only) — replay must land on the same image
        fail.fail_point("Exec.AfterChainSpeculationStart")
        with self._spec_lock:
            self._spec_slots.append(slot)
            self._spec_threads = [t for t in self._spec_threads
                                  if t.is_alive()]
            self._spec_threads.append(slot.thread)

    def _finish_run(self, run, block: Block) -> ABCIResponses:
        if run.conflicts:
            self.metrics.exec_conflicts.inc(run.conflicts)
        invalid = sum(1 for r in run.deliver_res if not r.is_ok)
        self.logger.info(
            "executed block height=%d valid_txs=%d invalid_txs=%d "
            "(parallel: conflicts=%d retry_rounds=%d%s)",
            block.header.height, len(run.deliver_res) - invalid, invalid,
            run.conflicts, getattr(run, "retry_rounds", 0),
            ", serial-fallback" if run.serial_fallback else "")
        responses = ABCIResponses(list(run.deliver_res), run.end_res)
        responses.begin_block = run.begin_res
        return responses

    def begin_speculation(self, state: State, block: Block) -> bool:
        """Kick a speculative execution of `block` on a background
        thread (consensus calls this once the proposal is complete and
        valid, during the prevote window). No-op unless [execution]
        speculative is on and the app supports exec sessions. Returns
        True if a new speculation was started."""
        if not self.speculation_enabled or block is None:
            return False
        from . import parallel as par

        app = par.unwrap_parallel_app(self.proxy_app)
        if app is None:
            if not self._warned_no_parallel_app:
                self._warned_no_parallel_app = True
                self.logger.warning(
                    "[execution] speculative=true but the app connection "
                    "has no exec-session surface; speculation disabled")
            return False
        height = block.header.height
        block_hash = block.hash() or b""
        with self._spec_lock:
            for cur in self._spec_slots:
                if (cur.height == height and cur.block_hash == block_hash
                        and (cur.parent_session is not None
                             or cur.base_app_hash == state.app_hash)):
                    # already speculating on this exact block (chained
                    # slots settle their base via parent identity at
                    # adoption time, not the app hash)
                    return False
            stale, self._spec_slots = self._spec_slots, []
        for cur in reversed(stale):  # children before parents
            cur.abandon()
            self.metrics.exec_speculation_wasted.inc()
        slot = par.SpeculationSlot(app, height, block_hash, state.app_hash)
        slot.start(list(block.data.txs),
                   self._begin_block_request(state, block),
                   abci.RequestEndBlock(height=height),
                   lanes=max(1, self.exec_config.parallel_lanes),
                   pool=self._lane_pool,
                   retry_rounds=getattr(self.exec_config,
                                        "retry_max_rounds", 0))
        with self._spec_lock:
            self._spec_slots.append(slot)
            self._spec_threads = [t for t in self._spec_threads
                                  if t.is_alive()]
            self._spec_threads.append(slot.thread)
        return True

    def _slot_matches(self, slot, state: State, block: Block) -> bool:
        height = block.header.height
        block_hash = block.hash() or b""
        if slot.parent_session is not None:
            # a chained slot executed against an overlay, not the
            # committed base: it is adoptable iff the decided block
            # matches AND its parent overlay is the EXACT session that
            # was just promoted (identity, not hash — two sessions can
            # agree on state yet differ in un-promoted buffers)
            return (slot.height == height
                    and slot.block_hash == block_hash
                    and slot.parent_session is self._last_promoted_session)
        return slot.matches(height, block_hash, state.app_hash)

    def _take_speculation(self, state: State, block: Block):
        """Settle the speculation slots against the DECIDED block: a
        matching head slot → wait for the worker and hand its run to
        the caller (descendant chained slots stay live — they become
        adoptable once this run promotes); anything else → abandon the
        whole chain children-first (each worker discards its own
        session) and count it wasted."""
        with self._spec_lock:
            slots, self._spec_slots = self._spec_slots, []
        if not slots:
            return None
        head, rest = slots[0], slots[1:]
        if self._slot_matches(head, state, block):
            run = head.wait()
            if run is not None:
                with self._spec_lock:
                    self._spec_slots = rest + self._spec_slots
                return run
            # worker failed: surface like a serial exec would have —
            # and any chained descendants are rooted in the dead
            # session, so the rest of the chain is garbage
            if head.error is not None:
                self.logger.warning(
                    "speculative execution failed (%s); re-executing",
                    head.error)
            self.metrics.exec_speculation_wasted.inc()
            for slot in reversed(rest):
                slot.abandon()
                self.metrics.exec_speculation_wasted.inc()
            return None
        for slot in reversed(slots):
            slot.abandon()
            self.metrics.exec_speculation_wasted.inc()
        return None

    def _fire_events(self, block: Block, abci_responses: ABCIResponses, val_updates) -> None:
        """Reference execution.go fireEvents:475-506. The block's tx
        events go to the bus in ONE publish_txs call when the bus has
        the block-scoped path and [execution] event_batch is on
        (default) — subscriber-observed sequences are identical to the
        per-tx loop (property-tested), the per-tx cost is not."""
        if self.event_bus is None:
            return
        self.event_bus.publish_new_block(
            block, abci_responses.begin_block, abci_responses.end_block
        )
        self.event_bus.publish_new_block_header(
            block.header, abci_responses.begin_block, abci_responses.end_block
        )
        publish_txs = (getattr(self.event_bus, "publish_txs", None)
                       if getattr(self.exec_config, "event_batch", True)
                       else None)
        if publish_txs is not None:
            publish_txs(block.header.height, block.data.txs,
                        abci_responses.deliver_tx)
        else:
            for i, tx in enumerate(block.data.txs):
                self.event_bus.publish_tx(
                    block.header.height, i, tx, abci_responses.deliver_tx[i]
                )
        if val_updates:
            self.event_bus.publish_validator_set_updates(val_updates)


# headroom for header, last commit, and framing when a tx is packed into a
# block — a tx may only use what's left (reference types.MaxDataBytes)
BLOCK_OVERHEAD_BYTES = 4096


def _tx_pre_check(state: State):
    """Max-bytes pre-check filter for the mempool (reference
    mempool.PreCheckAminoMaxBytes wiring at node/node.go:263)."""
    max_data = state.consensus_params.block_size.max_bytes - BLOCK_OVERHEAD_BYTES

    def check(tx: bytes):
        if len(tx) > max_data:
            raise ValueError(f"tx too large ({len(tx)} > {max_data})")

    return check


def make_last_commit_info(last_validators, block: Block) -> abci.LastCommitInfo:
    """(address, power, signed) per last validator (execution.go:277-300).
    Shared with handshake replay so replayed BeginBlocks carry the same
    vote info as original execution."""
    from ..types.block import AggregateCommit

    votes = []
    if block.header.height > 1 and block.last_commit is not None and last_validators is not None:
        if isinstance(block.last_commit, AggregateCommit):
            signers = block.last_commit.signers
            for i, v in enumerate(last_validators.validators):
                votes.append((v.address, v.voting_power, signers.get_index(i)))
        else:
            for i, v in enumerate(last_validators.validators):
                signed = (
                    i < len(block.last_commit.precommits)
                    and block.last_commit.precommits[i] is not None
                )
                votes.append((v.address, v.voting_power, signed))
    return abci.LastCommitInfo(round=block.last_commit.round() if block.last_commit else 0, votes=votes)


def _last_commit_info(state: State, block: Block) -> abci.LastCommitInfo:
    return make_last_commit_info(state.last_validators, block)


def _abci_validator_updates(abci_responses: ABCIResponses) -> List[abci.ValidatorUpdate]:
    if abci_responses.end_block is None:
        return []
    return list(abci_responses.end_block.validator_updates)


def _check_rotation_pop(val_set, changes: List[Validator]) -> None:
    """Rotation-time rogue-key defense for the BLS aggregate lane.

    Genesis validates every BLS key's proof of possession
    (types/genesis.py); EndBlock rotation is the OTHER door into the
    valset, and fast_aggregate_verify is only sound over keys that
    proved possession. The accept/reject decision depends ONLY on
    consensus state — a key already in the current valset is trusted
    (its membership is hash-chained back to a PoP-checked join), a NEW
    key must carry a valid PoP in its ValidatorUpdate — never on the
    process-local registry, which a freshly restarted or statesynced
    node holds in a different state than its long-lived peers (keys it
    never saw registered); consulting it would let nodes diverge on
    the same update. Verified keys are (re)registered as a side effect
    so the aggregate lane's registry stays warm. Ed25519 sets (and
    removals, power 0) are untouched."""
    if not val_set.is_bls():
        return
    from ..crypto import bls
    from ..crypto.bls import PubKeyBLS12381

    member_keys = {v.pub_key.data for v in val_set.validators
                   if isinstance(v.pub_key, PubKeyBLS12381)}
    for v in changes:
        if v.voting_power == 0 or not isinstance(v.pub_key, PubKeyBLS12381):
            continue
        pk = v.pub_key.data
        if pk in member_keys:
            # repower of a sitting validator: possession was proved when
            # the key joined; re-register for the registry's benefit
            bls._register_pop_unchecked(pk)
            continue
        if not v.pop or not bls.register_proof_of_possession(pk, v.pop):
            raise ValueError(
                "validator update rotates BLS key "
                f"{v.address.hex()[:12]} into an aggregate-lane valset "
                "without a valid proof of possession")


def update_state(
    state: State, block_id: BlockID, header, abci_responses: ABCIResponses
) -> State:
    """Pure state transition (reference execution.go updateState:411-472).
    Note: app_hash is filled AFTER Commit by the caller.

    Only next_validators is copied, because only it is written to. The
    other two sets move down a slot as the objects they are (with the
    bytes they were saved as, serde.encode_valset): a State's sets are
    read-only to everyone who did not build them, and whoever rotates
    one copies it first (consensus/state.py update_to_state,
    enter_new_round)."""
    n_val_set = state.next_validators.copy()

    last_height_vals_changed = state.last_height_validators_changed
    val_updates = _abci_validator_updates(abci_responses)
    if val_updates:
        changes = [
            Validator.new(pubkey_from_bytes(u.pub_key), u.power, pop=u.pop)
            for u in val_updates
        ]
        _check_rotation_pop(n_val_set, changes)
        n_val_set.update_with_changes(changes)
        # changes take effect at height+2 (execution.go:419)
        last_height_vals_changed = header.height + VALSET_CHANGE_DELAY

    # next's proposer rotates by 1 (execution.go:428)
    n_val_set.increment_proposer_priority(1)

    params = state.consensus_params
    last_height_params_changed = state.last_height_consensus_params_changed
    if abci_responses.end_block is not None and abci_responses.end_block.consensus_param_updates is not None:
        params = params.update(abci_responses.end_block.consensus_param_updates)
        params.validate()
        last_height_params_changed = header.height + 1

    return State(
        chain_id=state.chain_id,
        last_block_height=header.height,
        last_block_total_tx=state.last_block_total_tx + header.num_txs,
        last_block_id=block_id,
        last_block_time=header.time,
        next_validators=n_val_set,
        validators=state.next_validators,
        last_validators=state.validators,
        last_height_validators_changed=last_height_vals_changed,
        consensus_params=params,
        last_height_consensus_params_changed=last_height_params_changed,
        last_results_hash=abci_responses.results_hash(),
        app_hash=b"",  # set by caller after Commit
    )
