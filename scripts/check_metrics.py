#!/usr/bin/env python
"""check_metrics — boot a node in-process, scrape /metrics, and validate
the exposition.

Guards the observability subsystem end-to-end: a single-validator
kvstore node runs until it has committed a few blocks, then the
Prometheus endpoint is scraped and the body is run through a *strict*
text-exposition (v0.0.4) parser — the kind of errors a real Prometheus
server would reject (samples for undeclared families, labeled families
rendering label-less samples, duplicate series, non-monotonic histogram
buckets, `_count` != `+Inf` bucket) fail the check, not just malformed
lines. Finally the families the hot path must expose (crypto
batch-verify, consensus step durations) are asserted present.

Wired into the test suite as a tier-1 test (tests/test_check_metrics.py)
and runnable standalone:

    python scripts/check_metrics.py [--blocks N] [--timeout SECS]
"""

from __future__ import annotations

import argparse
import math
import re
import sys
import tempfile
import time
import urllib.request

_NAME_RE = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^(?P<name>{_NAME_RE})"
    r"(?:\{(?P<labels>.*)\})?"
    r" (?P<value>[^ ]+)"
    r"(?: (?P<ts>-?[0-9]+))?$"
)
_LABEL_RE = re.compile(
    rf'\s*(?P<name>{_NAME_RE})="(?P<value>(?:[^"\\]|\\.)*)"\s*(?:,|$)'
)
_HELP_RE = re.compile(rf"^# HELP (?P<name>{_NAME_RE}) (?P<doc>.*)$")
_TYPE_RE = re.compile(
    rf"^# TYPE (?P<name>{_NAME_RE}) "
    r"(?P<type>counter|gauge|histogram|summary|untyped)$"
)


class ExpositionError(Exception):
    """One strict-parse violation, with the offending line number."""


def _parse_labels(raw: str, lineno: int) -> tuple:
    labels, pos = [], 0
    while pos < len(raw):
        m = _LABEL_RE.match(raw, pos)
        if m is None:
            raise ExpositionError(f"line {lineno}: bad label syntax: {{{raw}}}")
        labels.append((m.group("name"), m.group("value")))
        pos = m.end()
    names = [n for n, _ in labels]
    if len(names) != len(set(names)):
        raise ExpositionError(f"line {lineno}: duplicate label name: {{{raw}}}")
    return tuple(sorted(labels))


def _parse_value(raw: str, lineno: int) -> float:
    try:
        return float(raw)  # accepts Inf/-Inf/NaN spellings too
    except ValueError:
        raise ExpositionError(f"line {lineno}: bad sample value: {raw!r}")


def parse_exposition(text: str) -> dict:
    """Strictly parse Prometheus text format v0.0.4.

    Returns {family: {"type": str, "samples": {(name, labelset): value}}}.
    Raises ExpositionError on the first violation.
    """
    if not text.endswith("\n"):
        raise ExpositionError("exposition must end with a newline")
    families: dict = {}
    seen_series: set = set()

    def family_of(name: str):
        fam = families.get(name)
        if fam is not None:
            return name, fam
        # histogram/summary component samples
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                base = name[: -len(suffix)]
                fam = families.get(base)
                if fam is not None and fam["type"] in ("histogram", "summary"):
                    if suffix == "_bucket" and fam["type"] == "summary":
                        break
                    return base, fam
        return None, None

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line.startswith("#"):
            m = _HELP_RE.match(line)
            if m:
                fam = families.setdefault(
                    m.group("name"),
                    {"type": None, "help": None, "samples": {}})
                if fam["help"] is not None:
                    raise ExpositionError(
                        f"line {lineno}: second HELP for {m.group('name')}")
                fam["help"] = m.group("doc")
                continue
            m = _TYPE_RE.match(line)
            if m:
                fam = families.setdefault(
                    m.group("name"),
                    {"type": None, "help": None, "samples": {}})
                if fam["type"] is not None:
                    raise ExpositionError(
                        f"line {lineno}: second TYPE for {m.group('name')}")
                if fam["samples"]:
                    raise ExpositionError(
                        f"line {lineno}: TYPE after samples for "
                        f"{m.group('name')}")
                fam["type"] = m.group("type")
                continue
            raise ExpositionError(f"line {lineno}: malformed comment: {line}")
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ExpositionError(f"line {lineno}: malformed sample: {line}")
        name = m.group("name")
        labels = _parse_labels(m.group("labels") or "", lineno)
        value = _parse_value(m.group("value"), lineno)
        base, fam = family_of(name)
        if fam is None or fam["type"] is None:
            raise ExpositionError(
                f"line {lineno}: sample {name} has no preceding # TYPE")
        series = (name, labels)
        if series in seen_series:
            raise ExpositionError(f"line {lineno}: duplicate series: {line}")
        seen_series.add(series)
        fam["samples"][series] = value

    _check_histograms(families)
    return families


def _check_histograms(families: dict) -> None:
    for base, fam in families.items():
        if fam["type"] != "histogram":
            continue
        # group buckets by their non-le labelset
        groups: dict = {}
        for (name, labels), value in fam["samples"].items():
            rest = tuple(l for l in labels if l[0] != "le")
            g = groups.setdefault(rest, {"buckets": [], "sum": None,
                                         "count": None})
            if name == base + "_bucket":
                le = dict(labels).get("le")
                if le is None:
                    raise ExpositionError(
                        f"{base}: bucket sample without le label")
                g["buckets"].append((float(le), value))
            elif name == base + "_sum":
                g["sum"] = value
            elif name == base + "_count":
                g["count"] = value
        for rest, g in groups.items():
            where = f"{base}{dict(rest) if rest else ''}"
            if not g["buckets"]:
                raise ExpositionError(f"{where}: histogram with no buckets")
            g["buckets"].sort(key=lambda b: b[0])
            counts = [c for _, c in g["buckets"]]
            if any(b > a for a, b in zip(counts[1:], counts)):
                raise ExpositionError(
                    f"{where}: bucket counts not monotonic: {counts}")
            les = [le for le, _ in g["buckets"]]
            if not math.isinf(les[-1]):
                raise ExpositionError(f"{where}: missing +Inf bucket")
            if g["count"] is None or g["sum"] is None:
                raise ExpositionError(f"{where}: missing _count/_sum")
            if counts[-1] != g["count"]:
                raise ExpositionError(
                    f"{where}: +Inf bucket {counts[-1]:g} != "
                    f"_count {g['count']:g}")


# families the observability PR promises; the check fails if the node
# stops exposing any of them (namespace-prefixed at runtime)
REQUIRED_FAMILIES = (
    "consensus_height",
    "consensus_step_duration_seconds",
    "crypto_batch_verify_seconds",
    "crypto_batch_size",
    "crypto_batch_lanes_per_device",
    "crypto_signatures_verified_total",
    # PR-2 async/cache families (declaration only: a node that commits
    # blocks without duplicate gossip may legitimately record no hits)
    "crypto_sig_cache_hits_total",
    "crypto_sig_cache_misses_total",
    "crypto_inflight_batches",
    "crypto_pipeline_overlap_seconds",
    "state_block_processing_time",
    # PR-3 watchdog + per-peer network telemetry (peer-labeled families
    # legitimately render no samples on a peerless node — declaration
    # presence is the contract; pruning removes series, never families)
    "consensus_round_dwell_seconds",
    "consensus_stalls_total",
    "p2p_peers",
    "p2p_peer_receive_bytes_total",
    "p2p_peer_send_bytes_total",
    "p2p_peer_msg_recv_total",
    "p2p_peer_lag_blocks",
    # PR-4 state sync (declaration presence: a node that never produces
    # or restores snapshots legitimately records no samples)
    "statesync_snapshots",
    "statesync_snapshot_height",
    "statesync_chunks_served_total",
    "statesync_chunks_received_total",
    "statesync_chunks_rejected_total",
    "statesync_restore_chunks_applied",
    "statesync_restore_phase_seconds",
    # PR-5 ABCI resilience: per-request deadlines + supervised reconnect
    # (timeouts/reconnects legitimately record nothing on a healthy
    # node; conn_state and request durations are always live)
    "abci_request_duration_seconds",
    "abci_request_timeouts_total",
    "abci_reconnects_total",
    "abci_conn_state",
    "mempool_recheck_failures_total",
    "wal_corrupted_records_total",
    # PR-6 high-throughput mempool (lane/ingest families legitimately
    # record no samples until txs flow; declaration presence is the
    # contract, as with the other families above)
    "mempool_size",
    "mempool_recheck_times",
    "mempool_lane_depth",
    "mempool_checktx_batch_size",
    "mempool_ingest_queue_wait_seconds",
    "mempool_preverify_cache_hits_total",
    "mempool_preverify_rejected_total",
    "mempool_recheck_skipped_total",
    # PR-7 BLS aggregate fast lane (declaration presence: Ed25519 chains
    # legitimately never record aggregate samples)
    "crypto_agg_verify_seconds",
    "crypto_agg_signers",
    "consensus_agg_gossip_merges_total",
    "agg_commit_size_bytes",
    # PR-8 compile-once kernels (declaration presence: a cpu-backend
    # node never compiles and a fully warm node never misses)
    "crypto_compile_seconds",
    "crypto_compile_cache_hits_total",
    "crypto_compile_cache_misses_total",
    # PR-9 RPC fan-out serving (declaration presence: a node with
    # caching off or no websocket subscribers legitimately records no
    # samples; rpc_ws_dropped_total only fires under slow clients)
    "rpc_cache_hits_total",
    "rpc_cache_misses_total",
    "rpc_cache_bytes",
    "rpc_ws_subscribers",
    "rpc_ws_dropped_total",
    "rpc_events_rendered_total",
    # PR-10 chaos engine + churn workload (declaration presence: a node
    # with no installed fault plan injects nothing, a stable valset
    # records no churn, and reconnect attempts need a dropped
    # persistent peer)
    "chaos_injected_total",
    "chaos_active_rules",
    "churn_validator_updates_total",
    "churn_valset_changes_total",
    "p2p_reconnect_attempts_total",
    "p2p_throttled_seconds_total",
    "p2p_frames_total",
    "p2p_socket_calls_total",
    # PR-11 runtime lockdep (declaration presence: samples flow only
    # under [instrumentation] lockdep = true — the chaos-under-lockdep
    # scenarios are where these families go live)
    "lockdep_hold_seconds",
    "lockdep_inversions_total",
    # PR-12 parallel block execution (declaration presence: with the
    # default [execution] serial config, lanes reads 1 and the conflict/
    # speculation counters legitimately never record)
    "exec_parallel_lanes",
    "exec_conflicts_total",
    "exec_speculation_hits_total",
    "exec_speculation_wasted_total",
    # PR-13 commit-path batching: per-stage commit profiler (live once
    # blocks commit — execute/events/mempool_update record on every
    # apply_block; index needs an indexing node, wal a consensus WAL)
    "commit_stage_seconds",
    # PR-14 crash-consistency engine (declaration presence: a clean
    # boot replays nothing, recovery_time records one sample per boot,
    # and storage faults flow only under an armed [storage] fault_plan)
    "recovery_replayed_blocks_total",
    "recovery_time_seconds",
    "storage_faults_injected_total",
    # PR-15 determinism gate (declaration presence: samples flow only
    # when a check_determinism lint or detcheck oracle run is driven
    # in-process — bench.py detcheck, the test gates, scenario runs;
    # divergence counters staying at zero IS the healthy signal)
    "detlint_findings_total",
    "detcheck_runs_total",
    "detcheck_divergence_total",
    # PR-16 exec-lane flight recorder (declaration presence: samples
    # flow only on the threaded exec path — parallel_lanes=1 nodes
    # structurally never record, which is the zero-overhead contract)
    "exec_lane_wakeup_seconds",
    "exec_lane_busy_ratio",
    # PR-17 Block-STM engine: conflict-cone retry + work-stealing pool
    "exec_lane_retries_total",
    "exec_lane_steals_total",
    # PR-18 incident observatory (declaration presence: MTTD/MTTR
    # histograms record only when the ledger pairs an injected fault
    # with a detection/fresh-commit; a fault-free node records nothing
    # and incident_open reads 0 — the healthy signal)
    "incident_detection_seconds",
    "incident_recovery_seconds",
    "incident_open",
    # PR-19 Handel aggregation overlay (declaration presence: every
    # family stays silent on Ed25519 chains and with [handel] off —
    # absence of samples is the disabled signal)
    "handel_level",
    "handel_contributions_total",
    "handel_verify_seconds",
    "handel_pruned_peers_total",
    # PR-20 replica fan-out tree (declaration presence: every family
    # stays silent on full nodes — absence of samples is the
    # flat-topology signal)
    "replica_tree_depth",
    "replica_parent_switches_total",
    "replica_lag_blocks",
    # PR-27 one Merkle root per validator set, one verification per
    # commit (types_valset_hash_total is live on any node: make_block
    # and validate_block ask for the root; last_commit_check_total
    # counts from the second block on, handed_down under fast sync only)
    "types_valset_hash_total",
    "state_last_commit_check_total",
    # PR-32 the frozen heap (0 until the verify warm-up has ended)
    "runtime_gc_frozen_objects",
    # PR-35 a height is encoded once on its way to disk (live on any
    # node: every save_block counts a height, a seen commit and a
    # next_validators packed; in fast sync nothing else is packed)
    "store_encodings_total",
    "store_heights_saved_total",
    # PR-36 the block pool by peer slot, and what a refused commit made
    # it ask again (declaration presence: a node that never fast-syncs
    # sends no request, and an honest catch-up redoes nothing)
    "blockchain_pool_requests_total",
    "blockchain_pool_blocks_received_total",
    "blockchain_redo_heights_total",
    # PR-37 a batch meets the verified-signature cache once: a digest a
    # triple looked up (live wherever the cache is on and a batch ran)
    "crypto_sig_cache_key_hashes_total",
)

# ...and of those, the hot-path families that must have RECORDED samples
# after blocks committed — HELP/TYPE render for registered metrics even
# with no children, so a declaration check alone would pass with the
# crypto/step wiring (batch.set_metrics, _step_span) silently broken
REQUIRED_LIVE_FAMILIES = (
    "consensus_step_duration_seconds",
    "crypto_batch_verify_seconds",
    "crypto_signatures_verified_total",
)


def check_body(body: str, namespace: str = "tendermint",
               require_live: bool = True) -> dict:
    """Parse + validate one /metrics body; returns the parsed families.

    require_live additionally demands a positive sample in each hot-path
    family — only meaningful for a scrape taken after ≥1 committed block."""
    families = parse_exposition(body)
    missing = [f"{namespace}_{f}" for f in REQUIRED_FAMILIES
               if f"{namespace}_{f}" not in families]
    if missing:
        raise ExpositionError(f"missing metric families: {missing}")
    # help-text lint: every registered family must document itself —
    # a scrape full of nameless numbers is unusable at 3am
    undocumented = [name for name, fam in families.items()
                    if not (fam.get("help") or "").strip()]
    if undocumented:
        raise ExpositionError(
            f"metric families without help text: {undocumented}")
    if require_live:
        dead = [f"{namespace}_{f}" for f in REQUIRED_LIVE_FAMILIES
                if not any(v > 0 for v in
                           families[f"{namespace}_{f}"]["samples"].values())]
        if dead:
            raise ExpositionError(
                f"metric families declared but never recorded: {dead}")
    return families


# --- README drift lint ----------------------------------------------
#
# The README's metric tables and REQUIRED_FAMILIES drift independently:
# a new PR adds a family here and forgets the docs, or a doc row
# outlives a renamed metric. The lint closes the loop both ways:
#   1. every REQUIRED_FAMILIES entry must appear in some README table
#      row (first cell, backticked, `tendermint_` prefix optional);
#   2. every README table row written WITH the `tendermint_` prefix
#      (the explicit "this is a contract family" spelling, used by the
#      reference table) must still be in REQUIRED_FAMILIES.
# Unprefixed rows not in REQUIRED_FAMILIES are fine — the README also
# documents real-but-unrequired families (e.g. flowrate gauges).

_TABLE_NAME_RE = re.compile(r"`(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)`")


def readme_metric_rows(readme_text: str) -> list:
    """Backticked metric names from the FIRST cell of markdown table
    rows, as (name, was_prefixed) pairs with the namespace stripped."""
    rows = []
    for line in readme_text.splitlines():
        if not line.lstrip().startswith("|"):
            continue
        cells = line.split("|")
        if len(cells) < 3:
            continue
        first = cells[1]
        if set(first.strip()) <= {"-", ":", " "}:  # separator row
            continue
        for m in _TABLE_NAME_RE.finditer(first):
            name = m.group("name")
            prefixed = name.startswith("tendermint_")
            if prefixed:
                name = name[len("tendermint_"):]
            rows.append((name, prefixed))
    return rows


def check_readme_drift(readme_text: str,
                       families=REQUIRED_FAMILIES) -> list:
    """Both directions of REQUIRED_FAMILIES <-> README drift; returns a
    list of human-readable problems (empty = in sync)."""
    rows = readme_metric_rows(readme_text)
    documented = {name for name, _ in rows}
    problems = []
    undocumented = sorted(f for f in families if f not in documented)
    if undocumented:
        problems.append(
            "families required by check_metrics but missing from the "
            f"README metric tables: {undocumented}")
    stale = sorted({name for name, prefixed in rows
                    if prefixed and name not in families})
    if stale:
        problems.append(
            "tendermint_-prefixed README table rows not in "
            f"REQUIRED_FAMILIES (renamed or removed?): {stale}")
    return problems


def run_readme_drift(readme_path: str = None) -> list:
    import os

    if readme_path is None:
        readme_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "README.md")
    with open(readme_path, encoding="utf-8") as f:
        return check_readme_drift(f.read())


def run_node_and_scrape(blocks: int = 3, timeout: float = 60.0) -> str:
    """Boot a single-validator kvstore node with instrumentation on,
    wait for `blocks` commits, return the /metrics body."""
    import os

    os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")
    os.environ.setdefault("TM_TPU_WARMUP", "0")

    # standalone `python scripts/check_metrics.py` from anywhere
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)

    from tendermint_tpu import config as cfg
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK,
        query_for_event,
    )

    with tempfile.TemporaryDirectory(prefix="check_metrics_") as root:
        c = cfg.test_config()
        c.set_root(root)
        c.base.proxy_app = "kvstore"
        c.base.moniker = "check-metrics"
        c.rpc.laddr = ""
        c.p2p.laddr = "tcp://127.0.0.1:0"
        c.consensus.wal_path = "data/cs.wal/wal"
        c.instrumentation.prometheus = True
        c.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        cfg.ensure_root(root)
        NodeKey.load_or_gen(c.base.node_key_path())
        pv = load_or_gen_file_pv(c.base.priv_validator_path())
        GenesisDoc(
            chain_id="check-metrics-chain",
            genesis_time=time.time_ns() - 10**9,
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        ).save(c.base.genesis_path())

        node = default_new_node(c)
        sub = node.event_bus.subscribe(
            "check-metrics", query_for_event(EVENT_NEW_BLOCK), 16)
        node.start()
        try:
            height, deadline = 0, time.time() + timeout
            while height < blocks and time.time() < deadline:
                msg = sub.get(timeout=1.0)
                if msg is not None:
                    height = msg.data["block"].header.height
            if height < blocks:
                raise RuntimeError(
                    f"node committed only {height}/{blocks} blocks "
                    f"in {timeout:g}s")
            addr = node._metrics_server.listen_addr
            with urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=10) as resp:
                ctype = resp.headers.get("Content-Type", "")
                if "text/plain" not in ctype:
                    raise RuntimeError(f"bad content type: {ctype}")
                return resp.read().decode()
        finally:
            node.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--blocks", type=int, default=3,
                    help="blocks to commit before scraping (default 3)")
    ap.add_argument("--timeout", type=float, default=60.0,
                    help="seconds to wait for the blocks (default 60)")
    args = ap.parse_args(argv)
    drift = run_readme_drift()
    if drift:
        for p in drift:
            print(f"check_metrics: README drift: {p}", file=sys.stderr)
        return 1
    try:
        body = run_node_and_scrape(args.blocks, args.timeout)
        families = check_body(body)
    except (ExpositionError, RuntimeError) as e:
        print(f"check_metrics: FAIL: {e}", file=sys.stderr)
        return 1
    n_series = sum(len(f["samples"]) for f in families.values())
    print(f"check_metrics: OK — {len(families)} families, "
          f"{n_series} series, README tables in sync, "
          f"strict exposition parse clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
