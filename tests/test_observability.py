"""Block-lifecycle timelines, per-peer network telemetry, and the
consensus stall watchdog (this PR's observability subsystem):

- libs/timeline.py unit behavior (marks, attribution, eviction)
- metric label hygiene: remove_labels + switch-side pruning on
  disconnect (peer churn must not leak series)
- the stall watchdog fires on an injected stall (libs/fail.py hook)
  and serves a non-empty /debug/consensus bundle
- golden /debug/timeline lifecycle for a committed height in a live
  two-node net, with per-peer attribution and stitched tracer spans
- net_info carries p2p.ConnectionStatus per peer
- tools/monitor surfaces stall + peer-lag alerts from the new endpoint
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts"))

from test_node import init_files, make_config

from tendermint_tpu.libs.timeline import COMMITTED_PHASES, Timeline


# --- timeline unit -----------------------------------------------------


def test_timeline_marks_and_vote_attribution():
    tl = Timeline(capacity=8, enabled=True)
    tl.mark(5, "new_height")
    tl.mark(5, "proposal_received", peer_id="peerA", round_=0)
    tl.mark(5, "proposal_received", peer_id="peerB")  # first wins
    tl.mark_vote(5, "prevote", 0, "")  # our own vote
    tl.mark_vote(5, "prevote", 1, "peerA")
    tl.mark_vote(5, "prevote", 1, "peerB")  # first delivery wins
    tl.mark(5, "prevote_23")
    rec = tl.record(5)
    assert rec["height"] == 5
    assert rec["marks"]["proposal_received"]["peer_id"] == "peerA"
    assert rec["marks"]["first_prevote"]["validator_index"] == 0
    assert rec["marks"]["last_prevote"]["validator_index"] == 1
    assert rec["votes"]["prevote"]["1"]["peer_id"] == "peerA"
    assert "prevote_23" in rec["phases_present"]
    assert rec["duration_s"] >= 0.0


def test_timeline_round_churn_counters():
    """mark_round counts every entry into (height, round): re-entries
    (catch-up churn) are distinguishable from slow gossip in stitched
    traces, which first-wins marks alone cannot express."""
    tl = Timeline(capacity=8, enabled=True)
    tl.mark_round(7, 0)
    tl.mark_round(7, 1)
    tl.mark_round(7, 1)  # re-entered round 1
    rec = tl.record(7)
    assert rec["rounds_seen"] == [0, 1]
    assert rec["round_entries"] == {"0": 1, "1": 2}
    assert rec["re_entries"] == 1
    assert rec["max_round"] == 1
    # disabled and non-positive heights never record
    tl.disable()
    tl.mark_round(8, 0)
    assert tl.record(8) is None
    tl.enable()
    tl.mark_round(0, 0)
    assert tl.record(0) is None


def test_timeline_disabled_records_nothing_and_eviction_bounds():
    tl = Timeline(capacity=4, enabled=False)
    tl.mark(1, "commit")
    assert tl.record(1) is None
    tl.enable()
    for h in range(1, 11):
        tl.mark(h, "commit")
    assert len(tl.heights()) == 4
    assert tl.heights() == [7, 8, 9, 10]
    assert tl.latest_height() == 10
    assert tl.record(1) is None
    assert tl.record(10)["marks"]["commit"]["t"] > 0


# --- metric label hygiene ---------------------------------------------


def test_remove_labels_counter_gauge_histogram():
    from tendermint_tpu.libs.metrics import Registry

    r = Registry()
    c = r.counter("c_total", "c", ("peer_id", "chID"))
    g = r.gauge("g", "g", ("peer_id",))
    h = r.histogram("h_secs", "h", ("peer_id",), buckets=(1.0,))
    c.with_labels("p1", "0x20").inc(3)
    c.with_labels("p1", "0x21").inc(1)
    c.with_labels("p2", "0x20").inc(2)
    g.with_labels("p1").set(7)
    h.with_labels("p1").observe(0.5)
    assert 'peer_id="p1"' in r.render()

    # one family, one matching label pair -> both p1 channel series go
    assert c.remove_labels(peer_id="p1") == 2
    out = r.render()
    assert 'c_total{peer_id="p1"' not in out
    assert 'c_total{peer_id="p2",chID="0x20"} 2' in out

    # registry-wide prune hits every family carrying the label
    removed = r.remove_labels(peer_id="p1")
    assert removed == 2  # gauge + histogram series
    out = r.render()
    assert 'peer_id="p1"' not in out
    # family declarations survive pruning (scrapers keep the metadata)
    assert "# TYPE g gauge" in out
    assert "# TYPE h_secs histogram" in out

    # unknown label names and values are no-ops
    assert c.remove_labels(nope="x") == 0
    assert c.remove_labels(peer_id="ghost") == 0


def test_prune_peer_series_nop_metrics():
    from tendermint_tpu.metrics import nop_metrics, prune_peer_series

    assert prune_peer_series(nop_metrics().p2p, "whatever") == 0


def test_switch_prunes_peer_metrics_on_disconnect():
    """Per-peer series appear on connect/traffic and are pruned when the
    switch removes the peer — churn must not grow cardinality."""
    from test_p2p_switch import EchoReactor, make_switch

    from tendermint_tpu.metrics import prometheus_metrics

    m1 = prometheus_metrics("t1")
    sw1, sw2 = make_switch("a"), make_switch("b")
    sw1.metrics = m1.p2p
    r1, r2 = EchoReactor("echo"), EchoReactor("echo")
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    sw1.start()
    sw2.start()
    try:
        peer = sw1.dial_peer(sw2.transport.listen_addr)
        assert peer is not None
        assert peer.send(0x01, b"ping-bytes")
        deadline = time.time() + 5
        while not r2.received and time.time() < deadline:
            time.sleep(0.01)
        body = m1.registry.render()
        assert f'peer_id="{peer.id}"' in body
        assert 'chID="0x01"' in body

        sw1.stop_peer_gracefully(peer)
        body = m1.registry.render()
        assert f'peer_id="{peer.id}"' not in body
        # the families themselves survive
        assert "# TYPE t1_p2p_peer_send_bytes_total counter" in body
    finally:
        sw1.stop()
        sw2.stop()


# --- stall watchdog ----------------------------------------------------


def test_classify_stall_reasons():
    from tendermint_tpu.consensus import cstypes
    from tendermint_tpu.consensus.state import classify_stall

    rs = cstypes.RoundState()
    rs.step = cstypes.STEP_PROPOSE
    assert classify_stall(rs) == "no_proposal"
    rs.step = cstypes.STEP_PREVOTE_WAIT
    assert classify_stall(rs) == "no_prevote_quorum"
    rs.step = cstypes.STEP_COMMIT
    assert classify_stall(rs) == "commit_not_finalized"


def test_watchdog_fires_on_injected_stall(tmp_path):
    """A consensus thread stalled via a libs/fail.py hook must trip the
    watchdog within stall_threshold_s: consensus_stalls_total{reason}
    increments and /debug/consensus serves a non-empty bundle."""
    from tendermint_tpu.libs import fail
    from tendermint_tpu.node import default_new_node

    c = make_config(tmp_path, "stall")
    c.base.prof_laddr = "tcp://127.0.0.1:0"
    c.instrumentation.prometheus = True
    c.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    c.instrumentation.stall_threshold_s = 0.5
    init_files(c)

    fired = threading.Event()

    def stall_once():
        if not fired.is_set():
            fired.set()
            time.sleep(2.0)

    fail.set_hook("FinalizeCommit.BeforeSave", stall_once)
    node = default_new_node(c)
    node.start()
    try:
        # wait for the INJECTED stall's bundle specifically: under
        # full-gate CPU load an unrelated slow round can trip first
        # (and the watchdog now also re-records when a stuck round's
        # diagnosis changes), so bundle order isn't guaranteed
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                b.get("reason") == "commit_not_finalized"
                for b in node.watchdog.stall_bundles()):
            time.sleep(0.05)
        assert node.watchdog.stalls_total >= 1, "watchdog never tripped"

        addr = node._prof_server.listen_addr
        with urllib.request.urlopen(
                f"http://{addr}/debug/consensus", timeout=10) as r:
            data = json.load(r)
        assert data["stalls_total"] >= 1
        assert data["threshold_s"] == 0.5
        bundle = next((b for b in data["stalls"]
                       if b["reason"] == "commit_not_finalized"), None)
        assert bundle is not None, data["stalls"]
        assert bundle["dwell_s"] >= 0.5
        assert bundle["round_state"]["height"] >= 1
        assert "missing_validators" in bundle
        assert "inflight_verify_batches" in bundle
        # the live section always renders, stalled or not
        assert data["live"]["round_state"]["height"] >= 1

        body = node.metrics.registry.render()
        assert ('tendermint_consensus_stalls_total'
                '{reason="commit_not_finalized"}') in body
        assert "tendermint_consensus_round_dwell_seconds" in body
    finally:
        fail.clear_hook()
        node.stop()


def test_debug_crypto_names_the_verifier_the_node_got(
        tmp_path, monkeypatch, caplog):
    """A node resolves its batch verifier once at start-up and says so:
    one log line, and /debug/crypto fields for backend, platform,
    device_kind, device count, fused-kernel mode, warm-up outcome and
    the cutoff in force. Under the test platform that device is `cpu` —
    the case that used to be silent on a machine whose chip was taken."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.node import default_new_node

    monkeypatch.setenv("TM_TPU_WARMUP", "0")  # the report, not the compile
    prev = crypto_batch.default_backend_name()
    crypto_batch.set_default_backend("adaptive")
    c = make_config(tmp_path, "verifier")
    c.base.prof_laddr = "tcp://127.0.0.1:0"
    init_files(c)
    try:
        with caplog.at_level("INFO", logger="node"):
            node = default_new_node(c)
            node.start()
            try:
                node._verify_warmup_thread.join(timeout=60)
                assert not node._verify_warmup_thread.is_alive()
                with urllib.request.urlopen(
                        f"http://{node._prof_server.listen_addr}"
                        "/debug/crypto", timeout=10) as r:
                    v = json.load(r)["verifier"]
            finally:
                node.stop()
    finally:
        crypto_batch.set_default_backend(prev)
    assert v["backend"] == "adaptive"
    assert v["platform"] == "cpu" and v["device_kind"] == "cpu"
    assert v["device_count"] == 8  # conftest's virtual mesh
    assert v["fused_kernel"] == "off"  # Mosaic only lowers on a TPU
    assert v["warmup"] == "disabled"
    assert v["batch_cutoff"] == crypto_batch.effective_batch_min()
    line = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("crypto verifier:")]
    assert len(line) == 1 and "platform=cpu" in line[0], line


# --- e2e: timeline + net_info over a live two-node net -----------------


def test_two_node_timeline_and_net_info(tmp_path):
    """Golden lifecycle: a committed height's /debug/timeline record has
    every phase mark, per-peer vote attribution from the other
    validator, and stitched tracer spans; net_info reports each peer's
    ConnectionStatus."""
    from tendermint_tpu import config as cfg
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK,
        query_for_event,
    )

    cs = [make_config(tmp_path, f"tl{i}") for i in range(2)]
    pvs = []
    for c in cs:
        cfg.ensure_root(c.root_dir)
        NodeKey.load_or_gen(c.base.node_key_path())
        pvs.append(load_or_gen_file_pv(c.base.priv_validator_path()))
    doc = GenesisDoc(
        chain_id="timeline-chain",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    for c in cs:
        doc.save(c.base.genesis_path())

    # n1 carries the observability stack under test
    cs[1].base.prof_laddr = "tcp://127.0.0.1:0"
    cs[1].rpc.laddr = "tcp://127.0.0.1:0"
    cs[1].instrumentation.tracing = True

    n0 = default_new_node(cs[0])
    n0.start()
    n1 = None
    try:
        cs[1].p2p.persistent_peers = (
            f"{n0.node_key.id}@{n0.transport.listen_addr}")
        n1 = default_new_node(cs[1])
        sub = n1.event_bus.subscribe(
            "tl", query_for_event(EVENT_NEW_BLOCK), 16)
        n1.start()
        height = 0
        deadline = time.time() + 60
        while height < 3 and time.time() < deadline:
            msg = sub.get(timeout=1.0)
            if msg is not None:
                height = msg.data["block"].header.height
        assert height >= 3, f"two-node net stalled at {height}"

        paddr = n1._prof_server.listen_addr
        with urllib.request.urlopen(
                f"http://{paddr}/debug/timeline?height=2", timeout=10) as r:
            rec = json.load(r)
        assert rec["height"] == 2
        for phase in COMMITTED_PHASES:
            assert phase in rec["marks"], (
                f"missing phase {phase}: {sorted(rec['marks'])}")
        # both validators' votes were seen; the other validator's came
        # over p2p, so at least one carries a non-empty peer_id
        assert len(rec["votes"]["prevote"]) == 2
        peer_ids = {v["peer_id"] for kind in rec["votes"].values()
                    for v in kind.values()}
        assert n0.node_key.id in peer_ids, peer_ids
        # phase ordering sanity on the wall clock
        marks = rec["marks"]
        assert marks["prevote_23"]["t"] <= marks["precommit_23"]["t"]
        assert marks["commit"]["t"] <= marks["apply_block"]["t"]
        # tracer spans for this height are stitched in
        assert any(s["name"].startswith("consensus.")
                   for s in rec["spans"]), rec["spans"][:3]

        # latest-height default + unknown-height 404
        with urllib.request.urlopen(
                f"http://{paddr}/debug/timeline", timeout=10) as r:
            assert json.load(r)["height"] >= 2
        try:
            urllib.request.urlopen(
                f"http://{paddr}/debug/timeline?height=99999", timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # net_info satellite: ConnectionStatus per peer
        ni = HTTPClient(n1.rpc_listen_addr).net_info()
        assert int(ni["n_peers"]) == 1
        st = ni["peers"][0]["connection_status"]
        assert st["Duration"] > 0
        assert st["SendMonitor"]["Bytes"] > 0
        assert st["RecvMonitor"]["Bytes"] > 0
        chans = {ch["ID"]: ch for ch in st["Channels"]}
        assert 0x22 in chans  # the vote channel exists
        assert chans[0x22]["SendQueueCapacity"] > 0

        # per-peer telemetry appeared on n0's side too (nop there) and
        # on any instrumented registry; n1 has no prometheus here, so
        # check the p2p families on the live switch metrics of n0 are
        # nops without error — i.e. nothing crashed getting this far.
    finally:
        if n1 is not None:
            n1.stop()
        n0.stop()


# --- monitor integration ----------------------------------------------


def _stub_debug_server(payload: dict):
    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    host, port = srv.server_address[:2]
    return srv, f"{host}:{port}"


def test_monitor_surfaces_stall_and_peer_lag():
    from tendermint_tpu.tools.monitor import (
        HEALTH_FULL,
        HEALTH_MODERATE,
        Monitor,
    )

    payload = {
        "height": 7, "round": 2, "step": "PrevoteWait",
        "dwell_s": 42.0, "threshold_s": 30.0, "stalls_total": 2,
        "stalls": [{"reason": "no_prevote_quorum", "dwell_s": 31.0,
                    "round_state": {"height": 7, "round": 2}}],
        "live": {"peers": [{"peer_id": "ab" * 20, "lag_blocks": 5}]},
    }
    srv, daddr = _stub_debug_server(payload)
    try:
        mon = Monitor(["rpc-addr"], debug_addrs=[daddr])
        ns = mon.nodes["rpc-addr"]
        ns.mark_online()
        ns.height = 7
        mon._poll_debug(ns, daddr)
        assert ns.round_dwell_s == 42.0
        assert ns.stalls_total == 2
        assert ns.stalled
        assert ns.max_peer_lag == 5
        # heights agree and node is up — but the stall forces moderate
        assert mon.health() == HEALTH_MODERATE
        snap = mon.snapshot()
        assert snap["stall_alerts"][0]["reason"] == "no_prevote_quorum"
        assert snap["stall_alerts"][0]["addr"] == "rpc-addr"
        assert snap["nodes"][0]["stalled"] is True
        assert snap["nodes"][0]["max_peer_lag"] == 5

        # healthy debug payload -> full again
        ns.round_dwell_s, ns.max_peer_lag = 0.2, 0
        ns.stall_alerts = []
        assert mon.health() == HEALTH_FULL
    finally:
        srv.shutdown()
        srv.server_close()


# --- check_metrics satellite ------------------------------------------


def test_check_metrics_help_text_lint():
    import check_metrics as cm

    from tendermint_tpu.libs.metrics import Registry

    r = Registry()
    r.counter("tendermint_undocumented_total", "")  # empty help
    body = r.render()
    # make the body pass the family-presence gate by checking namespace
    # mismatch first: use check_body's parse path directly
    fams = cm.parse_exposition(body)
    assert (fams["tendermint_undocumented_total"].get("help") or "") == ""
    with pytest.raises(cm.ExpositionError, match="without help text"):
        # full check_body path on a registry that has all required
        # families plus one undocumented straggler
        from tendermint_tpu.metrics import prometheus_metrics

        m = prometheus_metrics("tendermint")
        m.registry.counter("tendermint_mystery_total", "  ")
        m.crypto.batch_verify_seconds.with_labels("cpu").observe(0.001)
        m.crypto.signatures_verified.inc()
        m.consensus.step_duration.with_labels("propose").observe(0.001)
        cm.check_body(m.registry.render())


def test_new_families_registered_with_help():
    """Every PR-3 family is registered, documented, and prunable."""
    import check_metrics as cm

    from tendermint_tpu.metrics import prometheus_metrics

    m = prometheus_metrics("tendermint")
    fams = cm.parse_exposition(m.registry.render())
    for f in ("tendermint_consensus_round_dwell_seconds",
              "tendermint_consensus_stalls_total",
              "tendermint_p2p_peer_msg_recv_total",
              "tendermint_p2p_peer_lag_blocks",
              "tendermint_p2p_peer_send_rate_bytes",
              "tendermint_p2p_peer_recv_rate_bytes",
              "tendermint_p2p_peer_pending_send_msgs"):
        assert f in fams, f
        assert (fams[f]["help"] or "").strip(), f"no help for {f}"


def test_nop_metrics_absorb_watchdog_and_p2p_calls():
    from tendermint_tpu.metrics import nop_metrics

    m = nop_metrics()
    m.consensus.round_dwell.set(1.5)
    m.consensus.stalls.with_labels("no_proposal").inc()
    m.p2p.peer_msg_recv_total.with_labels("p", "0x20", "VoteMessage").inc()
    m.p2p.peer_lag_blocks.with_labels("p").set(3)
    m.p2p.peer_send_rate.with_labels("p").set(1000.0)
    m.p2p.peer_receive_bytes_total.with_labels("p", "0x20").inc(10)
