"""Metrics tests (reference per-package metrics.go + node/node.go
Prometheus listener): primitive rendering, and a live node exposing
consensus/mempool metrics at /metrics.
"""

import os
import time
import urllib.request

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.libs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsServer,
    Registry,
)


def test_counter_gauge_render():
    r = Registry()
    c = r.counter("test_total", "a counter")
    c.inc()
    c.inc(2)
    g = r.gauge("test_height", "a gauge", ("chain",))
    g.with_labels("main").set(7)
    out = r.render()
    assert "# TYPE test_total counter" in out
    assert "test_total 3" in out
    assert 'test_height{chain="main"} 7' in out


def test_histogram_render():
    r = Registry()
    h = r.histogram("test_secs", "timings", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    out = r.render()
    assert 'test_secs_bucket{le="0.1"} 1' in out
    assert 'test_secs_bucket{le="1"} 2' in out
    assert 'test_secs_bucket{le="+Inf"} 3' in out
    assert "test_secs_count 3" in out


def test_metrics_server():
    r = Registry()
    r.gauge("up", "is up").set(1)
    srv = MetricsServer(r, "127.0.0.1", 0)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.listen_addr}/metrics") as resp:
            body = resp.read().decode()
        assert "up 1" in body
    finally:
        srv.stop()


def test_node_prometheus_endpoint(tmp_path):
    from test_node import init_files, make_config

    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK,
        query_for_event,
    )

    c = make_config(tmp_path, "n0")
    c.instrumentation.prometheus = True
    c.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    init_files(c)
    node = default_new_node(c)
    sub = node.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
    node.start()
    try:
        h = 0
        deadline = time.time() + 30
        while h < 2 and time.time() < deadline:
            m = sub.get(timeout=1.0)
            if m is not None:
                h = m.data["block"].header.height
        assert h >= 2
        addr = node._metrics_server.listen_addr
        with urllib.request.urlopen(f"http://{addr}/metrics") as resp:
            body = resp.read().decode()
        # consensus height tracked and >= 2
        line = next(
            l for l in body.splitlines()
            if l.startswith("tendermint_consensus_height "))
        assert float(line.split()[-1]) >= 2
        assert "tendermint_consensus_validators 1" in body
        assert "tendermint_state_block_processing_time_count" in body
        assert "tendermint_mempool_size" in body
    finally:
        node.stop()


def test_crypto_and_step_metrics_exposition_golden():
    """Exposition-format golden test for the observability families:
    exact line shapes for the CryptoMetrics set and the consensus
    step-duration histogram, as a Prometheus scraper sees them."""
    from tendermint_tpu.metrics import prometheus_metrics

    m = prometheus_metrics("tm")
    m.crypto.batch_verify_seconds.with_labels("jax").observe(0.002)
    m.crypto.batch_size.with_labels("jax").observe(64)
    m.crypto.signatures_verified.inc(63)
    m.crypto.signatures_invalid.inc(1)
    m.crypto.routing_decisions.with_labels("device").inc()
    m.consensus.step_duration.with_labels("propose").observe(0.01)

    out = m.registry.render()
    for line in (
        "# TYPE tm_crypto_batch_verify_seconds histogram",
        'tm_crypto_batch_verify_seconds_bucket{backend="jax",le="0.0025"} 1',
        'tm_crypto_batch_verify_seconds_bucket{backend="jax",le="+Inf"} 1',
        'tm_crypto_batch_verify_seconds_count{backend="jax"} 1',
        "# TYPE tm_crypto_batch_size histogram",
        'tm_crypto_batch_size_bucket{backend="jax",le="64"} 1',
        'tm_crypto_batch_size_count{backend="jax"} 1',
        "# TYPE tm_crypto_signatures_verified_total counter",
        "tm_crypto_signatures_verified_total 63",
        "tm_crypto_signatures_invalid_total 1",
        'tm_crypto_batch_routing_total{route="device"} 1',
        "# TYPE tm_consensus_step_duration_seconds histogram",
        'tm_consensus_step_duration_seconds_bucket{step="propose",le="0.01"} 1',
        'tm_consensus_step_duration_seconds_count{step="propose"} 1',
    ):
        assert line in out, f"missing exposition line: {line}"
    # labeled families with no children render no samples at all
    assert "tm_crypto_batch_routing_total 0" not in out
    assert "tm_consensus_step_duration_seconds_count 0" not in out


def test_nop_metrics_accept_observability_calls():
    """nop_metrics() must swallow every new telemetry call for free —
    instrumentation-off nodes take these code paths on every block."""
    from tendermint_tpu.metrics import nop_metrics

    m = nop_metrics()
    m.crypto.batch_verify_seconds.with_labels("cpu").observe(0.1)
    m.crypto.batch_size.with_labels("cpu").observe(8)
    m.crypto.signatures_verified.inc(8)
    m.crypto.routing_decisions.with_labels("cpu").inc()
    m.consensus.step_duration.with_labels("commit").observe(0.1)
