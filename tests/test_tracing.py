"""libs/tracing.py — the span tracer behind /debug/trace.

Covers: span recording + nesting, Chrome-trace JSON schema, ring-buffer
bounds, the disabled path's no-op guarantees (shared context manager,
empty buffer, no measurable overhead on BatchVerifier.verify), and the
ProfServer /debug/trace route.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.libs.tracing import Tracer, get_tracer


def test_disabled_tracer_records_nothing():
    t = Tracer()
    assert not t.enabled
    with t.span("ignored", cat="x"):
        pass
    assert t.events() == []


def test_disabled_span_is_shared_noop():
    # the disabled fast path must not allocate per call
    t = Tracer()
    assert t.span("a") is t.span("b")


def test_enabled_spans_record_and_nest():
    t = Tracer(enabled=True)
    with t.span("outer", cat="test", height=5):
        with t.span("inner", cat="test"):
            time.sleep(0.001)
    evs = t.events()
    # inner finishes first (records are appended at span exit)
    assert [e.name for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert outer.start_ns <= inner.start_ns
    assert inner.end_ns <= outer.end_ns
    assert outer.dur_ns >= inner.dur_ns >= 1_000_000  # slept 1ms
    assert outer.args == {"height": 5}


def test_ring_buffer_keeps_newest():
    t = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert [e.name for e in t.events()] == ["s6", "s7", "s8", "s9"]


def test_chrome_trace_schema():
    t = Tracer(enabled=True)
    with t.span("alpha", cat="consensus", height=3, round=0):
        pass
    doc = json.loads(t.chrome_trace_json())
    assert isinstance(doc["traceEvents"], list)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert metas and metas[0]["name"] == "thread_name"
    (ev,) = spans
    assert ev["name"] == "alpha"
    assert ev["cat"] == "consensus"
    assert ev["args"] == {"height": 3, "round": 0}
    # complete events carry µs timestamps + duration and pid/tid ints
    for key in ("ts", "dur"):
        assert isinstance(ev[key], float)
    for key in ("pid", "tid"):
        assert isinstance(ev[key], int)


def test_inflight_span_exported_with_running_duration():
    t = Tracer(enabled=True)
    with t.span("outer", cat="consensus", height=7):
        with t.span("inner", cat="state"):
            pass
        doc = t.chrome_trace()
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        inner, outer = spans["inner"], spans["outer"]
        assert outer["args"] == {"height": 7, "inflight": True}
        assert "inflight" not in (inner.get("args") or {})
        # the open parent still encloses its finished child
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # once closed it exports as a normal finished span
    doc = t.chrome_trace()
    outer = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "outer"]
    assert len(outer) == 1 and outer[0]["args"] == {"height": 7}


def test_enable_disable_and_clear():
    t = Tracer()
    t.enable(capacity=128)
    assert t.enabled and t.capacity == 128
    with t.span("kept"):
        pass
    t.disable()
    with t.span("dropped"):
        pass
    assert [e.name for e in t.events()] == ["kept"]
    t.clear()
    assert t.events() == []


def test_global_tracer_is_disabled_by_default():
    assert get_tracer() is get_tracer()
    assert not get_tracer().enabled


def test_disabled_instrumentation_adds_no_overhead_to_verify():
    """BatchVerifier.verify with no metrics sink and tracing off must
    stay within noise of the raw backend call (the hot-path guarantee
    that always-on instrumentation is free until enabled)."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import PrivKeyEd25519

    assert B.get_metrics() is None
    assert not get_tracer().enabled

    priv = PrivKeyEd25519.generate()
    pub = priv.pub_key().bytes()
    msg = b"overhead-probe"
    sig = priv.sign(msg)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            v = B.CPUBatchVerifier()
            v.add(msg, sig, pub)
            assert v.verify() == [True]
        return time.perf_counter() - t0

    run(10)  # warm
    instrumented = run(200)

    class Raw(B.CPUBatchVerifier):
        verify = B.CPUBatchVerifier._verify  # bypass the telemetry wrapper

    def run_raw(n):
        t0 = time.perf_counter()
        for _ in range(n):
            v = Raw()
            v.add(msg, sig, pub)
            assert v.verify() == [True]
        return time.perf_counter() - t0

    run_raw(10)
    raw = run_raw(200)
    # generous bound — the wrapper is one module-global load, one
    # attribute read and one branch per call; 2x covers CI noise
    assert instrumented < raw * 2 + 0.05, (instrumented, raw)


def test_crypto_metrics_recorded_via_global_sink():
    """batch.set_metrics wires every verifier call site at once."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.metrics import prometheus_metrics

    m = prometheus_metrics("t_trace")
    priv = PrivKeyEd25519.generate()
    pub = priv.pub_key().bytes()
    sig = priv.sign(b"m1")
    B.set_metrics(m.crypto)
    try:
        v = B.CPUBatchVerifier()
        v.add(b"m1", sig, pub)
        v.add(b"m2", sig, pub)  # wrong message: invalid
        assert v.verify() == [True, False]
    finally:
        B.set_metrics(None)
    out = m.registry.render()
    assert "t_trace_crypto_signatures_verified_total 1" in out
    assert "t_trace_crypto_signatures_invalid_total 1" in out
    assert 't_trace_crypto_batch_verify_seconds_count{backend="cpu"} 1' in out
    assert 't_trace_crypto_batch_size_count{backend="cpu"} 1' in out


def test_adaptive_routing_decision_counter():
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.metrics import prometheus_metrics

    m = prometheus_metrics("t_route")
    B.set_metrics(m.crypto)
    try:
        v = B.AdaptiveBatchVerifier(B.CPUBatchVerifier, min_device_batch=4)
        assert v.verify() == []  # empty → below cutoff → cpu route
    finally:
        B.set_metrics(None)
    assert ('t_route_crypto_batch_routing_total{route="cpu"} 1'
            in m.registry.render())


def test_prof_server_debug_trace_route():
    from tendermint_tpu.rpc.prof import ProfServer

    tracer = Tracer(enabled=True)
    with tracer.span("consensus.enterPropose", cat="consensus", height=1):
        pass
    srv = ProfServer("127.0.0.1", 0, tracer=tracer)
    srv.start()
    try:
        url = f"http://{srv.listen_addr}/debug/trace"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read().decode())
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert names == ["consensus.enterPropose"]
        # ?clear=1 returns the buffer then empties it
        with urllib.request.urlopen(url + "?clear=1", timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert tracer.events() == []
    finally:
        srv.stop()


def test_concurrent_cpu_profile_returns_429():
    from tendermint_tpu.rpc import prof as prof_mod
    from tendermint_tpu.rpc.prof import ProfServer

    srv = ProfServer("127.0.0.1", 0)
    srv.start()
    try:
        url = f"http://{srv.listen_addr}/debug/pprof/profile?seconds=1"
        results = {}

        def first():
            with urllib.request.urlopen(url, timeout=15) as r:
                results["first"] = r.status

        t = threading.Thread(target=first)
        t.start()
        # wait until the first request holds the profiler
        deadline = time.time() + 5
        while not prof_mod._profile_lock.locked() and time.time() < deadline:
            time.sleep(0.01)
        assert prof_mod._profile_lock.locked()
        try:
            urllib.request.urlopen(url, timeout=15)
            raise AssertionError("second concurrent profile did not 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
        t.join()
        assert results["first"] == 200
    finally:
        srv.stop()


def test_node_tracing_end_to_end(tmp_path):
    """config.instrumentation.tracing + prof_laddr: after 3 committed
    blocks the prof server returns a non-empty Chrome-trace JSON with
    consensus-step, WAL and state spans, and stop() disables the
    global tracer again."""
    from test_node import init_files, make_config

    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK,
        query_for_event,
    )

    c = make_config(tmp_path, "n0")
    c.base.prof_laddr = "tcp://127.0.0.1:0"
    c.instrumentation.tracing = True
    c.instrumentation.tracing_buffer_size = 8192
    init_files(c)
    node = default_new_node(c)
    sub = node.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
    node.start()
    try:
        h = 0
        deadline = time.time() + 30
        while h < 3 and time.time() < deadline:
            m = sub.get(timeout=1.0)
            if m is not None:
                h = m.data["block"].header.height
        assert h >= 3
        addr = node._prof_server.listen_addr
        with urllib.request.urlopen(
                f"http://{addr}/debug/trace", timeout=10) as r:
            doc = json.loads(r.read().decode())
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert spans, "trace buffer empty after 3 blocks"
        names = {e["name"] for e in spans}
        assert "consensus.enterPropose" in names
        assert "consensus.finalizeCommit" in names
        assert "wal.write" in names
        assert "state.applyBlock" in names
        # spans nest sanely: every applyBlock sits inside finalizeCommit
        fin = [e for e in spans if e["name"] == "consensus.finalizeCommit"]
        apply_spans = [e for e in spans if e["name"] == "state.applyBlock"]
        for a in apply_spans:
            assert any(f["ts"] <= a["ts"] and
                       a["ts"] + a["dur"] <= f["ts"] + f["dur"] + 1e-3
                       for f in fin)
    finally:
        node.stop()
    assert not get_tracer().enabled


# --- causes, requests, self time, drops, stalls (PR 26) ---------------------


import gc
import subprocess
import sys

import pytest

from tendermint_tpu.libs import tracing


@pytest.fixture
def global_tracer():
    """The process-global recorder, on for one test and left as found."""
    t = get_tracer()
    assert not t.enabled
    t.enable()
    t.clear()
    try:
        yield t
    finally:
        t.disable()
        t.clear()


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e.name, []).append(e)
    return out


def _tree(events):
    """{(name, parent's name or None)} of a list of records."""
    by_id = {e.span_id: e for e in events}
    return {(e.name, by_id[e.parent_id].name if e.parent_id in by_id else None)
            for e in events}


def test_parent_and_request_ids_nest_on_one_thread():
    t = Tracer(enabled=True)
    with t.span("root", request=("block", 7)) as root:
        with t.span("child"):
            with t.span("grandchild", request=("post", 1)):
                pass
        with t.span("sibling"):
            pass
    with t.span("other"):
        pass
    ev = {e.name: e for e in t.events()}
    assert len({e.span_id for e in ev.values()}) == 5 and 0 not in {
        e.span_id for e in ev.values()}
    assert ev["root"].parent_id == 0 and ev["other"].parent_id == 0
    assert ev["child"].parent_id == ev["root"].span_id == root.span_id
    assert ev["sibling"].parent_id == ev["root"].span_id
    assert ev["grandchild"].parent_id == ev["child"].span_id
    # a request id is inherited unless given, and never leaks to the next root
    assert ev["root"].request == ev["child"].request == ("block", 7)
    assert ev["sibling"].request == ("block", 7)
    assert ev["grandchild"].request == ("post", 1)
    assert ev["other"].request is None
    assert t.cause() is None  # nothing left open on this thread


def test_cause_carries_parent_and_request_across_threads():
    t = Tracer(enabled=True)
    seen = {}

    def worker(cause):
        with t.span("worker.job", cause=cause) as sp:
            seen["inner"] = t.cause()
            seen["id"] = sp.span_id

    with t.span("submitter", request=("drain", 3)) as sub:
        cause = t.cause()
        th = threading.Thread(target=worker, args=(cause,))
        th.start()
        th.join(timeout=5)
    assert not th.is_alive()
    ev = {e.name: e for e in t.events()}
    assert cause == (sub.span_id, ("drain", 3))
    assert ev["worker.job"].parent_id == ev["submitter"].span_id
    assert ev["worker.job"].request == ("drain", 3)
    assert ev["worker.job"].thread_id != ev["submitter"].thread_id
    assert seen["inner"] == (seen["id"], ("drain", 3))
    # the recorder off: no cause, no request, nothing recorded
    off = Tracer()
    assert off.cause() is None and off.request("post") is None
    assert off.record("x", 0, 1) is None and off.events() == []


def test_record_takes_two_clock_readings_and_the_open_span_as_parent():
    t = Tracer(enabled=True)
    with t.span("outer", request=("batch", 1)) as outer:
        got = t.record("queue.wait", outer.start_ns - 5_000, outer.start_ns,
                       "crypto", n=4)
    wait = _by_name(t.events())["queue.wait"][0]
    assert got == (wait.span_id, ("batch", 1))
    assert wait.parent_id == outer.span_id and wait.dur_ns == 5_000
    assert wait.args == {"n": 4} and wait.cat == "crypto"


def test_set_adds_counts_known_only_at_the_end():
    t = Tracer(enabled=True)
    with t.span("a", n=1) as sp:
        sp.set(rejected=2)
    with t.span("b") as sp:
        sp.set(bytes=9)
    with Tracer().span("off") as sp:
        sp.set(ignored=1)  # the shared no-op takes it too
    ev = {e.name: e for e in t.events()}
    assert ev["a"].args == {"n": 1, "rejected": 2}
    assert ev["b"].args == {"bytes": 9}


def test_timed_reads_its_clock_whether_or_not_the_recorder_is_on():
    for t in (Tracer(), Tracer(enabled=True)):
        with t.timed("stage", cat="state") as sp:
            time.sleep(0.002)
        assert 0.002 <= sp.seconds < 1.0
    assert [e.name for e in t.events()] == ["stage"]
    assert t.events()[0].dur_ns == round(sp.seconds * 1e9)


def test_self_time_of_a_parent_with_two_children():
    def rec(name, start, dur, span_id, parent_id, thread=1):
        return tracing.SpanRecord(name, "", start, dur, thread, "t", None,
                                  span_id, parent_id)

    records = [
        rec("parent", 0, 100, 1, 0),
        rec("child.a", 10, 20, 2, 1),
        rec("child.b", 50, 30, 3, 1),
        rec("grandchild", 55, 10, 4, 3),
        rec("elsewhere", 0, 90, 5, 1, thread=2),  # beside it, not instead
    ]
    own = tracing.self_times(records)
    assert own == {1: 50, 2: 20, 3: 20, 4: 10, 5: 90}
    # overlapping or overhanging children never take more than the parent has
    own = tracing.self_times([rec("p", 0, 100, 1, 0), rec("a", 10, 50, 2, 1),
                              rec("b", 40, 100, 3, 1)])
    assert own[1] == 10


def test_dropped_counts_a_ring_overflow():
    t = Tracer(capacity=4, enabled=True)
    assert t.dropped == 0
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert t.dropped == 6 and len(t.events()) == 4
    assert t.chrome_trace()["dropped"] == 6
    t.clear()
    assert t.dropped == 0
    th = threading.Thread(target=lambda: [t.record("r", 0, 1) for _ in range(5)])
    th.start()
    th.join(timeout=5)
    with t.span("after"):
        pass
    assert t.dropped == 2  # 6 finished since clear(), 4 kept, on two threads


def test_inflight_export_carries_ids_and_the_enclosing_span():
    t = Tracer(enabled=True)
    with t.span("outer", request=("block", 2)):
        with t.span("inner"):
            pass
        doc = t.chrome_trace()
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans["outer"]["args"] == {"inflight": True}
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["request"] == spans["outer"]["request"] == ["block", 2]
    assert doc["dropped"] == 0
    # an open span is exported once even if it closes during the export
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 2


def test_runtime_gc_is_recorded_and_the_hook_leaves_with_disable():
    t = Tracer()
    before = len(gc.callbacks)
    t.enable()
    try:
        assert len(gc.callbacks) == before + 1
        t.enable()  # twice is once
        assert len(gc.callbacks) == before + 1
        with t.span("outer"):
            gc.collect()
    finally:
        t.disable()
    assert len(gc.callbacks) == before
    ev = _by_name(t.events())
    full = [e for e in ev["runtime.gc"] if e.args["generation"] == 2]
    assert full and full[-1].parent_id == ev["outer"][0].span_id
    assert set(full[-1].args) == {"generation", "collected"}
    n = len(t.events())
    gc.collect()
    assert len(t.events()) == n  # the hook is gone
    # a recorder switched on by its constructor hooks nothing process-wide
    Tracer(enabled=True)
    assert len(gc.callbacks) == before


def test_annotation_mirror_follows_the_recorder():
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    t = Tracer()
    t._annotation = Annotation
    with t.span("off"):
        pass
    assert entered == []
    t.enable()
    try:
        with t.span("on"):
            with t.span("inner"):
                pass
    finally:
        t.disable()
    assert entered == ["on", "inner", "/inner", "/on"]


def test_tracing_imports_without_jax():
    code = ("import sys; import tendermint_tpu.libs.tracing as t; "
            "assert 'jax' not in sys.modules, 'importing libs.tracing pulled in jax'; "
            "tr = t.Tracer(enabled=True)\n"
            "with tr.span('s'): pass\n"
            "assert tr._annotation_cls() is None and 'jax' not in sys.modules")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr


def test_verify_async_names_its_submitter_and_measures_the_queue(global_tracer):
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import PrivKeyEd25519

    priv = PrivKeyEd25519.generate()
    v = B.CPUBatchVerifier()
    for i in range(3):
        msg = b"m%d" % i
        v.add(msg, priv.sign(msg), priv.pub_key().bytes())
    with global_tracer.span("caller", request=("block", 9)) as caller:
        fut = v.verify_async()
    assert fut.result(timeout=10) == [True] * 3
    ev = _by_name(global_tracer.events())
    (bv,), (wait,) = ev["crypto.batchVerify"], ev["crypto.dispatchWait"]
    assert bv.parent_id == caller.span_id and bv.request == ("block", 9)
    assert bv.thread_id != ev["caller"][0].thread_id
    assert bv.args == {"backend": "cpu", "n": 3, "route": "direct",
                       "cache_hits": 0}
    assert wait.parent_id == bv.span_id and wait.end_ns == bv.start_ns
    assert wait.start_ns >= ev["caller"][0].start_ns
    assert wait.args == {"backend": "cpu", "n": 3}
    # called with no span open, a batch is its own request
    assert v.verify() == [True] * 3
    lone = _by_name(global_tracer.events())["crypto.batchVerify"][-1]
    assert lone.parent_id == 0 and lone.request[0] == "batch"


def test_ingest_drain_names_the_span_that_submitted(global_tracer):
    sys.path.insert(0, os.path.dirname(__file__))
    from test_mempool_throughput import KEYS, make_pool

    from tendermint_tpu.mempool.preverify import make_signed_tx

    mp = make_pool(preverify_batch=True, preverify_batch_max=64)
    try:
        with global_tracer.span("rpc.call", request=("post", 1)) as call:
            futs = [mp.check_tx_nowait(make_signed_tx(KEYS[0], b"k%d=v" % i))
                    for i in range(4)]
        bad = make_signed_tx(KEYS[1], b"bad=1")
        futs.append(mp.check_tx_nowait(bad[:-1] + bytes([bad[-1] ^ 1])))
        codes = [f.result(timeout=10).code for f in futs]
    finally:
        mp.stop()
    assert codes[:4] == [0] * 4 and codes[4] != 0
    ev = _by_name(global_tracer.events())
    drains = ev["ingest.drain"]
    assert drains[0].parent_id == call.span_id
    assert all(d.request[0] == "drain" for d in drains)
    assert sum(d.args["n"] for d in drains) == 5
    assert sum(d.args["rejected"] for d in drains) == 1
    assert all(d.args["wait_max_ms"] >= 0 for d in drains)
    ids = {d.span_id for d in drains}
    assert {e.parent_id for e in ev["crypto.batchVerify"]} <= ids
    assert {e.parent_id for e in ev["ingest.checkTx"]} <= ids
    assert {e.request for e in ev["crypto.batchVerify"]} <= {
        d.request for d in drains}
    assert "rpc.call" not in {e.name for e in global_tracer.events()
                              if e.thread_id == drains[0].thread_id}


def test_indexer_drain_names_the_publishing_span(global_tracer):
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.state.txindex import IndexerService, KVTxIndexer
    from tendermint_tpu.types.event_bus import EventBus

    bus = EventBus()
    bus.start()
    svc = IndexerService(KVTxIndexer(MemDB()), bus)
    svc.start()
    try:
        with global_tracer.span("commit.events", request=("block", 5)) as pub:
            bus.publish_txs(5, [b"a=1", b"b=2"],
                            [abci.ResponseDeliverTx(), abci.ResponseDeliverTx()])
        deadline = time.time() + 5
        while time.time() < deadline and not _by_name(
                global_tracer.events()).get("txindex.drain"):
            time.sleep(0.01)
    finally:
        svc.stop()
        bus.stop()
    drains = _by_name(global_tracer.events())["txindex.drain"]
    assert sum(d.args["txs"] for d in drains) == 2
    assert all(d.parent_id == pub.span_id and d.request == ("block", 5)
               and d.args["height"] == 5 for d in drains)
    assert drains[0].thread_id != pub._state.ident


def test_fast_sync_block_span_tree(global_tracer):
    """One pass of the pipelined sync loop over a toy chain: names and
    parents of the block-sync layer, not times."""
    sys.path.insert(0, os.path.dirname(__file__))
    from test_crypto_async import _make_reactor

    from tendermint_tpu.crypto import batch as crypto_batch

    crypto_batch.set_async_enabled(True)
    reactor, exec_, store, _, _ = _make_reactor(nblocks=3)
    assert reactor._try_sync_batch() is True
    assert exec_.applied == [1, 2, 3]
    events = global_tracer.events()
    tree = _tree(events)
    assert {("fastsync.block", None),
            ("fastsync.partSet", "fastsync.block"),
            ("fastsync.verifyBegin", "fastsync.block"),
            ("fastsync.verifyWait", "fastsync.block"),
            ("store.saveBlock", "fastsync.block"),
            ("crypto.batchVerify", "fastsync.verifyBegin"),
            ("crypto.dispatchWait", "crypto.batchVerify")} <= tree
    ev = _by_name(events)
    assert [b.args["height"] for b in ev["fastsync.block"]] == [1, 2, 3]
    assert [b.request for b in ev["fastsync.block"]] == [
        ("block", 1), ("block", 2), ("block", 3)]
    # verify(k+1) is dispatched under block k's span and carries k+1's id
    begins = {b.args["height"]: b for b in ev["fastsync.verifyBegin"]}
    blocks = {b.args["height"]: b for b in ev["fastsync.block"]}
    assert begins[1].parent_id == blocks[1].span_id
    assert begins[2].parent_id == blocks[1].span_id
    assert begins[2].request == ("block", 2)
    assert {e.request for e in ev["crypto.batchVerify"]} == {
        ("block", 1), ("block", 2), ("block", 3)}
    # the download is the block's cause when the p2p thread decoded it
    reactor2, _, _, _, _ = _make_reactor(nblocks=1)
    reactor2._recv_cause[1] = global_tracer.record(
        "p2p.recvBlock", 1, 2, "p2p", request=("block", 1), height=1)
    assert reactor2._try_sync_batch() is True
    assert ("fastsync.block", "p2p.recvBlock") in _tree(global_tracer.events())


def test_batch_post_and_block_span_tree(tmp_path):
    """A node with the recorder on, one JSON-RPC batch POST of signed
    txs, the block that commits them: the span tree of README "Spans"
    (names and parents, not times)."""
    import base64

    from test_node import init_files, make_config

    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.mempool.preverify import make_signed_tx
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.types.event_bus import (
        EVENT_NEW_BLOCK,
        query_for_event,
    )

    c = make_config(tmp_path, "n0")
    c.rpc.laddr = "tcp://127.0.0.1:0"
    c.mempool.preverify_batch = True
    c.instrumentation.tracing = True
    init_files(c)
    node = default_new_node(c)
    sub = node.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
    node.start()
    try:
        key = PrivKeyEd25519.generate()
        reqs = [{"jsonrpc": "2.0", "id": i, "method": "broadcast_tx_async",
                 "params": {"tx": base64.b64encode(
                     make_signed_tx(key, b"span%d=v" % i)).decode()}}
                for i in range(3)]
        reqs.append({"jsonrpc": "2.0", "id": 9, "method": "health",
                     "params": {}})
        post = urllib.request.Request(
            f"http://{node.rpc_listen_addr}/", data=json.dumps(reqs).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(post, timeout=10) as r:
            assert len(json.loads(r.read())) == 4
        # the txs' block, and a second height: block 1 has no commit to verify
        txs, h, deadline = 0, 0, time.time() + 30
        while (txs < 3 or h < 2) and time.time() < deadline:
            m = sub.get(timeout=1.0)
            if m is not None:
                txs += len(m.data["block"].data.txs)
                h = m.data["block"].header.height
        assert txs == 3 and h >= 2
        deadline = time.time() + 5
        while time.time() < deadline and not any(
                e.name == "txindex.drain" for e in get_tracer().events()):
            time.sleep(0.01)
        events = get_tracer().events()
    finally:
        node.stop()
    assert not get_tracer().enabled
    tree = _tree(events)
    assert {("rpc.handle", None), ("rpc.read", "rpc.handle"),
            ("rpc.call", "rpc.handle"), ("rpc.write", "rpc.handle"),
            ("ingest.drain", "rpc.call"),
            ("crypto.batchVerify", "ingest.drain"),
            ("ingest.checkTx", "ingest.drain"),
            ("state.applyBlock", "consensus.finalizeCommit"),
            ("store.saveBlock", "consensus.finalizeCommit"),
            ("state.validateBlock", "state.applyBlock"),
            ("valset.verifyCommit", "state.validateBlock"),
            ("commit.execute", "state.applyBlock"),
            ("state.saveResponses", "state.applyBlock"),
            ("state.updateState", "state.applyBlock"),
            ("commit.appCommit", "state.applyBlock"),
            ("commit.mempool_update", "state.applyBlock"),
            ("state.saveState", "state.applyBlock"),
            ("commit.events", "state.applyBlock"),
            ("txindex.drain", "commit.events"),
            ("wal.write", "wal.writeSync")} <= tree
    ev = _by_name(events)
    (handle,) = [h for h in ev["rpc.handle"] if h.args.get("n") == 4]
    calls = [c_ for c_ in ev["rpc.call"] if c_.parent_id == handle.span_id]
    # one rpc.call per run of one method, never one per request
    assert [(c_.args["method"], c_.args["n"]) for c_ in calls] == [
        ("broadcast_tx_async", 3), ("health", 1)]
    assert handle.request[0] == "post" and calls[0].request == handle.request
    assert sum(d.args["n"] for d in ev["ingest.drain"]) == 3
    assert {d.parent_id for d in ev["ingest.drain"]} == {calls[0].span_id}
    # everything done for one block shares its request id
    apply_ = ev["state.applyBlock"][-1]
    h = apply_.args["height"]
    assert apply_.request == ("block", h)
    kids = [e for e in events if e.parent_id == apply_.span_id]
    assert kids and all(k.request == ("block", h) for k in kids)
    assert all(w.request and w.request[0] == "block" for w in ev["wal.writeSync"])
    drained = [d for d in ev["txindex.drain"]]
    assert sum(d.args["txs"] for d in drained) == 3
    assert all(d.request == ("block", d.args["height"]) for d in drained)
