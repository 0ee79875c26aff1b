"""The frozen heap (libs/tracing.py FrozenHeap, taken by node/node.py's
warm-up thread): what exists when a node has finished starting is
collected once and moved to the collector's permanent generation, so no
later collection walks it; the last node of the process to stop
unfreezes. Thresholds and the `runtime.gc` rule are untouched.
"""

import gc
import json
import os
import threading
import urllib.request
import weakref

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

from test_node import init_files, make_config

from tendermint_tpu.libs import tracing
from tendermint_tpu.node import default_new_node


@pytest.fixture(autouse=True)
def heap_unheld(monkeypatch):
    """These tests count the process's holds from zero: a node that an
    earlier file of this worker left running keeps its own."""
    monkeypatch.setattr(tracing, "_heap_holds", 0)
    gc.unfreeze()
    yield
    assert tracing._heap_holds == 0
    assert gc.get_freeze_count() == 0


def started(tmp_path, name, **instrumentation):
    c = make_config(tmp_path, name)
    for k, v in instrumentation.items():
        setattr(c.instrumentation, k, v)
    c.base.prof_laddr = "tcp://127.0.0.1:0"
    init_files(c)
    node = default_new_node(c)
    node.start()
    node._verify_warmup_thread.join(timeout=30)
    assert not node._verify_warmup_thread.is_alive()
    return node


def debug_crypto(node) -> dict:
    with urllib.request.urlopen(
            f"http://{node._prof_server.listen_addr}/debug/crypto",
            timeout=10) as r:
        return json.load(r)["verifier"]


def test_a_started_node_runs_on_a_frozen_heap_and_stop_unfreezes(tmp_path):
    node = started(tmp_path, "frozen", prometheus=True,
                   prometheus_listen_addr="127.0.0.1:0")
    try:
        v = debug_crypto(node)
        # objects that die by reference count leave the permanent
        # generation, so the live count can only have fallen since
        assert 0 < gc.get_freeze_count() <= v["gc_frozen"]
        assert v["warmup"] == "disabled"  # the cpu backend freezes too
        assert (f"tendermint_runtime_gc_frozen_objects {v['gc_frozen']:g}"
                in node.metrics.registry.render())
        # thresholds are the interpreter's and the collector is on
        assert gc.isenabled() and gc.get_threshold() == (700, 10, 10)
    finally:
        node.stop()
    assert gc.get_freeze_count() == 0
    assert node._verifier["gc_frozen"] == 0
    assert ("tendermint_runtime_gc_frozen_objects 0"
            in node.metrics.registry.render())
    node.stop()  # a second stop gives back nothing twice
    assert tracing._heap_holds == 0


def test_the_last_of_two_nodes_to_stop_unfreezes(tmp_path):
    a = started(tmp_path, "a")
    b = started(tmp_path, "b")
    try:
        assert tracing._heap_holds == 2
        # the second hold froze what the second node built as well
        assert debug_crypto(b)["gc_frozen"] > 0
        a.stop()
        assert tracing._heap_holds == 1 and gc.get_freeze_count() > 0
        assert debug_crypto(b)["gc_frozen"] > 0
    finally:
        a.stop()
        b.stop()
    assert gc.get_freeze_count() == 0


def test_a_start_that_raises_holds_nothing(tmp_path, monkeypatch):
    c = make_config(tmp_path, "fails")
    init_files(c)
    node = default_new_node(c)
    real = node.sw.start

    def start_then_fail():
        real()
        raise RuntimeError("no switch today")

    monkeypatch.setattr(node.sw, "start", start_then_fail)
    try:
        with pytest.raises(RuntimeError, match="no switch today"):
            node.start()
        node._verify_warmup_thread.join(timeout=30)
        assert node._verifier["warmup"] == "disabled"
        assert node._verifier["gc_frozen"] == 0
        assert tracing._heap_holds == 0 and gc.get_freeze_count() == 0
    finally:
        node.stop()


def test_a_node_stopped_during_its_warm_up_freezes_nothing(
        tmp_path, monkeypatch):
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto.jaxed25519 import verify as jv

    go_on = threading.Event()
    monkeypatch.setattr(jv, "warmup", lambda buckets: go_on.wait(30))
    monkeypatch.setenv("TM_TPU_WARMUP", "1")
    prev = crypto_batch.default_backend_name()
    crypto_batch.set_default_backend("adaptive")
    try:
        c = make_config(tmp_path, "early")
        init_files(c)
        node = default_new_node(c)
        node.start()
        try:
            assert node._verifier["warmup"] == "pending"
        finally:
            node.stop()
        go_on.set()
        node._verify_warmup_thread.join(timeout=30)
        assert node._verifier["warmup"] == "ok"
        assert node._verifier["gc_frozen"] == 0
        assert tracing._heap_holds == 0 and gc.get_freeze_count() == 0
    finally:
        go_on.set()
        crypto_batch.set_default_backend(prev)


def test_one_gcfreeze_span_a_hold_and_runtime_gc_goes_on(tmp_path):
    tracer = tracing.get_tracer()
    tracer.enable()
    tracer.clear()
    try:
        frozen = tracing.hold_frozen_heap()
        again = tracing.hold_frozen_heap()
        mark = len(tracer.events())
        gc.collect()
        after = [e for e in tracer.events()[mark:] if e.cat == "runtime"]
        tracing.release_frozen_heap()
        assert gc.get_freeze_count() > 0  # one hold is left
        tracing.release_frozen_heap()
        events = tracer.events()
    finally:
        tracer.disable()
        tracer.clear()
    spans = [e for e in events if e.name == "runtime.gcFreeze"]
    assert len(spans) == 2
    assert all(e.cat == "runtime" and set(e.args) == {"frozen", "collected"}
               for e in spans)
    assert [e.args["frozen"] for e in spans] == [frozen, again]
    assert frozen > 0 and spans[0].args["collected"] >= 0
    # the hook is untouched: the collection inside each hold and the one
    # after the freeze are full collections, and recorded as such
    full = [e for e in events if e.name == "runtime.gc"
            and e.args["generation"] == 2]
    assert len(full) >= 3
    assert [e.name for e in after] == ["runtime.gc"]
    assert after[0].args["generation"] == 2


def test_a_node_with_the_recorder_on_records_its_hold(tmp_path):
    node = started(tmp_path, "traced", tracing=True)
    try:
        spans = [e for e in tracing.get_tracer().events()
                 if e.name == "runtime.gcFreeze"]
        assert len(spans) == 1
        assert spans[0].args["frozen"] == node._verifier["gc_frozen"]
        assert spans[0].thread_name == "verify-warmup"
    finally:
        node.stop()
        tracing.get_tracer().clear()


def test_garbage_made_before_the_start_is_collected_not_frozen_in(tmp_path):
    class Knot:
        pass

    c = make_config(tmp_path, "knot")
    init_files(c)
    node = default_new_node(c)
    # with the automatic collector off, only the hold's own collection
    # can take the cycle away before the freeze would keep it
    gc.disable()
    try:
        a, b = Knot(), Knot()
        a.other, b.other = b, a
        gone = weakref.ref(a)
        del a, b
        assert gone() is not None
        node.start()
        try:
            node._verify_warmup_thread.join(timeout=30)
            assert gc.get_freeze_count() > 0
            assert gone() is None
        finally:
            node.stop()
    finally:
        gc.enable()
