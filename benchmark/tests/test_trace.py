"""The reduction from .xplane.pb to numbers, on a trace recorded on the
chip (TPU v5 lite) by tools/record_trace.py: three device batches of 500
signatures through crypto.batch.batch_verify."""

import gzip
import json
import os
import types

import pytest

from benchmark.harness import manifest
from benchmark.harness import trace as tr
from benchmark.readers import trace_idle, trace_kernel

FIX = os.path.join(manifest.HERE, "tests", "fixtures")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "verify3.xplane.pb"
    with gzip.open(os.path.join(FIX, "verify3.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    meta = json.load(open(os.path.join(FIX, "verify3.spans.json")))
    t = tr.load(str(path))
    lo = t.sync_ns
    hi = lo + int(meta["traced_s"] * 1e9)
    shift = t.sync_ns - meta["sync_perf_ns"]
    spans = [(s["name"], s["start_ns"] + shift, s["start_ns"] + shift + s["dur_ns"])
             for s in meta["spans"]]
    run = types.SimpleNamespace(
        trace=t, trace_window=(lo, hi), traced_s=meta["traced_s"],
        busy_s=tr.busy_seconds(t, lo, hi),
        verify_spans=[{"start": s["start_ns"] + shift, "n": s["args"]["n"],
                       "backend": s["args"]["backend"]} for s in meta["spans"]],
        peaks=manifest.load_json("peaks.json")["TPU v5 lite"])
    return t, lo, hi, spans, run


def test_planes_and_clock_sync(recorded):
    t, lo, hi, _, _ = recorded
    assert list(t.devices) == ["/device:TPU:0"] and t.sync_ns is not None
    dev = t.devices["/device:TPU:0"]
    assert len(dev["modules"]) == 3 and len(dev["ops"]) > 1000
    assert all(lo <= s < hi for _, s, _ in dev["modules"])


def test_busy_is_the_union_of_op_intervals(recorded):
    t, lo, hi, _, run = recorded
    seconds, n = tr.named_seconds(t, "modules", ".", lo, hi)
    assert n == 3 and 0.0070 < seconds < 0.0071      # 2.35 ms a batch
    assert 0.95 * seconds < run.busy_s <= seconds    # ops lie inside programs
    assert tr.busy_seconds(t, lo, lo) == 0.0
    assert tr.union([(0, 5), (3, 9), (20, 30)], 1, 25) == [[1, 9], [20, 25]]


def test_kernel_time_and_roofline(recorded):
    run = recorded[4]
    p = {"line": "modules", "pattern": ".", "backend": "jax"}
    us = trace_kernel.read(dict(p, what="us_per_item"), run)
    assert 4.69 < us < 4.71                          # 7.05 ms over 1,500
    share = trace_kernel.read(dict(p, what="roofline"), run)
    assert 29.2 < share < 29.4                       # 5.3e6 / 3.85e12 / 4.70 us
    assert trace_kernel.read(dict(p, what="roofline", backend="none"), run) is None
    idle = trace_idle.read({}, run)
    assert 98.8 < idle < 98.95


def test_idle_gaps_go_to_the_open_host_span(recorded):
    t, lo, hi, spans, run = recorded
    assert [n for n, _, _ in spans] == ["crypto.batchVerify"] * 3
    dev = t.devices["/device:TPU:0"]
    inside = tr.overlap_ns(tr.union([(s, s + d) for _, s, d in dev["ops"]], lo, hi),
                           tr.union([(a, b) for _, a, b in spans], lo, hi))
    assert inside / 1e9 == pytest.approx(run.busy_s)  # the clock bridge holds
    assert tr.overlap_ns([[0, 4], [6, 9]], [[2, 7]]) == 3
    gaps = dict(tr.idle_gaps(t, spans, lo, hi))
    assert set(gaps) <= {"crypto.batchVerify", "unattributed", "within_a_program"}
    assert gaps["unattributed"] > gaps["crypto.batchVerify"] > 0
    assert sum(gaps.values()) == pytest.approx(run.traced_s - run.busy_s, rel=1e-3)


def test_operations_are_named_shortly(recorded):
    t, lo, hi, _, _ = recorded
    top = tr.top_ops(t, lo, hi)
    assert top[0][0] == "%_unknown_.1 custom-call tpu_custom_call"
    assert len(top) == 10 and all(len(n) <= 100 for n, _ in top)
    assert tr.short_name("no equals sign") == "no equals sign"
