"""The readers of the program's own spans (`span_time`, `span_coverage`)
on a synthetic span list, and on a trace recorded on the chip (TPU v5
lite) by tools/record_trace.py after the verify program got its name:
`fixtures/named3.*`, three device batches of 500 signatures."""

import gzip
import json
import os
import types

import pytest

from benchmark.harness import manifest
from benchmark.harness import trace as tr
from benchmark.readers import span_coverage, span_time, trace_kernel
from benchmark.tools import clock_agreement
from tendermint_tpu.libs.tracing import SpanRecord

FIX = os.path.join(manifest.HERE, "tests", "fixtures")
MS = 1_000_000


def rec(name, start_ms, dur_ms, span_id, parent_id=0, thread=1, request=None,
        **args):
    return SpanRecord(name, "", int(start_ms * MS), int(dur_ms * MS), thread,
                      f"t{thread}", args or None, span_id, parent_id, request)


def run_of(records, window_ms=(0, 1000), ops=()):
    """A Run as the readers see it: the recorder's clock is the trace's
    (shift 0), one chip, `ops` as (start_ms, dur_ms) device operations."""
    trace = tr.Trace()
    trace.sync_ns = 0
    trace.devices["/device:TPU:0"] = {
        "ops": [("op", int(s * MS), int(d * MS)) for s, d in ops],
        "modules": []}
    return types.SimpleNamespace(
        trace=trace, _spans_raw=list(records), _sync_perf_ns=0,
        trace_window=(window_ms[0] * MS, window_ms[1] * MS))


BLOCK = [
    rec("fastsync.block", 0, 100, 1, request=("block", 7), height=7),
    rec("state.applyBlock", 10, 80, 2, 1, request=("block", 7)),
    rec("state.saveResponses", 20, 10, 3, 2, request=("block", 7)),
    rec("state.saveState", 60, 20, 4, 2, request=("block", 7)),
    rec("runtime.gc", 62, 5, 5, 4, request=("block", 7), generation=2),
    rec("txindex.drain", 85, 30, 6, 2, thread=2, request=("block", 7), txs=10),
    rec("fastsync.block", 200, 50, 7, request=("block", 8), height=8),
    rec("state.applyBlock", 210, 30, 8, 7, request=("block", 8)),
    rec("state.saveState", 220, 10, 9, 8, request=("block", 8)),
]


def test_total_self_and_per():
    run = run_of(BLOCK)
    read = lambda **p: span_time.read(p, run)
    assert read(name="state.applyBlock", what="total_ms") == pytest.approx(55.0)
    # self time: less the children on the same thread, not the indexer's
    assert read(name="state.applyBlock", what="self_ms") == pytest.approx(
        (80 - 30 + 30 - 10) / 2)
    assert read(name="state.saveState", what="self_ms") == pytest.approx(12.5)
    # two names summed, per block (distinct request ids) and per arg
    assert read(name=["state.saveResponses", "state.saveState"],
                what="total_ms", per="request") == pytest.approx(20.0)
    assert read(name="txindex.drain", what="total_ms", per="arg:txs",
                scale=1000.0) == pytest.approx(3000.0)
    assert read(name="fastsync.block", what="total_ms",
                match={"height": 8}) == pytest.approx(50.0)
    assert read(name="runtime.gc", what="total_ms", has=["generation"]) == 5.0
    assert read(name="runtime.gc", what="total_ms", has=["collected"]) is None
    assert read(name="fastsync.block", what="pct_of_window") == pytest.approx(15.0)
    assert read(name="no.such.span", what="total_ms") is None
    assert read(name="txindex.drain", what="total_ms", per="arg:bytes") is None


def test_cross_thread_cause_gives_the_queue_wait():
    records = [
        rec("fastsync.verifyBegin", 0, 2, 1, request=("block", 3)),
        rec("crypto.batchVerify", 9, 16, 2, 1, thread=2, request=("block", 3),
            backend="jax", n=500),
        rec("crypto.dispatchWait", 1, 8, 3, 2, thread=2, request=("block", 3),
            backend="jax", n=500),
        rec("crypto.batchVerify", 40, 1, 4, 99, thread=2, backend="cpu", n=3),
    ]
    run = run_of(records)
    gap = span_time.read({"name": "crypto.batchVerify", "what": "gap_to_parent_ms",
                          "match": {"backend": "jax"}}, run)
    assert gap == pytest.approx(7.0)  # 9 ms start less the cause's end at 2
    # a span whose cause is not among the records has no gap to report
    assert span_time.read({"name": "crypto.batchVerify",
                           "what": "gap_to_parent_ms",
                           "match": {"backend": "cpu"}}, run) is None
    assert span_time.read({"name": "crypto.dispatchWait", "what": "total_ms",
                           "match": {"backend": "jax"}}, run) == pytest.approx(8.0)
    # the wait starts before its parent: it takes nothing from its self time
    assert span_time.read({"name": "crypto.batchVerify", "what": "self_ms",
                           "match": {"backend": "jax"}}, run) == pytest.approx(16.0)


def test_only_spans_of_the_window_count():
    run = run_of(BLOCK, window_ms=(150, 400))
    assert span_time.read({"name": "fastsync.block", "what": "total_ms"},
                          run) == pytest.approx(50.0)
    assert span_time.read({"name": "txindex.drain", "what": "total_ms"},
                          run) is None
    run = run_of(BLOCK, window_ms=(50, 225))  # clipped at both ends
    assert span_time.read({"name": "fastsync.block", "what": "pct_of_window"},
                          run) == pytest.approx(100 * 75 / 175)


def test_nothing_is_read_from_what_cannot_be_trusted(monkeypatch):
    p = {"name": "fastsync.block", "what": "total_ms"}
    run = run_of(BLOCK)
    assert span_time.read(p, run) is not None
    run.trace = None
    assert span_time.read(p, run) is None and span_coverage.read({}, run) is None
    # a program older than the ids: records with no span_id
    old = [types.SimpleNamespace(name=r.name, start_ns=r.start_ns,
                                 dur_ns=r.dur_ns, args=r.args) for r in BLOCK]
    assert span_time.read(p, run_of(old)) is None
    assert span_coverage.read({}, run_of(old)) is None
    assert span_time.read(p, run_of([])) is None
    # the ring was full at the snapshot: a span of the window may be gone
    from tendermint_tpu.libs import tracing

    monkeypatch.setattr(tracing, "_GLOBAL", tracing.Tracer(capacity=len(BLOCK)))
    assert span_time.read(p, run_of(BLOCK)) is None
    assert span_coverage.read({}, run_of(BLOCK)) is None


ABSENT = {"name": "p2p.recvThrottle", "what": "pct_of_window"}


def test_a_share_of_the_window_is_zero_for_a_span_that_never_opened():
    # the recorder was on and whole, and no span of the name is in the
    # window: that is a share of 0, not nothing to read
    run = run_of(BLOCK)
    assert span_time.read(ABSENT, run) == 0.0
    assert span_time.read({"name": "fastsync.block", "what": "pct_of_window"},
                          run_of(BLOCK, window_ms=(400, 900))) == 0.0
    # the same for a list of names, and with args that nothing matches
    assert span_time.read(dict(ABSENT, name=["p2p.recvThrottle",
                                             "p2p.sendThrottle"]), run) == 0.0
    assert span_time.read({"name": "runtime.gc", "what": "pct_of_window",
                           "match": {"generation": 0}}, run) == 0.0
    # every other `what` keeps saying nothing: a mean over nothing is not 0
    for what in ("total_ms", "self_ms", "gap_to_parent_ms"):
        assert span_time.read(dict(ABSENT, what=what), run) is None


@pytest.mark.parametrize("broken", ["no_trace", "no_sync", "no_records",
                                    "no_ids", "ring_wrapped"])
def test_a_share_is_not_zero_where_the_records_cannot_be_trusted(
        monkeypatch, broken):
    run = run_of(BLOCK)
    if broken == "no_trace":
        run.trace = None
    elif broken == "no_sync":
        run.trace.sync_ns = None
    elif broken == "no_records":
        run = run_of([])
    elif broken == "no_ids":
        run = run_of([types.SimpleNamespace(
            name=r.name, start_ns=r.start_ns, dur_ns=r.dur_ns, args=r.args)
            for r in BLOCK])
    else:
        from tendermint_tpu.libs import tracing

        monkeypatch.setattr(tracing, "_GLOBAL",
                            tracing.Tracer(capacity=len(BLOCK)))
    assert span_time.read(ABSENT, run) is None
    assert span_time.read({"name": "fastsync.block", "what": "pct_of_window"},
                          run) is None


def test_coverage_is_the_unattributed_idle_share():
    # the chip works 100..110 and 300..310 ms of a 1000 ms window; the
    # idle gaps are 0..100, 110..300, 310..1000 with middles 50, 205, 655
    run = run_of(BLOCK, ops=[(100, 10), (300, 10)])
    assert span_coverage.read({}, run) == pytest.approx(69.0)
    covered = BLOCK + [rec("fastsync.poolWait", 400, 500, 20)]
    assert span_coverage.read({}, run_of(covered, ops=[(100, 10), (300, 10)])
                              ) == pytest.approx(0.0)
    gaps = dict(tr.idle_gaps(run.trace, [(r.name, r.start_ns, r.end_ns)
                                         for r in BLOCK], 0, 1000 * MS))
    assert gaps["unattributed"] == pytest.approx(0.69)
    assert span_coverage.read({}, run_of(BLOCK)) == pytest.approx(100.0)  # no op: one gap


def test_every_new_metric_file_names_a_span_of_the_contract():
    readme = open(os.path.join(manifest.ROOT, "README.md")).read()
    for m in manifest.manifest()["per_layer"]:
        body = manifest.load_json("metrics", m["name"] + ".json")
        if body["reader"] != "span_time":
            continue
        assert m["source"] == "program_span"
        names = body["params"]["name"]
        for name in names if isinstance(names, list) else [names]:
            assert f"`{name}`" in readme, (m["name"], name)


# --- on the chip's own trace, recorded after the program got its names ------


@pytest.fixture(scope="module")
def named(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "named3.xplane.pb"
    with gzip.open(os.path.join(FIX, "named3.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    spans_json = os.path.join(FIX, "named3.spans.json")
    meta = json.load(open(spans_json))
    t = tr.load(str(path))
    lo = t.sync_ns
    hi = lo + int(meta["traced_s"] * 1e9)
    # tools/record_trace.py keeps name, start, duration and args: ids in
    # the order recorded, every span a root
    records = [SpanRecord(s["name"], "", s["start_ns"], s["dur_ns"], 1, "t",
                          s["args"], i + 1, 0)
               for i, s in enumerate(meta["spans"])]
    shift = t.sync_ns - meta["sync_perf_ns"]
    run = types.SimpleNamespace(
        trace=t, trace_window=(lo, hi), traced_s=meta["traced_s"],
        busy_s=tr.busy_seconds(t, lo, hi), _spans_raw=records,
        _sync_perf_ns=meta["sync_perf_ns"],
        verify_spans=[{"start": r.start_ns + shift, "n": r.args["n"],
                       "backend": r.args["backend"]} for r in records
                      if r.name == "crypto.batchVerify"],
        peaks=manifest.load_json("peaks.json")["TPU v5 lite"])
    return t, lo, hi, run, str(path), spans_json


def test_the_verify_program_is_found_by_name(named):
    t, lo, hi, run, _, _ = named
    by_name = tr.named_seconds(t, "modules", "ed25519_verify", lo, hi)
    assert by_name == tr.named_seconds(t, "modules", ".", lo, hi)
    assert by_name[1] == 3 and 0.0070 < by_name[0] < 0.0071
    names = {name for name, _, _ in t.devices["/device:TPU:0"]["modules"]}
    assert all(n.startswith("jit_ed25519_verify_packed") for n in names)
    assert tr.top_ops(t, lo, hi)[0][0] == (
        "%ed25519_straus_fused.1 custom-call tpu_custom_call")
    # the .kv twins' pattern reads what the .sync files' "." reads
    p = {"line": "modules", "backend": "jax", "what": "us_per_item"}
    assert trace_kernel.read(dict(p, pattern="ed25519_verify"), run) == (
        trace_kernel.read(dict(p, pattern="."), run))
    assert 4.69 < trace_kernel.read(dict(p, pattern="ed25519_verify"), run) < 4.71


def test_the_stages_of_a_device_batch_add_up(named):
    run = named[3]
    stage = lambda name: span_time.read({"name": name, "what": "total_ms"}, run)
    parts = [stage("verify." + s)
             for s in ("pack", "h2d", "launch", "wait", "unpack")]
    whole = stage("crypto.batchVerify")
    assert all(p is not None and p > 0 for p in parts)
    assert whole - 0.5 < sum(parts) <= whole  # the five are the batch's wall
    assert stage("verify.wait") > 2.35        # it holds the kernel's 2.35 ms
    assert span_time.read({"name": "verify.pack", "what": "total_ms",
                           "per": "arg:n", "scale": 1000.0}, run) < 10.0
    assert span_time.read({"name": "crypto.batchVerify", "what": "total_ms",
                           "match": {"backend": "cpu"}}, run) is None
    assert 0 < span_time.read({"name": "crypto.batchVerify",
                               "what": "pct_of_window"}, run) < 5


def test_coverage_on_the_recorded_trace(named):
    t, lo, hi, run, _, _ = named
    # the tool sleeps between its three batches with no span open
    share = span_coverage.read({}, run)
    gaps = dict(tr.idle_gaps(t, [(r.name, r.start_ns + t.sync_ns
                                  - run._sync_perf_ns, r.end_ns + t.sync_ns
                                  - run._sync_perf_ns) for r in run._spans_raw],
                             lo, hi))
    assert share == pytest.approx(100 * gaps["unattributed"] / run.traced_s)
    assert 90 < share < 100 and set(gaps) - {"unattributed",
                                             "within_a_program"}


def test_the_two_clocks_agree(named):
    # every span is in the trace twice: as its annotation, and as the
    # recorder's reading carried over the bench.clock_sync bridge
    _, _, _, _, xplane, spans_json = named
    diffs = clock_agreement.differences(xplane, spans_json)
    assert set(diffs) == {"crypto.batchVerify", "verify.pack", "verify.h2d",
                          "verify.launch", "verify.wait", "verify.unpack"}
    assert all(len(d) == 3 for d in diffs.values())
    assert max(abs(x) for d in diffs.values() for x in d) < 100_000  # 100 us
