"""The window's clock and the profiler's place (benchmark/README.md, "The
window and the profiler"), at toy size on the CPU with the profiler
replaced by stubs that record when they were called: the window is
`seconds` long whatever holds the driver's main thread, the traced part
lies inside it and ends with it, and the stop, which takes seconds,
falls after it. Nothing of this is a measurement."""

import os
import shutil
import time

import pytest

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import trace as tr
from benchmark.tests import faults, toy

SECONDS = 2
LOOKAHEAD = 8  # toy.sync_cell()


class Stubs:
    """Stand-ins for the profiler's start and stop, and for the reading
    of its trace; `run` is the Run the harness drove."""

    def __init__(self, monkeypatch, start_takes=0.0, stop_takes=0.0):
        self.started = self.stopped = self.run = self.run_now = None
        import jax.profiler as jp

        from tendermint_tpu.libs import tracing

        seen = run.Run.window_opens

        def window_opens(this, t_open, surf):
            self.run_now = this
            seen(this, t_open, surf)

        monkeypatch.setattr(run.Run, "window_opens", window_opens)

        def start_profile(trace_dir):
            self.started = time.monotonic()
            time.sleep(start_takes)
            return time.perf_counter_ns()

        def stop_trace():
            self.stopped = time.monotonic()
            time.sleep(stop_takes)
            if self.run_now is not None:
                # a wait that was open when the window closed ends while
                # the stop is at work, and is recorded then
                at = time.perf_counter_ns() - int(
                    1e9 * (time.monotonic() - self.run_now.t_close))
                tracing.get_tracer().record("test.openAtClose",
                                            at - 50_000_000, at + 50_000_000)

        def reduce_trace(this):
            self.run = this
            shutil.rmtree(this._trace_dir, ignore_errors=True)

        monkeypatch.setattr(tr, "start_profile", start_profile)
        monkeypatch.setattr(jp, "stop_trace", stop_trace)
        monkeypatch.setattr(run.Run, "reduce_trace", reduce_trace)


def _traced(cell, capsys, seconds=SECONDS, fault=None) -> dict:
    argv = ["--workload", "toy", "--seed", str(2**31 + 29), "--trace", "1",
            "--seconds", str(seconds)]
    try:
        assert run.main(argv, allow_cpu=True, cell=cell, fault=fault) == 0
    finally:
        faults.undo()
    return toy.last_line(capsys.readouterr().out)


def _sync_cell(trace_seconds):
    cell = toy.sync_cell()
    cell.traffic["trace_seconds"] = trace_seconds
    return cell


def test_a_slow_profiler_stop_stretches_no_sync_window(monkeypatch, capsys):
    stubs = Stubs(monkeypatch, stop_takes=3.0)
    out = _traced(_sync_cell(1), capsys)
    r = stubs.run
    assert out["correct"] is True and out["failed"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    assert r.facts["window_s"] == SECONDS  # not 5: the stop is outside
    assert r.t_close - r.t_open == SECONDS
    # the height is the one stamped last before t_close: the poller froze
    # the tip on that reading, `lookahead` above it
    assert r.facts["tip_at_close"] == r.facts["height_end"] + LOOKAHEAD
    assert r.facts["blocks"] == r.facts["height_end"] - r.facts["height_open"] > 0
    assert 0 < r.facts["chain_used_pct"] < 100
    # the traced part is the window's last second and ends at t_close
    assert r.t_open <= stubs.started <= r.t_close - 1 + 0.2
    assert out["device"]["window_s"] == r.traced_s
    assert r.traced_s == pytest.approx(r.t_close - stubs.started, abs=0.05)
    assert 0.8 <= r.traced_s <= 1.0
    # the stop came after the window, and after the corrupted commit was
    # on offer: the joiner was still syncing when it met it
    assert stubs.stopped >= r.t_close
    assert out["checks"]["height_past_bad_commit"]["value"] == 0
    # the spans are read when the stop returns: one that was open at
    # t_close and ended meanwhile is among them
    late = [s for s in r._spans_raw if s.name == "test.openAtClose"]
    assert len(late) == 1 and out["facts"] == r.facts


def test_a_main_thread_held_past_the_close_costs_no_sync_run_its_correct(
        monkeypatch, capsys):
    # the profiler's start holds the driver's main thread from the middle
    # of the window to 1.5 s past its end. The poller closes the window
    # and, in the same breath, puts the corrupted commit on offer: the
    # joiner, which would be at a tip that stands still within a second
    # and go on to consensus, meets it while it is still syncing
    stubs = Stubs(monkeypatch, start_takes=2.5)
    cell = _sync_cell(1)
    cell.traffic["deadline_s"] = 8
    out = _traced(cell, capsys)
    r = stubs.run
    assert stubs.started + 2.5 >= r.t_close + 1.4  # it was held that long
    assert r.facts["window_s"] == SECONDS
    assert r.facts["tip_at_close"] == r.facts["height_end"] + LOOKAHEAD
    assert out["attempted"] == r.facts["blocks"] > 0
    assert out["correct"] is True and out["failed"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())


def test_the_profiler_may_not_be_stopped_inside_the_window(monkeypatch):
    Stubs(monkeypatch)
    r = run.Run(toy.sync_cell(), 1, 30.0, True)
    r.t_open, r.t_close = time.monotonic(), time.monotonic() + 30
    r._trace_t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="inside the window"):
        r.trace_stop()


def test_the_rpc_window_is_traced_whole_and_stopped_after_its_drain(
        monkeypatch, capsys):
    stubs = Stubs(monkeypatch, stop_takes=1.0)
    out = _traced(toy.kv_cell(), capsys, seconds=3)
    r = stubs.run
    assert out["correct"] is True and out["attempted"] > 100
    assert r.t_close - r.t_open == 3
    assert r.t_open <= stubs.started <= r.t_open + 0.3
    assert r.traced_s == pytest.approx(3, abs=0.3) and r.traced_s <= 3
    assert stubs.stopped >= r.t_close
