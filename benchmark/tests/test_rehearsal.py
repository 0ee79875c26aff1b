"""The harness end to end on the CPU at toy size, the look for a chip
skipped: a sound run says `correct`, and each fault planted under the
timed path makes it say otherwise (or ends the run with no result).
The node's verifier is held to the host backend here; nothing of this
is a measurement."""

import os

import pytest

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.tests import faults, toy

ARGS = ["--workload", "toy", "--seed", str(2**31 + 11), "--trace", "0"]


def _run(cell, seconds, capsys, fault=None):
    try:
        rc = run.main(ARGS + ["--seconds", str(seconds)], allow_cpu=True,
                      cell=cell, fault=fault)
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


def test_sync_sound_run_is_correct(capsys):
    out = _run(toy.sync_cell(), 2, capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["height_past_bad_commit"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", ["accept_all", "half_batch"])
def test_sync_unchecked_signature_is_not_correct(capsys, fault):
    out = _run(toy.sync_cell(), 2, capsys, faults.FAULTS[fault])
    assert out["correct"] is False
    assert out["checks"]["height_past_bad_commit"]["value"] > 0


def test_sync_state_unchanged_is_not_correct(capsys):
    out = _run(toy.sync_cell(), 2, capsys, faults.state_unchanged)
    assert out["correct"] is False
    assert out["checks"]["hash_mismatches"]["value"] > 0
    assert out["checks"]["keys_read_back_wrong"]["value"] > 0


def test_sync_altered_answer_gives_no_result(capsys):
    # the joiner's app hash departs from the reference's in block 1, so it
    # refuses block 2 and never reaches the window
    with pytest.raises(RuntimeError, match="did not reach height"):
        _run(toy.sync_cell(), 2, capsys, faults.answer_altered)
    assert "correct" not in capsys.readouterr().out


def test_kv_sound_run_is_correct(capsys):
    out = _run(toy.kv_cell(), 3, capsys)
    assert out["correct"] is True and out["attempted"] > 100
    assert set(out["metrics"]) == {"commit_latency_p50_ms", "setup_s"}
    assert out["metrics"]["commit_latency_p50_ms"]["value"] > 0


def test_kv_unchecked_signature_is_not_correct(capsys):
    out = _run(toy.kv_cell(), 3, capsys, faults.accept_all)
    assert out["correct"] is False
    assert out["checks"]["corrupted_in_a_block"]["value"] > 0


def test_kv_altered_answer_is_not_correct(capsys):
    out = _run(toy.kv_cell(), 3, capsys, faults.answer_altered)
    assert out["correct"] is False
    assert out["checks"]["app_hash_differs"]["value"] == 1
