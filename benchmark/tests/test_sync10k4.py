"""The cell `sync10k-light-4chip` (configuration `sync-10kval-4chip`):
it resolves from the manifest with four chips, its configuration is
`sync-10kval` but for the host, every `.sync10k4` metric file is its
`.sync10k` twin or one of the four new ones, and the reader the cell
brings (`readers/trace_chips.py`) reads a four-plane trace as
`benchmark/README-4chip.md` says. A toy run of its files, cut to a
committee of 24 on the CPU, is `correct`. Nothing here is a measurement."""

import importlib
import os
import types

import pytest

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import manifest
from benchmark.harness import trace as tr
from benchmark.readers import prom_hist_mean, trace_chips
from benchmark.tests import faults, toy

CELL, ONE_CHIP = "sync10k-light-4chip", "sync10k-light"
NEW = {"chips_busy.sync10k4": ("device", "device_trace"),
       "chip_busy_skew_pct.sync10k4": ("device", "device_trace"),
       "verify_device_wall_ms_per_batch.sync10k4": ("kernel", "device_trace"),
       "device_lanes_per_chip_mean.sync10k4": ("batch funnel", "program_counter")}


def test_the_cell_resolves_with_four_chips_and_its_own_files():
    man = manifest.manifest()
    cell, one = manifest.Cell(CELL), manifest.Cell(ONE_CHIP)
    assert cell.chips == 4
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] == [CELL]
    cfg = cell.config
    assert cfg["name"] == "sync-10kval-4chip"
    assert cfg["reference"] == "benchmark/harness/reference.py"
    assert "verifier_chips" in cfg["assumed"]
    # the one-chip deployment word for word, but for the host
    differ = {k for k in set(cfg) | set(one.config)
              if cfg.get(k) != one.config.get(k)}
    assert differ == {"name", "source", "deployment", "chips", "assumed",
                      "reference"}
    assert {k: v for k, v in cfg["assumed"].items() if k != "verifier_chips"} \
        == one.config["assumed"]
    assert cfg["node"] == {"base.proxy_app": "kvstore"}
    # the traffic of the one-chip cell on a longer chain
    assert cell.traffic == dict(one.traffic, chain_blocks_per_s=4)
    assert {m["name"] for m in cell.end_to_end} == {"sync_blocks_per_s",
                                                    "setup_s"}
    # the corrupted commit fits up to 4.1 blocks/s, above the link's 2.92
    t, secs = cell.traffic, man["run_seconds"]
    n = t["warmup_blocks"] + t["lookahead_blocks"] + 4 + 4 * secs
    assert n == 100
    assert (n - t["lookahead_blocks"] - 2 - t["warmup_blocks"]) / secs == 4.1
    assert cfg["p2p_rate_bytes_per_s"] / 1752640 < 2.93


def test_every_sync10k4_metric_is_a_twin_or_one_of_the_four_new():
    cell = manifest.Cell(CELL)
    assert all(m["name"].endswith(".sync10k4") and m["workloads"] == [CELL]
               for m in cell.per_layer)
    assert len(cell.per_layer) == 19 + len(NEW)
    one = {m["name"]: m for m in manifest.Cell(ONE_CHIP).per_layer}
    for m in cell.per_layer:
        assert callable(importlib.import_module(
            f"benchmark.readers.{m['reader']}").read)
        if m["name"] in NEW:
            assert (m["layer"], m["source"]) == NEW[m["name"]]
            assert m["moves"] == "sync_blocks_per_s"
            continue
        twin = one[m["name"][:-len("4")]]
        for key in ("unit", "better", "source", "layer", "moves", "reader",
                    "params"):
            assert m[key] == twin[key], (m["name"], key)
    by = {m["name"]: m for m in cell.per_layer}
    assert by["device_lanes_per_chip_mean.sync10k4"]["params"] == {
        "family": "tendermint_crypto_batch_lanes_per_device",
        "labels": {"ndev": "4"}}


# --- the reader, on a four-plane trace made here ---------------------------

NAME = "jit_ed25519_verify_packed(123)"


def _trace(per_chip: list) -> tr.Trace:
    """per_chip[i]: [(program, start, duration)] of chip i; every program
    is one operation long."""
    t = tr.Trace()
    for i, mods in enumerate(per_chip):
        t.devices[f"/device:TPU:{i}"] = {
            "modules": list(mods),
            "ops": [("%fusion = f(x)", s, d) for _, s, d in mods]}
    return t


def _read(t, what: str, lo=0, hi=1_000_000_000, pattern="ed25519_verify"):
    r = types.SimpleNamespace(trace=t, trace_window=(lo, hi))
    return trace_chips.read({"what": what, "pattern": pattern}, r)


def test_four_planes_read_as_the_cell_says():
    # two batches 400 ms apart; the chips start 0.1 ms apart and chip 3
    # runs 12 ms where the others run 10
    ms = 1_000_000
    chips = [[(NAME, b + i * ms // 10, (12 if i == 3 else 10) * ms)
              for b in (100 * ms, 500 * ms)] for i in range(4)]
    t = _trace(chips)
    assert _read(t, "chips_busy") == 4.0
    assert _read(t, "busy_skew_pct") == pytest.approx(100 * 2 / 12)
    # first chip's start to the last chip's end: 0.3 + 12 ms
    assert _read(t, "wall_ms_per_batch") == pytest.approx(12.3)
    # a window that holds the second batch alone
    assert _read(t, "wall_ms_per_batch", lo=300 * ms) == pytest.approx(12.3)
    # a chip that ran another program is not counted, whatever it ran
    chips[2] = [("jit_other(1)", s, d) for _, s, d in chips[2]]
    assert _read(_trace(chips), "chips_busy") == 3.0
    assert _read(_trace(chips), "busy_skew_pct") == pytest.approx(100 * 2 / 12)


def test_nothing_to_read_is_none_and_never_zero():
    empty = types.SimpleNamespace(trace=None, trace_window=(0, 0))
    for what in ("chips_busy", "busy_skew_pct", "wall_ms_per_batch"):
        assert trace_chips.read({"what": what, "pattern": "x"}, empty) is None
        assert _read(tr.Trace(), what) is None
    other = _trace([[("jit_other(1)", 10, 10)]] * 4)
    assert _read(other, "chips_busy") is None
    assert _read(other, "wall_ms_per_batch") is None
    assert _read(other, "busy_skew_pct") == 0.0      # four chips, all as busy
    one_plane = _trace([[(NAME, 10, 10)]])
    assert _read(one_plane, "busy_skew_pct") is None  # no second chip
    assert _read(one_plane, "chips_busy") == 1.0
    # a program with no lanes family (the parent): the mean is left out
    r = types.SimpleNamespace(prom=({}, {("tendermint_other_count", ()): 3.0}))
    p = manifest.load_json("metrics", "device_lanes_per_chip_mean.sync10k4.json")
    assert prom_hist_mean.read(p["params"], r) is None


def test_the_recorded_one_chip_trace_reads_one_chip():
    import gzip
    import json
    import tempfile

    fix = os.path.join(manifest.HERE, "tests", "fixtures")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "named3.xplane.pb")
        with gzip.open(os.path.join(fix, "named3.xplane.pb.gz")) as f, \
                open(path, "wb") as out:
            out.write(f.read())
        t = tr.load(path)
    meta = json.load(open(os.path.join(fix, "named3.spans.json")))
    lo = t.sync_ns
    hi = lo + int(meta["traced_s"] * 1e9)
    assert _read(t, "chips_busy", lo, hi) == 1.0
    assert _read(t, "busy_skew_pct", lo, hi) is None
    seconds, n = tr.named_seconds(t, "modules", "ed25519_verify", lo, hi)
    assert _read(t, "wall_ms_per_batch", lo, hi) == pytest.approx(
        1e3 * seconds / n)


# --- the cell's own files at toy size --------------------------------------


def _run(capsys, fault=None) -> dict:
    cell = manifest.Cell(CELL)
    cfg = dict(cell.config, validators=24)
    traffic = dict(cell.traffic, warmup_blocks=3, lookahead_blocks=6,
                   chain_blocks_per_s=400, deadline_s=20)
    args = ["--workload", "toy", "--seed", str(2**31 + 33), "--trace", "0",
            "--seconds", "2"]
    try:
        rc = run.main(args, allow_cpu=True, fault=fault, cell=toy.ToyCell(
            "toy-sync10k4", cfg, traffic, ["sync_blocks_per_s"]))
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


def test_a_sound_toy_run_of_the_cells_files_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())


def test_half_a_batch_unchecked_is_not_correct(capsys):
    out = _run(capsys, faults.half_batch)
    assert out["correct"] is False
    assert out["checks"]["height_past_bad_commit"]["value"] > 0
