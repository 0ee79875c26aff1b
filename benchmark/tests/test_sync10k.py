"""The cell `sync10k-light` (configuration `sync-10kval`): it resolves
from the manifest, every per-layer entry that names it loads with a
reader that imports, and its own two files, cut to a committee of 24 on
the CPU, make a run that is `correct` and, with `accept_all` planted, one
that is not. Nothing of this is a measurement."""

import importlib
import os

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import manifest
from benchmark.tests import faults, toy

CELL = "sync10k-light"
ARGS = ["--workload", "toy", "--seed", str(2**31 + 28), "--trace", "0",
        "--seconds", "2"]


def test_the_cell_resolves_with_its_own_files():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1
    assert cell.config["validators"] == 10000
    assert cell.config["reduced"] == ["blocks"]
    assert cell.config["p2p_rate_bytes_per_s"] == 5120000
    assert cell.config["guarantees"] == manifest.load_json(
        "configs", "sync-500val.json")["guarantees"]
    t = cell.traffic
    assert (t["driver"], t["txs_per_block"], t["tx_bytes"], t["key_space"]) \
        == ("fastsync_from_peer", 10, 250, 1024)
    assert (t["warmup_blocks"], t["lookahead_blocks"], t["check_heights"],
            t["check_keys"], t["trace_seconds"]) == (6, 10, 16, 16, 16)
    assert {m["name"] for m in cell.end_to_end} == {"sync_blocks_per_s",
                                                    "setup_s"}


# What the cell reads: the entries whose `workloads` name it. A reading
# the 500-validator cells take too is their entry (a `.sync` or unsuffixed
# name); a `.sync10k` name is a reading first written for this cell.
SHARED = {
    "apply_save_ms_per_block.sync", "apply_validate_ms_per_block.sync",
    "block_save_ms_per_block.sync", "compiles_in_window",
    "device_idle_pct.sync", "device_route_pct.sync", "gc_pause_pct.sync",
    "host_cpu_ms_per_block.sync", "host_unattributed_pct.sync",
    "kernel_ready_s", "p2p_recv_calls_per_100_frames.sync",
    "save_encodings_per_100_heights.sync",
    "sig_cache_key_hashes_per_100_lookups.sync",
    "sync_loop_cpu_ms_per_block.sync", "sync_pool_wait_pct.sync",
    "sync_verify_wait_ms_per_block.sync", "verify_h2d_ms_per_batch.sync",
    "verify_lookup_cpu_ms_per_batch.sync", "verify_pack_cpu_ms_per_batch.sync",
    "verify_pack_ms_per_batch.sync", "verify_queue_wait_ms_per_batch.sync",
    "verify_wait_ms_per_batch.sync"}
OWN = {"ed25519_verify_roofline.sync10k", "ed25519_verify_us_per_sig.sync10k",
       "p2p_recv_throttled_pct.sync10k", "part_set_ms_per_block.sync10k",
       "recv_block_ms_per_block.sync10k", "verify_begin_ms_per_block.sync10k"}


def test_every_sync10k_metric_loads_and_its_reader_imports():
    cell = manifest.Cell(CELL)
    by = {m["name"]: m for m in cell.per_layer}
    assert set(by) == SHARED | OWN
    moves = {m["name"]: m["moves"] for m in cell.per_layer}
    assert {n for n, e in moves.items() if e == "setup_s"} == {
        "compiles_in_window", "kernel_ready_s"}
    for m in cell.per_layer:
        assert CELL in m["workloads"]
        reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
        assert callable(reader.read)
    # the 500-validator cells read every shared entry too
    sync500 = {m["name"] for m in manifest.Cell("sync500-light").per_layer}
    assert SHARED <= sync500 and not OWN & sync500
    for kernel in ("ed25519_verify_us_per_sig", "ed25519_verify_roofline"):
        assert by[kernel + ".sync10k"]["params"]["pattern"] == "ed25519_verify"
    assert by["sync_pool_wait_pct.sync"]["params"]["what"] == "pct_of_window"
    # the link's share of the window (0 while the limiter never sleeps)
    assert by["p2p_recv_throttled_pct.sync10k"]["params"] == {
        "name": "p2p.recvThrottle", "what": "pct_of_window"}
    assert by["p2p_recv_throttled_pct.sync10k"]["layer"] == "p2p link"
    assert cell.traffic["chain_blocks_per_s"] == 3
    # the kv cell reads the compile-once pair as well
    for name in ("compiles_in_window", "kernel_ready_s"):
        assert "kv-signed-steady" in by[name]["workloads"]


def _toy_cell() -> toy.ToyCell:
    cell = manifest.Cell(CELL)
    cfg = dict(cell.config, validators=24)
    traffic = dict(cell.traffic, warmup_blocks=3, lookahead_blocks=6,
                   chain_blocks_per_s=400, deadline_s=20)
    return toy.ToyCell("toy-sync10k", cfg, traffic, ["sync_blocks_per_s"])


def _run(capsys, fault=None) -> dict:
    try:
        rc = run.main(ARGS, allow_cpu=True, cell=_toy_cell(), fault=fault)
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


def test_a_sound_toy_run_of_the_cells_files_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    assert "bad_commit_not_offered" not in out["checks"]


def test_an_unchecked_signature_is_not_correct(capsys):
    out = _run(capsys, faults.accept_all)
    assert out["correct"] is False
    assert out["checks"]["height_past_bad_commit"]["value"] > 0
