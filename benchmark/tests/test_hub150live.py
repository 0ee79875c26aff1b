"""The cell `hub150-live` (configuration `hub-150val-live`): it resolves
from the manifest with one chip and its own files, it reads the four
readings of its own and every entry it shares with `hub150-follow` and
the sync cells, and a toy run of the cell's files (a committee of 7, the
node one of them, CPU) is `correct` with the node's turn, two own votes
a height and the corrupted tail in its line; with a signature
unchecked it is not. Nothing here is a measurement."""

import importlib
import os

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import manifest
from benchmark.tests import faults, toy

CELL, FOLLOW = "hub150-live", "hub150-follow"
# what only a validator does: its key's signing, its own votes' latency,
# the WAL's fsyncs, and the share of a height spent waiting for a proposal
OWN = {
    "privval_sign_ms_per_height.hub150live": (
        "span_time", {"name": "privval.sign", "what": "total_ms",
                      "per": "request"}, "validator key"),
    "own_vote_latency_ms.hub150live": (
        "span_time", {"name": "consensus.ownVoteLatency",
                      "what": "total_ms"}, "consensus vote path"),
    "wal_sync_ms_per_height.hub150live": (
        "span_time", {"name": "wal.writeSync", "what": "total_ms",
                      "per": "request"}, "consensus vote path"),
    "proposal_wait_pct.hub150live": (
        "span_time", {"name": "consensus.proposalWait",
                      "what": "pct_of_window"}, "consensus"),
}
SHARED_WITH_SYNC = {
    "device_route_pct.sync", "device_idle_pct.sync",
    "host_unattributed_pct.sync", "gc_pause_pct.sync",
    "host_cpu_ms_per_block.sync", "ed25519_verify_us_per_sig.sync10k",
    "ed25519_verify_roofline.sync10k", "compiles_in_window", "kernel_ready_s"}


def test_the_cell_resolves_with_one_chip_and_its_own_files():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1
    cfg = cell.config
    assert (cfg["name"], cfg["validators"], cfg["reduced"]) == (
        "hub-150val-live", 150, ["heights"])
    assert cfg["node"] == {"base.proxy_app": "kvstore",
                           "base.fast_sync": False,
                           "consensus.skip_timeout_commit": True}
    assert cfg["reference"] == "benchmark/harness/reference_live.py"
    assert len(cfg["guarantees"]) == 5 and cfg["architecture"] is None
    for key in ("voting_power", "skip_timeout_commit", "committee",
                "injected_delay_ms", "mempool", "p2p_rate_bytes_per_s"):
        assert key in cfg["assumed"], key
    entry = next(c for c in manifest.manifest()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    t = cell.traffic
    assert t["driver"] == "consensus_live"
    assert (t["warmup_heights"], t["delay_ms"], t["txs_per_block"],
            t["tx_bytes"], t["key_space"], t["trace_seconds"]) == (
        20, 1, 10, 250, 1024, 8)
    assert {m["name"] for m in cell.end_to_end} == {"sync_blocks_per_s",
                                                    "setup_s"}


def test_its_readings_are_its_own_four_and_the_shared_ones():
    cell = manifest.Cell(CELL)
    by = {m["name"]: m for m in cell.per_layer}
    follow = {m["name"] for m in manifest.Cell(FOLLOW).per_layer}
    # every reading of the follower's, and four of its own
    assert set(by) == follow | set(OWN)
    assert SHARED_WITH_SYNC <= set(by)
    for m in cell.per_layer:
        assert callable(importlib.import_module(
            f"benchmark.readers.{m['reader']}").read)
        assert CELL in m["workloads"]
        assert m["moves"] in ("sync_blocks_per_s", "setup_s")
    for name, (reader, params, layer) in OWN.items():
        m = by[name]
        assert (m["reader"], m["params"], m["layer"]) == (reader, params, layer)
        assert m["workloads"] == [CELL] and m["source"] == "program_span"
    e2e = next(m for m in manifest.manifest()["end_to_end"]
               if m["name"] == "sync_blocks_per_s")
    assert e2e["workloads"][-1] == CELL


def _run(capsys, fault=None, seed=2**31 + 44) -> dict:
    cell = manifest.Cell(CELL)
    cfg = dict(cell.config, validators=7)
    traffic = dict(cell.traffic, warmup_heights=8, deadline_s=30,
                   committee_workers=2)
    args = ["--workload", "toy", "--seed", str(seed), "--trace", "0",
            "--seconds", "2"]
    try:
        rc = run.main(args, allow_cpu=True, fault=fault, cell=toy.ToyCell(
            "toy-hub150live", cfg, traffic, ["sync_blocks_per_s"]))
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


CHECKS = ("height_not_committed", "bad_vote_in_seen_commit",
          "honest_peer_dropped", "committee_failed", "hash_mismatches",
          "keys_read_back_wrong", "chain_differs", "own_votes_wrong",
          "own_turns_wrong", "own_turn_missing")


def test_a_sound_toy_run_of_the_cells_files_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert set(out["checks"]) == set(CHECKS)
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    facts = out["facts"]
    assert facts["own_proposals"] >= 1
    assert facts["own_votes_signed"] == 2 * facts["blocks"]
    assert len(facts["corrupted_heights"]) == 4
    assert facts["corrupted_heights"][0] > facts["height_end"]
    assert facts["committee_ms_per_height"] > 0


def test_an_unchecked_signature_is_not_correct(capsys):
    out = _run(capsys, faults.accept_all)
    assert out["correct"] is False
    assert out["checks"]["bad_vote_in_seen_commit"]["value"] >= 1
