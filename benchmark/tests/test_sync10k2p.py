"""The cell `sync10k-light-2peer` (configuration `sync-10kval-2peer`):
it resolves from the manifest with one chip, its configuration is
`sync-10kval` but for the second peer and the fourth guarantee, it
reads every per-layer entry the one-peer cell reads and three of its
own, and the reader the cell brings (`readers/span_overlap.py`) reads
a list of spans made here. A toy run of its files, cut to a committee
of 24 on the CPU, is `correct`; with a signature unchecked, or with the
pool asking again for the refused height alone (what the program did
before the deployment's fourth guarantee), it is not. Nothing here is a
measurement."""

import importlib
import os

import pytest

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import manifest
from benchmark.readers import prom_share, span_overlap
from benchmark.tests import faults, toy
from benchmark.tests.test_spans import rec, run_of

CELL, ONE_PEER = "sync10k-light-2peer", "sync10k-light"
NEW = {"p2p_recv_all_throttled_pct.sync10k2p": ("p2p link", "program_span"),
       "pool_first_peer_share_pct.sync10k2p": ("block sync", "program_counter")}
FOURTH = ("after a block whose commit carries a corrupted signature is "
          "refused, its peer is dropped, the honest peer is kept, and the "
          "honest copies of that height and the next are applied from it")


def test_the_cell_resolves_with_one_chip_and_its_own_files():
    man = manifest.manifest()
    cell, one = manifest.Cell(CELL), manifest.Cell(ONE_PEER)
    assert cell.chips == 1
    cfg = cell.config
    assert (cfg["name"], cfg["peers"]) == ("sync-10kval-2peer", 2)
    assert cfg["reference"] == "benchmark/harness/reference.py"
    assert cfg["reduced"] == ["blocks"]
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    # the one-peer deployment word for word, but for the second peer
    differ = {k for k in set(cfg) | set(one.config)
              if cfg.get(k) != one.config.get(k)}
    assert differ == {"name", "source", "deployment", "peers", "guarantees",
                      "reference", "assumed"}
    assert cfg["guarantees"] == one.config["guarantees"] + [FOURTH]
    assert {k for k in cfg["assumed"]
            if cfg["assumed"][k] != one.config["assumed"].get(k)} == {
        "p2p_rate_bytes_per_s", "serving_peer"}
    assert set(cfg["assumed"]) == set(one.config["assumed"])
    assert cfg["node"] == {"base.proxy_app": "kvstore"}
    # the traffic of the one-peer cell on a longer chain, by another driver
    assert cell.traffic == dict(one.traffic, driver="fastsync_from_peers",
                                chain_blocks_per_s=6)
    assert {m["name"] for m in cell.end_to_end} == {"sync_blocks_per_s",
                                                    "setup_s"}
    # 140 blocks; the corrupted commit at T+2 and the honest block at T+3
    # fit up to 6.05 blocks/s, above the two links' 5.84
    t, secs = cell.traffic, man["run_seconds"]
    n = t["warmup_blocks"] + t["lookahead_blocks"] + 4 + 6 * secs
    assert n == 140
    assert (n - t["lookahead_blocks"] - 3 - t["warmup_blocks"]) / secs == 6.05
    assert 5.84 < 2 * cfg["p2p_rate_bytes_per_s"] / 1752640 < 5.85


def test_every_sync10k2p_metric_is_a_twin_or_one_of_the_two_new():
    # the cell reads every entry the one-peer cell reads (the same entry,
    # under its name) and three of its own: the two that only two peers
    # have, and the validation's own CPU
    cell = manifest.Cell(CELL)
    by = {m["name"]: m for m in cell.per_layer}
    one = {m["name"] for m in manifest.Cell(ONE_PEER).per_layer}
    own = set(NEW) | {"apply_validate_cpu_ms_per_block.sync10k2p"}
    assert one <= set(by) and set(by) - one == own
    for m in cell.per_layer:
        assert callable(importlib.import_module(
            f"benchmark.readers.{m['reader']}").read)
        if m["name"] in own:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "sync_blocks_per_s"
        else:
            assert {CELL, ONE_PEER} <= set(m["workloads"])
        if m["name"] in NEW:
            assert (m["layer"], m["source"]) == NEW[m["name"]]
            assert m["unit"] == "%"
    assert by["p2p_recv_all_throttled_pct.sync10k2p"]["params"] == {
        "name": "p2p.recvThrottle", "arg": "peer",
        "distinct_from_config": "peers"}
    family = "tendermint_blockchain_pool_blocks_received_total"
    assert by["pool_first_peer_share_pct.sync10k2p"]["params"] == {
        "share": [{"family": family, "labels": {"slot": "0"}}],
        "of": [{"family": family}]}


# --- the reader, on spans made here ----------------------------------------

OVERLAP = {"name": "p2p.recvThrottle", "arg": "peer",
           "distinct_from_config": "peers"}


def _read(records, peers=2, window_ms=(0, 1000)):
    r = run_of(records, window_ms)
    r.cell = toy.ToyCell("t", {"peers": peers}, {}, [])
    return span_overlap.read(OVERLAP, r)


def test_every_link_asleep_is_the_overlap_and_not_the_union():
    spans = [
        rec("p2p.recvThrottle", 0, 100, 1, peer="aaaaaaaa"),
        rec("p2p.recvThrottle", 100, 100, 2, peer="aaaaaaaa"),  # touches
        rec("p2p.recvThrottle", 150, 100, 3, thread=2, peer="bbbbbbbb"),
        rec("p2p.recvThrottle", 400, 100, 4, thread=2, peer="bbbbbbbb"),
        rec("p2p.sendThrottle", 0, 1000, 5, thread=3, peer="aaaaaaaa"),
        rec("p2p.recvThrottle", 900, 300, 6, peer="aaaaaaaa"),
        rec("p2p.recvThrottle", 950, 300, 7, thread=2, peer="bbbbbbbb"),
    ]
    # a and b together: 150..200 and 950..1000 (clipped at the window)
    assert _read(spans) == pytest.approx(10.0)
    assert _read(spans, peers=1) == pytest.approx(
        100 * (250 + 100 + 100) / 1000)  # the union
    assert _read(spans, peers=3) == 0.0
    assert _read(spans, window_ms=(150, 250)) == pytest.approx(50.0)
    # one link's own spans never count as two links
    assert _read(spans[:2] + [rec("p2p.recvThrottle", 50, 100, 8,
                                  peer="aaaaaaaa")]) == 0.0


def test_spans_that_name_no_peer_give_nothing_to_read():
    # the parent's spans: throttled stretches with no args at all
    old = [rec("p2p.recvThrottle", 0, 100, 1),
           rec("p2p.recvBlock", 10, 20, 2, height=5, txs=10, bytes=99)]
    assert _read(old) is None
    assert _read([]) is None
    # blocks arrived labelled and no limiter slept: a share of 0
    quiet = [rec("p2p.recvBlock", 10, 20, 2, height=5, peer="aaaaaaaa")]
    assert _read(quiet) == 0.0
    no_trace = run_of(quiet)
    no_trace.trace = None
    no_trace.cell = toy.ToyCell("t", {"peers": 2}, {}, [])
    assert span_overlap.read(OVERLAP, no_trace) is None
    # a program without the pool's families: the share is left out
    params = manifest.load_json(
        "metrics", "pool_first_peer_share_pct.sync10k2p.json")["params"]
    before = {("tendermint_other_total", ()): 1.0}
    r = run_of([])
    r.prom = (before, dict(before))
    assert prom_share.read(params, r) is None
    fam = "tendermint_blockchain_pool_blocks_received_total"
    r.prom = ({(fam, (("slot", "0"),)): 4.0, (fam, (("slot", "1"),)): 2.0},
              {(fam, (("slot", "0"),)): 10.0, (fam, (("slot", "1"),)): 20.0})
    assert prom_share.read(params, r) == pytest.approx(25.0)


# --- the cell's own files at toy size --------------------------------------


def redo_refused_height_alone(node) -> None:
    """The program before this deployment's fourth guarantee: after a
    refused commit the pool asks again for that height alone and removes
    its peer; the next block, whose LastCommit was the corrupted one,
    stays."""
    from tendermint_tpu.blockchain.pool import BlockPool

    def redo_request(self, height):
        with self._lock:
            req = self._requesters.get(height)
            if req is None:
                return 0, []
            bad, req.block, req.peer_id = req.peer_id, None, None
        if bad:
            self._error_fn(bad, f"bad block at height {height}")
            self.remove_peer(bad)
        self._dispatch(height)
        return 1, [bad] if bad else []

    faults._patch(BlockPool, "redo_request", redo_request)


def _run(capsys, fault=None, seed=2**31 + 36, deadline_s=20) -> dict:
    cell = manifest.Cell(CELL)
    cfg = dict(cell.config, validators=24)
    traffic = dict(cell.traffic, warmup_blocks=3, lookahead_blocks=6,
                   chain_blocks_per_s=400, deadline_s=deadline_s)
    args = ["--workload", "toy", "--seed", str(seed), "--trace", "0",
            "--seconds", "2"]
    try:
        rc = run.main(args, allow_cpu=True, fault=fault, cell=toy.ToyCell(
            "toy-sync10k2p", cfg, traffic, ["sync_blocks_per_s"]))
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


TAIL = ("height_past_bad_commit", "stopped_short_of_bad_commit",
        "dishonest_peer_not_dropped", "honest_copy_not_applied",
        "honest_peer_dropped")


@pytest.mark.parametrize("seed,a", [(2**31 + 36, 1), (2**31 + 39, 0)])
def test_a_sound_toy_run_of_the_cells_files_is_correct(capsys, seed, a):
    out = _run(capsys, seed=seed)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert set(TAIL) < set(out["checks"])
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    facts = out["facts"]
    assert len(facts["blocks_from_peer"]) == 2 and 0 not in facts["blocks_from_peer"]
    assert facts["dishonest_peer"] == a  # either peer can be the one


def test_an_unchecked_signature_is_not_correct(capsys):
    out = _run(capsys, faults.accept_all, deadline_s=8)
    assert out["correct"] is False
    checks = out["checks"]
    assert (checks["height_past_bad_commit"]["value"] >= 1
            or checks["dishonest_peer_not_dropped"]["value"] == 1)


def test_the_refused_height_asked_again_alone_is_not_correct(capsys):
    out = _run(capsys, redo_refused_height_alone)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["honest_peer_dropped"]["value"] == 1
    assert checks["honest_copy_not_applied"]["value"] == 2
    assert checks["dishonest_peer_not_dropped"]["value"] == 0
    assert checks["height_past_bad_commit"]["value"] == 0
