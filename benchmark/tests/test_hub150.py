"""The cell `hub150-follow` (configuration `hub-150val`): it resolves from
the manifest with one chip and its own files, it reads the vote path's own
per-layer entries and the entries it shares with the two-peer cell, and the
chain `harness/rounds.py` builds is the network's: the program's
proposer rotation, a proposal its proposer signed, prevotes and
precommits every one of which verifies, and precommits that are the
next block's LastCommit byte for byte. A toy run of the cell's files
(committee of 24, CPU) is `correct`; with a signature unchecked it is
not, and the corrupted precommits are found in the seen commits. Nothing
here is a measurement."""

import importlib
import os

import numpy as np
import pytest

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

from benchmark import run
from benchmark.harness import manifest
from benchmark.harness import rounds as roundslib
from benchmark.harness.reference import verify_one
from benchmark.tests import faults, toy

CELL, SHARES_WITH = "hub150-follow", "sync10k-light-2peer"
OWN = {"vote_run_mean.hub150", "votes_added_per_100_received.hub150",
       "sig_cache_hit_pct.hub150"}
# the vote run's share of the window, its wait for the mask, its WAL
# writes, and its own thread's CPU a height
VOTE_SPANS = {
    "vote_run_pct.hub150": ("span_time", {"name": "consensus.voteRun",
                                          "what": "pct_of_window"}),
    "preverify_wait_pct.hub150": ("span_time", {
        "name": "consensus.preverifyVotes", "what": "pct_of_window"}),
    "wal_write_pct.hub150": ("span_time", {"name": "wal.write",
                                           "what": "pct_of_window"}),
    "vote_run_cpu_ms_per_block.hub150": ("span_cpu", {
        "name": "consensus.voteRun", "what": "cpu_ms", "per": "request"})}
# entries the fast-sync cells read as well: device, runtime, compile-once,
# batch funnel and kernel
SHARED = {"device_route_pct.sync", "device_idle_pct.sync",
          "host_unattributed_pct.sync", "gc_pause_pct.sync",
          "host_cpu_ms_per_block.sync", "compiles_in_window", "kernel_ready_s",
          "ed25519_verify_roofline.sync10k", "ed25519_verify_us_per_sig.sync10k"}


def test_the_cell_resolves_with_one_chip_and_its_own_files():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1
    cfg = cell.config
    assert (cfg["name"], cfg["validators"], cfg["reduced"]) == (
        "hub-150val", 150, ["heights"])
    assert cfg["node"] == {"base.proxy_app": "kvstore", "base.fast_sync": False}
    assert cfg["reference"] == "benchmark/harness/reference.py"
    entry = next(c for c in manifest.manifest()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert cell.traffic["driver"] == "consensus_follow"
    assert cell.traffic["warmup_heights"] == 20
    assert {m["name"] for m in cell.end_to_end} == {"sync_blocks_per_s",
                                                    "setup_s"}


def test_every_hub150_metric_is_a_twin_or_the_vote_paths_own():
    cell = manifest.Cell(CELL)
    by = {m["name"]: m for m in cell.per_layer}
    assert set(by) == OWN | set(VOTE_SPANS) | SHARED
    two_peer = {m["name"] for m in manifest.Cell(SHARES_WITH).per_layer}
    assert SHARED <= two_peer
    for m in cell.per_layer:
        assert callable(importlib.import_module(
            f"benchmark.readers.{m['reader']}").read)
        if m["name"] in SHARED:
            assert SHARES_WITH in m["workloads"]
            continue
        assert m["name"].endswith(".hub150") and m["workloads"] == [CELL]
        assert m["moves"] == "sync_blocks_per_s"
    for name, (reader, params) in VOTE_SPANS.items():
        m = by[name]
        assert (m["reader"], m["params"]) == (reader, params)
        assert (m["layer"], m["source"]) == ("consensus vote path",
                                             "program_span")
    assert by["ed25519_verify_roofline.sync10k"]["params"]["pattern"] == \
        "ed25519_verify"
    assert by["host_cpu_ms_per_block.sync"]["params"]["per"] == "fact:blocks"
    assert by["vote_run_mean.hub150"]["params"] == {
        "family": "tendermint_consensus_vote_run_size"}


@pytest.fixture(scope="module")
def rounds():
    out = roundslib.committee(seed=2**31 + 41, validators=12)
    return roundslib.build(out, heights=6, txs_per_block=3, tx_bytes=64,
                           key_space=32, workers=2, warm_lanes=8)


def test_the_chain_is_the_networks(rounds):
    from tendermint_tpu.consensus.reactor import decode_msg
    from tendermint_tpu.types import serde

    vals, nxt = rounds.state0.validators.copy(), rounds.state0.next_validators.copy()
    proposers = set()
    for h in range(1, len(rounds) + 1):
        proposal = decode_msg(rounds.proposal_msg[h - 1]).proposal
        proposer = vals.get_proposer()
        proposers.add(proposer.address)
        assert verify_one(proposal.sign_bytes(rounds.chain_id),
                          proposal.signature, proposer.pub_key.bytes())
        parts = [decode_msg(m).part for m in rounds.part_msgs[h - 1]]
        block = serde.decode_block(b"".join(p.bytes for p in parts))
        assert block.hash() == rounds.block_hash[h - 1]
        assert block.header.proposer_address == proposer.address
        for msgs in (rounds.prevote_msgs[h - 1], rounds.precommit_msgs[h - 1]):
            for i, m in enumerate(msgs):
                v = decode_msg(m).vote
                assert (v.height, v.validator_index) == (h, i)
                assert verify_one(v.sign_bytes(rounds.chain_id), v.signature,
                                  rounds.pubkeys[i])
        if h > 1:  # this block's LastCommit is the last height's precommits
            assert [decode_msg(m).vote for m in rounds.precommit_msgs[h - 2]] \
                == block.last_commit.precommits
        vals, nxt = nxt, nxt.copy()
        nxt.increment_proposer_priority(1)
    assert len(proposers) == len(rounds)  # equal power: a new proposer a height
    assert all(verify_one(*t) for t in rounds.warm_triples)


def test_a_corrupted_height_flips_one_prevote_and_one_early_precommit(rounds):
    rng = np.random.default_rng(7)
    pre, pc, order, where = roundslib.corrupted(rounds, 3, rng, 6, 12, 8)
    assert [(t, i >= 6) for t, i, _ in where] == [(1, True), (2, True)]
    assert order.index(where[1][1]) < 8
    assert sorted(order) == list(range(12))
    changed = [i for i in range(12) if pre[i] != rounds.prevote_msgs[2][i]]
    assert changed == [where[0][1]]
    changed = [i for i in range(12) if pc[i] != rounds.precommit_msgs[2][i]]
    assert changed == [where[1][1]]


def _run(capsys, fault=None, seed=2**31 + 41) -> dict:
    cell = manifest.Cell(CELL)
    cfg = dict(cell.config, validators=24)
    traffic = dict(cell.traffic, warmup_heights=3, chain_heights_per_s=60,
                   deadline_s=20)
    args = ["--workload", "toy", "--seed", str(seed), "--trace", "0",
            "--seconds", "2"]
    try:
        rc = run.main(args, allow_cpu=True, fault=fault, cell=toy.ToyCell(
            "toy-hub150", cfg, traffic, ["sync_blocks_per_s"]))
    finally:
        faults.undo()
    assert rc == 0
    return toy.last_line(capsys.readouterr().out)


TAIL = ("height_not_committed", "bad_vote_in_seen_commit",
        "honest_peer_dropped", "hash_mismatches", "keys_read_back_wrong")


def test_a_sound_toy_run_of_the_cells_files_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert set(out["checks"]) == set(TAIL)
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    facts = out["facts"]
    assert len(facts["corrupted_heights"]) == 4
    assert facts["corrupted_heights"][0] > facts["height_end"]


def test_an_unchecked_signature_is_not_correct(capsys):
    out = _run(capsys, faults.accept_all)
    assert out["correct"] is False
    assert out["checks"]["bad_vote_in_seen_commit"]["value"] >= 1
