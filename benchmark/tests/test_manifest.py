"""BENCHMARK.json and every data file parse and cross-refer."""

import importlib
import json
import os
import re

import pytest

from benchmark.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MAN = manifest.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


def test_configs_and_files():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
        body = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        for key in ("source", "assumed", "reduced", "guarantees", "chips"):
            assert key in body, (c["name"], key)
        assert body["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in MAN["workloads"])
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = manifest.Cell(name, MAN)
    entry = next(w for w in MAN["workloads"] if w["name"] == name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert NAME.match(name) and NAME.match(entry["traffic"])
    importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        importlib.import_module(f"benchmark.readers.{m['reader']}")
        assert m["moves"] in e2e, (name, m["name"], m["moves"])


def test_metrics_are_well_formed():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    seen = set()
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert os.path.exists(os.path.join(manifest.HERE, "metrics",
                                           m["name"] + ".json"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    idle = [m for m in MAN["per_layer"] if m["name"].startswith("device_idle_pct")]
    assert idle and all(m["source"] == "device_trace" for m in idle)


def test_every_data_file_is_named_by_the_manifest():
    used = {m["name"] + ".json" for m in MAN["per_layer"]}
    assert set(os.listdir(os.path.join(manifest.HERE, "metrics"))) == used
    traffic = {w["traffic"] + ".json" for w in MAN["workloads"]}
    assert traffic <= set(os.listdir(os.path.join(manifest.HERE, "workloads")))


def test_the_metric_of_pr_29():
    name = "p2p_recv_throttled_pct.sync10k"
    cells = ["sync10k-light", "sync10k-light-4chip", "sync10k-light-2peer"]
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    body = manifest.load_json("metrics", name + ".json")
    assert body["reader"] == "span_time" and set(cells) <= set(entry["workloads"])
    assert entry["moves"] == "sync_blocks_per_s" and entry["unit"] == "%"
    for cell in cells:
        assert name in {m["name"] for m in manifest.Cell(cell, MAN).per_layer}
    # a share that reads 0 when the span is absent
    assert body["params"]["what"] == "pct_of_window"
    assert entry["layer"] == "p2p link" and entry["source"] == "program_span"
    # how much of its chain a run used is a fact in every run's line, not
    # a metric: it is the end-to-end rate times a constant
    assert not [m for m in MAN["per_layer"] if m["name"].startswith("chain_used")]


def test_no_two_per_layer_entries_read_the_same():
    # a cell that reads what an entry reads joins its `workloads` list; a
    # metric file copied under another name would be a second entry
    # reading the same thing
    seen = {}
    for m in MAN["per_layer"]:
        body = manifest.load_json("metrics", m["name"] + ".json")
        key = json.dumps([body["reader"], body.get("params", {}), m["unit"],
                          m["better"], m["source"], m["layer"], m["moves"]],
                         sort_keys=True)
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]


def test_chain_lengths_and_the_ceilings_they_set():
    # chain = warm + lookahead + 4 + rate x seconds. The joiner can use
    # (chain - 1 - warm) of it before fast sync is over; the corrupted
    # commit, two past a tip `lookahead` ahead, fits up to rate + 2/seconds
    ends, fits = {}, {}
    for cell in ("sync500-light", "sync500-busy", "sync10k-light"):
        t = manifest.Cell(cell, MAN).traffic
        n = (t["warmup_blocks"] + t["lookahead_blocks"] + 4
             + t["chain_blocks_per_s"] * MAN["run_seconds"])
        ends[cell] = (n - 1 - t["warmup_blocks"]) / MAN["run_seconds"]
        fits[cell] = (n - t["lookahead_blocks"] - 2
                      - t["warmup_blocks"]) / MAN["run_seconds"]
    assert ends == {"sync500-light": 82.15, "sync500-busy": 12.15,
                    "sync10k-light": 3.65}
    assert fits == {"sync500-light": 80.1, "sync500-busy": 11.1,
                    "sync10k-light": 3.1}
