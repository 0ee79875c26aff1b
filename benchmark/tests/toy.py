"""Toy-size cells for the CPU rehearsals: the real configuration and
traffic files, cut to a committee of 8 and a few hundred transactions."""

from __future__ import annotations

import json

from benchmark.harness import manifest


class ToyCell:
    chips = 1
    per_layer: list = []

    def __init__(self, name: str, config: dict, traffic: dict, end_to_end: list):
        self.name, self.config, self.traffic = name, config, traffic
        self.end_to_end = [{"name": n, "unit": "x"} for n in end_to_end + ["setup_s"]]


def sync_cell() -> ToyCell:
    cfg = dict(manifest.load_json("configs", "sync-500val.json"), validators=8)
    traffic = dict(manifest.load_json("workloads", "sync500-light.json"),
                   warmup_blocks=5, lookahead_blocks=8, chain_blocks_per_s=400,
                   deadline_s=20)
    return ToyCell("toy-sync", cfg, traffic, ["sync_blocks_per_s"])


def kv_cell() -> ToyCell:
    cfg = manifest.load_json("configs", "kvstore-1val.json")
    traffic = dict(manifest.load_json("workloads", "kv-signed-steady.json"),
                   rate_tx_per_s=150, warmup_seconds=1, drain_seconds=8)
    return ToyCell("toy-kv", cfg, traffic,
                   ["commit_latency_p50_ms"])


def last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
