"""BENCHMARK.json and the data files it names, found by name alone."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)


def load_json(*rel: str) -> dict:
    with open(os.path.join(HERE, *rel)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with its configuration, its traffic file
    and the metrics that name it."""

    def __init__(self, name: str, man: dict | None = None):
        man = man or manifest()
        entry = next((w for w in man["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{[w['name'] for w in man['workloads']]}")
        self.name = name
        self.chips = entry["chips"]
        cfg = next(c for c in man["configs"] if c["name"] == entry["config"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("workloads", entry["traffic"] + ".json")

        def mine(m: dict) -> bool:
            return name in m["workloads"] if "workloads" in m else True

        self.end_to_end = [m for m in man["end_to_end"] if mine(m)]
        self.per_layer = [dict(m, **load_json("metrics", m["name"] + ".json"))
                          for m in man["per_layer"] if mine(m)]
