"""Prometheus text exposition, parsed into {(family, labels): value}."""

from __future__ import annotations

import re
from urllib.request import urlopen

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        out[(m.group(1), labels)] = float(m.group(3))
    return out


def scrape(addr: str) -> dict:
    with urlopen(f"http://{addr}/metrics", timeout=30) as r:
        return parse(r.read().decode())


def total(samples: dict, family: str, match: dict | None = None) -> float:
    """Sum of the samples of `family` whose labels include `match`."""
    want = set((match or {}).items())
    return sum(v for (name, labels), v in samples.items()
               if name == family and want <= set(labels))


def delta(before: dict, after: dict, family: str, match: dict | None = None) -> float:
    return total(after, family, match) - total(before, family, match)
