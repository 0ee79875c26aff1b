"""100 x one counter's gain over another's across the window."""
from ..harness import prom


def read(p: dict, run) -> float | None:
    before, after = run.prom
    den = sum(prom.delta(before, after, d["family"], d.get("labels"))
              for d in p["of"])
    if den <= 0:
        return None
    num = sum(prom.delta(before, after, d["family"], d.get("labels"))
              for d in p["share"])
    return 100.0 * num / den
