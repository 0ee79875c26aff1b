"""Mean of a histogram family over the window: sum over count, scaled."""
from ..harness import prom


def read(p: dict, run) -> float | None:
    before, after = run.prom
    count = prom.delta(before, after, p["family"] + "_count", p.get("labels"))
    if count <= 0:
        return None
    total = prom.delta(before, after, p["family"] + "_sum", p.get("labels"))
    return p.get("scale", 1.0) * total / count
