"""A number the driver took on its own clock or count, scaled."""


def read(p: dict, run) -> float | None:
    v = run.facts.get(p["key"])
    return None if v is None else p.get("scale", 1.0) * float(v)
