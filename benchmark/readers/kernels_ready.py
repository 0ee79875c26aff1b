"""Seconds the node spent making kernel shapes ready (compiled or loaded
from the store) before the window opened: /debug/crypto `kernels`."""


def read(p: dict, run) -> float | None:
    kernels = run.crypto[0].get("kernels")
    if not kernels:
        return None
    return float(sum(k["seconds"] for k in kernels))
