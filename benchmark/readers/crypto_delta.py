"""Kernel shapes the node made ready (compiled or loaded) between the
window's two readings of /debug/crypto: none is expected."""


def read(p: dict, run) -> float | None:
    before, after = run.crypto
    return float(len(after.get("kernels", [])) - len(before.get("kernels", []))
                 + after.get("compiles", 0) - before.get("compiles", 0))
