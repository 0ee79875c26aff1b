"""The look for a chip. A run that finds none prints no result."""

from __future__ import annotations

import os
import sys


def cache_dir(root: str) -> str:
    """JAX_COMPILATION_CACHE_DIR when the machine comes with it, else the
    fixed git-ignored .jax_cache/ in the checkout: the path is part of
    the cache's key, so it never moves. The program reads the same
    variable itself and sets no directory of its own."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(root, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    os.makedirs(d, exist_ok=True)
    return d


def require(chips: int, *, allow_cpu: bool = False) -> dict:
    import jax

    backend = jax.default_backend()  # initialises the backend, compiles nothing
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"benchmark: device platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']} jax={jax.__version__}",
          file=sys.stderr, flush=True)
    if allow_cpu:
        return device
    if backend != "tpu":
        print(f"benchmark: no accelerator (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); nothing measured",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, jax sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return device


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
