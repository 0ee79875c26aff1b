"""Microbenchmark: decompose the 10k-sig verify cost on the real chip.

Times dependent chains of each primitive at the bench batch size so the
per-op device cost (including any HBM round-trips XLA fails to fuse) is
visible. Run: python scripts/prof_field.py [B]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto.jaxed25519 import curve, field

B = int(sys.argv[1]) if len(sys.argv) > 1 else 10240


def _sync(out):
    leaves = jax.tree_util.tree_leaves(out)
    return np.asarray(leaves[0]).ravel()[0]


def timeit(name, fn, *args, n=3):
    _sync(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        _sync(fn(*args))
        ts.append(time.perf_counter() - t0)
    ms = min(ts) * 1000
    print(f"{name:38s} {ms:9.3f} ms")
    return ms


rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, 8191, size=(20, B), dtype=np.int32))
b = jnp.asarray(rng.integers(0, 8191, size=(20, B), dtype=np.int32))


from functools import partial


@partial(jax.jit, static_argnums=2)
def mul_chain(a, b, n):
    def body(i, v):
        return field.mul(v, b)
    return jax.lax.fori_loop(0, n, body, a)


@partial(jax.jit, static_argnums=1)
def sq_chain(a, n):
    def body(i, v):
        return field.square(v)
    return jax.lax.fori_loop(0, n, body, a)


@partial(jax.jit, static_argnums=2)
def add_chain(a, b, n):
    def body(i, v):
        return field.add(v, b)
    return jax.lax.fori_loop(0, n, body, a)


@jax.jit
def dbl_chain(a, b):
    p = (a, b, a, b)
    def body(i, p):
        return curve.double(p)
    return jax.lax.fori_loop(0, 20, body, p)


@jax.jit
def straus(a, b):
    pt = curve.identity_p3_like(a)
    pt = (a, b, pt[1], a)  # junk point; cost is shape-driven
    return curve.straus_mul_sub(a, b, pt)


rt = timeit("pure d2h fetch (round trip)", lambda x: x, a)
m100 = timeit("100x field.mul (dependent)", mul_chain, a, b, 100)
m1k = timeit("1000x field.mul", mul_chain, a, b, 1000)
s1k = timeit("1000x field.square", sq_chain, a, 1000)
a1k = timeit("1000x field.add", add_chain, a, b, 1000)
d20 = timeit("20x curve.double", dbl_chain, a, b)
st = timeit("straus_mul_sub (full)", straus, a, b)

mul_us = (m1k - m100) / 900 * 1000
print(f"\nround-trip overhead : {rt:8.1f} ms")
print(f"per field.mul (slope): {mul_us:8.1f} us")
print(f"per field.square     : {(s1k-rt)/1000*1000:8.1f} us")
print(f"per field.add        : {(a1k-rt)/1000*1000:8.1f} us")
print(f"straus compute       : {st-rt:8.1f} ms  (expect ~{(252*7+64*8+64*8)*mul_us/1000:.0f} ms if mul-bound)")

# HBM roofline: one mul reads 2x(20,B)x4B, writes (20,B)x4B
bytes_per_mul = 3 * 20 * B * 4
print(f"min HBM traffic/mul: {bytes_per_mul/1e6:.2f} MB -> at 800GB/s = {bytes_per_mul/800e9*1e6:.1f} us")
