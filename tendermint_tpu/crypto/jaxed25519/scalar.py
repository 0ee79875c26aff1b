"""Scalar (mod L) arithmetic for the verify kernel.

Reduces the 512-bit SHA-512 output k to < 2^253 with k ≡ SHA mod L, via
three fold stages at the 2^252 boundary: k = lo + 2^252*hi ≡ lo - C*hi
(C = L - 2^252). Negative intermediates are avoided by adding a fixed
multiple of L per stage. Only partial reduction is needed — the scalar
mult consumes any 256-bit representative.
"""

from __future__ import annotations

from functools import lru_cache

import jax.numpy as jnp
import numpy as np

from . import ref
from .pack import BITS, MASK

C = ref.L - 2**252  # 125 bits


def _int_to_limbs_n(v: int, n: int) -> np.ndarray:
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = v & MASK
        v >>= BITS
    assert v == 0, "constant too large for limb count"
    return out


@lru_cache(maxsize=1)
def _consts():
    c10 = _int_to_limbs_n(C, 10)
    m1 = ((1 << 393) // ref.L + 1) * ref.L
    m2 = ((1 << 276) // ref.L + 1) * ref.L
    m3 = ((1 << 150) // ref.L + 1) * ref.L
    return c10, _int_to_limbs_n(m1, 31), _int_to_limbs_n(m2, 22), _int_to_limbs_n(m3, 20)


def _seq_carry_exact(coeffs, out_limbs: int):
    """Exact sequential carry into out_limbs 13-bit limbs. The final carry
    must be provably zero by construction (value fits)."""
    outs = []
    carry = jnp.zeros(coeffs.shape[1:], dtype=jnp.int32)
    n = coeffs.shape[0]
    for i in range(out_limbs):
        v = (coeffs[i] if i < n else jnp.zeros_like(carry)) + carry
        carry = v >> BITS
        outs.append(v & MASK)
    return jnp.stack(outs)


def _fold_stage(k, in_limbs: int, out_limbs: int, m_limbs: np.ndarray):
    c10, *_ = _consts()
    bdim = k.shape[-1]
    # hi limbs: bits >= 252 (limb 19, offset 5)
    n_hi = in_limbs - 19
    his = []
    for j in range(n_hi):
        v = k[19 + j] >> 5
        if 20 + j < in_limbs:
            v = v | (k[20 + j] << 8)
        his.append(v & MASK)
    hi = jnp.stack(his)  # (n_hi, B)
    lo = k[:20].at[19].set(k[19] & 31)
    # t = hi * C  (conv, coefficients < 10 * 2^26)
    t = jnp.zeros((n_hi + 10 - 1, bdim), dtype=jnp.int32)
    for i in range(10):
        t = t.at[i : i + n_hi].add(jnp.int32(int(c10[i])) * hi)
    # k' = lo + M - t; M (a multiple of L >= max t) keeps the value nonnegative
    width = out_limbs
    assert len(m_limbs) == width and t.shape[0] <= width and width >= 20
    acc = jnp.zeros((width, bdim), dtype=jnp.int32)
    acc = acc.at[:20].add(lo)
    acc = acc.at[: t.shape[0]].add(-t)
    acc = acc + jnp.asarray(m_limbs[:, None])
    return _seq_carry_exact(acc, out_limbs)


def _cond_sub(v, const_limbs: np.ndarray):
    """v - const if nonnegative else v (canonical 20-limb, exact chain)."""
    c = jnp.asarray(const_limbs[:, None])
    t = v - c
    outs = []
    borrow = jnp.zeros(v.shape[1:], dtype=jnp.int32)
    for i in range(v.shape[0]):
        x = t[i] + borrow
        borrow = x >> BITS
        outs.append(x & MASK)
    t_norm = jnp.stack(outs)
    return jnp.where((borrow < 0)[None, :], v, t_norm)


def reduce_512(k40):
    """(40, B) 13-bit limbs of a 512-bit value -> (20, B) canonical mod L.

    Full canonical reduction (not just partial): Go's sc_reduce is
    canonical, and for adversarial pubkeys with small-order components
    [k]A differs between k and k+m*L — consensus-critical to match.
    """
    _, m1, m2, m3 = _consts()
    k = _fold_stage(k40, 40, 31, m1)
    k = _fold_stage(k, 31, 22, m2)
    k = _fold_stage(k, 22, 20, m3)
    # k < 2^254 < 4L: two conditional subtracts make it canonical
    k = _cond_sub(k, _int_to_limbs_n(2 * ref.L, 20))
    k = _cond_sub(k, _int_to_limbs_n(ref.L, 20))
    return k


def scalar_bits(s20, nbits: int = 256):
    """(20, B) canonical limbs -> (nbits, B) int32 bits, little-endian."""
    shifts = jnp.arange(BITS, dtype=jnp.int32)[None, :, None]
    bits = (s20[:, None, :] >> shifts) & 1  # (20, 13, B)
    return bits.reshape(20 * BITS, -1)[:nbits]
