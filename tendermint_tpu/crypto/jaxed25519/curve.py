"""Edwards25519 point operations on device (batched, extended coordinates).

Points are tuples of field elements (each (20, B) int32 limbs):
  P3     = (X, Y, Z, T)           extended homogeneous, T = XY/Z
  niels  = (Y+X, Y-X, 2dXY)       affine precomputed (fixed-base table rows)
  cached = (Y+X, Y-X, Z, 2dT)     projective precomputed (variable base)

Formulas are the RFC 8032 §5.1.4 unified add/double (complete on the
curve, no exceptional cases — crucial: batches mix arbitrary adversarial
points and everything must stay branch-free).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from . import field, ref
from .pack import int_to_limbs
from .scalar import scalar_bits


def identity_p3(bdim):
    zero = jnp.zeros((20, bdim), dtype=jnp.int32)
    one = zero.at[0].set(1)
    return (zero, one, one, zero)


def identity_p3_like(fe):
    """Identity point whose arrays derive from `fe` — keeps loop carries
    varying over a shard_map mesh axis (plain constants are unvarying and
    fail scan's carry-type check)."""
    zero = fe - fe
    one = zero.at[0].set(1)
    return (zero, one, one, zero)


def broadcast_const_p3(pt_ints, bdim):
    """Python-int extended point -> batched device point."""
    X, Y, Z, T = pt_ints
    mk = lambda v: jnp.broadcast_to(field.const_fe(v), (20, bdim)).astype(jnp.int32)
    return (mk(X), mk(Y), mk(Z), mk(T))


def double(p):
    X1, Y1, Z1, _ = p
    a = field.square(X1)
    b = field.square(Y1)
    zz = field.square(Z1)
    c = field.add(zz, zz)
    h = field.add(a, b)
    xy = field.add(X1, Y1)
    e = field.sub(h, field.square(xy))
    g = field.sub(a, b)
    f = field.add(c, g)
    return (field.mul(e, f), field.mul(g, h), field.mul(f, g), field.mul(e, h))


def to_cached(p):
    X, Y, Z, T = p
    d2 = field.const_fe(ref.D2)
    return (field.add(Y, X), field.sub(Y, X), Z, field.mul(T, d2))


def add_cached(p, q):
    X1, Y1, Z1, T1 = p
    yplusx2, yminusx2, Z2, t2d2 = q
    a = field.mul(field.sub(Y1, X1), yminusx2)
    b = field.mul(field.add(Y1, X1), yplusx2)
    c = field.mul(T1, t2d2)
    zz = field.mul(Z1, Z2)
    d = field.add(zz, zz)
    e = field.sub(b, a)
    f = field.sub(d, c)
    g = field.add(d, c)
    h = field.add(b, a)
    return (field.mul(e, f), field.mul(g, h), field.mul(f, g), field.mul(e, h))


def add_niels(p, n):
    """Mixed add: P3 + affine niels (Z2 = 1)."""
    X1, Y1, Z1, T1 = p
    yplusx2, yminusx2, xy2d2 = n
    a = field.mul(field.sub(Y1, X1), yminusx2)
    b = field.mul(field.add(Y1, X1), yplusx2)
    c = field.mul(T1, xy2d2)
    d = field.add(Z1, Z1)
    e = field.sub(b, a)
    f = field.sub(d, c)
    g = field.add(d, c)
    h = field.add(b, a)
    return (field.mul(e, f), field.mul(g, h), field.mul(f, g), field.mul(e, h))


def negate(p):
    X, Y, Z, T = p
    return (field.neg(X), Y, Z, field.neg(T))


def select_point(mask, p, q):
    return tuple(field.select(mask, a, b) for a, b in zip(p, q))


# --- decompression ---------------------------------------------------------


def decompress(y_limbs, sign):
    """y (20, B) raw 255-bit limbs, sign (B,) -> (P3 point, ok (B,) bool).

    Go-compatible (crypto/ed25519 feFromBytes): y is interpreted mod p —
    no canonicity rejection. Fails only when x recovery has no root, or
    x == 0 with sign bit set. Failed items yield the identity (safe for
    downstream arithmetic); callers mask by `ok`.
    """
    y = y_limbs
    one = field.const_fe(1)
    yy = field.mul(y, y)
    u = field.sub(yy, one)
    v = field.add(field.mul(field.const_fe(ref.D), yy), one)
    x, ok = field.sqrt_ratio(u, v)
    xf = field.freeze(x)
    x_is_zero = field.is_zero_frozen(xf)
    ok = ok & ~(x_is_zero & (sign == 1))
    # match parity to the sign bit (on the canonical representative)
    flip = (field.parity_frozen(xf) != sign) & ~x_is_zero
    x = field.select(flip, field.neg(xf), xf)
    pt = (x, y, jnp.broadcast_to(one, y.shape).astype(jnp.int32), field.mul(x, y))
    return select_point(ok, pt, identity_p3(y.shape[-1])), ok


# --- encoding --------------------------------------------------------------


def encode(p):
    """P3 -> (y_frozen (20, B) canonical limbs, x_parity (B,)).

    The canonical 32-byte encoding is y (255 bits) | parity(x) << 255;
    we keep it in limb space for comparison against raw signature bytes.
    """
    X, Y, Z, _ = p
    zinv = field.invert(Z)
    x = field.freeze(field.mul(X, zinv))
    y = field.freeze(field.mul(Y, zinv))
    return y, field.parity_frozen(x)


# --- scalar multiplication -------------------------------------------------


@lru_cache(maxsize=1)
def _base_table_np():
    """(64, 16, 60) float32: niels rows [j * 16^i]B, limbs concatenated.

    f32 is exact here (limb values < 2^13 << 2^24) and enables one-hot
    selection as an MXU matmul instead of a gather.
    """
    table = ref.base_table()
    out = np.zeros((64, 16, 60), dtype=np.float32)
    for i in range(64):
        for j in range(16):
            yplusx, yminusx, xy2d = table[i][j]
            out[i, j, :20] = int_to_limbs(yplusx)
            out[i, j, 20:40] = int_to_limbs(yminusx)
            out[i, j, 40:] = int_to_limbs(xy2d)
    return out


def fixed_base_mul(s_limbs):
    """[s]B via 64 windowed mixed additions, no doublings.

    s_limbs: (20, B) canonical limbs, value < 2^256.
    """
    bdim = s_limbs.shape[-1]
    bits = scalar_bits(s_limbs, 256)  # (256, B)
    weights = jnp.asarray([1, 2, 4, 8], dtype=jnp.int32)[None, :, None]
    windows = jnp.sum(bits.reshape(64, 4, bdim) * weights, axis=1)  # (64, B)
    table = jnp.asarray(_base_table_np())  # (64, 16, 60) f32

    def body(i, acc):
        row = jax.lax.dynamic_slice_in_dim(table, i, 1, axis=0)[0]  # (16, 60)
        onehot = (windows[i][None, :] == jnp.arange(16)[:, None]).astype(jnp.float32)
        # HIGHEST precision: default matmul precision is bf16 (8 mantissa
        # bits), which rounds the 13-bit limb values — must be exact f32
        entry = jnp.matmul(
            row.T,
            onehot,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        entry = entry.astype(jnp.int32)  # (60, B)
        return add_niels(acc, (entry[:20], entry[20:40], entry[40:]))

    return jax.lax.fori_loop(0, 64, body, identity_p3_like(s_limbs))


@lru_cache(maxsize=1)
def _small_base_table_np():
    """(16, 60) float32 niels rows [j]B for j = 0..15 (row 0 is the
    identity in niels form: (1, 1, 0)). Used by the Straus joint loop,
    which shares one doubling chain across both scalars so the base
    table needs no 16^i positioning."""
    out = np.zeros((16, 60), dtype=np.float32)
    out[0, 0] = 1.0
    out[0, 20] = 1.0
    base = ref.base_point()
    for j in range(1, 16):
        x, y = ref.to_affine(ref.scalar_mult(j, base))
        yplusx = (y + x) % ref.P
        yminusx = (y - x) % ref.P
        xy2d = (x * y % ref.P) * ref.D2 % ref.P
        out[j, :20] = int_to_limbs(yplusx)
        out[j, 20:40] = int_to_limbs(yminusx)
        out[j, 40:] = int_to_limbs(xy2d)
    return out


def _windows_msb_first(s_limbs, bdim):
    """(64, B) int32 4-bit windows, most-significant first."""
    bits = scalar_bits(s_limbs, 256)  # (256, B) LSB-first
    weights = jnp.asarray([1, 2, 4, 8], dtype=jnp.int32)[None, :, None]
    w = jnp.sum(bits.reshape(64, 4, bdim) * weights, axis=1)
    return w[::-1]


def straus_mul_sub(s_limbs, k_limbs, neg_a):
    """[s]B + [k]·neg_a with ONE shared doubling chain (Straus/Shamir,
    4-bit windows) — the joint form of the verification equation
    R' = [S]B − [k]A. Replaces fixed_base_mul + var_base_mul + final
    add: 252 doublings + 64 cached adds + 64 niels adds instead of
    256 doublings + 256 conditional adds + 64 niels adds + 1 add.

    s_limbs, k_limbs: (20, B) canonical scalars. neg_a: P3 batch.
    """
    bdim = s_limbs.shape[-1]
    s_win = _windows_msb_first(s_limbs, bdim)
    k_win = _windows_msb_first(k_limbs, bdim)

    # per-item table of cached([j]·neg_a), j = 1..15: odd rows by cached
    # add, even rows by doubling j/2 (14 point ops total)
    neg_a_cached = to_cached(neg_a)
    mults = [neg_a]
    for j in range(2, 16):
        if j % 2 == 0:
            mults.append(double(mults[j // 2 - 1]))
        else:
            mults.append(add_cached(mults[j - 2], neg_a_cached))
    cached = [to_cached(pt) for pt in mults]  # 15 × (4 × (20, B))
    # stack per component: 4 arrays of (15, 20, B)
    a_table = tuple(
        jnp.stack([c[comp] for c in cached], axis=0) for comp in range(4)
    )
    b_table = jnp.asarray(_small_base_table_np())  # (16, 60) f32

    def body(i, acc):
        acc = double(double(double(double(acc))))
        # variable-base window: masked-sum select of cached([j]negA)
        kw = k_win[i]  # (B,)
        mask = (jnp.arange(1, 16, dtype=jnp.int32)[:, None]
                == kw[None, :])  # (15, B)
        sel = tuple(
            jnp.sum(jnp.where(mask[:, None, :], comp, 0), axis=0)
            for comp in a_table
        )
        added = add_cached(acc, sel)
        acc = select_point(kw != 0, added, acc)
        # fixed-base window: one-hot × (16, 60) table on the MXU
        onehot = (s_win[i][None, :]
                  == jnp.arange(16)[:, None]).astype(jnp.float32)
        entry = jnp.matmul(
            b_table.T,
            onehot,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (60, B)
        return add_niels(acc, (entry[:20], entry[20:40], entry[40:]))

    return jax.lax.fori_loop(0, 64, body, identity_p3_like(s_limbs))


def var_base_mul(p, s_limbs):
    """[s]P by double-and-(conditionally-)add over 256 bits, branch-free.

    Simple and robust first cut; windowed/table version is a later-round
    optimization (see SURVEY §7 hard parts — latency discipline).
    """
    bdim = s_limbs.shape[-1]
    bits = scalar_bits(s_limbs, 256)  # (256, B)
    p_cached = to_cached(p)

    def body(i, acc):
        acc = double(acc)
        added = add_cached(acc, p_cached)
        bit = bits[255 - i]
        return select_point(bit == 1, added, acc)

    return jax.lax.fori_loop(0, 256, body, identity_p3_like(s_limbs))
