"""Fused Pallas TPU kernels for the Ed25519 batch-verify hot path.

Why: the XLA path in field.py/curve.py materializes the schoolbook-conv
intermediates of every field multiply in HBM (~10-60 MB per mul at
B=10k), which makes the ~2800-mul Straus chain HBM-bound (~21.5 us/mul
measured vs a ~3 us fused roofline — see PROFILE.md). This module runs
the ENTIRE joint scalar-multiplication loop as one Pallas kernel: the
accumulator, the per-item 15-entry table and every conv intermediate
stay in VMEM; HBM traffic collapses to the kernel inputs and outputs.

Semantics mirror field.py/curve.py exactly (same 20x13-bit limb
representation, same LIMB_BOUND invariant, same RFC 8032 complete
addition formulas); the reference behavior being replaced is the serial
verify loop at crypto/ed25519/ed25519.go:151-157 driven by
types/validator_set.go:345-371.

Value-level differences from field.py (pallas-friendly forms only):
- jnp.pad / .at[] are replaced by concatenate + pltpu.roll with static
  shifts (interpret mode substitutes jnp.roll, which pltpu.roll does
  not support off-TPU).
- The fixed-base niels table lookup is a one-hot f32 matmul on the MXU
  (exact: one-hot times 13-bit entries, single-term sums stay far under
  the 24-bit f32 mantissa), which is otherwise idle in this kernel.
- The per-item variable-base window select is a 4-level binary tree of
  lane-broadcast selects on the window bits (half the VPU ops of the
  15-term masked multiply-accumulate it replaces).
- Doublings and the per-window niels add skip the extended T coordinate
  whenever no consumer reads it (T is only needed by the one doubling
  that feeds add_cached, and by the final window when the caller wants
  T back): 4 of the ~45 field muls per window are dead and dropped.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pack import BITS, MASK, NLIMB


# --- field arithmetic on VMEM values (mirrors field.py) --------------------


def _zeros(rows, blk):
    return jnp.zeros((rows, blk), jnp.int32)


def _carry(v):
    """One parallel carry round within 20 limbs (field._carry_round)."""
    blk = v.shape[1]
    r = v >> BITS
    m = v & MASK
    # m[1:] += r[:-1]; m[0] += 608 * r[19]
    shifted = jnp.concatenate([_zeros(1, blk), r[:-1]], axis=0)
    top = jnp.concatenate([608 * r[19:20], _zeros(NLIMB - 1, blk)], axis=0)
    return m + shifted + top


def _reduce39(c):
    """39-coefficient conv output -> 20 bounded limbs (field._reduce_conv)."""
    blk = c.shape[1]
    r = c >> BITS
    m = c & MASK
    full = jnp.concatenate([m, _zeros(1, blk)], axis=0) + jnp.concatenate(
        [_zeros(1, blk), r], axis=0
    )
    v = full[:NLIMB] + 608 * full[NLIMB:]
    for _ in range(3):
        v = _carry(v)
    return v


def _tree_sum(terms):
    while len(terms) > 1:
        terms = [
            terms[j] + terms[j + 1] if j + 1 < len(terms) else terms[j]
            for j in range(0, len(terms), 2)
        ]
    return terms[0]


def _make_ops(interpret: bool):
    """Field + point ops bound to the right roll implementation."""
    roll = jnp.roll if interpret else pltpu.roll

    def neg(a):
        return _carry(-a)

    # Exact carry/borrow resolution: the Kogge-Stone parallel-prefix
    # resolves in field.py (one shared implementation — everything they
    # use lowers in Mosaic: concatenate/full/where/shifts on 2-D shapes).
    # vs the old sequential 20-step chains this is 5 dependent rounds of
    # full-width (20, blk) selects instead of ~60 dependent (1, blk) ops
    # at 1/8 sublane utilization.
    from . import field as _field

    seq_carry = _field._seq_carry
    cond_sub = _field._cond_sub

    def freeze(a, p_mults):
        """Canonical limbs in [0, p); p_mults = (16p, 8p, 4p, 2p, p, p)."""
        v = a
        for _ in range(2):
            limbs, carry = seq_carry(v)
            v = jnp.concatenate([limbs[:1] + 608 * carry, limbs[1:]], axis=0)
        limbs, _ = seq_carry(v)
        v = limbs
        for m in p_mults:
            v = cond_sub(v, m)
        return v

    def mul(a, b):
        blk = a.shape[1]
        z19 = _zeros(NLIMB - 1, blk)
        terms = []
        for i in range(NLIMB):
            prod = a[i : i + 1] * b  # (20, blk)
            padded = jnp.concatenate([prod, z19], axis=0)  # (39, blk)
            terms.append(roll(padded, i, 0) if i else padded)
        return _reduce39(_tree_sum(terms))

    def sq(a):
        blk = a.shape[1]
        a2 = a + a
        terms = []
        for i in range(NLIMB):
            # diagonal term once, cross terms doubled for j > i (20-i rows)
            parts = [a[i : i + 1]]
            if i + 1 < NLIMB:
                parts.append(a2[i + 1 :])
            row = a[i : i + 1] * jnp.concatenate(parts, axis=0)
            padded = jnp.concatenate([row, _zeros(NLIMB - 1 + i, blk)], axis=0)
            terms.append(roll(padded, 2 * i, 0) if i else padded)
        return _reduce39(_tree_sum(terms))

    add = lambda a, b: _carry(a + b)
    sub = lambda a, b: _carry(a - b)

    def _double_efgh(p):
        X1, Y1, Z1 = p[0], p[1], p[2]
        a = sq(X1)
        b = sq(Y1)
        zz = sq(Z1)
        c = add(zz, zz)
        h = add(a, b)
        xy = add(X1, Y1)
        e = sub(h, sq(xy))
        g = sub(a, b)
        f = add(c, g)
        return e, f, g, h

    def double(p):
        e, f, g, h = _double_efgh(p)
        return (mul(e, f), mul(g, h), mul(f, g), mul(e, h))

    def double3(p):
        """Doubling without the extended T output — for chains where the
        next op is another doubling (which never reads T)."""
        e, f, g, h = _double_efgh(p)
        return (mul(e, f), mul(g, h), mul(f, g))

    def to_cached(p, d2):
        X, Y, Z, T = p
        return (add(Y, X), sub(Y, X), Z, mul(T, d2))

    def add_cached(p, q):
        X1, Y1, Z1, T1 = p
        yplusx2, yminusx2, Z2, t2d2 = q
        a = mul(sub(Y1, X1), yminusx2)
        b = mul(add(Y1, X1), yplusx2)
        c = mul(T1, t2d2)
        zz = mul(Z1, Z2)
        d = add(zz, zz)
        e = sub(b, a)
        f = sub(d, c)
        g = add(d, c)
        h = add(b, a)
        return (mul(e, f), mul(g, h), mul(f, g), mul(e, h))

    def _add_niels_efgh(p, n):
        X1, Y1, Z1, T1 = p
        yplusx2, yminusx2, xy2d2 = n
        a = mul(sub(Y1, X1), yminusx2)
        b = mul(add(Y1, X1), yplusx2)
        c = mul(T1, xy2d2)
        d = add(Z1, Z1)
        e = sub(b, a)
        f = sub(d, c)
        g = add(d, c)
        h = add(b, a)
        return e, f, g, h

    def add_niels(p, n):
        e, f, g, h = _add_niels_efgh(p, n)
        return (mul(e, f), mul(g, h), mul(f, g), mul(e, h))

    def add_niels3(p, n):
        """Niels add without the extended T output — for window tails
        where the next consumer is a doubling."""
        e, f, g, h = _add_niels_efgh(p, n)
        return (mul(e, f), mul(g, h), mul(f, g))

    def pow2k(x, k):
        return jax.lax.fori_loop(0, k, lambda _, v: sq(v), x)

    def pow_chain_250(z):
        """z^(2^250 - 1) — shared prefix of invert/pow22523 (field.py)."""
        z2 = sq(z)
        t = sq(sq(z2))
        z9 = mul(t, z)
        z11 = mul(z9, z2)
        t = sq(z11)
        z_5_0 = mul(t, z9)
        t = pow2k(z_5_0, 5)
        z_10_0 = mul(t, z_5_0)
        t = pow2k(z_10_0, 10)
        z_20_0 = mul(t, z_10_0)
        t = pow2k(z_20_0, 20)
        z_40_0 = mul(t, z_20_0)
        t = pow2k(z_40_0, 10)
        z_50_0 = mul(t, z_10_0)
        t = pow2k(z_50_0, 50)
        z_100_0 = mul(t, z_50_0)
        t = pow2k(z_100_0, 100)
        z_200_0 = mul(t, z_100_0)
        t = pow2k(z_200_0, 50)
        z_250_0 = mul(t, z_50_0)
        return z_250_0, z11

    def invert(z):
        z_250_0, z11 = pow_chain_250(z)
        return mul(pow2k(z_250_0, 5), z11)

    def pow22523(z):
        z_250_0, _ = pow_chain_250(z)
        return mul(pow2k(z_250_0, 2), z)

    import types

    return types.SimpleNamespace(
        mul=mul, sq=sq, add=add, sub=sub, neg=neg, double=double,
        double3=double3, to_cached=to_cached, add_cached=add_cached,
        add_niels=add_niels, add_niels3=add_niels3,
        seq_carry=seq_carry, cond_sub=cond_sub, freeze=freeze,
        pow2k=pow2k, invert=invert, pow22523=pow22523,
    )


@lru_cache(maxsize=1)
def _btab_np():
    """(16, 64) int32: niels rows [j]B for j=0..15 in cols 0:60."""
    from .curve import _small_base_table_np

    t = np.zeros((16, 64), dtype=np.int32)
    t[:, :60] = _small_base_table_np().astype(np.int64).astype(np.int32)
    return t


def _tree_select(idx, entries):
    """4-level binary-tree select of one of 16 table entries per lane.

    idx: (1, blk) int32 in [0, 16); entries: length-16 list of tuples of
    (rows, blk) arrays. Costs 15 lane-broadcast selects per component —
    about half the VPU work of a 16-term masked multiply-accumulate."""
    level = entries
    for bit in range(4):
        b = ((idx >> bit) & 1) != 0  # (1, blk)
        level = [
            tuple(jnp.where(b, hi, lo) for lo, hi in zip(level[2 * j], level[2 * j + 1]))
            for j in range(len(level) // 2)
        ]
    return level[0]


def _straus_loop(ops, s_win_ref, k_win_ref, neg_a, d2, btab, blk,
                 want_t: bool = False):
    """The joint [s]B + [k]*neg_a chain on VMEM values (see
    curve.straus_mul_sub for the algorithm). Returns (X, Y, Z) — plus the
    extended T when want_t (callers that only encode never read T, and
    skipping it drops 4 dead muls per window)."""
    # per-item table cached([j]*neg_a), j=1..15 — VMEM-resident
    na_cached = ops.to_cached(neg_a, d2)
    mults = [neg_a]
    for j in range(2, 16):
        if j % 2 == 0:
            mults.append(ops.double(mults[j // 2 - 1]))
        else:
            mults.append(ops.add_cached(mults[j - 2], na_cached))
    table = [ops.to_cached(p, d2) for p in mults]
    # tree-select domain is 16 entries; index 15 is only produced by the
    # kw==0 lanes whose add is discarded by the where below — pad with a
    # duplicate so every index is in range
    table16 = table + [table[14]]

    zero = _zeros(NLIMB, blk)
    one = jnp.concatenate(
        [jnp.ones((1, blk), jnp.int32), _zeros(NLIMB - 1, blk)], axis=0
    )
    btab_f = btab[:, :60].astype(jnp.float32)  # (16, 60), loop-invariant

    def window(w, acc3, tail_t: bool):
        acc3 = ops.double3(ops.double3(ops.double3(acc3)))
        acc = ops.double(acc3)  # full: add_cached consumes T
        # variable-base window: binary-tree select over the cached table
        kw = k_win_ref[pl.ds(w, 1), :]  # (1, blk)
        sel = _tree_select((kw - 1) & 15, table16)
        added = ops.add_cached(acc, sel)
        acc = tuple(jnp.where(kw != 0, x, y) for x, y in zip(added, acc))
        # fixed-base window: one-hot f32 matmul on the (otherwise idle)
        # MXU — exact, one-hot times 13-bit entries
        sw = s_win_ref[pl.ds(w, 1), :]  # (1, blk)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (16, blk), 0) == sw
        ).astype(jnp.float32)
        # HIGHEST precision is required: the TPU MXU's default f32 path
        # rounds inputs to bf16 (8 mantissa bits), which corrupts 13-bit
        # table entries; the 3-way bf16 split is exact at these magnitudes
        ent = jax.lax.dot_general(
            btab_f, onehot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (60, blk)
        n = (ent[:20], ent[20:40], ent[40:60])
        return ops.add_niels(acc, n) if tail_t else ops.add_niels3(acc, n)

    acc3 = jax.lax.fori_loop(
        0, 63, lambda w, a: window(w, a, False), (zero, one, one)
    )
    return window(63, acc3, want_t)


def _make_straus_kernel(interpret: bool):
    ops = _make_ops(interpret)

    def kernel(s_win_ref, k_win_ref, nax_ref, nay_ref, naz_ref, nat_ref,
               btab_ref, ox_ref, oy_ref, oz_ref, ot_ref):
        from . import ref

        na = (nax_ref[:], nay_ref[:], naz_ref[:], nat_ref[:])
        blk = na[0].shape[1]
        d2 = _const_fe_rows(ref.D2, blk)
        btab = btab_ref[:]  # (16, 64)
        X, Y, Z, T = _straus_loop(ops, s_win_ref, k_win_ref, na, d2, btab, blk,
                                  want_t=True)
        ox_ref[:] = X
        oy_ref[:] = Y
        oz_ref[:] = Z
        ot_ref[:] = T

    return kernel


def _pick_block(b: int) -> int:
    # widest lane block that divides the batch. Buckets under one
    # 128-lane tile (8..64) run as a single narrower block; Mosaic
    # compiles those too (v5e, jax 0.9.0, PR 21). blk=1024 overflowed
    # the default scoped-VMEM limit when measured before the Kogge-Stone
    # freeze landed; not re-measured since, 512 fits.
    for blk in (512, 256, 128):
        if b % blk == 0:
            return blk
    return b


@lru_cache(maxsize=16)
def _straus_call(bdim: int, interpret: bool):
    blk = _pick_block(bdim)
    win_spec = pl.BlockSpec((64, blk), lambda i: (0, i))
    fe_spec = pl.BlockSpec((NLIMB, blk), lambda i: (0, i))
    btab_spec = pl.BlockSpec((16, 64), lambda i: (0, 0))
    out_sh = jax.ShapeDtypeStruct((NLIMB, bdim), jnp.int32)
    return pl.pallas_call(
        _make_straus_kernel(interpret),
        grid=(bdim // blk,),
        in_specs=[win_spec, win_spec, fe_spec, fe_spec, fe_spec, fe_spec,
                  btab_spec],
        out_specs=[fe_spec] * 4,
        out_shape=[out_sh] * 4,
        interpret=interpret,
        name="ed25519_straus",
    )


# --- the fused verify tail: decompress -> straus -> encode -> compare ------


def _const_fe_rows(v: int, blk: int):
    """Python-int field constant -> (20, blk) rows of scalar splats (Mosaic
    rejects (n,1)->(n,blk) lane broadcasts; splat-from-immediate is fine)."""
    rows = [
        jnp.full((1, blk), (v >> (BITS * i)) & MASK, jnp.int32)
        for i in range(NLIMB)
    ]
    return jnp.concatenate(rows, axis=0)


def _make_verify_tail_kernel(interpret: bool):
    ops = _make_ops(interpret)
    from . import ref

    def kernel(ay_ref, asign_ref, ry_ref, rsign_ref, s_win_ref, k_win_ref,
               btab_ref, mask_ref):
        a_y = ay_ref[:]
        blk = a_y.shape[1]
        d = _const_fe_rows(ref.D, blk)
        d2 = _const_fe_rows(ref.D2, blk)
        sqrt_m1 = _const_fe_rows(ref.SQRT_M1, blk)
        p1 = _const_fe_rows(ref.P, blk)
        p_mults = [
            _const_fe_rows(16 * ref.P, blk), _const_fe_rows(8 * ref.P, blk),
            _const_fe_rows(4 * ref.P, blk), _const_fe_rows(2 * ref.P, blk),
            p1, p1,
        ]
        one = jnp.concatenate(
            [jnp.ones((1, blk), jnp.int32), _zeros(NLIMB - 1, blk)], axis=0
        )

        # decompress A (curve.decompress: Go feFromBytes semantics, y mod p)
        a_sign = asign_ref[:]  # (1, blk)
        yy = ops.mul(a_y, a_y)
        u = ops.sub(yy, one)
        v = ops.add(ops.mul(d, yy), one)
        # sqrt_ratio (field.sqrt_ratio, RFC 8032 5.1.3)
        v2 = ops.sq(v)
        v3 = ops.mul(v2, v)
        v7 = ops.mul(ops.sq(v3), v)
        t = ops.pow22523(ops.mul(u, v7))
        x = ops.mul(ops.mul(u, v3), t)
        vxx = ops.mul(v, ops.sq(x))
        is0 = lambda fz: jnp.all(fz == 0, axis=0, keepdims=True)  # (1, blk)
        ok_plus = is0(ops.freeze(ops.sub(vxx, u), p_mults))
        ok_minus = is0(ops.freeze(ops.sub(vxx, ops.neg(u)), p_mults))
        x = jnp.where(ok_minus, ops.mul(x, sqrt_m1), x)
        ok = ok_plus | ok_minus
        xf = ops.freeze(x, p_mults)
        x_is_zero = is0(xf)
        ok = ok & ~(x_is_zero & (a_sign == 1))
        flip = ((xf[:1] & 1) != a_sign) & ~x_is_zero
        x = jnp.where(flip, ops.neg(xf), xf)
        a_pt = (x, a_y, jnp.broadcast_to(one, a_y.shape), ops.mul(x, a_y))
        # failed decompress -> identity (safe downstream), masked by ok
        ident = (_zeros(NLIMB, blk), one, one, _zeros(NLIMB, blk))
        a_pt = tuple(jnp.where(ok, g, i) for g, i in zip(a_pt, ident))
        neg_a = (ops.neg(a_pt[0]), a_pt[1], a_pt[2], ops.neg(a_pt[3]))

        # R' = [S]B + [k](-A), one shared-doubling chain (T never read)
        X, Y, Z = _straus_loop(
            ops, s_win_ref, k_win_ref, neg_a, d2, btab_ref[:], blk
        )

        # encode + compare against the signature's R
        zinv = ops.invert(Z)
        xe = ops.freeze(ops.mul(X, zinv), p_mults)
        ye = ops.freeze(ops.mul(Y, zinv), p_mults)
        eq = jnp.all(ye == ry_ref[:], axis=0, keepdims=True)
        eq = eq & ((xe[:1] & 1) == rsign_ref[:])
        mask_ref[:] = (ok & eq).astype(jnp.int32)

    return kernel


@lru_cache(maxsize=16)
def _verify_tail_call(bdim: int, interpret: bool):
    blk = _pick_block(bdim)
    win_spec = pl.BlockSpec((64, blk), lambda i: (0, i))
    fe_spec = pl.BlockSpec((NLIMB, blk), lambda i: (0, i))
    row_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    btab_spec = pl.BlockSpec((16, 64), lambda i: (0, 0))
    return pl.pallas_call(
        _make_verify_tail_kernel(interpret),
        grid=(bdim // blk,),
        in_specs=[fe_spec, row_spec, fe_spec, row_spec, win_spec, win_spec,
                  btab_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, bdim), jnp.int32),
        interpret=interpret,
        name="ed25519_straus_fused",  # the kernel's name in a device trace
    )


def verify_tail(a_y, a_sign, r_y, r_sign, s_limbs, k_limbs, *,
                interpret: bool = False):
    """Fused device tail of _verify_core: decompress(A), R' = [S]B − [k]A,
    encode, compare with R. Returns a (B,) bool mask. Inputs as in
    verify._verify_core (a_sign/r_sign are (B,) int32)."""
    from .curve import _windows_msb_first

    bdim = a_y.shape[-1]
    s_win = _windows_msb_first(s_limbs, bdim)
    k_win = _windows_msb_first(k_limbs, bdim)
    btab = jnp.asarray(_btab_np())
    mask = _verify_tail_call(bdim, bool(interpret))(
        a_y, a_sign.reshape(1, bdim).astype(jnp.int32), r_y,
        r_sign.reshape(1, bdim).astype(jnp.int32), s_win, k_win, btab,
    )
    return mask[0] != 0


def straus_mul_sub(s_limbs, k_limbs, neg_a, *, interpret: bool = False):
    """Drop-in fused replacement for curve.straus_mul_sub: [s]B + [k]*neg_a
    with one shared doubling chain, entirely VMEM-resident per block."""
    from .curve import _windows_msb_first

    bdim = s_limbs.shape[-1]
    s_win = _windows_msb_first(s_limbs, bdim)
    k_win = _windows_msb_first(k_limbs, bdim)
    btab = jnp.asarray(_btab_np())
    X, Y, Z, T = _straus_call(bdim, bool(interpret))(s_win, k_win, *neg_a, btab)
    return (X, Y, Z, T)
