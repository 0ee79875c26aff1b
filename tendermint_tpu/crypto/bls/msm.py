"""Bitmap -> aggregate-pubkey G1 summation kernels.

The aggregate-verification hot path reduces a signer bitmap over the
validator set's G1 pubkeys to ONE aggregate public key. That is a
multi-scalar multiplication with every scalar equal to 1 — the
degenerate (single-bucket) case of a windowed/Pippenger MSM — so the
kernel is a masked Jacobian tree reduction.

Two registered backends, mirroring crypto/batch's registry idiom
(select with TM_TPU_BLS_MSM or set_default_msm_backend):

  "python" — sequential Jacobian accumulation (curve.g1_sum); the
             reference implementation and the default.
  "jax"    — vectorized tree reduction: field elements are (26, B)
             int64 arrays of 15-bit limbs (the jaxed25519 layout scaled
             to 381 bits), one jitted level-step reused across all
             log2(n) levels via roll-based pairing, so the kernel
             compiles once per batch shape. Guarded: any jax failure
             falls back to the python path (the two are property-tested
             identical in tests/test_bls.py).

The kernels consume AFFINE point tuples ((x, y) ints, None = infinity)
and return a Jacobian curve.G1Point.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Dict, List, Optional, Tuple

from .curve import G1Point, g1_add, g1_sum
from .fields import P

LOG = logging.getLogger("crypto.bls.msm")

AffinePoint = Optional[Tuple[int, int]]

_registry: Dict[str, Callable[[List[AffinePoint]], G1Point]] = {}
_default_lock = threading.Lock()
_default_name: Optional[str] = None


def register_msm_backend(name: str, fn) -> None:
    _registry[name] = fn


def msm_backends() -> List[str]:
    return sorted(_registry)


def set_default_msm_backend(name: str) -> None:
    global _default_name
    if name not in _registry:
        raise KeyError(f"unknown BLS MSM backend {name!r}; have {msm_backends()}")
    with _default_lock:
        _default_name = name


def default_msm_backend() -> str:
    global _default_name
    with _default_lock:
        if _default_name is None:
            env = os.environ.get("TM_TPU_BLS_MSM")
            _default_name = env if env in _registry else "python"
        return _default_name


def aggregate_points(points: List[AffinePoint], backend: Optional[str] = None) -> G1Point:
    """Sum the given affine G1 points (the bitmap-selected pubkeys)."""
    name = backend or default_msm_backend()
    fn = _registry.get(name)
    if fn is None:
        raise KeyError(f"unknown BLS MSM backend {name!r}; have {msm_backends()}")
    if name != "python":
        try:
            return fn(points)
        except Exception as e:  # noqa: BLE001 - host path is authoritative
            LOG.warning("BLS MSM backend %s failed, python fallback: %s",
                        name, e)
            return _python_sum(points)
    return fn(points)


def _python_sum(points: List[AffinePoint]) -> G1Point:
    return g1_sum([(x, y, 1) for x, y in (p for p in points if p is not None)])


register_msm_backend("python", _python_sum)


# --- jax kernel --------------------------------------------------------
#
# Field layout: 26 limbs of 15 bits, limb-major (26, B) int64. A full
# 381x381 product is a 51-coefficient convolution (partial products
# <= 2^30, at most 26 summed -> < 2^35, safely inside int64); the high
# 25 coefficients fold back through a precomputed (25, 26) table of
# 2^(15*(i+26)) mod p in limb form, then parallel carry rounds restore
# the 15-bit invariant. Comparisons (the add formula's doubling /
# negation cases) are exact because operands are frozen (canonical,
# < p) after every operation.

_NLIMB = 26
_BITS = 15
_MASK = (1 << _BITS) - 1


def _int_to_limbs_py(v: int) -> List[int]:
    return [(v >> (_BITS * i)) & _MASK for i in range(_NLIMB)]


def _limbs_to_int_py(ls) -> int:
    return sum(int(l) << (_BITS * i) for i, l in enumerate(ls))


def _build_jax():
    import jax
    import jax.numpy as jnp
    import numpy as np

    # FOLD[i] = limbs(2^(15*(26+i)) mod p): positional fold table for
    # conv coefficients 26..51 (numpy so the x64 trace keeps int64)
    FOLD = np.array(
        [_int_to_limbs_py(pow(2, _BITS * (i + _NLIMB), P))
         for i in range(_NLIMB)], dtype=np.int64)
    P_LIMBS = np.array(_int_to_limbs_py(P), dtype=np.int64)
    # Barrett-lite estimator: qhat = ((V >> 380) * C20) >> 20 with
    # C20 = floor(2^400 / p) underestimates floor(V/p) by at most a few,
    # so one multiply-subtract leaves V' < 4p for the conditional
    # subtract freeze
    C20 = (1 << 400) // P

    def _carry_rounds(v, rounds):
        """Parallel carry rounds over 26 limbs; the (small) top carry
        folds back through FOLD as a two-limb decomposition so limb
        magnitudes strictly shrink toward canonical."""
        for _ in range(rounds):
            r = v >> _BITS
            v = (v & _MASK).at[1:].add(r[:-1])
            t = r[-1]
            t0 = t & _MASK
            t1 = t >> _BITS
            v = v + t0 * FOLD[0][:, None] + t1 * FOLD[1][:, None]
        return v

    def _reduce_full(v):
        """Canonicalize limbs (possibly up to ~2^40 each) to the exact
        residue: carries -> Barrett-lite quotient subtract -> freeze."""
        v = _carry_rounds(v, 6)
        # limbs now canonical up to +-1 ulp (value < 2^390 + eps);
        # estimate the quotient from the top 11 bits. qhat can be off by
        # a couple in either direction, so add one p back before the
        # subtract and let the freeze pass absorb the slack (< 5p).
        hi = v[-1] >> 5  # V >> 380 (lower limbs contribute < 2^380)
        qhat = (hi * C20) >> 20
        v = v + P_LIMBS[:, None] - qhat[None, :] * P_LIMBS[:, None]
        # signed carries (arithmetic shift handles borrows)
        for _ in range(3):
            r = v >> _BITS
            v = (v & _MASK).at[1:].add(r[:-1])
        return _freeze(v)

    def _modmul(a, b):
        # a, b canonical (26, B) -> canonical (26, B)
        prod = jnp.zeros((2 * _NLIMB - 1,) + a.shape[1:], dtype=jnp.int64)
        for i in range(_NLIMB):
            prod = prod.at[i : i + _NLIMB].add(a[i][None, :] * b)
        # one positional carry round so fold inputs are ~2^20
        r = prod >> _BITS
        m = prod & _MASK
        pad = [(0, 0)] * (prod.ndim - 1)
        ext = jnp.pad(m, [(0, 1)] + pad) + jnp.pad(r, [(1, 0)] + pad)
        v = ext[:_NLIMB] + jnp.tensordot(
            jnp.asarray(FOLD), ext[_NLIMB:], axes=([0], [0]))
        return _reduce_full(v)

    # borrow-safe 2p: value == 2p, every limb >= MASK, so (a + B2P - b)
    # has non-negative limbs for canonical a, b (no borrow chains)
    _b2p = [2 * int(x) for x in P_LIMBS]
    for _i in range(_NLIMB - 1):
        _b2p[_i] += 1 << _BITS
        _b2p[_i + 1] -= 1
    B2P = np.array(_b2p, dtype=np.int64)

    def _modsub(a, b):
        v = a + B2P[:, None] - b
        v = _carry_rounds(v, 2)
        return _freeze(v)

    def _modadd(a, b):
        v = _carry_rounds(a + b, 2)
        return _freeze(v)

    def _geq_p(v):
        # lexicographic v >= p over limbs (both canonical-ish, < 2^15)
        gt = v > P_LIMBS[:, None]
        eq = v == P_LIMBS[:, None]
        res = jnp.ones(v.shape[1:], dtype=bool)  # running "equal so far"
        out = jnp.zeros(v.shape[1:], dtype=bool)
        for i in range(_NLIMB - 1, -1, -1):
            out = out | (res & gt[i])
            res = res & eq[i]
        return out | res  # equal counts as >=

    def _sub_p(v):
        borrow = jnp.zeros(v.shape[1:], dtype=jnp.int64)
        out = jnp.zeros_like(v)
        for i in range(_NLIMB):
            d = v[i] - P_LIMBS[i] - borrow
            borrow = (d < 0).astype(jnp.int64)
            out = out.at[i].set(d + borrow * (1 << _BITS))
        return out

    def _freeze(v):
        # conditional subtracts; callers guarantee v < 5p
        for _ in range(4):
            m = _geq_p(v)
            v = jnp.where(m[None, :], _sub_p(v), v)
        return v

    def _is_zero(v):
        return jnp.all(v == 0, axis=0)

    def _pt_add(ax, ay, az, bx, by, bz):
        """Full Jacobian add with infinity (z == 0), doubling, and
        negation masks, vectorized over the batch axis."""
        a_inf = _is_zero(az)
        b_inf = _is_zero(bz)
        z1z1 = _modmul(az, az)
        z2z2 = _modmul(bz, bz)
        u1 = _modmul(ax, z2z2)
        u2 = _modmul(bx, z1z1)
        s1 = _modmul(_modmul(ay, bz), z2z2)
        s2 = _modmul(_modmul(by, az), z1z1)
        x_eq = _is_zero(_modsub(u1, u2))
        y_eq = _is_zero(_modsub(s1, s2))
        # generic add
        h = _modsub(u2, u1)
        two_h = _modadd(h, h)
        i = _modmul(two_h, two_h)
        j = _modmul(h, i)
        rr = _modsub(s2, s1)
        rr = _modadd(rr, rr)
        v = _modmul(u1, i)
        x3 = _modsub(_modsub(_modmul(rr, rr), j), _modadd(v, v))
        s1j = _modmul(s1, j)
        y3 = _modsub(_modmul(rr, _modsub(v, x3)), _modadd(s1j, s1j))
        zz = _modsub(_modsub(_modmul(_modadd(az, bz), _modadd(az, bz)), z1z1), z2z2)
        z3 = _modmul(zz, h)
        # doubling branch (a == b)
        da = _modmul(ax, ax)
        db = _modmul(ay, ay)
        dc = _modmul(db, db)
        t = _modadd(ax, db)
        d = _modsub(_modsub(_modmul(t, t), da), dc)
        d = _modadd(d, d)
        e = _modadd(_modadd(da, da), da)
        f = _modmul(e, e)
        dx3 = _modsub(f, _modadd(d, d))
        c8 = _modadd(_modadd(dc, dc), _modadd(dc, dc))
        c8 = _modadd(c8, c8)
        dy3 = _modsub(_modmul(e, _modsub(d, dx3)), c8)
        dz3 = _modmul(_modadd(ay, ay), az)
        dbl_m = (x_eq & y_eq)[None, :]
        x3 = jnp.where(dbl_m, dx3, x3)
        y3 = jnp.where(dbl_m, dy3, y3)
        z3 = jnp.where(dbl_m, dz3, z3)
        # negation (x equal, y differing) -> infinity (z = 0)
        inf_m = (x_eq & ~y_eq)[None, :]
        z3 = jnp.where(inf_m, jnp.zeros_like(z3), z3)
        # infinity absorbers
        x3 = jnp.where(a_inf[None, :], bx, jnp.where(b_inf[None, :], ax, x3))
        y3 = jnp.where(a_inf[None, :], by, jnp.where(b_inf[None, :], ay, y3))
        z3 = jnp.where(a_inf[None, :], bz, jnp.where(b_inf[None, :], az, z3))
        return x3, y3, z3

    def _level_impl(xs, ys, zs, shift):
        """One tree level: lane i (i % (2*shift) == 0) absorbs lane
        i+shift; other lanes are zeroed to infinity."""
        n = xs.shape[1]
        bx = jnp.roll(xs, -shift, axis=1)
        by = jnp.roll(ys, -shift, axis=1)
        bz = jnp.roll(zs, -shift, axis=1)
        x3, y3, z3 = _pt_add(xs, ys, zs, bx, by, bz)
        lane = jnp.arange(n)
        keep = (lane % (2 * shift)) == 0
        x3 = jnp.where(keep[None, :], x3, jnp.zeros_like(x3))
        y3 = jnp.where(keep[None, :], y3, jnp.zeros_like(y3))
        z3 = jnp.where(keep[None, :], z3, jnp.zeros_like(z3))
        return x3, y3, z3

    # compile-once: the level step costs ~minutes of XLA compile (the
    # Jacobian add formula is a huge graph), which is why the backend
    # is opt-in — the AOT store turns that into once per MACHINE.
    # `shift` is a runtime scalar, so ONE executable per batch width
    # serves every tree level.
    from .. import kernel_cache

    _level = kernel_cache.aot_wrap("bls_msm_level", (),
                                   jax.jit(_level_impl))

    def jax_sum(points: List[AffinePoint]) -> G1Point:
        live = [p for p in points if p is not None]
        if not live:
            return None
        if len(live) == 1:
            return (live[0][0], live[0][1], 1)
        n = 1
        while n < len(live):
            n <<= 1
        xs = np.zeros((_NLIMB, n), dtype=np.int64)
        ys = np.zeros((_NLIMB, n), dtype=np.int64)
        zs = np.zeros((_NLIMB, n), dtype=np.int64)
        for i, (x, y) in enumerate(live):
            xs[:, i] = _int_to_limbs_py(x)
            ys[:, i] = _int_to_limbs_py(y)
            zs[0, i] = 1
        # int64 limbs need the x64 trace context; scoping it here
        # (instead of flipping jax_enable_x64 globally) keeps the
        # jaxed25519 kernels' int32 world untouched
        with jax.enable_x64(True):
            jx, jy, jz = jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(zs)
            shift = 1
            while shift < n:
                jx, jy, jz = _level(jx, jy, jz, shift)
                shift <<= 1
            out = (np.asarray(jx[:, 0]), np.asarray(jy[:, 0]),
                   np.asarray(jz[:, 0]))
        X = _limbs_to_int_py(out[0])
        Y = _limbs_to_int_py(out[1])
        Z = _limbs_to_int_py(out[2])
        if Z == 0:
            return None
        return (X, Y, Z)

    return jax_sum


_jax_fn = None
_jax_lock = threading.Lock()


def _jax_sum(points: List[AffinePoint]) -> G1Point:
    global _jax_fn
    with _jax_lock:
        if _jax_fn is None:
            _jax_fn = _build_jax()
        fn = _jax_fn
    return fn(points)


def _register_jax_backend() -> None:
    """Deferred like crypto/batch: importing this module never forces a
    jax init; the kernel builds on first use."""
    try:
        import jax  # noqa: F401
    except ImportError:
        LOG.info("jax unavailable; BLS MSM runs on the python backend")
        return
    register_msm_backend("jax", _jax_sum)


_register_jax_backend()
