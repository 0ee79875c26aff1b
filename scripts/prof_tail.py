"""Split device time of the packed verify pipeline: XLA prelude (unpack,
SHA-512, scalar reduce, window build) vs the fused pallas tail.

Run on real TPU (no platform override). Slope-timed: k back-to-back
dispatches minus one.
"""

import os
import secrets
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import keys
from tendermint_tpu.crypto.jaxed25519 import pack, pallas_kernels, scalar, sha512
from tendermint_tpu.crypto.jaxed25519 import verify as V
from tendermint_tpu.crypto.jaxed25519.curve import _windows_msb_first

N = int(sys.argv[1]) if len(sys.argv) > 1 else 10000

sks = [keys.PrivKeyEd25519.generate() for _ in range(256)]
msgs, sigs, pks = [], [], []
for i in range(N):
    sk = sks[i % len(sks)]
    m = secrets.token_bytes(110)
    msgs.append(m)
    sigs.append(sk.sign(m))
    pks.append(sk.pub_key().bytes())

sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(N, 64)
pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(N, 32)
buf, nb, mrows, bpad = V.pack_buffer(msgs, sig_arr, pk_arr, 1)
dbuf = jax.device_put(buf)


def prelude(buf):
    """Everything _verify_packed_core does before the pallas tail,
    ending in the tail's actual inputs."""
    bdim = buf.shape[-1]
    mlen = buf[0]
    sig_bytes = V._bytes_from_rows(buf[1:17], 64)
    pk_bytes = V._bytes_from_rows(buf[17:25], 32)
    msg_bytes = V._bytes_from_rows(buf[25:], mrows * 4)
    region_len = nb * 128 - 64
    if mrows * 4 < region_len:
        msg_bytes = jnp.concatenate(
            [msg_bytes, jnp.zeros((region_len - mrows * 4, bdim), jnp.int32)], axis=0)
    j = jnp.arange(region_len, dtype=jnp.int32)[:, None]
    inb = (mlen + 64 + 17 + 127) // 128
    region = jnp.where(j < mlen[None, :], msg_bytes, 0)
    region = region + jnp.where(j == mlen[None, :], 0x80, 0)
    bitlen = (mlen + 64) * 8
    base = inb * 128 - 72
    for t in range(8):
        v = (bitlen >> (8 * (7 - t))) & 0xFF
        region = region + jnp.where(j == (base + t)[None, :], v[None, :], 0)
    full = jnp.concatenate([sig_bytes[:32], pk_bytes, region], axis=0)
    f4 = full.astype(jnp.uint32).reshape(nb * 32, 4, bdim)
    words32 = (f4[:, 0] << 24) | (f4[:, 1] << 16) | (f4[:, 2] << 8) | f4[:, 3]
    words = words32.reshape(nb, 16, 2, bdim)
    r_y = V._limbs_from_bytes(sig_bytes[:32])
    r_sign = (r_y[19] >> 8) & 1
    r_y = r_y.at[19].set(r_y[19] & 0xFF)
    s_limbs = V._limbs_from_bytes(sig_bytes[32:64])
    a_y = V._limbs_from_bytes(pk_bytes)
    a_sign = (a_y[19] >> 8) & 1
    a_y = a_y.at[19].set(a_y[19] & 0xFF)
    digest = sha512.sha512_batch(words, inb)
    k = scalar.reduce_512(sha512.digest_to_scalar_limbs(digest))
    s_win = _windows_msb_first(s_limbs, bdim)
    k_win = _windows_msb_first(k, bdim)
    return a_y, a_sign, r_y, r_sign, s_win, k_win


prelude_j = jax.jit(prelude)


def tail(a_y, a_sign, r_y, r_sign, s_win, k_win):
    bdim = a_y.shape[-1]
    btab = jnp.asarray(pallas_kernels._btab_np())
    mask = pallas_kernels._verify_tail_call(bdim, False)(
        a_y, a_sign.reshape(1, bdim), r_y, r_sign.reshape(1, bdim),
        s_win, k_win, btab)
    return mask


tail_j = jax.jit(tail)


def slope(fn, args, k=6):
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: np.asarray(x), out)
    t0 = time.perf_counter()
    out = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(k):
        out = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    tk = time.perf_counter() - t0
    return (tk - t1) / (k - 1) * 1000


pre_ms = slope(prelude_j, (dbuf,))
pre_out = prelude_j(dbuf)
pre_out = tuple(jnp.asarray(x) for x in pre_out)
tail_ms = slope(tail_j, pre_out)
full = V._jitted_packed(nb, mrows, bpad, 1)
full_ms = slope(full, (dbuf,))
print(f"N={N} bpad={bpad}: prelude {pre_ms:.1f} ms, pallas tail {tail_ms:.1f} ms, "
      f"full pipeline {full_ms:.1f} ms")
