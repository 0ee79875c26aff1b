"""The batched Ed25519 verify kernel — the north-star TPU path.

Replaces the reference's serial verify loop (types/validator_set.go:345-371
→ crypto/ed25519/ed25519.go:151-157) with one jitted device program per
(batch-bucket, block-count) shape:

    SHA-512(R||A||M) → reduce mod L → decompress A → [S]B (fixed-base
    windowed) + [k](-A) (double-and-add) → canonical encode → compare R.

Per-item validity masks come back — mixed valid/invalid batches are
first-class (no all-or-nothing batch equation). With more than one device
visible a batch of 512 lanes or more (batch_devices) shards across a 1-D
"dp" mesh via shard_map; signatures are the batch dimension, so the commit
of a 10k-validator set simply spreads over the host's chips with no
cross-device traffic: each chip's part of the mask is read back.
"""

from __future__ import annotations

import os
import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ...libs import tracing
from .. import kernel_cache
from ..batch import BatchVerifier, get_sig_cache, note_device_batch
from . import curve, pack, pallas_kernels, scalar, sha512

# compile-once layer (crypto/kernel_cache): persistent XLA compilation
# cache + AOT-serialized executables under JAX_COMPILATION_CACHE_DIR
# (else the fixed in-checkout default), so kernels compile once per
# machine instead of per process.
kernel_cache.ensure_configured()


def on_tpu() -> bool:
    """True when the default backend is TPU hardware — gates the fused
    pallas kernels, which only lower via Mosaic (a GPU backend must keep
    the XLA path). Initialises the backend; a backend that cannot come
    up raises here instead of reading as "not a TPU"."""
    return jax.default_backend() == "tpu"


def _verify_core(msg_words, nblocks, a_y, a_sign, r_y, r_sign, s_limbs,
                 use_pallas: bool = False, pallas_interpret: bool = False):
    # the scopes put each stage's name into its operations' names, so a
    # device trace says which stage a `while` loop or a fusion belongs to
    with jax.named_scope("sha512"):
        digest = sha512.sha512_batch(msg_words, nblocks)
        k = scalar.reduce_512(sha512.digest_to_scalar_limbs(digest))
    if use_pallas:
        # fused VMEM-resident tail: decompress -> Straus -> encode -> compare
        # (one Mosaic kernel, no HBM intermediates — see PROFILE.md);
        # interpret=True runs the SAME kernel path on a CPU mesh (dryrun)
        return pallas_kernels.verify_tail(a_y, a_sign, r_y, r_sign, s_limbs, k,
                                          interpret=pallas_interpret)
    with jax.named_scope("decompress"):
        a_pt, ok_a = curve.decompress(a_y, a_sign)
    with jax.named_scope("scalar_mul"):
        # R' = [S]B + [k](−A) in ONE Straus chain (shared doublings)
        r_prime = curve.straus_mul_sub(s_limbs, k, curve.negate(a_pt))
    with jax.named_scope("compare"):
        y, parity = curve.encode(r_prime)
        eq = jnp.all(y == r_y, axis=0) & (parity == r_sign)
        return ok_a & eq


def _bytes_from_rows(rows_i32, nbytes: int):
    """(ceil(nbytes/4), B) int32 of 4 packed LE bytes -> (nbytes, B) int32."""
    parts = [(rows_i32 >> (8 * k)) & 0xFF for k in range(4)]
    stacked = jnp.stack(parts, axis=1)  # (rows, 4, B)
    return stacked.reshape(-1, rows_i32.shape[-1])[:nbytes]


def _limbs_from_bytes(bts):
    """(32, B) int32 LE bytes -> (20, B) 13-bit limbs (device twin of
    pack.bytes_to_limbs_batch)."""
    bdim = bts.shape[-1]
    zero = jnp.zeros((1, bdim), dtype=jnp.int32)
    rows = []
    for i in range(pack.NLIMB):
        bit = pack.BITS * i
        s, o = bit // 8, bit % 8
        v = bts[s] >> o
        if s + 1 < 32:
            v = v | (bts[s + 1] << (8 - o))
        if s + 2 < 32 and 16 - o < pack.BITS:
            v = v | (bts[s + 2] << (16 - o))
        rows.append(v & pack.MASK)
    return jnp.stack(rows, axis=0)


ROWS_AUX = 25  # mlen row + 16 sig rows + 8 pk rows


def _verify_packed_core(buf, nb: int, mrows: int, use_pallas: bool = False,
                        pallas_interpret: bool = False):
    """Unpack ONE (25 + mrows, B) int32 buffer into the _verify_core
    inputs. One host→device transfer; everything rides byte-dense
    (signature/pubkey/message bytes 4-per-int32) and the SHA-512 block
    construction — R||A prefix placement, 0x80 terminator, big-endian bit
    length — happens ON DEVICE from the raw bytes. vs shipping padded
    blocks + limbs this cuts the 10k-sig transfer 5.2MB → ~2.2MB.

    Layout: row 0 = message length (bytes); rows 1:17 = signature;
    rows 17:25 = pubkey; rows 25: = message bytes."""
    bdim = buf.shape[-1]
    mlen = buf[0]
    sig_bytes = _bytes_from_rows(buf[1:17], 64)
    pk_bytes = _bytes_from_rows(buf[17:25], 32)
    msg_bytes = _bytes_from_rows(buf[25:], mrows * 4)

    # SHA-512 message region (after the 64-byte R||A prefix): mask tail
    # garbage, place 0x80 at mlen and the BE bit-length at inb*128-8
    region_len = nb * 128 - 64
    if mrows * 4 < region_len:
        msg_bytes = jnp.concatenate(
            [msg_bytes, jnp.zeros((region_len - mrows * 4, bdim), jnp.int32)],
            axis=0,
        )
    j = jnp.arange(region_len, dtype=jnp.int32)[:, None]
    inb = (mlen + 64 + 17 + 127) // 128  # per-item padded block count
    region = jnp.where(j < mlen[None, :], msg_bytes, 0)
    region = region + jnp.where(j == mlen[None, :], 0x80, 0)
    bitlen = (mlen + 64) * 8
    base = inb * 128 - 72  # region-relative start of the 8-byte BE length
    for t in range(8):
        v = (bitlen >> (8 * (7 - t))) & 0xFF
        region = region + jnp.where(j == (base + t)[None, :], v[None, :], 0)

    full = jnp.concatenate([sig_bytes[:32], pk_bytes, region], axis=0)
    f4 = full.astype(jnp.uint32).reshape(nb * 32, 4, bdim)
    words32 = (f4[:, 0] << 24) | (f4[:, 1] << 16) | (f4[:, 2] << 8) | f4[:, 3]
    words = words32.reshape(nb, 16, 2, bdim)

    r_y = _limbs_from_bytes(sig_bytes[:32])
    r_sign = (r_y[19] >> 8) & 1
    r_y = r_y.at[19].set(r_y[19] & 0xFF)
    s_limbs = _limbs_from_bytes(sig_bytes[32:64])
    a_y = _limbs_from_bytes(pk_bytes)
    a_sign = (a_y[19] >> 8) & 1
    a_y = a_y.at[19].set(a_y[19] & 0xFF)
    return _verify_core(words, inb, a_y, a_sign, r_y, r_sign, s_limbs,
                        use_pallas=use_pallas,
                        pallas_interpret=pallas_interpret)


def _pallas_flags(force_pallas=None) -> tuple:
    """(use_pallas, pallas_interpret) for the current backend.

    Default: the fused Mosaic kernel on TPU, the XLA kernel elsewhere.
    force_pallas=True additionally enables INTERPRET mode on non-TPU
    backends so a CPU mesh exercises the exact pallas-in-shard_map code
    path (dryrun_multichip does this); it is far too slow for general
    CPU testing, hence opt-in."""
    if force_pallas is None:
        return on_tpu(), False
    if not force_pallas:
        return False, False
    return True, not on_tpu()


def _shard_map(fn, mesh, in_specs, out_specs):
    # pallas_call out_shapes don't declare vma; skip the check so the
    # fused kernel can live inside the shard_map body
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@lru_cache(maxsize=8)
def _dp_mesh(ndev: int):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:ndev]), ("dp",))


def _put(buf, ndev: int):
    """Host array -> device, batch-last sharded over the 'dp' mesh when
    ndev > 1: every device receives its own shard instead of the whole
    batch landing on device 0 for the jit call to redistribute."""
    if ndev == 1:
        return jax.device_put(buf)
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(*([None] * (buf.ndim - 1) + ["dp"]))
    return jax.device_put(buf, NamedSharding(_dp_mesh(ndev), spec))


def _jitted_packed(nb: int, mrows: int, bpad: int, ndev: int,
                   force_pallas=None):
    # the flags are resolved before the cache: they are part of its key
    use_pallas, interp = _pallas_flags(force_pallas)
    return _jitted_packed_impl(nb, mrows, bpad, ndev, use_pallas, interp)


@lru_cache(maxsize=32)
def _jitted_packed_impl(nb: int, mrows: int, bpad: int, ndev: int,
                        use_pallas: bool, interp: bool):
    def body(buf):
        return _verify_packed_core(buf, nb=nb, mrows=mrows,
                                   use_pallas=use_pallas,
                                   pallas_interpret=interp)

    if ndev > 1:
        from jax.sharding import PartitionSpec as P

        # GSPMD cannot auto-partition a Mosaic custom call, but shard_map
        # hands the body per-device blocks — exactly the shape the pallas
        # kernel wants — so the fused kernel runs per chip with no
        # cross-device traffic except the output concat
        body = _shard_map(body, _dp_mesh(ndev), in_specs=(P(None, "dp"),),
                          out_specs=P("dp"))

    # jitted from a named function, not a partial: the device trace then
    # shows the program as jit_ed25519_verify_packed, not jit__unknown
    def ed25519_verify_packed(buf):
        return body(buf)

    fn = jax.jit(ed25519_verify_packed)
    if interp:
        # pallas interpret mode is a CPU-mesh dryrun path; its artifacts
        # are worthless cross-process and its lowering is the slow part
        return fn
    return kernel_cache.aot_wrap(
        "ed25519_packed", (nb, mrows, bpad, ndev, use_pallas), fn, ndev=ndev)


@lru_cache(maxsize=1)
def _ref_L() -> int:
    from . import ref

    return ref.L


def _pack_le_rows(arr: np.ndarray) -> np.ndarray:
    """(B, nbytes) uint8 -> (nbytes//4, B) int32, 4 LE bytes per word."""
    b, nbytes = arr.shape
    w = arr.reshape(b, nbytes // 4, 4).astype(np.uint32)
    packed = w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24)
    return np.ascontiguousarray(packed.T).view(np.int32)


def pack_buffer(msgs, sig_arr: np.ndarray, pk_arr: np.ndarray, ndev: int = 1):
    """Build the single packed h2d buffer (see _verify_packed_core layout).
    Returns (buf (ROWS_AUX+mrows, bpad) int32, nb, mrows, bpad). The ONLY
    place the layout and its shape are produced — verify_batch and the
    profiling code both take them from here."""
    n = len(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n)
    maxlen = int(lens.max()) if n else 0
    nb = (64 + maxlen + 17 + 127) // 128
    # mrows bucketed to 64-byte granularity: vote sign-bytes from 65 to
    # 128 bytes (any realistic chain id) share the mrows=32 compile that
    # warmup() pre-builds — a fresh mrows key would stall the live path
    mrows = max(16, ((maxlen + 3) // 4 + 15) // 16 * 16)

    bpad = _padded_bucket(n, ndev)

    msg_mat = np.zeros((n, mrows * 4), dtype=np.uint8)
    pack.fill_msg_bytes(msg_mat, [bytes(m) for m in msgs], lens)

    buf = np.zeros((ROWS_AUX + mrows, bpad), dtype=np.int32)
    buf[0, :n] = lens
    buf[1:17, :n] = _pack_le_rows(sig_arr)
    buf[17:25, :n] = _pack_le_rows(pk_arr)
    buf[25:, :n] = _pack_le_rows(msg_mat)
    return buf, nb, mrows, bpad


def _bucket(n: int) -> int:
    if n <= 8:
        return 8
    if n <= 512:
        return 1 << (n - 1).bit_length()
    return (n + 511) // 512 * 512


# A batch under this many lanes stays on one chip. One chip against
# four of a v5e host, the call's wall by bucket (benchmark/tools/
# chips_table.py, PERF.md §6, PR 33): 2.63 against 3.18 ms at 8 lanes and
# 2.66 against 3.28 at 64 (four shard puts and four launches for 0.84 ms
# of kernel), then 4.76 against 3.78 at 512, 12.6 against 7.1 at 2,048,
# 53.2 against 23.7 at 10,240.
MULTI_CHIP_MIN_LANES = 512


def batch_devices(n: int) -> int:
    """How many of the visible chips a batch of n signatures is cut
    over: all of them from MULTI_CHIP_MIN_LANES lanes up, else one. The
    one rule for verify_batch, the funnel's span and warmup(); on a
    one-chip host it is 1 whatever n."""
    ndev = len(jax.devices())
    return ndev if ndev > 1 and _bucket(n) >= MULTI_CHIP_MIN_LANES else 1


def _padded_bucket(n: int, ndev: int) -> int:
    """The bucket of n lanes, padded to a multiple of the chips that
    share it (every chip gets the same number of lanes)."""
    bpad = max(_bucket(n), ndev)
    return (bpad + ndev - 1) // ndev * ndev


def _pack_well_formed(msgs, sigs, pks):
    """Shared validation+packing front end: -> (sig_arr (n,64), pk_arr
    (n,32), ok_host (n,) bool) where ok_host = well-formed lengths AND
    canonical S (s < L, a pure host-side byte check — no transfer).
    Malformed rows are zeroed so downstream vector code stays shape-stable."""
    n = len(msgs)
    well_formed = np.array(
        [len(s) == 64 and len(p) == 32 for s, p in zip(sigs, pks)], dtype=bool
    )
    if well_formed.all():
        sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
        pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32)
    else:
        sig_arr = np.zeros((n, 64), dtype=np.uint8)
        pk_arr = np.zeros((n, 32), dtype=np.uint8)
        for i, (s, p) in enumerate(zip(sigs, pks)):
            if well_formed[i]:
                sig_arr[i] = np.frombuffer(s, dtype=np.uint8)
                pk_arr[i] = np.frombuffer(p, dtype=np.uint8)
    s_ok = pack.lt_const_le_batch(sig_arr[:, 32:], _ref_L())
    return sig_arr, pk_arr, s_ok & well_formed


def verify_batch(msgs, sigs, pks, devices: int | None = None):
    """Lists of (msg bytes, 64-byte sig, 32-byte pubkey) -> list[bool].
    `devices` chips share the batch; left out, batch_devices(n) decides.

    The host side of a device batch as five spans under the jax
    backend's crypto.batchVerify (README "Spans"): together they are
    the batch's whole wall. device_put and the dispatch are async, so
    verify.h2d and verify.launch are submissions and verify.wait is
    the blocking read-back of the masks. verify.pack also holds the
    length checks and the kernel lookup."""
    n = len(msgs)
    if n == 0:
        return []
    ndev = devices if devices is not None else batch_devices(n)
    with tracing.span("verify.pack", cat="crypto", n=n) as sp:
        sig_arr, pk_arr, ok_host = _pack_well_formed(msgs, sigs, pks)
        buf, nb, mrows, bpad = pack_buffer(msgs, sig_arr, pk_arr, ndev)
        fn = _jitted_packed(nb, mrows, bpad, ndev)
        shape = {"bucket": bpad, "nb": nb, "mrows": mrows, "ndev": ndev}
        sp.set(**shape)
    note_device_batch(bpad, ndev)
    with tracing.span("verify.h2d", cat="crypto", n=n, **shape):
        dev = _put(buf, ndev)
    with tracing.span("verify.launch", cat="crypto", n=n, **shape):
        mask = fn(dev)
    with tracing.span("verify.wait", cat="crypto", n=n, **shape):
        host = np.asarray(mask)[:n]
    with tracing.span("verify.unpack", cat="crypto", n=n, **shape):
        return [bool(v) for v in host & ok_host]


def make_sharded_commit_step(mesh, force_pallas=None):
    """Sharded verify-commit step over a 1-D 'dp' mesh: per-signature
    validity masks (sharded) plus the 2/3-quorum voting-power tally via a
    psum collective — the device-parallel equivalent of the reference's
    talliedVotingPower loop (types/validator_set.go:358-366). Each device
    runs the fused pallas kernel on its own block when on TPU (shard_map
    hands the body per-device shapes, so the Mosaic call never meets
    GSPMD); force_pallas=True exercises the same path in interpret mode
    on a CPU mesh.

    The tally is exact int32 arithmetic in 2^16 limbs (powers split into
    lo/hi 16-bit halves, summed separately, recombined on host as Python
    ints by the caller via `lo + (hi << 16)`), so the 2/3-quorum decision
    never rounds: batch ≤ 2^15 items with per-item power < 2^31 stays
    exact. The authoritative quorum decision in verify_commit additionally
    re-tallies host-side from the mask with unbounded Python ints."""
    from jax.sharding import PartitionSpec as P

    use_pallas, interp = _pallas_flags(force_pallas)
    dp = lambda n: P(*([None] * (n - 1) + ["dp"]))

    def step(words, nblocks, a_y, a_sign, r_y, r_sign, s_limbs, powers, for_block):
        mask = _verify_core(words, nblocks, a_y, a_sign, r_y, r_sign, s_limbs,
                            use_pallas=use_pallas, pallas_interpret=interp)
        powers = powers.astype(jnp.int32)
        counted = jnp.where(mask & (for_block == 1), powers, 0)
        lo = jnp.sum(counted & 0xFFFF)
        hi = jnp.sum(counted >> 16)
        return mask, jax.lax.psum(lo, "dp"), jax.lax.psum(hi, "dp")

    sharded = _shard_map(
        step,
        mesh,
        in_specs=(dp(4), dp(1), dp(2), dp(1), dp(2), dp(1), dp(2), dp(1), dp(1)),
        out_specs=(dp(1), P(), P()),
    )

    def ed25519_commit_sharded(*args):  # the program's name on the device
        return sharded(*args)

    return jax.jit(ed25519_commit_sharded)


def tallied_power(lo, hi) -> int:
    """Recombine the limb sums from make_sharded_commit_step exactly."""
    return int(lo) + (int(hi) << 16)


def _sharded_commit_fn(ndev: int, force_pallas=None):
    # the flags are resolved before the cache: they are part of its key
    use_pallas, interp = _pallas_flags(force_pallas)
    return _sharded_commit_fn_impl(ndev, use_pallas, interp)


@lru_cache(maxsize=8)
def _sharded_commit_fn_impl(ndev: int, use_pallas: bool, interp: bool):
    # interp is True only when use_pallas is, and make_sharded_commit_step
    # re-derives it identically from the boolean
    step = make_sharded_commit_step(_dp_mesh(ndev), force_pallas=use_pallas)
    if interp:
        return step  # CPU-mesh dryrun: artifacts are worthless cross-run
    return kernel_cache.aot_wrap(
        "ed25519_commit_step", (ndev, use_pallas), step, ndev=ndev)


def sharded_commit_verify(msgs, sigs, pks, powers, for_block,
                          devices: int | None = None):
    """Device-parallel commit verification over every visible device:
    per-signature validity masks (batch sharded on a 1-D 'dp' mesh) plus
    the 2/3-quorum voting-power tally as an on-device psum — the
    multi-chip equivalent of the reference's talliedVotingPower loop
    (types/validator_set.go:345-371).

    powers must each be < 2^31 (the exact lo/hi 16-bit tally bound);
    callers with larger powers must use the host path. Returns
    (mask list[bool], psum_tally int). Host-side canonicity (s < L) and
    well-formedness zero out both the mask and the item's tally weight.
    """
    n = len(msgs)
    ndev = devices if devices is not None else len(jax.devices())
    if n == 0:
        return [], 0
    sig_arr, pk_arr, ok_host = _pack_well_formed(msgs, sigs, pks)
    r_y, r_sign, s_limbs, _ = pack.split_signatures(sig_arr)
    a_y, a_sign = pack.split_pubkeys(pk_arr)
    prefixes = np.concatenate([sig_arr[:, :32], pk_arr], axis=1)
    words, nblocks = pack.sha512_pad_batch(prefixes, [bytes(m) for m in msgs])

    bpad = _padded_bucket(n, ndev)

    def padb(a, fill=0):  # pad batch-last axis to bpad
        padw = [(0, 0)] * (a.ndim - 1) + [(0, bpad - n)]
        return np.pad(a, padw, constant_values=fill)

    powers_arr = np.asarray(powers, dtype=np.int64)
    if (powers_arr >= 2**31).any() or (powers_arr < 0).any():
        raise ValueError("sharded tally requires 0 <= power < 2^31")
    counted_powers = np.where(ok_host, powers_arr, 0).astype(np.int32)
    fb = np.asarray(for_block, dtype=np.int32)

    fn = _sharded_commit_fn(ndev)
    # each argument is put already sharded over the mesh: the batch never
    # lands whole on device 0 for the jit call to redistribute
    args = [padb(a) for a in (words, nblocks, a_y, a_sign, r_y, r_sign,
                              s_limbs, counted_powers, fb)]
    mask, lo, hi = fn(*(_put(a, ndev) for a in args))
    out = np.asarray(mask)[:n] & ok_host
    return [bool(v) for v in out], tallied_power(lo, hi)


def warmup(buckets=(8, 16, 64), nb: int = 2, mrows: int = 32,
           devices: int | None = None, calibrate: bool = True):
    """Compile the hot bucket shapes ahead of time. First-use compile of
    a bucket costs tens of seconds on TPU (the compile cache makes later
    processes cheap, but the FIRST node on a machine pays it) — a
    consensus node must not discover that cost inside the live vote
    path, so node startup calls this from a background thread. Vote
    sign-bytes are ~97-128 bytes (nb=2 blocks, mrows=32 message rows);
    bucket sizes cover the adaptive batcher's first escalation steps.
    Any failure — backend init, a shape the compiler refuses — raises.

    With calibrate=True (default; TM_TPU_CALIBRATE=0 disables), also
    measures the compiled-dispatch round trip vs the serial per-sig
    host cost and installs the break-even as the adaptive batch cutoff
    (crypto.batch.set_calibrated_batch_min) — the device is then only
    chosen where it wins on the latency of the hardware actually
    attached. Returns the calibrated cutoff, or None."""
    # the psum commit step is reached only round the funnel's cache
    # (ValidatorSet._run_batch_verify: a synchronous verify_commit with
    # no sig cache installed), and then over every chip; a node that
    # cannot take that way does not compile it (109.5 s cold at 10,240
    # lanes on four chips, PERF.md)
    all_dev = devices if devices is not None else len(jax.devices())
    commit_step = all_dev > 1 and get_sig_cache() is None
    small = None  # (bucket, fn, shape, ndev) of the smallest shape
    for b in buckets:
        ndev = devices if devices is not None else batch_devices(b)
        bpad = _padded_bucket(b, ndev)
        fn = _jitted_packed(nb, mrows, bpad, ndev)
        fn(_put(np.zeros((ROWS_AUX + mrows, bpad), dtype=np.int32), ndev))
        if small is None or bpad < small[0]:
            small = (bpad, fn, (ROWS_AUX + mrows, bpad), ndev)
        if commit_step:
            bpad = _padded_bucket(b, all_dev)
            step = _sharded_commit_fn(all_dev)
            z20 = np.zeros((20, bpad), np.int32)
            zrow = np.zeros((bpad,), np.int32)
            step(*(_put(a, all_dev) for a in (
                np.zeros((nb, 16, 2, bpad), np.uint32), zrow + 1, z20, zrow,
                z20, zrow, z20, zrow, zrow)))
    if (calibrate and small is not None
            and os.environ.get("TM_TPU_CALIBRATE", "1") != "0"):
        return _calibrate_batch_min(*small[1:])
    return None


def _calibrate_batch_min(fn, shape, ndev: int = 1) -> int:
    """Measure break-even between one device dispatch (round trip incl.
    transfer) and serial host verifies; install it via
    crypto.batch.set_calibrated_batch_min. Median-of-3 on the dispatch;
    small margin toward serial so borderline batches stay on the
    predictable host path."""
    from ..batch import set_calibrated_batch_min
    from ..keys import PrivKeyEd25519

    ts = []
    for _ in range(3):
        # put INSIDE the timed region (and fresh per rep): the live
        # path pays the transfer every batch
        t0 = time.perf_counter()
        d = _put(np.zeros(shape, dtype=np.int32), ndev)
        np.asarray(fn(d))
        ts.append(time.perf_counter() - t0)
    dispatch_ms = sorted(ts)[1] * 1e3

    sk = PrivKeyEd25519.gen_from_secret(b"tm-tpu-calibration")
    msg = b"\xa5" * 110
    sig = sk.sign(msg)
    pk = sk.pub_key()
    reps = 32
    t0 = time.perf_counter()
    for _ in range(reps):
        pk.verify_bytes(msg, sig)
    serial_ms = (time.perf_counter() - t0) / reps * 1e3
    n_star = int(min(max(round(dispatch_ms / serial_ms * 1.1), 4), 4096))
    set_calibrated_batch_min(n_star)
    return n_star


class JAXBatchVerifier(BatchVerifier):
    """BatchVerifier backend running the vectorized TPU kernel."""

    BACKEND = "jax"

    def _verify(self):
        if not self._items:
            return []
        if not self._ed25519_only and any(
                len(p) != 32 for _, _, p in self._items):
            # non-Ed25519 triples (e.g. 48-byte BLS pubkeys): this
            # kernel is Ed25519-specific — serial host dispatch instead
            # (the adaptive router has looked already and sends none)
            from ..batch import CPUBatchVerifier

            inner = CPUBatchVerifier()
            inner._items = self._items
            return inner._verify()
        msgs, sigs, pks = zip(*self._items)
        self.ndev = batch_devices(len(msgs))
        return verify_batch(msgs, sigs, pks, devices=self.ndev)
