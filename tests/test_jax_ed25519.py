"""JAX Ed25519 engine vs pure-python reference vs OpenSSL."""

import secrets

import numpy as np
import pytest

from tendermint_tpu.crypto import keys
from tendermint_tpu.crypto.jaxed25519 import pack, ref


def _keypair():
    sk = keys.PrivKeyEd25519.generate()
    return sk, sk.pub_key().bytes()


# --- pure-python reference vs OpenSSL --------------------------------------


def test_ref_verify_matches_openssl():
    for i in range(6):
        sk, pk = _keypair()
        msg = secrets.token_bytes(10 + 37 * i)
        sig = sk.sign(msg)
        assert ref.verify(pk, msg, sig)
        assert not ref.verify(pk, msg + b"x", sig)
        bad = bytes([sig[0] ^ 1]) + sig[1:]
        assert not ref.verify(pk, msg, bad)


def test_ref_rejects_high_s():
    sk, pk = _keypair()
    msg = b"malleability"
    sig = sk.sign(msg)
    s = int.from_bytes(sig[32:], "little")
    s_high = s + ref.L
    if s_high < 2**256:
        forged = sig[:32] + s_high.to_bytes(32, "little")
        assert not ref.verify(pk, msg, forged)


def test_ref_base_point_order():
    b = ref.base_point()
    lb = ref.scalar_mult(ref.L, b)
    assert ref.equal(lb, ref.IDENTITY)


def test_ref_compress_decompress_roundtrip():
    for _ in range(4):
        k = secrets.randbelow(ref.L)
        p = ref.scalar_mult(k, ref.base_point())
        enc = ref.compress(p)
        p2 = ref.decompress(enc)
        assert p2 is not None and ref.equal(p, p2)


def test_base_table_correct():
    table = ref.base_table()
    # spot-check: row i entry j must be niels([j*16^i]B)
    for i, j in [(0, 1), (0, 15), (3, 7), (63, 1), (63, 15)]:
        want = ref.niels(ref.scalar_mult(j * 16**i, ref.base_point()))
        assert table[i][j] == want
    assert table[5][0] == ref.NIELS_IDENTITY


# --- device kernel ---------------------------------------------------------


@pytest.fixture(scope="module")
def batch():
    """Mixed batch: valid sigs, corrupted sig, wrong msg, bad pubkey,
    zero sig, high-S forgery, long msg crossing a SHA block boundary."""
    items = []  # (msg, sig, pk, expect)
    for i in range(4):
        sk, pk = _keypair()
        msg = secrets.token_bytes(40 + i)
        items.append((msg, sk.sign(msg), pk, True))
    sk, pk = _keypair()
    msg = b"corrupted"
    sig = sk.sign(msg)
    items.append((msg, bytes([sig[0] ^ 1]) + sig[1:], pk, False))
    items.append((b"wrong msg", sig, pk, False))
    items.append((b"zero sig", b"\x00" * 64, pk, False))
    items.append((b"bad pk", sig, b"\x01" * 32, False))
    sk, pk = _keypair()
    msg = b"high-s"
    sig = sk.sign(msg)
    s = int.from_bytes(sig[32:], "little")
    if s + ref.L < 2**256:
        items.append((msg, sig[:32] + (s + ref.L).to_bytes(32, "little"), pk, False))
    sk, pk = _keypair()
    long_msg = secrets.token_bytes(300)  # 64+300 spans 3+ blocks
    items.append((long_msg, sk.sign(long_msg), pk, True))
    sk, pk = _keypair()
    items.append((b"", sk.sign(b""), pk, True))  # empty message
    return items


def test_jax_verify_batch(batch):
    from tendermint_tpu.crypto.jaxed25519.verify import verify_batch

    msgs = [m for m, _, _, _ in batch]
    sigs = [s for _, s, _, _ in batch]
    pks = [p for _, _, p, _ in batch]
    want = [e for _, _, _, e in batch]
    got = verify_batch(msgs, sigs, pks, devices=1)
    assert got == want


def test_verify_program_is_named_for_the_profiler(batch):
    """The device trace finds the program and its stages by name: the
    jitted function is `ed25519_verify_packed`, not a partial, and the
    XLA path's operations carry their stage's scope. Lowers the shape
    test_jax_verify_batch ran, so the trace is already cached."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.crypto.jaxed25519 import verify as V

    fn = V._jitted_packed(3, 80, 16, 1, donate=False)
    assert fn.jitted.__name__ == "ed25519_verify_packed"
    text = fn.jitted.lower(jax.ShapeDtypeStruct(
        (V.ROWS_AUX + 80, 16), jnp.int32)).as_text(debug_info=True)
    assert "module @jit_ed25519_verify_packed" in text
    for scope in ("sha512", "decompress", "scalar_mul", "compare"):
        assert f"jit(ed25519_verify_packed)/{scope}/" in text, scope


def test_jax_verify_multidevice(batch):
    import jax

    from tendermint_tpu.crypto.jaxed25519.verify import verify_batch

    ndev = len(jax.devices())
    assert ndev == 8, "conftest should provide 8 virtual devices"
    msgs = [m for m, _, _, _ in batch]
    sigs = [s for _, s, _, _ in batch]
    pks = [p for _, _, p, _ in batch]
    want = [e for _, _, _, e in batch]
    got = verify_batch(msgs, sigs, pks, devices=ndev)
    assert got == want


def test_chunked_composes_with_multidevice(batch, monkeypatch):
    """PR 8: chunking is no longer forced off on multi-device meshes —
    every chunk's bpad stays a multiple of ndev so each shards cleanly,
    and the masks match the single-dispatch mesh path exactly. Same
    padded dims as test_jax_verify_multidevice, so no extra compile."""
    import jax

    from tendermint_tpu.crypto.jaxed25519.verify import verify_batch

    ndev = len(jax.devices())
    msgs = [m for m, _, _, _ in batch]
    sigs = [s for _, s, _, _ in batch]
    pks = [p for _, _, p, _ in batch]
    want = verify_batch(msgs, sigs, pks, devices=ndev)
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNKS", "2")
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNK_MIN", "4")
    got = verify_batch(msgs, sigs, pks, devices=ndev)
    assert got == want
    assert got == [e for _, _, _, e in batch]


@pytest.mark.slow  # pallas interpret mode: ~60s on CPU-only hosts (same
# class as the other slow-marked pallas tests in this file)
def test_pallas_straus_matches_xla():
    """The fused pallas Straus kernel (interpret mode on CPU) must produce
    bit-identical limbs to the XLA curve.straus_mul_sub path."""
    import jax.numpy as jnp

    from tendermint_tpu.crypto.jaxed25519 import curve, pallas_kernels

    rng = np.random.default_rng(7)
    B = 8
    mk = lambda: jnp.asarray(
        np.stack(
            [pack.int_to_limbs(int(rng.integers(0, 2**63)) % ref.L) for _ in range(B)],
            axis=1,
        ).astype(np.int32)
    )
    s_limbs, k_limbs, a_limbs = mk(), mk(), mk()
    neg_a = curve.negate(curve.fixed_base_mul(a_limbs))
    want = curve.straus_mul_sub(s_limbs, k_limbs, neg_a)
    got = pallas_kernels.straus_mul_sub(s_limbs, k_limbs, neg_a, interpret=True)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.slow  # pallas interpret mode: minutes on CPU-only hosts
def test_pallas_verify_tail_matches_xla(batch):
    """The fused verify-tail kernel (decompress -> straus -> encode ->
    compare, production path on TPU) must agree item-for-item with the
    XLA _verify_core on a mixed valid/invalid batch — including failed
    decompress, corrupted sigs and flipped-parity cases."""
    import jax.numpy as jnp

    from tendermint_tpu.crypto.jaxed25519 import pack as P
    from tendermint_tpu.crypto.jaxed25519 import pallas_kernels, scalar, sha512

    n = len(batch)
    sig_arr = np.zeros((n, 64), dtype=np.uint8)
    pk_arr = np.zeros((n, 32), dtype=np.uint8)
    for i, (_, s, p, _) in enumerate(batch):
        if len(s) == 64:
            sig_arr[i] = np.frombuffer(s, dtype=np.uint8)
        if len(p) == 32:
            pk_arr[i] = np.frombuffer(p, dtype=np.uint8)
    r_y, r_sign, s_limbs, _ = P.split_signatures(sig_arr)
    a_y, a_sign = P.split_pubkeys(pk_arr)
    prefixes = np.concatenate([sig_arr[:, :32], pk_arr], axis=1)
    words, nblocks = P.sha512_pad_batch(prefixes, [m for m, _, _, _ in batch])

    digest = sha512.sha512_batch(jnp.asarray(words), jnp.asarray(nblocks))
    k = scalar.reduce_512(sha512.digest_to_scalar_limbs(digest))
    from tendermint_tpu.crypto.jaxed25519.verify import _verify_core

    want = _verify_core(
        jnp.asarray(words), jnp.asarray(nblocks), jnp.asarray(a_y),
        jnp.asarray(a_sign), jnp.asarray(r_y), jnp.asarray(r_sign),
        jnp.asarray(s_limbs),
    )
    got = pallas_kernels.verify_tail(
        jnp.asarray(a_y), jnp.asarray(a_sign), jnp.asarray(r_y),
        jnp.asarray(r_sign), jnp.asarray(s_limbs), k, interpret=True,
    )
    assert np.array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.slow  # fresh XLA compile: minutes on CPU-only hosts
def test_rlc_aggregate_exact_masks():
    """verify_batch_rlc (random-linear-combination aggregate mode) must
    return exactly the same masks as the per-item path on an adversarial
    mixed batch: corrupted sigs, wrong msg, bad pk, malformed, high-S,
    non-canonical R, plus valid items — with group fallback resolving
    failed groups per-item."""
    from tendermint_tpu.crypto.jaxed25519 import ref as R
    from tendermint_tpu.crypto.jaxed25519.verify import (
        verify_batch,
        verify_batch_rlc,
    )

    items = []
    for i in range(12):
        sk, pk = _keypair()
        msg = secrets.token_bytes(60 + i)
        items.append((msg, sk.sign(msg), pk))
    sk, pk = _keypair()
    msg = b"bad"
    sig = sk.sign(msg)
    items.append((msg, bytes([sig[0] ^ 1]) + sig[1:], pk))
    items.append((b"other", sig, pk))
    items.append((msg, sig, b"\x07" * 32))
    items.append((msg, b"\x00" * 30, pk))
    s = int.from_bytes(sig[32:], "little")
    if s + R.L < 2**256:
        items.append((msg, sig[:32] + (s + R.L).to_bytes(32, "little"), pk))
    # non-canonical R: y' = y + p still < 2^255 only if y < 2^255 - p = 19
    # — craft instead by setting R to p (y=p ≡ 0 mod p, non-canonical)
    bad_r = (R.P).to_bytes(32, "little")
    items.append((msg, bad_r + sig[32:], pk))

    msgs = [m for m, _, _ in items]
    sigs = [s_ for _, s_, _ in items]
    pks = [p for _, _, p in items]
    want = verify_batch(msgs, sigs, pks, devices=1)
    got = verify_batch_rlc(msgs, sigs, pks, group=8, devices=1)
    assert got == want
    assert sum(want) == 12  # the 12 honest items


@pytest.mark.slow  # fresh XLA compile: minutes on CPU-only hosts
def test_rlc_all_valid_no_fallback(monkeypatch):
    """On an all-valid batch every group passes the aggregate equation —
    the per-item fallback must not run."""
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    # 18 items lands in the same (nb=2, bpad=32, group=8) jit key as
    # test_rlc_aggregate_exact_masks — one shared compile per session
    items = []
    for i in range(18):
        sk, pk = _keypair()
        msg = secrets.token_bytes(60 + i)
        items.append((msg, sk.sign(msg), pk))
    msgs = [m for m, _, _ in items]
    sigs = [s for _, s, _ in items]
    pks = [p for _, _, p in items]

    def boom(*a, **kw):
        raise AssertionError("fallback ran on an all-valid batch")

    monkeypatch.setattr(V, "verify_batch", boom)
    got = V.verify_batch_rlc(msgs, sigs, pks, group=8, devices=1)
    assert got == [True] * 18


@pytest.mark.slow  # fresh XLA compile: minutes on CPU-only hosts
def test_sharded_commit_verify_masks_and_tally():
    """The psum sharded commit step (production path when >1 device is
    visible) must produce exact per-item masks and an exact on-device
    2/3 tally on mixed-validity, uneven-power batches — the device twin
    of the reference's talliedVotingPower loop
    (types/validator_set.go:358-366)."""
    import jax

    from tendermint_tpu.crypto.jaxed25519 import verify as V

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(11)
    n = 24
    msgs, sigs, pks, valid = [], [], [], []
    for i in range(n):
        sk, pk = _keypair()
        msg = secrets.token_bytes(100)
        sig = sk.sign(msg)
        ok = True
        if i % 5 == 3:
            sig = bytes([sig[3] ^ 0x40]) + sig[1:]  # corrupt
            ok = False
        if i == 7:
            sig = b"\x11" * 30  # malformed length
            ok = False
        msgs.append(msg)
        sigs.append(sig)
        pks.append(pk)
        valid.append(ok)
    powers = [int(rng.integers(1, 1 << 18)) for _ in range(n)]
    for_block = [int(rng.random() < 0.8) for _ in range(n)]

    mask, tally = V.sharded_commit_verify(msgs, sigs, pks, powers, for_block,
                                          devices=8)
    assert mask == valid
    want = sum(p for p, ok, fb in zip(powers, valid, for_block) if ok and fb)
    assert tally == want


def test_verify_commit_routes_through_psum(monkeypatch):
    """ValidatorSet.verify_commit must take the sharded psum path when
    multiple devices are visible and agree with the host tally."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.types import validator_set as vsm
    from tendermint_tpu.types.basic import (
        VOTE_TYPE_PRECOMMIT,
        BlockID,
        PartSetHeader,
        Vote,
    )
    from tendermint_tpu.types.block import Commit

    prev_backend = batch.default_backend_name()
    monkeypatch.setenv("TM_TPU_CRYPTO_BACKEND", "jax")
    batch.set_default_backend("jax")
    try:
        calls = {}
        from tendermint_tpu.crypto.jaxed25519 import verify as V

        orig = V.sharded_commit_verify

        def spy(*a, **kw):
            calls["hit"] = True
            return orig(*a, **kw)

        monkeypatch.setattr(V, "sharded_commit_verify", spy)

        vs, keys = vsm.random_validator_set(6, power=7)
        block_id = BlockID(hash=b"\x01" * 20,
                           parts_header=PartSetHeader(1, b"\x02" * 20))
        precommits = [None] * 6
        for key in keys:
            addr = key.pub_key().address()
            idx, _ = vs.get_by_address(addr)
            vote = Vote(
                validator_address=addr, validator_index=idx, height=5, round=0,
                timestamp=1_700_000_100_000_000_000, type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            vote.signature = key.sign(vote.sign_bytes("psum-chain"))
            precommits[idx] = vote
        commit = Commit(block_id=block_id, precommits=precommits)
        vs.verify_commit("psum-chain", block_id, 5, commit)
        assert calls.get("hit"), "sharded psum path was not taken"
    finally:
        batch.set_default_backend(prev_backend)


def test_jax_backend_registered():
    from tendermint_tpu.crypto.batch import backends

    assert "jax" in backends()


@pytest.mark.slow  # ~90s fresh XLA compile for a 5-sig batch shape; the
# BatchVerifier interface itself is tier-1-covered on the cpu backend
# (test_sig_cache / test_crypto_async) and the jax kernel by
# test_jax_verify_batch
def test_batch_verifier_interface(batch):
    from tendermint_tpu.crypto.batch import new_batch_verifier

    bv = new_batch_verifier("jax")
    for m, s, p, _ in batch[:5]:
        bv.add(m, s, p)
    want = [e for _, _, _, e in batch[:5]]
    assert bv.verify() == want
    assert bv.verify_all() == all(want)


@pytest.mark.slow  # ~160s on CPU-only hosts: compiles BOTH the rlc and
# per-item kernels to pin one documented edge-case divergence
def test_rlc_is_cofactored_torsion_divergence_pinned():
    """verify_batch_rlc uses the COFACTORED group equation (z = 8u).
    This test pins the one documented divergence from the per-item
    (Go byte-compare) path: a signature whose defect is pure 8-torsion
    (R' = R + T, s computed against H(R'||A||M)) fails per-item verify
    but passes the cofactored batch equation deterministically. No batch
    equation can match cofactorless single verification on such inputs
    (Chalkias et al.); anything with a prime-order defect must still
    match the per-item masks exactly (checked here too)."""
    import hashlib

    from tendermint_tpu.crypto.jaxed25519 import ref as R
    from tendermint_tpu.crypto.jaxed25519.verify import (
        verify_batch,
        verify_batch_rlc,
    )

    # find a small-order (torsion) point T != identity: [L]P for an
    # arbitrary decompressable point P kills the prime-order component
    T = None
    for y in range(2, 200):
        pt = R.decompress(y.to_bytes(32, "little"))
        if pt is None:
            continue
        cand = R.scalar_mult(R.L, pt)
        if not R.equal(cand, R.scalar_mult(0, pt)):  # not identity
            T = cand
            break
    assert T is not None, "no torsion point found"
    assert R.equal(R.scalar_mult(8, T), R.scalar_mult(0, T))  # order | 8

    # craft the torsion-defect signature
    a = 0x5DEB3C55C3425C44E57C46E5288AD9D655D7B26A5EA3BE1251A55D6E5BD95A77 % R.L
    A_pt = R.scalar_mult(a, R.base_point())
    A = R.compress(A_pt)
    msg = b"torsion-defect"
    r = 0x1F19E27C0C3B4A85D7F4C2E8A1B35D9F17A3C5E7091B3D5F7A9BCDEF01234567 % R.L
    R0 = R.scalar_mult(r, R.base_point())
    r_bytes = R.compress(R.add(R0, T))
    k = int.from_bytes(hashlib.sha512(r_bytes + A + msg).digest(),
                       "little") % R.L
    s = (r + k * a) % R.L
    sig = r_bytes + s.to_bytes(32, "little")

    # sanity: defect is pure torsion — cofactorless reject
    assert not R.verify(A, msg, sig)

    # group 1 (items 0-7): the torsion sig + 7 valid — its group must
    # PASS the cofactored equation. group 2 (items 8-15): an ordinary
    # prime-order forgery + 7 valid — its group must FAIL and fall back.
    items = [(msg, sig, A, "torsion")]
    for i in range(7):
        sk, pk = _keypair()
        m = secrets.token_bytes(80 + i)
        items.append((m, sk.sign(m), pk, True))
    sk, pk = _keypair()
    m = b"ordinary-forgery"
    bad = sk.sign(m)
    items.append((m, bytes([bad[0] ^ 4]) + bad[1:], pk, False))
    for i in range(7):
        sk, pk = _keypair()
        m = secrets.token_bytes(90 + i)
        items.append((m, sk.sign(m), pk, True))

    msgs = [m for m, _, _, _ in items]
    sigs = [s_ for _, s_, _, _ in items]
    pks = [p for _, _, p, _ in items]

    per_item = verify_batch(msgs, sigs, pks, devices=1)
    assert per_item[0] is False  # Go semantics reject the torsion sig
    assert per_item[1:8] == [True] * 7
    assert per_item[8] is False
    assert per_item[9:] == [True] * 7

    got = verify_batch_rlc(msgs, sigs, pks, group=8, devices=1)
    # the ONLY divergence: the torsion item is accepted (cofactored);
    # every prime-order defect still matches per-item exactly
    assert got[0] is True, "cofactored equation must accept pure torsion"
    assert got[1:] == per_item[1:]


def test_chunked_verify_matches_single_dispatch(monkeypatch):
    """TM_TPU_VERIFY_CHUNKS pipelines transfers against kernels; the
    masks must be identical to the single-dispatch path, including
    chunk-boundary alignment of the host-side canonicity bits."""
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    items = []
    for i in range(24):
        sk, pk = _keypair()
        m = secrets.token_bytes(70 + i)
        s = sk.sign(m)
        if i % 6 == 1:
            s = bytes([s[0] ^ 1]) + s[1:]
        if i == 13:
            s = b"\x00" * 10  # malformed: ok_host must stay aligned
        items.append((m, s, pk))
    msgs = [m for m, _, _ in items]
    sigs = [s for _, s, _ in items]
    pks = [p for _, _, p in items]

    want = V.verify_batch(msgs, sigs, pks, devices=1)
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNKS", "3")
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNK_MIN", "8")
    got = V.verify_batch(msgs, sigs, pks, devices=1)
    assert got == want
    assert sum(want) == 20  # invalid: i in {1,7,13,19} (13 also malformed)


@pytest.mark.slow  # fresh XLA compile: donate=True is its own kernel key
def test_donated_dispatch_matches_undonated(monkeypatch):
    """PR 8 donated-buffer dispatch: with TM_TPU_DONATE=1 the packed
    h2d buffer is donated to the kernel (steady-state device-memory
    reuse); verdicts must be identical to the undonated path, across
    repeat dispatches of the same shape (a donated buffer must never be
    reused by the host after dispatch)."""
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    items = []
    for i in range(12):
        sk, pk = _keypair()
        m = secrets.token_bytes(80)
        s = sk.sign(m)
        if i % 4 == 2:
            s = bytes([s[0] ^ 1]) + s[1:]
        items.append((m, s, pk))
    msgs = [m for m, _, _ in items]
    sigs = [s for _, s, _ in items]
    pks = [p for _, _, p in items]

    monkeypatch.setenv("TM_TPU_DONATE", "0")
    want = V.verify_batch(msgs, sigs, pks, devices=1)
    monkeypatch.setenv("TM_TPU_DONATE", "1")
    for _ in range(3):  # steady state: repeated donated dispatches
        assert V.verify_batch(msgs, sigs, pks, devices=1) == want
    # chunked + donated: ping-pong host buffers over a donated kernel
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNKS", "2")
    monkeypatch.setenv("TM_TPU_VERIFY_CHUNK_MIN", "4")
    assert V.verify_batch(msgs, sigs, pks, devices=1) == want
