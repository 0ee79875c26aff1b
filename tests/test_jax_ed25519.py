"""JAX Ed25519 engine vs pure-python reference vs OpenSSL."""

import math
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

    fn = V._jitted_packed(3, 80, 16, 1)
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


def test_device_batch_records_five_spans_once(batch):
    """One verify_batch call is five spans, once each and in order,
    every one with the batch's n and shape: the benchmark's readers and
    README "Spans" read them by these names and args. A malformed row
    rides along (the host mask must stay aligned with the device's).
    The shape is the one test_jax_verify_batch compiled."""
    from tendermint_tpu.crypto.jaxed25519.verify import verify_batch
    from tendermint_tpu.libs import tracing

    msgs = [m for m, _, _, _ in batch]
    sigs = [s for _, s, _, _ in batch]
    pks = [p for _, _, p, _ in batch]
    want = [e for _, _, _, e in batch]
    sigs[1], want[1] = sigs[1][:10], False
    tracer = tracing.get_tracer()
    was_on = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        got = verify_batch(msgs, sigs, pks, devices=1)
        spans = [e for e in tracer.events() if e.name.startswith("verify.")]
    finally:
        if not was_on:
            tracer.disable()
    assert got == want
    spans.sort(key=lambda e: e.start_ns)
    assert [e.name for e in spans] == [
        "verify.pack", "verify.h2d", "verify.launch", "verify.wait",
        "verify.unpack"]
    for e in spans:
        assert e.cat == "crypto"
        assert e.args == {"n": len(batch), "bucket": 16, "nb": 3,
                          "mrows": 80, "ndev": 1}, e.name
    for a, b in zip(spans, spans[1:]):
        assert a.end_ns <= b.start_ns


# --- the host side of a device batch: layout, buckets, input checks --------


def _le_words(b: bytes) -> list:
    return [int.from_bytes(b[k:k + 4], "little", signed=True)
            for k in range(0, len(b), 4)]


def _pack_one_by_one(msgs, sigs, pks, ndev):
    """The packed buffer written out item by item, as
    verify._verify_packed_core's docstring gives the layout: what
    pack_buffer, however it is vectorised, has to produce."""
    n = len(msgs)
    maxlen = max(len(m) for m in msgs)
    # SHA-512 blocks of R || A || M, its 0x80 and its 16-byte length
    nb = math.ceil((64 + maxlen + 1 + 16) / 128)
    # message rows: 4 bytes a row, in steps of 16 rows, at least 16
    mrows = max(16, math.ceil(math.ceil(maxlen / 4) / 16) * 16)
    if n <= 8:
        bpad = 8
    elif n <= 512:
        bpad = 1 << (n - 1).bit_length()  # the next power of two
    else:
        bpad = math.ceil(n / 512) * 512
    bpad = math.ceil(bpad / ndev) * ndev
    buf = np.zeros((25 + mrows, bpad), dtype=np.int32)
    for i, (m, s, p) in enumerate(zip(msgs, sigs, pks)):
        buf[0, i] = len(m)
        buf[1:17, i] = _le_words(s)
        buf[17:25, i] = _le_words(p)
        words = _le_words(m + b"\x00" * (mrows * 4 - len(m)))
        buf[25:, i] = words
    return buf, (nb, mrows, bpad)


@pytest.mark.parametrize("lens,ndev", [
    ([0], 1),
    ([1], 1),
    ([63] * 3, 1),
    ([64] * 3, 1),
    ([65] * 3, 1),
    ([110] * 5, 1),
    ([128] * 9, 1),
    ([250] * 2, 1),
    ([0, 1, 63, 64, 65, 110, 128, 250], 1),
    ([110, 0, 65, 128, 1, 250, 64, 63, 110], 2),
    ([97] * 5, 3),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else f"ndev{v}")
def test_pack_buffer_layout(lens, ndev):
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    rng = np.random.default_rng(len(lens) * 1000 + sum(lens))
    msgs = [rng.bytes(k) for k in lens]
    sigs = [rng.bytes(64) for _ in lens]
    pks = [rng.bytes(32) for _ in lens]
    sig_arr = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    pk_arr = np.frombuffer(b"".join(pks), np.uint8).reshape(-1, 32)
    want, want_shape = _pack_one_by_one(msgs, sigs, pks, ndev)
    buf, nb, mrows, bpad = V.pack_buffer(msgs, sig_arr, pk_arr, ndev)
    assert (nb, mrows, bpad) == want_shape
    assert buf.dtype == np.int32 and buf.shape == (V.ROWS_AUX + mrows, bpad)
    np.testing.assert_array_equal(buf, want)


@pytest.mark.parametrize("n,bucket", [
    (1, 8), (8, 8), (9, 16), (512, 512), (513, 1024), (10000, 10240)])
def test_bucket_boundaries(n, bucket):
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    assert V._bucket(n) == bucket


@pytest.mark.parametrize("fault", [
    "short signature", "long key", "S >= L", "all well formed"])
def test_pack_well_formed_rows(fault):
    """Malformed rows are zeroed and masked, the arrays keep their
    shape; a non-canonical S is masked on the host and left in place."""
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    items = []
    for i in range(4):
        sk, pk = _keypair()
        msg = b"row-%d" % i
        items.append([msg, sk.sign(msg), pk])
    bad = 2
    if fault == "short signature":
        items[bad][1] = items[bad][1][:63]
    elif fault == "long key":
        items[bad][2] = items[bad][2] + b"\x00"
    elif fault == "S >= L":
        items[bad][1] = items[bad][1][:32] + ref.L.to_bytes(32, "little")
    msgs, sigs, pks = (list(c) for c in zip(*items))
    sig_arr, pk_arr, ok = V._pack_well_formed(msgs, sigs, pks)
    assert sig_arr.shape == (4, 64) and sig_arr.dtype == np.uint8
    assert pk_arr.shape == (4, 32) and pk_arr.dtype == np.uint8
    assert ok.dtype == bool
    assert ok.tolist() == [fault == "all well formed" or i != bad
                           for i in range(4)]
    for i in range(4):
        if i == bad and fault in ("short signature", "long key"):
            assert not sig_arr[i].any() and not pk_arr[i].any()
        else:
            assert sig_arr[i].tobytes() == sigs[i]
            assert pk_arr[i].tobytes() == pks[i]


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
