"""Key interfaces and the Ed25519 implementation.

Capability parity with the reference's crypto/crypto.go:22-34 (PubKey /
PrivKey interfaces) and crypto/ed25519/ed25519.go (64-byte privkey =
seed || pubkey; SHA256-20 addresses). Single-signature sign/verify runs on
CPU via the `cryptography` package (OpenSSL); bulk verification routes
through crypto.batch.BatchVerifier, whose TPU backend is the framework's
north-star kernel (see crypto/jaxed25519/).
"""

from __future__ import annotations

import hmac
from dataclasses import dataclass

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
except ImportError:  # no OpenSSL bindings: pure-Python RFC 8032 fallback
    from ._ed25519_fallback import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
        InvalidSignature,
    )

from . import tmhash

ED25519_PUBKEY_SIZE = 32
ED25519_PRIVKEY_SIZE = 64  # seed (32) || pubkey (32), as in the reference
ED25519_SIGNATURE_SIZE = 64
ADDRESS_SIZE = tmhash.TRUNCATED_SIZE


class PubKey:
    """Interface: Address() Bytes() VerifyBytes(msg, sig) Equals()."""

    def address(self) -> bytes:
        raise NotImplementedError

    def bytes(self) -> bytes:
        raise NotImplementedError

    def verify_bytes(self, msg: bytes, sig: bytes) -> bool:
        raise NotImplementedError

    def __eq__(self, other) -> bool:
        return isinstance(other, PubKey) and self.bytes() == other.bytes()

    def __hash__(self):
        return hash(self.bytes())


class PrivKey:
    """Interface: Bytes() Sign(msg) PubKey() Equals()."""

    def bytes(self) -> bytes:
        raise NotImplementedError

    def sign(self, msg: bytes) -> bytes:
        raise NotImplementedError

    def pub_key(self) -> PubKey:
        raise NotImplementedError

    def __eq__(self, other) -> bool:
        return isinstance(other, PrivKey) and hmac.compare_digest(
            self.bytes(), other.bytes()
        )

    def __hash__(self):
        return hash(self.bytes())


@dataclass(frozen=True)
class PubKeyEd25519(PubKey):
    data: bytes  # 32 raw bytes

    def __post_init__(self):
        if len(self.data) != ED25519_PUBKEY_SIZE:
            raise ValueError(f"ed25519 pubkey must be {ED25519_PUBKEY_SIZE} bytes")

    def address(self) -> bytes:
        return tmhash.sum_truncated(self.data)

    def bytes(self) -> bytes:
        return self.data

    def verify_bytes(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != ED25519_SIGNATURE_SIZE:
            return False
        try:
            Ed25519PublicKey.from_public_bytes(self.data).verify(sig, msg)
            return True
        except (InvalidSignature, ValueError):
            return False

    def __eq__(self, other):
        return PubKey.__eq__(self, other)

    def __hash__(self):
        return PubKey.__hash__(self)


@dataclass(frozen=True)
class PrivKeyEd25519(PrivKey):
    data: bytes  # 64 bytes: seed || pubkey

    def __post_init__(self):
        if len(self.data) != ED25519_PRIVKEY_SIZE:
            raise ValueError(f"ed25519 privkey must be {ED25519_PRIVKEY_SIZE} bytes")
        derived = (
            Ed25519PrivateKey.from_private_bytes(self.data[:32])
            .public_key()
            .public_bytes_raw()
        )
        if derived != self.data[32:]:
            raise ValueError("ed25519 privkey pubkey half does not match seed")

    @staticmethod
    def generate() -> "PrivKeyEd25519":
        sk = Ed25519PrivateKey.generate()
        seed = sk.private_bytes_raw()
        pub = sk.public_key().public_bytes_raw()
        return PrivKeyEd25519(seed + pub)

    @staticmethod
    def from_seed(seed: bytes) -> "PrivKeyEd25519":
        sk = Ed25519PrivateKey.from_private_bytes(seed)
        pub = sk.public_key().public_bytes_raw()
        return PrivKeyEd25519(seed + pub)

    @staticmethod
    def gen_from_secret(secret: bytes) -> "PrivKeyEd25519":
        """Deterministic key from a secret (test fixtures; reference
        crypto/ed25519/ed25519.go GenPrivKeyFromSecret)."""
        return PrivKeyEd25519.from_seed(tmhash.sum(secret))

    def bytes(self) -> bytes:
        return self.data

    def seed(self) -> bytes:
        return self.data[:32]

    def sign(self, msg: bytes) -> bytes:
        return Ed25519PrivateKey.from_private_bytes(self.data[:32]).sign(msg)

    def pub_key(self) -> PubKeyEd25519:
        return PubKeyEd25519(self.data[32:])

    def __eq__(self, other):
        return PrivKey.__eq__(self, other)

    def __hash__(self):
        return PrivKey.__hash__(self)


# --- key (de)serialization -------------------------------------------------
# The reference uses amino type-prefixed bytes; we use a 1-byte type tag.

TYPE_ED25519 = 0x01
TYPE_SECP256K1 = 0x02
TYPE_MULTISIG = 0x03
TYPE_BLS12381 = 0x04

# key-type names accepted by genesis / priv_validator / [crypto] config
KEY_TYPE_ED25519 = "ed25519"
KEY_TYPE_BLS12381 = "bls12381"


def generate_priv_key(key_type: str = KEY_TYPE_ED25519) -> PrivKey:
    """Key-type registry entry point for config/CLI plumbing."""
    if key_type == KEY_TYPE_ED25519:
        return PrivKeyEd25519.generate()
    if key_type == KEY_TYPE_BLS12381:
        from .bls import PrivKeyBLS12381

        return PrivKeyBLS12381.generate()
    raise ValueError(
        f"unknown key type {key_type!r}; have "
        f"{KEY_TYPE_ED25519!r}, {KEY_TYPE_BLS12381!r}")


def key_type_of(pk) -> str:
    """Canonical key-type name of a PubKey or PrivKey instance."""
    from .bls import PrivKeyBLS12381, PubKeyBLS12381

    if isinstance(pk, (PubKeyBLS12381, PrivKeyBLS12381)):
        return KEY_TYPE_BLS12381
    return KEY_TYPE_ED25519


def pubkey_to_bytes(pk: PubKey) -> bytes:
    # the common key first: a validator set is encoded key by key, and
    # three import statements a key cost more than the encoding
    if isinstance(pk, PubKeyEd25519):
        return bytes([TYPE_ED25519]) + pk.data
    from .bls import PubKeyBLS12381
    from .multisig import PubKeyMultisigThreshold
    from .secp256k1 import PubKeySecp256k1

    if isinstance(pk, PubKeySecp256k1):
        return bytes([TYPE_SECP256K1]) + pk.data
    if isinstance(pk, PubKeyMultisigThreshold):
        return bytes([TYPE_MULTISIG]) + pk.bytes()
    if isinstance(pk, PubKeyBLS12381):
        return bytes([TYPE_BLS12381]) + pk.data
    raise TypeError(f"unknown pubkey type {type(pk)}")


def pubkey_from_bytes(data: bytes) -> PubKey:
    if not data:
        raise ValueError("empty pubkey bytes")
    if data[0] == TYPE_ED25519:
        return PubKeyEd25519(data[1:])
    if data[0] == TYPE_SECP256K1:
        from .secp256k1 import PubKeySecp256k1

        return PubKeySecp256k1(data[1:])
    if data[0] == TYPE_MULTISIG:
        from .multisig import PubKeyMultisigThreshold

        return PubKeyMultisigThreshold.from_bytes(data[1:])
    if data[0] == TYPE_BLS12381:
        from .bls import PubKeyBLS12381

        return PubKeyBLS12381(data[1:])
    raise ValueError(f"unknown pubkey type tag {data[0]:#x}")


def privkey_to_bytes(sk: PrivKey) -> bytes:
    from .bls import PrivKeyBLS12381
    from .secp256k1 import PrivKeySecp256k1

    if isinstance(sk, PrivKeyEd25519):
        return bytes([TYPE_ED25519]) + sk.data
    if isinstance(sk, PrivKeySecp256k1):
        return bytes([TYPE_SECP256K1]) + sk.data
    if isinstance(sk, PrivKeyBLS12381):
        return bytes([TYPE_BLS12381]) + sk.data
    raise TypeError(f"unknown privkey type {type(sk)}")


def privkey_from_bytes(data: bytes) -> PrivKey:
    if not data:
        raise ValueError("empty privkey bytes")
    if data[0] == TYPE_ED25519:
        return PrivKeyEd25519(data[1:])
    if data[0] == TYPE_SECP256K1:
        from .secp256k1 import PrivKeySecp256k1

        return PrivKeySecp256k1(data[1:])
    if data[0] == TYPE_BLS12381:
        from .bls import PrivKeyBLS12381

        return PrivKeyBLS12381(data[1:])
    raise ValueError(f"unknown privkey type tag {data[0]:#x}")
