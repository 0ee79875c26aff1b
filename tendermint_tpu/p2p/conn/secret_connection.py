"""SecretConnection — authenticated encryption transport.

Reference parity: p2p/conn/secret_connection.go.  STS protocol:
exchange ephemeral X25519 pubkeys → ECDH shared secret → HKDF-SHA256
derives one key per direction plus a 32-byte challenge → all further
traffic is 1028-byte plaintext frames (4-byte length + ≤1024 data)
sealed with ChaCha20-Poly1305 under incrementing 96-bit counter nonces
→ each side proves its long-term Ed25519 identity by signing the
challenge (frames :109-140, key schedule :200-260 in the reference).

Wire format is our own (this is a new framework, not a wire-compatible
client), but the cryptographic structure and frame discipline match.

The socket is called once for a batch of sealed frames, not once a
frame (what the reference's bufio reader and writer do around its
conn): one `write(data)` is every frame of `data`, sealed in nonce
order, in ONE `sendall`; a read takes up to RECV_CHUNK_SIZE of
ciphertext off the socket in one `recv` and opens frames from that
buffer one at a time as plaintext is asked for. The sealed stream is
frame for frame what a call a frame would carry.
"""

from __future__ import annotations

import socket
import struct
from typing import Optional

import msgpack

try:
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric.x25519 import (
        X25519PrivateKey,
        X25519PublicKey,
    )
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
    from cryptography.hazmat.primitives.kdf.hkdf import HKDF
except ImportError:  # no OpenSSL bindings: pure-Python RFC 7748/8439 fallback
    from ...crypto._aead_fallback import (
        HKDF,
        ChaCha20Poly1305,
        X25519PrivateKey,
        X25519PublicKey,
        hashes,
    )

from ...crypto.keys import PrivKey, PubKey, pubkey_from_bytes, pubkey_to_bytes

DATA_LEN_SIZE = 4
DATA_MAX_SIZE = 1024
TOTAL_FRAME_SIZE = DATA_MAX_SIZE + DATA_LEN_SIZE  # 1028
AEAD_TAG_SIZE = 16
SEALED_FRAME_SIZE = TOTAL_FRAME_SIZE + AEAD_TAG_SIZE  # 1044 on the wire
NONCE_SIZE = 12
# the most ciphertext one recv takes off the socket (62 sealed frames)
RECV_CHUNK_SIZE = 64 * 1024

HKDF_INFO = b"TENDERMINT_TPU_SECRET_CONNECTION_KEY_AND_CHALLENGE_GEN"


class AuthError(Exception):
    pass


def _recv_exact(conn: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("connection closed during read")
        buf.extend(chunk)
    return bytes(buf)


class SecretConnection:
    """Encrypted, authenticated stream over a connected socket."""

    def __init__(self, conn: socket.socket, loc_priv_key: PrivKey):
        self._conn = conn
        self._sealed = bytearray()  # ciphertext received, not yet opened
        self._recv_buffer = b""  # plaintext of the frame opened last
        self._send_nonce = 0
        self._recv_nonce = 0
        # sealed frames and socket calls, each way: plain integers, no
        # metric call a frame (MConnection publishes their gains)
        self.frames_sent = 0
        self.send_calls = 0  # one a sendall
        self.frames_recv = 0
        self.recv_calls = 0  # one a recv that returned bytes

        # 1. ephemeral X25519 exchange (every 32-byte string is a valid
        #    Curve25519 pubkey, so no validation step is needed)
        eph_priv = X25519PrivateKey.generate()
        loc_eph_pub = eph_priv.public_key().public_bytes_raw()
        conn.sendall(loc_eph_pub)
        rem_eph_pub = _recv_exact(conn, 32)

        loc_is_least = loc_eph_pub < rem_eph_pub
        dh_secret = eph_priv.exchange(X25519PublicKey.from_public_bytes(rem_eph_pub))

        # 2. HKDF → (recv key, send key, challenge); key order is fixed
        #    by the lexical sort so both sides agree which is which
        okm = HKDF(
            algorithm=hashes.SHA256(), length=96, salt=None, info=HKDF_INFO
        ).derive(dh_secret)
        if loc_is_least:
            recv_secret, send_secret = okm[0:32], okm[32:64]
        else:
            recv_secret, send_secret = okm[32:64], okm[0:32]
        challenge = okm[64:96]

        self._send_aead = ChaCha20Poly1305(send_secret)
        self._recv_aead = ChaCha20Poly1305(recv_secret)

        # 3. authenticate: exchange (pubkey, sig(challenge)) in secret
        loc_pub = loc_priv_key.pub_key()
        auth_msg = msgpack.packb(
            [pubkey_to_bytes(loc_pub), loc_priv_key.sign(challenge)],
            use_bin_type=True,
        )
        self.write_msg(auth_msg)
        rem_auth = msgpack.unpackb(self.read_msg(), raw=False)
        rem_pub = pubkey_from_bytes(bytes(rem_auth[0]))
        if not rem_pub.verify_bytes(challenge, bytes(rem_auth[1])):
            raise AuthError("challenge signature verification failed")
        self._rem_pub_key: PubKey = rem_pub

    # -- identity ------------------------------------------------------

    def remote_pub_key(self) -> PubKey:
        return self._rem_pub_key

    # -- frame I/O -----------------------------------------------------

    def _seal(self, frame: bytes) -> bytes:
        nonce = self._send_nonce.to_bytes(NONCE_SIZE, "little")
        self._send_nonce += 1
        return self._send_aead.encrypt(nonce, frame, None)

    def _open(self, sealed: bytes) -> bytes:
        nonce = self._recv_nonce.to_bytes(NONCE_SIZE, "little")
        self._recv_nonce += 1
        return self._recv_aead.decrypt(nonce, sealed, None)

    def write(self, data: bytes) -> int:
        """Seal data as one-or-more frames and send them all in one
        sendall: a write is never split over socket calls."""
        view = memoryview(data)
        sealed = []
        for off in range(0, len(view), DATA_MAX_SIZE):
            chunk = view[off : off + DATA_MAX_SIZE]
            frame = struct.pack("<I", len(chunk)) + bytes(chunk)
            frame += b"\x00" * (TOTAL_FRAME_SIZE - len(frame))
            sealed.append(self._seal(frame))
        if sealed:
            self._conn.sendall(b"".join(sealed))
            self.frames_sent += len(sealed)
            self.send_calls += 1
        return len(view)

    def _next_frame(self) -> bytes:
        """Open the next sealed frame and return its data. Ciphertext
        stays in self._sealed until a whole frame of it is there, so a
        socket.timeout in mid-frame loses nothing; a frame is opened and
        its length checked before any of its bytes is handed up."""
        buf = self._sealed
        while len(buf) < SEALED_FRAME_SIZE:
            chunk = self._conn.recv(RECV_CHUNK_SIZE)
            if not chunk:
                raise ConnectionError("connection closed during read")
            buf += chunk
            self.recv_calls += 1
        sealed = bytes(buf[:SEALED_FRAME_SIZE])
        del buf[:SEALED_FRAME_SIZE]
        frame = self._open(sealed)
        self.frames_recv += 1
        (length,) = struct.unpack_from("<I", frame)
        if length > DATA_MAX_SIZE:
            raise ConnectionError(f"frame length {length} > {DATA_MAX_SIZE}")
        return frame[DATA_LEN_SIZE : DATA_LEN_SIZE + length]

    def read(self, n: int) -> bytes:
        """Read up to n plaintext bytes (at least 1, blocking)."""
        if not self._recv_buffer:
            self._recv_buffer = self._next_frame()
        out, self._recv_buffer = self._recv_buffer[:n], self._recv_buffer[n:]
        return out

    def read_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            buf.extend(self.read(n - len(buf)))
        return bytes(buf)

    MAX_HANDSHAKE_MSG = 64 * 1024

    def write_msg(self, msg: bytes) -> None:
        """Length-prefixed message (handshake helper; spans frames)."""
        self.write(struct.pack("<I", len(msg)) + msg)

    def read_msg(self) -> bytes:
        (length,) = struct.unpack("<I", self.read_exact(4))
        if length > self.MAX_HANDSHAKE_MSG:
            raise ConnectionError(f"handshake msg too large: {length}")
        return self.read_exact(length)

    def close(self) -> None:
        try:
            self._conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._conn.close()

    def settimeout(self, t: Optional[float]) -> None:
        self._conn.settimeout(t)
