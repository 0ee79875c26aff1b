"""FilePV — file-backed validator key with double-sign protection.

Reference parity: privval/priv_validator.go:43-61 (struct + persisted
last-sign state), :176-204 (SignVote/SignProposal), :206-280 (sign +
height/round/step regression checks), :302-340 (checkVotesOnlyDifferByTimestamp).
A validator that crashes and restarts must never sign conflicting votes:
the last signed (height, round, step, sign-bytes, signature) is fsync'd
to disk BEFORE the signature is released to the caller.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Optional

from ..libs import fail, tracing
from ..metrics import NOP

from ..crypto import (
    PrivKey,
    PrivKeyEd25519,
    privkey_from_bytes,
    pubkey_from_bytes,
    pubkey_to_bytes,
)
from ..crypto.keys import KEY_TYPE_ED25519, generate_priv_key
from ..types.basic import (
    VOTE_TYPE_PRECOMMIT,
    VOTE_TYPE_PREVOTE,
    Proposal,
    Vote,
    canonical_proposal_sign_bytes,
    canonical_vote_sign_bytes,
)

# sign step numbers (reference privval/priv_validator.go:27-31)
STEP_PROPOSE = 1
STEP_PREVOTE = 2
STEP_PRECOMMIT = 3


# the `type` of a `privval.sign` span and of privval_signatures_total
_KIND = {VOTE_TYPE_PREVOTE: "prevote", VOTE_TYPE_PRECOMMIT: "precommit"}


def vote_to_step(vote: Vote) -> int:
    if vote.type == VOTE_TYPE_PREVOTE:
        return STEP_PREVOTE
    if vote.type == VOTE_TYPE_PRECOMMIT:
        return STEP_PRECOMMIT
    raise ValueError(f"unknown vote type {vote.type}")


class DoubleSignError(Exception):
    pass


class FilePV:
    """Implements the PrivValidator interface (types/priv_validator.go):
    get_pub_key / sign_vote / sign_proposal."""

    def __init__(self, priv_key: PrivKey, file_path: Optional[str] = None):
        self.priv_key = priv_key
        self.file_path = file_path
        self.last_height = 0
        self.last_round = 0
        self.last_step = 0
        self.last_signature: bytes = b""
        self.last_sign_bytes: bytes = b""
        self._lock = threading.Lock()
        # signatures this key made, labeled type (metrics
        # privval_signatures_total; the node wires its counter in)
        self.signatures = NOP

    # --- PrivValidator interface -------------------------------------------

    def get_pub_key(self):
        return self.priv_key.pub_key()

    def get_address(self) -> bytes:
        return self.get_pub_key().address()

    def sign_vote(self, chain_id: str, vote: Vote) -> None:
        """Signs vote in place; raises DoubleSignError on regression
        (reference priv_validator.go:176-183 → signVote :206-254).
        One `privval.sign` span, the persist of the last-sign state a
        `privval.save` inside it; `resigned` says the signature was the
        last one handed out again."""
        with self._lock, tracing.span(
                "privval.sign", cat="privval", type=_KIND.get(vote.type),
                height=vote.height, round=vote.round) as sp:
            sp.set(resigned=self._sign_vote(chain_id, vote))

    def sign_proposal(self, chain_id: str, proposal: Proposal) -> None:
        with self._lock, tracing.span(
                "privval.sign", cat="privval", type="proposal",
                height=proposal.height, round=proposal.round) as sp:
            sp.set(resigned=self._sign_proposal(chain_id, proposal))

    # --- internals ----------------------------------------------------------

    def _check_hrs(self, height: int, round_: int, step: int) -> bool:
        """Returns True if HRS exactly matches the last signed HRS (maybe
        re-sign case); raises on regression (reference :282-300)."""
        if self.last_height > height:
            raise DoubleSignError(f"height regression: {self.last_height} > {height}")
        if self.last_height == height:
            if self.last_round > round_:
                raise DoubleSignError(
                    f"round regression at height {height}: {self.last_round} > {round_}"
                )
            if self.last_round == round_:
                if self.last_step > step:
                    raise DoubleSignError(
                        f"step regression at {height}/{round_}: {self.last_step} > {step}"
                    )
                if self.last_step == step:
                    if not self.last_sign_bytes:
                        raise DoubleSignError("no last_sign_bytes for repeated HRS")
                    return True
        return False

    def _sign_vote(self, chain_id: str, vote: Vote) -> bool:
        """Signs in place; True where the last signature was reused."""
        height, round_, step = vote.height, vote.round, vote_to_step(vote)
        same_hrs = self._check_hrs(height, round_, step)
        sign_bytes = vote.sign_bytes(chain_id)

        if same_hrs:
            # idempotent re-sign: identical payload, or only the timestamp
            # differs (crash between sign and broadcast; reference :233-247)
            if sign_bytes == self.last_sign_bytes:
                vote.signature = self.last_signature
                return True
            ts = _vote_only_differs_by_timestamp(
                chain_id, self.last_sign_bytes, vote
            )
            if ts is not None:
                vote.timestamp = ts
                vote.signature = self.last_signature
                return True
            raise DoubleSignError(
                f"conflicting vote data at the same HRS {height}/{round_}/{step}"
            )

        sig = self.priv_key.sign(sign_bytes)
        self._save_signed(height, round_, step, sign_bytes, sig)
        vote.signature = sig
        self.signatures.with_labels(_KIND[vote.type]).inc()
        return False

    def _sign_proposal(self, chain_id: str, proposal: Proposal) -> bool:
        height, round_, step = proposal.height, proposal.round, STEP_PROPOSE
        same_hrs = self._check_hrs(height, round_, step)
        sign_bytes = proposal.sign_bytes(chain_id)

        if same_hrs:
            if sign_bytes == self.last_sign_bytes:
                proposal.signature = self.last_signature
                return True
            ts = _proposal_only_differs_by_timestamp(
                chain_id, self.last_sign_bytes, proposal
            )
            if ts is not None:
                proposal.timestamp = ts
                proposal.signature = self.last_signature
                return True
            raise DoubleSignError(
                f"conflicting proposal data at the same HRS {height}/{round_}/{step}"
            )

        sig = self.priv_key.sign(sign_bytes)
        self._save_signed(height, round_, step, sign_bytes, sig)
        proposal.signature = sig
        self.signatures.with_labels("proposal").inc()
        return False

    def _save_signed(self, height: int, round_: int, step: int, sign_bytes: bytes, sig: bytes) -> None:
        """Persist-before-release (reference :256-280). A crash here —
        signature computed, sign-state not yet durable — must lose the
        signature entirely: it was never released to the caller, so the
        recovered (older) last-sign state cannot enable a double sign."""
        self.last_height = height
        self.last_round = round_
        self.last_step = step
        self.last_signature = sig
        self.last_sign_bytes = sign_bytes
        fail.fail_point("Privval.BeforeSignStateSave")
        with tracing.span("privval.save", cat="privval"):
            self.save()

    # --- persistence --------------------------------------------------------

    def to_json(self) -> str:
        # Ed25519 keys keep the legacy raw-64-byte spelling (existing
        # priv_validator.json files stay loadable byte-for-byte); other
        # key types (bls12381) persist type-tagged
        if isinstance(self.priv_key, PrivKeyEd25519):
            raw = self.priv_key.bytes().hex()
        else:
            from ..crypto import privkey_to_bytes

            raw = privkey_to_bytes(self.priv_key).hex()
        return json.dumps(
            {
                "address": self.get_address().hex(),
                "pub_key": pubkey_to_bytes(self.get_pub_key()).hex(),
                "priv_key": raw,
                "last_height": self.last_height,
                "last_round": self.last_round,
                "last_step": self.last_step,
                "last_signature": self.last_signature.hex(),
                "last_sign_bytes": self.last_sign_bytes.hex(),
            },
            indent=2,
        )

    def save(self) -> None:
        """Atomic persist (the kernel_cache.py pattern): a UNIQUE
        same-directory tempfile, fsync'd, then os.replace'd over the
        target. A crash at any point leaves either the previous
        complete file or the new complete file — never a truncated
        double-sign guard; a fixed tmp name would let two racing
        writers interleave into one torn tempfile before the rename."""
        if not self.file_path:
            return
        payload = self.to_json()
        d = os.path.dirname(self.file_path) or "."
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp-privval-")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.file_path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    @classmethod
    def load(cls, file_path: str) -> "FilePV":
        with open(file_path) as f:
            o = json.load(f)
        raw = bytes.fromhex(o["priv_key"])
        # legacy files hold the raw 64-byte Ed25519 key; anything else
        # is type-tagged (crypto.keys registry)
        key = PrivKeyEd25519(raw) if len(raw) == 64 else privkey_from_bytes(raw)
        pv = cls(key, file_path)
        pv.last_height = o.get("last_height", 0)
        pv.last_round = o.get("last_round", 0)
        pv.last_step = o.get("last_step", 0)
        pv.last_signature = bytes.fromhex(o.get("last_signature", ""))
        pv.last_sign_bytes = bytes.fromhex(o.get("last_sign_bytes", ""))
        return pv

    @classmethod
    def generate(cls, file_path: Optional[str] = None,
                 key_type: str = KEY_TYPE_ED25519) -> "FilePV":
        pv = cls(generate_priv_key(key_type), file_path)
        pv.save()
        return pv

    def reset(self) -> None:
        """Danger: wipes last-sign state (reference ResetAll; only for
        testing / `reset_priv_validator`)."""
        self.last_height = 0
        self.last_round = 0
        self.last_step = 0
        self.last_signature = b""
        self.last_sign_bytes = b""
        self.save()

    def __str__(self):
        return f"FilePV{{{self.get_address().hex()[:12]} LH:{self.last_height} LR:{self.last_round} LS:{self.last_step}}}"


def load_or_gen_file_pv(file_path: str,
                        key_type: str = KEY_TYPE_ED25519) -> FilePV:
    """Reference privval/priv_validator.go:108 LoadOrGenFilePV.
    key_type ([crypto] config) applies only when generating — an
    existing file keeps whatever key it holds."""
    if os.path.exists(file_path):
        return FilePV.load(file_path)
    return FilePV.generate(file_path, key_type=key_type)


def _vote_only_differs_by_timestamp(chain_id: str, last_sign_bytes: bytes, vote: Vote):
    """If the new vote matches the last signed vote except for timestamp,
    return the previously-signed timestamp (reference :302-320). The
    canonical codec makes this a pure byte-compare: re-encode the new vote
    with every candidate timestamp? No — we extract the old timestamp by
    re-encoding the new vote with each field identical; equality of the two
    encodings modulo the timestamp field is checked by splicing."""
    for ts in _candidate_timestamps(last_sign_bytes):
        trial = canonical_vote_sign_bytes(
            chain_id, vote.type, vote.height, vote.round, vote.block_id, ts
        )
        if trial == last_sign_bytes:
            return ts
    return None


def _proposal_only_differs_by_timestamp(chain_id: str, last_sign_bytes: bytes, p: Proposal):
    for ts in _candidate_timestamps(last_sign_bytes):
        trial = canonical_proposal_sign_bytes(
            chain_id, p.height, p.round, p.block_parts_header, p.pol_round, p.pol_block_id, ts
        )
        if trial == last_sign_bytes:
            return ts
    return None


def _candidate_timestamps(sign_bytes: bytes):
    """Candidate fixed64 timestamp values found in the old sign-bytes.
    The timestamp is a tagged fixed64; rather than fully parsing, scan for
    its tag and yield the value (at most a handful of candidates)."""
    from .. import codec

    out = []
    pos = 0
    n = len(sign_bytes)
    while pos < n:
        try:
            t, npos = codec.read_uvarint(sign_bytes, pos)
        except ValueError:
            break
        wire = t & 0x7
        if wire == codec.WIRE_FIXED64:
            if npos + 8 > n:
                break
            out.append(int.from_bytes(sign_bytes[npos : npos + 8], "little"))
            pos = npos + 8
        elif wire == codec.WIRE_VARINT:
            try:
                _, pos = codec.read_uvarint(sign_bytes, npos)
            except ValueError:
                break
        elif wire == codec.WIRE_BYTES:
            try:
                ln, p2 = codec.read_uvarint(sign_bytes, npos)
            except ValueError:
                break
            pos = p2 + ln
        else:
            break
    return out
