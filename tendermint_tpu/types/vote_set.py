"""VoteSet — 2/3-quorum vote tallying (reference types/vote_set.go).

North-star call site #2: votes arrive one-per-message on the live path
(add_vote, latency-shaped — single CPU verify), but bulk ingestion
(add_votes: reactor catch-up, WAL replay, fast-sync) verifies the whole
batch in ONE BatchVerifier call before tallying — the TPU path.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..crypto import batch
from ..libs.bit_array import BitArray
from .basic import BlockID, ErrVoteConflictingVotes, Vote
from .validator_set import ValidatorSet


class ErrVoteInvalid(Exception):
    pass


class ErrVoteBadSignature(ErrVoteInvalid):
    """A vote that maps to its validator and whose signature is refused."""


# Aggregate-certificate DoS bounds (Handel-lite lane): a certificate
# claiming fewer signers than this rides the per-vote path instead of
# paying a pairing; a peer whose certificates fail verification this
# many times in one VoteSet (height, round) is ignored thereafter; the
# failed-certificate memo holds this many digests, FIFO-evicted.
_AGG_MIN_CERT_SIGNERS = 2
_AGG_CERT_FAIL_BUDGET = 8
_AGG_REJECT_MEMO_MAX = 512


class VoteSet:
    def __init__(self, chain_id: str, height: int, round_: int, type_: int, val_set: ValidatorSet):
        if height == 0:
            raise ValueError("VoteSet height cannot be 0")
        self.chain_id = chain_id
        self.height = height
        self.round = round_
        self.type = type_
        self.val_set = val_set
        self._lock = threading.RLock()
        n = len(val_set)
        self.votes_bit_array = BitArray(n)
        self.votes: List[Optional[Vote]] = [None] * n
        self.sum = 0
        self.maj23: Optional[BlockID] = None
        # per-block tallies; block key -> (votes bit array, power sum)
        self._votes_by_block: Dict[bytes, "_BlockVotes"] = {}
        # peer id -> block key they claim has 2/3 (reference peerMaj23s)
        self._peer_maj23s: Dict[str, bytes] = {}
        # BLS aggregate lane (precommit sets over BLS valsets only):
        # block key -> running (signer bits, aggregate G2 point, power),
        # grown incrementally from individual votes and absorbed gossip
        # certificates so make_commit / gossip compose in O(1)
        from .basic import VOTE_TYPE_PRECOMMIT as _PC

        self._agg_enabled = type_ == _PC and n > 0 and val_set.is_bls()
        self._agg: Dict[bytes, "_AggState"] = {}
        # failed-certificate memo: a certificate that failed its pairing
        # check is remembered (FIFO-bounded) so a replaying peer costs a
        # sha256 per repeat instead of ~90ms of pairing — the cert-lane
        # analogue of the verified-signature cache. Unique garbage is
        # bounded separately: each peer gets _AGG_CERT_FAIL_BUDGET
        # failed verifications per (height, round), then its
        # certificates are ignored (per-vote gossip still progresses).
        self._agg_rejects: Dict[bytes, bool] = {}
        self._agg_cert_fails: Dict[str, int] = {}

    def size(self) -> int:
        return len(self.val_set)

    # --- add ---------------------------------------------------------------

    def add_vote(self, vote: Vote, verified: bool = False) -> bool:
        """Verify + add one vote. Returns True if it was added (False =
        benign duplicate). Raises ErrVoteInvalid / ErrVoteConflictingVotes.

        verified=True means the signature was ALREADY checked against
        this VoteSet's (chain_id, valset) by a batched pre-verification —
        only internal callers that ran the BatchVerifier themselves may
        set it (the live batched vote path in consensus/state.py)."""
        with self._lock:
            self._precheck(vote)
            _, val = self.val_set.get_by_index(vote.validator_index)
            conflict = self._conflict_check_locked(vote)
            if conflict == "dup":
                return False
            if not verified and not vote.verify(self.chain_id, val.pub_key):
                raise ErrVoteBadSignature(f"invalid signature on {vote}")
            if conflict is not None:
                raise ErrVoteConflictingVotes(conflict, vote)
            self._add_verified_locked(vote, val.voting_power)
            return True

    def add_votes(self, votes: List[Vote]) -> List[bool]:
        """Bulk-add: one batched signature verification for all votes
        (TPU path), then tally with PER-ITEM acceptance — every vote whose
        signature is valid is applied even when the batch also contains
        invalid ones, so a peer-supplied batch with one bad signature
        cannot suppress the valid votes riding with it (the kernel
        returns per-item masks; use them). After the good votes are
        applied, the first bad signature raises ErrVoteInvalid and the
        first conflict raises ErrVoteConflictingVotes (evidence)."""
        with self._lock:
            to_verify = []
            for vote in votes:
                self._precheck(vote)
                _, val = self.val_set.get_by_index(vote.validator_index)
                to_verify.append((vote, val))
            bv = batch.new_batch_verifier()
            for vote, val in to_verify:
                bv.add(vote.sign_bytes(self.chain_id), vote.signature, val.pub_key.bytes())
            mask = bv.verify()
            added = []
            first_invalid: Optional[Vote] = None
            first_conflict = None
            for ok, (vote, val) in zip(mask, to_verify):
                if not ok:
                    if first_invalid is None:
                        first_invalid = vote
                    added.append(False)
                    continue
                conflict = self._conflict_check_locked(vote)
                if conflict == "dup":
                    added.append(False)
                    continue
                if conflict is not None:
                    if first_conflict is None:
                        first_conflict = (conflict, vote)
                    added.append(False)
                    continue
                self._add_verified_locked(vote, val.voting_power)
                added.append(True)
            if first_conflict is not None:
                raise ErrVoteConflictingVotes(first_conflict[0], first_conflict[1])
            if first_invalid is not None:
                raise ErrVoteBadSignature(f"invalid signature on {first_invalid}")
            return added

    def _precheck(self, vote: Optional[Vote]) -> None:
        if vote is None:
            raise ErrVoteInvalid("nil vote")
        if (vote.height, vote.round, vote.type) != (self.height, self.round, self.type):
            raise ErrVoteInvalid(
                f"vote {vote.height}/{vote.round}/{vote.type} does not match "
                f"VoteSet {self.height}/{self.round}/{self.type}"
            )
        idx = vote.validator_index
        if not 0 <= idx < len(self.val_set):
            raise ErrVoteInvalid(f"validator index {idx} out of range")
        addr, _ = self.val_set.get_by_index(idx)
        if addr != vote.validator_address:
            raise ErrVoteInvalid("validator address does not match index")
        if len(vote.signature) not in (64, 96):  # ed25519 | bls12381
            raise ErrVoteInvalid("malformed signature")
        if self._agg_enabled and vote.timestamp != 0:
            # BLS-lane precommits MUST sign timestamp 0: aggregation
            # composes votes into one certificate whose sign-bytes
            # assume it. A vote with any other timestamp verifies
            # individually (it signs its own bytes) but would poison
            # the running aggregate — make_commit would emit a
            # certificate that fails verification chain-wide
            raise ErrVoteInvalid(
                f"BLS-lane precommit carries timestamp {vote.timestamp} "
                "!= 0 (aggregate sign-bytes invariant)")

    def _conflict_check_locked(self, vote: Vote):
        """Returns None (new), "dup" (same again), or the existing
        conflicting Vote."""
        existing = self.votes[vote.validator_index]
        if existing is None:
            # also check block-keyed duplicates (maj23 rollback paths)
            return None
        if existing.block_id == vote.block_id:
            return "dup"
        return existing

    def _add_verified_locked(self, vote: Vote, power: int) -> None:
        idx = vote.validator_index
        self.votes[idx] = vote
        # a certificate may already have claimed this bit (aggregate
        # lane); the global power sum counts each validator once
        if not self.votes_bit_array.get_index(idx):
            self.votes_bit_array.set_index(idx, True)
            self.sum += power
        key = vote.block_id.key()
        bv = self._votes_by_block.get(key)
        if bv is None:
            bv = _BlockVotes(len(self.val_set))
            self._votes_by_block[key] = bv
        bv.add(idx, power)
        if self._agg_enabled:
            self._agg_fold_vote(vote, power)
        if (
            self.maj23 is None
            and 3 * bv.sum > 2 * self.val_set.total_voting_power()
        ):
            self.maj23 = vote.block_id

    # --- BLS aggregate lane -------------------------------------------------

    def _agg_state(self, key: bytes, block_id: BlockID) -> "_AggState":
        st = self._agg.get(key)
        if st is None:
            st = _AggState(block_id)
            self._agg[key] = st
        return st

    def _agg_fold_vote(self, vote: Vote, power: int) -> None:
        """Fold one verified BLS precommit into its block's running
        aggregate (decompression is cached process-wide in crypto.bls)."""
        from ..crypto import bls
        from ..crypto.bls.curve import g2_add

        if vote.timestamp != 0:
            # enforced by _precheck; defensive — a non-zero timestamp
            # vote signs different bytes and must never fold into the
            # timestamp-0 aggregate
            return
        st = self._agg_state(vote.block_id.key(), vote.block_id)
        idx = vote.validator_index
        if idx in st.bits:
            return
        pt = bls._parse_signature_point(vote.signature)
        if pt is None:  # verified upstream; defensive
            return
        st.point = g2_add(st.point, pt)
        st.bits.add(idx)
        st.power += power

    def _agg_cert_composable(self, key: bytes, bits: set) -> bool:
        """Would merging `bits` advance the running aggregate for this
        block? (lock held by caller)"""
        st = self._agg.get(key)
        have = st.bits if st is not None else set()
        if bits <= have:
            return False  # nothing new
        if have and not (bits.isdisjoint(have) or bits >= have):
            return False  # non-composable overlap; keep what we have
        return True

    def absorb_certificate(self, cert, peer_id: str = "") -> bool:
        """Absorb a gossiped (bitmap, aggregate-signature) precommit
        certificate (Handel-lite lane). The certificate's aggregate
        signature is verified over exactly its bitmap, then merged into
        the running aggregate when composable (disjoint, or a superset
        that replaces it); newly covered validators join the power
        tallies. Returns True when the certificate advanced our
        aggregate, False otherwise (bad certificates and non-composable
        overlaps are just ignored — per-vote gossip still makes
        progress).

        DoS posture: the pairing (~hundreds of ms pure-Python) runs
        OUTSIDE the VoteSet lock so certificate verification never
        stalls vote processing; composability is re-checked after
        reacquiring. A certificate only pays a pairing when it claims
        at least _AGG_MIN_CERT_SIGNERS signers (singletons ride the
        per-vote path) and would advance our aggregate, and each peer
        gets _AGG_CERT_FAIL_BUDGET failed verifications per VoteSet
        before its certificates are dropped unexamined. Both admission
        gates apply to gossip input only: local call sites (stored
        seen-commit reconstruction, self-composed certificates) pass an
        empty peer_id and skip them — a whale chain's legitimate
        1-signer certificate must still reconstruct on restart."""
        import hashlib as _hashlib

        from ..crypto import bls
        from ..crypto.bls.curve import g2_add
        from .block import AggregateCommit

        if not self._agg_enabled or not isinstance(cert, AggregateCommit):
            return False
        with self._lock:
            n = len(self.val_set)
            if (cert.agg_height != self.height or cert.agg_round != self.round
                    or cert.signers.size() != n):
                return False
            bits = set(cert.signers.true_indices())
            if not bits:
                return False
            # DoS admission gates apply to REMOTE input only (non-empty
            # peer_id, i.e. the gossip lane). Local call sites — the
            # stored seen-commit on restart, self-composed certificates
            # — must not be bounced: a whale chain can legitimately
            # persist a 1-signer certificate, and rejecting it at
            # reconstruction would crash-loop the node.
            if peer_id:
                if len(bits) < min(_AGG_MIN_CERT_SIGNERS, n):
                    return False
                if self._agg_cert_fails.get(peer_id, 0) >= \
                        _AGG_CERT_FAIL_BUDGET:
                    return False
            if not self._agg_cert_composable(cert.block_id.key(), bits):
                return False
            reject_key = _hashlib.sha256(
                cert.block_id.key() + cert.signers.to_bytes() + cert.agg_sig
            ).digest()
            if reject_key in self._agg_rejects:
                return False
            pubkeys = [self.val_set.validators[i].pub_key.bytes()
                       for i in sorted(bits)]
            msg = cert.sign_bytes(self.chain_id)
        # pairing outside the lock — votes keep flowing while we verify
        ok = bls.fast_aggregate_verify(pubkeys, msg, cert.agg_sig,
                                       require_pop=False)
        with self._lock:
            if not ok:
                if len(self._agg_rejects) >= _AGG_REJECT_MEMO_MAX:
                    # FIFO eviction (insertion-ordered dict), not a
                    # wholesale clear a flooder could exploit to force
                    # re-verification of replayed garbage
                    self._agg_rejects.pop(next(iter(self._agg_rejects)))
                self._agg_rejects[reject_key] = True
                if peer_id:  # gossip lane only — local calls aren't peers
                    self._agg_cert_fails[peer_id] = \
                        self._agg_cert_fails.get(peer_id, 0) + 1
                return False
            # the set may have advanced while the pairing ran
            if not self._agg_cert_composable(cert.block_id.key(), bits):
                return False
            pt = bls._parse_signature_point(cert.agg_sig)
            if pt is None:
                return False
            power_of = {}
            for i in bits:
                _, val = self.val_set.get_by_index(i)
                power_of[i] = val.voting_power
            st = self._agg_state(cert.block_id.key(), cert.block_id)
            if bits >= st.bits:
                st.bits = set(bits)
                st.point = pt
                st.power = sum(power_of[i] for i in bits)
            else:  # disjoint merge
                st.point = g2_add(st.point, pt)
                st.bits |= bits
                st.power += sum(power_of[i] for i in bits)
            # tally newly covered validators (each counted once globally)
            bv = self._votes_by_block.get(cert.block_id.key())
            if bv is None:
                bv = _BlockVotes(n)
                self._votes_by_block[cert.block_id.key()] = bv
            for i in bits:
                if not self.votes_bit_array.get_index(i):
                    self.votes_bit_array.set_index(i, True)
                    self.sum += power_of[i]
                bv.add(i, power_of[i])
            if (self.maj23 is None
                    and 3 * bv.sum > 2 * self.val_set.total_voting_power()):
                self.maj23 = cert.block_id
            return True

    def aggregate_certificate(self, block_id: Optional[BlockID] = None):
        """Current best AggregateCommit for block_id (default: the maj23
        block, else the highest-power block) — what the reactor gossips.
        Returns None when the lane is off or nothing is aggregated."""
        from .block import AggregateCommit

        with self._lock:
            if not self._agg_enabled or not self._agg:
                return None
            if block_id is None:
                key = None
                if self.maj23 is not None:
                    key = self.maj23.key()
                if key is None or key not in self._agg:
                    key = max(self._agg, key=lambda k: self._agg[k].power)
                st = self._agg[key]
            else:
                st = self._agg.get(block_id.key())
                if st is None:
                    return None
            signers = BitArray(len(self.val_set))
            for i in st.bits:
                signers.set_index(i, True)
            from ..crypto.bls.curve import g2_compress

            return AggregateCommit(
                block_id=st.block_id, agg_height=self.height,
                agg_round=self.round, signers=signers,
                agg_sig=g2_compress(st.point),
            )

    # --- queries -----------------------------------------------------------

    def get_by_index(self, idx: int) -> Optional[Vote]:
        with self._lock:
            return self.votes[idx] if 0 <= idx < len(self.votes) else None

    def get_by_address(self, addr: bytes) -> Optional[Vote]:
        with self._lock:
            idx, _ = self.val_set.get_by_address(addr)
            return self.votes[idx] if idx >= 0 else None

    def has_two_thirds_majority(self) -> bool:
        with self._lock:
            return self.maj23 is not None

    def two_thirds_majority(self) -> Optional[BlockID]:
        with self._lock:
            return self.maj23

    def has_two_thirds_any(self) -> bool:
        with self._lock:
            return 3 * self.sum > 2 * self.val_set.total_voting_power()

    def has_all(self) -> bool:
        with self._lock:
            return self.sum == self.val_set.total_voting_power()

    def bit_array(self) -> BitArray:
        with self._lock:
            return self.votes_bit_array.copy()

    def bit_array_by_block_id(self, block_id: BlockID) -> Optional[BitArray]:
        with self._lock:
            bv = self._votes_by_block.get(block_id.key())
            return bv.bit_array.copy() if bv else None

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """Record a peer's claim that block_id has 2/3 (drives vote-bitmap
        gossip; reference vote_set.go SetPeerMaj23)."""
        with self._lock:
            self._peer_maj23s.setdefault(peer_id, block_id.key())

    def make_commit(self):
        from .block import Commit

        with self._lock:
            from .basic import VOTE_TYPE_PRECOMMIT

            if self.type != VOTE_TYPE_PRECOMMIT:
                raise ValueError("cannot make commit from non-precommit VoteSet")
            if self.maj23 is None:
                raise ValueError("cannot make commit: no 2/3 majority")
            if self._agg_enabled:
                # BLS fast lane: the running aggregate for the decided
                # block IS the commit — bitmap + one 96-byte signature.
                # Its power covers at least the tallied quorum (every
                # tallied bit was folded when counted).
                cert = self.aggregate_certificate(self.maj23)
                if cert is None or 3 * self._agg[self.maj23.key()].power <= \
                        2 * self.val_set.total_voting_power():
                    raise ValueError(
                        "cannot make aggregate commit: composed "
                        "certificate below 2/3")
                return cert
            precommits = [
                v.copy() if v is not None and v.block_id == self.maj23 else None
                for v in self.votes
            ]
            return Commit(block_id=self.maj23, precommits=precommits)

    def __str__(self):
        with self._lock:
            return (
                f"VoteSet{{h:{self.height}/{self.round}/{self.type} "
                f"{self.votes_bit_array.num_true()}/{len(self.val_set)} "
                f"sum:{self.sum} maj23:{self.maj23}}}"
            )


class _BlockVotes:
    __slots__ = ("bit_array", "sum")

    def __init__(self, n: int):
        self.bit_array = BitArray(n)
        self.sum = 0

    def add(self, idx: int, power: int) -> None:
        if not self.bit_array.get_index(idx):
            self.bit_array.set_index(idx, True)
            self.sum += power


class _AggState:
    """Running (signer bits, aggregate G2 point, power) for one block —
    the incremental composition behind make_commit and cert gossip."""

    __slots__ = ("block_id", "bits", "point", "power")

    def __init__(self, block_id: BlockID):
        self.block_id = block_id
        self.bits: set = set()
        self.point = None  # curve.G2Point (Jacobian); None = identity
        self.power = 0
