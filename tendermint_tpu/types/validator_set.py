"""Validator, ValidatorSet, and the BATCHED commit verification.

Reference parity: types/validator_set.go. The crucial departure:
verify_commit (reference :330-378 — a serial per-precommit signature loop)
assembles all (sign-bytes, signature, pubkey) triples and issues ONE
BatchVerifier call, which on the jax backend is a single TPU program over
the whole commit. This is north-star call site #1.
"""

from __future__ import annotations

import logging
from bisect import bisect_left
from operator import attrgetter
from dataclasses import dataclass, field as dc_field
from typing import List, Optional

from .. import codec
from ..crypto import PubKey, batch, tmhash
from .basic import VOTE_TYPE_PRECOMMIT, BlockID, votes_sign_bytes

LOG = logging.getLogger("types.validator_set")

MAX_TOTAL_VOTING_POWER = 2**63 // 8  # overflow guard (reference :19)


class ErrInvalidCommit(Exception):
    pass


class ErrInvalidCommitSignatures(ErrInvalidCommit):
    pass


class ErrNotEnoughVotingPower(ErrInvalidCommit):
    pass


class PendingCommitVerify:
    """Handle for an in-flight begin_verify_commit. result() blocks on
    the dispatched signature batch, finishes the tally, and raises
    exactly what verify_commit would have raised. Idempotent: the
    outcome is computed once and replayed on repeat calls."""

    __slots__ = ("_finish", "_exc", "_done")

    def __init__(self, finish=None, exc=None):
        self._finish = finish
        self._exc = exc
        self._done = finish is None

    def result(self) -> None:
        if not self._done:
            self._done = True
            finish, self._finish = self._finish, None
            try:
                finish()
            except Exception as e:  # noqa: BLE001 - replayed to every caller
                self._exc = e
        if self._exc is not None:
            raise self._exc


@dataclass(slots=True)
class Validator:
    address: bytes
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0
    # BLS12-381 proof of possession (96-byte signature over the pubkey
    # bytes under the POP DST; empty for Ed25519). Travels with the
    # validator on the wire so lite clients / statesync — which never
    # see the genesis doc — can prove possession of keys outside their
    # trusted set before an aggregate check (rogue-key defense).
    # Deliberately EXCLUDED from encode()/hash_bytes(): the valset hash
    # must stay identical whether or not the PoP rides along.
    pop: bytes = b""

    @classmethod
    def new(cls, pub_key: PubKey, power: int, pop: bytes = b"") -> "Validator":
        return cls(pub_key.address(), pub_key, power, 0, pop)

    def copy(self) -> "Validator":
        return Validator(self.address, self.pub_key, self.voting_power,
                         self.proposer_priority, self.pop)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties break by lower address (reference
        validator.go CompareProposerPriority)."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        return self if self.address < other.address else other

    def encode(self) -> bytes:
        from ..crypto import pubkey_to_bytes

        return (
            codec.t_bytes(1, self.address)
            + codec.t_bytes(2, pubkey_to_bytes(self.pub_key))
            + codec.t_fixed64(3, self.voting_power)
        )

    def hash_bytes(self) -> bytes:
        """Bytes contributing to ValidatorSet.hash (no priority — it
        changes every round)."""
        return self.encode()

    def __str__(self):
        return f"Val{{{self.address.hex()[:8]} pow:{self.voting_power} pri:{self.proposer_priority}}}"


_address_of = attrgetter("address")


class ValidatorSet:
    """Sorted-by-address validator set with proposer rotation
    (reference types/validator_set.go:33-117).

    A set keeps the bytes it was last saved as (`_packed_memo`, filled
    by serde.encode_valset): a height saves three sets of which two are
    the sets of the height before, unchanged. Everything here that
    writes a priority, the proposer or the membership drops them, and
    nothing outside writes to a member of a set it did not build."""

    # class-level defaults keep instances built via __new__ (copy,
    # serde) safe, as getattr-with-default does for the other memos
    _packed_memo: Optional[bytes] = None
    _proposer: Optional[Validator] = None

    def __init__(self, validators: List[Validator]):
        vals = sorted((v.copy() for v in validators), key=lambda v: v.address)
        addrs = [v.address for v in vals]
        if len(set(addrs)) != len(addrs):
            raise ValueError("duplicate validator address")
        self.validators = vals
        self._total: Optional[int] = None
        self.proposer = None
        if vals:
            self.increment_proposer_priority(1)

    def __len__(self):
        return len(self.validators)

    @property
    def proposer(self) -> Optional[Validator]:
        return self._proposer

    @proposer.setter
    def proposer(self, val: Optional[Validator]) -> None:
        self._proposer = val
        self._packed_memo = None

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = [v.copy() for v in self.validators]
        vs._total = self._total
        # the root covers (address, pub_key, voting_power) only, which a
        # copy shares: update_state copies next_validators every height
        vs._hash_memo = getattr(self, "_hash_memo", None)
        if self.proposer is not None:
            _, vs.proposer = vs.get_by_address(self.proposer.address)
        # priorities and proposer are the original's, so the saved bytes
        # are too (set last: the proposer's setter drops them)
        vs._packed_memo = self._packed_memo
        return vs

    def total_voting_power(self) -> int:
        if self._total is None:
            t = sum(v.voting_power for v in self.validators)
            if t > MAX_TOTAL_VOTING_POWER:
                raise ValueError("total voting power exceeds maximum")
            self._total = t
        return self._total

    def has_address(self, address: bytes) -> bool:
        return self.get_by_address(address)[1] is not None

    def get_by_address(self, address: bytes):
        """-> (index, Validator) or (-1, None). The set is sorted by
        address, so this is a binary search as the reference's is
        (validator_set.go GetByAddress: sort.Search)."""
        vals = self.validators
        i = bisect_left(vals, address, key=_address_of)
        if i < len(vals) and vals[i].address == address:
            return i, vals[i]
        return -1, None

    def get_by_index(self, index: int):
        if 0 <= index < len(self.validators):
            v = self.validators[index]
            return v.address, v
        return None, None

    def increment_proposer_priority(self, times: int) -> None:
        """Advance proposer rotation `times` rounds (reference :76-117).

        Deliberate redesign vs the reference: priorities are unbounded
        Python ints, so the int64-overflow clamps of
        types/validator_set.go:547-585 are unnecessary for safety — but
        the reference's *behavioral* bounds are kept so proposer
        selection matches across implementations: before incrementing,
        priorities are centered on their average and the spread is
        clipped to 2*total_voting_power (same window factor, same
        truncated-division semantics as Go). The per-round loop itself is
        O(times*n) exactly like the reference; `times` is the round/height
        delta, which state transitions keep small (capped here as a
        guard against pathological callers)."""
        if not self.validators:
            return
        if times > 100_000:
            raise ValueError(f"increment_proposer_priority: times {times} too large")
        total = self.total_voting_power()
        self._rescale_priorities(2 * total)
        self._shift_by_avg_priority()
        self._packed_memo = None
        for _ in range(times):
            mx = None
            for v in self.validators:
                v.proposer_priority += v.voting_power
                mx = v if mx is None else mx.compare_proposer_priority(v)
            mx.proposer_priority -= total
            self.proposer = mx

    @staticmethod
    def _trunc_div(a: int, b: int) -> int:
        """Go's integer division truncates toward zero; Python's floors."""
        q = abs(a) // b
        return -q if a < 0 else q

    def _rescale_priorities(self, diff_max: int) -> None:
        """Clip the priority spread to diff_max (reference
        types/validator_set.go:547-585 RescalePriorities)."""
        if diff_max <= 0:
            return
        self._packed_memo = None
        prios = [v.proposer_priority for v in self.validators]
        dist = max(prios) - min(prios)
        if dist > diff_max:
            ratio = (dist + diff_max - 1) // diff_max
            for v in self.validators:
                v.proposer_priority = self._trunc_div(v.proposer_priority, ratio)

    def _shift_by_avg_priority(self) -> None:
        """Center priorities on their average (reference
        shiftByAvgProposerPriority). The reference computes the average
        with big.Int.Div — Euclidean division, which for a positive
        divisor equals Python's floor `//` (NOT Go's truncating `/`)."""
        self._packed_memo = None
        n = len(self.validators)
        avg = sum(v.proposer_priority for v in self.validators) // n
        for v in self.validators:
            v.proposer_priority -= avg

    def get_proposer(self) -> Validator:
        if self.proposer is None:
            self.increment_proposer_priority(1)
        return self.proposer

    def hash(self) -> bytes:
        """Merkle root over every validator's hash_bytes (address, key,
        power; no priority). Memoised: the catch-up loop and
        validate_block ask it of an unchanged committee several times a
        height, and at 500 validators one walk is milliseconds of
        interpreter time. Computed on first use, carried by copy(),
        dropped by update_with_changes — the one place membership and
        powers change; the priority walks leave it alone.
        getattr-with-default as in is_bls()."""
        memo = getattr(self, "_hash_memo", None)
        m = batch.get_metrics()
        if m is not None:
            m.valset_hash.with_labels(
                "computed" if memo is None else "memo").inc()
        if memo is None:
            from ..crypto import merkle

            memo = merkle.hash_from_byte_slices(
                [v.hash_bytes() for v in self.validators])
            self._hash_memo = memo
        return memo

    def is_bls(self) -> bool:
        """True when every validator key is BLS12-381 — the aggregate
        fast lane's opt-in switch (mixed sets are rejected at genesis).
        Cached: hot paths (gossip ticks, vote signing, VoteSet
        construction) query this per call, and at mega-committee sizes
        an O(N) isinstance scan per query is real interpreter time.
        getattr-with-default keeps instances built via __new__ (copy,
        serde) safe; update_with_changes invalidates."""
        cached = getattr(self, "_is_bls_cache", None)
        if cached is not None:
            return cached
        if not self.validators:
            return False  # not cached: an empty set may still be grown
        from ..crypto.bls import PubKeyBLS12381

        result = all(isinstance(v.pub_key, PubKeyBLS12381)
                     for v in self.validators)
        self._is_bls_cache = result
        return result

    # --- commit verification (north-star call site #1) ---------------------

    def verify_commit(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """Verify +2/3 precommits for block_id at height. Raises
        ErrInvalidCommit subclasses on failure.

        Reference types/validator_set.go:330-378, except the per-signature
        loop becomes one BatchVerifier call (TPU-batched). An
        AggregateCommit certificate (BLS fast lane) instead routes to
        verify_commit_aggregate: ONE pairing check regardless of
        committee size.
        """
        from ..libs import tracing
        from .block import AggregateCommit

        with tracing.span("valset.verifyCommit", cat="types", height=height,
                          n=len(self.validators)):
            if isinstance(commit, AggregateCommit):
                self.verify_commit_aggregate(chain_id, block_id, height,
                                             commit)
                return
            bv, entries = self._prepare_commit_verify(
                chain_id, block_id, height, commit)
            mask, psum_tally = self._run_batch_verify(bv, entries, block_id)
            self._finish_commit_verify(mask, psum_tally, entries, block_id)

    def _gate_commit_aggregate(self, chain_id: str, block_id: BlockID,
                               height: int, commit):
        """Crypto-free front of aggregate-commit verification: structural
        checks and the voting-power tally over the signer bitmap. Returns
        (pubkeys, sign_bytes) ready for the pairing check; raises
        ErrInvalidCommit subclasses on any gate failure — an
        under-powered or malformed certificate must not cost a
        pairing."""
        if commit.signers.size() != len(self.validators):
            raise ErrInvalidCommit(
                f"invalid aggregate commit: {commit.signers.size()} signer "
                f"bits for {len(self.validators)} validators")
        if height != commit.height():
            raise ErrInvalidCommit(
                f"invalid aggregate commit height {commit.height()} != {height}")
        if commit.block_id != block_id:
            raise ErrInvalidCommit(
                f"invalid aggregate commit block id {commit.block_id} != {block_id}")
        pubkeys = []
        tallied = 0
        for idx in range(len(self.validators)):
            if commit.signers.get_index(idx):
                val = self.validators[idx]
                pubkeys.append(val.pub_key.bytes())
                tallied += val.voting_power
        if 3 * tallied <= 2 * self.total_voting_power():
            raise ErrNotEnoughVotingPower(
                f"invalid aggregate commit: tallied {tallied} <= 2/3 of "
                f"{self.total_voting_power()}")
        return pubkeys, commit.sign_bytes(chain_id)

    def verify_commit_aggregate(self, chain_id: str, block_id: BlockID,
                                height: int, commit) -> None:
        """Verify an AggregateCommit: structural checks, the voting-power
        tally over the signer bitmap, then ONE fast_aggregate_verify
        (bitmap->aggregate-pubkey MSM + a 2-pairing product check)
        instead of N signature checks.

        PoP note: rogue-key safety for the aggregate check rests on
        proof-of-possession at key REGISTRATION time (genesis validation
        / the app's validator updates); a valset reaching this method is
        hash-chained from that trust root, so the per-call registry
        check is skipped (require_pop=False)."""
        from ..crypto import batch as crypto_batch
        from ..crypto import bls

        pubkeys, msg = self._gate_commit_aggregate(
            chain_id, block_id, height, commit)
        if not bls.fast_aggregate_verify(pubkeys, msg, commit.agg_sig,
                                         require_pop=False):
            raise ErrInvalidCommitSignatures(
                f"invalid aggregate signature over {len(pubkeys)} signers")
        m = crypto_batch.get_metrics()
        if m is not None:
            m.agg_commit_size_bytes.set(commit.size_bytes())

    def verify_commits_aggregate_many(self, chain_id: str, checks):
        """Batched aggregate-commit verification: checks =
        [(block_id, height, commit), ...], every certificate against
        THIS validator set. The per-certificate structural/power gates
        are exactly verify_commit_aggregate's; the k certificates that
        survive them collapse into ONE bls.verify_aggregates_many
        multi-pair product check instead of k sequential 2-pairing
        checks. Returns one Optional[Exception] per check (None =
        verified) — the replica catch-up and statesync bisection
        callers want per-height verdicts, not a first-failure raise."""
        from ..crypto import bls

        results = [None] * len(checks)
        idxs = []
        items = []
        for i, (block_id, height, commit) in enumerate(checks):
            try:
                pubkeys, msg = self._gate_commit_aggregate(
                    chain_id, block_id, height, commit)
            except ErrInvalidCommit as e:
                results[i] = e
                continue
            idxs.append(i)
            items.append((pubkeys, msg, commit.agg_sig))
        if items:
            verdicts = bls.verify_aggregates_many(items)
            for i, ok in zip(idxs, verdicts):
                if not ok:
                    results[i] = ErrInvalidCommitSignatures(
                        "invalid aggregate signature over "
                        f"{checks[i][2].signers.num_true()} signers")
        return results

    def begin_verify_commit(
        self, chain_id: str, block_id: BlockID, height: int, commit
    ) -> "PendingCommitVerify":
        """verify_commit with the signature batch dispatched ASYNC
        (BatchVerifier.verify_async): structural pre-checks run — and
        raise — here; .result() blocks on the device batch, completes
        the tally, and raises exactly what verify_commit would have.
        The fast-sync pipeline uses this to verify block k+1's commit
        on-device while block k applies on the host. When async dispatch
        is disabled the whole verification runs synchronously here and
        .result() just replays the outcome. (The multi-device psum tally
        path is sync-only; the host tally is authoritative either way.)

        AggregateCommit certificates verify synchronously (one pairing —
        there is no batch to overlap); the pending handle just replays
        the outcome."""
        from .block import AggregateCommit

        if isinstance(commit, AggregateCommit):
            try:
                self.verify_commit_aggregate(chain_id, block_id, height, commit)
            except ErrInvalidCommit as e:
                return PendingCommitVerify(exc=e)
            return PendingCommitVerify()
        bv, entries = self._prepare_commit_verify(chain_id, block_id, height, commit)
        if entries and batch.async_enabled():
            fut = bv.verify_async()
            return PendingCommitVerify(
                lambda: self._finish_commit_verify(
                    fut.result(), None, entries, block_id)
            )
        try:
            mask, psum_tally = self._run_batch_verify(bv, entries, block_id)
            self._finish_commit_verify(mask, psum_tally, entries, block_id)
        except ErrInvalidCommit as e:
            return PendingCommitVerify(exc=e)
        return PendingCommitVerify()

    def _prepare_commit_verify(self, chain_id: str, block_id: BlockID,
                               height: int, commit):
        """Structural pre-checks + batch assembly (raises ErrInvalidCommit
        on malformed commits). Returns (bv, entries) with entries =
        [(index, precommit, validator)] aligned to the batch."""
        if len(self.validators) != len(commit.precommits):
            raise ErrInvalidCommit(
                f"invalid commit: {len(commit.precommits)} precommits for {len(self.validators)} validators"
            )
        if height != commit.height():
            raise ErrInvalidCommit(f"invalid commit height {commit.height()} != {height}")
        round_ = commit.round()

        bv = batch.new_batch_verifier()
        present = [(idx, pc) for idx, pc in enumerate(commit.precommits)
                   if pc is not None]
        for _, precommit in present:
            if precommit.height != height:
                raise ErrInvalidCommit(f"invalid commit precommit height {precommit.height}")
            if precommit.round != round_:
                raise ErrInvalidCommit(f"invalid commit precommit round {precommit.round}")
            if precommit.type != VOTE_TYPE_PRECOMMIT:
                raise ErrInvalidCommit("invalid commit vote type")
        # what the votes share is encoded once, not once a validator
        msgs = votes_sign_bytes(chain_id, [pc for _, pc in present])
        validators = self.validators
        entries = []  # (index, precommit, validator)
        for (idx, precommit), msg in zip(present, msgs):
            val = validators[idx]
            bv.add(msg, precommit.signature, val.pub_key.bytes())
            entries.append((idx, precommit, val))
        return bv, entries

    def _finish_commit_verify(self, mask, psum_tally, entries,
                              block_id: BlockID) -> None:
        """Tally the verified mask and enforce the +2/3 threshold."""
        tallied = 0
        for ok, (idx, precommit, val) in zip(mask, entries):
            if not ok:
                raise ErrInvalidCommitSignatures(
                    f"invalid commit signature from validator {idx} ({val.address.hex()[:12]})"
                )
            if precommit.block_id == block_id:
                tallied += val.voting_power

        if psum_tally is not None and psum_tally != tallied:
            # the host loop above is authoritative; a differing on-device
            # psum tally can only mean a kernel defect — surface it loudly
            LOG.error(
                "sharded psum tally %d != host tally %d (using host)",
                psum_tally, tallied,
            )

        if 3 * tallied <= 2 * self.total_voting_power():
            raise ErrNotEnoughVotingPower(
                f"invalid commit: tallied {tallied} <= 2/3 of {self.total_voting_power()}"
            )

    @staticmethod
    def _run_batch_verify(bv, entries, block_id):
        """Run the accumulated signature batch. With more than one device
        visible and the jax backend active, the batch shards across the
        'dp' mesh and the 2/3 tally happens on-device via psum
        (crypto/jaxed25519/verify.sharded_commit_verify); the host tally
        in verify_commit stays authoritative. Returns (mask, psum_tally
        or None)."""
        if entries:
            try:
                # Backend and batch-size checks come FIRST: importing jax /
                # calling jax.devices() initializes the TPU backend, which
                # must never happen inside the consensus path when the host
                # OpenSSL backend is selected or the batch is tiny.
                backend = batch.default_backend_name()
                min_batch = (batch.effective_batch_min()
                             if backend == "adaptive" else 1)
                if (backend in ("jax", "adaptive")
                        and len(entries) >= min_batch
                        # the fused psum path reads the raw batch and
                        # would bypass the verified-signature cache; with
                        # a cache installed, bv.verify() below serves
                        # hits and device-dispatches only the misses
                        # (host tally is authoritative either way)
                        and batch.get_sig_cache() is None
                        and all(0 <= v.voting_power < 2**31
                                for _, _, v in entries)):
                    import jax

                    from ..crypto.jaxed25519 import verify as jv

                    if len(jax.devices()) > 1:
                        msgs, sigs, pks = zip(*bv._items)
                        powers = [v.voting_power for _, _, v in entries]
                        for_block = [int(p.block_id == block_id)
                                     for _, p, _ in entries]
                        return jv.sharded_commit_verify(
                            list(msgs), list(sigs), list(pks), powers,
                            for_block)
            except Exception as e:  # noqa: BLE001 - host path is authoritative
                # any device-side failure (compile error, OOM, topology
                # change) must not abort commit verification: the batch
                # path below verifies identically — but say so loudly,
                # the sharded path is what this deployment was built for
                LOG.warning("sharded commit verify failed (%s: %s); "
                            "re-verifying through the batch path",
                            type(e).__name__, e, exc_info=True)
        return bv.verify(), None

    # --- updates (reference :411-472 via state.updateState) ---------------

    def update_with_changes(self, changes: List[Validator]) -> None:
        """Apply validator updates (power 0 removes). Reference
        validator_set.go Update/Add/Remove semantics."""
        by_addr = {v.address: v for v in self.validators}
        for c in changes:
            if c.voting_power < 0:
                raise ValueError("negative voting power")
            if c.voting_power == 0:
                if c.address not in by_addr:
                    raise ValueError("removing unknown validator")
                del by_addr[c.address]
            else:
                prev = by_addr.get(c.address)
                nv = c.copy()
                nv.proposer_priority = prev.proposer_priority if prev else 0
                by_addr[c.address] = nv
        self.validators = sorted(by_addr.values(), key=lambda v: v.address)
        self._total = None
        self._is_bls_cache = None
        self._hash_memo = None
        self._packed_memo = None
        if self.proposer is not None and self.proposer.address not in by_addr:
            self.proposer = None
        self.total_voting_power()

    def __str__(self):
        prop = self.proposer.address.hex()[:8] if self.proposer else "none"
        return f"ValidatorSet{{n:{len(self.validators)} proposer:{prop}}}"


def random_validator_set(n: int, power: int = 10):
    """Test fixture (reference types/validator_set.go:531 RandValidatorSet).
    Returns (ValidatorSet, [PrivKeyEd25519] sorted to match)."""
    from ..crypto import PrivKeyEd25519

    keys = [PrivKeyEd25519.generate() for _ in range(n)]
    vals = [Validator.new(k.pub_key(), power) for k in keys]
    vs = ValidatorSet(vals)
    keys_sorted = sorted(keys, key=lambda k: k.pub_key().address())
    return vs, keys_sorted


def random_bls_validator_set(n: int, power: int = 10, seed: bytes = b"bls"):
    """BLS-keyed fixture for the aggregate fast lane: deterministic keys
    (pairing-grade keygen is ~10ms/key, so fixtures stay cheap and
    cacheable). Returns (ValidatorSet, [PrivKeyBLS12381] sorted to
    match)."""
    from ..crypto.bls import PrivKeyBLS12381

    from ..crypto import bls

    keys = [PrivKeyBLS12381.gen_from_secret(seed + b"-%d" % i)
            for i in range(n)]
    vals = [Validator.new(k.pub_key(), power, pop=bls.pop_prove(k))
            for k in keys]
    vs = ValidatorSet(vals)
    keys_sorted = sorted(keys, key=lambda k: k.pub_key().address())
    return vs, keys_sorted
