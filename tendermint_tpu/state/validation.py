"""Block validation against State (reference state/validation.go:16-160).

The LastCommit check routes through ValidatorSet.verify_commit — ONE
batched TPU verification for the whole commit (north-star call site #1;
reference does a serial loop at types/validator_set.go:345-371 invoked
from state/validation.go:102-103).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

from ..types.basic import BlockID
from ..types.block import Block
from .state import State, median_time


class ErrInvalidBlock(Exception):
    pass


class VerifiedCommit(NamedTuple):
    """A commit its holder has fully verified (verify_commit returned),
    and what it was verified as: +2/3 of the set with Merkle root
    `valset_root` for `block_id` at `height` on `chain_id`. Fast sync
    verifies block k's commit (carried by block k+1) before it saves k,
    and one iteration later validate_block would verify the same object
    under the same set again; the reactor hands this down instead."""

    commit: object
    valset_root: bytes
    chain_id: str
    block_id: BlockID
    height: int


# Aggregate-lane block-time bound: BLS certificates carry no per-vote
# timestamps, so block time is proposer-chosen (validated for strict
# monotonicity). Without an upper bound a malicious proposer could set
# a time arbitrarily far in the future and — monotonicity — drag every
# later block past it, corrupting evidence expiry and lite-client
# trusting windows chain-wide. Mirror proposer-based-timestamp designs:
# reject h.time beyond our local clock plus an allowed drift. Like PBTS
# timely checks, this applies ONLY to undecided proposals (prevote
# time, decided=False): an honest 2/3 then never commits such a block,
# and a node whose own clock lags must still accept blocks the network
# already decided (replay, fast sync, finalize-commit apply all pass
# decided=True) or it would crash-loop on a committed block.
AGG_MAX_CLOCK_DRIFT_NS = 10_000_000_000  # 10s


def validate_block(state: State, block: Block, evidence_pool=None,
                   decided: bool = False,
                   verified_last_commit: Optional[VerifiedCommit] = None
                   ) -> Optional[str]:
    """Raises ErrInvalidBlock (or ErrInvalidCommit subclasses) on failure.

    Returns how LastCommit's signatures were checked: "verified" (a full
    verify_commit), "handed_down" (verified_last_commit is this very
    commit object, verified under state.last_validators' root for
    state.last_block_id at this height on this chain — anything else
    takes the full path), or None at height 1."""
    h = block.header
    # header matches state (reference validation.go:25-98; chain/height
    # checks come before structural validation so errors are precise)
    if h.chain_id != state.chain_id:
        raise ErrInvalidBlock(f"wrong chain_id {h.chain_id!r} != {state.chain_id!r}")
    if h.height != state.last_block_height + 1:
        raise ErrInvalidBlock(
            f"wrong height {h.height}, expected {state.last_block_height + 1}"
        )
    block.validate_basic()
    if h.last_block_id != state.last_block_id:
        raise ErrInvalidBlock(
            f"wrong last_block_id {h.last_block_id} != {state.last_block_id}"
        )
    if h.total_txs != state.last_block_total_tx + h.num_txs:
        raise ErrInvalidBlock(f"wrong total_txs {h.total_txs}")
    if h.app_hash != state.app_hash:
        raise ErrInvalidBlock("wrong app_hash")
    if h.last_results_hash != state.last_results_hash:
        raise ErrInvalidBlock("wrong last_results_hash")
    if h.validators_hash != state.validators.hash():
        raise ErrInvalidBlock("wrong validators_hash")
    if h.next_validators_hash != state.next_validators.hash():
        raise ErrInvalidBlock("wrong next_validators_hash")
    if h.consensus_hash != state.consensus_params.hash():
        raise ErrInvalidBlock("wrong consensus_hash")

    # last commit (reference validation.go:100-116)
    from ..types.block import AggregateCommit

    is_agg = isinstance(block.last_commit, AggregateCommit)
    last_commit_check = None
    if h.height == 1:
        if block.last_commit is not None and (
            is_agg or block.last_commit.precommits
        ):
            raise ErrInvalidBlock("block at height 1 can't have LastCommit precommits")
        # block time at height 1 IS the genesis time (validation.go:126-133)
        if h.time != state.last_block_time:
            raise ErrInvalidBlock(
                f"block time {h.time} != genesis time {state.last_block_time}"
            )
    else:
        if is_agg:
            # BLS fast lane: the certificate replaces the precommit list.
            # Size/height checks + the single-pairing verification all
            # live in verify_commit_aggregate (via the same dispatch).
            if state.last_validators.is_bls() is False:
                raise ErrInvalidBlock(
                    "aggregate LastCommit on a non-BLS validator set")
        elif block.last_commit is None or len(block.last_commit.precommits) != len(
            state.last_validators
        ):
            got = 0 if block.last_commit is None else len(block.last_commit.precommits)
            raise ErrInvalidBlock(
                f"wrong LastCommit size {got}, expected {len(state.last_validators)}"
            )
        rec = verified_last_commit
        if (rec is not None and rec.commit is block.last_commit
                and rec.chain_id == state.chain_id
                and rec.block_id == state.last_block_id
                and rec.height == h.height - 1
                and rec.valset_root == state.last_validators.hash()):
            last_commit_check = "handed_down"
        else:
            # ★ batched signature verification (TPU path); AggregateCommit
            # dispatches to the one-pairing certificate check
            state.last_validators.verify_commit(
                state.chain_id, state.last_block_id, h.height - 1,
                block.last_commit
            )
            last_commit_check = "verified"
        # median-time rule (reference validation.go:110-124): strictly
        # increasing AND exactly the weighted median of LastCommit times
        if h.time <= state.last_block_time:
            raise ErrInvalidBlock(
                f"block time {h.time} not greater than last block time {state.last_block_time}"
            )
        if not is_agg:
            expected = median_time(block.last_commit, state.last_validators)
            if h.time != expected:
                raise ErrInvalidBlock(
                    f"invalid block time {h.time}, expected (median) {expected}"
                )
        elif not decided:
            # aggregate certificates carry no per-vote timestamps
            # (identical sign-bytes are what make aggregation possible),
            # so BFT median time degrades to the proposer's clock under
            # strict monotonicity (above) PLUS a local-clock upper bound
            # — proposal-time only, see AGG_MAX_CLOCK_DRIFT_NS above
            # (PARITY_DEVIATIONS.md item 13)
            from ..types.basic import now_ns

            if h.time > now_ns() + AGG_MAX_CLOCK_DRIFT_NS:
                raise ErrInvalidBlock(
                    f"aggregate-lane block time {h.time} is further than "
                    f"{AGG_MAX_CLOCK_DRIFT_NS}ns past the local clock"
                )

    # proposer must be in the current validator set (validation.go:131-138)
    if not state.validators.has_address(h.proposer_address):
        raise ErrInvalidBlock(
            f"proposer {h.proposer_address.hex()} is not a validator"
        )

    # evidence (validation.go:141-152)
    for ev in block.evidence.evidence:
        verify_evidence(state, ev)
        if evidence_pool is not None and evidence_pool.is_committed(ev):
            raise ErrInvalidBlock(f"evidence was already committed: {ev}")
    return last_commit_check


def verify_evidence(state: State, evidence, load_validators=None) -> None:
    """Reference state/validation.go:167-199 VerifyEvidence.

    load_validators(height) loads the historical valset; defaults to the
    current-state sets (enough for max_age within unchanged valsets)."""
    height = state.last_block_height
    ev_height = evidence.height()
    max_age = state.consensus_params.evidence.max_age
    if height - ev_height > max_age:
        raise ErrInvalidBlock(
            f"evidence from height {ev_height} is too old (max age {max_age})"
        )
    # equivocation at the in-flight height (ev_height == height+1) is the
    # NORMAL case for evidence created live from conflicting votes (the
    # reference checks only the age bound, validation.go:167-199); heights
    # beyond the in-flight one cannot have legitimate votes yet and would
    # be verified against a valset we cannot know — reject those
    if ev_height > height + 1:
        raise ErrInvalidBlock(f"evidence from future height {ev_height}")

    if load_validators is not None and ev_height <= height:
        valset = load_validators(ev_height)
    else:
        valset = state.validators
    addr = evidence.address()
    idx, val = valset.get_by_address(addr)
    if val is None:
        raise ErrInvalidBlock(
            f"address {addr.hex()} was not a validator at height {ev_height}"
        )
    evidence.verify(state.chain_id)
