"""The canonical chain State (reference state/state.go:51-84).

State is immutable-by-convention: execution produces a NEW State via
BlockExecutor.apply_block; copies are cheap (validator sets are copied,
everything else is value-like).
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import List, Optional

from ..crypto import merkle
from ..types import serde
from ..types.basic import BlockID
from ..types.block import Block, Commit, Data, EvidenceData, Header
from ..types.genesis import ConsensusParams, GenesisDoc
from ..types.validator_set import ValidatorSet

# the height of validator-set changes takes effect 2 blocks later
# (reference state/state.go:30 valSetCheckpointInterval semantics differ;
# +2 offset is state/execution.go:419)
VALSET_CHANGE_DELAY = 2


@dataclass
class State:
    chain_id: str = ""
    last_block_height: int = 0
    last_block_total_tx: int = 0
    last_block_id: BlockID = dc_field(default_factory=BlockID)
    last_block_time: int = 0  # unix ns

    # validators at height h+1 (next), h (current), h-1 (last)
    # (reference state/state.go:62-72)
    next_validators: Optional[ValidatorSet] = None
    validators: Optional[ValidatorSet] = None
    last_validators: Optional[ValidatorSet] = None
    last_height_validators_changed: int = 0

    consensus_params: ConsensusParams = dc_field(default_factory=ConsensusParams)
    last_height_consensus_params_changed: int = 0

    last_results_hash: bytes = b""
    app_hash: bytes = b""

    def copy(self) -> "State":
        return State(
            chain_id=self.chain_id,
            last_block_height=self.last_block_height,
            last_block_total_tx=self.last_block_total_tx,
            last_block_id=self.last_block_id,
            last_block_time=self.last_block_time,
            next_validators=self.next_validators.copy() if self.next_validators else None,
            validators=self.validators.copy() if self.validators else None,
            last_validators=self.last_validators.copy() if self.last_validators else None,
            last_height_validators_changed=self.last_height_validators_changed,
            consensus_params=self.consensus_params,
            last_height_consensus_params_changed=self.last_height_consensus_params_changed,
            last_results_hash=self.last_results_hash,
            app_hash=self.app_hash,
        )

    def is_empty(self) -> bool:
        return self.validators is None

    def equals(self, other: "State") -> bool:
        return self.to_bytes() == other.to_bytes()

    # --- block creation (reference state/state.go MakeBlock:96-121) ---------

    def make_block(
        self,
        height: int,
        txs: List[bytes],
        commit: Optional[Commit],
        evidence: list,
        proposer_address: bytes,
        time_ns: Optional[int] = None,
    ) -> Block:
        block = Block(
            header=Header(
                chain_id=self.chain_id,
                height=height,
                time=time_ns if time_ns is not None else _median_time(commit, self.last_validators) if commit else 0,
                num_txs=len(txs),
                total_txs=self.last_block_total_tx + len(txs),
                last_block_id=self.last_block_id,
                validators_hash=self.validators.hash(),
                next_validators_hash=self.next_validators.hash(),
                consensus_hash=self.consensus_params.hash(),
                app_hash=self.app_hash,
                last_results_hash=self.last_results_hash,
                proposer_address=proposer_address,
            ),
            data=Data(txs=list(txs)),
            evidence=EvidenceData(evidence=list(evidence)),
            last_commit=commit,
        )
        block.fill_header()
        return block

    # --- serde --------------------------------------------------------------

    def to_obj(self):
        return self._obj(serde.valset_obj)

    def _obj(self, valset):
        next_vals, vals, last_vals = (
            None if vs is None else valset(vs) for vs in
            (self.next_validators, self.validators, self.last_validators))
        return [
            self.chain_id,
            self.last_block_height,
            self.last_block_total_tx,
            serde.block_id_obj(self.last_block_id),
            self.last_block_time,
            next_vals,
            vals,
            last_vals,
            self.last_height_validators_changed,
            [
                self.consensus_params.block_size.max_bytes,
                self.consensus_params.block_size.max_gas,
                self.consensus_params.evidence.max_age,
            ],
            self.last_height_consensus_params_changed,
            self.last_results_hash,
            self.app_hash,
        ]

    @classmethod
    def from_obj(cls, o) -> "State":
        from ..types.genesis import BlockSizeParams, EvidenceParams

        return cls(
            chain_id=o[0],
            last_block_height=o[1],
            last_block_total_tx=o[2],
            last_block_id=serde.block_id_from(o[3]),
            last_block_time=o[4],
            next_validators=serde.valset_from(o[5]) if o[5] is not None else None,
            validators=serde.valset_from(o[6]) if o[6] is not None else None,
            last_validators=serde.valset_from(o[7]) if o[7] is not None else None,
            last_height_validators_changed=o[8],
            consensus_params=ConsensusParams(
                BlockSizeParams(o[9][0], o[9][1]), EvidenceParams(o[9][2])
            ),
            last_height_consensus_params_changed=o[10],
            last_results_hash=o[11],
            app_hash=o[12],
        )

    def to_bytes(self) -> bytes:
        """serde.pack(self.to_obj()), byte for byte, with each validator
        set's part taken from the bytes the set keeps: a height packs
        next_validators, the other two were packed a height and two
        before (update_state hands them down a slot, unwritten)."""
        return serde.pack_list(self._obj(serde.encode_valset))

    @classmethod
    def from_bytes(cls, data: bytes) -> "State":
        return cls.from_obj(serde.unpack(data))


def _median_time(commit: Commit, validators: Optional[ValidatorSet]) -> int:
    """Voting-power-weighted median of commit vote timestamps (reference
    types/validator_set.go MedianTime via state/validation.go:118-124)."""
    if validators is None:
        votes = [v for v in commit.precommits if v is not None]
        if not votes:
            return 0
        ts = sorted(v.timestamp for v in votes)
        return ts[len(ts) // 2]
    # zip stops at the shorter: a vote past the set's end has no power
    pairs = sorted((v.timestamp, val.voting_power) for v, val
                   in zip(commit.precommits, validators.validators)
                   if v is not None)
    if not pairs:
        return 0
    total = sum(power for _, power in pairs)
    half = total // 2
    acc = 0
    for ts, power in pairs:
        acc += power
        if acc > half:
            return ts
    return pairs[-1][0]


def median_time(commit: Commit, validators: Optional[ValidatorSet]) -> int:
    return _median_time(commit, validators)


def state_from_genesis_doc(genesis_doc: GenesisDoc) -> State:
    """MakeGenesisState (reference state/state.go:186-226)."""
    genesis_doc.validate_and_complete()
    val_set = ValidatorSet(genesis_doc.validator_set_validators())
    next_val_set = val_set.copy()
    next_val_set.increment_proposer_priority(1)
    return State(
        chain_id=genesis_doc.chain_id,
        last_block_height=0,
        last_block_id=BlockID(),
        last_block_time=genesis_doc.genesis_time,
        next_validators=next_val_set,
        validators=val_set,
        last_validators=ValidatorSet([]),
        last_height_validators_changed=1,
        consensus_params=genesis_doc.consensus_params,
        last_height_consensus_params_changed=1,
        app_hash=genesis_doc.app_hash,
    )
