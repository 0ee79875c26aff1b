"""State persistence (reference state/store.go).

Layout:
  stateKey                 -> State bytes (latest)
  validatorsKey:<height>   -> ValidatorSet effective AT height
  consensusParamsKey:<h>   -> ConsensusParams effective AT height
  abciResponsesKey:<h>     -> ABCIResponses for block at height
Historical valsets/params are saved only when they change, with a
last_height_changed pointer chased on load (reference store.go:180-227).
"""

from __future__ import annotations

import struct
from typing import Optional

from ..libs.db import DB
from ..types import serde
from ..types.genesis import BlockSizeParams, ConsensusParams, EvidenceParams, GenesisDoc
from ..types.validator_set import ValidatorSet
from .state import State, state_from_genesis_doc

_STATE_KEY = b"stateKey"


def _vals_key(height: int) -> bytes:
    return b"validatorsKey:" + struct.pack(">Q", height)


def _params_key(height: int) -> bytes:
    return b"consensusParamsKey:" + struct.pack(">Q", height)


def _abci_key(height: int) -> bytes:
    return b"abciResponsesKey:" + struct.pack(">Q", height)


def save_state(db: DB, state: State) -> None:
    """Persist State + the valset/params it makes effective
    (reference state/store.go:84-105)."""
    next_height = state.last_block_height + 1
    if next_height == 1:
        # genesis bootstrap: heights 1 and 2 valsets (store.go:92-99)
        save_validators_info(db, next_height, next_height, state.validators)
    save_validators_info(
        db, next_height + 1, state.last_height_validators_changed, state.next_validators
    )
    save_consensus_params_info(
        db, next_height, state.last_height_consensus_params_changed, state.consensus_params
    )
    db.set_sync(_STATE_KEY, state.to_bytes())


def load_state(db: DB) -> Optional[State]:
    raw = db.get(_STATE_KEY)
    return State.from_bytes(raw) if raw else None


def load_state_from_db_or_genesis(db: DB, genesis_doc: GenesisDoc) -> State:
    """Reference state/store.go:46 LoadStateFromDBOrGenesisDoc."""
    state = load_state(db)
    if state is None or state.is_empty():
        state = state_from_genesis_doc(genesis_doc)
        save_state(db, state)
    return state


# --- historical validators (reference store.go:161-227) ---------------------


def save_validators_info(db: DB, height: int, last_changed: int, val_set: Optional[ValidatorSet]) -> None:
    if last_changed > height:
        raise ValueError("last_height_changed cannot be greater than height")
    if height == last_changed and val_set is not None:
        # the set's own bytes: State.to_bytes() packs them anyway
        obj = [last_changed, serde.encode_valset(val_set)]
    else:
        obj = [last_changed, None]  # pointer record
    db.set(_vals_key(height), serde.pack_list(obj))


def load_validators(db: DB, height: int) -> ValidatorSet:
    """ValidatorSet effective AT `height`; chases the changed-height
    pointer (reference store.go:180-205)."""
    o = _load_vals_obj(db, height)
    if o is None:
        raise NoValSetForHeightError(height)
    last_changed, vs_obj = o
    if vs_obj is None:
        o2 = _load_vals_obj(db, last_changed)
        if o2 is None or o2[1] is None:
            raise NoValSetForHeightError(height)
        vs_obj = o2[1]
    return serde.valset_from(vs_obj)


def _load_vals_obj(db: DB, height: int):
    raw = db.get(_vals_key(height))
    return serde.unpack(raw) if raw else None


class NoValSetForHeightError(Exception):
    def __init__(self, height: int):
        super().__init__(f"could not find validator set for height #{height}")
        self.height = height


class NoConsensusParamsForHeightError(Exception):
    def __init__(self, height: int):
        super().__init__(f"could not find consensus params for height #{height}")
        self.height = height


# --- historical consensus params (reference store.go:228-280) ---------------


def save_consensus_params_info(db: DB, height: int, last_changed: int, params: ConsensusParams) -> None:
    if height == last_changed:
        obj = [last_changed, [params.block_size.max_bytes, params.block_size.max_gas, params.evidence.max_age]]
    else:
        obj = [last_changed, None]
    db.set(_params_key(height), serde.pack(obj))


def load_consensus_params(db: DB, height: int) -> ConsensusParams:
    raw = db.get(_params_key(height))
    if raw is None:
        raise NoConsensusParamsForHeightError(height)
    last_changed, p = serde.unpack(raw)
    if p is None:
        raw2 = db.get(_params_key(last_changed))
        if raw2 is None:
            raise NoConsensusParamsForHeightError(height)
        _, p = serde.unpack(raw2)
        if p is None:
            raise NoConsensusParamsForHeightError(height)
    return ConsensusParams(BlockSizeParams(p[0], p[1]), EvidenceParams(p[2]))


# --- ABCI responses (reference store.go:109-160) ----------------------------


def save_abci_responses(db: DB, height: int, abci_responses) -> None:
    db.set(_abci_key(height), abci_responses.to_bytes())


def load_abci_responses(db: DB, height: int):
    from .execution import ABCIResponses

    raw = db.get(_abci_key(height))
    return ABCIResponses.from_bytes(raw) if raw else None
