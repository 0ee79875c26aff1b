"""State: chain state, block validation + execution (reference state/)."""

from .state import State, state_from_genesis_doc  # noqa: F401
from .store import (  # noqa: F401
    load_abci_responses,
    load_consensus_params,
    load_state,
    load_state_from_db_or_genesis,
    load_validators,
    save_abci_responses,
    save_state,
)
from .execution import ABCIResponses, BlockExecutor, update_state  # noqa: F401
from .txindex import IndexerService, KVTxIndexer, NullTxIndexer, TxResult  # noqa: F401
from .validation import ErrInvalidBlock, VerifiedCommit, validate_block  # noqa: F401
