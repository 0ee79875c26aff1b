#!/usr/bin/env python3
"""What a height costs on its way to disk, alone: the fast-sync loop's
per-height stages in one thread on a quiet host, collector off, no peer,
no dispatch thread, no device (the host backend verifies nothing here:
every LastCommit is handed down as the reactor hands it).

    python scripts/save_stages.py --validators 500 [--blocks 24] [--seed 7] [--workers 4]

A span in a benchmark cell reads its own work plus what the other
threads did under the interpreter lock meanwhile; this table is the
stage's own cost, so the two side by side tell work from waiting. Prints
ms a height by the program's own spans (median over the heights after
the first two), then the pieces the stores are made of, each timed
alone. A number from here is a host cost of the machine it ran on.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

STAGES = ("store.saveBlock", "state.applyBlock", "state.validateBlock",
          "commit.execute", "state.saveResponses", "state.updateState",
          "commit.appCommit", "state.saveState")


def _ms(fn, reps: int) -> float:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter_ns()
        fn()
        out.append((time.perf_counter_ns() - t0) / 1e6)
    return statistics.median(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--validators", type=int, required=True)
    ap.add_argument("--blocks", type=int, default=24)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--workers", type=int,
                    default=max(1, min(12, (os.cpu_count() or 2) - 1)),
                    help="signing processes for the chain")
    args = ap.parse_args(argv)

    from benchmark.harness import chain as chains
    from tendermint_tpu import state as sm
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.node.node import db_provider
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state.validation import VerifiedCommit
    from tendermint_tpu.types import serde
    from tendermint_tpu.types.basic import BlockID
    from tendermint_tpu.types.part_set import PartSet

    chain = chains.sign_blocks(
        chains.committee(seed=args.seed, validators=args.validators),
        blocks=args.blocks, txs_per_block=10, tx_bytes=250, key_space=1024,
        workers=args.workers)
    head = len(serde.pack(["block_response", None])) - 1
    blocks = []
    for message in chain.messages:
        block = serde.decode_block(message[head:])
        block.arrived_as = message[head:]
        blocks.append(block)

    tracer = tracing.get_tracer()
    tracer.enable()
    gc.collect()
    gc.disable()
    with tempfile.TemporaryDirectory() as home:
        state_db = db_provider("state", "filedb", home)
        state = sm.load_state_from_db_or_genesis(state_db, chain.genesis)
        conns = AppConns(local_client_creator(KVStoreApplication()))
        conns.start()
        executor = sm.BlockExecutor(state_db, conns.consensus)
        store = BlockStore(db_provider("blockstore", "filedb", home))
        verified = None
        for first, second in zip(blocks, blocks[1:]):
            parts = PartSet.from_data(first.arrived_as)
            block_id = BlockID(hash=first.hash(), parts_header=parts.header())
            store.save_block(first, parts, second.last_commit)
            executor.verified_last_commit = verified
            verified = VerifiedCommit(
                second.last_commit, state.validators.hash(), state.chain_id,
                block_id, first.header.height)
            state = executor.apply_block(state, block_id, first)

    by_stage: dict = {name: [] for name in STAGES}
    for rec in tracer.events():
        if rec.name in by_stage and (rec.args or {}).get("height", 0) > 2:
            by_stage[rec.name].append(rec.dur_ns / 1e6)
    table = {name: round(statistics.median(ms), 3)
             for name, ms in by_stage.items() if ms}

    # the pieces, each alone: a commit that has not been saved yet, a
    # set with and without the bytes it was saved as, one set's copy
    commit = blocks[-1].last_commit
    plain_commit = _ms(lambda: serde.pack(serde.commit_obj(commit)), 9)
    vals = state.next_validators
    plain_valset = _ms(lambda: serde.pack(serde.valset_obj(vals)), 9)
    plain_state = _ms(lambda: serde.pack(state.to_obj()), 9)
    kept_state = _ms(state.to_bytes, 9)
    set_copy = _ms(vals.copy, 9)
    print(json.dumps({
        "validators": args.validators, "heights": len(blocks) - 3,
        "ms_per_height": table,
        "pieces_ms": {
            "pack(commit_obj(commit))": round(plain_commit, 3),
            "pack(valset_obj(set))": round(plain_valset, 3),
            "pack(state.to_obj())": round(plain_state, 3),
            "State.to_bytes()": round(kept_state, 3),
            "ValidatorSet.copy()": round(set_copy, 3),
        }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
