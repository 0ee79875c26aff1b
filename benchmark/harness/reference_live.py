"""The plain reference of the live-consensus cell (`hub150-live`): what
the configuration `hub-150val-live` guarantees, checked straight from
what the node committed, wrote and sent, sharing no code with the
program. It takes plain values (bytes, integers, lists) that `consensus_live`
reads out of the node's block store and WAL and out of the committee's
record; every signature is checked by OpenSSL one at a time
(`reference.verify_one`) and every app hash is the reference kvstore's
(`reference.KVReference`).

The guarantees, for the heights 1..`final` the node committed:

1. every height has the committee's block hash (at the node's turns the
   hash of the block the node proposed and the committee accepted), and
   its header carries the reference's app hash after the height before;
2. keys read back (`_check_applied` of `consensus_live`, with `Applied` below);
3. the node signed exactly one prevote and one precommit a height, both
   in round 0, each verifying under its key; no (height, round, type)
   has two different sign-bytes among all it wrote and sent; every vote
   of its the committee received is one it wrote; its precommit of h is
   in block h+1's LastCommit;
4. at each of its turns its proposal verified, the height committed its
   block, and that block's LastCommit holds a precommit of every
   validator (but one corrupted after the window, which nobody holds),
   each accepted by OpenSSL;
5. (the tail, checked by `consensus_live`) a corrupted phantom vote is refused
   by itself: the height commits, no seen commit holds it or any
   signature OpenSSL refuses, and the committee stays connected.
"""

from __future__ import annotations

from .reference import KVReference, verify_one

PREVOTE, PRECOMMIT = 1, 2


def weighted_median(stamps: list) -> int:
    """The voting-power-weighted median of [(timestamp, power)]: the
    first stamp, in time order, past which more than half the power
    lies (BFT time: a block's time from its LastCommit)."""
    pairs = sorted(stamps)
    half = sum(p for _, p in pairs) // 2
    acc = 0
    for ts, power in pairs:
        acc += power
        if acc > half:
            return ts
    return pairs[-1][0]


class Applied:
    """The committed chain in the shape `_check_applied` reads: by
    height (index h-1) the block hash, the txs and the reference's app
    hash after it."""

    def __init__(self, heights: list):
        self.block_hash = [bytes.fromhex(r["block_hash"]) for r in heights]
        self.txs = [[bytes.fromhex(t) for t in r["txs"]] for r in heights]
        ref = KVReference()
        self.app_hash = []
        for txs in self.txs:
            for tx in txs:
                ref.deliver(tx)
            self.app_hash.append(ref.commit())


def check(*, final: int, heights: list, app_hash0: bytes, stored: dict,
          own_votes: list, received: list, proposals: list,
          node_index: int, pubkeys: list) -> dict:
    """The numbers of guarantees 1, 3 and 4 (each must be 0), the heights
    behind each (`heights`), and the node's turns committed.

    - `heights`: the committee's record, index h-1: `block_hash`, `txs`
      (hex), `own` (the node's turn);
    - `stored`: by height h <= final: `block_hash`, `app_hash` (the
      header's), `last_commit`: None at height 1, else one entry a
      validator, None or (sign_bytes, signature);
    - `own_votes`: the node's votes in its WAL, `received`: those the
      committee got; each (height, round, type, sign_bytes, signature);
    - `proposals`: [(height, verified)] of the node's the committee got.
    """
    applied = Applied(heights[:final])
    chain_wrong = []
    for h in range(1, final + 1):
        got = stored[h]
        app_before = app_hash0 if h == 1 else applied.app_hash[h - 2]
        if got["block_hash"] != applied.block_hash[h - 1] \
                or got["app_hash"] != app_before:
            chain_wrong.append(h)

    pub = pubkeys[node_index]
    sign_bytes: dict = {}
    for h, r, t, sb, sig in own_votes + received:
        sign_bytes.setdefault((h, r, t), set()).add(sb)
    double = {k for k, v in sign_bytes.items() if len(v) > 1}
    wrote = {(h, r, t, sb, sig) for h, r, t, sb, sig in own_votes}
    by_height: dict = {}
    for h, r, t, sb, sig in own_votes:
        by_height.setdefault(h, []).append((r, t, sb, sig))
    votes_wrong = []
    for h in range(1, final + 1):
        mine = by_height.get(h, [])
        ok = (sorted((r, t) for r, t, _, _ in mine)
              == [(0, PREVOTE), (0, PRECOMMIT)]
              and all(verify_one(sb, sig, pub) for _, _, sb, sig in mine)
              and not any(k[0] == h for k in double))
        if ok and h < final:
            held = stored[h + 1]["last_commit"][node_index]
            pc = next(sig for _, t, _, sig in mine if t == PRECOMMIT)
            ok = held is not None and held[1] == pc
        if not ok:
            votes_wrong.append(h)
    # a vote sent that was never written, counted at its height
    votes_wrong += [v[0] for v in received if tuple(v) not in wrote
                    and v[0] <= final]

    verified = {h for h, ok in proposals if ok}
    turns = [h for h in range(1, final + 1) if heights[h - 1]["own"]]
    turns_wrong = []
    for h in turns:
        ok = h in verified
        if ok and h > 1:
            # every precommit of h-1 but a corrupted one nobody held
            absent = {i for t, i, _ in heights[h - 2]["corrupt"]
                      if t == PRECOMMIT}
            last = stored[h]["last_commit"]
            ok = all((v is None) == (i in absent) for i, v in enumerate(last)) \
                and all(verify_one(v[0], v[1], pubkeys[i])
                        for i, v in enumerate(last) if v is not None)
        if not ok:
            turns_wrong.append(h)
    return {"chain_differs": len(chain_wrong),
            "own_votes_wrong": len(votes_wrong),
            "own_turns_wrong": len(turns_wrong), "own_turns": len(turns),
            "heights": {"chain_differs": chain_wrong,
                        "own_votes_wrong": votes_wrong,
                        "own_turns_wrong": turns_wrong}}
