"""The live vote path handles a received vote with what it arrived with.

A drained run's WAL records are spliced from the bytes each peer vote was
received as and go down in one write (`WAL.write_run`): the file is byte
for byte what one `WAL.write` a vote leaves, replay reads the same
messages, an internal vote in the run is still fsync'd by itself in queue
order, and a crash inside the run's one write loses only its tail. The
reactor's vote bookkeeping reads the three integers `ConsensusState`
publishes (`vote_sizes`), never a `RoundState` copy, and sizes the peer's
last-commit bit array by the last commit's set."""

from types import SimpleNamespace

import pytest

from tendermint_tpu.consensus.cstypes import RoundState
from tendermint_tpu.consensus.messages import (NewRoundStepMessage,
                                               VoteMessage)
from tendermint_tpu.consensus.reactor import (VOTE_CHANNEL, ConsensusReactor,
                                              decode_msg, encode_msg)
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.ticker import TimeoutInfo
from tendermint_tpu.consensus.wal import WAL, EndHeightMessage, NilWAL
from tendermint_tpu.crypto import PrivKeyEd25519
from tendermint_tpu.libs import tracing
from tendermint_tpu.metrics import prometheus_metrics
from tendermint_tpu.types.basic import (VOTE_TYPE_PRECOMMIT,
                                        VOTE_TYPE_PREVOTE, BlockID,
                                        PartSetHeader, Vote)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote_set import VoteSet

PEER = "ab" * 20
BID = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))


def _vote(i: int, type_=VOTE_TYPE_PREVOTE, height: int = 5) -> Vote:
    return Vote(bytes([i]) * 20, i, height, 0, 1_700_000_000_000_000_000 + i,
                type_, BID, bytes([i]) * 64)


def _arrived(v: Vote) -> VoteMessage:
    """A VoteMessage as the reactor hands it on: decoded, with its bytes."""
    raw = encode_msg(VoteMessage(v))
    msg = decode_msg(raw)
    msg.arrived_as = raw
    return msg


def _file(wal: WAL) -> bytes:
    wal.group.flush()
    with open(wal.group.head_path, "rb") as f:
        return f.read()


def _run(n: int = 6):
    """A run of peer votes, one of them without its arrival bytes."""
    run = [(PEER, _arrived(_vote(i, VOTE_TYPE_PREVOTE if i % 2 else
                                 VOTE_TYPE_PRECOMMIT))) for i in range(n)]
    run[2] = (PEER, VoteMessage(run[2][1].vote))
    return run


def _wal(tmp_path, name: str, **kw) -> WAL:
    wal = WAL(str(tmp_path / name / "wal"), **kw)
    wal.start()
    return wal


def test_a_run_in_one_write_leaves_the_bytes_of_a_write_a_vote(tmp_path):
    run = _run()
    at_once, one_by_one = _wal(tmp_path, "run"), _wal(tmp_path, "each")
    for wal in (at_once, one_by_one):
        wal.write(TimeoutInfo(duration=1.0, height=5, round=0, step=3))
    at_once.write_run(run)
    for m in run:
        one_by_one.write(m)
    for wal in (at_once, one_by_one):
        wal.write_end_height(5)
        wal.write((PEER, _arrived(_vote(9, height=6))))
    assert _file(at_once) == _file(one_by_one)
    assert list(at_once.iter_messages()) == list(one_by_one.iter_messages())
    assert (at_once.search_for_end_height(4) is None
            and one_by_one.search_for_end_height(4) is None)
    logged = at_once.search_for_end_height(0)
    assert logged == one_by_one.search_for_end_height(0)
    assert logged[1:-1] == run
    assert at_once.search_for_end_height(5) == [
        (PEER, VoteMessage(_vote(9, height=6)))]
    at_once.stop()
    one_by_one.stop()


def test_a_run_is_one_span_and_counts_its_records_by_source(
        tmp_path, monkeypatch):
    records = prometheus_metrics().consensus.wal_records
    wal = _wal(tmp_path, "w", records_counter=records)
    monkeypatch.setattr(tracing, "_GLOBAL", tracing.Tracer(enabled=True))
    wal.write_run(_run(6))
    spans = [e for e in tracing.get_tracer().events() if e.name == "wal.write"]
    assert [(s.args["n"], s.args["arrived"]) for s in spans] == [(6, 5)]
    assert records._vals == {("arrived",): 5.0, ("encoded",): 1.0}
    wal.stop()


def test_an_internal_vote_in_a_run_is_fsynced_alone_in_queue_order(
        tmp_path, monkeypatch):
    run = _run(5)
    own = ("", VoteMessage(_vote(7, VOTE_TYPE_PRECOMMIT)))
    run.insert(3, own)
    wal = _wal(tmp_path, "w")
    syncs, writes = [], []
    real_sync, real_write = wal.group.sync, wal.group.write
    monkeypatch.setattr(wal.group, "sync",
                        lambda: (syncs.append(len(writes)), real_sync()))
    monkeypatch.setattr(wal.group, "write",
                        lambda data: (writes.append(data), real_write(data)))
    wal.write_run(run)
    # the three records ahead of it in one write, the vote fsync'd after
    # its own write, the last two in one write
    assert len(writes) == 3 and syncs == [2]
    assert list(wal.iter_messages())[1:] == run
    wal.stop()


def test_a_crash_inside_the_runs_write_replays_to_the_last_whole_record(
        tmp_path):
    run = _run(6)
    wal = _wal(tmp_path, "w")
    head = len(_file(wal))
    wal.write_run(run)
    data = _file(wal)
    wal.stop()
    record = (len(data) - head) // len(run)  # the votes' records are alike
    cut = head + 3 * record + record // 2
    with open(wal.group.head_path, "r+b") as f:
        f.truncate(cut)
    again = WAL(wal.group.head_path)
    assert list(again.iter_messages()) == [EndHeightMessage(0)] + run[:3]
    assert again.corrupted_records == 0
    again.group.close()


def test_the_nil_wal_takes_a_run():
    assert NilWAL().write_run(_run(3)) is None


# --- the reactor's vote branch ---------------------------------------------


class _Peer:
    id = PEER

    def __init__(self):
        self._kv = {}

    def get(self, key):
        return self._kv.get(key)

    def set(self, key, value):
        self._kv[key] = value

    def is_running(self) -> bool:
        return False


def _no_copy(*_):
    raise AssertionError("a vote's receive copied the RoundState")


def _reactor(vote_sizes):
    queued = []
    cs = SimpleNamespace(vote_sizes=vote_sizes, get_round_state=_no_copy,
                         config=None,
                         add_peer_message=lambda m, pid: queued.append(m))
    reactor, peer = ConsensusReactor(cs), _Peer()
    reactor.init_peer(peer)
    ps = peer.get("consensus_peer_state")
    ps.apply_new_round_step(NewRoundStepMessage(5, 0, 3, 0, 0))
    return reactor, peer, ps, queued


def test_a_vote_is_received_without_a_round_state_copy():
    reactor, peer, ps, queued = _reactor((5, 7, 7))
    raw = encode_msg(VoteMessage(_vote(3)))
    reactor.receive(VOTE_CHANNEL, peer, raw)
    (msg,) = queued
    assert msg.arrived_as == raw
    assert msg == VoteMessage(_vote(3)) and msg.vote == _vote(3)
    assert ps.prs.prevotes.get_index(3)


def test_a_heights_later_votes_find_the_peers_bit_arrays_made(monkeypatch):
    reactor, peer, ps, _ = _reactor((5, 7, 7))
    calls = []
    real = ps.ensure_vote_bit_arrays
    monkeypatch.setattr(ps, "ensure_vote_bit_arrays",
                        lambda h, n: (calls.append((h, n)), real(h, n)))
    for i in range(3):
        reactor.receive(VOTE_CHANNEL, peer, encode_msg(VoteMessage(_vote(i))))
    assert calls == [(5, 7), (4, 7)]
    assert [ps.prs.prevotes.get_index(i) for i in range(4)] == [
        True, True, True, False]


@pytest.mark.parametrize("peer_height", [5, 4])
def test_the_last_heights_bit_arrays_have_the_last_commits_size(peer_height):
    reactor, peer, ps, _ = _reactor((5, 7, 4))
    if peer_height == 4:
        ps.prs = type(ps.prs)()
        ps.apply_new_round_step(NewRoundStepMessage(4, 0, 3, 0, 0))
        reactor.receive(VOTE_CHANNEL, peer, encode_msg(
            VoteMessage(_vote(2, VOTE_TYPE_PRECOMMIT, height=4))))
        assert (ps.prs.prevotes.size(), ps.prs.precommits.size()) == (4, 4)
        assert ps.prs.precommits.get_index(2)
    else:
        reactor.receive(VOTE_CHANNEL, peer, encode_msg(
            VoteMessage(_vote(1, VOTE_TYPE_PRECOMMIT, height=4))))
        assert ps.prs.last_commit.size() == 4
        assert (ps.prs.prevotes.size(), ps.prs.precommits.size()) == (7, 7)


def test_the_published_sizes_are_the_height_the_set_and_the_last_commit():
    def valset(n):
        return ValidatorSet([Validator.new(
            PrivKeyEd25519.gen_from_secret(b"v%d" % i).pub_key(), 10)
            for i in range(n)])

    rs = RoundState()
    rs.height, rs.validators = 5, valset(7)
    cs = SimpleNamespace(rs=rs)
    ConsensusState._publish_vote_sizes(cs)
    assert cs.vote_sizes == (5, 7, 0)
    rs.last_commit = VoteSet("c", 4, 0, VOTE_TYPE_PRECOMMIT, valset(4))
    ConsensusState._publish_vote_sizes(cs)
    assert cs.vote_sizes == (5, 7, 4)
