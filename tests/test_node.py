"""Node assembly tests (reference node/node_test.go): a full Node built
from a config root commits blocks; two Nodes connect and stay in sync;
the address book + PEX reactor exchange addresses.
"""

import os
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

from tendermint_tpu import config as cfg
from tendermint_tpu.node import Node, default_new_node
from tendermint_tpu.p2p.pex import AddrBook, parse_net_address
from tendermint_tpu.types.event_bus import EVENT_NEW_BLOCK, query_for_event


def make_config(tmp_path, name, pex=False):
    c = cfg.test_config()
    c.set_root(str(tmp_path / name))
    c.base.proxy_app = "kvstore"
    c.base.moniker = name
    c.rpc.laddr = ""  # no RPC in these tests
    c.p2p.laddr = "tcp://127.0.0.1:0"
    c.p2p.pex = pex
    c.consensus.wal_path = "data/cs.wal/wal"
    c.consensus.create_empty_blocks = True
    return c


def init_files(c: cfg.Config, genesis_doc=None):
    """tendermint init equivalent: key + privval + genesis."""
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    cfg.ensure_root(c.root_dir)
    NodeKey.load_or_gen(c.base.node_key_path())
    pv = load_or_gen_file_pv(c.base.priv_validator_path())
    if genesis_doc is None:
        genesis_doc = GenesisDoc(
            chain_id="test-node-chain",
            genesis_time=time.time_ns() - 10**9,
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        )
    genesis_doc.save(c.base.genesis_path())
    return pv


def test_single_node_commits_blocks(tmp_path):
    c = make_config(tmp_path, "n0")
    init_files(c)
    node = default_new_node(c)
    sub = node.event_bus.subscribe("test", query_for_event(EVENT_NEW_BLOCK), 16)
    node.start()
    try:
        heights = []
        deadline = time.time() + 30
        while len(heights) < 3 and time.time() < deadline:
            msg = sub.get(timeout=1.0)
            if msg is not None:
                heights.append(msg.data["block"].header.height)
        assert len(heights) >= 3, f"only committed {heights}"
        assert heights == sorted(heights)
    finally:
        node.stop()


@pytest.mark.slow  # 8-device XLA warmup compile: minutes on CPU-only hosts
def test_node_start_warms_verify_kernel(tmp_path, monkeypatch):
    """Node.start() must pre-compile the hot verify-kernel bucket shapes
    on a background thread (verify.warmup) so the first live vote batch
    never pays the device compile inside the consensus path."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.jaxed25519 import verify as V

    # one bucket keeps the 8-virtual-device CPU compile inside the timeout
    monkeypatch.setenv("TM_TPU_WARMUP_BUCKETS", "8")
    monkeypatch.setenv("TM_TPU_WARMUP", "1")
    # warmup is gated off for the "cpu" (OpenSSL) backend; other suites in
    # this process may have pinned it — force the adaptive backend here
    prev_backend = batch.default_backend_name()
    batch.set_default_backend("adaptive")
    c = make_config(tmp_path, "warm")
    init_files(c)
    node = default_new_node(c)
    node.start()
    try:
        node._verify_warmup_thread.join(timeout=240)
        assert node._verifier["warmup"] == "ok", node._verifier
        # the warmed shape is actually in the jit cache: a warmup() call
        # for the same bucket must not add compiles
        before = V._jitted_packed_impl.cache_info().misses
        V.warmup(buckets=(8,), calibrate=False)
        assert V._jitted_packed_impl.cache_info().misses == before
    finally:
        node.stop()
        batch.set_default_backend(prev_backend)


def test_node_restart_resumes(tmp_path):
    """Stop after a few blocks, restart from disk (WAL + stores + app
    handshake), and confirm the chain continues from where it left off."""
    c = make_config(tmp_path, "n0")
    c.base.db_backend = "filedb"
    c.base.proxy_app = "kvstore"  # NB: in-proc kvstore is NOT persistent
    init_files(c)

    node = default_new_node(c)
    sub = node.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
    node.start()
    h1 = 0
    deadline = time.time() + 30
    while h1 < 2 and time.time() < deadline:
        msg = sub.get(timeout=1.0)
        if msg is not None:
            h1 = msg.data["block"].header.height
    node.stop()
    assert h1 >= 2

    node2 = default_new_node(c)
    sub2 = node2.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
    node2.start()
    try:
        h2 = 0
        deadline = time.time() + 30
        while h2 <= h1 and time.time() < deadline:
            msg = sub2.get(timeout=1.0)
            if msg is not None:
                h2 = msg.data["block"].header.height
        assert h2 > h1, f"chain did not advance past {h1} (got {h2})"
    finally:
        node2.stop()


def test_two_node_net(tmp_path):
    """Two-validator net assembled via Node + persistent_peers."""
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    cs = [make_config(tmp_path, f"n{i}") for i in range(2)]
    pvs = []
    for c in cs:
        cfg.ensure_root(c.root_dir)
        NodeKey.load_or_gen(c.base.node_key_path())
        pvs.append(load_or_gen_file_pv(c.base.priv_validator_path()))
    doc = GenesisDoc(
        chain_id="two-node-chain",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    for c in cs:
        doc.save(c.base.genesis_path())

    n0 = default_new_node(cs[0])
    n0.start()
    try:
        cs[1].p2p.persistent_peers = f"{n0.node_key.id}@{n0.transport.listen_addr}"
        n1 = default_new_node(cs[1])
        sub = n1.event_bus.subscribe("t", query_for_event(EVENT_NEW_BLOCK), 16)
        n1.start()
        try:
            deadline = time.time() + 60
            height = 0
            while height < 3 and time.time() < deadline:
                msg = sub.get(timeout=1.0)
                if msg is not None:
                    height = msg.data["block"].header.height
            assert height >= 3, f"two-node net stalled at {height}"
        finally:
            n1.stop()
    finally:
        n0.stop()


def test_abci_peer_filters_reject(tmp_path):
    """With filter_peers on, a peer whose ID the app rejects via the
    /p2p/filter/id query must be kept out of the switch (reference
    node/node.go:378-416)."""
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.privval import load_or_gen_file_pv
    from tendermint_tpu.proxy import local_client_creator
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    class FilteringApp(KVStoreApplication):
        def query(self, req):
            from tendermint_tpu.abci import types as abci

            if req.path.startswith("/p2p/filter/id/"):
                return abci.ResponseQuery(code=1, log="id banned")
            if req.path.startswith("/p2p/filter/addr/"):
                return abci.ResponseQuery(code=0)
            return super().query(req)

    cs = [make_config(tmp_path, f"f{i}") for i in range(2)]
    cs[0].base.filter_peers = True
    pvs = []
    for c in cs:
        cfg.ensure_root(c.root_dir)
        NodeKey.load_or_gen(c.base.node_key_path())
        pvs.append(load_or_gen_file_pv(c.base.priv_validator_path()))
    doc = GenesisDoc(
        chain_id="filter-chain",
        genesis_time=time.time_ns() - 10**9,
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    for c in cs:
        doc.save(c.base.genesis_path())

    nk0 = NodeKey.load_or_gen(cs[0].base.node_key_path())
    n0 = Node(cs[0], pvs[0], nk0, local_client_creator(FilteringApp()), doc)
    n0.start()
    try:
        cs[1].p2p.persistent_peers = f"{n0.node_key.id}@{n0.transport.listen_addr}"
        n1 = default_new_node(cs[1])
        n1.start()
        try:
            deadline = time.time() + 8
            while time.time() < deadline:
                if n0.sw.peers.size() > 0:
                    break
                time.sleep(0.25)
            assert n0.sw.peers.size() == 0, "banned peer was admitted"
            assert n1.sw.peers.size() == 0
        finally:
            n1.stop()
    finally:
        n0.stop()


# --- address book unit tests (reference p2p/pex/addrbook_test.go) ------


def test_addrbook_basics(tmp_path):
    book = AddrBook(str(tmp_path / "addrbook.json"))
    book.add_our_address("1.2.3.4:26656", "f" * 40)
    assert not book.add_address(("f" * 40) + "@1.2.3.4:26656")  # self
    assert book.add_address(("a" * 40) + "@10.0.0.1:26656", src_id="src1")
    assert book.add_address(("b" * 40) + "@10.0.0.2:26656", src_id="src1")
    assert book.size() == 2
    assert book.has_address(("a" * 40) + "@10.0.0.1:26656")
    pick = book.pick_address(50)
    assert pick is not None
    nid, addr = parse_net_address(pick)
    assert nid in ("a" * 40, "b" * 40)

    book.mark_good(("a" * 40) + "@10.0.0.1:26656")
    # old-tier addresses aren't clobbered by re-adds
    assert not book.add_address(("a" * 40) + "@6.6.6.6:666", src_id="evil")

    sel = book.get_selection()
    assert 1 <= len(sel) <= 2

    book.save()
    book2 = AddrBook(str(tmp_path / "addrbook.json"))
    assert book2.size() == 2
    assert book2._addrs["a" * 40].bucket_type == "old"


def test_addrbook_attempts_and_bad():
    book = AddrBook(None)
    a = ("c" * 40) + "@10.1.1.1:26656"
    book.add_address(a, src_id="s")
    for _ in range(3):
        book.mark_attempt(a)
    ka = book._addrs["c" * 40]
    assert ka.attempts == 3
    assert ka.is_bad(time.time())
    book.mark_bad(a)
    assert book.size() == 0
