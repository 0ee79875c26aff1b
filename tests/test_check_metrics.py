"""scripts/check_metrics.py as a tier-1 guard: the strict exposition
parser rejects the classes of breakage a real Prometheus scrape would
choke on, and the end-to-end node-boot check passes against the live
registry.
"""

import os
import sys

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts"))

import check_metrics as cm


def test_parser_accepts_registry_output():
    from tendermint_tpu.libs.metrics import Registry

    r = Registry()
    r.counter("t_total", "c").inc(3)
    r.gauge("t_height", "g", ("chain",)).with_labels("main").set(7)
    h = r.histogram("t_secs", "h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    fams = cm.parse_exposition(r.render())
    assert fams["t_total"]["samples"][("t_total", ())] == 3
    assert fams["t_height"]["samples"][
        ("t_height", (("chain", "main"),))] == 7
    assert fams["t_secs"]["type"] == "histogram"


@pytest.mark.parametrize("body,err", [
    ("no_type_declared 1\n", "no preceding # TYPE"),
    ("# TYPE x counter\nx 1\nx 1\n", "duplicate series"),
    ("# TYPE x counter\nx{a=b} 1\n", "bad label syntax"),
    ("# TYPE x counter\nx not-a-number\n", "bad sample value"),
    ("# TYPE x counter\nx 1", "end with a newline"),
    ("# TYPE x counter\n# TYPE x gauge\nx 1\n", "second TYPE"),
    ('# TYPE x histogram\nx_bucket{le="1"} 2\n'
     'x_bucket{le="+Inf"} 1\nx_sum 0\nx_count 1\n', "not monotonic"),
    ('# TYPE x histogram\nx_bucket{le="1"} 1\nx_sum 0\nx_count 1\n',
     r"\+Inf"),
    ('# TYPE x histogram\nx_bucket{le="+Inf"} 2\nx_sum 0\nx_count 1\n',
     "!= _count"),
])
def test_parser_rejects(body, err):
    with pytest.raises(cm.ExpositionError, match=err):
        cm.parse_exposition(body)


def test_labeled_family_without_children_is_valid():
    """The satellite fix: a labeled Counter/Gauge with no children must
    render no samples — previously it emitted a label-less `name 0`
    that the strict parser (and Prometheus) reject as a phantom series."""
    from tendermint_tpu.libs.metrics import Registry

    r = Registry()
    r.counter("evt_total", "labeled, never used", ("kind",))
    r.gauge("lvl", "labeled, never used", ("kind",))
    out = r.render()
    assert "evt_total 0" not in out
    assert "lvl 0" not in out
    fams = cm.parse_exposition(out)
    assert fams["evt_total"]["samples"] == {}
    # unlabeled metrics still expose their zero before first use
    r2 = Registry()
    r2.counter("plain_total", "unlabeled")
    assert "plain_total 0" in r2.render()


def test_check_body_flags_missing_families():
    body = "# TYPE tendermint_consensus_height gauge\n" \
           "tendermint_consensus_height 1\n"
    with pytest.raises(cm.ExpositionError, match="missing metric families"):
        cm.check_body(body)


def test_check_body_flags_declared_but_never_recorded():
    """Declaration alone must not satisfy the hot-path families: a fresh
    registry renders HELP/TYPE for every registered metric, so a broken
    set_metrics wiring would otherwise slip through."""
    from tendermint_tpu.metrics import prometheus_metrics

    m = prometheus_metrics("tendermint")
    body = m.registry.render()
    with pytest.raises(cm.ExpositionError, match="never recorded"):
        cm.check_body(body)
    # same body passes once the hot-path families have real samples
    m.crypto.batch_verify_seconds.with_labels("cpu").observe(0.001)
    m.crypto.signatures_verified.inc()
    m.consensus.step_duration.with_labels("propose").observe(0.001)
    cm.check_body(m.registry.render())


def test_readme_drift_lint_both_directions():
    """The README metric table and REQUIRED_FAMILIES must agree:
    required families may not go undocumented, and tendermint_-prefixed
    table rows may not name families the script no longer requires."""
    fams = ("consensus_height", "mempool_size")
    ok = ("| `tendermint_consensus_height` | gauge | — | height |\n"
          "|---|---|---|---|\n"
          "| `tendermint_mempool_size` | gauge | — | txs |\n"
          "| `p2p_peer_send_rate_bytes` | gauge | `peer_id` | legacy |\n")
    assert cm.check_readme_drift(ok, families=fams) == []

    missing = cm.check_readme_drift(
        "| `tendermint_consensus_height` | gauge | — | height |\n",
        families=fams)
    assert len(missing) == 1 and "mempool_size" in missing[0]

    stale = cm.check_readme_drift(
        ok + "| `tendermint_ghost_total` | counter | — | gone |\n",
        families=fams)
    assert len(stale) == 1 and "ghost_total" in stale[0]

    # backticks OUTSIDE the first cell (e.g. a labels column) and
    # separator rows never count as documented names
    labels_only = cm.check_readme_drift(
        "| plain text | gauge | `tendermint_consensus_height` | x |\n",
        families=fams)
    assert any("missing from" in p for p in labels_only)


def test_readme_drift_real_readme_in_sync():
    """The shipped README's metric table stays in lockstep with the
    gate — this is the satellite's actual CI teeth."""
    assert cm.run_readme_drift() == []


def test_live_node_scrape_passes_strict_check():
    """The script's end-to-end path: boot a node, commit 3 blocks,
    scrape /metrics, strict-parse, assert the promised families."""
    body = cm.run_node_and_scrape(blocks=3, timeout=60.0)
    fams = cm.check_body(body)
    height = fams["tendermint_consensus_height"]["samples"][
        ("tendermint_consensus_height", ())]
    assert height >= 3
    # the step machine reported per-step wall time for real steps
    step = fams["tendermint_consensus_step_duration_seconds"]
    steps = {dict(labels).get("step")
             for (name, labels) in step["samples"]
             if name.endswith("_count")}
    assert {"propose", "prevote", "precommit", "commit"} <= steps
    # PR 37: a digest for every triple looked up in the verified-
    # signature cache, and no more (no batch here repeats a triple)

    def total(family):
        return sum(fams[f"tendermint_{family}"]["samples"].values())

    assert total("crypto_sig_cache_key_hashes_total") == (
        total("crypto_sig_cache_hits_total")
        + total("crypto_sig_cache_misses_total")) > 0
