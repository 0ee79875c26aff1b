"""chip_smoke.py off the chip: its control flow at toy sizes on the host
batch backend (nothing compiles), and its refusal to report anything
where there is no accelerator or no program beside it. What the stages
check at full size, only a run on the chip can say (`chiprun -- python
chip_smoke.py`)."""

import os
import shutil
import subprocess
import sys
import time

os.environ.setdefault("TM_TPU_CRYPTO_BACKEND", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)


def test_stages_one_and_two_at_toy_sizes(tmp_path):
    """Stages 1-2 end to end — init, default_new_node, RPC writes and
    read-backs, an envelope burst with one corrupted tx, the commit /
    vote-round / fast-sync funnel — each against the serial oracle."""
    import chip_smoke

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import kernel_cache

    def fast_blocks(c):
        c.base.db_backend = "memdb"
        c.consensus.timeout_commit = 0.02
        c.consensus.skip_timeout_commit = True
        c.consensus.blocktime_iota = 10_000_000

    prev = crypto_batch.default_backend_name()
    crypto_batch.set_default_backend("cpu")
    compiles = kernel_cache.stats()["compiles"]
    try:
        out = chip_smoke.stage_node(
            str(tmp_path / "home"), 7, n_txs=24, n_keys=4, n_commit_txs=1,
            tweak=fast_blocks, deadline_s=60)
        assert out["verifier"]["backend"] == "cpu"
        assert out["committed"] == 23 and out["bounced"] == 1
        assert out["device_batches"] == 0
        out = chip_smoke.stage_funnel(7, n_mega=64, n_round=9,
                                      n_sync_vals=7, n_sync_blocks=3)
        assert out["multi_device"] == {}
    finally:
        crypto_batch.set_default_backend(prev)
    assert kernel_cache.stats()["compiles"] == compiles


def test_exits_nonzero_without_an_accelerator(tmp_path):
    """Under JAX_PLATFORMS=cpu the script names the cause, prints no
    result, and is gone within seconds having compiled nothing."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode not in (0, None)
    assert time.monotonic() - t0 < 30
    assert r.stdout.strip() == "", r.stdout
    assert "no accelerator" in r.stderr and "platform=cpu" in r.stderr
    assert not cache.exists()  # the cache layer was never even configured


def test_exits_nonzero_outside_a_checkout(tmp_path):
    """Alone in a directory — without the program — it fails too, even
    where an installed copy of the package is importable."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == "" and "not beside this script" in r.stderr
