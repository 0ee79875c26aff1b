"""A run off the chip exits non-zero before compiling, with no result;
so does a run in a directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from benchmark.harness import manifest

CELL = manifest.manifest()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_no_result():
    p = _run(manifest.ROOT)
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "no accelerator" in p.stderr and "platform=cpu" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "the program is not in this checkout" in p.stderr
