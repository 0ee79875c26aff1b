"""ops.py is PROFILE.md's inventory as a function of the item count."""

import json
import os

from benchmark import ops
from benchmark.harness import manifest


def test_inventory_at_ten_thousand():
    assert ops.verify_ops(10_000) == 5.3e10
    assert ops.verify_ops(500) * 20 == ops.verify_ops(10_000)


def test_peaks_name_their_source():
    peaks = json.load(open(os.path.join(manifest.HERE, "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert v5e["vpu_scalar_ops_per_s"] == 3.85e12
    assert "derived" in v5e["vpu_scalar_ops_per_s_source"]
    # 10,000 signatures at perfect issue: ~14 ms (PROFILE.md)
    assert abs(ops.verify_ops(10_000) / v5e["vpu_scalar_ops_per_s"] - 0.01377) < 1e-4
