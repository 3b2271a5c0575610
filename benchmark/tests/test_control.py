"""The control of `correct` (the reference in float64 in the program's
place) must come out NOT correct, in every cell, on every seed."""

import os

import pytest

from benchmark import control
from benchmark.harness import manifest

CELLS = [w["name"] for w in manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 7, 99])
def test_lower_precision_fails_the_comparison(workload, seed):
    out = control.control(workload, seed, rehearse=True, writes=4000)
    assert out["compared"] >= 200
    assert out["control_mismatched"] > 3 * max(1, out["limit"]) and not out["control_correct"]
