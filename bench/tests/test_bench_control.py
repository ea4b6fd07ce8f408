"""The control of the comparison: the reference in the program's place, one
precision below the stated one, must come out not correct; at the stated
precision it must pass (CPU, small size; on the chip it runs at the cells'
own size through ``bench/control.py``)."""

import os
import sys

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check  # noqa: E402
from bench.control import readings  # noqa: E402
from bench.tests.test_bench_reference import load_union  # noqa: E402

# the configurations, and the test unions (a branching tree with a composite
# edge, a cycle, a split layout) at five times their test size
SCALE = {"uq1_sf1": 0.01, "uq2_sf1": 0.01, "ps_tree": 0.005,
         "q5_cycle": 0.01, "uq3_split": 0.005}


@pytest.mark.parametrize("name", list(SCALE))
@pytest.mark.parametrize("dtype,correct", [(np.float32, True),
                                           (ml_dtypes.bfloat16, False)])
def test_control(name, dtype, correct):
    cfg, mod = load_union(name)
    cfg = dict(cfg, scale_factor=SCALE[name])
    u = mod.build(cfg)
    for seed, numbers, _, _ in readings(cfg, u, dtype, 200_000, [3, 4], 10):
        assert check.passed(numbers) == correct, (seed, numbers)


@pytest.mark.parametrize("name", list(SCALE))
def test_probe_that_misses_the_last_row_of_a_range_is_caught(name):
    """Every row it serves is a member of its home piece; the range-position
    histogram of the deep relation sees the rows it never reaches."""
    cfg, mod = load_union(name)
    cfg = dict(cfg, scale_factor=SCALE[name])
    u = mod.build(cfg)
    for seed, numbers, _, _ in readings(cfg, u, np.float32, 1_000_000, [5],
                                        10, drop_last=True):
        got = {n: v for n, v, _ in numbers}
        assert got["rows_outside_home"] == 0, numbers
        assert not check.passed(numbers), numbers
        assert got["position_chi2"] > cfg["limits"]["position_chi2"], numbers

