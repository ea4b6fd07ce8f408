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
from bench.harness import load_config  # noqa: E402


@pytest.mark.parametrize("name", ["uq1_sf1", "uq2_sf1"])
@pytest.mark.parametrize("dtype,correct", [(np.float32, True),
                                           (ml_dtypes.bfloat16, False)])
def test_control(name, dtype, correct):
    cfg, mod = load_config(ROOT, name)
    cfg = dict(cfg, scale_factor=0.01)
    u = mod.build(cfg)
    for seed, numbers, _, _ in readings(cfg, u, dtype, 200_000, [3, 4], 10):
        assert check.passed(numbers) == correct, (seed, numbers)


@pytest.mark.parametrize("name", ["uq1_sf1", "uq2_sf1"])
def test_probe_that_misses_the_last_row_of_a_range_is_caught(name):
    """Every row it serves is a member of its home piece; the range-position
    histogram of the deep relation sees the rows it never reaches."""
    cfg, mod = load_config(ROOT, name)
    cfg = dict(cfg, scale_factor=0.01)
    u = mod.build(cfg)
    for seed, numbers, _, _ in readings(cfg, u, np.float32, 1_000_000, [5],
                                        10, drop_last=True):
        got = {n: v for n, v, _ in numbers}
        assert got["rows_outside_home"] == 0, numbers
        assert not check.passed(numbers), numbers
        assert got["position_chi2"] > cfg["limits"]["position_chi2"], numbers
