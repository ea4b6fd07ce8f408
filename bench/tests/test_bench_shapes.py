"""A whole run of the harness on a branching tree, a cycle and a split
layout (CPU, tiny size, the look for a chip skipped): the test unions of
``bench/tests/unions`` are laid into a copy of ``bench/`` as
configurations, so nothing is added under ``bench/configs``.  A sound run
comes out correct, and a run with answers cut short or altered does not."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.tests.test_bench_faults import (altered_answer,  # noqa: E402
                                           half_left_out)

UNIONS = os.path.join(ROOT, "bench", "tests", "unions")
SHAPES = ["ps_tree", "q5_cycle", "uq3_split"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout that holds ``bench/`` and the test unions as configs."""
    top = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), top / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in SHAPES:
        for ext in (".json", ".py"):
            shutil.copy(os.path.join(UNIONS, name + ext),
                        top / "bench" / "configs" / (name + ext))
    return str(top)


def cell(name):
    return {"name": f"{name}.bulk", "config": name, "traffic": "closed4x8192",
            "chips": 1, "why": "test union"}


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (half_left_out, False),
                                           (altered_answer, False)])
def test_run_on_a_union_of_another_shape(root, name, fault, correct):
    bench = harness.load_benchmark(ROOT)
    bench = dict(bench, workloads=bench["workloads"] + [cell(name)])
    out, numbers = harness.run_cell(
        root, bench, cell(name), seed=2 ** 34 + 5, seconds=1.0, trace=False,
        t_start=0.0, chip=False, fault=fault)
    assert out["correct"] is correct, numbers
