"""A run with the timed path broken underneath comes out not correct
(CPU, tiny size: the look for a chip is skipped, the rest of the run is
the benchmark's own)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

SMALL = {"scale_factor": 0.002, "round_batch": 1024}


def frozen_state(sampler, svc):
    """The engine's step returns its state unchanged: from the first call
    after set-up on, every call serves the batch that call did.  The
    engine is driven only from the producer thread, as in a real run."""
    eng = sampler._engine
    dispatch = eng.sample_async
    first = []

    class Frozen:
        def __init__(self, h):
            self.h = h

        def result(self):
            if not first:
                first.append(self.h.result())
            return first[0]
    eng.sample_async = lambda n: Frozen(None if first else dispatch(n))


def half_left_out(sampler, svc):
    """Half of every answer left out."""
    request = svc.request

    def half(n, **kw):
        ss = request(n, **kw)
        k = len(ss) // 2
        ss.rows = {a: c[:k] for a, c in ss.rows.items()}
        ss.home = ss.home[:k]
        return ss
    svc.request = half


def altered_answer(sampler, svc):
    """An attribute of some rows altered where the engine produces them."""
    eng = sampler._engine
    dispatch = eng.sample_async

    class Altered:
        def __init__(self, h):
            self.h = h

        def result(self):
            ss = self.h.result()
            a = ss.attrs[-1]
            col = np.array(ss.rows[a])
            col[::97] += 1
            ss.rows[a] = col
            return ss
    eng.sample_async = lambda n: Altered(dispatch(n))


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", ["uq1_sf1.bulk", "uq2_sf1.bulk"])
@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (frozen_state, False),
                                           (half_left_out, False),
                                           (altered_answer, False)])
def test_fault_is_caught(bench, cell, fault, correct):
    out, numbers = harness.run_cell(
        ROOT, bench, harness.find_cell(bench, cell), seed=2 ** 35 + 3,
        seconds=1.0, trace=False, t_start=0.0, chip=False,
        config_override=SMALL, fault=fault)
    assert out["correct"] is correct, numbers


@pytest.mark.parametrize("cell", ["uq1_sf1.bulk", "uq2_sf1.bulk"])
@pytest.mark.parametrize("data_seed", [2, 3])
def test_sound_run_is_correct_on_other_data(bench, cell, data_seed):
    """The cells fix their data seed; the program and the check hold on data
    drawn from other seeds too."""
    out, numbers = harness.run_cell(
        ROOT, bench, harness.find_cell(bench, cell), seed=2 ** 33 + data_seed,
        seconds=1.0, trace=False, t_start=0.0, chip=False,
        config_override=dict(SMALL, data_seed=data_seed))
    assert out["correct"], numbers
