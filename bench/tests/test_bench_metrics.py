"""The per-layer metric readers on hand-built contexts (CPU): each reads
its number, and returns nothing where it finds nothing to read."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, kernels  # noqa: E402
from bench.load import Request  # noqa: E402
from bench.trace_reduce import Reduced  # noqa: E402


def read(name, ctx):
    return harness.load_metric(ROOT, name).read(ctx)


def ctx(reduced=None):
    window = {"drain_sum": 2.0, "drain_count": 100, "draws": 2_000_000,
              "emitted": 1_000_000, "rounds": 150.0,
              "piece_draws": np.array([1_500_000, 500_000])}
    reqs = [Request(due=0.1 * i, asked=1024, send=0.1 * i,
                    done=0.1 * i + 0.01 * (i + 1)) for i in range(20)]
    return {"window": window, "requests": reqs, "reduced": reduced,
            "engine": {"hops": [4, 4]},
            "device_kind": "TPU v5 lite"}


def traced():
    return Reduced(window_s=1.0, busy_s=0.9, module_s={"jit_loop_fn": 0.8},
                   module_runs={"jit_loop_fn": 20}, op_s={}, kernel_s=0.2,
                   gaps=[])


def test_counter_and_host_clock_readers():
    c = ctx()
    assert read("psi.bulk", c) == pytest.approx(2.0)
    assert read("drain_ms_per_call.bulk", c) == pytest.approx(20.0)
    # 20 x 1024 samples; the last answer comes at 1.9 + 0.2 s
    assert read("samples_per_s", c) == pytest.approx(20 * 1024 / 2.1)


def test_trace_readers():
    c = ctx(traced())
    assert read("device_idle_pct.bulk", c) == pytest.approx(10.0)
    # 20 loop runs in the trace, 1.5 rounds per run over the window
    assert read("device_ms_per_round.bulk", c) == pytest.approx(
        800.0 / 30)
    per_run = kernels.probe_bytes([1_500_000, 500_000], [4, 4]) / 100
    want = 100 * 20 * per_run / 819e9 / 0.2
    got = read("searchsorted_roofline.bulk", c)
    assert got == pytest.approx(want) and 0 < got <= 100


@pytest.mark.parametrize("name", ["device_idle_pct.bulk",
                                  "device_ms_per_round.bulk",
                                  "searchsorted_roofline.bulk"])
def test_trace_readers_return_nothing_without_a_trace(name):
    assert read(name, ctx(None)) is None
    empty = Reduced(1.0, 0.5, {}, {}, {}, 0.0, [])
    if name != "device_idle_pct.bulk":
        assert read(name, ctx(empty)) is None


def test_counter_readers_return_nothing_without_work():
    c = ctx()
    c["window"].update(emitted=0, drain_count=0)
    c["requests"] = []
    for name in ("psi.bulk", "drain_ms_per_call.bulk"):
        assert read(name, c) is None
