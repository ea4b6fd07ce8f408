"""The readers of the program's own instrumentation (``bench/phases.py``)
on hand-built contexts (CPU): the device loop's time by phase, the run's
host histograms, and nothing read where the program publishes nothing."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from repro import obs  # noqa: E402

from bench import harness, phases  # noqa: E402
from bench.trace_reduce import Reduced  # noqa: E402

# a loop program of six ops: two walks, a probe, a compaction, an emission
# and one op outside every phase
LOOP_HLO = "\n".join([
    "HloModule jit_loop_fn, entry_computation_layout={()->s32[]}",
    "",
    "ENTRY %main.1 () -> s32[] {"] + [
    f'  %{op} = s32[8]{{0}} fusion(), kind=kLoop, metadata={{op_name="'
    f'jit(loop_fn)/while/body/{scope}/add" stack_frame_id=1}}'
    for op, scope in [("fusion.1", "algo1_fused_round/walk/J0"),
                      ("_searchsorted_i32.2", "algo1_fused_round/walk/J1"),
                      ("fusion.3", "algo1_fused_round/member/J1"),
                      ("fusion.4", "algo1_fused_round/compact/J1"),
                      ("fusion.5", "emit")]] + [
    "  ROOT %while.6 = s32[] while(), condition=%c, body=%b, "
    'metadata={op_name="jit(loop_fn)/while"}',
    "}"])

# self seconds in the traced window: the loop's ops add up to 0.5 s; the
# op of another program (`copy.9`) is left out
OP_S = {"fusion.1": 0.10, "_searchsorted_i32.2": 0.20, "fusion.3": 0.08,
        "fusion.4": 0.04, "fusion.5": 0.03, "while.6": 0.05, "copy.9": 0.5}


def read(name, ctx):
    return harness.load_metric(ROOT, name).read(ctx)


def ctx():
    red = Reduced(window_s=1.0, busy_s=0.9, module_s={"jit_loop_fn": 0.8},
                  module_runs={"jit_loop_fn": 20}, op_s=dict(OP_S),
                  kernel_s=0.2, gaps=[])
    window = {"drain_sum": 2.0, "drain_count": 100, "draws": 2_000_000,
              "emitted": 1_000_000, "rounds": 150.0,
              "piece_draws": np.array([1_500_000, 500_000])}
    return {"window": window, "requests": [], "reduced": red,
            "engine": {"hops": [4, 4]}, "device_kind": "TPU v5 lite"}


@pytest.fixture
def published(monkeypatch):
    """The toy loop's phases, published in a table of this test's own."""
    from repro.obs import tracing
    monkeypatch.setattr(tracing, "_op_phases", {})
    obs.publish_op_phases(LOOP_HLO)


@pytest.fixture
def registry():
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        yield reg
    finally:
        obs.set_registry(prev)


def test_phase_seconds_split_the_loop_ops(published):
    got = phases.phase_seconds(OP_S, phases.loop_phases())
    assert got == pytest.approx({"walk/J0": 0.10, "walk/J1": 0.20,
                                 "member/J1": 0.08, "compact/J1": 0.04,
                                 "emit": 0.03, obs.UNSCOPED: 0.05})


@pytest.mark.parametrize("name,share", [
    ("walk_ms_per_round.bulk", 0.30 / 0.50),
    ("member_ms_per_round.bulk", 0.08 / 0.50),
    ("emit_ms_per_round.bulk", 0.07 / 0.50)])
def test_phase_readers(published, name, share):
    # device_ms_per_round.bulk: 800 ms over 20 runs of 1.5 rounds
    per_round = read("device_ms_per_round.bulk", ctx())
    assert per_round == pytest.approx(800.0 / 30)
    assert read(name, ctx()) == pytest.approx(per_round * share)


def test_phases_and_unscoped_add_up_to_the_round(published):
    c = ctx()
    by_phase = phases.phase_seconds(c["reduced"].op_s, phases.loop_phases())
    kinds = {p.split("/")[0] for p in by_phase}
    total = sum(phases.ms_per_round(c, [k]) for k in kinds)
    assert total == pytest.approx(read("device_ms_per_round.bulk", c))


@pytest.mark.parametrize("name", ["walk_ms_per_round.bulk",
                                  "member_ms_per_round.bulk",
                                  "emit_ms_per_round.bulk"])
def test_phase_readers_return_nothing_without_phases(published, name,
                                                     monkeypatch):
    c = ctx()
    c["reduced"] = None
    assert read(name, c) is None
    # a program that publishes no map for this loop, or has no such call
    monkeypatch.setattr(obs, "op_phases", lambda module: {})
    assert read(name, ctx()) is None
    monkeypatch.delattr(obs, "op_phases")
    assert read(name, ctx()) is None


@pytest.mark.parametrize("name,metric", [
    ("drain_wait_ms_per_call.bulk", "repro_engine_drain_wait_seconds"),
    ("assemble_ms_per_call.bulk", "repro_engine_assemble_seconds"),
    ("request_assemble_ms.bulk", "repro_serve_assemble_seconds")])
def test_histogram_readers(registry, name, metric):
    # a program without the histogram (the parent of this reader), or one
    # that observed nothing, gives no reading
    assert read(name, ctx()) is None
    h = registry.histogram(metric)
    assert read(name, ctx()) is None
    for v in (0.010, 0.030, 0.020):
        h.observe(v)
    assert read(name, ctx()) == pytest.approx(20.0)
