"""The trace reduction on a hand-built trace and on one recorded on the
CPU (no accelerator)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import kernels, trace_reduce as tr  # noqa: E402
from bench.trace_reduce import Event  # noqa: E402

MS = 1_000_000  # ns

FENCE = ('%_searchsorted_i32.7 = (s32[32,1,256], s32[32,1,256]) custom-call('
         's32[32,1,256] %a, s32[32,1,256] %b, s32[220,128] %c, s32[220,128] '
         '%d), custom_call_target="tpu_custom_call"')


def hand_built():
    """Window [0, 100] ms: program runs 10-40 and 50-90 ms (and one run
    20-60 ms on a second chip); a loop op holds a fusion and a kernel."""
    modules = {"/device:TPU:0": [Event("jit_loop_fn(1)", 10 * MS, 30 * MS),
                                 Event("jit_loop_fn(1)", 50 * MS, 40 * MS),
                                 Event("jit_convert(2)", 95 * MS, 10 * MS)],
               "/device:TPU:1": [Event("jit_loop_fn(1)", 20 * MS, 40 * MS)]}
    ops = {"/device:TPU:0": [
        Event("%while.3 = (s32[5]) while(s32[5] %x)", 10 * MS, 30 * MS),
        Event("%fusion.1 = f32[8] fusion(f32[8] %y)", 12 * MS, 5 * MS),
        Event(FENCE, 20 * MS, 8 * MS),
        Event(FENCE, 50 * MS, 4 * MS),
        Event(FENCE, 96 * MS, 2 * MS)]}
    host = [Event("bench/window", 0, 100 * MS),
            Event("repro/sample_dispatch", 41 * MS, 5 * MS),
            Event("bench/request", 0, 48 * MS)]
    return tr.Trace(modules, ops, host)


def test_busy_idle_and_gaps():
    t = hand_built()
    win = tr.window(t, "bench/window")
    assert win == (0, 100 * MS)
    red = tr.reduce(t, *win, is_kernel=kernels.is_probe_kernel)
    # chip 0 busy 30 + 40 + 5 (clipped at 100) = 75 ms; chip 1 busy 40 ms
    assert red.busy_s == pytest.approx((0.075 + 0.040) / 2)
    assert red.window_s == pytest.approx(0.1)
    assert red.idle_share == pytest.approx(1 - 0.0575 / 0.1)
    # runs wholly inside: two loops on chip 0, one on chip 1
    assert red.module_runs == {"jit_loop_fn": 3}
    assert red.module_s["jit_loop_fn"] == pytest.approx(0.110)
    # kernels of the counted runs only (the one at 96 ms is outside them);
    # averaged over the two chips
    assert red.kernel_s == pytest.approx(0.012 / 2)
    # self time: the loop op less the fusion and the kernel it holds
    assert red.op_s["while.3"] == pytest.approx(0.030 - 0.005 - 0.008)
    assert red.op_s["fusion.1"] == pytest.approx(0.005)
    # chip 0 gaps: 0-10 (request open), 40-50 (dispatch covers 41-46,
    # the request 40-48: the request covers most), 90-95
    labels = dict((round(s, 3), []) for _, s in red.gaps)
    for name, s in red.gaps:
        labels[round(s, 3)].append(name)
    assert "bench/request" in labels[0.01]
    assert red.gaps[0][1] == pytest.approx(0.04)     # chip 1: 60-100 ms
    bd = tr.breakdown(red, top=2)
    assert len(bd["device_ops"]) == 2 and len(bd["idle_gaps"]) == 2
    assert bd["device_ops"][0][0] == "while.3"


def test_label_prefers_the_span_covering_most():
    host = [Event("a", 0, 10), Event("b", 4, 2), Event("c", 8, 20)]
    assert tr.label((3, 7), host) == "a"
    assert tr.label((9, 30), host) == "c"
    assert tr.label((40, 50), host) == "no span"


def test_kernel_names():
    assert kernels.is_probe_kernel(FENCE)
    assert not kernels.is_probe_kernel("%fusion.1 = f32[8] fusion(f32[8] %y)")
    assert not kernels.is_probe_kernel(
        '%other.1 = s32[8] custom-call(s32[8] %y), '
        'custom_call_target="tpu_custom_call"')
    assert kernels.probe_bytes([8192, 256], [4, 4]) == 12 * 4 * (8192 + 256)


def test_an_empty_window_or_trace_is_refused():
    with pytest.raises(ValueError):
        tr.reduce(hand_built(), 5, 5)
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace({}, {}, []), 0, 10)


def test_load_a_recorded_cpu_trace(tmp_path):
    """A trace recorded here holds the host spans (the CPU has no device
    plane); ``load`` reads them on the profiler's clock."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        with jax.profiler.TraceAnnotation("bench/request"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(str(p) for p in tmp_path.rglob("*.xplane.pb"))
    t = tr.load(path, ["bench/window", "bench/request"])
    w = tr.window(t, "bench/window")
    req = tr.window(t, "bench/request")
    assert w is not None and req is not None
    assert w[0] <= req[0] <= req[1] <= w[1]
