"""Kernels: the range probe's (``kernels/searchsorted.py``: fence sweep and
refine) share of its memory roofline in the traced window.  The bytes are
the least any probe must move (``bench/kernels.py``) for the probes the
loop runs inside the traced window made: a loop run's candidate draws (the
program's exact per-piece counters over the whole window, per run) times
its tree's non-root nodes.  The time is the device time of the probe's
Pallas calls in those runs; the bandwidth is the chip's published peak."""

from bench import kernels, peaks

LOOP = "jit_loop_fn"


def read(ctx):
    red, w, eng = ctx.get("reduced"), ctx["window"], ctx["engine"]
    if (red is None or red.kernel_s <= 0 or not red.module_runs.get(LOOP)
            or not w["drain_count"]):
        return None
    per_run = kernels.probe_bytes(w["piece_draws"], eng["hops"]) \
        / w["drain_count"]
    nbytes = red.module_runs[LOOP] * per_run
    bw = peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / red.kernel_s
