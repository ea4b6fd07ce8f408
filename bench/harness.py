"""One run of one benchmark cell.

Everything a cell needs is found by name: its configuration
(``bench/configs/<config>.json`` with ``<config>.py`` beside it), its
traffic mix (``bench/traffic/<traffic>.json``) and every metric but
``setup_s`` (``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns a
number, or ``None`` where a per-layer metric finds nothing to read).  Adding a cell, a mix or a metric adds files and entries in
``BENCHMARK.json``; nothing here changes.

A configuration's ``.py`` provides ``build(cfg)``, which returns the
union as ``bench.reference.tree.Union``: the relations as a join tree
(``parent`` and ``edge``, one attribute or several; ``tree.chain`` writes
a chain in one line), residual relations that close cycles, and the joins
as row masks and pushed-down selections over them.  The reference counts
and judges that union; the program is given the same columns as join specs
built from it (``bench.system.program_joins``).  Where the deployment's
users submit another layout of the same joins, such as a §5.2 vertical
split, the ``.py`` also provides ``program_joins(u)``, which returns the
program's ``JoinSpec`` of every join under the reference's join names, in
cover order.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import check, kernels, load, trace_reduce
from bench.reference import tree

TRACE_SPAN = "bench/window"            # host span around the traced window
REQUEST_SPAN = "bench/request"         # host span around every request
DISPATCH_SPAN = "repro/sample_dispatch"
TRACE_SECONDS = 1.0                    # traced part of a --trace 1 window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class EngineFault(RuntimeError):
    """The program left its device path (fallback, host degrade)."""


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(root: str, name: str):
    base = os.path.join(root, "bench", "configs", name)
    with open(base + ".json") as f:
        cfg = json.load(f)
    return cfg, _module(base + ".py", f"bench_config_{name}")


def load_metric(root: str, name: str):
    return _module(os.path.join(root, "bench", "metrics", name + ".py"),
                   "bench_metric_" + name.replace(".", "_"))


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, kind: str) -> List[dict]:
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def program_seed(seed: int) -> int:
    """Run seeds may exceed 32 bits; the program takes 31."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"the cell needs {chips} TPU chip(s)")
    return devs


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache at one fixed path inside the checkout; every
    program is kept, so only a checkout's first run compiles."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileClock:
    """Backend compiles (persistent-cache reads included) as JAX reports
    them; a compile inside the window is a harness fault."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def counters(sampler) -> Dict[str, object]:
    """The program's exact counters, read from the host."""
    from repro import obs
    reg = obs.get_registry()
    drain = reg.get("repro_engine_drain_seconds")
    d = drain.snapshot().get((), {"sum": 0.0, "count": 0}) if drain else \
        {"sum": 0.0, "count": 0}
    rounds = reg.get("repro_engine_rounds_total")
    eng = sampler._engine
    return {"drain_sum": d["sum"], "drain_count": d["count"],
            "draws": sampler.stats.candidate_draws,
            "emitted": sampler.stats.samples_emitted,
            "rounds": rounds.snapshot().get((), 0.0) if rounds else 0.0,
            "piece_draws": eng.piece_stats[:, 0].copy()}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def run_cell(root: str, bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_start: float, chip: bool = True,
             config_override: Optional[dict] = None,
             fault=None) -> Tuple[dict, List[Tuple[str, float, float]]]:
    """Set up, warm, measure, check.  Returns the result line's object and
    the numbers compared.  ``chip=False`` (tests) skips the look for a TPU;
    ``fault`` (tests) breaks the timed path after set-up."""
    if trace:
        os.environ["REPRO_OBS_TRACE"] = "1"
    import jax
    devs = require_chips(cell["chips"]) if chip else jax.devices()
    dev = devs[0]
    if chip:
        use_compile_cache(root)
    clock = CompileClock()
    from repro.serve import SampleService
    from bench.system import build_sampler, engine_faults, fallbacks

    cfg, mod = load_config(root, cell["config"])
    if config_override:
        cfg = dict(cfg, **config_override)
    mix = load.load_mix(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    u = mod.build(cfg)
    since = fallbacks()
    sizes = tree.intersection_sizes(u)
    pieces = tree.pieces_from(sizes, len(u.joins))
    sampler = build_sampler(u, sizes, program_seed(seed), cfg["round_batch"],
                            config=mod)
    got = [sampler.cover.piece_sizes[j.name] for j in u.joins]
    if got != [float(p) for p in pieces]:
        raise EngineFault(f"program cover {got} != exact pieces {pieces}")
    faults = engine_faults(sampler, since, pallas=chip)
    if faults:
        raise EngineFault("; ".join(faults))
    eng = sampler._engine

    svc = SampleService(sampler, batch=cfg["round_batch"],
                        prefetch=cfg["prefetch"]).start()
    try:
        for _ in range(cfg["prefetch"] + 2):     # compile, fill the queue
            svc.request(cfg["round_batch"])
        deadline = time.perf_counter() + 30
        while svc._queue.qsize() < svc.prefetch and time.perf_counter() < deadline:
            time.sleep(0.01)
        if fault is not None:
            fault(sampler, svc)
        setup_s = time.perf_counter() - t_start
        compiles_before = clock.count

        annotate = ((lambda: jax.profiler.TraceAnnotation(REQUEST_SPAN))
                    if trace else contextlib.nullcontext)
        c0 = counters(sampler)
        traced = {}
        tracer = None
        if trace:
            tracer = threading.Thread(
                target=_trace_part, args=(root, seconds, traced),
                name="bench-tracer")
            tracer.start()
        reqs = load.run_closed(svc, mix, seconds, annotate=annotate)
        if tracer is not None:
            tracer.join()
        c1 = counters(sampler)
        window_compiles = clock.count - compiles_before
    finally:
        svc.stop()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    fell_back = engine_faults(sampler, since, pallas=chip)
    if fell_back:
        raise EngineFault("; ".join(fell_back))
    engine = {"hops": [len(t.node_cfgs) for t in eng.trees]}
    del svc, sampler, eng
    gc.collect()

    t_check = time.perf_counter()
    numbers = check.compare(u, sizes, reqs, cfg)
    check_s = time.perf_counter() - t_check
    failed = sum(1 for r in reqs if r.error is not None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": check.passed(numbers) and failed == 0,
           "attempted": len(reqs), "failed": failed}
    ctx = {"requests": reqs, "window": delta(c0, c1), "engine": engine,
           "device_kind": dev.device_kind}
    if trace:
        ctx.update(traced)
        red = ctx.get("reduced")
        metrics = {}
        for m in metrics_of(bench, cell["name"], "per_layer"):
            v = load_metric(root, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            out["breakdown"] = trace_reduce.breakdown(red)
    else:
        metrics = {}
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            v = (setup_s if m["name"] == "setup_s"
                 else load_metric(root, m["name"]).read(ctx))
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["window_compiles"] = window_compiles
    out["check_s"] = check_s
    return out, numbers


def _trace_part(root: str, seconds: float, into: dict) -> None:
    """Trace ``TRACE_SECONDS`` from the middle of the window and reduce it
    into ``into["reduced"]`` (left out where the trace holds no device
    event)."""
    import jax
    time.sleep(max(0.0, (seconds - TRACE_SECONDS) / 2))
    logdir = os.path.join(root, ".bench_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(TRACE_SPAN):
        time.sleep(TRACE_SECONDS)
    jax.profiler.stop_trace()
    files = sorted(os.path.join(d, f) for d, _, fs in os.walk(logdir)
                   for f in fs if f.endswith(".xplane.pb"))
    if not files:
        return
    tr = trace_reduce.load(files[-1], [TRACE_SPAN, REQUEST_SPAN,
                                       DISPATCH_SPAN])
    for f in files:
        os.remove(f)
    win = trace_reduce.window(tr, TRACE_SPAN)
    if win is not None and (tr.modules or tr.ops):
        into["reduced"] = trace_reduce.reduce(tr, *win,
                                              is_kernel=kernels.is_probe_kernel)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        bench = load_benchmark(root)
        cell = find_cell(bench, args.workload)
        out, numbers = run_cell(root, bench, cell, args.seed, args.seconds,
                                bool(args.trace), t_start)
    except (NoChip, EngineFault, ImportError, KeyError, OSError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in numbers}
    for n, v, lim in numbers:
        print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
