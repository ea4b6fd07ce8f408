"""Sharded-engine scaling: samples/sec vs mesh size on a 2-join union.

Sweeps the mesh-sharded Algorithm-1 engine
(:class:`repro.core.sharding.ShardedUnionSampler` via
``SetUnionSampler(backend="jax", mesh=...)``) over mesh sizes 1..K on a
2-join TPC-H-style union (UQ1), reporting steady-state samples/sec per mesh
size and the 1→K speedup.  Weak-scaling configuration: the per-shard round
batch is fixed, so a K-shard mesh processes ``K×`` candidates per fused
round — the regime a real multi-device deployment runs in.

Needs K visible devices.  With ``JAX_PLATFORMS=cpu`` the module sets
``XLA_FLAGS=--xla_force_host_platform_device_count=<K>`` *before* importing
jax when run as a script.  From ``benchmarks.run`` the sweep runs in the
orchestrator's process when it holds accelerator devices (a chip belongs to
one process, so a child could not reach them); on the CPU it re-executes
itself in a subprocess with the flag set.

Reading the numbers: host-platform devices *emulate* a mesh by running each
shard's program in its own thread of one CPU, so the attainable samples/sec
speedup is bounded by the physical core count, not by the mesh size — on a
>=8-core host the 1→8 sweep shows the >=3x target; on a 2-core container it
saturates near the all-cores single-device rate (use ``--require-speedup``
to gate only on real parallel hardware).

    PYTHONPATH=src python -m benchmarks.sharded_scaling --smoke
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

_DEF_DEVICES = 8


def _sweep(args) -> int:
    """Run the mesh sweep (assumes the device count is already forced)."""
    import time

    import numpy as np

    from repro.core.framework import estimate_union, warmup
    from repro.core.sharding import make_sampler_mesh
    from repro.core.union_sampler import SetUnionSampler
    from repro.data.workloads import uq1
    from repro.serve.service import SampleService

    from benchmarks.common import emit, record, write_json

    import jax
    ndev = len(jax.devices())
    wl = uq1(scale=args.scale, overlap=0.5, seed=1, n_joins=2)
    wr = warmup(wl.cat, wl.joins, method="histogram")
    est = estimate_union(wr.oracle)

    worlds = [w for w in (1, 2, 4, 8, 16) if w <= ndev]
    cores = os.cpu_count() or 1
    rates = {}
    last = None
    for world in worlds:
        mesh = make_sampler_mesh(world=world)
        s = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=7,
                            backend="jax", round_batch=args.round_batch,
                            mesh=mesh)
        s.sample(args.warm)                  # compile + warm the banks
        s.sample(args.samples)               # compile the n-capacity loop
        bt = sum(getattr(s._engine, "piece_batches", None)
                 or [args.round_batch * world])
        it0, cd0 = s.stats.iterations, s.stats.candidate_draws
        t0 = time.perf_counter()
        s.sample(args.samples)
        dt = time.perf_counter() - t0
        rate = args.samples / max(dt, 1e-9)
        rates[world] = rate
        last = s
        emit(f"sharded_union_w{world}", dt / args.samples * 1e6,
             f"{rate:,.0f} samples/s ({world} shards, "
             f"per-shard round_batch={args.round_batch})")
        record(f"sharded_union_w{world}", world=world,
               round_batch=args.round_batch, n=args.samples, seconds=dt,
               samples_per_s=rate, cpu_count=cores,
               rounds=(s.stats.iterations - it0) // max(bt, 1),
               psi=(s.stats.candidate_draws - cd0) / args.samples)

    # pipelined serving path: dispatch-then-drain double buffering hides the
    # host-side batch assembly behind the next round's device compute
    if last is not None:
        world = worlds[-1]
        with SampleService(last, batch=max(args.round_batch, 4096),
                           prefetch=2) as svc:
            svc.request(args.warm)           # producer warm + queue primed
            t0 = time.perf_counter()
            svc.request(args.samples)
            dt = time.perf_counter() - t0
        rate = args.samples / max(dt, 1e-9)
        emit(f"serve_pipelined_w{world}", dt / args.samples * 1e6,
             f"{rate:,.0f} samples/s through SampleService "
             f"(async double-buffered rounds, {world} shards)")
        record(f"serve_pipelined_w{world}", world=world,
               round_batch=args.round_batch, n=args.samples, seconds=dt,
               samples_per_s=rate, cpu_count=cores, pipelined=True)
    if len(worlds) > 1:
        speedup = rates[worlds[-1]] / max(rates[1], 1e-9)
        emit("sharded_scaling", 0.0,
             f"{speedup:.2f}x samples/s from 1 -> {worlds[-1]} shards "
             f"(host has {cores} cores; emulated multi-device scaling is "
             f"bounded by min(shards, cores)/shard-efficiency)")
        record("sharded_scaling_summary", worlds=worlds, cpu_count=cores,
               speedup=speedup,
               speedup_gated=bool(args.require_speedup)
               and cores >= worlds[-1])
        if args.require_speedup:
            if cores < worlds[-1]:
                # host-platform shards emulate devices on threads of the
                # same CPUs — with fewer physical cores than shards the
                # "scaling" number measures core contention, not the engine
                print(f"SKIP: --require-speedup {args.require_speedup}x not "
                      f"gated ({cores} physical cores < {worlds[-1]} shards; "
                      "emulated mesh is core-bound)", flush=True)
            elif speedup < args.require_speedup:
                print(f"FAIL: speedup {speedup:.2f}x < required "
                      f"{args.require_speedup}x", flush=True)
                return 1
    write_json(args.json, bench="sharded_scaling", scale=args.scale)
    return 0


def _respawn(argv, devices: int) -> int:
    """Re-run this module in a subprocess with the device count forced."""
    env = dict(os.environ)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={devices}"])
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-m", "benchmarks.sharded_scaling",
                        *argv], env=env)
    return r.returncode


def main(small: bool = True) -> None:
    """benchmarks.run entry point: jax is already live there."""
    import jax
    argv = ["--smoke"] if small else []
    if jax.default_backend() == "cpu":
        rc = _respawn(argv, _DEF_DEVICES)
    else:
        rc = _sweep(_parse(argv))
    if rc:
        raise RuntimeError(f"sharded_scaling sweep failed (rc={rc})")


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=_DEF_DEVICES)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--samples", type=int, default=None)
    ap.add_argument("--warm", type=int, default=None)
    ap.add_argument("--round-batch", type=int, default=None)
    ap.add_argument("--require-speedup", type=float, default=0.0,
                    help="exit non-zero when 1->K speedup is below this")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write structured results (BENCH_serve.json)")
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = 0.05 if args.smoke else 0.2
    if args.samples is None:
        args.samples = 60_000 if args.smoke else 400_000
    if args.warm is None:
        args.warm = 4096
    if args.round_batch is None:
        args.round_batch = 1024 if args.smoke else 4096
    return args


if __name__ == "__main__":
    args = _parse()
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count="
                                   f"{args.devices}").strip()
    from benchmarks.common import header
    header()
    sys.exit(_sweep(args))
