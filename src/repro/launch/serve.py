"""Serve uniform union samples from the command line.

Samples go through the streaming :class:`repro.serve.SampleService`
(prefetched sample queue + request batching) over the device-resident
engine, optionally mesh-sharded: ``--shards k`` builds a k-device mesh and
runs the shard_map'd Algorithm-1 rounds of ``repro.core.sharding`` (on CPU
set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first).
``--metrics-port P`` additionally starts a background HTTP thread with
``/metrics`` (Prometheus text: request-latency histogram + p50/p99, queue
depth, per-replica engine stats) and ``/healthz`` (``P=0`` binds an
ephemeral port, printed at startup); ``--linger S`` keeps the service and
endpoint up for S extra seconds after the request loop so external scrapers
can collect.

    PYTHONPATH=src python -m repro.launch.serve \
        --workload UQ1 --requests 16 --samples 4096 --backend jax \
        --shards 4 --metrics-port 9100
"""

from __future__ import annotations

import argparse
import time
from typing import Optional


def build_sampler(workload: str, scale: float, seed: int = 0,
                  backend: str = "jax", round_batch: int = 8192,
                  shards: int = 0, plan: str = "static", **workload_kw):
    """The served union: workload data, histogram warm-up, the union
    estimate and a ``SetUnionSampler`` over it.  ``shards > 0`` builds the
    mesh-sharded engine over that many devices.  Returns
    ``(workload, sampler)``."""
    from ..core.framework import estimate_union, warmup
    from ..core.union_sampler import SetUnionSampler
    from ..data.workloads import WORKLOADS

    wl = WORKLOADS[workload](scale=scale, seed=seed, **workload_kw)
    wr = warmup(wl.cat, wl.joins, method="histogram")
    est = estimate_union(wr.oracle)
    mesh = None
    if shards:
        from ..core.sharding import make_sampler_mesh
        mesh = make_sampler_mesh(world=shards)
    sampler = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=seed,
                              backend=backend, round_batch=round_batch,
                              mesh=mesh, plan=plan)
    return wl, sampler


def serve_samples(args) -> None:
    """Union-sample serving loop through the streaming SampleService."""
    from ..serve import SampleService

    _, sampler = build_sampler(args.workload, args.scale, seed=args.seed,
                               backend=args.backend,
                               round_batch=args.round_batch,
                               shards=args.shards, plan=args.plan)
    sampler.sample(256)                     # warm up / compile
    metrics = None
    if args.metrics_port is not None:
        from .. import obs
        metrics = obs.MetricsServer(port=args.metrics_port).start()
        print(f"metrics: {metrics.url}/metrics  (health: "
              f"{metrics.url}/healthz)", flush=True)
    try:
        with SampleService(sampler, batch=args.round_batch,
                           prefetch=args.prefetch) as svc:
            svc.request(args.samples)       # fill the pipeline
            t0 = time.time()
            served = 0
            for rid in range(args.requests):
                ss = svc.request(args.samples)
                served += len(ss)
            dt = time.time() - t0
            st = svc.stats()
            if args.linger > 0:             # let external scrapers collect
                print(f"lingering {args.linger:.0f}s for scrapes...",
                      flush=True)
                time.sleep(args.linger)
        shard_note = f", shards={args.shards}" if args.shards else ""
        print(f"served {args.requests} requests x {args.samples} samples "
              f"({served} total) in {dt:.2f}s — "
              f"{served/max(dt, 1e-9):,.0f} samples/s "
              f"[backend={args.backend}{shard_note}; "
              f"psi={st.psi():.2f}, draws={st.candidate_draws}, "
              f"rejects={st.cover_rejects}]",
              flush=True)
        from .. import obs
        if obs.enabled():
            reg = obs.get_registry()
            hist = reg.get("repro_serve_request_seconds")
            if hist is not None and hist.quantile(0.5) > 0:
                print(f"request latency: p50={hist.quantile(0.5)*1e3:.2f}ms "
                      f"p99={hist.quantile(0.99)*1e3:.2f}ms", flush=True)
    finally:
        if metrics is not None:
            metrics.stop()


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default="UQ1")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--round-batch", type=int, default=8192)
    ap.add_argument("--plan", choices=("static", "adaptive"),
                    default="static",
                    help="round planner: 'adaptive' budgets candidates by "
                         "acceptance EMAs inside the device loop")
    ap.add_argument("--shards", type=int, default=0,
                    help="mesh size for the sharded engine (0 = unsharded)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="prefetched sample batches in the serve queue")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics + /healthz on this port "
                         "(0 = ephemeral, URL printed at startup)")
    ap.add_argument("--linger", type=float, default=0.0,
                    help="keep the service + /metrics up this many seconds "
                         "after the request loop (for external scrapers)")
    args = ap.parse_args(argv)
    from .jax_cache import use_persistent_cache
    use_persistent_cache()
    serve_samples(args)


if __name__ == "__main__":
    main()
