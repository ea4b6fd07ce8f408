"""Batched serving drivers.

Two modes:

* ``--mode lm`` (default) — continuous-batching-lite greedy decoding.
  Maintains a fixed pool of B decode slots; finished requests are replaced
  from the queue (continuous batching), each slot carrying its own length —
  the per-row ``lengths`` vector is exactly what ``decode_step`` masks on.

      PYTHONPATH=src python -m repro.launch.serve --arch minitron-8b --smoke \
          --requests 8 --max-new 16

* ``--mode samples`` — serve uniform union samples through the streaming
  :class:`repro.serve.SampleService` (prefetched sample queue + request
  batching) over the device-resident engine, optionally mesh-sharded:
  ``--shards k`` builds a k-device mesh and runs the shard_map'd
  Algorithm-1 rounds of ``repro.core.sharding`` (on CPU set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first).
  ``--metrics-port P`` additionally starts a background HTTP thread with
  ``/metrics`` (Prometheus text: request-latency histogram + p50/p99,
  queue depth, per-replica engine stats) and ``/healthz`` (``P=0`` binds an
  ephemeral port, printed at startup); ``--linger S`` keeps the service and
  endpoint up for S extra seconds after the request loop so external
  scrapers can collect.

      PYTHONPATH=src python -m repro.launch.serve --mode samples \
          --workload UQ1 --requests 16 --samples 4096 --backend jax \
          --shards 4 --metrics-port 9100
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


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
    ap.add_argument("--mode", choices=("lm", "samples"), default="lm")
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    # samples mode
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

    if args.mode == "samples":
        serve_samples(args)
        return

    from ..configs import get_config, get_smoke_config
    from ..models.serve import decode_step, init_cache
    from ..models.transformer import init_params

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(cfg, seed=args.seed)
    rng = np.random.default_rng(args.seed)

    B = args.slots
    cache = init_cache(cfg, B, args.max_len)
    dstep = jax.jit(lambda c, t, l: decode_step(params, cfg, c, t, l))

    # request queue: (request_id, prompt tokens)
    queue: List = [(i, rng.integers(4, cfg.vocab, rng.integers(2, 6)).tolist())
                   for i in range(args.requests)]
    slots = [None] * B          # (req_id, tokens emitted, remaining prompt)
    lengths = np.zeros(B, np.int64)
    current = np.full(B, 1, np.int64)   # BOS
    done: List = []
    t0 = time.time()
    steps = 0

    def refill():
        for b in range(B):
            if slots[b] is None and queue:
                rid, prompt = queue.pop(0)
                slots[b] = [rid, [], list(prompt)]
                lengths[b] = 0
                current[b] = 1

    refill()
    while any(s is not None for s in slots):
        toks = jnp.asarray(current.reshape(B, 1), jnp.int32)
        lens = jnp.asarray(lengths, jnp.int32)
        cache, logits = dstep(cache, toks, lens)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        steps += 1
        for b in range(B):
            if slots[b] is None:
                continue
            rid, out, prompt = slots[b]
            lengths[b] += 1
            if prompt:                       # still consuming the prompt
                current[b] = prompt.pop(0)
            else:
                out.append(int(nxt[b]))
                current[b] = int(nxt[b])
                if len(out) >= args.max_new or lengths[b] >= args.max_len - 1:
                    done.append((rid, out))
                    slots[b] = None
        refill()
    dt = time.time() - t0
    print(f"served {len(done)} requests, {steps} decode steps in {dt:.1f}s "
          f"({steps/max(dt,1e-9):.1f} steps/s, batch={B})", flush=True)
    for rid, out in sorted(done)[:4]:
        print(f"  req {rid}: {out[:10]}", flush=True)


if __name__ == "__main__":
    main()
