"""Benchmark orchestrator — one module per paper figure (DESIGN.md §9).

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` uses the paper-scale
settings (minutes); default is the quick functional pass.  ``--json PATH``
additionally persists every structured :func:`benchmarks.common.record` row
(plus git sha / device count meta) as one JSON trajectory file.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (bias_ablation, breakdown, data_scale, device_sampler,
               estimation_device, estimation_error, estimation_runtime,
               kernels_bench, reuse, sampling_scaling,
               sharded_scaling, union_engine)
from .common import emit, header, write_json

MODULES = [
    ("estimation_error", estimation_error),     # Fig 4a/4b + 5a
    ("estimation_runtime", estimation_runtime), # Fig 4c/4d
    ("estimation_device", estimation_device),   # device walk+probe batches
    ("sampling_scaling", sampling_scaling),     # Fig 5c/5d/5e
    ("breakdown", breakdown),                   # Fig 5f/5g/5h
    ("data_scale", data_scale),                 # Fig 5b
    ("reuse", reuse),                           # Fig 6a/6b
    ("bias_ablation", bias_ablation),           # DESIGN §7.9 ablation
    ("device_sampler", device_sampler),         # host vs jitted sampler
    ("union_engine", union_engine),             # fused union rounds (backends)
    ("sharded_scaling", sharded_scaling),       # mesh scaling (subprocess)
    ("kernels_bench", kernels_bench),           # kernel micro-bench
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write structured records as a JSON trajectory")
    args = ap.parse_args()
    from repro.launch.jax_cache import use_persistent_cache
    use_persistent_cache()
    header()
    t0 = time.time()
    failures = 0
    for name, mod in MODULES:
        if args.only and name != args.only:
            continue
        ts = time.time()
        try:
            mod.main(small=not args.full)
            emit(f"_section_{name}", (time.time() - ts) * 1e6, "ok")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            traceback.print_exc()
            emit(f"_section_{name}", (time.time() - ts) * 1e6,
                 f"FAILED:{type(e).__name__}")
    emit("_total", (time.time() - t0) * 1e6,
         f"failures={failures}")
    write_json(args.json, full=args.full)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
