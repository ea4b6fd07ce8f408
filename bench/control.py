"""The control of the comparison that decides ``correct``.

The reference sampler is put in the program's place and computed one
precision below what the configuration states (its EW prefix sums and cover
CDF are float32; the control keeps them in bfloat16).  The same comparison
then has to call its stream not correct.  Beside it the reference at the
stated precision (float32) has to pass.

    python bench/control.py --config uq1_sf1 --samples 5650000 \
        --seeds 11,12,13 --dtype bfloat16 --draws-per-sample 4

With ``--fault drop_last`` the reference at the stated precision stands in
for a program whose range probes never return the last row of a range in
the configuration's ``position_relation`` (for UQ1 the last line of every
order): every sample it serves is a member of its home piece, but part of
the union is never reached.

``--samples`` is what one run of the cell serves.  ``--draws-per-sample``
caps the candidate draws of each piece (a few times the program's own
draws per sample), so a control too broken to finish still ends and is
judged on the samples it produced.  Prints one JSON line per seed with the
numbers compared.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, load  # noqa: E402
from bench.harness import load_config  # noqa: E402
from bench.reference import tree  # noqa: E402


@dataclasses.dataclass
class Served:
    rows: dict
    home: np.ndarray

    def __len__(self) -> int:
        return int(self.home.shape[0])


def as_requests(rows, home, size: int):
    """The stream cut into requests of ``size`` rows, as clients get it."""
    out = []
    for lo in range(0, home.shape[0], size):
        hi = min(lo + size, home.shape[0])
        out.append(load.Request(due=0.0, asked=hi - lo, result=Served(
            {a: c[lo:hi] for a, c in rows.items()}, home[lo:hi])))
    return out


def readings(cfg: dict, u, dtype, samples: int, seeds,
             draws_per_sample=None, drop_last: bool = False):
    """(seed, numbers compared, samples produced, seconds) per seed."""
    sizes = tree.intersection_sizes(u)
    pieces = tree.pieces_from(sizes, len(u.joins))
    at = ([r.name for r in u.rels].index(cfg["check"]["position_relation"])
          if drop_last else None)
    sampler = tree.UnionSampler(u, pieces, dtype, drop_last=at)
    for seed in seeds:
        t0 = time.perf_counter()
        rows, home = sampler.sample(samples, np.random.default_rng(seed),
                                    draws_per_sample)
        numbers = check.compare(u, sizes, as_requests(rows, home,
                                                      cfg["round_batch"]), cfg)
        yield seed, numbers, home.shape[0], time.perf_counter() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--samples", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--draws-per-sample", type=float, default=None)
    ap.add_argument("--fault", choices=("none", "drop_last"), default="none")
    args = ap.parse_args(argv)
    import ml_dtypes
    dtype = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[args.dtype]
    cfg, mod = load_config(ROOT, args.config)
    u = mod.build(cfg)
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, numbers, produced, secs in readings(
            cfg, u, dtype, args.samples, seeds, args.draws_per_sample,
            args.fault == "drop_last"):
        print(json.dumps({"config": args.config, "dtype": args.dtype,
                          "fault": args.fault,
                          "seed": seed, "samples": args.samples,
                          "produced": produced, "seconds": secs,
                          "correct": check.passed(numbers),
                          "checks": {n: v for n, v, _ in numbers}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
