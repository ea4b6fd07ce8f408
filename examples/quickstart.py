"""Quickstart: sample i.i.d. tuples from a union of joins, then batch them
as tokens for a trainer.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (JoinSampler, SetUnionSampler, estimate_union,
                        exact_union_size, warmup)
from repro.data.encode import TokenEncoder
from repro.data.pipeline import UnionSamplePipeline
from repro.data.workloads import uq3


def main() -> None:
    # 1. a union of three joins over TPC-H-lite (different schemas per join)
    wl = uq3(scale=0.02, overlap=0.3, seed=0)
    print(f"workload {wl.name}: {[j.name for j in wl.joins]}")
    for j in wl.joins:
        kind = "cyclic" if j.is_cyclic else ("chain" if j.is_chain else "acyclic")
        print(f"  {j.name}: {kind}, relations="
              f"{[n.relation.name for n in j.nodes]}")

    # 2. warm-up: estimate |J_i| and |U| three ways
    for method in ("histogram", "random_walk", "exact"):
        wr = warmup(wl.cat, wl.joins, method=method, rw_max_walks=4000)
        est = estimate_union(wr.oracle)
        print(f"  |U| via {method:11s}: {est.union_size_cover:10.1f} "
              f"(eq1: {est.union_size_eq1:10.1f}, {wr.seconds*1e3:7.1f} ms)")
    print(f"  |U| exact (FULLJOIN): {exact_union_size(wl.cat, wl.joins)}")

    # 3. Algorithm 1: uniform i.i.d. samples from the set union
    wr = warmup(wl.cat, wl.joins, method="random_walk", rw_max_walks=4000)
    est = estimate_union(wr.oracle)
    sampler = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=0)
    ss = sampler.sample(1000)
    print(f"sampled {len(ss)} tuples; per-join credit: "
          f"{np.bincount(ss.home, minlength=len(wl.joins)).tolist()}; "
          f"cover rejects: {ss.stats.cover_rejects}")

    # 4. the same stream as fixed-shape token batches for a trainer
    enc = TokenEncoder(sorted(wl.joins[0].output_attrs), vocab_size=2048)
    pipe = UnionSamplePipeline(sampler, enc, batch=4, seq_len=128)
    tokens, targets = pipe.next_batch()
    print(f"token batch {tokens.shape} from {pipe.stats.tuples} tuples")


if __name__ == "__main__":
    main()
