"""Kernel micro-benchmarks (interpret-mode functional timing + op census).

Wall-clock on CPU interpret mode is NOT a TPU number — rows report the
per-call operation counts that the §Roofline kernel story uses (compares the
fused hop against its unfused two-searchsorted + pick decomposition).
"""

from __future__ import annotations

import numpy as np

from repro.kernels import ops, ref

from .common import emit, timed


def main(small: bool = True) -> None:
    rng = np.random.default_rng(0)
    nk = 20_000 if small else 200_000
    nq = 2_000 if small else 20_000
    keys = np.sort(rng.integers(0, nk // 4, nk).astype(np.int64))
    qs = rng.integers(0, nk // 4, nq).astype(np.int64)
    u = rng.random(nq).astype(np.float32)

    t = timed(lambda: ops.searchsorted(keys, qs), repeats=3)
    emit("kernel_searchsorted", t * 1e6, f"nk={nk};nq={nq}")
    t = timed(lambda: ops.walk_hop(keys, qs, u), repeats=3)
    emit("kernel_walk_hop_fused", t * 1e6, "fuses refine+pick (1 pass)")

    B, H, KVH, D, S = (2, 8, 4, 128, 1024) if small else (4, 16, 8, 128, 4096)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    v = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    lens = np.full(B, S)
    t = timed(lambda: ops.decode_attention(q, k, v, lens), repeats=2)
    emit("kernel_decode_attention", t * 1e6, f"B{B}H{H}S{S}")


if __name__ == "__main__":
    main(small=False)
