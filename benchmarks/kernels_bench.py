"""Kernel micro-benchmarks: interpret-mode functional timing of the probe
and the fused walk hop.

Wall-clock on CPU interpret mode is NOT a TPU number; the chip benchmark
(`bench/`) measures the kernels' time and roofline share on the device.
"""

from __future__ import annotations

import numpy as np

from repro.kernels import ops

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


if __name__ == "__main__":
    main(small=False)
