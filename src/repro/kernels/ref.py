"""Numpy oracles for every Pallas kernel (allclose targets)."""

from __future__ import annotations

from typing import Tuple

import numpy as np


def searchsorted_ref(keys: np.ndarray, queries: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    lo = np.searchsorted(keys, queries, side="left")
    hi = np.searchsorted(keys, queries, side="right")
    return lo.astype(np.int64), hi.astype(np.int64)


def walk_hop_ref(keys: np.ndarray, queries: np.ndarray, u: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    lo, hi = searchsorted_ref(keys, queries)
    d = hi - lo
    off = np.minimum(np.floor(u * np.maximum(d, 1)).astype(np.int64),
                     np.maximum(d - 1, 0))
    return lo + off, d


def ranged_weighted_pick_ref(cs: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                             u: np.ndarray) -> np.ndarray:
    """Weighted pick inside [lo, hi) via prefix sums cs (len n+1)."""
    tot = cs[hi] - cs[lo]
    tgt = cs[lo] + u * np.maximum(tot, 1e-300)
    pos = np.searchsorted(cs, tgt, side="right") - 1
    return np.clip(pos, lo, np.maximum(hi - 1, lo))

