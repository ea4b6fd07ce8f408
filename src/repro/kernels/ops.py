"""Public jit'd wrappers for the Pallas kernels.

``interpret`` is True only on the CPU backend, where Pallas has no compiler.
On any other backend the kernels lower via Mosaic, or the call fails: a
kernel never falls back to the interpreter on an accelerator.

``ranged_weighted_pick`` — the Exact-Weight child-pick primitive — composes
the searchsorted kernel over the *bit-cast* prefix-sum array: non-negative
float32 IEEE bit patterns are order-isomorphic to their int32 views, so the
lexicographic integer compare machinery applies unchanged (hi word = 0).
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

from .searchsorted import PreparedKeys, searchsorted_pallas
from .walk import walk_hop_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def searchsorted(keys, queries) -> Tuple[np.ndarray, np.ndarray]:
    return searchsorted_pallas(keys, queries, interpret=default_interpret())


def walk_hop(keys, queries, u) -> Tuple[np.ndarray, np.ndarray]:
    return walk_hop_pallas(keys, queries, u, interpret=default_interpret())


def ranged_weighted_pick(cs: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                         u: np.ndarray) -> np.ndarray:
    """EW pick: position in [lo,hi) with prob ∝ weight, via prefix sums cs.

    cs must be non-negative float32-representable prefix sums (len n+1).
    """
    cs32 = np.asarray(cs, dtype=np.float32)
    tot = cs32[hi] - cs32[lo]
    tgt = (cs32[lo] + np.asarray(u, np.float32) * np.maximum(tot, 1e-30))
    # order-isomorphic bit-cast: non-negative float32 -> int32
    cs_bits = cs32.view(np.int32).astype(np.int64)
    tgt_bits = np.minimum(tgt, np.nextafter(cs32[-1], -np.inf)).astype(np.float32)
    tgt_bits = tgt_bits.view(np.int32).astype(np.int64)
    _, le_count = searchsorted(cs_bits, tgt_bits)
    pos = le_count - 1
    return np.clip(pos, lo, np.maximum(hi - 1, lo))
