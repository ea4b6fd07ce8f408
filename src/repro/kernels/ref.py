"""Pure-jnp / numpy oracles for every Pallas kernel (allclose targets)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
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


def decode_attention_ref(q, k, v, lengths, scale: Optional[float] = None,
                         softcap: float = 0.0, window: int = 0) -> jnp.ndarray:
    """q (B,H,D), k/v (B,S,KVH,D), lengths (B,) -> (B,H,D). fp32 math."""
    q = jnp.asarray(q, jnp.float32)
    k = jnp.asarray(k, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    B, H, D = q.shape
    S, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    qg = q.reshape(B, KVH, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k) * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    spos = jnp.arange(S)[None, :]
    lens = jnp.asarray(lengths, jnp.int32)[:, None]
    mask = spos < lens
    if window > 0:
        mask &= spos >= (lens - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v)
    return out.reshape(B, H, D)
