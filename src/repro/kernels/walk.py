"""Fused wander-join hop kernel.

One random-walk hop for B walks advances each walk's frontier key through the
next relation's sorted index: ``[lo, hi) = range of matches``, then a ranged
uniform pick ``pos = lo + floor(u * d)``.  This kernel fuses the phase-B
refinement of :mod:`searchsorted` with the pick + probability update, so a hop
is: fence search (phase A, in one or two levels: ``fence_blocks``) → XLA
row gather → **fused refine+pick** → XLA neighbor gather.  Dead walks
(``d == 0``) are masked, matching the paper's "failed random walk, p(t) = 0"
semantics.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .searchsorted import (QUERY_TILE, PreparedKeys, _pad_np, fence_blocks,
                           gather_rows, row_call, row_counts, split64_np)


def hop_refine_pick_kernel(*refs):
    """Fused: exact [lo,hi) + ranged uniform pick + degree output."""
    lo, hi = row_counts(*refs[:8])
    u_ref, pos_ref, deg_ref = refs[8:]
    d = hi - lo
    u = u_ref[0, 0, :]
    off = jnp.floor(u * jnp.maximum(d, 1).astype(jnp.float32)).astype(jnp.int32)
    off = jnp.minimum(off, jnp.maximum(d - 1, 0))
    pos_ref[0, 0, :] = lo + off
    deg_ref[0, 0, :] = d


@functools.partial(jax.jit, static_argnames=("interpret",))
def _hop_i32(q_hi3, q_lo3, u3, t_hi2, t_lo2, f_hi2, f_lo2, keys2d_hi,
             keys2d_lo, interpret: bool = True):
    blk_l, blk_r = fence_blocks(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2,
                                keys2d_hi.shape[0], interpret)
    return row_call(hop_refine_pick_kernel, q_hi3, q_lo3, blk_l, blk_r,
                    gather_rows(keys2d_hi, keys2d_lo, blk_l, blk_r),
                    extra=(u3,), interpret=interpret)


def walk_hop_pallas(keys, queries, u, interpret: bool = True
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """One hop: (pos, degree) per walk. keys sorted; u uniform [0,1);
    queries below INT64_MAX, the key padding's sentinel."""
    prep = keys if isinstance(keys, PreparedKeys) else PreparedKeys(keys)
    q = np.asarray(queries, dtype=np.int64)
    nq = q.shape[0]
    qp = _pad_np(q, QUERY_TILE, 0)
    up = _pad_np(np.asarray(u, dtype=np.float32), QUERY_TILE, 0)
    q_hi, q_lo = split64_np(qp)
    qt = qp.shape[0] // QUERY_TILE
    pos, deg = _hop_i32(
        jnp.asarray(q_hi.reshape(qt, 1, QUERY_TILE)),
        jnp.asarray(q_lo.reshape(qt, 1, QUERY_TILE)),
        jnp.asarray(up.reshape(qt, 1, QUERY_TILE)),
        *prep.arrays(), interpret=interpret)
    pos = np.minimum(np.asarray(pos).reshape(-1)[:nq], max(prep.n - 1, 0))
    deg = np.asarray(deg).reshape(-1)[:nq]
    return pos, deg
