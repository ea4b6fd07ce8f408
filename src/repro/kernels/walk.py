"""Fused wander-join hop kernel.

One random-walk hop for B walks advances each walk's frontier key through the
next relation's sorted index: ``[lo, hi) = range of matches``, then a ranged
uniform pick ``pos = lo + floor(u * d)``.  This kernel fuses the phase-B
refinement of :mod:`searchsorted` with the pick + probability update, so a hop
is: fence sweep (phase A) → XLA row gather → **fused refine+pick** → XLA
neighbor gather.  Dead walks (``d == 0``) are masked, matching the paper's
"failed random walk, p(t) = 0" semantics.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .searchsorted import (KEY_BLOCK, QUERY_TILE, PreparedKeys, _le, _lt,
                           _pad_np, fence_blocks, split64_np, tile_spec,
                           tiles_shape)


def hop_refine_pick_kernel(q_hi_ref, q_lo_ref, blk_l_ref, blk_r_ref,
                           row_l_hi_ref, row_l_lo_ref,
                           row_r_hi_ref, row_r_lo_ref,
                           u_ref, pos_ref, deg_ref):
    """Fused: exact [lo,hi) + ranged uniform pick + degree output."""
    q_hi = q_hi_ref[0, 0, :][:, None]
    q_lo = q_lo_ref[0, 0, :][:, None]
    lt = _lt(row_l_hi_ref[0], row_l_lo_ref[0], q_hi, q_lo)
    le = _le(row_r_hi_ref[0], row_r_lo_ref[0], q_hi, q_lo)
    lo = blk_l_ref[0, 0, :] * KEY_BLOCK + jnp.sum(lt.astype(jnp.int32), axis=1)
    hi = blk_r_ref[0, 0, :] * KEY_BLOCK + jnp.sum(le.astype(jnp.int32), axis=1)
    d = hi - lo
    u = u_ref[0, 0, :]
    off = jnp.floor(u * jnp.maximum(d, 1).astype(jnp.float32)).astype(jnp.int32)
    off = jnp.minimum(off, jnp.maximum(d - 1, 0))
    pos_ref[0, 0, :] = lo + off
    deg_ref[0, 0, :] = d


@functools.partial(jax.jit,
                   static_argnames=("n_chunks", "n_fences", "interpret"))
def _hop_i32(q_hi3, q_lo3, u3, f_hi2, f_lo2, keys2d_hi, keys2d_lo,
             n_chunks: int, n_fences: int, interpret: bool = True):
    qt = q_hi3.shape[0]
    blk_l, blk_r = fence_blocks(q_hi3, q_lo3, f_hi2, f_lo2, n_chunks,
                                n_fences, interpret)

    bl, br = blk_l.reshape(-1), blk_r.reshape(-1)
    rl_hi = keys2d_hi[bl].reshape(qt, QUERY_TILE, KEY_BLOCK)
    rl_lo = keys2d_lo[bl].reshape(qt, QUERY_TILE, KEY_BLOCK)
    rr_hi = keys2d_hi[br].reshape(qt, QUERY_TILE, KEY_BLOCK)
    rr_lo = keys2d_lo[br].reshape(qt, QUERY_TILE, KEY_BLOCK)

    tile = tile_spec()
    row = pl.BlockSpec((1, QUERY_TILE, KEY_BLOCK), lambda i: (i, 0, 0))
    pos, deg = pl.pallas_call(
        hop_refine_pick_kernel,
        grid=(qt,),
        in_specs=[tile, tile, tile, tile, row, row, row, row, tile],
        out_specs=[tile, tile],
        out_shape=[tiles_shape(qt)] * 2,
        interpret=interpret,
    )(q_hi3, q_lo3, blk_l, blk_r, rl_hi, rl_lo, rr_hi, rr_lo, u3)
    return pos, deg


def walk_hop_pallas(keys, queries, u, interpret: bool = True
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """One hop: (pos, degree) per walk. keys sorted; u uniform [0,1)."""
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
        prep.f_hi2, prep.f_lo2, prep.keys2d_hi, prep.keys2d_lo,
        n_chunks=prep.n_chunks, n_fences=prep.n_blocks, interpret=interpret)
    pos = np.minimum(np.asarray(pos).reshape(-1)[:nq], max(prep.n - 1, 0))
    deg = np.asarray(deg).reshape(-1)[:nq]
    return pos, deg
