"""Tiled sorted-probe (searchsorted) Pallas TPU kernels.

Every probe / degree / membership / EW-aggregation primitive in the sampler
reduces to ``lo = #keys < q`` / ``hi = #keys <= q`` against a sorted key
column.  TPUs have no efficient per-lane gather, so the paper's hash-probe
becomes a **two-phase dense-compare search** (DESIGN.md §2/§6):

* **Phase A — fence sweep** (`fence_count_kernel`): the fence array
  (every 128th sorted key) is VMEM-resident; each query tile counts
  ``#fences < q`` and ``#fences <= q`` by chunked broadcast-compare on the
  VPU (branchless, gather-free).  This pins each boundary to one 128-key
  block: for ``blk_l = #fences<q - 1``, every key in an earlier block is
  ``<= fences[blk_l] < q`` and every key in a later block is
  ``>= fences[blk_l+1] >= q`` — including runs of equal keys that straddle
  block boundaries.
* **XLA row-gather**: the per-query 128-key refinement rows are gathered by
  XLA (`keys2d[block_id]`) — irregular data movement is XLA's job on TPU;
  dense compute is Pallas's.
* **Phase B — refine** (`refine_kernel`): one dense ``(TQ, 128)`` compare per
  tile finishes the exact position.

int64 keys are carried as (hi32, biased-lo32) pairs with lexicographic
compares (TPU vector ALUs are 32-bit; the split happens host-side in numpy so
the jitted graph is pure int32).  Padding uses +inf sentinels (INT32_MAX
pairs), which never count as ``< q`` or ``<= q`` for real queries.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

KEY_BLOCK = 128          # keys per refinement block (fence stride)
QUERY_TILE = 256         # queries per grid step
FENCE_CHUNK = 128        # fences compared per inner iteration

_I64_MAX = np.iinfo(np.int64).max


def split64_np(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 -> (hi32, biased lo32); lexicographic (hi, lo) preserves order."""
    x = np.asarray(x, dtype=np.int64)
    hi = (x >> 64 - 32).astype(np.int32)
    lo = ((x & 0xFFFFFFFF).astype(np.uint32) ^ np.uint32(0x80000000)).view(np.int32)
    return hi, lo


def _pad_np(x: np.ndarray, m: int, fill: int) -> np.ndarray:
    pad = (-x.shape[0]) % m
    if pad == 0:
        return x
    return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])


def _lt(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def _le(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


# ---------------------------------------------------------------------------
# Phase A: fence sweep
# ---------------------------------------------------------------------------


def fence_count_kernel(q_hi_ref, q_lo_ref, f_hi_ref, f_lo_ref,
                       blk_l_ref, blk_r_ref, *, n_chunks: int,
                       n_fences: int):
    """Per query: block ids of the lo/hi boundaries (broadcast-compare sweep)."""
    q_hi = q_hi_ref[0, 0, :]                  # (TQ,)
    q_lo = q_lo_ref[0, 0, :]
    tq = q_hi.shape[0]
    acc_l = jnp.zeros((tq,), jnp.int32)
    acc_r = jnp.zeros((tq,), jnp.int32)

    def body(c, carry):
        acc_l, acc_r = carry
        f_hi = f_hi_ref[c, :]                 # (FENCE_CHUNK,)
        f_lo = f_lo_ref[c, :]
        # mask fence padding (chunk grid may overrun n_fences)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, FENCE_CHUNK), 1)[0]
        valid = (c * FENCE_CHUNK + lane) < n_fences
        lt = _lt(f_hi[None, :], f_lo[None, :], q_hi[:, None], q_lo[:, None]) & valid[None, :]
        le = _le(f_hi[None, :], f_lo[None, :], q_hi[:, None], q_lo[:, None]) & valid[None, :]
        return (acc_l + jnp.sum(lt.astype(jnp.int32), axis=1),
                acc_r + jnp.sum(le.astype(jnp.int32), axis=1))

    acc_l, acc_r = jax.lax.fori_loop(0, n_chunks, body, (acc_l, acc_r))
    blk_l_ref[0, 0, :] = jnp.clip(acc_l - 1, 0, None)
    blk_r_ref[0, 0, :] = jnp.clip(acc_r - 1, 0, None)


# ---------------------------------------------------------------------------
# Phase B: refine within the gathered 128-key rows
# ---------------------------------------------------------------------------


def refine_kernel(q_hi_ref, q_lo_ref, blk_l_ref, blk_r_ref,
                  row_l_hi_ref, row_l_lo_ref, row_r_hi_ref, row_r_lo_ref,
                  lo_ref, hi_ref):
    q_hi = q_hi_ref[0, 0, :][:, None]         # (TQ, 1)
    q_lo = q_lo_ref[0, 0, :][:, None]
    lt = _lt(row_l_hi_ref[0], row_l_lo_ref[0], q_hi, q_lo)
    le = _le(row_r_hi_ref[0], row_r_lo_ref[0], q_hi, q_lo)
    lo_ref[0, 0, :] = (blk_l_ref[0, 0, :] * KEY_BLOCK
                       + jnp.sum(lt.astype(jnp.int32), axis=1))
    hi_ref[0, 0, :] = (blk_r_ref[0, 0, :] * KEY_BLOCK
                       + jnp.sum(le.astype(jnp.int32), axis=1))


# ---------------------------------------------------------------------------
# Jitted int32 pipeline + host prep
# ---------------------------------------------------------------------------

# Per-query vectors travel as (qt, 1, QUERY_TILE) arrays in (1, 1, QUERY_TILE)
# blocks: Mosaic requires a block's last two dims to be multiples of (8, 128)
# or equal to the array's, and the unit axis makes the sublane dim equal.


def tile_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, QUERY_TILE), lambda i: (i, 0, 0))


def tiles_shape(qt: int, dtype=jnp.int32) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((qt, 1, QUERY_TILE), dtype)


def to_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad a (b,) query vector to whole tiles: (qt, 1, QUERY_TILE)."""
    x = jnp.pad(x, (0, (-x.shape[0]) % QUERY_TILE))
    return x.reshape(-1, 1, QUERY_TILE)


def fence_blocks(q_hi3, q_lo3, f_hi2, f_lo2, n_chunks: int, n_fences: int,
                 interpret: bool):
    """Phase A over all query tiles: per-query lo/hi boundary block ids."""
    qt = q_hi3.shape[0]
    fences = pl.BlockSpec((n_chunks, FENCE_CHUNK), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(fence_count_kernel, n_chunks=n_chunks,
                          n_fences=n_fences),
        grid=(qt,),
        in_specs=[tile_spec(), tile_spec(), fences, fences],
        out_specs=[tile_spec()] * 2,
        out_shape=[tiles_shape(qt)] * 2,
        interpret=interpret,
    )(q_hi3, q_lo3, f_hi2, f_lo2)


@functools.partial(jax.jit,
                   static_argnames=("n_chunks", "n_fences", "interpret"))
def _searchsorted_i32(q_hi3, q_lo3, f_hi2, f_lo2, keys2d_hi, keys2d_lo,
                      n_chunks: int, n_fences: int, interpret: bool = True):
    qt = q_hi3.shape[0]
    blk_l, blk_r = fence_blocks(q_hi3, q_lo3, f_hi2, f_lo2, n_chunks,
                                n_fences, interpret)

    # XLA row-gather of refinement blocks
    bl = blk_l.reshape(-1)
    br = blk_r.reshape(-1)
    row_l_hi = keys2d_hi[bl].reshape(qt, QUERY_TILE, KEY_BLOCK)
    row_l_lo = keys2d_lo[bl].reshape(qt, QUERY_TILE, KEY_BLOCK)
    row_r_hi = keys2d_hi[br].reshape(qt, QUERY_TILE, KEY_BLOCK)
    row_r_lo = keys2d_lo[br].reshape(qt, QUERY_TILE, KEY_BLOCK)

    lo, hi = pl.pallas_call(
        refine_kernel,
        grid=(qt,),
        in_specs=[tile_spec()] * 4 + [
            pl.BlockSpec((1, QUERY_TILE, KEY_BLOCK), lambda i: (i, 0, 0))] * 4,
        out_specs=[tile_spec()] * 2,
        out_shape=[tiles_shape(qt)] * 2,
        interpret=interpret,
    )(q_hi3, q_lo3, blk_l, blk_r, row_l_hi, row_l_lo, row_r_hi, row_r_lo)
    return lo, hi


class PreparedKeys:
    """Host-side preparation of a sorted key column for the kernel path."""

    def __init__(self, keys: np.ndarray):
        keys = np.asarray(keys, dtype=np.int64)
        self.n = keys.shape[0]
        kp = _pad_np(keys, KEY_BLOCK, _I64_MAX)
        self.n_blocks = kp.shape[0] // KEY_BLOCK
        k_hi, k_lo = split64_np(kp)
        self.keys2d_hi = jnp.asarray(k_hi.reshape(self.n_blocks, KEY_BLOCK))
        self.keys2d_lo = jnp.asarray(k_lo.reshape(self.n_blocks, KEY_BLOCK))
        fences = _pad_np(kp[::KEY_BLOCK], FENCE_CHUNK, _I64_MAX)
        f_hi, f_lo = split64_np(fences)
        self.n_chunks = f_hi.shape[0] // FENCE_CHUNK
        self.f_hi2 = jnp.asarray(f_hi.reshape(self.n_chunks, FENCE_CHUNK))
        self.f_lo2 = jnp.asarray(f_lo.reshape(self.n_chunks, FENCE_CHUNK))


def searchsorted_pallas(keys, queries, interpret: bool = True
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) = (#keys < q, #keys <= q) per query. keys must be sorted."""
    prep = keys if isinstance(keys, PreparedKeys) else PreparedKeys(keys)
    q = np.asarray(queries, dtype=np.int64)
    nq = q.shape[0]
    qp = _pad_np(q, QUERY_TILE, 0)
    q_hi, q_lo = split64_np(qp)
    qt = qp.shape[0] // QUERY_TILE
    lo, hi = _searchsorted_i32(
        jnp.asarray(q_hi.reshape(qt, 1, QUERY_TILE)),
        jnp.asarray(q_lo.reshape(qt, 1, QUERY_TILE)),
        prep.f_hi2, prep.f_lo2, prep.keys2d_hi, prep.keys2d_lo,
        n_chunks=prep.n_chunks, n_fences=prep.n_blocks, interpret=interpret)
    lo = np.minimum(np.asarray(lo).reshape(-1)[:nq], prep.n)
    hi = np.minimum(np.asarray(hi).reshape(-1)[:nq], prep.n)
    return lo, hi
