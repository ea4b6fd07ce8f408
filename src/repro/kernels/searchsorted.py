"""Tiled sorted-probe (searchsorted) Pallas TPU kernels.

Every probe / degree / membership / EW-aggregation primitive in the sampler
reduces to ``lo = #keys < q`` / ``hi = #keys <= q`` against a sorted key
column.  TPUs have no efficient per-lane gather, so the paper's hash-probe
becomes a **dense-compare search over fences** (DESIGN.md §2/§6):

* **Phase A — block ids.**  The fences are every 128th sorted key; a
  query's boundary block is ``blk_l = #fences<q - 1`` (clipped at 0): every
  key in an earlier block is ``<= fences[blk_l] < q`` and every key in a
  later block is ``>= fences[blk_l+1] >= q`` — including runs of equal keys
  that straddle block boundaries (``blk_r`` likewise from ``#fences<=q``).

  - *One level* (`fence_count_kernel`): the fence array is VMEM-resident;
    each query tile counts ``#fences < q`` and ``#fences <= q`` by chunked
    broadcast-compare on the VPU (branchless, gather-free).  Its work is
    one 128-fence chunk per 16,384 keys, per query.
  - *Two levels*, on an index of at least ``TWO_LEVEL_MIN_CHUNKS`` fence
    chunks: the **top fences** (every 128th fence, every 16,384th key) are
    swept the same way, which pins each boundary to one row of 128 fences
    (``s = #top<q - 1``, by the same counting argument one level up).  XLA
    gathers that row and a refine-form compare counts the fences in it:
    ``#fences<q = 128 s + #row<q``.  The work per query is then a sweep of
    the top fences plus one row compare, whatever the index size.  The
    number of levels is static, from the index's size alone.
* **XLA row-gather**: the per-query 128-key refinement rows are gathered by
  XLA (`keys2d[block_id]`) — irregular data movement is XLA's job on TPU;
  dense compute is Pallas's.
* **Phase B — refine** (`refine_kernel`): one dense ``(TQ, 128)`` compare per
  tile finishes the exact position.

Two jitted entry points run the same Pallas calls: ``_searchsorted_i32``,
the range probe of the join trees, and ``_member_probe_i32``, the
cover-membership probe of a 64-bit row-fingerprint pair key
(:func:`pair_keys_np`, :func:`pair_tiles`).  XLA names each kernel after its
entry point, so a trace tells the two apart.

int64 keys are carried as (hi32, biased-lo32) pairs with lexicographic
compares (TPU vector ALUs are 32-bit; the split happens host-side in numpy so
the jitted graph is pure int32).  Padding uses +inf sentinels (INT32_MAX
pairs), which never count as ``< q`` or ``<= q`` for real queries; fence
counts are capped at the number of real fences, so a query equal to the
sentinel cannot count fence padding either.
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
# An index of at least this many fence chunks (x 16,384 keys) searches its
# fences in two levels; below it, one sweep is cheaper (chip sweep: PERF.md).
TWO_LEVEL_MIN_CHUNKS = 5

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


def probe_levels(n_chunks: int) -> int:
    """Fence levels of an index with ``n_chunks`` chunks of 128 fences."""
    return 2 if n_chunks >= TWO_LEVEL_MIN_CHUNKS else 1


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
# Row compares: fence rows (second level) and key rows (phase B)
# ---------------------------------------------------------------------------


def row_counts(q_hi_ref, q_lo_ref, base_l_ref, base_r_ref,
               row_l_hi_ref, row_l_lo_ref, row_r_hi_ref, row_r_lo_ref):
    """``(#sorted < q, #sorted <= q)`` per query, from the gathered
    ``(TQ, W)`` rows holding each boundary and the row ids ``base``."""
    q_hi = q_hi_ref[0, 0, :][:, None]         # (TQ, 1)
    q_lo = q_lo_ref[0, 0, :][:, None]
    width = row_l_hi_ref.shape[-1]
    lt = _lt(row_l_hi_ref[0], row_l_lo_ref[0], q_hi, q_lo)
    le = _le(row_r_hi_ref[0], row_r_lo_ref[0], q_hi, q_lo)
    return (base_l_ref[0, 0, :] * width + jnp.sum(lt.astype(jnp.int32), axis=1),
            base_r_ref[0, 0, :] * width + jnp.sum(le.astype(jnp.int32), axis=1))


def fence_row_kernel(*refs, n_fences: int):
    """Second level: boundary block ids from the fence rows under each
    query's top fences (the counts are capped at the real fences)."""
    lo, hi = row_counts(*refs[:8])
    blk_l_ref, blk_r_ref = refs[8:]
    blk_l_ref[0, 0, :] = jnp.clip(jnp.minimum(lo, n_fences) - 1, 0, None)
    blk_r_ref[0, 0, :] = jnp.clip(jnp.minimum(hi, n_fences) - 1, 0, None)


def refine_kernel(*refs):
    """Phase B: exact ``(lo, hi)`` from the gathered 128-key rows."""
    lo_ref, hi_ref = refs[8:]
    lo_ref[0, 0, :], hi_ref[0, 0, :] = row_counts(*refs[:8])


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


def gather_rows(a_hi2, a_lo2, blk_l, blk_r):
    """XLA row-gather of the rows holding each query's two boundaries:
    ``(row_l_hi, row_l_lo, row_r_hi, row_r_lo)``, each (qt, TQ, width)."""
    qt = blk_l.shape[0]
    return tuple(a[b.reshape(-1)].reshape(qt, QUERY_TILE, a.shape[1])
                 for b in (blk_l, blk_r) for a in (a_hi2, a_lo2))


def row_call(kernel, q_hi3, q_lo3, base_l, base_r, rows, extra=(),
             interpret: bool = True):
    """One Pallas row compare over all query tiles: the query tiles, the
    row ids, the four gathered rows and ``extra`` tiles in; two tiles out."""
    qt = q_hi3.shape[0]
    tile = tile_spec()
    row = pl.BlockSpec((1, QUERY_TILE, rows[0].shape[-1]), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(qt,),
        in_specs=[tile] * 4 + [row] * 4 + [tile] * len(extra),
        out_specs=[tile] * 2,
        out_shape=[tiles_shape(qt)] * 2,
        interpret=interpret,
    )(q_hi3, q_lo3, base_l, base_r, *rows, *extra)


def fence_sweep(q_hi3, q_lo3, f_hi2, f_lo2, n_fences: int, interpret: bool):
    """One sweep over all query tiles: per-query boundary ids among the
    ``n_fences`` fences of ``f_hi2``/``f_lo2`` (chunks of FENCE_CHUNK)."""
    qt = q_hi3.shape[0]
    n_chunks = f_hi2.shape[0]
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


def fence_blocks(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2, n_fences: int,
                 interpret: bool):
    """Phase A over all query tiles: per-query lo/hi boundary block ids,
    in one level or two (``probe_levels`` of the index's fence chunks)."""
    n_chunks = f_hi2.shape[0]
    if probe_levels(n_chunks) == 1:
        return fence_sweep(q_hi3, q_lo3, f_hi2, f_lo2, n_fences, interpret)
    s_l, s_r = fence_sweep(q_hi3, q_lo3, t_hi2, t_lo2, n_chunks, interpret)
    return row_call(functools.partial(fence_row_kernel, n_fences=n_fences),
                    q_hi3, q_lo3, s_l, s_r,
                    gather_rows(f_hi2, f_lo2, s_l, s_r), interpret=interpret)


def probe_counts(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2, keys2d_hi,
                 keys2d_lo, interpret: bool):
    """``(lo, hi)`` tiles of the queries against one index's arrays
    (:meth:`PreparedKeys.arrays`): phase A, the key-row gather, phase B."""
    blk_l, blk_r = fence_blocks(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2,
                                keys2d_hi.shape[0], interpret)
    return row_call(refine_kernel, q_hi3, q_lo3, blk_l, blk_r,
                    gather_rows(keys2d_hi, keys2d_lo, blk_l, blk_r),
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _searchsorted_i32(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2, keys2d_hi,
                      keys2d_lo, interpret: bool = True):
    """The range probe: :func:`probe_counts` as one jitted call."""
    return probe_counts(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2, keys2d_hi,
                        keys2d_lo, interpret)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _member_probe_i32(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2, keys2d_hi,
                      keys2d_lo, n: int, interpret: bool = True):
    """The membership probe: per query tile lane, whether the query equals
    one of the index's ``n`` real keys.  The same Pallas calls as the range
    probe, under a name of their own, so a trace tells the two apart.  The
    counts are capped at ``n``, so a query equal to the padding sentinel
    is a member only if a real key equals it."""
    lo, hi = probe_counts(q_hi3, q_lo3, t_hi2, t_lo2, f_hi2, f_lo2,
                          keys2d_hi, keys2d_lo, interpret)
    return jnp.minimum(hi, n) > jnp.minimum(lo, n)


def pair_keys_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """int64 keys of uint32 word pairs: ``hi`` viewed as int32 is the high
    word and ``lo`` the low one.  Injective, and :func:`split64_np` of a key
    gives back ``(hi as int32, lo biased)``, as :func:`pair_tiles` does."""
    hi = np.asarray(hi, np.uint32).view(np.int32).astype(np.int64)
    return (hi << 32) | np.asarray(lo, np.uint32).astype(np.int64)


def pair_tiles(hi: jnp.ndarray, lo: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traced query tiles ``(q_hi3, q_lo3)`` of uint32 word pairs: the
    split of :func:`pair_keys_np`'s key, without a 64-bit type."""
    q_hi = jax.lax.bitcast_convert_type(hi, jnp.int32)
    q_lo = jax.lax.bitcast_convert_type(lo, jnp.int32) ^ jnp.int32(-(1 << 31))
    return to_tiles(q_hi), to_tiles(q_lo)


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
        fences = kp[::KEY_BLOCK]
        f_hi, f_lo = split64_np(_pad_np(fences, FENCE_CHUNK, _I64_MAX))
        self.n_chunks = f_hi.shape[0] // FENCE_CHUNK
        self.f_hi2 = jnp.asarray(f_hi.reshape(self.n_chunks, FENCE_CHUNK))
        self.f_lo2 = jnp.asarray(f_lo.reshape(self.n_chunks, FENCE_CHUNK))
        # top fences: the first fence of every row of f_hi2 / f_lo2
        t_hi, t_lo = split64_np(_pad_np(fences[::FENCE_CHUNK], FENCE_CHUNK,
                                        _I64_MAX))
        self.t_hi2 = jnp.asarray(t_hi.reshape(-1, FENCE_CHUNK))
        self.t_lo2 = jnp.asarray(t_lo.reshape(-1, FENCE_CHUNK))
        self.levels = probe_levels(self.n_chunks)

    def arrays(self) -> Tuple[jnp.ndarray, ...]:
        """The device arrays a probe reads, in ``_searchsorted_i32`` order:
        top fences, fences, key blocks (each as hi, lo)."""
        return (self.t_hi2, self.t_lo2, self.f_hi2, self.f_lo2,
                self.keys2d_hi, self.keys2d_lo)


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
        *prep.arrays(), interpret=interpret)
    lo = np.minimum(np.asarray(lo).reshape(-1)[:nq], prep.n)
    hi = np.minimum(np.asarray(hi).reshape(-1)[:nq], prep.n)
    return lo, hi
