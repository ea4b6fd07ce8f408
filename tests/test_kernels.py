"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp/numpy oracles."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st


from repro.kernels import ops, ref
from repro.kernels.searchsorted import (FENCE_CHUNK, KEY_BLOCK,
                                        TWO_LEVEL_MIN_CHUNKS, PreparedKeys,
                                        searchsorted_pallas)


# ---------------------------------------------------------------------------
# searchsorted
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31), st.integers(1, 3000), st.integers(1, 800),
       st.sampled_from([8, 64, 2**20, 2**45]))
@settings(max_examples=25, deadline=None)
def test_searchsorted_sweep(seed, nk, nq, dom):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(-dom, dom, nk).astype(np.int64))
    qs = rng.integers(-2 * dom, 2 * dom, nq).astype(np.int64)
    lo, hi = ops.searchsorted(keys, qs)
    lo_r, hi_r = ref.searchsorted_ref(keys, qs)
    assert np.array_equal(lo, lo_r)
    assert np.array_equal(hi, hi_r)


def test_searchsorted_equal_runs_across_blocks():
    keys = np.sort(np.repeat(np.arange(5, dtype=np.int64), 200))
    qs = np.arange(-1, 7, dtype=np.int64)
    lo, hi = ops.searchsorted(keys, qs)
    lo_r, hi_r = ref.searchsorted_ref(keys, qs)
    assert np.array_equal(lo, lo_r) and np.array_equal(hi, hi_r)


def test_searchsorted_prepared_reuse():
    rng = np.random.default_rng(0)
    keys = np.sort(rng.integers(0, 1000, 5000).astype(np.int64))
    prep = PreparedKeys(keys)
    for _ in range(3):
        qs = rng.integers(0, 1000, 300).astype(np.int64)
        lo, hi = searchsorted_pallas(prep, qs)
        lo_r, hi_r = ref.searchsorted_ref(keys, qs)
        assert np.array_equal(lo, lo_r) and np.array_equal(hi, hi_r)


# one top fence (and one row of 128 fences) per SUPER keys
SUPER = KEY_BLOCK * FENCE_CHUNK
# (keys, fence levels): one level just under the threshold, two levels at it
# and with at least three top fences
FENCE_LEVEL_CASES = [
    ((TWO_LEVEL_MIN_CHUNKS - 1) * SUPER - 5, 1),
    ((TWO_LEVEL_MIN_CHUNKS - 1) * SUPER + 7, 2),
    (max(3, TWO_LEVEL_MIN_CHUNKS) * SUPER + 2_000, 2),
]


def _keys_and_queries(n_keys: int, dom: int, seed: int, q_max: int):
    """Sorted keys with runs of equal keys across a 128-key block and a
    super-block boundary, and queries below, above, inside and exactly on
    keys, fences and top fences, down to INT64_MIN and up to ``q_max``."""
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(-dom, dom, n_keys).astype(np.int64))
    for b, half in ((KEY_BLOCK, 40), (SUPER, 300), (2 * SUPER, 3)):
        if b + half < n_keys:
            keys[b - half:b + half] = keys[b - half]
    i64 = np.iinfo(np.int64)
    qs = np.concatenate([
        rng.integers(-2 * dom, 2 * dom, 600), keys[::KEY_BLOCK],
        keys[::SUPER], keys[::SUPER] - 1, keys[::SUPER] + 1,
        keys[KEY_BLOCK - 1::KEY_BLOCK],
        [keys[0] - 1, keys[0], keys[-1], keys[-1] + 1, i64.min, q_max]])
    return keys, qs.astype(np.int64), rng


@pytest.mark.parametrize("dom", [2**45, 64])
@pytest.mark.parametrize("n_keys,levels", FENCE_LEVEL_CASES)
def test_searchsorted_fence_levels(n_keys, levels, dom):
    """Exact on both sides of the two-level threshold, against numpy."""
    keys, qs, _ = _keys_and_queries(n_keys, dom, n_keys,
                                    np.iinfo(np.int64).max)
    prep = PreparedKeys(keys)
    assert prep.levels == levels
    lo, hi = searchsorted_pallas(prep, qs)
    lo_r, hi_r = ref.searchsorted_ref(keys, qs)
    assert np.array_equal(lo, lo_r) and np.array_equal(hi, hi_r)


# ---------------------------------------------------------------------------
# walk hop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_keys,levels", FENCE_LEVEL_CASES)
def test_walk_hop_fence_levels(n_keys, levels):
    # the hop's queries lie below INT64_MAX, its padding sentinel
    keys, qs, rng = _keys_and_queries(n_keys, n_keys // 8, n_keys + 1,
                                      np.iinfo(np.int64).max - 1)
    prep = PreparedKeys(keys)
    assert prep.levels == levels
    u = rng.random(qs.shape[0]).astype(np.float32)
    pos, deg = ops.walk_hop(prep, qs, u)
    pos_r, deg_r = ref.walk_hop_ref(keys, qs, u)
    assert np.array_equal(deg, deg_r)
    alive = deg_r > 0
    assert np.array_equal(pos[alive], pos_r[alive])


@given(st.integers(0, 2**31), st.integers(1, 2000), st.integers(1, 600))
@settings(max_examples=20, deadline=None)
def test_walk_hop_sweep(seed, nk, nq):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, max(nk // 4, 2), nk).astype(np.int64))
    qs = rng.integers(-1, max(nk // 4, 2) + 1, nq).astype(np.int64)
    u = rng.random(nq).astype(np.float32)
    pos, deg = ops.walk_hop(keys, qs, u)
    pos_r, deg_r = ref.walk_hop_ref(keys, qs, u)
    assert np.array_equal(deg, deg_r)
    alive = deg_r > 0
    assert np.array_equal(pos[alive], pos_r[alive])


# ---------------------------------------------------------------------------
# weighted pick
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31))
@settings(max_examples=15, deadline=None)
def test_ranged_weighted_pick(seed):
    rng = np.random.default_rng(seed)
    n = 500
    w = rng.random(n)
    w[rng.random(n) < 0.3] = 0.0
    cs = np.concatenate([[0.0], np.cumsum(w)])
    lo = rng.integers(0, n - 50, 200)
    hi = lo + rng.integers(1, 50, 200)
    u = rng.random(200)
    pos = ops.ranged_weighted_pick(cs, lo, hi, u)
    assert ((pos >= lo) & (pos < hi)).all()
    nonempty = (cs[hi] - cs[lo]) > 0
    assert (w[pos[nonempty]] > 0).all()


def test_ranged_weighted_pick_distribution():
    w = np.array([1.0, 0.0, 3.0, 0.0, 6.0], dtype=np.float64)
    cs = np.concatenate([[0.0], np.cumsum(w)])
    rng = np.random.default_rng(0)
    N = 30_000
    lo = np.zeros(N, np.int64)
    hi = np.full(N, 5, np.int64)
    pos = ops.ranged_weighted_pick(cs, lo, hi, rng.random(N))
    freq = np.bincount(pos, minlength=5) / N
    np.testing.assert_allclose(freq, w / w.sum(), atol=0.02)

