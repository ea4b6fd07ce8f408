"""Join sampler (EW/EO/WJ) + size/overlap estimator properties."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from _conformance import chi2_p
from conftest import brute_force_join, tiny_db

from repro.core.index import Catalog
from repro.core.joins import JoinNode, JoinSpec, chain_join, full_join_matrix
from repro.core.join_sampler import JoinSampler
from repro.core.overlap import (HistogramOverlap, RandomWalkOverlap,
                                distinct_tuples, exact_overlap)
from repro.core.size_estimation import (WanderJoinSizeEstimator, olken_bound)
from repro.data.workloads import uq1, uq3


def _chain(seed=0):
    R, S, T = tiny_db(seed)
    return Catalog(), chain_join(f"RST{seed}", [R, S, T], ["b", "c"])


# ---------------------------------------------------------------------------
# Exact weights
# ---------------------------------------------------------------------------


def test_ew_total_equals_join_size():
    cat, spec = _chain(0)
    s = JoinSampler(cat, spec, method="ew")
    assert s.exact_acyclic_size() == full_join_matrix(cat, spec).shape[0]


def test_ew_sampling_uniform_chi2():
    cat, spec = _chain(1)
    s = JoinSampler(cat, spec, method="ew")
    mat = full_join_matrix(cat, spec)
    n_tuples = mat.shape[0]
    assert n_tuples > 30
    rng = np.random.default_rng(0)
    N = 60 * n_tuples
    rows, draws = s.sample_uniform(rng, N, batch=4096)
    # EW on acyclic joins: zero rejection (draws only overshoot by the final
    # batch's granularity)
    assert draws <= N + 4096
    got = np.stack([rows[a] for a in spec.output_attrs], axis=1)
    assert np.unique(got, axis=0).shape[0] == n_tuples
    p = chi2_p(got, n_tuples)
    assert p > 1e-3, f"EW sampling not uniform (p={p})"


def test_eo_sampling_uniform_chi2():
    cat, spec = _chain(2)
    s = JoinSampler(cat, spec, method="eo")
    mat = full_join_matrix(cat, spec)
    n_tuples = mat.shape[0]
    rng = np.random.default_rng(0)
    N = 50 * n_tuples
    rows, draws = s.sample_uniform(rng, N, batch=4096)
    assert draws > N  # EO rejects
    got = np.stack([rows[a] for a in spec.output_attrs], axis=1)
    p = chi2_p(got, n_tuples)
    assert p > 1e-3, f"EO sampling not uniform (p={p})"


def test_wj_horvitz_thompson_unbiased():
    cat, spec = _chain(3)
    true_size = full_join_matrix(cat, spec).shape[0]
    est = WanderJoinSizeEstimator(cat, spec, seed=0, batch=1024)
    for _ in range(30):
        est.step()
    assert est.estimate == pytest.approx(true_size, rel=0.15)


def test_wj_ci_stopping():
    cat, spec = _chain(4)
    true_size = full_join_matrix(cat, spec).shape[0]
    est = WanderJoinSizeEstimator(cat, spec, seed=1, batch=512)
    v = est.run(confidence=0.90, rel_halfwidth=0.10, max_walks=60_000)
    assert v == pytest.approx(true_size, rel=0.25)


def test_olken_bound_is_upper_bound():
    for seed in range(5):
        cat, spec = _chain(seed)
        assert olken_bound(cat, spec) >= full_join_matrix(cat, spec).shape[0]


# ---------------------------------------------------------------------------
# Cyclic join sampling (skeleton + residual accept/reject)
# ---------------------------------------------------------------------------


def _cyclic(seed=0):
    rng = np.random.default_rng(seed)
    R = Relation = None
    from repro.core.relation import Relation
    R = Relation("R", {"a": rng.integers(0, 5, 25), "b": rng.integers(0, 5, 25),
                       "rid": np.arange(25)})
    S = Relation("S", {"b": rng.integers(0, 5, 25), "c": rng.integers(0, 5, 25),
                       "sid": np.arange(25)})
    T = Relation("T", {"c": rng.integers(0, 5, 40), "a": rng.integers(0, 5, 40),
                       "tid": np.arange(40)})
    spec = JoinSpec("tri", [
        JoinNode("R", R, None, ()),
        JoinNode("S", S, "R", ("b",)),
        JoinNode("T", T, None, ("c", "a"), kind="residual"),
    ])
    return Catalog(), spec


def test_cyclic_sampling_uniform():
    cat, spec = _cyclic(0)
    mat = full_join_matrix(cat, spec)
    n_tuples = mat.shape[0]
    assert n_tuples > 20
    s = JoinSampler(cat, spec, method="ew")
    rng = np.random.default_rng(0)
    N = 60 * n_tuples
    rows, draws = s.sample_uniform(rng, N, batch=8192)
    got = np.stack([rows[a] for a in spec.output_attrs], axis=1)
    p = chi2_p(got, n_tuples)
    assert p > 1e-3, f"cyclic sampling not uniform (p={p})"


# ---------------------------------------------------------------------------
# Overlap estimators
# ---------------------------------------------------------------------------


def _two_chains(seed=0, overlap=0.5):
    """Two chain joins over variant relations with controlled overlap."""
    from repro.data.tpch import make_variants
    R, S, T = tiny_db(seed, n_r=80, n_s=90, n_t=70)
    cat = Catalog()
    Rv = make_variants(R, 2, overlap, seed=seed + 10)
    Sv = make_variants(S, 2, overlap, seed=seed + 11)
    Tv = make_variants(T, 2, overlap, seed=seed + 12)
    j0 = chain_join("J0", [Rv[0], Sv[0], Tv[0]], ["b", "c"])
    j1 = chain_join("J1", [Rv[1], Sv[1], Tv[1]], ["b", "c"])
    return cat, [j0, j1]


def test_histogram_overlap_is_sound_upper_bound():
    for seed in range(4):
        cat, joins = _two_chains(seed)
        hist = HistogramOverlap(cat, joins)
        bound = hist.estimate(joins)
        exact = exact_overlap(cat, joins)
        assert bound >= exact, f"seed={seed}: bound {bound} < exact {exact}"


def test_random_walk_overlap_converges():
    cat, joins = _two_chains(1, overlap=0.7)
    exact = exact_overlap(cat, joins)
    rw = RandomWalkOverlap(cat, joins, seed=0, batch=1024)
    est = rw.estimate(joins, rel_halfwidth=0.2, max_walks=40_000, min_walks=4096)
    if exact == 0:
        assert est.value < 5
    else:
        assert est.value == pytest.approx(exact, rel=0.5)


def test_random_walk_join_size():
    cat, joins = _two_chains(2)
    rw = RandomWalkOverlap(cat, joins, seed=3, batch=1024)
    true0 = full_join_matrix(cat, joins[0]).shape[0]
    est = rw.join_size(joins[0], min_walks=8192)
    assert est == pytest.approx(true0, rel=0.2)
