"""Theorem 3 / Eq 1 / cover / Algorithm 1 / Algorithm 2 properties."""

import numpy as np
import pytest
from _conformance import chi2_p
from _hypothesis_compat import given, settings, st

from repro.core.cover import build_cover
from repro.core.framework import estimate_union, warmup
from repro.core.index import Catalog
from repro.core.joins import chain_join
from repro.core.koverlap import KOverlaps, OverlapOracle, k_overlaps
from repro.core.online import OnlineUnionSampler
from repro.core.overlap import exact_union_size
from repro.core.union_sampler import (BernoulliUnionSampler,
                                      DisjointUnionSampler, SetUnionSampler)
from repro.data.workloads import uq3


# ---------------------------------------------------------------------------
# Theorem 3 on random set systems (no joins needed — pure set identity)
# ---------------------------------------------------------------------------


class _SetOracle:
    """Oracle over explicit sets (ground truth for the lattice algebra)."""

    def __init__(self, sets):
        self.sets = sets
        names = list(sets)
        import dataclasses

        @dataclasses.dataclass
        class FakeJoin:
            name: str
        self.joins = [FakeJoin(n) for n in names]
        self.by_name = {n: j for n, j in zip(names, self.joins)}
        self._cache = {}

    def overlap(self, names):
        cur = None
        for n in set(names):
            cur = self.sets[n] if cur is None else (cur & self.sets[n])
        return float(len(cur))

    def size(self, name):
        return float(len(self.sets[name]))


@given(st.integers(0, 10_000), st.integers(2, 5))
@settings(max_examples=40, deadline=None)
def test_theorem3_and_eq1_identity(seed, n_sets):
    rng = np.random.default_rng(seed)
    universe = list(range(60))
    sets = {f"J{i}": set(rng.choice(universe, size=rng.integers(5, 40),
                                    replace=False).tolist())
            for i in range(n_sets)}
    oracle = _SetOracle(sets)
    ko = k_overlaps(oracle)
    # A_j^k ground truth: elements of J_j in exactly k-1 other sets
    union = set().union(*sets.values())
    for name, s in sets.items():
        for k in range(1, n_sets + 1):
            truth = sum(1 for e in s
                        if sum(e in t for t in sets.values()) == k)
            assert ko.a[name][k - 1] == pytest.approx(truth), (name, k)
    assert ko.union_size() == pytest.approx(len(union))


@given(st.integers(0, 10_000), st.integers(2, 5))
@settings(max_examples=40, deadline=None)
def test_cover_partition_identity(seed, n_sets):
    rng = np.random.default_rng(seed)
    universe = list(range(50))
    sets = {f"J{i}": set(rng.choice(universe, size=rng.integers(5, 35),
                                    replace=False).tolist())
            for i in range(n_sets)}
    oracle = _SetOracle(sets)
    cover = build_cover(oracle)
    # ground truth cover: J'_i = J_i \ union of earlier
    seen = set()
    for name in cover.order:
        piece = sets[name] - seen
        assert cover.piece_sizes[name] == pytest.approx(len(piece)), name
        seen |= sets[name]
    assert cover.union_size == pytest.approx(len(seen))


# ---------------------------------------------------------------------------
# Algorithm 1 (uniformity; probe mode exact, record mode converging).  The
# probe engine's uniformity on every union is the engine × union matrix
# (``test_conformance_host.py``).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wl3():
    return uq3(scale=0.01, overlap=0.3, seed=0)


def test_setunion_record_mode_converges(wl3):
    cat, joins = wl3.cat, wl3.joins
    wr = warmup(cat, joins, method="exact")
    est = estimate_union(wr.oracle)
    U = exact_union_size(cat, joins)
    s = SetUnionSampler(cat, joins, est.cover, membership="record", seed=8)
    ss = s.sample(60 * U)
    # record mode discovers the cover lazily; allow a looser bar
    p = chi2_p(ss.matrix(), U)
    assert p > 1e-5, f"record mode wildly non-uniform: p={p}"
    assert ss.stats.revisions >= 0


def test_bernoulli_union_uniform(wl3):
    cat, joins = wl3.cat, wl3.joins
    wr = warmup(cat, joins, method="exact")
    sizes = {j.name: wr.oracle.size(j.name) for j in joins}
    U = exact_union_size(cat, joins)
    s = BernoulliUnionSampler(cat, joins, sizes, float(U), seed=9)
    ss = s.sample(80 * U)
    p = chi2_p(ss.matrix(), U)
    assert p > 1e-3, f"Bernoulli union sampler not uniform: p={p}"
    assert ss.stats.canonical_rejects > 0


def test_disjoint_union_proportional(wl3):
    cat, joins = wl3.cat, wl3.joins
    wr = warmup(cat, joins, method="exact")
    sizes = {j.name: wr.oracle.size(j.name) for j in joins}
    s = DisjointUnionSampler(cat, joins, sizes, seed=10)
    ss = s.sample(6000)
    tot = sum(sizes.values())
    for j_idx, j in enumerate(joins):
        frac = (ss.home == j_idx).mean()
        assert frac == pytest.approx(sizes[j.name] / tot, abs=0.03)


def test_sampling_cost_within_theorem2_bound(wl3):
    """§3.3: expected candidate draws ≲ O(N + N log N) (generous constant)."""
    cat, joins = wl3.cat, wl3.joins
    wr = warmup(cat, joins, method="exact")
    est = estimate_union(wr.oracle)
    s = SetUnionSampler(cat, joins, est.cover, membership="probe", seed=11)
    N = 2000
    ss = s.sample(N)
    bound = 40 * (N + N * np.log(max(N, 2)))
    assert ss.stats.candidate_draws < bound


# ---------------------------------------------------------------------------
# Algorithm 2 (online union)
# ---------------------------------------------------------------------------


def test_online_union_end_to_end(wl3):
    cat, joins = wl3.cat, wl3.joins
    ou = OnlineUnionSampler(cat, joins, seed=12, phi=512, rw_batch=128)
    U = exact_union_size(cat, joins)
    ss = ou.sample(40 * U)
    assert len(ss) == 40 * U
    assert ss.stats.reuse_accepts > 0
    # marginal approx-uniformity (estimates refine online; generous bar)
    mat = ss.matrix()
    uni, counts = np.unique(mat.view([("", mat.dtype)] * mat.shape[1]).ravel(),
                            return_counts=True)
    assert uni.shape[0] >= 0.9 * U
    assert counts.max() <= 12 * counts.mean()


def test_online_reuse_rate_sane(wl3):
    """Guard for the l-factor bug: copies per reuse draw must be ~1."""
    cat, joins = wl3.cat, wl3.joins
    ou = OnlineUnionSampler(cat, joins, seed=13, phi=10_000, rw_batch=256)
    ss = ou.sample(500)
    if ss.stats.reuse_accepts:
        assert ss.stats.reuse_accepts <= 3 * ss.stats.iterations


def test_rejection_mode_predicate(wl3):
    """§8.3 mode 2: sampler-side predicate == sampling the filtered union."""
    from repro.core.predicates import Pred, RejectingPredicate, pushdown
    from repro.core.joins import JoinSpec
    cat, joins = wl3.cat, wl3.joins
    preds = [Pred("odate", "<=", 1500)]
    # ground truth: union of pushed-down joins
    filtered = [JoinSpec(j.name + "#f", pushdown(j, preds).nodes) for j in joins]
    U_f = exact_union_size(cat, filtered)
    if U_f < 10:
        pytest.skip("filtered union too small for a distribution check")
    wr = warmup(cat, joins, method="exact")
    est = estimate_union(wr.oracle)
    s = SetUnionSampler(cat, joins, est.cover, seed=21,
                        predicate=RejectingPredicate(preds))
    ss = s.sample(60 * U_f)
    assert (ss.rows["odate"] <= 1500).all()
    p = chi2_p(ss.matrix(), U_f)
    assert p > 1e-3, f"rejection-mode predicate sampling not uniform: p={p}"


# ---------------------------------------------------------------------------
# Record-mode revision path (Alg 1 lines 10-12) + membership matrix
# ---------------------------------------------------------------------------


def test_record_mode_revision_path():
    """A tuple recorded at a later join moves home (and drops stale copies)
    when re-sampled from an earlier join."""
    from repro.core.cover import Cover
    from repro.core.relation import Relation
    rng = np.random.default_rng(0)
    R = Relation("Rbase", {"a": np.arange(12), "v": rng.integers(0, 5, 12)})
    # two identical single-relation joins: J1's true cover piece is empty,
    # but the lazy record only discovers that through revisions
    j0 = chain_join("J0", [R], [])
    j1 = chain_join("J1", [R], [])
    cat = Catalog()
    cover = Cover(order=["J0", "J1"],
                  piece_sizes={"J0": 12.0, "J1": 12.0},
                  join_sizes={"J0": 12.0, "J1": 12.0})
    s = SetUnionSampler(cat, [j0, j1], cover, membership="record", seed=5)
    ss = s.sample(150)
    assert ss.stats.revisions > 0, "revision path never exercised"
    assert ss.stats.backtrack_removed > 0, "stale copies never removed"
    assert ss.stats.cover_rejects > 0    # re-draws at J1 after revision reject
    # after revision a tuple has exactly one home join in the output
    keys = ss.matrix()
    uniq = {}
    for i, t in enumerate(map(tuple, keys.tolist())):
        uniq.setdefault(t, set()).add(int(ss.home[i]))
    assert all(len(h) == 1 for h in uniq.values()), \
        "a tuple kept copies credited to two different joins"


def test_membership_prober_matrix(wl3):
    from repro.core.joins import full_join_matrix
    from repro.core.membership import MembershipProber
    cat, joins = wl3.cat, wl3.joins
    prober = MembershipProber(cat, joins)
    attrs = list(joins[0].output_attrs)
    truth = {j.name: set(map(tuple, full_join_matrix(cat, j, attrs=attrs).tolist()))
             for j in joins}
    # probe every tuple of join 0 plus perturbed non-members
    mat0 = full_join_matrix(cat, joins[0], attrs=attrs)
    fakes = mat0 + 5003
    probe = np.concatenate([mat0, fakes])
    rows = {a: probe[:, i] for i, a in enumerate(attrs)}
    names = [j.name for j in joins]
    m = prober.membership_matrix(rows, names)
    assert m.shape == (probe.shape[0], len(joins))
    expected = np.zeros_like(m)
    for k, name in enumerate(names):
        expected[:, k] = [tuple(t) in truth[name] for t in probe.tolist()]
    assert np.array_equal(m, expected)
    # column order follows join_names; default order covers all joins
    m_default = prober.membership_matrix(rows)
    assert np.array_equal(m_default, m)
