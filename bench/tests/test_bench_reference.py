"""The reference's exact counts and membership against brute-force
enumeration of the joins, on small data (CPU, no accelerator): the two
chain configurations and the test unions of ``bench/tests/unions`` (a
branching tree with a composite edge, Q5's cycle closed by a residual
relation, and UQ3's shape submitted in a split layout)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tpch  # noqa: E402
from bench.harness import _module, load_config  # noqa: E402
from bench.reference import tree  # noqa: E402
from bench.tests.chain_pins import PINS  # noqa: E402

UNIONS = os.path.join(ROOT, "bench", "tests", "unions")

TEST_UNIONS = [("ps_tree", 0.001), ("q5_cycle", 0.002), ("uq3_split", 0.001)]
CASES = [("uq1_sf1", 0.002), ("uq2_sf1", 0.001)] + TEST_UNIONS


def load_union(name):
    """(cfg, module) of a configuration, or of a test union."""
    if os.path.exists(os.path.join(ROOT, "bench", "configs", name + ".json")):
        return load_config(ROOT, name)
    base = os.path.join(UNIONS, name)
    with open(base + ".json") as f:
        cfg = json.load(f)
    return cfg, _module(base + ".py", f"bench_test_union_{name}")


def small(name, scale_factor, data_seed=3):
    cfg, mod = load_union(name)
    cfg = dict(cfg, scale_factor=scale_factor, data_seed=data_seed)
    return cfg, mod.build(cfg)


def enumerate_join(u, j):
    """Every tuple of join j as a row-id tuple: the natural join of the kept
    rows, relation by relation (rows agree on every attribute they share;
    parents and edges are not read)."""
    masks = u.masks(j)
    tuples = np.zeros((1, 0), np.int64)
    first = {}                                 # attribute -> relation
    for i, (r, m) in enumerate(zip(u.rels, masks)):
        shared = [a for a in r.cols if a in first]
        kept = np.nonzero(m)[0]
        index = {}
        keys = zip(*[r.cols[a][kept].tolist() for a in shared]) if shared \
            else [()] * kept.shape[0]
        for row, key in zip(kept.tolist(), keys):
            index.setdefault(key, []).append(row)
        want = zip(*[u.rels[first[a]].cols[a][tuples[:, first[a]]].tolist()
                     for a in shared]) if shared else [()] * tuples.shape[0]
        pairs = [(t, row) for t, key in enumerate(want)
                 for row in index.get(key, ())]
        if not pairs:
            return set()
        t_idx, r_idx = map(np.asarray, zip(*pairs))
        tuples = np.column_stack([tuples[t_idx], r_idx])
        for a in r.cols:
            first.setdefault(a, i)
    return set(map(tuple, tuples.tolist()))


@pytest.mark.parametrize("name,sf", CASES)
def test_counts_match_enumeration(name, sf):
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    assert all(sets), "every join of a test union holds tuples"
    sizes = tree.intersection_sizes(u)
    for s, size in sizes.items():
        assert size == len(set.intersection(*[sets[j] for j in s])), s
    pieces = tree.pieces_from(sizes, len(u.joins))
    seen = set()
    for j, piece in enumerate(pieces):
        assert piece == len(sets[j] - seen)
        seen |= sets[j]
    names = [r.name for r in u.rels]
    for rel, bucket in (
            (names.index(cfg["check"]["marginal_relation"]), "id"),
            (names.index(cfg["check"]["position_relation"]), "position")):
        b = (tree.id_buckets(u, rel, 8) if bucket == "id"
             else tree.position_buckets(u, rel, 4))
        vec = tree.pieces_from(tree.bucket_counts(u, rel, b), len(u.joins))
        seen = set()
        for j, v in enumerate(vec):
            mine = sets[j] - seen
            seen |= sets[j]
            want = np.bincount([b[t[rel]] for t in mine], minlength=v.shape[0])
            np.testing.assert_array_equal(v, want)


@pytest.mark.parametrize("name,sf", TEST_UNIONS)
def test_bucket_counts_of_every_relation_match_enumeration(name, sf):
    """Through counts at the root, every inner and leaf tree relation and
    the residual relation, one subset at a time."""
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    for rel, r in enumerate(u.rels):
        b = tree.id_buckets(u, rel, 5)
        for s, vec in tree.bucket_counts(u, rel, b).items():
            mine = set.intersection(*[sets[j] for j in s])
            want = np.bincount([b[t[rel]] for t in mine], minlength=5)
            np.testing.assert_array_equal(vec, want, err_msg=f"{r.name} {s}")


@pytest.mark.parametrize("name,sf", [("uq1_sf1", 0.002), ("uq2_sf1", 0.001)])
def test_chain_counts_are_pinned(name, sf):
    """The chain configurations keep the numbers the chain-only reference
    gave: sizes, pieces and both check relations' bucket counts."""
    cfg, u = small(name, sf)
    pin = PINS[name]
    assert tree.intersection_sizes(u) == pin["sizes"]
    assert tree.pieces_from(pin["sizes"], len(u.joins)) == pin["pieces"]
    names = [r.name for r in u.rels]
    chk = cfg["check"]
    rel = names.index(chk["marginal_relation"])
    got = tree.bucket_counts(u, rel, tree.id_buckets(u, rel, chk["buckets"]))
    assert {s: v.tolist() for s, v in got.items()} == pin["marginal"]
    rel = names.index(chk["position_relation"])
    got = tree.bucket_counts(u, rel, tree.position_buckets(
        u, rel, chk["position_buckets"]))
    assert {s: v.tolist() for s, v in got.items()} == pin["position"]


def test_position_buckets_place_rows_in_their_range():
    cfg, u = small("uq1_sf1", 0.002)
    li = u.rel("lineitem")
    b = tree.position_buckets(u, len(u.rels) - 1, 16)
    lines = np.bincount(li.cols["ok"])[li.cols["ok"]]
    np.testing.assert_array_equal(b, li.cols["ln"] * 16 // lines)
    assert (b[li.cols["ln"] == 0] == 0).all()
    last = (li.cols["ln"] == lines - 1) & (lines > 1)
    assert last.any() and (b[last] >= 8).all()


def test_position_buckets_on_a_composite_edge():
    """lineitem under partsupp on (pk, sk): ranks count within a pair."""
    cfg, u = small("ps_tree", 0.001)
    rel = [r.name for r in u.rels].index("lineitem")
    li = u.rel("lineitem")
    b = tree.position_buckets(u, rel, 4)
    pair = li.cols["pk"] * 10 ** 6 + li.cols["sk"]
    for key in np.unique(pair)[:50]:
        rows = np.nonzero(pair == key)[0]
        np.testing.assert_array_equal(
            b[rows], np.arange(rows.shape[0]) * 4 // rows.shape[0])
    with pytest.raises(ValueError, match="non-root tree relation"):
        tree.position_buckets(u, 0, 4)


def program_sizes_agree(name, sf):
    from repro.core.index import Catalog
    from repro.core.overlap import exact_overlap

    from bench.system import joins_for
    cfg, mod = load_union(name)
    cfg = dict(cfg, scale_factor=sf, data_seed=3)
    u = mod.build(cfg)
    specs = joins_for(u, mod)
    sizes = tree.intersection_sizes(u)
    for s, size in sizes.items():
        assert exact_overlap(Catalog(), [specs[j] for j in s]) == size, s


def test_counts_match_the_program_enumeration():
    """The program's exact_overlap (materialised joins) agrees."""
    program_sizes_agree("uq2_sf1", 0.001)


# The program's FULLJOIN baseline (``repro.core.joins._expand``, under
# ``full_join`` and ``exact_overlap``) packs a composite edge key with
# ``combine_columns`` on each side apart, each with its own radices: where
# the two sides' largest values of a later edge attribute differ, equal key
# tuples get different codes, and other tuples the same code.
FULLJOIN_COMPOSITE = pytest.mark.xfail(
    strict=True, reason="program fault: full_join packs composite edge keys "
    "with per-side radices (repro.core.joins._expand)")


@pytest.mark.parametrize("name,sf", [
    pytest.param("ps_tree", 0.001, marks=FULLJOIN_COMPOSITE),
    pytest.param("q5_cycle", 0.002, marks=FULLJOIN_COMPOSITE),
    ("uq3_split", 0.001)])
def test_counts_match_the_program_enumeration_of_other_shapes(name, sf):
    """The same over the program's joins of a tree, a cycle and a split
    layout (the configuration's own ``program_joins``)."""
    program_sizes_agree(name, sf)


@pytest.mark.parametrize("name,sf", [("uq1_sf1", 0.002), ("uq2_sf1", 0.001)])
def test_program_joins_of_a_chain_are_its_chain_joins(name, sf):
    """For a chain the program is given what ``chain_join`` builds: the same
    names, node order, parents, edges and relations."""
    from repro.core.joins import chain_join
    from repro.core.predicates import Pred, pushdown
    from repro.core.relation import Relation

    from bench.system import program_joins
    cfg, u = small(name, sf)
    edges = [r.edge for r in u.rels[1:]]
    want = []
    for jd in u.joins:
        rels = [Relation(f"{r.name}@{jd.name}" if r.name in jd.variants
                         else r.name,
                         {a: c[jd.variants[r.name]] if r.name in jd.variants
                          else c for a, c in r.cols.items()}) for r in u.rels]
        spec = chain_join(jd.name if jd.variants else "base", rels, edges)
        if jd.preds:
            spec = pushdown(spec, [Pred(*p) for p in jd.preds], name=jd.name)
        want.append(spec)
    for got, spec in zip(program_joins(u), want, strict=True):
        assert got.name == spec.name
        assert ([(n.alias, n.parent, n.edge_attrs, n.kind, n.relation.name)
                 for n in got.nodes] ==
                [(n.alias, n.parent, n.edge_attrs, n.kind, n.relation.name)
                 for n in spec.nodes])
        for a, b in zip(got.nodes, spec.nodes):
            assert list(a.relation.columns) == list(b.relation.columns)
            for col in a.relation.columns:
                np.testing.assert_array_equal(a.relation.columns[col],
                                              b.relation.columns[col])
        assert got.pushed_preds == spec.pushed_preds


@pytest.mark.parametrize("name,sf", CASES)
def test_membership_matches_enumeration(name, sf):
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    union = sorted(set.union(*sets))
    ids = [np.asarray([t[r] for t in union]) for r in range(len(u.rels))]
    rows = {}
    for r, rid in zip(u.rels, ids):
        for a, c in r.cols.items():
            rows.setdefault(a, c[rid])
    mem = tree.Membership(u)
    found, got = mem.row_ids(rows)
    assert found.all()
    for a, b in zip(got, ids):
        np.testing.assert_array_equal(a, b)
    m = mem.matrix(found, got)
    for j, s in enumerate(sets):
        assert m[:, j].tolist() == [t in s for t in union]
    # a tuple with one attribute changed names no row
    bad = dict(rows)
    last = list(u.rels[-1].cols)[-1]
    bad[last] = rows[last] + 1000
    assert not mem.row_ids(bad)[0].any()


@pytest.mark.parametrize("name", ["uq1_sf1", "uq2_sf1"])
def test_variant_sizes_do_not_depend_on_the_seed(name):
    shapes = []
    for seed in (1, 2):
        cfg, u = small(name, 0.01, data_seed=seed)
        shapes.append([(r.nrows, [int(j.variants[r.name].sum())
                                  for j in u.joins if r.name in j.variants])
                       for r in u.rels])
    assert shapes[0] == shapes[1]
    masks = tpch.variant_masks(1000, 3, 0.2, 0.5, seed=9)
    assert [int(m.sum()) for m in masks] == [600] * 3
    assert all(m[:200].all() for m in masks)


def uniform_and_canonical(name, sf, draws_per_tuple):
    """Float64 reference draws: canonical homes and a uniform stream."""
    from scipy import stats
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    union = sorted(set.union(*sets))
    pieces = tree.pieces_from(tree.intersection_sizes(u), len(u.joins))
    n = draws_per_tuple * len(union)
    rows, home = tree.sample_union(u, pieces, n, np.random.default_rng(0))
    mem = tree.Membership(u)
    found, ids = mem.row_ids(rows)
    m = mem.matrix(found, ids)
    assert m[np.arange(n), home].all()
    assert not (m & (np.arange(len(u.joins))[None, :] < home[:, None])).any()
    pos = {t: i for i, t in enumerate(union)}
    counts = np.bincount([pos[t] for t in zip(*[i.tolist() for i in ids])],
                         minlength=len(union))
    assert stats.chisquare(counts).pvalue > 1e-4
    return cfg, u, home, ids


def test_reference_sampler_is_uniform_and_canonical():
    uniform_and_canonical("uq1_sf1", 0.002, 30)


@pytest.mark.parametrize("name,sf", [("ps_tree", 0.001), ("q5_cycle", 0.002)])
def test_reference_sampler_matches_the_bucket_counts(name, sf):
    """On a branching tree and on a cycle: uniform over the tuples, and the
    (home piece, bucket) histogram of the check relations against the
    exact bucket counts by chi-square."""
    from scipy import stats
    cfg, u, home, ids = uniform_and_canonical(name, sf, 30)
    names = [r.name for r in u.rels]
    nj = len(u.joins)
    for rel, b in ((names.index(cfg["check"]["marginal_relation"]), None),
                   (names.index(cfg["check"]["position_relation"]), 4)):
        bucket = (tree.id_buckets(u, rel, 8) if b is None
                  else tree.position_buckets(u, rel, b))
        nb = int(bucket.max()) + 1
        expect = np.stack(tree.pieces_from(tree.bucket_counts(u, rel, bucket),
                                           nj)).astype(np.float64)
        obs = np.bincount(home * nb + bucket[ids[rel]],
                          minlength=nj * nb).reshape(nj, nb)
        live = expect > 0
        assert obs[~live].sum() == 0
        e = expect[live] * home.shape[0] / expect.sum()
        chi2 = float(((obs[live] - e) ** 2 / e).sum())
        assert stats.chi2.sf(chi2, live.sum() - 1) > 1e-4, (names[rel], chi2)


def test_residual_conditioning_has_a_limit():
    """A residual edge attribute left to the conditioning with more than
    2**16 values is refused, by name."""
    n = tree.CONDITION_LIMIT + 10
    ids = np.arange(n)
    rels = [tree.Rel("a", {"x": ids, "y": ids}, ("x",)),
            tree.Rel("b", {"x": ids, "z": ids}, ("x",), "a", ("x",)),
            tree.Rel("r", {"r_id": ids, "y": ids, "z": ids}, ("r_id",), None,
                     ("y", "z"), "residual")]
    with pytest.raises(ValueError, match="'[yz]'"):
        tree.Union(rels, [tree.JoinDef("J", {}, [])])


@pytest.mark.parametrize("rels,match", [
    ([("a", {"x": 1, "y": 1}, None, ()), ("b", {"x": 1, "y": 1}, "a", ("x",))],
     "'y' is shared"),
    ([("a", {"x": 1}, None, ()), ("b", {"x": 1}, "c", ("x",))],
     "earlier tree relation"),
    ([("a", {"x": 1}, None, ()), ("b", {"w": 1}, "a", ("x",))],
     "missing on one side"),
    ([("a", {"x": 1}, None, ()),
      ("r", {"x": 1, "q": 1}, None, ("x", "q"), "residual")],
     "not produced by a tree relation"),
], ids=["shared-not-joined", "parent-later", "edge-missing",
        "residual-unproduced"])
def test_unions_that_are_not_join_trees_are_refused(rels, match):
    col = np.arange(4)
    made = [tree.Rel(n, {a: col for a in cols}, (next(iter(cols)),), p, e,
                     *kind) for n, cols, p, e, *kind in rels]
    with pytest.raises(ValueError, match=match):
        tree.Union(made, [tree.JoinDef("J", {}, [])])
