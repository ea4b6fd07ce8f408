"""The reference's exact counts and membership against brute-force
enumeration of the joins, on small data (CPU, no accelerator)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tpch  # noqa: E402
from bench.harness import load_config  # noqa: E402
from bench.reference import chain  # noqa: E402


def small(name, scale_factor, data_seed=3):
    cfg, mod = load_config(ROOT, name)
    cfg = dict(cfg, scale_factor=scale_factor, data_seed=data_seed)
    return cfg, mod.build(cfg)


def enumerate_join(u, j):
    """Every tuple of join j as a row-id tuple, by nested expansion."""
    masks = u.masks(j)
    paths = [np.nonzero(masks[0])[0][:, None]]
    for i, e in enumerate(u.edges):
        parent = u.rels[i].cols[e][paths[-1][:, -1]]
        child = u.rels[i + 1].cols[e]
        kept = np.nonzero(masks[i + 1])[0]
        pairs = [(p, c) for p in range(parent.shape[0])
                 for c in kept[child[kept] == parent[p]]]
        if not pairs:
            return set()
        p_idx, c_idx = map(np.asarray, zip(*pairs))
        paths.append(np.column_stack([paths[-1][p_idx], c_idx]))
    return set(map(tuple, paths[-1].tolist()))


@pytest.mark.parametrize("name,sf", [("uq1_sf1", 0.002), ("uq2_sf1", 0.001)])
def test_counts_match_enumeration(name, sf):
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    sizes = chain.intersection_sizes(u)
    for s, size in sizes.items():
        assert size == len(set.intersection(*[sets[j] for j in s])), s
    pieces = chain.pieces_from(sizes, len(u.joins))
    seen = set()
    for j, piece in enumerate(pieces):
        assert piece == len(sets[j] - seen)
        seen |= sets[j]
    names = [r.name for r in u.rels]
    for rel, bucket in (
            (names.index(cfg["check"]["marginal_relation"]), "id"),
            (names.index(cfg["check"]["position_relation"]), "position")):
        b = (chain.id_buckets(u, rel, 8) if bucket == "id"
             else chain.position_buckets(u, rel, 4))
        vec = chain.pieces_from(chain.bucket_counts(u, rel, b), len(u.joins))
        seen = set()
        for j, v in enumerate(vec):
            mine = sets[j] - seen
            seen |= sets[j]
            want = np.bincount([b[t[rel]] for t in mine], minlength=v.shape[0])
            np.testing.assert_array_equal(v, want)


def test_position_buckets_place_rows_in_their_range():
    cfg, u = small("uq1_sf1", 0.002)
    li = u.rel("lineitem")
    b = chain.position_buckets(u, len(u.rels) - 1, 16)
    lines = np.bincount(li.cols["ok"])[li.cols["ok"]]
    np.testing.assert_array_equal(b, li.cols["ln"] * 16 // lines)
    assert (b[li.cols["ln"] == 0] == 0).all()
    last = (li.cols["ln"] == lines - 1) & (lines > 1)
    assert last.any() and (b[last] >= 8).all()


def test_counts_match_the_program_enumeration():
    """The program's exact_overlap (materialised joins) agrees."""
    from repro.core.index import Catalog
    from repro.core.overlap import exact_overlap

    from bench.system import program_joins
    cfg, u = small("uq2_sf1", 0.001)
    specs = program_joins(u)
    sizes = chain.intersection_sizes(u)
    for s, size in sizes.items():
        assert exact_overlap(Catalog(), [specs[j] for j in s]) == size


@pytest.mark.parametrize("name,sf", [("uq1_sf1", 0.002), ("uq2_sf1", 0.001)])
def test_membership_matches_enumeration(name, sf):
    cfg, u = small(name, sf)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    union = sorted(set.union(*sets))
    ids = [np.asarray([t[r] for t in union]) for r in range(len(u.rels))]
    rows = {}
    for r, rid in zip(u.rels, ids):
        for a, c in r.cols.items():
            rows.setdefault(a, c[rid])
    mem = chain.Membership(u)
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


def test_reference_sampler_is_uniform_and_canonical():
    """Float64 reference draws: canonical homes and a uniform stream."""
    from scipy import stats
    cfg, u = small("uq1_sf1", 0.002)
    sets = [enumerate_join(u, j) for j in range(len(u.joins))]
    union = sorted(set.union(*sets))
    pieces = chain.pieces_from(chain.intersection_sizes(u), len(u.joins))
    n = 30 * len(union)
    rows, home = chain.sample_union(u, pieces, n, np.random.default_rng(0))
    mem = chain.Membership(u)
    found, ids = mem.row_ids(rows)
    m = mem.matrix(found, ids)
    assert m[np.arange(n), home].all()
    assert not (m & (np.arange(len(u.joins))[None, :] < home[:, None])).any()
    pos = {t: i for i, t in enumerate(union)}
    counts = np.bincount([pos[t] for t in zip(*[i.tolist() for i in ids])],
                         minlength=len(union))
    assert stats.chisquare(counts).pvalue > 1e-4
