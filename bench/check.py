"""The comparison that decides ``correct``.

It covers what clients received from ``SampleService.request``: every row
served in the window and its home piece, judged by the reference alone.

* ``requests_short`` — requests answered with another number of rows than
  asked (exact: limit 0).
* ``rows_outside_home`` — rows that are not a tuple of their home piece:
  not a row of the home join, or also a row of an earlier join in cover
  order (exact: limit 0).  This judges the membership probes.
* ``cover_bucket_chi2`` — chi-square of the rows' (home piece, bucket of
  their row in one relation: row id mod ``buckets``) against the
  exact counts of the union.  This judges the cover selection and the
  weighted walk down to that relation.
* ``position_chi2`` — the same over (home piece, place of the row among
  the rows of its range) in a deeper tree relation.  This judges the range
  probes of the walk's last hops: a probe that misses one end of its ranges
  leaves the row-id buckets nearly even but empties one end of this
  histogram.
* ``collision_gap`` — |pairs of identical samples / the pairs a uniform
  independent stream over |U| tuples gives, minus 1|.  Any concentration of
  the stream (a walk that reaches only some tuples, repeated batches) raises
  it.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import tree


def served_rows(reqs) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    ok = [r.result for r in reqs if r.error is None and r.result is not None]
    attrs = list(ok[0].rows)
    rows = {a: np.concatenate([np.asarray(s.rows[a]) for s in ok])
            for a in attrs}
    home = np.concatenate([np.asarray(s.home) for s in ok])
    return rows, home


def compare(u: tree.Union, sizes: Dict[Tuple[int, ...], int], reqs,
            cfg: dict) -> List[Tuple[str, float, float]]:
    """``(name, value, limit)`` for every number compared."""
    limits = cfg["limits"]
    short = sum(1 for r in reqs if r.error is None
                and (r.result is None or len(r.result) != r.asked))
    if not any(r.error is None and r.result is not None for r in reqs):
        return [("requests_short", float(len(reqs)),
                 float(limits["requests_short"]))]      # nothing served
    rows, home = served_rows(reqs)
    n = home.shape[0]
    nj = len(u.joins)

    mem = tree.Membership(u)
    found, ids = mem.row_ids(rows)
    m = mem.matrix(found, ids)
    h = np.clip(home, 0, nj - 1)
    earlier = (np.arange(nj)[None, :] < h[:, None]) & m
    good = (home == h) & m[np.arange(n), h] & ~earlier.any(axis=1)
    outside = int(n - good.sum())

    names = [r.name for r in u.rels]
    chk = cfg["check"]
    rel = names.index(chk["marginal_relation"])
    chi2, total = _chi2(u, rel, tree.id_buckets(u, rel, int(chk["buckets"])),
                        h, ids)
    rel = names.index(chk["position_relation"])
    pos_chi2, _ = _chi2(u, rel, tree.position_buckets(
        u, rel, int(chk["position_buckets"])), h, ids)

    sizes_ = [r.nrows for r in u.rels]
    pairs = tree.colliding_pairs(tree.tuple_codes(ids, sizes_))
    expected_pairs = n * (n - 1) / 2 / total
    gap = abs(pairs / expected_pairs - 1.0) if expected_pairs > 0 else 0.0

    return [("requests_short", float(short), float(limits["requests_short"])),
            ("rows_outside_home", float(outside),
             float(limits["rows_outside_home"])),
            ("cover_bucket_chi2", chi2, float(limits["cover_bucket_chi2"])),
            ("position_chi2", pos_chi2, float(limits["position_chi2"])),
            ("collision_gap", gap, float(limits["collision_gap"]))]


def _chi2(u: tree.Union, rel: int, bucket: np.ndarray, home: np.ndarray,
          ids: List[np.ndarray]) -> Tuple[float, float]:
    """(chi-square of (home piece, ``bucket`` of the row in relation
    ``rel``) against the exact counts, |U|)."""
    nj, nb = len(u.joins), int(bucket.max(initial=0)) + 1
    pieces = tree.pieces_from(tree.bucket_counts(u, rel, bucket), nj)
    expect = np.stack(pieces).astype(np.float64)          # (nj, nb)
    total = float(expect.sum())
    obs = np.bincount(home * nb + bucket[ids[rel]],
                      minlength=nj * nb).reshape(nj, nb)
    e = expect * (home.shape[0] / total)
    live = e > 0
    return float((((obs - e) ** 2)[live] / e[live]).sum()), total


def passed(numbers: Sequence[Tuple[str, float, float]]) -> bool:
    return all(v <= lim for _, v, lim in numbers)
