"""Plain reference for unions of chain joins over shared base relations.

A union here is a chain ``R_1 -e_1- R_2 -e_2- ... R_k`` of base relations
(each edge one dense integer attribute) and a list of joins, each keeping a
subset of every relation's rows: a variant copy, a pushed-down selection, or
both.  Every relation's key is in the output tuple, so a tuple is in a join
iff each of its rows is kept there, and the intersection of several joins is
the chain over their row-wise intersected relations.  Sizes are therefore
counted by dynamic programming over the chain, never by materialising a
join.

Nothing here imports the program under test: it is numpy over the
benchmark's own generated columns.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Columns = Dict[str, np.ndarray]

_OPS = {"<=": np.less_equal, ">=": np.greater_equal, "<": np.less,
        ">": np.greater, "==": np.equal}


@dataclasses.dataclass
class Rel:
    name: str
    cols: Columns
    key: Tuple[str, ...]

    @property
    def nrows(self) -> int:
        return int(next(iter(self.cols.values())).shape[0])


@dataclasses.dataclass
class JoinDef:
    name: str
    variants: Dict[str, np.ndarray]      # relation name -> kept rows
    preds: List[Tuple[str, str, int]]    # pushed-down selections (attr, op, v)


@dataclasses.dataclass
class Union:
    rels: List[Rel]          # chain order, root first
    edges: List[str]         # edges[i] joins rels[i] and rels[i + 1]
    joins: List[JoinDef]     # cover order

    @property
    def attrs(self) -> List[str]:
        seen: List[str] = []
        for r in self.rels:
            seen += [a for a in r.cols if a not in seen]
        return seen

    def rel(self, name: str) -> Rel:
        return next(r for r in self.rels if r.name == name)

    def masks(self, j: int) -> List[np.ndarray]:
        """Rows of every chain relation that join ``j`` keeps."""
        jd = self.joins[j]
        out = []
        for r in self.rels:
            m = np.ones(r.nrows, dtype=bool)
            if r.name in jd.variants:
                m &= jd.variants[r.name]
            for a, op, v in jd.preds:
                if a in r.cols:
                    m &= _OPS[op](r.cols[a], v)
            out.append(m)
        return out


def _domain(u: Union, i: int) -> int:
    e = u.edges[i]
    return int(max(u.rels[i].cols[e].max(initial=0),
                   u.rels[i + 1].cols[e].max(initial=0))) + 1


def _backward(u: Union, masks: Sequence[np.ndarray], stop: int = 0
              ) -> List[Optional[np.ndarray]]:
    """bwd[i][r]: paths from row r of R_i to the leaf (rows i..k kept)."""
    k = len(u.rels)
    bwd: List[Optional[np.ndarray]] = [None] * k
    bwd[k - 1] = masks[k - 1].astype(np.float64)
    for i in range(k - 2, stop - 1, -1):
        e = u.edges[i]
        agg = np.bincount(u.rels[i + 1].cols[e], weights=bwd[i + 1],
                          minlength=_domain(u, i))
        bwd[i] = masks[i] * agg[u.rels[i].cols[e]]
    return bwd


def _forward(u: Union, masks: Sequence[np.ndarray], stop: int
             ) -> List[Optional[np.ndarray]]:
    """fwd[i][r]: paths from the root to row r of R_i (rows 0..i kept)."""
    fwd: List[Optional[np.ndarray]] = [None] * len(u.rels)
    fwd[0] = masks[0].astype(np.float64)
    for i in range(1, stop + 1):
        e = u.edges[i - 1]
        agg = np.bincount(u.rels[i - 1].cols[e], weights=fwd[i - 1],
                          minlength=_domain(u, i - 1))
        fwd[i] = masks[i] * agg[u.rels[i].cols[e]]
    return fwd


def _and(mask_lists: Sequence[Sequence[np.ndarray]]) -> List[np.ndarray]:
    out = [m.copy() for m in mask_lists[0]]
    for ms in mask_lists[1:]:
        for o, m in zip(out, ms):
            o &= m
    return out


def _subsets(n: int):
    for size in range(1, n + 1):
        yield from itertools.combinations(range(n), size)


def intersection_sizes(u: Union) -> Dict[Tuple[int, ...], int]:
    """|J_S| = |∩_{j in S} J_j| for every non-empty subset S of joins."""
    masks = [u.masks(j) for j in range(len(u.joins))]
    out = {}
    for s in _subsets(len(u.joins)):
        bwd = _backward(u, _and([masks[j] for j in s]))
        out[s] = int(round(float(bwd[0].sum())))
    return out


def pieces_from(vals: Dict[Tuple[int, ...], object], n: int) -> List[object]:
    """Cover pieces J'_i = J_i minus the earlier joins, by inclusion and
    exclusion over the intersection values (ints or count vectors)."""
    out = []
    for i in range(n):
        acc = 0
        for size in range(0, i + 1):
            for t in itertools.combinations(range(i), size):
                acc = acc + (-1) ** size * vals[tuple(sorted(t + (i,)))]
        out.append(acc)
    return out


def id_buckets(u: Union, rel: int, buckets: int) -> np.ndarray:
    """Bucket of every row of chain relation ``rel``: its row id mod
    ``buckets``."""
    return np.arange(u.rels[rel].nrows) % buckets


def position_buckets(u: Union, rel: int, buckets: int) -> np.ndarray:
    """Bucket of every row of chain relation ``rel`` (not the root) by its
    place among the rows that share its key on the edge from its parent:
    ``rank * buckets // group size``, rank in row order.  The first rows of
    every range fall in bucket 0 and the last in the top buckets, so a walk
    that misses one end of its ranges shifts this histogram."""
    key = u.rels[rel].cols[u.edges[rel - 1]]
    order = np.argsort(key, kind="stable")
    s = key[order]
    start = np.searchsorted(s, s, side="left")
    size = np.searchsorted(s, s, side="right") - start
    out = np.empty(key.shape[0], np.int64)
    out[order] = (np.arange(s.shape[0]) - start) * buckets // size
    return out


def bucket_counts(u: Union, rel: int, bucket: np.ndarray
                  ) -> Dict[Tuple[int, ...], np.ndarray]:
    """Per subset S: tuples of J_S by ``bucket`` (one per row of chain
    relation ``rel``) of their row in that relation."""
    masks = [u.masks(j) for j in range(len(u.joins))]
    nb = int(bucket.max(initial=0)) + 1
    out = {}
    for s in _subsets(len(u.joins)):
        m = _and([masks[j] for j in s])
        through = _forward(u, m, rel)[rel] * _backward(u, m, rel)[rel]
        out[s] = np.rint(np.bincount(bucket, weights=through,
                                     minlength=nb)).astype(np.int64)
    return out


# ---------------------------------------------------------------------------
# Membership: which base row a tuple names, and which joins keep it
# ---------------------------------------------------------------------------


class KeyIndex:
    """Key -> row id of one base relation (keys are unique)."""

    def __init__(self, rel: Rel):
        self.rel = rel
        self.radix = [int(rel.cols[a].max(initial=0)) + 1 for a in rel.key]
        packed = self._pack([rel.cols[a] for a in rel.key])
        self.order = np.argsort(packed, kind="stable")
        self.sorted = packed[self.order]

    def _pack(self, cols: Sequence[np.ndarray]) -> np.ndarray:
        out = np.zeros(np.asarray(cols[0]).shape[0], dtype=np.int64)
        for c, w in zip(cols, self.radix):
            out = out * w + np.asarray(c, np.int64)
        return out

    def lookup(self, rows: Columns) -> Tuple[np.ndarray, np.ndarray]:
        """(found, row id) per tuple: found iff the key names a row and every
        attribute of that row equals the tuple's."""
        cols = [np.asarray(rows[a], np.int64) for a in self.rel.key]
        ok = np.ones(cols[0].shape[0], dtype=bool)
        for c, w in zip(cols, self.radix):
            ok &= (c >= 0) & (c < w)
        packed = self._pack([np.where(ok, c, 0) for c in cols])
        pos = np.minimum(np.searchsorted(self.sorted, packed),
                         self.sorted.shape[0] - 1)
        ok &= self.sorted[pos] == packed
        ids = self.order[pos]
        for a, c in self.rel.cols.items():
            ok &= c[ids] == np.asarray(rows[a], np.int64)
        return ok, ids


class Membership:
    """Reference membership of tuples in the union's joins."""

    def __init__(self, u: Union):
        self.u = u
        self.index = [KeyIndex(r) for r in u.rels]
        self.masks = [u.masks(j) for j in range(len(u.joins))]

    def row_ids(self, rows: Columns) -> Tuple[np.ndarray, List[np.ndarray]]:
        found = None
        ids = []
        for ix in self.index:
            ok, rid = ix.lookup(rows)
            found = ok if found is None else found & ok
            ids.append(rid)
        return found, ids

    def matrix(self, found: np.ndarray, ids: Sequence[np.ndarray]
               ) -> np.ndarray:
        """(N, joins) bool: tuple is a row of the join."""
        out = np.empty((found.shape[0], len(self.masks)), dtype=bool)
        for j, ms in enumerate(self.masks):
            m = found.copy()
            for mask, rid in zip(ms, ids):
                m &= mask[rid]
            out[:, j] = m
        return out


def tuple_codes(ids: Sequence[np.ndarray], sizes: Sequence[int]
                ) -> np.ndarray:
    """(N, words) int64 packing of the row-id tuple (one tuple, one code)."""
    words, cur, width = [], None, 1
    for rid, n in zip(ids, sizes):
        n = max(int(n), 1)
        if cur is not None and width * n < (1 << 62):
            cur, width = cur * n + rid, width * n
        else:
            if cur is not None:
                words.append(cur)
            cur, width = np.asarray(rid, np.int64).copy(), n
    words.append(cur)
    return np.stack(words, axis=1)


def colliding_pairs(codes: np.ndarray) -> int:
    """Pairs of samples that are the same tuple."""
    if codes.shape[0] < 2:
        return 0
    order = np.lexsort(codes.T[::-1])
    s = codes[order]
    new = np.ones(s.shape[0], dtype=bool)
    new[1:] = (s[1:] != s[:-1]).any(axis=1)
    runs = np.diff(np.append(np.nonzero(new)[0], s.shape[0]))
    return int((runs * (runs - 1) // 2).sum())


# ---------------------------------------------------------------------------
# Reference sampler (Algorithm 1 over exact EW weights), at a chosen precision
# ---------------------------------------------------------------------------


def _round(x: np.ndarray, dtype) -> np.ndarray:
    return np.asarray(x).astype(dtype).astype(np.float64)


class ChainSampler:
    """Uniform draws from one join by exact-weight walks over the join's
    live rows (rows with a path to a leaf), as over filtered relations.

    ``dtype`` is the precision in which the weight prefix sums are kept and
    the inverse-CDF targets are formed (float64 is exact; lower precisions
    round both, as a device path storing them so would).  ``drop_last``
    names a chain relation whose ranges never yield their last row: a
    probe fault that a control plants."""

    def __init__(self, u: Union, masks: Sequence[np.ndarray], dtype,
                 drop_last: Optional[int] = None):
        self.u, self.dtype, self.drop_last = u, dtype, drop_last
        bwd = _backward(u, masks)
        live = [np.nonzero(w > 0)[0] for w in bwd]
        self.root_rows = live[0]
        self.root = _round(np.concatenate([[0.0], np.cumsum(bwd[0][live[0]])]),
                           dtype)
        self.hops = []
        for i, e in enumerate(u.edges):
            rows = live[i + 1]
            key = u.rels[i + 1].cols[e][rows]
            order = np.argsort(key, kind="stable")
            starts = np.searchsorted(key[order], np.arange(_domain(u, i) + 1))
            prefix = _round(np.concatenate(
                [[0.0], np.cumsum(bwd[i + 1][rows[order]])]), dtype)
            self.hops.append((rows[order], starts, prefix))

    def _pick(self, prefix: np.ndarray, lo, hi, uu: np.ndarray):
        """Inverse-CDF pick in ``[lo, hi)``; dead where the range weighs 0."""
        span = _round(prefix[hi] - prefix[lo], self.dtype)
        tgt = _round(prefix[lo] + _round(uu * span, self.dtype), self.dtype)
        pos = np.searchsorted(prefix, tgt, side="right") - 1
        return np.clip(pos, lo, np.maximum(hi - 1, lo)), span > 0

    def draw(self, rng: np.random.Generator, n: int
             ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Row ids of ``n`` walks, one array per chain relation, and which
        walks found a non-empty range at every hop."""
        nroot = self.root.shape[0] - 1
        pos, ok = self._pick(self.root, np.zeros(n, np.int64),
                             np.full(n, nroot, np.int64), rng.random(n))
        ids = [self.root_rows[np.minimum(pos, nroot - 1)]]
        for i, (rows, starts, prefix) in enumerate(self.hops):
            v = self.u.rels[i].cols[self.u.edges[i]][ids[-1]]
            lo, hi = starts[v], starts[v + 1]
            if self.drop_last == i + 1:
                hi = np.maximum(hi - 1, lo)
            pos, alive = self._pick(prefix, lo, hi, rng.random(n))
            ok &= alive
            ids.append(rows[np.minimum(pos, rows.shape[0] - 1)])
        return ids, ok


class UnionSampler:
    """Algorithm 1 over exact pieces: pick a piece by its share, draw in
    its join, keep a candidate iff no earlier join holds it."""

    def __init__(self, u: Union, pieces: Sequence[int], dtype=np.float64,
                 drop_last: Optional[int] = None):
        self.u = u
        self.masks = [u.masks(j) for j in range(len(u.joins))]
        p = _round(np.asarray(pieces, np.float64) / float(sum(pieces)), dtype)
        self.probs = p / p.sum()
        self.joins = [ChainSampler(u, m, dtype, drop_last) if q > 0 else None
                      for m, q in zip(self.masks, self.probs)]

    def sample(self, n: int, rng: np.random.Generator,
               draws_per_sample: Optional[float] = None
               ) -> Tuple[Columns, np.ndarray]:
        """``n`` samples; with ``draws_per_sample`` each piece stops after
        that many candidate draws per sample it owes, and the stream holds
        what the draws yielded (a sampler too broken to finish in time)."""
        u = self.u
        counts = rng.multinomial(n, self.probs)
        parts, homes = [], []
        for j, need in enumerate(counts):
            if need == 0:
                continue
            budget = (np.inf if draws_per_sample is None
                      else int(np.ceil(need * draws_per_sample)))
            got, have, idle = [], 0, 0
            while have < need and budget > 0:
                if idle == 50 and draws_per_sample is None:
                    raise RuntimeError(f"piece {j} yields no candidate")
                k = int(min(max(2 * (need - have), 1024), budget))
                budget -= k
                ids, keep = self.joins[j].draw(rng, k)
                for q in range(j):
                    inq = np.ones_like(keep)
                    for m, rid in zip(self.masks[q], ids):
                        inq &= m[rid]
                    keep &= ~inq
                idx = np.nonzero(keep)[0][:need - have]
                got.append([rid[idx] for rid in ids])
                have += idx.shape[0]
                idle = 0 if idx.shape[0] else idle + 1
            parts.append([np.concatenate([g[r] for g in got])
                          for r in range(len(u.rels))])
            homes.append(np.full(have, j, np.int64))
        ids = [np.concatenate([p_[r] for p_ in parts])
               for r in range(len(u.rels))]
        home = np.concatenate(homes)
        perm = rng.permutation(home.shape[0])
        rows: Columns = {}
        for r, rid in zip(u.rels, ids):
            for a, c in r.cols.items():
                if a not in rows:
                    rows[a] = c[rid[perm]]
        return rows, home[perm]


def sample_union(u: Union, pieces: Sequence[int], n: int,
                 rng: np.random.Generator, dtype=np.float64
                 ) -> Tuple[Columns, np.ndarray]:
    """``n`` samples of the union by :class:`UnionSampler`."""
    return UnionSampler(u, pieces, dtype).sample(n, rng)
