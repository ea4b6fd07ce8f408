"""Plain reference for unions of joins over shared base relations.

A union here is one join tree of base relations, each joined to its parent
on one or more attributes, plus residual relations that close cycles (a
residual relation is joined on attributes that tree relations produce, and
has no children: the skeleton and residual form of
``repro.core.joins.JoinNode``), and a list of joins, each keeping a subset
of every relation's rows: a variant copy, a pushed-down selection, or both.
Every relation's key is in the output tuple, so a tuple is in a join iff
each of its rows is kept there, and the intersection of several joins is the
same tree over their row-wise intersected relations.  Sizes are therefore
counted by dynamic programming over the tree, never by materialising a
join.  A residual relation hangs in that tree under the tree relation that
produces most of its edge; the count is summed over every value of the
edge attributes that relation does not produce.

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

# values of the attributes a residual relation is conditioned on, at most
CONDITION_LIMIT = 1 << 16


@dataclasses.dataclass
class Rel:
    name: str
    cols: Columns
    key: Tuple[str, ...]
    parent: Optional[str] = None     # tree parent; None for the root and residuals
    edge: Tuple[str, ...] = ()       # attributes it is joined on
    kind: str = "tree"               # "tree" | "residual" (closes a cycle)

    @property
    def nrows(self) -> int:
        return int(next(iter(self.cols.values())).shape[0])


def chain(rels: Sequence[Rel], edges: Sequence) -> List[Rel]:
    """``rels`` as a chain: each the child of the one before it, joined on
    ``edges[i]`` (an attribute, or a tuple of them) with ``rels[i]``."""
    if len(edges) != len(rels) - 1:
        raise ValueError("a chain of n relations needs n - 1 edges")
    out = [dataclasses.replace(rels[0], parent=None, edge=(), kind="tree")]
    for r, e in zip(rels[1:], edges):
        out.append(dataclasses.replace(
            r, parent=out[-1].name, kind="tree",
            edge=(e,) if isinstance(e, str) else tuple(e)))
    return out


@dataclasses.dataclass
class JoinDef:
    name: str
    variants: Dict[str, np.ndarray]      # relation name -> kept rows
    preds: List[Tuple[str, str, int]]    # pushed-down selections (attr, op, v)


@dataclasses.dataclass
class Union:
    rels: List[Rel]          # the root first, every tree relation after its parent
    joins: List[JoinDef]     # cover order
    plan: "_Plan" = dataclasses.field(init=False, repr=False, compare=False)

    def __post_init__(self):
        self.plan = _Plan(self.rels)

    @property
    def attrs(self) -> List[str]:
        seen: List[str] = []
        for r in self.rels:
            seen += [a for a in r.cols if a not in seen]
        return seen

    def rel(self, name: str) -> Rel:
        return next(r for r in self.rels if r.name == name)

    def masks(self, j: int) -> List[np.ndarray]:
        """Rows of every relation that join ``j`` keeps."""
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


# ---------------------------------------------------------------------------
# The tree the counting walks
# ---------------------------------------------------------------------------


def _edge_keys(child: Rel, parent: Rel, attrs: Sequence[str]
               ) -> Tuple[np.ndarray, np.ndarray, int]:
    """(child key, parent key, domain): one integer per row in ``[0,
    domain)``, equal iff the rows agree on ``attrs``.  A single attribute is
    its own key; several are packed into int64 and ranked densely."""
    if len(attrs) == 1:
        a = attrs[0]
        ck, pk = child.cols[a], parent.cols[a]
        return ck, pk, int(max(ck.max(initial=0), pk.max(initial=0))) + 1
    ck = np.zeros(child.nrows, np.int64)
    pk = np.zeros(parent.nrows, np.int64)
    width = 1
    for a in attrs:
        c, p = np.asarray(child.cols[a], np.int64), np.asarray(parent.cols[a],
                                                               np.int64)
        if min(c.min(initial=0), p.min(initial=0)) < 0:
            raise ValueError(f"edge attribute {a!r} of {child.name!r} has "
                             "negative values")
        w = int(max(c.max(initial=0), p.max(initial=0))) + 1
        if width * w >= 1 << 62:
            ck, pk, width = _dense(ck, pk)
        ck, pk, width = ck * w + c, pk * w + p, width * w
    ck, pk, width = _dense(ck, pk)
    return ck, pk, width


def _dense(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
    vals, inv = np.unique(np.concatenate([a, b]), return_inverse=True)
    inv = inv.reshape(-1)
    return inv[:a.shape[0]], inv[a.shape[0]:], int(vals.shape[0])


class _Plan:
    """How the counts run over a union's relations.

    ``up[i]`` is the relation whose rows key relation ``i``'s ranges: its
    tree parent, or for a residual relation the tree relation it hangs
    under; ``child_key[i]``, ``parent_key[i]`` and ``domain[i]`` key that
    edge.  ``cond`` lists the residual edge attributes the hang leaves out,
    each with the first tree relation that produces it; ``values`` every
    combination of their values the counts sum over."""

    def __init__(self, rels: Sequence[Rel]):
        names = [r.name for r in rels]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate relation names in {names}")
        at = {n: i for i, n in enumerate(names)}
        k = len(rels)
        self.rels = rels
        self.up = [-1] * k
        self.edge: List[Tuple[str, ...]] = [()] * k
        self.children: List[List[int]] = [[] for _ in range(k)]
        self.tree = [i for i, r in enumerate(rels) if r.kind == "tree"]
        self.residual = [i for i, r in enumerate(rels) if r.kind == "residual"]
        if len(self.tree) + len(self.residual) != k:
            raise ValueError("a relation's kind is 'tree' or 'residual'")
        if rels[0].kind != "tree" or rels[0].parent is not None:
            raise ValueError(f"the first relation, {names[0]!r}, must be the "
                             "tree root")
        for i in self.tree[1:]:
            r = rels[i]
            p = at.get(r.parent, k) if r.parent is not None else k
            if p >= i or rels[p].kind != "tree":
                raise ValueError(f"tree relation {r.name!r} must name an "
                                 "earlier tree relation as its parent")
            self._link(i, p, r.edge)
        for i in self.residual:
            r = rels[i]
            if r.parent is not None:
                raise ValueError(f"residual relation {r.name!r} has no parent")
            self._hang(i)
        _check_shared(rels, self.up, self.edge)
        self.cond: List[Tuple[str, int]] = []
        for i in self.residual:
            for a in rels[i].edge:
                if a not in self.edge[i] and a not in [c for c, _ in self.cond]:
                    self.cond.append((a, next(t for t in self.tree
                                              if a in rels[t].cols)))
        self.values = self._values()
        self.path = [self._path(i) for i in range(k)]
        # relations whose counts depend on the conditioned values
        self.affected = set()
        for i in self.residual + [p for _, p in self.cond]:
            self.affected.update(self.path[i])
        self.bottom_up = self.residual + self.tree[::-1]
        self.walk = self.tree[1:] + self.residual
        self.child_key: List[Optional[np.ndarray]] = [None] * k
        self.parent_key: List[Optional[np.ndarray]] = [None] * k
        self.domain = [0] * k
        for i in self.walk:
            (self.child_key[i], self.parent_key[i],
             self.domain[i]) = _edge_keys(rels[i], rels[self.up[i]],
                                          self.edge[i])

    def _link(self, i: int, p: int, edge: Sequence[str]) -> None:
        r, pr = self.rels[i], self.rels[p]
        if not edge:
            raise ValueError(f"relation {r.name!r} has no edge attributes")
        bad = [a for a in edge if a not in r.cols or a not in pr.cols]
        if bad:
            raise ValueError(f"edge {tuple(edge)} of {r.name!r} to "
                             f"{pr.name!r}: {bad} missing on one side")
        self.up[i], self.edge[i] = p, tuple(edge)
        self.children[p].append(i)

    def _hang(self, i: int) -> None:
        """Hang residual relation ``i`` under the tree relation producing
        most of its edge; ties go to the smaller conditioned domain."""
        r = self.rels[i]
        if not r.edge:
            raise ValueError(f"residual relation {r.name!r} has no edge")
        for a in r.edge:
            if a not in r.cols or not any(a in self.rels[t].cols
                                          for t in self.tree):
                raise ValueError(f"residual edge attribute {a!r} of "
                                 f"{r.name!r} is not produced by a tree "
                                 "relation")

        def rank(t):
            own = [a for a in r.edge if a in self.rels[t].cols]
            rest = 1
            for a in r.edge:
                if a not in own:
                    rest *= int(r.cols[a].max(initial=0)) + 1
            return (-len(own), rest)
        best = min((t for t in self.tree
                    if any(a in self.rels[t].cols for a in r.edge)), key=rank)
        self._link(i, best, tuple(a for a in r.edge
                                  if a in self.rels[best].cols))

    def _values(self) -> List[Tuple[int, ...]]:
        per = []
        for a, p in self.cond:
            v = np.unique(self.rels[p].cols[a])
            for i in self.residual:
                if a in self.rels[i].edge:
                    v = np.intersect1d(v, self.rels[i].cols[a])
            if v.shape[0] > CONDITION_LIMIT:
                raise ValueError(
                    f"residual edge attribute {a!r} takes {v.shape[0]} "
                    f"values: the reference sums over at most "
                    f"{CONDITION_LIMIT}; hang the residual relation under a "
                    f"tree relation that produces {a!r}")
            per.append(v.tolist())
        n = int(np.prod([len(v) for v in per])) if per else 1
        if n > CONDITION_LIMIT:
            raise ValueError(
                f"residual edge attributes {[a for a, _ in self.cond]} take "
                f"{n} value combinations, over the {CONDITION_LIMIT} the "
                "reference sums over")
        return list(itertools.product(*per))

    def _path(self, i: int) -> List[int]:
        out = [i]
        while self.up[out[-1]] >= 0:
            out.append(self.up[out[-1]])
        return out[::-1]

    def cases(self, masks: Sequence[np.ndarray]):
        """``masks`` under every combination of the conditioned values
        (the masks themselves where nothing is conditioned); a combination
        that no residual row carries is left out, as its count is 0."""
        if not self.cond:
            yield list(masks)
            return
        for combo in self.values:
            m = list(masks)
            for (a, p), v in zip(self.cond, combo):
                m[p] = m[p] & (self.rels[p].cols[a] == v)
                for i in self.residual:
                    if a in self.rels[i].edge:
                        m[i] = m[i] & (self.rels[i].cols[a] == v)
            if all(m[i].any() for i in self.residual):
                yield m


def _check_shared(rels: Sequence[Rel], up: Sequence[int],
                  edge: Sequence[Tuple[str, ...]]) -> None:
    """Natural-join semantics, which the counts assume: the tree relations
    that share an attribute are joined on it along the tree, and a residual
    relation's attributes that any other relation has are on its edge."""
    attrs = {a for r in rels for a in r.cols}
    for a in sorted(attrs):
        has = [i for i, r in enumerate(rels) if a in r.cols]
        if len(has) < 2:
            continue
        for i in has:
            if rels[i].kind == "residual" and a not in rels[i].edge:
                raise ValueError(f"attribute {a!r} of residual relation "
                                 f"{rels[i].name!r} is shared but not on "
                                 "its edge")
        tops = [i for i in has if rels[i].kind == "tree"
                and (up[i] < 0 or up[i] not in has or a not in edge[i])]
        if len(tops) > 1:
            raise ValueError(f"attribute {a!r} is shared by "
                             f"{[rels[i].name for i in tops]} but they are "
                             "not joined on it")


# ---------------------------------------------------------------------------
# Counting
# ---------------------------------------------------------------------------


def _down(plan: _Plan, masks: Sequence[np.ndarray], skip=frozenset(),
          target: int = -1, fixed=None):
    """(down, got): ``down[i][r]`` the tuples of the subtree under row r of
    relation i (rows kept by ``masks``); ``got[c]`` child c's counts summed
    by edge key, at every row of its parent.  Relations in ``skip`` are left
    out (and ``target``'s own sum); those outside ``plan.affected`` are
    taken from ``fixed``, an earlier pass."""
    k = len(masks)
    down: List[Optional[np.ndarray]] = [None] * k
    got: List[Optional[np.ndarray]] = [None] * k
    for i in plan.bottom_up:
        if i in skip:
            continue
        if fixed is not None and i not in plan.affected:
            down[i], got[i] = fixed[0][i], fixed[1][i]
            continue
        w = None
        for c in plan.children[i]:
            w = masks[i] * got[c] if w is None else w * got[c]
        down[i] = masks[i].astype(np.float64) if w is None else w
        if plan.up[i] >= 0 and i != target:
            agg = np.bincount(plan.child_key[i], weights=down[i],
                              minlength=plan.domain[i])
            got[i] = agg[plan.parent_key[i]]
    return down, got


def _top(plan: _Plan, masks: Sequence[np.ndarray], got, x: int
         ) -> np.ndarray:
    """top[r]: the ways to complete row r of relation ``x`` above and beside
    its subtree (its own row kept)."""
    path = plan.path[x]
    top = masks[path[0]].astype(np.float64)
    for p, c in zip(path, path[1:]):
        for s in plan.children[p]:
            if s != c:
                top = top * got[s]
        agg = np.bincount(plan.parent_key[c], weights=top,
                          minlength=plan.domain[c])
        top = masks[c] * agg[plan.child_key[c]]
    return top


def _passes(plan: _Plan, masks: Sequence[np.ndarray], skip=frozenset(),
            target: int = -1):
    """(masks, down, got) of every conditioned case; the relations the
    conditioning leaves alone are counted once."""
    fixed = None
    for m in plan.cases(masks):
        down, got = _down(plan, m, skip, target, fixed)
        fixed = (down, got)
        yield m, down, got


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
        total = 0.0
        for _, down, _ in _passes(u.plan, _and([masks[j] for j in s])):
            total += float(down[0].sum())
        out[s] = int(round(total))
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
    """Bucket of every row of relation ``rel``: its row id mod
    ``buckets``."""
    return np.arange(u.rels[rel].nrows) % buckets


def position_buckets(u: Union, rel: int, buckets: int) -> np.ndarray:
    """Bucket of every row of tree relation ``rel`` (not the root) by its
    place among the rows that share its key on the edge to its parent:
    ``rank * buckets // group size``, rank in row order.  The first rows of
    every range fall in bucket 0 and the last in the top buckets, so a walk
    that misses one end of its ranges shifts this histogram."""
    if rel == 0 or u.rels[rel].kind != "tree":
        raise ValueError(f"{u.rels[rel].name!r} is not a non-root tree "
                         "relation")
    key = u.plan.child_key[rel]
    order = np.argsort(key, kind="stable")
    s = key[order]
    start = np.searchsorted(s, s, side="left")
    size = np.searchsorted(s, s, side="right") - start
    out = np.empty(key.shape[0], np.int64)
    out[order] = (np.arange(s.shape[0]) - start) * buckets // size
    return out


def bucket_counts(u: Union, rel: int, bucket: np.ndarray
                  ) -> Dict[Tuple[int, ...], np.ndarray]:
    """Per subset S: tuples of J_S by ``bucket`` (one per row of relation
    ``rel``) of their row in that relation."""
    plan = u.plan
    masks = [u.masks(j) for j in range(len(u.joins))]
    nb = int(bucket.max(initial=0)) + 1
    above = frozenset(plan.path[rel][:-1])
    out = {}
    for s in _subsets(len(u.joins)):
        through = None
        for m, down, got in _passes(plan, _and([masks[j] for j in s]),
                                    above, rel):
            t = _top(plan, m, got, rel) * down[rel]
            through = t if through is None else through + t
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


def _pick(prefix: np.ndarray, lo, hi, uu: np.ndarray, dtype):
    """Inverse-CDF pick in ``[lo, hi)``; dead where the range weighs 0."""
    span = _round(prefix[hi] - prefix[lo], dtype)
    tgt = _round(prefix[lo] + _round(uu * span, dtype), dtype)
    pos = np.searchsorted(prefix, tgt, side="right") - 1
    return np.clip(pos, lo, np.maximum(hi - 1, lo)), span > 0


class _Walk:
    """Exact-weight walks from the root over one case's live rows (rows
    with a completion below them)."""

    def __init__(self, plan: _Plan, down: Sequence[np.ndarray], dtype,
                 drop_last: Optional[int]):
        self.plan, self.dtype, self.drop_last = plan, dtype, drop_last
        live = [np.nonzero(w > 0)[0] for w in down]
        self.root_rows = live[0]
        self.root = _round(np.concatenate([[0.0], np.cumsum(down[0][live[0]])]),
                           dtype)
        self.hops = []
        for c in plan.walk:
            rows = live[c]
            key = plan.child_key[c][rows]
            order = np.argsort(key, kind="stable")
            starts = np.searchsorted(key[order], np.arange(plan.domain[c] + 1))
            prefix = _round(np.concatenate(
                [[0.0], np.cumsum(down[c][rows[order]])]), dtype)
            self.hops.append((c, rows[order], starts, prefix))

    def draw(self, rng: np.random.Generator, n: int
             ) -> Tuple[List[np.ndarray], np.ndarray]:
        nroot = self.root.shape[0] - 1
        pos, ok = _pick(self.root, np.zeros(n, np.int64),
                        np.full(n, nroot, np.int64), rng.random(n), self.dtype)
        ids: List[Optional[np.ndarray]] = [None] * len(self.plan.rels)
        ids[0] = self.root_rows[np.minimum(pos, nroot - 1)]
        for c, rows, starts, prefix in self.hops:
            v = self.plan.parent_key[c][ids[self.plan.up[c]]]
            lo, hi = starts[v], starts[v + 1]
            if self.drop_last == c:
                hi = np.maximum(hi - 1, lo)
            pos, alive = _pick(prefix, lo, hi, rng.random(n), self.dtype)
            ok &= alive
            ids[c] = rows[np.minimum(pos, rows.shape[0] - 1)]
        return ids, ok


class JoinSampler:
    """Uniform draws from one join by exact-weight walks, as over filtered
    relations: the conditioned residual values by their exact weight, then
    every child given its parent's key, then each residual row uniformly
    among the rows that match its edge key.

    ``dtype`` is the precision in which the weight prefix sums are kept and
    the inverse-CDF targets are formed (float64 is exact; lower precisions
    round both, as a device path storing them so would).  ``drop_last``
    names a non-root tree relation whose ranges never yield their last row:
    a probe fault that a control plants."""

    def __init__(self, u: Union, masks: Sequence[np.ndarray], dtype,
                 drop_last: Optional[int] = None):
        plan = u.plan
        if drop_last is not None and (drop_last == 0 or
                                      u.rels[drop_last].kind != "tree"):
            raise ValueError("drop_last names a non-root tree relation")
        self.dtype = dtype
        self.walks, weights = [], []
        for _, down, _ in _passes(plan, masks):
            w = float(down[0].sum())
            if w > 0:
                weights.append(w)
                self.walks.append(_Walk(plan, down, dtype, drop_last))
        self.cases = _round(np.concatenate([[0.0], np.cumsum(weights)]),
                            dtype)

    def draw(self, rng: np.random.Generator, n: int
             ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Row ids of ``n`` walks, one array per relation, and which walks
        found a non-empty range at every hop."""
        if len(self.walks) == 1:
            return self.walks[0].draw(rng, n)
        nc = len(self.walks)
        case, ok = _pick(self.cases, np.zeros(n, np.int64),
                         np.full(n, nc, np.int64), rng.random(n), self.dtype)
        ids: Optional[List[np.ndarray]] = None
        for c in np.unique(case):
            at = np.nonzero(case == c)[0]
            got, alive = self.walks[c].draw(rng, at.shape[0])
            if ids is None:
                ids = [np.zeros(n, np.int64) for _ in got]
            for out, g in zip(ids, got):
                out[at] = g
            ok[at] &= alive
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
        self.joins = [JoinSampler(u, m, dtype, drop_last) if q > 0 else None
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
