"""Device (JAX) estimation engine — batched wander-join walks on accelerator.

Mirrors :class:`~repro.core.estimators.numpy_estimator.NumpyEstimator`
semantics with the whole observation pipeline fused into one jitted program
per ``(pivot, Δ)``:

* :class:`DeviceWalkJoin` — a whole batch of wander-join walks (§6.1) as one
  traced program: uniform root pick, then per relation in expansion order a
  composite-key range probe + ranged uniform pick with dead-walk masking and
  per-walk probability accumulation ``p(t) = 1/|R_root| · Π 1/d_i``.  On TPU
  each hop routes through the fused Pallas ``hop_refine_pick`` kernel of
  :mod:`repro.kernels.walk` (fence sweep → row gather → fused refine+pick);
  on CPU it lowers via ``jnp.searchsorted``.  Residual (cycle-closing) edges
  are plain hops for wander join, so cyclic joins walk too.
* :class:`DeviceRunning` — Horvitz–Thompson mean/variance accumulators kept
  as device scalars ``(count, mean, M2)``; each batch folds in via the
  associative Chan/Welford merge (algebraically identical to the host
  reference's sequential Welford update).
* the fused observe program — walks + membership indicators (probing walk
  endpoints against the PR-1 :class:`~repro.core.backends.jax_backend.
  DeviceJoinMembership` sorted-fingerprint oracle) + the HT reduction into
  the ``|J|`` and ``|O_Δ|`` accumulators, all in one jit.  Only the walk
  pool (reuse, §7) is pulled back to the host.
* :class:`DeviceHistogramOverlap` — §5 / Theorem 4 bucketed join-size and
  overlap bounds with the per-value histogram algebra (intersect / min /
  sum) as vectorised device ops, so ONLINE-UNION initialisation is also
  off-host.

Limits match the PR-1 device engine: non-negative dict-encoded values whose
packed edge-key domains fit in int32 (checked at build time with clear
errors).  Accumulation is float32 on device; the equivalence tests bound the
drift against the float64 host reference.
"""

from __future__ import annotations

import math
from typing import Dict, FrozenSet, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..index import Catalog
from ..join_sampler import JoinSampler
from ..joins import JoinSpec
from ..overlap import HistogramOverlap
from ..size_estimation import z_value
from .base import EstimationLoop, OverlapEstimate, PoolBatch, ReservoirPool

from ..backends.jax_backend import (DeviceJoinMembership, _as_i32,
                                    _attr_widths, _pack_jnp, _pack_np,
                                    _I32_LIM)

Rows = Dict[str, np.ndarray]

_TINY = 1e-30


# ---------------------------------------------------------------------------
# Device walker: batched wander-join walks over one join
# ---------------------------------------------------------------------------


class DeviceWalkJoin:
    """One join prepared for jitted batched wander-join walks."""

    def __init__(self, cat: Catalog, spec: JoinSpec,
                 use_pallas: Optional[bool] = None):
        if use_pallas is None:
            from ...kernels.ops import on_tpu
            use_pallas = on_tpu()
        self.use_pallas = bool(use_pallas)
        self.name = spec.name
        self.spec = spec
        self.attrs = tuple(spec.output_attrs)

        js = JoinSampler(cat, spec, method="wj")   # host walk plan (no weights)
        widths = _attr_widths(spec)
        self.node_edge_attrs: List[Tuple[str, ...]] = []
        self.node_radices: List[Tuple[int, ...]] = []
        self.sorted_keys: List[jnp.ndarray] = []
        self.perm: List[jnp.ndarray] = []
        self.cols: List[Dict[str, jnp.ndarray]] = []
        self._prepped: List[object] = []

        produced = set(js.root_rel.attrs)
        for n in js.order[1:]:
            rel = js._reduced[n.alias]
            radices = tuple(widths[a] for a in n.edge_attrs)
            dom = 1
            for w in radices:
                dom *= w
            if dom >= _I32_LIM:
                raise ValueError(
                    f"jax estimator: packed edge-key domain of node "
                    f"{n.alias!r} ({dom}) exceeds int32; use the numpy "
                    "estimator")
            key = _pack_np([rel.columns[a] for a in n.edge_attrs], radices)
            perm = np.argsort(key, kind="stable")
            new_attrs = tuple(a for a in rel.attrs if a not in produced)
            produced.update(rel.attrs)
            self.node_edge_attrs.append(tuple(n.edge_attrs))
            self.node_radices.append(radices)
            self.sorted_keys.append(jnp.asarray(key[perm].astype(np.int32)))
            self.perm.append(jnp.asarray(perm.astype(np.int32)))
            self.cols.append({a: jnp.asarray(_as_i32(c, f"{rel.name}.{a}"))
                              for a, c in rel.columns.items()
                              if a in new_attrs})
            if self.use_pallas:
                from ...kernels.searchsorted import PreparedKeys
                self._prepped.append(PreparedKeys(key[perm]))
            else:
                self._prepped.append(None)

        self.root_cols = {a: jnp.asarray(_as_i32(c, f"root.{a}"))
                          for a, c in js.root_rel.columns.items()}
        self.n_root = js.root_rel.nrows
        self._empty = (self.n_root == 0 or
                       any(k.shape[0] == 0 for k in self.sorted_keys))

    def is_empty(self) -> bool:
        return self._empty

    # -- one hop: (pos, degree) per walk --------------------------------------
    def _hop(self, i: int, q: jnp.ndarray, u: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if not self.use_pallas:
            sk = self.sorted_keys[i]
            lo = jnp.searchsorted(sk, q, side="left").astype(jnp.int32)
            hi = jnp.searchsorted(sk, q, side="right").astype(jnp.int32)
            d = hi - lo
            off = jnp.floor(u * jnp.maximum(d, 1).astype(jnp.float32)
                            ).astype(jnp.int32)
            off = jnp.minimum(off, jnp.maximum(d - 1, 0))
            return lo + off, d
        from ...kernels.ops import default_interpret
        from ...kernels.searchsorted import to_tiles
        from ...kernels.walk import _hop_i32
        prep = self._prepped[i]
        b = q.shape[0]
        # keys are non-negative int32, so the 64-bit split is (hi=0, lo=q^MIN)
        q_lo = to_tiles(q) ^ jnp.int32(-(1 << 31))
        q_hi = jnp.zeros_like(q_lo)
        pos, deg = _hop_i32(q_hi, q_lo, to_tiles(u.astype(jnp.float32)),
                            *prep.arrays(), interpret=default_interpret())
        return pos.reshape(-1)[:b], deg.reshape(-1)[:b]

    # -- one batch of walks (traced; jit at the call site) --------------------
    def draw(self, key: jax.Array, batch: int
             ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, jnp.ndarray]:
        """``batch`` wander-join walks: (rows, p(t), ok).  p(t)=0 for dead."""
        keys = jax.random.split(key, len(self.sorted_keys) + 1)
        r_pos = jax.random.randint(keys[0], (batch,), 0, max(self.n_root, 1))
        rows = {a: c[r_pos] for a, c in self.root_cols.items()}
        ok = jnp.full((batch,), self.n_root > 0)
        prob = jnp.full((batch,), 1.0 / max(self.n_root, 1), jnp.float32)
        for i, (edge_attrs, radices) in enumerate(
                zip(self.node_edge_attrs, self.node_radices)):
            q = _pack_jnp(rows, edge_attrs, radices)
            u = jax.random.uniform(keys[i + 1], (batch,))
            pos, d = self._hop(i, q, u)
            alive = ok & (d > 0)
            prob = jnp.where(alive,
                             prob / jnp.maximum(d, 1).astype(jnp.float32), 0.0)
            ok = alive
            n_i = self.perm[i].shape[0]
            child = self.perm[i][jnp.clip(pos, 0, n_i - 1)]
            for a, c in self.cols[i].items():
                rows[a] = c[child]
        return rows, prob, ok


# ---------------------------------------------------------------------------
# Device-resident HT accumulators
# ---------------------------------------------------------------------------


def _batch_moments(x: jnp.ndarray):
    """(n, mean, M2) of one batch — every element counts (zeros included)."""
    mean = jnp.mean(x)
    m2 = jnp.sum((x - mean) ** 2)
    return jnp.int32(x.shape[0]), mean, m2


def _merge_moments(count, mean, m2, bn, bmean, bm2):
    """Chan's associative merge — the batched form of Welford's update."""
    n = count + bn
    nf = jnp.maximum(n.astype(jnp.float32), 1.0)
    bnf = bn.astype(jnp.float32)
    d = bmean - mean
    return (n,
            mean + d * bnf / nf,
            m2 + bm2 + d * d * count.astype(jnp.float32) * bnf / nf)


class DeviceRunning:
    """Running mean/variance kept as device scalars (count, mean, M2).

    Read surface matches :class:`~repro.core.size_estimation.RunningMean`
    (``count`` / ``mean`` / ``variance`` / ``half_width``); reads pull the
    scalars to host lazily.
    """

    def __init__(self):
        self.state = (jnp.int32(0), jnp.float32(0.0), jnp.float32(0.0))

    @property
    def count(self) -> int:
        return int(self.state[0])

    @property
    def mean(self) -> float:
        return float(self.state[1])

    @property
    def m2(self) -> float:
        return float(self.state[2])

    @property
    def variance(self) -> float:
        c = self.count
        return self.m2 / (c - 1) if c > 1 else 0.0

    def half_width(self, confidence: float = 0.90) -> float:
        c = self.count
        if c < 2:
            return math.inf
        return z_value(confidence) * math.sqrt(self.variance / c)

    def update_zeros(self, n: int) -> None:
        """Fold in ``n`` all-zero observations (walks on an empty join)."""
        self.state = _merge_moments(*self.state, jnp.int32(n),
                                    jnp.float32(0.0), jnp.float32(0.0))


# ---------------------------------------------------------------------------
# The estimator backend
# ---------------------------------------------------------------------------


class JaxEstimator(EstimationLoop):
    """Device-resident |J| / |O_Δ| estimation: walks + probes + HT in one jit."""

    name = "jax"

    def __init__(self, cat: Catalog, joins: Sequence[JoinSpec], seed: int = 0,
                 batch: int = 512, pool_cap: int = 512,
                 use_pallas: Optional[bool] = None,
                 members: Optional[Dict[str, DeviceJoinMembership]] = None,
                 mesh=None, mesh_axis: str = "shards"):
        self.cat = cat
        # mesh=: run each observation as `world` independent walk batches
        # under shard_map (walker arrays replicated, per-shard fold-in keys)
        # and merge the per-shard HT moments on-mesh in one psum
        # (repro.core.sharding.stats.psum_merge_moments) before folding them
        # into the host-visible DeviceRunning accumulators.
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.world = int(mesh.shape[mesh_axis]) if mesh is not None else 1
        self.joins = list(joins)
        self.by_name = {j.name: j for j in self.joins}
        schemas = {tuple(sorted(j.output_attrs)) for j in self.joins}
        if len(schemas) > 1:
            raise ValueError(
                f"joins must share an output schema; got {sorted(schemas)}")
        self.batch = int(batch)
        self.key = jax.random.PRNGKey(seed)
        self.walkers: Dict[str, DeviceWalkJoin] = {
            j.name: DeviceWalkJoin(cat, j, use_pallas=use_pallas)
            for j in self.joins}
        # reuse the sampling backend's membership indexes when handed in
        # (OnlineUnionSampler shares them) — otherwise build our own
        self.members: Dict[str, DeviceJoinMembership] = (
            members if members is not None
            else {j.name: DeviceJoinMembership(j, use_pallas=use_pallas)
                  for j in self.joins})
        self._stats: Dict[FrozenSet[str], DeviceRunning] = {}
        self._size_stats: Dict[str, DeviceRunning] = {}
        self._pool = ReservoirPool(cap=pool_cap, seed=seed)
        self._observe_fns: Dict[Tuple[str, Tuple[str, ...]], object] = {}

    # -- accumulator views / pool ---------------------------------------------
    @property
    def size_stats(self) -> Mapping[str, DeviceRunning]:
        return self._size_stats

    @property
    def overlap_stats(self) -> Mapping[FrozenSet[str], DeviceRunning]:
        return self._stats

    @property
    def walk_pool(self) -> Dict[str, List[PoolBatch]]:
        return self._pool.pools

    def drain_pool(self) -> Dict[str, List[PoolBatch]]:
        return self._pool.drain()

    # -- fused observe program ------------------------------------------------
    def _observe_fn(self, pivot_name: str, other_names: Tuple[str, ...]):
        key = (pivot_name, other_names)
        fn = self._observe_fns.get(key)
        if fn is None:
            walker = self.walkers[pivot_name]
            members = [self.members[n] for n in other_names]
            batch = self.batch

            if self.mesh is None:
                def run(k, size_state, overlap_state):
                    rows, prob, ok = walker.draw(k, batch)
                    inv = jnp.where(ok & (prob > 0),
                                    1.0 / jnp.maximum(prob, _TINY), 0.0)
                    ind = ok
                    for m in members:
                        ind = ind & m.contains(rows)
                    contrib = jnp.where(ind, inv, 0.0)
                    size_state = _merge_moments(*size_state,
                                                *_batch_moments(inv))
                    overlap_state = _merge_moments(*overlap_state,
                                                   *_batch_moments(contrib))
                    return rows, prob, size_state, overlap_state
            else:
                from jax.sharding import PartitionSpec as P
                from ..sharding.stats import psum_merge_moments
                axis, world = self.mesh_axis, self.world

                def shard_run(k):
                    sid = jax.lax.axis_index(axis)
                    ks = jax.random.fold_in(k, sid) if world > 1 else k
                    rows, prob, ok = walker.draw(ks, batch)
                    inv = jnp.where(ok & (prob > 0),
                                    1.0 / jnp.maximum(prob, _TINY), 0.0)
                    ind = ok
                    for m in members:
                        ind = ind & m.contains(rows)
                    contrib = jnp.where(ind, inv, 0.0)
                    smom = psum_merge_moments(*_batch_moments(inv), axis)
                    omom = psum_merge_moments(*_batch_moments(contrib), axis)
                    return ({a: v[None] for a, v in rows.items()},
                            prob[None],
                            tuple(x[None] for x in smom),
                            tuple(x[None] for x in omom))

                sharded = jax.shard_map(shard_run, mesh=self.mesh,
                                        in_specs=(P(),), out_specs=P(axis),
                                        check_vma=False)

                def run(k, size_state, overlap_state):
                    rows, prob, smom, omom = sharded(k)
                    size_state = _merge_moments(
                        *size_state, smom[0][0], smom[1][0], smom[2][0])
                    overlap_state = _merge_moments(
                        *overlap_state, omom[0][0], omom[1][0], omom[2][0])
                    return rows, prob, size_state, overlap_state

            fn = self._observe_fns[key] = jax.jit(run)
        return fn

    def observe(self, delta: Sequence[JoinSpec], rounds: int = 1
                ) -> OverlapEstimate:
        """Run ``rounds`` device walk+probe batches on Δ's pivot."""
        delta = list(delta)
        dkey = frozenset(j.name for j in delta)
        stat = self._stats.setdefault(dkey, DeviceRunning())
        pivot = self._pivot(delta)
        sstat = self._size_stats.setdefault(pivot.name, DeviceRunning())
        walker = self.walkers[pivot.name]
        if walker.is_empty():
            # every walk fails: HT draws are observations of zero
            for _ in range(rounds):
                sstat.update_zeros(self.batch * self.world)
                stat.update_zeros(self.batch * self.world)
            return OverlapEstimate(stat.mean, stat.half_width(0.90), stat.count)
        others = tuple(sorted(j.name for j in delta if j.name != pivot.name))
        fn = self._observe_fn(pivot.name, others)
        for _ in range(rounds):
            self.key, sub = jax.random.split(self.key)
            rows, prob, sstat.state, stat.state = fn(sub, sstat.state,
                                                     stat.state)
            # on a mesh the shards' batches come back stacked (world, batch);
            # flatten into one pool batch (dead walks keep prob 0)
            self._pool.add(pivot.name, (
                {a: np.asarray(v, dtype=np.int64).reshape(-1)
                 for a, v in rows.items()},
                np.asarray(prob, dtype=np.float64).reshape(-1)))
        return OverlapEstimate(stat.mean, stat.half_width(0.90), stat.count)

    # -- §5 initialisation ----------------------------------------------------
    def histogram(self, mode: str = "max") -> "DeviceHistogramOverlap":
        return DeviceHistogramOverlap(self.cat, self.joins, mode=mode)


# ---------------------------------------------------------------------------
# Device histogram overlap (§5 / Theorem 4 on device)
# ---------------------------------------------------------------------------


def _lookup_sorted(v: jnp.ndarray, c: jnp.ndarray, valid: jnp.ndarray,
                   q: jnp.ndarray):
    """Per-query (hit, count) lookup into a sorted unique value histogram."""
    n = v.shape[0]
    if n == 0:
        z = jnp.zeros(q.shape[0], bool)
        return z, jnp.zeros(q.shape[0], jnp.float32)
    pos = jnp.searchsorted(v, q)
    posc = jnp.clip(pos, 0, n - 1)
    hit = (pos < n) & (v[posc] == q) & valid[posc]
    return hit, jnp.where(hit, c[posc], 0.0)


class DeviceHistogramOverlap(HistogramOverlap):
    """§5 histogram bounds with the per-value algebra as device ops.

    The split-plan construction and the Theorem-4 scalar multipliers stay on
    host (they are O(#pairs) scalars); the heavy part — per-value histogram
    intersection, min-reduction, and summation over the first-edge domain
    K(1) — runs as vectorised jnp ops over device-resident histograms.
    Counts are float32 on device: exact for integer counts below 2^24, which
    the equivalence tests verify against the float64 host path.
    """

    def __init__(self, cat: Catalog, joins: Sequence[JoinSpec],
                 template: Optional[Sequence[str]] = None,
                 mode: str = "max", cap_with_join_bound: bool = True):
        super().__init__(cat, joins, template=template, mode=mode,
                         cap_with_join_bound=cap_with_join_bound)
        self._dev_hists: Dict[Tuple[str, int, str],
                              Tuple[jnp.ndarray, jnp.ndarray]] = {}

    def _pair_hist_dev(self, plan, i: int, attr: str
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        key = (plan.join.name, i, attr)
        if key not in self._dev_hists:
            vals, counts = self._pair_degree_hist(plan, i, attr)
            self._dev_hists[key] = (jnp.asarray(vals.astype(np.int64)),
                                    jnp.asarray(counts.astype(np.float32)))
        return self._dev_hists[key]

    def estimate(self, delta: Sequence[JoinSpec]) -> float:
        """Upper bound (mode='max') or refined estimate (mode='avg') of |O_Δ|."""
        delta = list(delta)
        if len(delta) == 1:
            return float(self._join_bounds[delta[0].name])
        plans = [self.plans[j.name] for j in delta]
        k = len(self.template) - 1  # number of pairs

        # K(1): per join, the per-value count over the first edge's shared
        # attr (pair0 × pair1 when the edge is real) — each as (values,
        # counts, valid) device triples with masks standing in for the host
        # path's materialised intersections.
        first_attr = self.template[1]
        per_join: List[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = []
        for plan in plans:
            v0, c0 = self._pair_hist_dev(plan, 0, first_attr)
            valid0 = jnp.ones(v0.shape[0], bool)
            if k >= 2:
                p1 = plan.pairs[1]
                if p1.fake_edge_to_prev:
                    # row identity: pairs with A2=v == d(v) rows
                    per_join.append((v0, c0, valid0))
                    continue
                v1, c1 = self._pair_hist_dev(plan, 1, first_attr)
                hit, cc = _lookup_sorted(v1, c1,
                                         jnp.ones(v1.shape[0], bool), v0)
                per_join.append((v0, c0 * cc, hit))
            else:
                per_join.append((v0, c0, valid0))

        # intersect the value domains across joins and take the min count
        base_v, acc, valid = per_join[0]
        for v2, c2, m2 in per_join[1:]:
            hit, cc = _lookup_sorted(v2, c2, m2, base_v)
            valid = valid & hit
            acc = jnp.minimum(acc, jnp.where(hit, cc, jnp.inf))
        k1 = float(jnp.sum(jnp.where(valid, acc, 0.0)))
        if k1 <= 0:
            return 0.0

        # K(i) for the remaining pairs: multiply by min over joins of M_{j,i}
        bound = k1
        for i in range(2, k):
            bound *= min(self._pair_multiplier(plan, i) for plan in plans)
        if self.cap:
            bound = min(bound, min(self._join_bounds[j.name] for j in delta))
        return float(bound)
