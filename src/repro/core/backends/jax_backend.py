"""Device (JAX) backend — the union sampling engine resident on accelerator.

Three layers, bottom-up:

* :class:`DeviceTreeJoin` — generalises the jitted chain sampler to arbitrary
  acyclic (tree) joins **and to cyclic joins via the paper's §8.2
  skeleton+residual scheme**.  Each non-root node keeps its child rows sorted
  by a **composite mixed-radix key** over the node's edge attributes (radices
  are per-attribute domain widths shared across the whole join, so
  parent-side query keys pack identically and probes stay exact), plus
  prefix-summed EW weights; one draw is root inverse-CDF + per-node
  ``searchsorted`` → ranged weighted pick → payload gathers, all ``jax.lax``
  over fixed shapes.  For cyclic joins the EW weights cover the acyclic
  skeleton only; each residual (cycle-closing) edge is then verified inside
  the same traced draw with a batched sorted-key membership probe — uniform
  pick among the ``d`` matches + an accumulated ``Π d/M`` acceptance test —
  mirroring the host :class:`~repro.core.join_sampler.JoinSampler`
  semantics exactly.  On TPU the per-node range probe routes through the
  two-phase Pallas pipeline of :mod:`repro.kernels.searchsorted`
  (``use_pallas``); on CPU it lowers via ``jnp.searchsorted``.
* :class:`DeviceJoinMembership` — batched "is tuple in join J" probes as
  sorted-row-fingerprint lookups resident on device: per base relation, rows
  are indexed by a 32-bit primary fingerprint (sorted) with a 32-bit
  secondary for verification (64 bits total; the host oracle uses 128 — see
  DESIGN.md for the collision budget).  A probe is one ``searchsorted`` per
  relation plus a ``kmax``-wide duplicate window check, AND-reduced.
* :class:`JaxUnionSampler` — runs the *entire multi-round* Algorithm-1 loop
  as one device-resident jitted program: a ``lax.while_loop`` over fused
  rounds (multinomial cover selection, candidate generation for all joins,
  cover-membership acceptance with **retry-within-the-selected-join** — the
  distribution-correct loop, see union_sampler's module docstring on the
  printed-pseudocode pitfall), with the per-piece shortfall vector, FIFO
  ring-buffer surplus banks, dead-piece flags and the stats counters all as
  donated device carry.  ``sample(n)`` crosses the host boundary once;
  ``sample_async(n)`` exposes the dispatch for double-buffered serving.
  ``fused_rounds="host"`` drives the identical round program from a host
  loop (one sync per round) for parity testing.

:class:`JaxBackend` packages the per-join pieces behind the
:class:`~repro.core.backends.base.Backend` protocols so
``SetUnionSampler(backend="jax")`` / ``OnlineUnionSampler(backend="jax")``
select the device engine without touching the algorithm layer.

Limits (all checked at build time with clear errors): ``method="ew"``
weights, non-negative dict-encoded values whose packed edge domains fit in
int32 (the device substrate is 32-bit; see DESIGN.md).  Chain, acyclic, and
cyclic (§8.2 skeleton+residual) join shapes all run on device, as do §8.3
predicates (pushdown provenance becomes build-time validity masks; rejection
predicates lower to in-round acceptance masks via
:func:`repro.core.predicates.compile_preds_jnp`) and ``membership="record"``
(:class:`JaxRecordUnionSampler`).  A union whose *individual* joins trip a
device limit degrades those joins to host candidate draws with a single
warning (and a ``repro_engine_fallback_total`` event) instead of rejecting
the whole union.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ..index import Catalog
from ..join_sampler import EmptyJoinError, JoinSampler
from ..joins import JoinSpec
from ..membership import rows_length
from .. import planner
from ..predicates import selectivity_factor
from .base import Backend, Rows

_I32_LIM = 1 << 31


# ---------------------------------------------------------------------------
# 32-bit row fingerprints — identical arithmetic on host (build) and device
# (probe): murmur3-style finalizer, FNV-style column combine, uint32 wraps.
# ---------------------------------------------------------------------------


def _mix32_consts(salt: int) -> Tuple[int, int, int]:
    return ((0x9E3779B9 * (salt + 1)) & 0xFFFFFFFF, 0x85EBCA6B, 0xC2B2AE35)


def mix32_np(x: np.ndarray, salt: int = 0) -> np.ndarray:
    add, m1, m2 = _mix32_consts(salt)
    z = (np.asarray(x, np.int64) & 0xFFFFFFFF).astype(np.uint32)
    with np.errstate(over="ignore"):
        z = z + np.uint32(add)
        z = (z ^ (z >> np.uint32(16))) * np.uint32(m1)
        z = (z ^ (z >> np.uint32(13))) * np.uint32(m2)
        z = z ^ (z >> np.uint32(16))
    return z


def mix32_jnp(x: jnp.ndarray, salt: int = 0) -> jnp.ndarray:
    add, m1, m2 = _mix32_consts(salt)
    z = x.astype(jnp.uint32)
    z = z + jnp.uint32(add)
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(m1)
    z = (z ^ (z >> jnp.uint32(13))) * jnp.uint32(m2)
    z = z ^ (z >> jnp.uint32(16))
    return z


_FNV32 = 16777619


def fp32_np(cols: Sequence[np.ndarray], salt: int) -> np.ndarray:
    acc = np.zeros(np.asarray(cols[0]).shape[0], dtype=np.uint32)
    with np.errstate(over="ignore"):
        for i, c in enumerate(cols):
            acc = acc * np.uint32(_FNV32) ^ mix32_np(c, salt=salt * 1000 + i)
    return acc


def fp32_jnp(cols: Sequence[jnp.ndarray], salt: int) -> jnp.ndarray:
    acc = jnp.zeros(cols[0].shape[0], dtype=jnp.uint32)
    for i, c in enumerate(cols):
        acc = acc * jnp.uint32(_FNV32) ^ mix32_jnp(c, salt=salt * 1000 + i)
    return acc


# ---------------------------------------------------------------------------
# Composite-key encoding
# ---------------------------------------------------------------------------


def _attr_widths(spec: JoinSpec) -> Dict[str, int]:
    """Per-attribute mixed-radix width over *all* relations of the join.

    Using the join-wide width (not the per-relation one) makes the packing a
    single injective code over the joint domain, so a parent-side query key
    and a child-side index key for the same tuple of values always coincide.
    """
    widths: Dict[str, int] = {}
    for node in spec.nodes:
        for a, c in node.relation.columns.items():
            lo = int(c.min(initial=0))
            if lo < 0:
                raise ValueError(
                    f"jax backend: attribute {a!r} of {node.relation.name!r} "
                    "has negative values; device engine requires non-negative "
                    "dict-encoded columns")
            hi = int(c.max(initial=0))
            widths[a] = max(widths.get(a, 1), hi + 1)
    return widths


def _pack_np(cols: Sequence[np.ndarray], radices: Sequence[int]) -> np.ndarray:
    key = np.zeros(np.asarray(cols[0]).shape[0], dtype=np.int64)
    for c, w in zip(cols, radices):
        key = key * np.int64(w) + np.asarray(c, np.int64)
    return key


def _pack_jnp(rows: Dict[str, jnp.ndarray], attrs: Sequence[str],
              radices: Sequence[int]) -> jnp.ndarray:
    key = jnp.zeros(rows[attrs[0]].shape[0], dtype=jnp.int32)
    for a, w in zip(attrs, radices):
        key = key * jnp.int32(w) + rows[a]
    return key


def _as_i32(col: np.ndarray, what: str) -> np.ndarray:
    col = np.asarray(col, np.int64)
    if col.size and (int(col.min()) < 0 or int(col.max()) >= _I32_LIM):
        lo, hi = int(col.min()), int(col.max())
        raise ValueError(
            f"jax backend: {what} outside the int32 device domain "
            f"(values span [{lo}, {hi}], needing {max(hi, abs(lo)).bit_length()}"
            " bits but the device substrate has 31 usable bits). Re-encode the"
            " dictionary, use backend='numpy', or see the ROADMAP item on"
            " int64/two-limb packed keys for the device-side fix")
    return col.astype(np.int32)


def _inverse_cdf_pick(prefix: jnp.ndarray, lo, hi, u):
    """Weighted pick within [lo, hi) via prefix sums (vectorised)."""
    tot = prefix[hi] - prefix[lo]
    tgt = prefix[lo] + u * jnp.maximum(tot, 1e-30)
    pos = jnp.searchsorted(prefix, tgt, side="right") - 1
    pos = jnp.clip(pos, lo, jnp.maximum(hi - 1, lo))
    return pos, tot > 0


# ---------------------------------------------------------------------------
# Device-resident tree join (generalised EW candidate source)
# ---------------------------------------------------------------------------


def _device_index_cache(cat: Catalog) -> Dict:
    """Catalog-level cache of device-side sorted indexes and column uploads,
    keyed by relation identity.  Pushdown flavours of one base join (the UQ2
    regime: one base chain, several overlapping §8.3 filters) share the base
    relation's sorted keys, permutation and payload buffers instead of
    re-sorting and re-uploading per flavour.  Cache entries keep a strong
    reference to the relation so ``id()`` keys cannot be reused after GC."""
    cache = cat.__dict__.get("_device_index_cache")
    if cache is None:
        cache = cat.__dict__["_device_index_cache"] = {}
    return cache


def _cached_node_index(cache: Dict, rel, edge_attrs: Tuple[str, ...],
                       radices: Tuple[int, ...], use_pallas: bool):
    """Sorted composite-key index over ``rel`` (host perm + device arrays),
    shared across :class:`DeviceTreeJoin` flavours through the catalog cache.
    The caller has already verified the packed domain fits in int32."""
    k = ("idx", id(rel), rel.name, edge_attrs, radices, bool(use_pallas))
    hit = cache.get(k)
    if hit is None:
        key = _pack_np([rel.columns[a] for a in edge_attrs], radices)
        perm = np.argsort(key, kind="stable")
        prepped = None
        if use_pallas:
            from ...kernels.searchsorted import PreparedKeys
            prepped = PreparedKeys(key[perm])
        hit = (rel, perm, jnp.asarray(key[perm].astype(np.int32)),
               jnp.asarray(perm.astype(np.int32)), prepped)
        cache[k] = hit
    return hit[1], hit[2], hit[3], hit[4]


def _cached_col(cache: Dict, rel, attr: str) -> jnp.ndarray:
    """Device upload of one relation column, shared across flavours."""
    k = ("col", id(rel), rel.name, attr)
    hit = cache.get(k)
    if hit is None:
        hit = (rel, jnp.asarray(_as_i32(rel.columns[attr],
                                        f"{rel.name}.{attr}")))
        cache[k] = hit
    return hit[1]


@dataclasses.dataclass(frozen=True)
class _NodeCfg:
    alias: str
    edge_attrs: Tuple[str, ...]
    radices: Tuple[int, ...]
    new_attrs: Tuple[str, ...]
    kind: str = "tree"               # "tree" | "residual" (§8.2 cycle closer)
    max_degree: int = 0              # residual only: M of the d/M acceptance
    uniform: bool = False            # all EW weights equal: pick by floor(u*d)


class DeviceTreeJoin:
    """Join prepared for jitted EW sampling (chain ⊂ tree ⊂ skeleton+residual).

    Acyclic (tree) joins draw with zero rejection.  Cyclic joins follow the
    paper's §8.2 scheme, all inside the same traced draw: the EW weights are
    computed over the acyclic *skeleton* only, each residual (cycle-closing)
    node keeps the identical sorted composite-key index as a tree node, and a
    draw resolves every residual edge with the same batched sorted-key range
    probe — a uniform pick among the ``d`` matches plus an accumulated
    ``Π d/M`` acceptance test (``M`` = the residual index's max degree, as in
    the host :class:`~repro.core.join_sampler.JoinSampler`).  Residual
    rejections surface through the third element of ``draw``'s return.
    """

    def __init__(self, cat: Catalog, spec: JoinSpec,
                 use_pallas: Optional[bool] = None):
        if use_pallas is None:
            from ...kernels.ops import on_tpu
            use_pallas = on_tpu()
        self.use_pallas = bool(use_pallas)
        self.name = spec.name
        self.spec = spec
        self.attrs = tuple(spec.output_attrs)
        if spec.pushdown_base is not None and spec.pushed_preds:
            # §8.3 pushdown provenance: rebuild the filtered join as validity
            # masks over the shared *base* relations (masked EW prefix sums,
            # cache-shared sorted indexes).  A base-only device limit (the
            # unfiltered columns may span a wider packed domain than the
            # filtered ones) falls back to indexing the filtered relations
            # directly — same sampling law, no index sharing.
            try:
                self._build(cat, spec, spec.pushdown_base, spec.pushed_preds)
                return
            except ValueError:
                pass
        self._build(cat, spec, None, ())

    def _build(self, cat: Catalog, spec: JoinSpec, base: Optional[JoinSpec],
               preds: Tuple) -> None:
        """Build the device state.  ``base is None`` indexes ``spec``'s own
        relations (the standard build).  Otherwise ``spec`` must be a
        :func:`repro.core.predicates.pushdown` of ``base``: tree-node indexes
        are built over the base relations (shared across flavours through the
        catalog-level device cache) and the filters are baked in as
        zero-weight rows in the EW prefix sums — masked-out rows are
        unreachable because their prefix region is flat (``searchsorted``
        side='right' never lands inside it).  Residual (§8.2) nodes keep
        per-flavour *filtered* indexes — their match count ``d`` feeds the
        ``Π d/M`` acceptance, so the index must hold surviving rows only —
        and the ``uniform`` floor(u·d) shortcut is disabled under a mask for
        the same reason."""
        js = JoinSampler(cat, spec, method="ew")  # reuse host weight computation
        self.node_cfgs: List[_NodeCfg] = []
        self.sorted_keys: List[jnp.ndarray] = []
        self.perm: List[jnp.ndarray] = []
        self.wprefix: List[jnp.ndarray] = []
        self.cols: List[Dict[str, jnp.ndarray]] = []
        self._prepped: List[object] = []
        masked = base is not None
        if masked:
            from ..predicates import relation_mask
            base_rels = {bn.alias: bn.relation for bn in base.nodes}
            cache = _device_index_cache(cat)
            widths = _attr_widths(base)
        else:
            widths = _attr_widths(spec)

        def _mask_of(alias: str, filtered_nrows: int):
            rel_b = base_rels.get(alias)
            if rel_b is None:
                raise ValueError(
                    f"jax backend: pushdown base of {spec.name!r} has no "
                    f"node {alias!r}")
            m = relation_mask(rel_b, preds)
            if m is None:
                m = np.ones(rel_b.nrows, dtype=bool)
            if int(m.sum()) != filtered_nrows:
                raise ValueError(
                    f"jax backend: pushdown provenance of {spec.name!r} is "
                    f"stale for node {alias!r} (mask keeps {int(m.sum())} "
                    f"rows, the filtered relation has {filtered_nrows})")
            return rel_b, m

        produced = set(js.root_rel.attrs)
        for n in js.order[1:]:
            rel = js._reduced[n.alias]
            radices = tuple(widths[a] for a in n.edge_attrs)
            dom = 1
            for w in radices:
                dom *= w
            if dom >= _I32_LIM:
                raise ValueError(
                    f"jax backend: packed edge-key domain of node {n.alias!r} "
                    f"(relation {rel.name!r}, edge attrs "
                    f"{tuple(n.edge_attrs)!r}) spans {dom} key combinations "
                    f"needing {int(dom).bit_length()} bits, but the device "
                    "key substrate is int32 (31 usable bits). Re-encode the "
                    "dictionary, use backend='numpy', or see the ROADMAP item "
                    "on int64/two-limb packed keys for the device-side fix")
            use_base = masked and n.kind != "residual"
            if use_base:
                rel_b, m = _mask_of(n.alias, rel.nrows)
                perm, skeys_dev, perm_dev, prepped = _cached_node_index(
                    cache, rel_b, tuple(n.edge_attrs), radices,
                    self.use_pallas)
                # scatter the filtered EW weights onto the base rows (the
                # pushdown filter preserves row order) — masked-out rows get
                # weight 0 and are never picked by the inverse-CDF step
                w = np.zeros(rel_b.nrows, dtype=np.float64)
                w[np.nonzero(m)[0]] = js.node_weights[n.alias]
                # the uniform floor(u·d) shortcut picks among *index* rows,
                # so any mask forces the weighted inverse-CDF path
                uniform = (bool(m.all()) and bool(w.size)
                           and float(w.flat[0]) > 0
                           and bool(np.all(w == w.flat[0])))
                if uniform:
                    wp = np.zeros(1, dtype=np.float64)
                else:
                    wp = np.zeros(rel_b.nrows + 1, dtype=np.float64)
                    np.cumsum(w[perm], out=wp[1:])
                col_rel = rel_b
                cols = {a: _cached_col(cache, rel_b, a)
                        for a in rel_b.attrs if a not in produced}
            else:
                key = _pack_np([rel.columns[a] for a in n.edge_attrs],
                               radices)
                perm = np.argsort(key, kind="stable")
                skeys_dev = jnp.asarray(key[perm].astype(np.int32))
                perm_dev = jnp.asarray(perm.astype(np.int32))
                prepped = None
                if self.use_pallas:
                    from ...kernels.searchsorted import PreparedKeys
                    prepped = PreparedKeys(key[perm])
                uniform = False
                if n.kind == "residual":
                    # §8.2: residual picks are uniform among matches via
                    # floor(u*d) in _residual_step — no weight prefix needed;
                    # the EW weights cover the skeleton only (host parity)
                    wp = np.zeros(1, dtype=np.float64)
                else:
                    w = js.node_weights[n.alias]
                    # equal-weight nodes (leaves always; any node whose rows
                    # all continue identically) pick uniformly among the d
                    # matches — same law as the inverse-CDF pick, one
                    # searchsorted cheaper
                    uniform = (bool(w.size) and float(w.flat[0]) > 0
                               and bool(np.all(w == w.flat[0])))
                    if uniform:
                        wp = np.zeros(1, dtype=np.float64)
                    else:
                        wp = np.zeros(rel.nrows + 1, dtype=np.float64)
                        np.cumsum(w[perm], out=wp[1:])
                col_rel = rel
                cols = {a: jnp.asarray(_as_i32(c, f"{rel.name}.{a}"))
                        for a, c in rel.columns.items()
                        if a not in produced}
            new_attrs = tuple(a for a in col_rel.attrs if a not in produced)
            produced.update(col_rel.attrs)
            self.node_cfgs.append(_NodeCfg(
                n.alias, tuple(n.edge_attrs), radices, new_attrs,
                kind=n.kind, max_degree=int(js.edges[n.alias].max_degree),
                uniform=uniform))
            self.sorted_keys.append(skeys_dev)
            self.perm.append(perm_dev)
            self.wprefix.append(jnp.asarray(wp, jnp.float32))
            self.cols.append(cols)
            self._prepped.append(prepped)

        self.has_residual = any(c.kind == "residual" for c in self.node_cfgs)
        if masked:
            rel_b0, m0 = _mask_of(js.order[0].alias, js.root_rel.nrows)
            w0 = np.zeros(rel_b0.nrows, dtype=np.float64)
            w0[np.nonzero(m0)[0]] = np.diff(
                np.asarray(js.root_weight_prefix, np.float64))
            self.host_root_cols = {a: _as_i32(c, f"root.{a}")
                                   for a, c in rel_b0.columns.items()}
            self.root_cols = {a: _cached_col(cache, rel_b0, a)
                              for a in rel_b0.columns}
            wp0 = np.zeros(rel_b0.nrows + 1, dtype=np.float64)
            np.cumsum(w0, out=wp0[1:])
            self.host_root_wprefix = wp0
            self.n_root = rel_b0.nrows
        else:
            self.host_root_cols = {a: _as_i32(c, f"root.{a}")
                                   for a, c in js.root_rel.columns.items()}
            self.root_cols = {a: jnp.asarray(c)
                              for a, c in self.host_root_cols.items()}
            # float64 host prefix retained: the sharding layer cuts
            # weight-quantile root ranges from it
            # (repro.core.sharding.catalog.ShardedTreeJoin)
            self.host_root_wprefix = np.asarray(js.root_weight_prefix,
                                                np.float64)
            self.n_root = js.root_rel.nrows
        self.root_wprefix = jnp.asarray(self.host_root_wprefix, jnp.float32)
        self.total_weight = float(js.root_weight_total)
        self._empty = js.is_empty()
        if obs.enabled():
            levels = obs.get_registry().gauge(
                "repro_engine_probe_levels",
                "fence levels of a node's Pallas range probe (1 or 2)",
                ("join", "node"))
            for cfg, prep in zip(self.node_cfgs, self._prepped):
                if prep is not None:
                    levels.labels(join=self.name, node=cfg.alias).set(
                        prep.levels)

    def is_empty(self) -> bool:
        return self._empty

    # -- device state as a pytree -------------------------------------------
    def device_arrays(self) -> Dict[str, object]:
        """Every device array a draw reads, as one pytree.

        The device loops pass it into their jitted programs as an argument,
        so the catalog is never compiled into a program as constants:
        program size, compile time and the compile-cache key stay
        independent of the data.  Per non-root node, ``probe`` is the sorted
        key column (``jnp.searchsorted``) or the Pallas layout
        (``PreparedKeys.arrays``: top fences, fences, key blocks)."""
        nodes = []
        for i in range(len(self.node_cfgs)):
            prep = self._prepped[i]
            probe = self.sorted_keys[i] if prep is None else prep.arrays()
            nodes.append({"probe": probe, "perm": self.perm[i],
                          "wprefix": self.wprefix[i], "cols": self.cols[i]})
        return {"root_wprefix": self.root_wprefix,
                "root_cols": self.root_cols, "nodes": nodes}

    # -- range probe: jnp.searchsorted, or the Pallas fence search ----------
    # analysis: traced
    def _ranges(self, i: int, probe, q: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if not self.use_pallas:
            return (jnp.searchsorted(probe, q, side="left").astype(jnp.int32),
                    jnp.searchsorted(probe, q, side="right").astype(jnp.int32))
        from ...kernels.ops import default_interpret
        from ...kernels.searchsorted import _searchsorted_i32, to_tiles
        b = q.shape[0]
        # keys are non-negative int32, so the 64-bit split is (hi=0, lo=q^MIN)
        q_lo = to_tiles(q) ^ jnp.int32(-(1 << 31))
        q_hi = jnp.zeros_like(q_lo)
        lo, hi = _searchsorted_i32(q_hi, q_lo, *probe,
                                   interpret=default_interpret())
        n = jnp.int32(self._prepped[i].n)
        return (jnp.minimum(lo.reshape(-1)[:b], n),
                jnp.minimum(hi.reshape(-1)[:b], n))

    # -- one batch of EW tree draws (traced; jit at the call site) ------------
    # analysis: traced
    def draw(self, key: jax.Array, batch: int, arrays=None,
             skeleton: bool = False):
        """``arrays`` is :meth:`device_arrays` (or its traced twin);
        ``None`` reads this tree's own arrays.  ``skeleton``: see
        :meth:`draw_with_root`."""
        if arrays is None:
            arrays = self.device_arrays()
        return self.draw_with_root(key, batch, arrays["root_wprefix"],
                                   arrays["root_cols"], self.n_root,
                                   arrays["nodes"], skeleton=skeleton)

    # analysis: traced
    def _tree_pick(self, i: int, node, rows, ok, u):
        """The range probe and pick of tree node ``i`` for the walks whose
        edge keys ``rows`` holds: returns the picked row and ``ok`` cleared
        where the edge had no match."""
        cfg = self.node_cfgs[i]
        q = _pack_jnp(rows, cfg.edge_attrs, cfg.radices)
        lo, hi = self._ranges(i, node["probe"], q)
        if cfg.uniform:
            d = hi - lo
            off = jnp.floor(u * jnp.maximum(d, 1).astype(jnp.float32)
                            ).astype(jnp.int32)
            pos = lo + jnp.minimum(off, jnp.maximum(d - 1, 0))
            ok = ok & (d > 0)
        else:
            pos, alive = _inverse_cdf_pick(node["wprefix"], lo, hi, u)
            ok = ok & alive & (hi > lo)
        perm = node["perm"]
        return perm[jnp.clip(pos, 0, perm.shape[0] - 1)], ok

    # analysis: traced
    def _residual_pick(self, i: int, cfg: _NodeCfg, node, rows, ok,
                       acc_ratio, u):
        """The probe, pick and d/M factor of :meth:`_residual_step`:
        returns the picked row of node ``i`` instead of its columns."""
        q = _pack_jnp(rows, cfg.edge_attrs, cfg.radices)
        lo, hi = self._ranges(i, node["probe"], q)
        d = hi - lo
        off = jnp.floor(u * jnp.maximum(d, 1).astype(jnp.float32)
                        ).astype(jnp.int32)
        pos = lo + jnp.minimum(off, jnp.maximum(d - 1, 0))
        ok = ok & (d > 0)
        acc_ratio = acc_ratio * (d.astype(jnp.float32)
                                 / jnp.float32(max(cfg.max_degree, 1)))
        perm = node["perm"]
        return perm[jnp.clip(pos, 0, perm.shape[0] - 1)], ok, acc_ratio

    # analysis: traced
    def _residual_step(self, i: int, cfg: _NodeCfg, node, rows, ok,
                       acc_ratio, u):
        """One residual edge: sorted-key probe, uniform pick, d/M factor.
        ``node`` is node ``i`` of :meth:`device_arrays`.  Its ops sit in
        the ``residual/<join>`` phase (``repro.obs.LOOP_PHASES``)."""
        with jax.named_scope(f"residual/{self.name}"):
            child, ok, acc_ratio = self._residual_pick(i, cfg, node, rows,
                                                       ok, acc_ratio, u)
            for a, c in node["cols"].items():
                rows[a] = c[child]
        return rows, ok, acc_ratio

    # analysis: traced
    def draw_with_root(self, key: jax.Array, batch: int,
                       root_wprefix: jnp.ndarray,
                       root_cols: Dict[str, jnp.ndarray], n_root, nodes,
                       skeleton: bool = False):
        """Tree draw with a caller-supplied root slice.

        The sharding layer passes each shard's local root range (weight
        prefix, payload columns, row count); the non-root node indexes are
        this tree's replicated device arrays (``nodes``, the ``"nodes"``
        entry of :meth:`device_arrays`).  ``draw`` is the degenerate whole-root call, so both paths
        share one op sequence (and a 1-shard mesh reproduces unsharded
        draws bit for bit).

        Returns ``(rows, accept, walk_ok)``: ``walk_ok`` marks walks whose
        every edge (tree and residual) had a match; ``accept`` additionally
        applies the §8.2 residual ``Π d/M`` acceptance test, so
        ``walk_ok & ~accept`` are exactly the residual rejections.  On
        acyclic joins the two are the same array.  ``skeleton=True`` adds
        a fourth element, ``skel_ok``: the walks whose tree edges all
        matched, so ``skel_ok & ~accept`` are the walks turned away at a
        residual edge, by a probe with no match (d = 0) or by the test
        (``None`` on acyclic joins, which turn no walk away there).
        """
        keys = jax.random.split(key, len(self.node_cfgs) + 1
                                + (1 if self.has_residual else 0))
        u0 = jax.random.uniform(keys[0], (batch,))
        r_pos, ok = _inverse_cdf_pick(
            root_wprefix, jnp.zeros((batch,), jnp.int32),
            jnp.full((batch,), n_root, jnp.int32), u0)
        rows = {a: c[r_pos] for a, c in root_cols.items()}
        acc_ratio = jnp.ones((batch,), jnp.float32)
        res_ok = jnp.ones((batch,), bool)       # every residual edge matched
        for i, (cfg, node) in enumerate(zip(self.node_cfgs, nodes)):
            u = jax.random.uniform(keys[i + 1], (batch,))
            if cfg.kind == "residual":
                rows, res_ok, acc_ratio = self._residual_step(
                    i, cfg, node, rows, res_ok, acc_ratio, u)
                continue
            child, ok = self._tree_pick(i, node, rows, ok, u)
            for a, c in node["cols"].items():
                rows[a] = c[child]
        if not self.has_residual:
            return (rows, ok, ok, None) if skeleton else (rows, ok, ok)
        u_acc = jax.random.uniform(keys[-1], (batch,))
        with jax.named_scope(f"residual/{self.name}"):
            walk_ok = ok & res_ok
            accept = walk_ok & (u_acc < acc_ratio)
        return ((rows, accept, walk_ok, ok) if skeleton
                else (rows, accept, walk_ok))

    # analysis: traced
    def walk_keys(self, key: jax.Array, batch: int, arrays):
        """The walk of :meth:`draw` without its payload.

        Every edge runs as in :meth:`draw_with_root` (whole root): the same
        key split, uniforms, range probes, picks and ``Π d/M`` test, so a
        walk picks the same rows.  Of the columns it gathers only those a
        later edge key reads; :meth:`gather_rows` fetches the rest for the
        walks a caller keeps.  Returns ``(idx, accept, walk_ok, skel_ok)``
        as :meth:`draw` with ``skeleton=True``, where ``idx`` is the
        ``(batch, nodes + 1)`` int32 matrix of each walk's row of the root
        (column 0) and of every node in :attr:`node_cfgs` order."""
        nodes = arrays["nodes"]
        keyed = {a for cfg in self.node_cfgs for a in cfg.edge_attrs}
        keys = jax.random.split(key, len(self.node_cfgs) + 1
                                + (1 if self.has_residual else 0))
        u0 = jax.random.uniform(keys[0], (batch,))
        r_pos, ok = _inverse_cdf_pick(
            arrays["root_wprefix"], jnp.zeros((batch,), jnp.int32),
            jnp.full((batch,), self.n_root, jnp.int32), u0)
        rows = {a: c[r_pos] for a, c in arrays["root_cols"].items()
                if a in keyed}
        picked = [r_pos]
        acc_ratio = jnp.ones((batch,), jnp.float32)
        res_ok = jnp.ones((batch,), bool)
        for i, (cfg, node) in enumerate(zip(self.node_cfgs, nodes)):
            u = jax.random.uniform(keys[i + 1], (batch,))
            if cfg.kind == "residual":
                with jax.named_scope(f"residual/{self.name}"):
                    child, res_ok, acc_ratio = self._residual_pick(
                        i, cfg, node, rows, res_ok, acc_ratio, u)
            else:
                child, ok = self._tree_pick(i, node, rows, ok, u)
            picked.append(child)
            for a, c in node["cols"].items():
                if a in keyed:
                    rows[a] = c[child]
        idx = jnp.stack([p.astype(jnp.int32) for p in picked], axis=1)
        if not self.has_residual:
            return idx, ok, ok, None
        u_acc = jax.random.uniform(keys[-1], (batch,))
        with jax.named_scope(f"residual/{self.name}"):
            walk_ok = ok & res_ok
            accept = walk_ok & (u_acc < acc_ratio)
        return idx, accept, walk_ok, ok

    # analysis: traced
    def gather_rows(self, idx: jnp.ndarray, arrays) -> Dict[str, jnp.ndarray]:
        """Every output column of the walks whose rows ``idx`` holds (the
        matrix :meth:`walk_keys` returns, or some of its rows)."""
        rows = {a: c[idx[:, 0]] for a, c in arrays["root_cols"].items()}
        for i, node in enumerate(arrays["nodes"]):
            for a, c in node["cols"].items():
                rows[a] = c[idx[:, i + 1]]
        return rows


# ---------------------------------------------------------------------------
# Device-resident membership (sorted-row-fingerprint lookups)
# ---------------------------------------------------------------------------


class DeviceJoinMembership:
    """Batched 'is tuple in join J' probes on device.

    Mirrors the host :class:`~repro.core.membership.MembershipProber`
    semantics: a tuple is in the join iff every base relation contains the
    tuple's projection onto that relation's attributes (the shared output
    schema makes connectivity automatic).
    """

    def __init__(self, spec: JoinSpec):
        self.join_name = spec.name
        # §8.3 rejection predicates: membership in the *filtered* join is the
        # base membership AND the predicate over the tuple's own columns
        # (predicates constrain output attributes, so no relation filtering
        # is needed).  Unlowerable predicates raise ValueError here and the
        # backend degrades probing to the host prober.
        self._pred_fn = None
        if spec.reject_preds:
            from ..predicates import compile_preds_jnp
            self._pred_fn = compile_preds_jnp(spec.reject_preds,
                                              spec.output_attrs)
        # (attrs, sorted_fp1, fp2_in_fp1_order, kmax, nrows) per base relation
        self.rels: List[Tuple[Tuple[str, ...], jnp.ndarray, jnp.ndarray,
                              int, int]] = []
        seen = set()
        for node in spec.nodes:
            rel = node.relation
            attrs = tuple(sorted(rel.attrs))
            # dedup on the host Catalog.rowset cache key, so repeated nodes
            # over one relation build one index but distinct relations that
            # merely share a name are still probed (host parity)
            if (rel.name, attrs) in seen:
                continue
            seen.add((rel.name, attrs))
            for a in attrs:
                _as_i32(rel.columns[a], f"{rel.name}.{a}")  # domain check
            fp1 = fp32_np([rel.columns[a] for a in attrs], salt=1)
            fp2 = fp32_np([rel.columns[a] for a in attrs], salt=2)
            order = np.argsort(fp1, kind="stable")
            s1 = fp1[order]
            if s1.shape[0]:
                _, counts = np.unique(s1, return_counts=True)
                kmax = int(counts.max())
            else:
                kmax = 0
            self.rels.append((attrs, jnp.asarray(s1), jnp.asarray(fp2[order]),
                              kmax, int(rel.nrows)))

    def device_arrays(self) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
        """``(sorted fp1, fp2)`` per base relation: the probe's device state
        as a pytree, passed into the device loops as an argument (see
        :meth:`DeviceTreeJoin.device_arrays`)."""
        return [(s1, s2) for _attrs, s1, s2, _kmax, _n in self.rels]

    # analysis: traced
    def contains(self, rows: Dict[str, jnp.ndarray],
                 arrays=None) -> jnp.ndarray:
        """Traced probe: rows are device int32 columns of the output schema.
        ``arrays`` is :meth:`device_arrays` or its traced twin (``None``
        reads this index's own arrays)."""
        if arrays is None:
            arrays = self.device_arrays()
        b = rows[next(iter(rows))].shape[0]
        res = (jnp.ones((b,), bool) if self._pred_fn is None
               else self._pred_fn(rows))
        for (attrs, _s1, _s2, kmax, n), (s1, s2) in zip(self.rels, arrays):
            if n == 0:
                return jnp.zeros((b,), bool)
            q1 = fp32_jnp([rows[a] for a in attrs], salt=1)
            q2 = fp32_jnp([rows[a] for a in attrs], salt=2)
            lo = jnp.searchsorted(s1, q1, side="left")
            m = jnp.zeros((b,), bool)
            for k in range(kmax):  # duplicate window (kmax is tiny, static)
                pos = jnp.minimum(lo + k, n - 1)
                m = m | ((lo + k < n) & (s1[pos] == q1) & (s2[pos] == q2))
            res = res & m
        return res


# ---------------------------------------------------------------------------
# Backend protocol implementations
# ---------------------------------------------------------------------------


class JaxCandidateSource:
    """CandidateSource over a :class:`DeviceTreeJoin`.

    Carries its own PRNG key; the host ``rng`` argument of ``draw`` is
    ignored (documented deviation — the numpy and jax engines are
    distributionally, not bitwise, equivalent).
    """

    def __init__(self, tree: DeviceTreeJoin, seed: int = 0,
                 device_batch: int = 4096):
        self.join_name = tree.name
        self.tree = tree
        self.attrs = tree.attrs
        self.key = jax.random.PRNGKey(seed)
        self._batch = int(device_batch)
        self._draw_jit = jax.jit(functools.partial(tree.draw,
                                                   batch=self._batch))
        # buffer of accepted-but-unserved rows: device rounds are fixed-width,
        # so small draws (OnlineUnionSampler asks for 1 at a time) are served
        # from the remainder of the last round instead of a fresh round each.
        self._buf: Optional[Rows] = None
        self._buf_pos = 0
        self._res_rej = 0
        # double-buffered dispatch: the next device round is launched before
        # the current one's rows are compacted on the host, so device compute
        # hides behind the host-side top-up work (serving path)
        self._inflight = None

    def is_empty(self) -> bool:
        return self.tree.is_empty()

    def pop_residual_rejects(self) -> int:
        """Residual (§8.2 cyclic) rejections since the last pop."""
        n, self._res_rej = self._res_rej, 0
        return n

    def _dispatch(self):
        """Launch one device round without blocking (JAX async dispatch)."""
        self.key, sub = jax.random.split(self.key)
        return self._draw_jit(sub)

    def _refill(self) -> int:
        """Drain the in-flight device round into the buffer and immediately
        dispatch the next one, so round *k+1* computes on device while the
        host compacts round *k*'s rows.  Returns rows banked."""
        pending = self._inflight if self._inflight is not None \
            else self._dispatch()
        self._inflight = self._dispatch()
        rows, ok, walk_ok = pending
        ok = np.asarray(ok)
        if self.tree.has_residual:
            self._res_rej += int(np.asarray(walk_ok).sum() - ok.sum())
        idx = np.nonzero(ok)[0]
        # copy=False: the gather already materialises int64-compatible rows,
        # so a matching dtype round-trips without a second allocation
        self._buf = {a: np.asarray(rows[a])[idx].astype(np.int64, copy=False)
                     for a in self.attrs}
        self._buf_pos = 0
        return int(idx.shape[0])

    def draw(self, rng: np.random.Generator, count: int,
             batch: Optional[int] = None) -> Tuple[Rows, int]:
        if self.is_empty():
            raise EmptyJoinError(f"join {self.join_name!r} is empty")
        # fast path: the buffer already covers the request — serve one
        # zero-copy slice without re-entering the refill machinery at all
        if (self._buf is not None
                and self._buf_pos + count <= rows_length(self._buf)):
            lo, hi = self._buf_pos, self._buf_pos + count
            self._buf_pos = hi
            return {a: c[lo:hi] for a, c in self._buf.items()}, 0
        got: List[Rows] = []
        draws = 0
        have = 0
        # round budget scales with the request (device rounds are fixed-width;
        # the numpy source instead grows its batch with `count`)
        max_rounds = 1000 + 20 * (count // self._batch + 1)
        for _ in range(max_rounds):
            if self._buf is None or self._buf_pos >= rows_length(self._buf):
                draws += self._batch
                if self._refill() == 0:
                    continue
            lo = self._buf_pos
            hi = min(lo + count - have, rows_length(self._buf))
            got.append({a: c[lo:hi] for a, c in self._buf.items()})
            self._buf_pos = hi
            have += hi - lo
            if have >= count:
                break
        else:
            raise RuntimeError(f"JaxCandidateSource({self.join_name}): "
                               "round budget exhausted")
        if len(got) == 1:
            return got[0], draws
        return ({a: np.concatenate([g[a] for g in got])
                 for a in self.attrs}, draws)


class JaxMembershipOracle:
    """MembershipOracle facade over per-join device membership indexes.

    Host-facing: accepts numpy rows, pads to power-of-two buckets (bounding
    the number of jit retraces), probes on device, returns numpy booleans.
    """

    def __init__(self, members: Dict[str, DeviceJoinMembership],
                 output_attrs: Sequence[str]):
        self.members = members
        self.output_attrs = list(output_attrs)
        self._fns = {name: jax.jit(m.contains) for name, m in members.items()}

    @staticmethod
    def _bucket(n: int) -> int:
        b = 256
        while b < n:
            b <<= 1
        return b

    def contains(self, join_name: str, rows: Rows) -> np.ndarray:
        n = rows_length(rows)
        if n == 0:
            return np.zeros(0, dtype=bool)
        p = self._bucket(n)
        dev = {a: jnp.asarray(np.pad(_as_i32(np.asarray(rows[a])[:n],
                                             f"probe.{a}"), (0, p - n)))
               for a in self.output_attrs}
        out = self._fns[join_name](dev)
        return np.asarray(out)[:n]

    def membership_matrix(self, rows: Rows,
                          join_names: Optional[Sequence[str]] = None
                          ) -> np.ndarray:
        names = list(join_names) if join_names is not None else list(self.members)
        return np.stack([self.contains(nm, rows) for nm in names], axis=1)


class JaxBackend(Backend):
    """Device-resident engine: tree candidate sources + membership indexes."""

    name = "jax"

    def __init__(self, cat: Catalog, joins: Sequence[JoinSpec],
                 join_method: str = "ew", seed: int = 0,
                 device_batch: int = 4096,
                 use_pallas: Optional[bool] = None):
        if join_method != "ew":
            obs.record_fallback("join_method", detail=join_method)
            raise ValueError("jax backend: only method='ew' runs on device "
                             "(eo/wj walks stay on the numpy backend)")
        self.cat = cat
        self.joins = list(joins)
        schemas = {tuple(sorted(j.output_attrs)) for j in self.joins}
        if len(schemas) > 1:
            raise ValueError(
                f"joins must share an output schema; got {sorted(schemas)}")
        self.attrs = list(self.joins[0].output_attrs)
        # per-join degrade: a join that trips a device limit (packed edge-key
        # domain over int32, negative dict values) falls back to the host
        # candidate source instead of failing the whole union; fused rounds
        # need every piece on device, so they disable when any join degrades
        self.trees: Dict[str, DeviceTreeJoin] = {}
        self.degraded: Dict[str, str] = {}          # join name -> reason
        for j in self.joins:
            try:
                self.trees[j.name] = DeviceTreeJoin(cat, j,
                                                    use_pallas=use_pallas)
            except ValueError as e:
                self.degraded[j.name] = str(e)
                obs.record_fallback("int32_domain", detail=str(e),
                                    join=j.name)
        if self.degraded:
            import warnings
            warnings.warn(
                "jax backend: joins "
                f"{sorted(self.degraded)} fall back to host candidate draws "
                f"({'; '.join(sorted(set(self.degraded.values())))}); fused "
                "device rounds are disabled for this union", stacklevel=2)
        self._sources: Dict[str, object] = {}
        for i, j in enumerate(self.joins):
            if j.name in self.trees:
                self._sources[j.name] = JaxCandidateSource(
                    self.trees[j.name], seed=seed + i,
                    device_batch=device_batch)
            else:
                from .numpy_backend import NumpyCandidateSource
                self._sources[j.name] = NumpyCandidateSource(
                    cat, j, method=join_method)
        # replicated membership indexes are built lazily: the mesh-sharded
        # engine (repro.core.sharding) keeps its own hash-partitioned
        # indexes and must not pay for (or hold) the full replicated ones
        self._members: Optional[Dict[str, DeviceJoinMembership]] = None
        self._oracle = None

    @property
    def members(self) -> Dict[str, DeviceJoinMembership]:
        if self._members is None:
            self._members = {j.name: DeviceJoinMembership(j)
                             for j in self.joins}
        return self._members

    def source(self, join_name: str):
        return self._sources[join_name]

    def oracle(self):
        if self._oracle is None:
            try:
                self._oracle = JaxMembershipOracle(self.members, self.attrs)
            except ValueError as e:
                # same degrade rule as the draw side: out-of-domain values
                # keep membership on the (128-bit, exact) host prober
                import warnings
                warnings.warn(
                    f"jax backend: device membership unavailable ({e}); "
                    "probing through the host oracle", stacklevel=2)
                obs.record_fallback("host_oracle", detail=str(e))
                from ..membership import MembershipProber
                self._oracle = MembershipProber(self.cat, self.joins)
        return self._oracle

    def supports_fused_rounds(self) -> bool:
        return not self.degraded


# ---------------------------------------------------------------------------
# Fused Algorithm-1 rounds — one-round program + the persistent device loop
# ---------------------------------------------------------------------------


# SamplerStats fields the fused engines accumulate as one device vector
# (fetched once per sample() call in device mode)
_STAT_FIELDS = ("iterations", "candidate_draws", "cover_rejects",
                "residual_rejects", "pred_rejects", "dropped_slots")

# Per-piece round counters carried as one (nj, 6) int32 matrix in the
# persistent loop (device mode) / accumulated by the numpy twin (host mode)
# and surfaced at the same single host sync as the scalar stats vector.
# Columns: candidate draws, cover-accepted rows, §8.2 residual rejections
# (the Π d/M test), §8.2 residual kills (skeleton walks turned away at a
# residual edge: a probe with no match, or the Π d/M test; 0 on acyclic
# pieces), residual survivors dropped because the round's survivor width
# was full (0 where the piece is not narrowed), rows drained from the
# surplus bank, and the post-round bank-occupancy high-water mark (max over
# the call; folded with max across calls).  Every column but the last sums.
PIECE_STAT_FIELDS = ("draws", "accepts", "residual_rejects",
                     "residual_kills", "residual_overflow", "bank_drained",
                     "bank_hwm")


def _cover_cum(probs_base: jnp.ndarray, dead: jnp.ndarray):
    """Dead-masked, renormalised selection CDF + unreachable flag.

    Shared by the host-driven round wrapper and the device loop body so the
    float32 arithmetic (and hence every categorical pick) is identical on
    both paths — the parity tests pin them bit for bit."""
    p = jnp.where(dead, jnp.float32(0), probs_base)
    s = jnp.sum(p)
    return jnp.cumsum(p) / jnp.maximum(s, jnp.float32(1e-30)), s <= 0


def _piece_batches(probs, round_batch: int, balance: str,
                   slack: float) -> Tuple[int, ...]:
    """Static per-join candidate widths for one round.

    ``balance="cover"`` sizes each join's draw batch proportionally to its
    cover selection probability (head-room ``slack``, floor 256, rounded to
    multiples of 128 to bound shape variety) instead of drawing
    ``round_batch`` candidates from *every* join — most of a round's compute
    is the per-join draws, and a piece with 5 % selection mass can never
    emit more than ~5 % of the round's slots.  Undershoot is harmless: the
    shortfall carry tops the piece up next round.  ``balance="full"`` keeps
    the uniform-width behaviour."""
    nj = len(probs)
    if balance != "cover":
        return (int(round_batch),) * nj
    p = np.maximum(np.asarray(probs, np.float64), 0)
    s = p.sum()
    if s <= 0:
        return (int(round_batch),) * nj
    out = []
    for j in range(nj):
        want = int(np.ceil(slack * (p[j] / s) * round_batch))
        b = max(256, ((want + 127) // 128) * 128)
        out.append(min(int(round_batch), b))
    return tuple(out)


def survival_share(tree: DeviceTreeJoin, join_size) -> Optional[float]:
    """The share of a cyclic piece's skeleton walks that its residual edges
    accept: each join tuple is reached with probability 1 / (W · Π M),
    W the skeleton's EW total and M each residual edge's max degree, so
    the share is |J| / (W · Π M).  ``join_size`` is the cover's size of
    the join, which counts only the tuples its §8.3 rejection predicates
    keep; those predicates run after the survivors are cut, so the share is
    taken over the unfiltered join, the size over
    :func:`~repro.core.predicates.selectivity_factor`.  ``None`` on an
    acyclic piece or where the sizes are unknown."""
    sel = selectivity_factor(tree.spec)
    if (not tree.has_residual or not join_size or tree.total_weight <= 0
            or sel <= 0):
        return None
    m = 1
    for cfg in tree.node_cfgs:
        if cfg.kind == "residual":
            m *= max(cfg.max_degree, 1)
    return float(join_size) / sel / (tree.total_weight * m)


def survivor_width(batch: int, share: Optional[float]) -> int:
    """Static width of the residual survivors a cyclic piece keeps from one
    round's ``batch`` walks, each surviving with probability ``share``: the
    mean plus 8 binomial sigmas, rounded up to 128 lanes, at most ``batch``
    (no narrowing where the share is unknown or at least one half)."""
    if share is None or not 0 <= share < 0.5:
        return int(batch)
    mean = batch * share
    want = math.ceil(mean + 8.0 * math.sqrt(mean * (1.0 - share)))
    return min(int(batch), max(128, -(-want // 128) * 128))


def _emit_and_bank(out, pos, bank, head, count,
                   cols, dt, ft, acc, cap: int, C: int, W: int,
                   bank_base=None, fresh_base=None):
    """Scatter one round's emission into the output buffer + roll the banks.

    Row layout: all attributes plus the home piece id travel as one
    ``(rows, A+1)`` int32 matrix, so every emission/banking step is a
    single scatter (or gather) op instead of one per attribute —
    XLA:CPU scatter has high per-op cost.  ``out`` is ``(C, A+1)``,
    ``bank`` is ``(nj, cap, A+1)``, ``cols[j]`` is the piece's
    accepted-compacted ``(B_j, A+1)`` matrix.

    Emission order (mirrored exactly by the host loop): pieces in cover
    order; per piece the ``dt`` banked rows (FIFO, oldest first) then the
    ``ft`` freshly accepted rows.  Surplus accepted rows are pushed at the
    ring tail — which the take leaves in place (``tail = head + count``
    before both operations).  All scatters use ``mode="drop"`` with an
    out-of-range destination (``C`` / ``cap``) as the mask.

    ``bank_base``/``fresh_base`` override the per-piece output offsets of
    the banked/fresh rows — the sharded loop passes globally computed
    offsets so each shard scatters its rows straight to their final global
    positions (the default packs this shard's take contiguously at ``pos``).
    """
    nj = dt.shape[0]
    take = dt + ft
    if bank_base is None:
        base = pos + jnp.cumsum(take) - take        # exclusive prefix
        bank_base = base
        fresh_base = base + dt
    # banked rows: one (nj, W, A+1) ring gather + one masked scatter
    r = jnp.arange(W, dtype=jnp.int32)
    bmask = r[None, :] < dt[:, None]
    bidx = (head[:, None] + r[None, :]) % cap
    bdst = jnp.where(bmask, bank_base[:, None] + r[None, :], C).reshape(-1)
    jrow = jnp.arange(nj, dtype=jnp.int32)[:, None]
    bvals = bank[jrow, bidx]                        # (nj, W, A+1)
    out = out.at[bdst].set(bvals.reshape(nj * W, -1), mode="drop")
    # fresh rows + surplus push, per piece (static per-join widths)
    push = jnp.minimum(acc - ft, cap - (count - dt))
    for j in range(nj):
        cj = cols[j]
        bj = cj.shape[0]
        rj = jnp.arange(bj, dtype=jnp.int32)
        fdst = jnp.where(rj < ft[j], fresh_base[j] + rj, C)
        pidx = jnp.where((rj >= ft[j]) & (rj < ft[j] + push[j]),
                         (head[j] + count[j] + rj - ft[j]) % cap, cap)
        out = out.at[fdst].set(cj, mode="drop")
        bank = bank.at[j, pidx].set(cj, mode="drop")
    head = (head + dt) % cap
    count = count - dt + push
    return out, pos + jnp.sum(take), bank, head, count


class _ReadyHandle:
    """Degenerate async handle: the sample already exists."""

    def __init__(self, ss):
        self._ss = ss

    def result(self):
        return self._ss


class _PendingSample:
    """In-flight device-loop sample.

    The whole multi-round loop is already dispatched (JAX async dispatch);
    ``result()`` performs the single device→host fetch, folds the stats
    vector, applies the host-drawn output shuffle and builds the SampleSet.
    The serving path dispatches call *k+1* before draining call *k*.

    The drain has two parts, timed apart: the wait until the loop's scalar
    outputs are on the host (``repro_engine_drain_wait_seconds``: the block
    on the device), then the host's own assembly — fetch, widening, shuffle,
    column split and ``fingerprint128`` (``repro_engine_assemble_seconds``).
    The two add up to the call's ``repro_engine_drain_seconds``.  ``call``
    is the engine's dispatch number, the ``call`` argument of its spans.
    """

    def __init__(self, sampler, n, out, total, rounds, fail,
                 stats_vec, piece_vec, shuffle, call: int):
        self._sampler = sampler
        self.call = int(call)
        self._n = int(n)
        self._out = out
        self._total = total
        self._rounds = rounds
        self._fail = fail
        self._stats_vec = stats_vec
        self._piece_vec = piece_vec
        self._shuffle = shuffle
        self._done = None

    def result(self):
        if self._done is not None:
            return self._done
        with obs.span("repro/engine/drain", call=self.call):
            self._done = self._drain()
        return self._done

    def _drain(self):
        s = self._sampler
        timed = obs.enabled()
        t0 = time.perf_counter() if timed else 0.0
        with obs.span("repro/engine/drain_wait", call=self.call):
            if bool(np.asarray(self._fail)):
                raise RuntimeError("all cover pieces unreachable")
            total = int(np.asarray(self._total))
            s.last_rounds = int(np.asarray(self._rounds))
            vec = np.asarray(self._stats_vec)
        t1 = time.perf_counter() if timed else 0.0
        with obs.span("repro/engine/assemble", call=self.call):
            if total < self._n:
                raise RuntimeError("JaxUnionSampler: top-up budget exhausted")
            for f, v in zip(_STAT_FIELDS, vec):
                setattr(s.stats, f, getattr(s.stats, f) + int(v))
            ema = None
            if (s.plan == "adaptive" and timed
                    and s._dev_state is not None):
                # snapshot the latest carried EMAs (tiny fetch; result()
                # already syncs) for the repro_engine_piece_ema gauges
                ema = np.asarray(s._dev_state["ema"])
            s._fold_piece_stats(np.asarray(self._piece_vec),
                                rounds=s.last_rounds, samples=self._n,
                                ema=ema)
            mat = s._merge_out(self._out)[:self._n].astype(np.int64)[
                self._shuffle]
            rows = {a: np.ascontiguousarray(mat[:, i])
                    for i, a in enumerate(s.attrs)}
            home = np.ascontiguousarray(mat[:, -1])
            from ..relation import fingerprint128
            from ..union_sampler import SampleSet
            with obs.span("repro/engine/fingerprint", call=self.call):
                fp = fingerprint128([rows[a] for a in sorted(s.attrs)])
            done = SampleSet(list(s.attrs), rows, home, fp, s.stats)
        if timed:
            t2 = time.perf_counter()
            h = s._obs_handles()
            h["drain_wait"].observe(t1 - t0)
            h["assemble"].observe(t2 - t1)
            h["drain"].observe(t2 - t0)
        return done


class JaxUnionSampler:
    """The multi-round Algorithm-1 loop as a single device-resident program.

    Per round (fixed shapes; ``piece_batches[j]`` candidates for join j):

    1. **multinomial cover selection** — per-slot categorical on the piece
       probabilities, histogrammed into per-piece targets (an i.i.d.
       factorisation of the host path's multinomial) and added to the
       shortfall carried from earlier rounds,
    2. **candidate generation for all joins** — one batched EW tree draw per
       join; cyclic pieces verify their residual edges inside the same
       program (sorted-key probes + ``Π d/M`` acceptance, §8.2), so a
       residual rejection simply leaves the slot unaccepted and its target
       flows into the per-piece shortfall carry like any other rejection —
       round shapes stay static and no piece is ever re-selected.  A cyclic
       piece whose residual turns most walks away walks keys and row
       indices only, keeps the first ``survivor_widths[j]`` walks its
       residual accepted, and gathers their payload alone for steps 3-4,
    3. **cover-membership acceptance** — a candidate of piece ``j`` survives
       iff no earlier cover piece contains it (batched device probes),
    4. **compaction** — accepted candidates ranked to the front per join
       (a cumsum scatter, not a sort); the round serves each per-piece
       target first from that piece's FIFO surplus bank, then from the
       fresh accepts, and pushes leftover accepts back into the bank.

    Crucially the shortfall of piece ``j`` stays *assigned to piece j* across
    rounds (it is carried, never re-drawn from the selection distribution):
    re-selecting a piece after a rejection is the printed-pseudocode pitfall
    documented in union_sampler.  Since each round's accepted candidates are
    i.i.d. uniform over their piece, serving a target from the bank (a
    deterministic FIFO over an i.i.d. stream) is unbiased — this is what
    makes the engine a streaming source for serving.

    ``fused_rounds="device"`` (default) runs the *whole* loop — shortfall
    vector, ring-buffer banks, dead-piece detection, output compaction and
    the SamplerStats counters — inside one ``lax.while_loop`` program with
    donated carry buffers, so ``sample(n)`` crosses the device boundary
    once.  ``fused_rounds="host"`` drives the identical round program from a
    host loop with numpy twin banks (one sync per round) — kept for parity
    testing and debugging; the two modes produce bit-identical samples and
    stats from the same seed.
    """

    # whether the round narrows a cyclic piece to its residual survivors
    # (``_round_core_impl``); engines with rounds of their own draw full rows
    _narrows_residual = True

    def __init__(self, backend: JaxBackend, cover, seed: int = 0,
                 round_batch: int = 4096,
                 dead_rounds: int = 8, max_rounds: int = 4096,
                 surplus_cap: Optional[int] = None, stats=None,
                 fused_rounds: str = "device", balance: str = "cover",
                 balance_slack: float = 1.5, predicate=None,
                 plan: str = "static"):
        self.backend = backend
        self.cover = cover
        self.order = list(cover.order)
        self.trees = [backend.trees[n] for n in self.order]
        self.attrs = tuple(backend.attrs)
        # §8.3 predicate lowering, two flavours per cover piece (None = none):
        #  * _pred_fns[j]   — the piece's own acceptance mask: its
        #    reject_preds AND the union-wide predicate, fused between the
        #    candidate draw and the earlier-piece probes;
        #  * _cont_pred_fns[j] — the piece's reject_preds only, ANDed into
        #    *containment* checks against piece j by engines that probe raw
        #    relation fingerprints (the sharded exchange; the replicated
        #    DeviceJoinMembership carries its own equivalent mask).  The
        #    union-wide predicate is excluded: candidates already passed it,
        #    so it cannot separate a tuple from an earlier filtered piece.
        self.predicate = predicate
        from ..predicates import compile_preds_jnp
        gp = tuple(predicate.preds) if predicate is not None else ()
        self._pred_fns = []
        self._cont_pred_fns = []
        for name in self.order:
            spec = backend.trees[name].spec
            own = tuple(spec.reject_preds) + gp
            self._pred_fns.append(
                compile_preds_jnp(own, spec.output_attrs) if own else None)
            self._cont_pred_fns.append(
                compile_preds_jnp(spec.reject_preds, spec.output_attrs)
                if spec.reject_preds else None)
        self.key = jax.random.PRNGKey(seed)
        self.host_rng = np.random.default_rng(seed)
        self.round_batch = int(round_batch)
        self.dead_rounds = int(dead_rounds)
        self.max_rounds = int(max_rounds)
        self.surplus_cap = max(1, 8 * self.round_batch if surplus_cap is None
                               else int(surplus_cap))
        if fused_rounds not in ("device", "host"):
            raise ValueError("fused_rounds must be 'device' or 'host', got "
                             f"{fused_rounds!r}")
        self.fused_rounds = fused_rounds
        if stats is None:
            from ..union_sampler import SamplerStats
            stats = SamplerStats()
        self.stats = stats
        base = np.maximum(np.asarray(cover.selection_probs(), np.float64), 0)
        s = base.sum()
        self._probs_base = jnp.asarray(base / s if s > 0 else base,
                                       jnp.float32)
        self.piece_batches = _piece_batches(base, self.round_batch,
                                            balance, balance_slack)
        # per-piece bank drain cap per round (a semantics constant — the
        # host twin uses the same cap, keeping dt = min(need, count, W)
        # identical).  It bounds the ring gather/scatter width inside the
        # device loop, where XLA:CPU per-op scatter cost dominates; banks
        # stay shallow under cover-balanced batches, so a narrow window
        # drains them just as fast while the wide one mostly moves padding.
        self._drain_w = min(self.round_batch, 256)
        # adaptive round planner (plan="adaptive"): per-piece acceptance
        # EMAs carried on device budget the candidate draws each round and
        # the draw widths shrink to the demand-matched schedule below;
        # plan="static" traces exactly the pre-planner program and stays
        # the bitwise parity oracle.
        if plan not in ("static", "adaptive"):
            raise ValueError(f"plan must be 'static' or 'adaptive', got "
                             f"{plan!r}")
        self.plan = plan
        if plan == "adaptive":
            # masked draw slots still cost full compute under XLA's static
            # shapes, so the planner re-sizes the *widths* themselves:
            # piece j draws ~ slot * p_j / seeded-acceptance candidates,
            # where the slot array is expanded to amortize the fixed
            # per-round dispatch cost (planner.SLOT_EXPANSION)
            self.piece_batches = planner.alloc_batches(
                self.piece_batches, base,
                planner.seed_rates(cover, self._tree_specs())[:, 0],
                planner.adaptive_slot(self.round_batch))
        self._setup_planner()
        self.last_rounds = 0
        # per-piece telemetry (PIECE_STAT_FIELDS columns): counters sum
        # across sample() calls, the bank high-water column folds with max.
        # Filled once per call at the single host sync in both loop modes.
        self.piece_stats = np.zeros((len(self.order),
                                     len(PIECE_STAT_FIELDS)), np.int64)
        self._obs_metrics = None
        self._round_jit = jax.jit(self._round_impl)
        # persistent device-loop state (fused_rounds="device"): PRNG key,
        # shortfall vector, ring banks and dead-piece flags all live on
        # device and carry across sample() calls.  The compile cache is
        # keyed by (capacity class, plan, mode) — not kwargs identity — so
        # flipping `plan` post-build retraces instead of silently reusing
        # the other plan's program, and each class compiles exactly once
        # (audited by repro.analysis.recompile).
        self._loop_cache: Dict[Tuple[int, str, str], object] = {}
        # one entry appended per *trace* of the loop body (Python executes
        # the body only while tracing); the recompile audit reads this
        self._trace_events: List[Tuple[str, int, str]] = []
        self._dev_state = None
        self._calls = 0             # device-loop dispatches (span `call`)
        self._phases_published: set = set()    # loops with published phases
        # host-loop twin state (fused_rounds="host"): numpy ring banks with
        # identical FIFO semantics; allocated on first host sample
        nj = len(self.order)
        self._h_dead = np.zeros(nj, dtype=bool)
        self._h_streak = np.zeros(nj, dtype=np.int64)
        self._h_bank = None
        self._h_head = np.zeros(nj, dtype=np.int64)
        self._h_count = np.zeros(nj, dtype=np.int64)

    def _tree_specs(self) -> Dict[str, object]:
        return {n: self.backend.trees[n].spec
                for n in self.order if n in self.backend.trees}

    def _setup_planner(self) -> None:
        """Derive planner constants and the survivor widths from the
        (possibly overridden) ``piece_batches``.  Called again by the
        sharded engine after it rescales the per-piece widths to ``world``
        shards."""
        # per piece, the static width its residual survivors are narrowed
        # to before the payload gathers, membership probes and compaction
        # (the piece's draw width where that would not narrow, and in the
        # engines whose rounds draw full rows)
        self.survivor_widths = tuple(
            survivor_width(b, survival_share(t, self.cover.join_sizes.get(n)))
            if self._narrows_residual else b
            for b, t, n in zip(self.piece_batches, self.trees, self.order))
        if self.plan == "adaptive":
            # expanded selection slots amortize the fixed per-round cost;
            # the demand-matched widths above size the supply to fill them
            self._slot_width = planner.adaptive_slot(self.round_batch)
        else:
            self._slot_width = self.round_batch
        self._ema_shifts = planner.ema_shifts(self.piece_batches)
        self._ema_seed = planner.seed_rates(self.cover, self._tree_specs())
        self._h_ema = None          # host-twin EMA state (lazy copy of seed)
        self._pbatch_i32 = np.asarray(self.piece_batches, np.int32)
        try:
            self._plan_cache_key = planner.plan_key(
                self.backend.cat, self.backend.joins, self.cover)
        except Exception:
            self._plan_cache_key = None

    # -- the fused round program ----------------------------------------------
    def _ensure_device_inputs(self) -> None:
        """Materialise the replicated membership indexes *outside* any trace
        (their device buffers are stored on the index objects; building them
        lazily inside a jit/while_loop trace would store tracers instead).
        The sharded engine keeps its own hash-partitioned indexes and
        overrides this to a no-op."""
        _ = self.backend.members

    def _catalog_args(self) -> Dict[str, list]:
        """The catalog's device arrays (trees + membership indexes, cover
        order) as the pytree argument of the round programs."""
        return {"trees": [t.device_arrays() for t in self.trees],
                "members": [self.backend.members[n].device_arrays()
                            for n in self.order]}

    def _round_core(self, key: jax.Array, probs_cum: jnp.ndarray,
                    carry_need: jnp.ndarray, extra_target: jnp.ndarray,
                    cat: Dict[str, list],
                    ema: Optional[jnp.ndarray] = None,
                    bank_count: Optional[jnp.ndarray] = None):
        """One Algorithm-1 round (traceable; shared by the host-driven
        wrapper and the device loop body).  ``cat`` is
        :meth:`_catalog_args` as traced by the caller.  Returns per join the
        accepted-compacted candidate columns (``survivor_widths[j]`` rows
        where the piece is narrowed, else ``piece_batches[j]``) plus (ok,
        residual, accepted, predicate-reject, residual-kill,
        residual-overflow) counts and the per-piece need = carry + this
        round's targets.  Under ``plan="adaptive"`` the acceptance EMAs and
        current bank occupancy come in too and the per-piece candidate
        budget goes out as a ninth element."""
        with jax.named_scope("algo1_fused_round"):
            return self._round_core_impl(key, probs_cum, carry_need,
                                         extra_target, cat, ema, bank_count)

    def _round_core_impl(self, key: jax.Array, probs_cum: jnp.ndarray,
                         carry_need: jnp.ndarray, extra_target: jnp.ndarray,
                         cat: Dict[str, list],
                         ema: Optional[jnp.ndarray] = None,
                         bank_count: Optional[jnp.ndarray] = None):
        nj = len(self.trees)
        adaptive = self.plan == "adaptive"
        # resolved at trace time (first round): keeps the lazy backend
        # membership unbuilt for subclasses that override the round program
        members = [self.backend.members[n] for n in self.order]
        # Each step sits in a named scope of its phase (repro.obs.LOOP_PHASES;
        # per-piece phases carry the join name), so a profile splits the
        # round's device time by phase.  Scopes are op metadata only: the
        # compiled program and the stream are the same without them.
        with jax.named_scope("select"):
            kpick, *jks = jax.random.split(key, nj + 1)
            # (1) multinomial cover selection: categorical picks → histogram
            u = jax.random.uniform(kpick, (self._slot_width,))
            pick = jnp.clip(jnp.searchsorted(probs_cum, u, side="right"
                                             ).astype(jnp.int32), 0, nj - 1)
            valid = (jnp.arange(self._slot_width)
                     < extra_target).astype(jnp.int32)
            need = carry_need + jnp.zeros((nj,), jnp.int32).at[pick].add(
                valid)
            budget = None
            if adaptive:
                # integer candidate budget from counts only (owed work minus
                # usable bank coverage over the accept EMA) —
                # planner.budget_for is the same fixed-point arithmetic the
                # numpy twin runs, so host/device budgets are bit-identical
                # from identical carries
                budget = planner.budget_for(
                    need, bank_count, ema[:, 0],
                    jnp.asarray(self._pbatch_i32), self._drain_w, jnp)
        # (2)+(3) per join: batched candidate draw (incl. §8.2 residual-edge
        # verification for cyclic pieces) + fused §8.3 predicate acceptance
        # + earlier-piece rejection
        cols, okc, resc, accc, predc, killc, ovfc = [], [], [], [], [], [], []
        for j, tree in enumerate(self.trees):
            bj, sj = self.piece_batches[j], self.survivor_widths[j]
            narrow = sj < bj
            name = self.order[j]
            with jax.named_scope(f"walk/{name}"):
                if narrow:
                    idx, acc, walk_ok, skel_ok = tree.walk_keys(
                        jks[j], bj, cat["trees"][j])
                else:
                    rows, acc, walk_ok, skel_ok = tree.draw(
                        jks[j], bj, cat["trees"][j], skeleton=True)
            with jax.named_scope(f"filter/{name}"):
                if budget is not None:
                    # budget mask: the first budget[j] slots of an i.i.d.
                    # candidate stream — a count-derived prefix, so the
                    # surviving candidates stay i.i.d. uniform
                    elig = jnp.arange(bj) < budget[j]
                    acc = acc & elig
                    walk_ok = walk_ok & elig
                    if skel_ok is not None:
                        skel_ok = skel_ok & elig
                resc.append(jnp.sum(walk_ok) - jnp.sum(acc))
                killc.append(jnp.int32(0) if skel_ok is None
                             else jnp.sum(skel_ok) - jnp.sum(acc))
            if narrow:
                # residual first, payload later: the first sj walks the
                # residual accepted, in slot order, go on; the rest are
                # spent draws.  The cut depends on counts alone, so the
                # kept walks stay i.i.d. uniform over the piece.
                with jax.named_scope(f"compact/{name}"):
                    kept = jnp.sum(acc)
                    ovfc.append(jnp.maximum(kept - sj, 0))
                    dst = jnp.where(acc, jnp.cumsum(acc) - 1, sj)
                    idx = (jnp.zeros((sj, idx.shape[1]), jnp.int32)
                           .at[dst].set(idx, mode="drop"))
                    acc = jnp.arange(sj) < kept
                with jax.named_scope(f"walk/{name}"):
                    rows = tree.gather_rows(idx, cat["trees"][j])
                bj = sj
            else:
                ovfc.append(jnp.int32(0))
            with jax.named_scope(f"filter/{name}"):
                pf = self._pred_fns[j]
                if pf is None:
                    predc.append(jnp.int32(0))
                else:
                    pok = pf(rows)
                    predc.append(jnp.sum(acc & ~pok).astype(jnp.int32))
                    acc = acc & pok
            with jax.named_scope(f"member/{name}"):
                for q in range(j):         # pieces earlier in cover order
                    acc = acc & ~members[q].contains(rows, cat["members"][q])
            # (4) compaction: accepted rows to the front in slot order — a
            # rank scatter (cumsum - 1) on the (B_j, A+1) row matrix (last
            # column = home piece id, so it rides every later scatter for
            # free): one scatter per piece, cheaper than the per-attr argsort
            with jax.named_scope(f"compact/{name}"):
                dst = jnp.where(acc, jnp.cumsum(acc) - 1, bj)
                mat = jnp.stack([rows[a].astype(jnp.int32)
                                 for a in self.attrs]
                                + [jnp.full(bj, j, jnp.int32)], axis=1)
                cols.append(jnp.zeros((bj, mat.shape[1]), jnp.int32)
                            .at[dst].set(mat, mode="drop"))
                okc.append(jnp.sum(walk_ok))
                accc.append(jnp.sum(acc))
        with jax.named_scope("carry"):
            out = (cols, jnp.stack(okc).astype(jnp.int32),
                   jnp.stack(resc).astype(jnp.int32),
                   jnp.stack(accc).astype(jnp.int32),
                   jnp.stack(predc).astype(jnp.int32),
                   jnp.stack(killc).astype(jnp.int32),
                   jnp.stack(ovfc).astype(jnp.int32), need)
            if adaptive:
                out = out + (budget.astype(jnp.int32),)
        return out

    def _round_impl(self, probs_base: jnp.ndarray, dead: jnp.ndarray,
                    carry_need: jnp.ndarray, extra_target: jnp.ndarray,
                    key: jax.Array, ema: Optional[jnp.ndarray] = None,
                    bank_count: Optional[jnp.ndarray] = None, *, cat):
        """Host-driven entry point: one jitted round (fused_rounds="host")."""
        probs_cum, bad = _cover_cum(probs_base, dead)
        res = self._round_core(key, probs_cum, carry_need, extra_target,
                               cat, ema, bank_count)
        return res + (bad,)

    # -- the persistent device loop -------------------------------------------
    def _init_state(self):
        """Fresh device carry: key + shortfall + ring banks + dead flags
        (+ the planner's acceptance EMAs under ``plan="adaptive"``)."""
        nj, cap = len(self.order), self.surplus_cap
        st = {
            "key": self.key,
            "owed": jnp.zeros(nj, jnp.int32),
            "dead": jnp.zeros(nj, dtype=bool),
            "streak": jnp.zeros(nj, jnp.int32),
            "bank": jnp.zeros((nj, cap, len(self.attrs) + 1), jnp.int32),
            "bank_head": jnp.zeros(nj, jnp.int32),
            "bank_count": jnp.zeros(nj, jnp.int32),
        }
        if self.plan == "adaptive":
            st["ema"] = jnp.asarray(self._ema_seed)
        return st

    def _build_loop(self, C: int):
        """Compile the whole multi-round loop for output capacity ``C``.

        The carry (state + output buffers) is donated, so repeated calls
        reuse the same device allocations; everything the host needs back —
        samples, home pieces, total, round count and the stats vector —
        comes out of the single program invocation.  The catalog comes in
        as the last argument (:meth:`_catalog_args`), never as constants."""
        cap = self.surplus_cap
        W = min(self._drain_w, cap)
        bt = int(sum(self.piece_batches))
        adaptive = self.plan == "adaptive"
        max_rounds = jnp.int32(self.max_rounds)
        dead_rounds = jnp.int32(self.dead_rounds)

        pbatch = jnp.asarray(self.piece_batches, jnp.int32)
        shifts = jnp.asarray(self._ema_shifts)

        def loop_fn(state, out, n, probs_base, cat):
            self._trace_events.append(("loop", C, self.plan))

            def cond(c):
                total, rounds, fail = c[2], c[3], c[4]
                return (total < n) & (rounds < max_rounds) & ~fail

            def body(c):
                state, out, total, rounds, fail, stats, pstats = c
                with jax.named_scope("select"):
                    probs_cum, bad = _cover_cum(probs_base, state["dead"])
                    key, kround = jax.random.split(state["key"])
                    extra = jnp.clip(n - total - jnp.sum(state["owed"]),
                                     0, self._slot_width)
                if adaptive:
                    (cols, okc, resc, accc, predc, killc, ovfc, need,
                     budget) = self._round_core(
                        kround, probs_cum, state["owed"], extra, cat,
                        state["ema"], state["bank_count"])
                else:
                    budget = None
                    cols, okc, resc, accc, predc, killc, ovfc, need = \
                        self._round_core(kround, probs_cum, state["owed"],
                                         extra, cat)
                # bank take (FIFO, capped) → fresh take → carried shortfall
                with jax.named_scope("emit"):
                    dt = jnp.minimum(jnp.minimum(need, state["bank_count"]),
                                     self._drain_w)
                    ft = jnp.minimum(need - dt, accc)
                    out2, total2, bank2, head2, count2 = _emit_and_bank(
                        out, total, state["bank"],
                        state["bank_head"], state["bank_count"],
                        cols, dt, ft, accc, cap, C, W)
                with jax.named_scope("carry"):
                    shortfall = need - dt - ft
                    # dead-piece bookkeeping (same rules as the host twin):
                    # stray picks on dead pieces are dropped; a live piece
                    # that keeps a target but yields nothing for dead_rounds
                    # rounds is empty in reality (estimation noise) — drop it
                    dropped = jnp.sum(jnp.where(state["dead"], shortfall, 0))
                    shortfall = jnp.where(state["dead"], 0, shortfall)
                    # (a piece whose survivors overflowed is not empty)
                    trig = ((shortfall > 0) & (accc == 0) & (count2 == 0)
                            & (ovfc == 0))
                    streak = jnp.where(
                        state["dead"], state["streak"],
                        jnp.where(trig, state["streak"] + 1, 0))
                    newly = ~state["dead"] & (streak >= dead_rounds)
                    dropped = dropped + jnp.sum(
                        jnp.where(newly, shortfall, 0))
                    shortfall = jnp.where(newly, 0, shortfall)
                    # adaptive rounds draw only the budgeted slots; static
                    # rounds spend the full static width every round
                    drawn = (jnp.sum(budget) if adaptive
                             else jnp.int32(bt))
                    stats2 = stats + jnp.stack(
                        [drawn.astype(jnp.int32), drawn.astype(jnp.int32),
                         (jnp.sum(okc) - jnp.sum(resc) - jnp.sum(predc)
                          - jnp.sum(accc) - jnp.sum(ovfc)).astype(jnp.int32),
                         jnp.sum(resc).astype(jnp.int32),
                         jnp.sum(predc).astype(jnp.int32),
                         dropped.astype(jnp.int32)])
                    # per-piece telemetry rides the same carry
                    # (PIECE_STAT_FIELDS columns); pure extra outputs —
                    # nothing feeds back into the sampling arithmetic, so
                    # the emitted stream is unchanged
                    pstats2 = jnp.stack(
                        [pstats[:, 0] + (budget if adaptive else pbatch),
                         pstats[:, 1] + accc,
                         pstats[:, 2] + resc,
                         pstats[:, 3] + killc,
                         pstats[:, 4] + ovfc,
                         pstats[:, 5] + dt.astype(jnp.int32),
                         jnp.maximum(pstats[:, 6],
                                     count2.astype(jnp.int32))],
                        axis=1)
                    state2 = {"key": key,
                              "owed": shortfall.astype(jnp.int32),
                              "dead": state["dead"] | newly,
                              "streak": streak.astype(jnp.int32),
                              "bank": bank2,
                              "bank_head": head2.astype(jnp.int32),
                              "bank_count": count2.astype(jnp.int32)}
                    if adaptive:
                        # one EMA step from this round's counts (accept /
                        # walk_ok / residual / pred per budgeted slot)
                        counts = jnp.stack([accc, okc, resc, predc], axis=1)
                        state2["ema"] = planner.ema_update(
                            state["ema"], budget, counts, shifts, jnp)
                    # `bad` (unreachable cover) is terminal: the loop exits
                    # on `fail` and the host raises, discarding the buffers
                    # — no need to gate the state updates (which would
                    # force a full copy of the banks + output every round)
                    rounds2, fail2 = rounds + 1, fail | bad
                return (state2, out2, total2, rounds2, fail2, stats2, pstats2)

            init = (state, out, jnp.int32(0), jnp.int32(0),
                    jnp.bool_(False), jnp.zeros(len(_STAT_FIELDS),
                                                jnp.int32),
                    jnp.zeros((len(self.order), len(PIECE_STAT_FIELDS)),
                              jnp.int32))
            return jax.lax.while_loop(cond, body, init)

        return jax.jit(loop_fn, donate_argnums=(0, 1))

    def _loop_for(self, C: int):
        lk = (C, self.plan, self.fused_rounds)
        fn = self._loop_cache.get(lk)
        if fn is None:
            fn = self._build_loop(C)
            self._loop_cache[lk] = fn
        return fn

    def sample_async(self, n: int):
        """Dispatch a full ``sample(n)`` without blocking; returns a handle
        whose ``result()`` fetches the answer.  Device mode dispatches the
        persistent loop (JAX async dispatch) so the serving path can launch
        call *k+1* before draining call *k*; host mode computes eagerly and
        returns a ready handle."""
        from ..union_sampler import empty_sample_set
        if n <= 0:
            return _ReadyHandle(empty_sample_set(list(self.attrs),
                                                 self.stats))
        if self.fused_rounds == "host":
            return _ReadyHandle(self._sample_host(n))
        t0 = time.perf_counter() if obs.enabled() else 0.0
        self._ensure_device_inputs()
        C = 1 << max(10, (int(n) - 1).bit_length())
        if self._dev_state is None:
            self._dev_state = self._init_state()
        call, self._calls = self._calls, self._calls + 1
        fn = self._loop_for(C)
        args = (self._dev_state, self._out_buffer(C), jnp.int32(n),
                self._probs_base, self._catalog_args())
        # under REPRO_OBS_TRACE, the first call of a capacity class also
        # publishes which phase each op of the compiled loop belongs to
        # (the profiler's trace names the ops but carries no name stack);
        # the shapes are taken now, as the call donates the carry.  The
        # sharded loop is a plain function over shard_map and publishes none
        publish = (obs.trace_annotations_enabled()
                   and fn not in self._phases_published
                   and hasattr(fn, "lower"))
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                              args) if publish else None
        with obs.span("repro/sample_dispatch", call=call):
            st, out, total, rounds, fail, stats, pstats = fn(*args)
        self._dev_state = st
        # the output shuffle is host randomness, drawn at dispatch time so
        # both modes consume host_rng identically (one permutation per call)
        shuffle = self.host_rng.permutation(n)
        if obs.enabled():
            self._obs_handles()["dispatch"].observe(time.perf_counter() - t0)
        if publish:
            # lowering and compiling again hit the caches the call filled
            obs.publish_op_phases(fn.lower(*shapes).compile().as_text())
            self._phases_published.add(fn)
        return _PendingSample(self, n, out, total, rounds, fail, stats,
                              pstats, shuffle, call)

    def _out_buffer(self, C: int):
        """Fresh output buffer for one device-loop call (donated away)."""
        return jnp.zeros((C, len(self.attrs) + 1), jnp.int32)

    def _merge_out(self, out) -> np.ndarray:
        """Collapse a fetched output buffer to one ``(C, A+1)`` matrix
        (the sharded loop returns one disjointly-filled buffer per shard)."""
        return np.asarray(out)

    def sample(self, n: int):
        if self.fused_rounds == "host":
            return self._sample_host(n)
        t0 = time.perf_counter()
        ss = self.sample_async(n).result()
        if self._plan_cache_key is not None and n > 0:
            # feed the host-side cost model (t_round = c0 + c1*slots); the
            # fastest warm call per round_batch displaces the compile call
            planner.PLAN_CACHE.observe(
                self._plan_cache_key, self.round_batch,
                int(sum(self.piece_batches)), self.last_rounds,
                time.perf_counter() - t0, n)
        return ss

    # -- telemetry surfacing (repro.obs) --------------------------------------
    def piece_stats_dict(self) -> Dict[str, Dict[str, int]]:
        """Cumulative per-piece round counters keyed by join name
        (PIECE_STAT_FIELDS columns; ``bank_hwm`` is a high-water mark)."""
        return {name: {f: int(self.piece_stats[j, i])
                       for i, f in enumerate(PIECE_STAT_FIELDS)}
                for j, name in enumerate(self.order)}

    def _obs_handles(self):
        """Lazily bound metric children (one registry lookup per engine)."""
        if self._obs_metrics is None:
            reg = obs.get_registry()
            per_piece = [
                reg.counter("repro_engine_piece_draws_total",
                            "candidate draws per cover piece", ("join",)),
                reg.counter("repro_engine_piece_accepts_total",
                            "cover-accepted candidates per piece", ("join",)),
                reg.counter("repro_engine_piece_residual_rejects_total",
                            "§8.2 residual rejections per piece", ("join",)),
                reg.counter("repro_engine_piece_residual_kills_total",
                            "skeleton walks turned away at a §8.2 residual "
                            "edge (no match, or the d/M test) per piece",
                            ("join",)),
                reg.counter("repro_engine_piece_residual_overflow_total",
                            "residual survivors dropped as spent draws "
                            "because the round's survivor width was full",
                            ("join",)),
                reg.counter("repro_engine_piece_bank_drained_total",
                            "rows served from the surplus bank", ("join",)),
            ]
            self._obs_metrics = {
                "piece": [[c.labels(join=n) for c in per_piece]
                          for n in self.order],
                "hwm": reg.gauge("repro_engine_piece_bank_hwm",
                                 "surplus-bank occupancy high-water mark",
                                 ("join",)),
                "width": reg.gauge(
                    "repro_engine_piece_survivor_width",
                    "rows a piece keeps per round for the payload gathers, "
                    "membership probes and compaction",
                    ("join",)),
                "ema": reg.gauge(
                    "repro_engine_piece_ema",
                    "adaptive-planner acceptance EMA (fraction of budget)",
                    ("join", "component")),
                "rounds": reg.counter("repro_engine_rounds_total",
                                      "fused Algorithm-1 rounds run"),
                "samples": reg.counter("repro_engine_samples_total",
                                       "samples emitted by the fused loop"),
                "dispatch": reg.histogram(
                    "repro_engine_dispatch_seconds",
                    "host wall-clock of sample(n) loop dispatch"),
                "drain": reg.histogram(
                    "repro_engine_drain_seconds",
                    "host wall-clock of result fetch + assembly"),
                "drain_wait": reg.histogram(
                    "repro_engine_drain_wait_seconds",
                    "part of the drain blocked until the loop's scalar "
                    "outputs are on the host"),
                "assemble": reg.histogram(
                    "repro_engine_assemble_seconds",
                    "part of the drain in host assembly: fetch, widen, "
                    "shuffle, column split, fingerprint128"),
            }
        return self._obs_metrics

    def _fold_piece_stats(self, p: np.ndarray, rounds: int = 0,
                          samples: int = 0,
                          ema: Optional[np.ndarray] = None) -> None:
        """Fold one call's per-piece counter matrix into the cumulative
        engine state (+ registry publication unless REPRO_OBS=off)."""
        p = np.asarray(p, np.int64)
        self.piece_stats[:, :-1] += p[:, :-1]
        self.piece_stats[:, -1] = np.maximum(self.piece_stats[:, -1],
                                             p[:, -1])
        self.stats.samples_emitted += int(samples)
        if not obs.enabled():
            return
        h = self._obs_handles()
        for j, name in enumerate(self.order):
            children = h["piece"][j]
            for i, child in enumerate(children):
                v = int(p[j, i])
                if v:
                    child.inc(v)
            h["hwm"].labels(join=name).set(int(self.piece_stats[j, -1]))
            h["width"].labels(join=name).set(self.survivor_widths[j])
            if ema is not None:
                for i, comp in enumerate(planner.EMA_COMPONENTS):
                    h["ema"].labels(join=name, component=comp).set(
                        float(ema[j, i]) / planner.EMA_ONE)
        if rounds:
            h["rounds"].inc(int(rounds))
        if samples:
            h["samples"].inc(int(samples))

    # -- host twin loop (fused_rounds="host") ---------------------------------
    def _sample_host(self, n: int):
        """Host-driven round loop with numpy twin banks.

        Same round program, same PRNG discipline, same banking semantics as
        the device loop — one device sync per round instead of one per call.
        Kept for parity testing (the device loop is pinned bit-equal to
        this) and as the debugging fallback."""
        from ..union_sampler import SampleSet, empty_sample_set
        if n <= 0:
            return empty_sample_set(list(self.attrs), self.stats)
        self._ensure_device_inputs()
        nj, cap = len(self.order), self.surplus_cap
        if self._h_bank is None:
            self._h_bank = np.zeros((nj, cap, len(self.attrs) + 1),
                                    np.int32)
        bank, head, count = self._h_bank, self._h_head, self._h_count
        dead, streak = self._h_dead, self._h_streak
        bt = int(sum(self.piece_batches))
        adaptive = self.plan == "adaptive"
        if adaptive and self._h_ema is None:
            self._h_ema = self._ema_seed.copy()
        pbatch = np.asarray(self.piece_batches, np.int64)
        # numpy twin of the device loop's per-piece telemetry carry
        pstats = np.zeros((nj, len(PIECE_STAT_FIELDS)), np.int64)
        parts: List[np.ndarray] = []      # (k, A+1) rows + home matrices
        owed = np.zeros(nj, dtype=np.int64)   # per-piece carried shortfall
        total = 0
        rounds = 0
        while total < n:
            rounds += 1
            if rounds > self.max_rounds:
                raise RuntimeError("JaxUnionSampler: top-up budget exhausted")
            extra = max(0, min(n - total - int(owed.sum()),
                               self._slot_width))
            self.key, sub = jax.random.split(self.key)
            if adaptive:
                (cols, okc, resc, accc, predc, killc, ovfc, need, budget,
                 bad) = self._round_jit(
                        self._probs_base, jnp.asarray(dead),
                        jnp.asarray(owed.astype(np.int32)),
                        jnp.int32(extra), sub, jnp.asarray(self._h_ema),
                        jnp.asarray(count.astype(np.int32)),
                        cat=self._catalog_args())
                budget = np.asarray(budget)
            else:
                budget = None
                (cols, okc, resc, accc, predc, killc, ovfc, need,
                 bad) = self._round_jit(
                    self._probs_base, jnp.asarray(dead),
                    jnp.asarray(owed.astype(np.int32)), jnp.int32(extra),
                    sub, cat=self._catalog_args())
            if bool(np.asarray(bad)):
                raise RuntimeError("all cover pieces unreachable")
            okc = np.asarray(okc).astype(np.int64)
            resc = np.asarray(resc).astype(np.int64)
            accc = np.asarray(accc).astype(np.int64)
            predc = np.asarray(predc).astype(np.int64)
            killc = np.asarray(killc).astype(np.int64)
            ovfc = np.asarray(ovfc).astype(np.int64)
            need = np.asarray(need).astype(np.int64)
            drawn = bt if budget is None else int(budget.sum())
            self.stats.iterations += drawn
            self.stats.candidate_draws += drawn
            # residual (§8.2), predicate (§8.3) and membership rejections are
            # accounted separately (dead walks are none of the three; the
            # per-piece residual_kills column counts those that die at a
            # residual edge, residual_overflow the survivors past the
            # survivor width)
            self.stats.residual_rejects += int(resc.sum())
            self.stats.pred_rejects += int(predc.sum())
            self.stats.cover_rejects += int(okc.sum() - resc.sum()
                                            - predc.sum() - accc.sum()
                                            - ovfc.sum())
            dt = np.minimum(np.minimum(need, count), self._drain_w)
            ft = np.minimum(need - dt, accc)
            for j in range(nj):
                if dt[j]:
                    idx = (head[j] + np.arange(dt[j])) % cap
                    parts.append(bank[j, idx])
                cj = None
                if ft[j]:
                    cj = np.asarray(cols[j])
                    parts.append(cj[:ft[j]])
                # push surplus accepts at the ring tail (invariant under
                # the take: tail = head + count before both operations)
                push = int(min(accc[j] - ft[j], cap - (count[j] - dt[j])))
                if push > 0:
                    if cj is None:
                        cj = np.asarray(cols[j])
                    pidx = (head[j] + count[j] + np.arange(push)) % cap
                    bank[j, pidx] = cj[ft[j]:ft[j] + push]
                head[j] = (head[j] + dt[j]) % cap
                count[j] = count[j] - dt[j] + push
            total += int((dt + ft).sum())
            # identical accumulation rules to the device carry (post-round
            # bank occupancy for the high-water column)
            pstats[:, 0] += pbatch if budget is None else budget.astype(
                np.int64)
            pstats[:, 1] += accc
            pstats[:, 2] += resc
            pstats[:, 3] += killc
            pstats[:, 4] += ovfc
            pstats[:, 5] += dt
            pstats[:, 6] = np.maximum(pstats[:, 6], count)
            if adaptive:
                # numpy EMA step — planner.ema_update with xp=np runs the
                # same int32 adds/shifts/divides as the device carry
                counts4 = np.stack([accc, okc, resc, predc],
                                   axis=1).astype(np.int32)
                self._h_ema = planner.ema_update(
                    self._h_ema, budget.astype(np.int32), counts4,
                    self._ema_shifts, np)
            shortfall = need - dt - ft
            # dead-piece bookkeeping — identical rules to the device loop
            self.stats.dropped_slots += int(shortfall[dead].sum())
            shortfall[dead] = 0
            trig = ((shortfall > 0) & (accc == 0) & (count == 0)
                    & (ovfc == 0))
            streak[:] = np.where(dead, streak,
                                 np.where(trig, streak + 1, 0))
            newly = ~dead & (streak >= self.dead_rounds)
            self.stats.dropped_slots += int(shortfall[newly].sum())
            shortfall[newly] = 0
            dead |= newly
            owed = shortfall
        self.last_rounds = rounds
        self._fold_piece_stats(pstats, rounds=rounds, samples=n,
                               ema=self._h_ema if adaptive else None)
        mat = np.concatenate(parts)[:n].astype(np.int64)
        shuffle = self.host_rng.permutation(n)
        mat = mat[shuffle]
        rows = {a: np.ascontiguousarray(mat[:, i])
                for i, a in enumerate(self.attrs)}
        home = np.ascontiguousarray(mat[:, -1])
        from ..relation import fingerprint128
        fp = fingerprint128([rows[a] for a in sorted(self.attrs)])
        return SampleSet(list(self.attrs), rows, home, fp, self.stats)


# ---------------------------------------------------------------------------
# Record-mode membership on device (the lazy orig_join record, Alg 1 l.8-12)
# ---------------------------------------------------------------------------


class JaxRecordUnionSampler(JaxUnionSampler):
    """Algorithm 1 with ``membership="record"`` and the ``orig_join`` record
    as a device-resident sorted-fingerprint multiset.

    The record is four aligned device arrays of capacity ``R``: sorted
    64-bit row fingerprints (two uint32 halves, the same
    :func:`fp32_np`/:func:`fp32_jnp` arithmetic as
    :class:`DeviceJoinMembership` — see DESIGN.md for the collision budget;
    empty slots hold the all-ones sentinel pair and sort last), the tuple's
    current **home** piece, and the count of output rows currently credited
    to the entry.  One round is one jitted program (host-driven: the lazy
    record semantics need the emitted stream back each round, so there is
    exactly one device sync per round) that processes the cover pieces in
    ascending order against the live record:

    * draw ``piece_batches[j]`` candidates (tree walk + §8.2 residual + the
      fused §8.3 predicate mask),
    * probe the record (``searchsorted`` + a static duplicate window): a
      candidate is **rejected** when its record home is an earlier piece
      (Alg 1 line 8), **revises** when its home is a later piece (lines
      10-12: the old entry's credited rows are debited and its home moves
      to ``j``), and is accepted otherwise,
    * take the first ``need_j`` accepted candidates in slot order (the
      remaining accepts are discarded — a truncation of an i.i.d. stream,
      so the emitted prefix stays i.i.d. uniform; there is no surplus
      banking because banked rows could be invalidated by later revisions),
    * fold the taken rows into the record: revision flags scatter onto hit
      entries (credit zeroed, home lowered to ``j``), missed fingerprints
      are deduplicated with run-length credit counts and merged by one
      sorted concatenation.  Pieces later in the same round see the updated
      record, so within-round semantics match the sequential host dict
      exactly (processing pieces in ascending order means within-round hits
      on entries created earlier in the round are always earlier-piece
      rejections, never revisions).

    Revision cannot rewrite rows already handed out, so emission is settled
    at the end: every emitted row is kept iff its emit-time home equals its
    **final** record home (revised copies are exactly the rows whose home
    moved after they were emitted), and the per-round valid total — taken
    rows minus revision-debited credits — tells the driver when ``n`` valid
    rows exist.  The first ``n`` valid rows in emission order, shuffled,
    are the sample.

    The engine is host-driven either way, so ``fused_rounds`` only selects
    where the round program's carry lives (it is donated device state in
    both modes); the equivalence test replays ``debug_capture=True`` round
    captures through a sequential host dict instead.  The sharded engine
    does not support record mode (the multiset is device-global).
    """

    _KWIN = 8          # static fp1 duplicate window (cf. DeviceJoinMembership)
    _SENTINEL = 0xFFFFFFFF
    _narrows_residual = False          # _record_round draws full rows

    def __init__(self, backend: JaxBackend, cover, seed: int = 0,
                 round_batch: int = 4096,
                 dead_rounds: int = 8, max_rounds: int = 4096,
                 surplus_cap: Optional[int] = None, stats=None,
                 fused_rounds: str = "device", balance: str = "cover",
                 balance_slack: float = 1.5, predicate=None,
                 record_capacity: Optional[int] = None,
                 debug_capture: bool = False, plan: str = "static"):
        # record mode is take-in-slot-order with in-round record revision;
        # budget masking would interleave with the lazy-record semantics, so
        # the adaptive planner is not offered here
        if plan != "static":
            raise ValueError(
                "membership='record' supports plan='static' only")
        super().__init__(backend, cover, seed=seed, round_batch=round_batch,
                         dead_rounds=dead_rounds, max_rounds=max_rounds,
                         surplus_cap=surplus_cap, stats=stats,
                         fused_rounds=fused_rounds, balance=balance,
                         balance_slack=balance_slack, predicate=predicate)
        self._sorted_attrs = tuple(sorted(self.attrs))
        self.record_capacity = record_capacity
        self.debug_capture = bool(debug_capture)
        self.captured: List[Dict] = []
        self._rec_state = None
        self._rec_jit = jax.jit(self._record_round, donate_argnums=(0,))

    def _ensure_device_inputs(self) -> None:
        """No-op: record mode never probes the replicated membership
        indexes, so the backend's lazy build must not be triggered."""

    # -- record state ---------------------------------------------------------
    def _init_record_state(self, n: int):
        if self.record_capacity is not None:
            r = int(self.record_capacity)
        else:
            r = 1 << max(12, (4 * int(n) - 1).bit_length())
        self.R = r
        return {
            "f1": jnp.full((r,), self._SENTINEL, jnp.uint32),
            "f2": jnp.full((r,), self._SENTINEL, jnp.uint32),
            "home": jnp.full((r,), 0x7FFFFFFF, jnp.int32),
            "emit": jnp.zeros((r,), jnp.int32),
            "count": jnp.int32(0),
            "fail": jnp.bool_(False),
        }

    # -- one round (traced) ---------------------------------------------------
    def _record_round(self, state, need: jnp.ndarray, key: jax.Array):
        nj = len(self.trees)
        R = self.R
        keys = jax.random.split(key, nj)
        cols_out, debug = [], []
        ft_l, okc_l, resc_l, predc_l, rejc_l = [], [], [], [], []
        accc_l, revc_l, inval_l, killc_l = [], [], [], []
        for j, tree in enumerate(self.trees):
            bj = self.piece_batches[j]
            rows, acc, walk_ok, skel_ok = tree.draw(keys[j], bj,
                                                    skeleton=True)
            killc_l.append(jnp.int32(0) if skel_ok is None
                           else (jnp.sum(skel_ok) - jnp.sum(acc))
                           .astype(jnp.int32))
            okc_l.append(jnp.sum(walk_ok).astype(jnp.int32))
            resc_l.append((jnp.sum(walk_ok) - jnp.sum(acc))
                          .astype(jnp.int32))
            pf = self._pred_fns[j]
            if pf is None:
                predc_l.append(jnp.int32(0))
            else:
                pok = pf(rows)
                predc_l.append(jnp.sum(acc & ~pok).astype(jnp.int32))
                acc = acc & pok
            if self.debug_capture:
                debug.append((dict(rows), acc))
            f1 = fp32_jnp([rows[a] for a in self._sorted_attrs], salt=1)
            f2 = fp32_jnp([rows[a] for a in self._sorted_attrs], salt=2)
            # record lookup against the start-of-piece state
            lo = jnp.searchsorted(state["f1"], f1, side="left")
            hit = jnp.zeros((bj,), bool)
            epos = jnp.zeros((bj,), jnp.int32)
            for k in range(self._KWIN):
                pos = jnp.minimum(lo + k, R - 1).astype(jnp.int32)
                m = ((lo + k < R) & (state["f1"][pos] == f1)
                     & (state["f2"][pos] == f2))
                epos = jnp.where(m & ~hit, pos, epos)
                hit = hit | m
            home = state["home"][epos]
            rejc_l.append(jnp.sum(acc & hit & (home < j))
                          .astype(jnp.int32))
            accepted = acc & (~hit | (home >= j))
            accc_l.append(jnp.sum(accepted).astype(jnp.int32))
            rank = jnp.cumsum(accepted) - 1
            taken = accepted & (rank < need[j])
            ft_l.append(jnp.minimum(jnp.sum(accepted), need[j])
                        .astype(jnp.int32))
            # emit: taken rows compacted to the front (rank scatter)
            dst = jnp.where(taken, jnp.cumsum(taken) - 1, bj)
            mat = jnp.stack([rows[a].astype(jnp.int32)
                             for a in self.attrs], axis=1)
            cols_out.append(jnp.zeros((bj, mat.shape[1]), jnp.int32)
                            .at[dst].set(mat, mode="drop"))
            # revisions: taken hits whose entry currently lives at a LATER
            # piece — debit the entry's credited rows, move it home to j
            th = taken & hit
            rev = th & (home > j)
            rev_flag = (jnp.zeros((R,), bool)
                        .at[jnp.where(rev, epos, R)].set(True, mode="drop"))
            revc_l.append(jnp.sum(rev_flag).astype(jnp.int32))
            inval_l.append(jnp.sum(jnp.where(rev_flag, state["emit"], 0))
                           .astype(jnp.int32))
            emit2 = jnp.where(rev_flag, 0, state["emit"])
            home2 = jnp.where(rev_flag, jnp.int32(j), state["home"])
            emit2 = emit2.at[jnp.where(th, epos, R)].add(1, mode="drop")
            # insert taken misses: lexicographic (f1, f2) sort → dedup →
            # run-length credit counts → one sorted-concat merge
            tm = taken & ~hit
            cf1 = jnp.where(tm, f1, jnp.uint32(self._SENTINEL))
            cf2 = jnp.where(tm, f2, jnp.uint32(self._SENTINEL))
            o = jnp.argsort(cf2)
            o = o[jnp.argsort(cf1[o])]
            sf1, sf2, stm = cf1[o], cf2[o], tm[o]
            first = jnp.arange(bj) == 0
            dup = (~first & (sf1 == jnp.roll(sf1, 1))
                   & (sf2 == jnp.roll(sf2, 1)))
            is_new = stm & ~dup
            g = jnp.cumsum(is_new) - 1
            counts = (jnp.zeros((bj,), jnp.int32)
                      .at[jnp.where(stm, g, bj)].add(1, mode="drop"))
            n_new = jnp.sum(is_new).astype(jnp.int32)
            new_emit = jnp.where(is_new, counts[jnp.clip(g, 0, bj - 1)], 0)
            nf1 = jnp.where(is_new, sf1, jnp.uint32(self._SENTINEL))
            nf2 = jnp.where(is_new, sf2, jnp.uint32(self._SENTINEL))
            nhome = jnp.where(is_new, jnp.int32(j), jnp.int32(0x7FFFFFFF))
            mf1 = jnp.concatenate([state["f1"], nf1])
            morder = jnp.argsort(mf1)[:R]
            state = {
                "f1": mf1[morder],
                "f2": jnp.concatenate([state["f2"], nf2])[morder],
                "home": jnp.concatenate([home2, nhome])[morder],
                "emit": jnp.concatenate([emit2, new_emit.astype(jnp.int32)]
                                        )[morder],
                "count": state["count"] + n_new,
                "fail": state["fail"] | (state["count"] + n_new > R),
            }
        out = (state, cols_out, jnp.stack(ft_l), jnp.stack(okc_l),
               jnp.stack(resc_l), jnp.stack(predc_l), jnp.stack(rejc_l),
               jnp.stack(accc_l), jnp.stack(revc_l), jnp.stack(inval_l),
               jnp.stack(killc_l))
        if self.debug_capture:
            return out + (debug,)
        return out

    # -- driver ---------------------------------------------------------------
    def sample_async(self, n: int):
        from ..union_sampler import empty_sample_set
        if n <= 0:
            return _ReadyHandle(empty_sample_set(list(self.attrs),
                                                 self.stats))
        return _ReadyHandle(self._sample_record(n))

    def sample(self, n: int):
        from ..union_sampler import empty_sample_set
        if n <= 0:
            return empty_sample_set(list(self.attrs), self.stats)
        return self._sample_record(n)

    def _host_lookup(self, f1s: np.ndarray, q1: np.ndarray):
        """Positions of (q1, q2) probes: returns the searchsorted lows (the
        window scan happens at the call site, numpy-vectorised)."""
        return np.searchsorted(f1s, q1, side="left")

    def _sample_record(self, n: int):
        from ..union_sampler import SampleSet
        nj, bt = len(self.order), int(sum(self.piece_batches))
        if self._rec_state is None:
            self._rec_state = self._init_record_state(n)
        pbatch = np.asarray(self.piece_batches, np.int64)
        pstats = np.zeros((nj, len(PIECE_STAT_FIELDS)), np.int64)
        dead, streak = self._h_dead, self._h_streak
        base = np.asarray(self._probs_base, np.float64)
        parts: List[Tuple[np.ndarray, int]] = []   # (rows matrix, home) in
        carry = np.zeros(nj, dtype=np.int64)       # emission order
        valid = 0
        rounds = 0
        while valid < n:
            rounds += 1
            if rounds > self.max_rounds:
                raise RuntimeError(
                    "JaxRecordUnionSampler: top-up budget exhausted")
            probs = np.where(dead, 0.0, base)
            s = probs.sum()
            if s <= 0:
                raise RuntimeError("all cover pieces unreachable")
            extra = max(0, min(n - valid - int(carry.sum()),
                               self.round_batch))
            fresh = self.host_rng.multinomial(extra, probs / s)
            need = carry + fresh
            self.key, sub = jax.random.split(self.key)
            res = self._rec_jit(self._rec_state,
                                jnp.asarray(need.astype(np.int32)), sub)
            (self._rec_state, cols, ft, okc, resc, predc, rejc, accc,
             revc, inval, killc) = res[:11]
            if self.debug_capture:
                self.captured.append({
                    "need": need.copy(),
                    "pieces": [({a: np.asarray(c) for a, c in rows.items()},
                                np.asarray(acc))
                               for rows, acc in res[11]],
                })
            ft = np.asarray(ft).astype(np.int64)
            okc = np.asarray(okc).astype(np.int64)
            resc = np.asarray(resc).astype(np.int64)
            predc = np.asarray(predc).astype(np.int64)
            rejc = np.asarray(rejc).astype(np.int64)
            accc = np.asarray(accc).astype(np.int64)
            if bool(np.asarray(self._rec_state["fail"])):
                raise RuntimeError(
                    f"JaxRecordUnionSampler: record capacity R={self.R} "
                    "exhausted; pass record_capacity= to size the multiset "
                    "for the expected distinct-tuple volume")
            for j in range(nj):
                if ft[j]:
                    parts.append((np.asarray(cols[j])[:ft[j]], j))
            valid += int(ft.sum()) - int(np.asarray(inval).sum())
            self.stats.iterations += bt
            self.stats.candidate_draws += bt
            self.stats.residual_rejects += int(resc.sum())
            self.stats.pred_rejects += int(predc.sum())
            self.stats.cover_rejects += int(rejc.sum())
            self.stats.revisions += int(np.asarray(revc).sum())
            self.stats.backtrack_removed += int(np.asarray(inval).sum())
            pstats[:, 0] += pbatch
            pstats[:, 1] += accc
            pstats[:, 2] += resc
            pstats[:, 3] += np.asarray(killc).astype(np.int64)
            # no surplus banking in record mode: the bank columns stay zero
            shortfall = need - ft
            self.stats.dropped_slots += int(shortfall[dead].sum())
            shortfall[dead] = 0
            trig = (shortfall > 0) & (accc == 0)
            streak[:] = np.where(dead, streak,
                                 np.where(trig, streak + 1, 0))
            newly = ~dead & (streak >= self.dead_rounds)
            self.stats.dropped_slots += int(shortfall[newly].sum())
            shortfall[newly] = 0
            dead |= newly
            carry = shortfall
        self.last_rounds = rounds
        self._fold_piece_stats(pstats, rounds=rounds, samples=n)
        # settle emission: keep rows whose emit-time home is still the final
        # record home (revised copies are exactly the ones whose home moved)
        f1s = np.asarray(self._rec_state["f1"])
        f2s = np.asarray(self._rec_state["f2"])
        homes = np.asarray(self._rec_state["home"])
        kept: List[np.ndarray] = []
        for mat, j in parts:
            by_attr = {a: mat[:, i].astype(np.int64)
                       for i, a in enumerate(self.attrs)}
            q1 = fp32_np([by_attr[a] for a in self._sorted_attrs], salt=1)
            q2 = fp32_np([by_attr[a] for a in self._sorted_attrs], salt=2)
            lo = self._host_lookup(f1s, q1)
            fh = np.full(q1.shape[0], -1, np.int64)
            found = np.zeros(q1.shape[0], bool)
            for k in range(self._KWIN):
                pos = np.minimum(lo + k, self.R - 1)
                m = ((lo + k < self.R) & (f1s[pos] == q1)
                     & (f2s[pos] == q2) & ~found)
                fh = np.where(m, homes[pos], fh)
                found |= m
            keep = found & (fh == j)
            if keep.any():
                km = mat[keep].astype(np.int64)
                kept.append(np.concatenate(
                    [km, np.full((km.shape[0], 1), j, np.int64)], axis=1))
        mat = (np.concatenate(kept) if kept
               else np.zeros((0, len(self.attrs) + 1), np.int64))
        if mat.shape[0] < n:
            raise RuntimeError(
                "JaxRecordUnionSampler: settled emission came up short "
                f"({mat.shape[0]} < {n}) — record fingerprint collision")
        mat = mat[:n]
        shuffle = self.host_rng.permutation(n)
        mat = mat[shuffle]
        rows = {a: np.ascontiguousarray(mat[:, i])
                for i, a in enumerate(self.attrs)}
        home = np.ascontiguousarray(mat[:, -1])
        from ..relation import fingerprint128
        fp = fingerprint128([rows[a] for a in sorted(self.attrs)])
        return SampleSet(list(self.attrs), rows, home, fp, self.stats)

    def record_dict(self) -> Dict[int, Tuple[int, int]]:
        """The current record as ``{fp64: (home, credited_rows)}`` (test
        hook: the debug-capture replay compares its host dict to this)."""
        if self._rec_state is None:
            return {}
        f1 = np.asarray(self._rec_state["f1"]).astype(np.uint64)
        f2 = np.asarray(self._rec_state["f2"]).astype(np.uint64)
        home = np.asarray(self._rec_state["home"])
        emit = np.asarray(self._rec_state["emit"])
        real = ~((f1 == self._SENTINEL) & (f2 == self._SENTINEL))
        return {int((f1[i] << np.uint64(32)) | f2[i]):
                (int(home[i]), int(emit[i]))
                for i in np.nonzero(real)[0]}
