"""Mesh-partitioned catalog: row-range shards + hash-partitioned membership.

The third execution layer under the samplers (host numpy → device JAX →
sharded JAX).  A :class:`ShardedCatalog` partitions the columnar stores of a
union's relations across a 1-axis :class:`jax.sharding.Mesh`:

* **row-range shards** — each relation's rows are cut into ``world``
  contiguous ranges; the per-shard slices are placed on their devices as
  stacked ``P(axis)`` arrays (``columns_for``).  Dict-encodings (the
  per-attribute mixed-radix widths of the device engine) are *replicated*:
  every shard packs composite keys identically, so probes and fingerprints
  agree across shards.
* **replicated candidate roots** (:class:`ShardedTreeJoin`) — the per-join
  draw state (root weight prefix + payload columns, plus the non-root node
  indexes of the underlying
  :class:`~repro.core.backends.jax_backend.DeviceTreeJoin`) is broadcast to
  every shard, so each shard draws i.i.d. candidates from the *whole* join
  under its own fold-in key with zero communication — the exactness
  rationale is in the class docstring (root-*range* pieces would make the
  shard streams non-exchangeable and bias any fixed-shape consumption).
* **hash-partitioned membership** (:class:`ShardedMembership`) — the
  row-fingerprint space of every base relation is split by
  :func:`partition_of_fp32` (the 32-bit twin of
  :func:`repro.core.distributed.partition_of`): shard ``s`` owns and indexes
  only fingerprints with ``fp1 % world == s``.  A membership probe is
  resolved by the owner, which is why the sampler's round needs exactly one
  all-gather + one reduce-scatter exchange (see
  :class:`~repro.core.sharding.sampler.ShardedUnionSampler`).  Residual
  (§8.2 cycle-closing) relations of cyclic joins are base relations like
  any other here, so their fingerprints ride the same exchange; the
  residual *draw* state (sorted composite-key indexes) is replicated
  non-root node state of the underlying :class:`DeviceTreeJoin`, like every
  child index.

With ``world == 1`` every per-shard structure degenerates to the PR-1 device
engine's arrays bit for bit — the acceptance bar the equivalence tests pin.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index import Catalog
from ..joins import JoinSpec
from ..relation import Relation
from ..backends.jax_backend import (DeviceTreeJoin, JaxBackend, _as_i32,
                                    fp32_np)

SHARD_AXIS = "shards"

_FP_PAD = np.uint32(0xFFFFFFFF)   # sort-stable pad; real hits are n-guarded


def make_sampler_mesh(world: Optional[int] = None,
                      axis: str = SHARD_AXIS) -> Mesh:
    """1-axis mesh over the first ``world`` local devices (default: all)."""
    devs = jax.devices()
    if world is None:
        world = len(devs)
    if world > len(devs):
        raise ValueError(
            f"requested {world} shards but only {len(devs)} devices are "
            "visible (set XLA_FLAGS=--xla_force_host_platform_device_count "
            "on CPU)")
    return Mesh(np.asarray(devs[:world]), (axis,))


def partition_of_fp32(fp1: np.ndarray, world: int) -> np.ndarray:
    """Shard ownership of 32-bit row fingerprints (device-engine twin of
    :func:`repro.core.distributed.partition_of`)."""
    return (np.asarray(fp1, np.uint32) % np.uint32(world)).astype(np.int64)


def _shard_put(mesh: Mesh, axis: str, arr: np.ndarray) -> jax.Array:
    """Place a stacked ``(world, ...)`` host array one row per device."""
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, P(axis)))


def row_range_bounds(nrows: int, world: int) -> np.ndarray:
    """Balanced contiguous row-range bounds ``(world + 1,)``."""
    return np.linspace(0, nrows, world + 1).astype(np.int64)


# ---------------------------------------------------------------------------
# Per-join root partition (candidate generation side)
# ---------------------------------------------------------------------------


class ShardedTreeJoin:
    """One join's candidate-generation state laid out for the mesh.

    The root draw arrays (weight prefix + payload columns) are *replicated*:
    every shard draws i.i.d. from the **whole** join under its own fold-in
    key, so each shard's accepted stream is uniform over the full cover
    piece and any fixed-shape consumption order (prefix take, surplus
    banking) stays exactly uniform — the paper's independence guarantee
    makes the shard streams exchangeable.

    Why not partition the root rows?  A root-range shard draws candidates
    uniform over its *local* piece ``J_s`` only; with fixed per-shard batch
    shapes, every downstream consumption rule (take the first ``need``
    accepted, bank the rest) then over-represents whichever shards are
    consumed first, and correcting that exactly needs per-``(cover piece,
    shard)`` sizes no estimator provides.  Replicating the root is the
    classic broadcast side of a distributed join; the state that dominates
    memory at scale — the membership fingerprint indexes — *is* partitioned
    (:class:`ShardedMembership`), and relation stores row-range shard via
    :meth:`ShardedCatalog.columns_for`.  ``store_bounds`` records the root
    store's row-range ownership.
    """

    def __init__(self, tree: DeviceTreeJoin, mesh: Mesh, axis: str = SHARD_AXIS):
        self.tree = tree
        self.name = tree.name
        self.attrs = tree.attrs
        world = int(mesh.shape[axis])
        self.world = world
        self.mode = "replicated"
        n_root = tree.n_root
        self.store_bounds = row_range_bounds(n_root, world)
        wp32 = tree.host_root_wprefix.astype(np.float32)   # (n_root + 1,)
        prefix_stk = np.broadcast_to(wp32, (world, n_root + 1)).copy()
        cols_stk = {
            a: (np.broadcast_to(c, (world, n_root)).copy() if n_root
                else np.zeros((world, 1), dtype=np.int32))
            for a, c in tree.host_root_cols.items()}
        self.root_prefix = _shard_put(mesh, axis, prefix_stk)
        self.root_cols = {a: _shard_put(mesh, axis, c)
                          for a, c in cols_stk.items()}
        self.n_root = _shard_put(
            mesh, axis, np.full(world, n_root, dtype=np.int32))
        # the non-root node indexes, replicated once on every device (the
        # sampler passes them into its programs as a P() argument)
        self.nodes = jax.device_put(tree.device_arrays()["nodes"],
                                    NamedSharding(mesh, P()))

    def is_empty(self) -> bool:
        return self.tree.is_empty()

    def state(self) -> Dict[str, object]:
        """Per-shard leaves for the sampler's ``shard_map`` inputs."""
        return {"prefix": self.root_prefix, "cols": self.root_cols,
                "n_root": self.n_root}


# ---------------------------------------------------------------------------
# Per-join hash-partitioned membership (cover-acceptance side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _ShardedRelIndex:
    attrs: Tuple[str, ...]
    fp1: jax.Array          # (world, max_owned) uint32, sorted per shard
    fp2: jax.Array          # (world, max_owned) uint32, fp1 order
    n_owned: jax.Array      # (world,) int32
    kmax: int               # global duplicate window (>= any shard's)
    nrows: int


class ShardedMembership:
    """'Is tuple t in join J' with fingerprint hash-partition ownership.

    Mirrors :class:`~repro.core.backends.jax_backend.DeviceJoinMembership`
    (same fp32 arithmetic, same sorted-index + ``kmax`` duplicate-window
    probe) but each shard indexes only the row fingerprints it owns under
    :func:`partition_of_fp32`, so the total index memory is ``1/world`` per
    shard and a probe must be routed to the owner.  With ``world == 1`` the
    owned index equals the unsharded one exactly.
    """

    def __init__(self, spec: JoinSpec, mesh: Mesh, axis: str = SHARD_AXIS):
        self.join_name = spec.name
        world = int(mesh.shape[axis])
        self.world = world
        self.rels: List[_ShardedRelIndex] = []
        seen = set()
        for node in spec.nodes:
            rel = node.relation
            attrs = tuple(sorted(rel.attrs))
            if (rel.name, attrs) in seen:
                continue
            seen.add((rel.name, attrs))
            for a in attrs:
                _as_i32(rel.columns[a], f"{rel.name}.{a}")   # domain check
            fp1 = fp32_np([rel.columns[a] for a in attrs], salt=1)
            fp2 = fp32_np([rel.columns[a] for a in attrs], salt=2)
            owner = partition_of_fp32(fp1, world)
            owned1: List[np.ndarray] = []
            owned2: List[np.ndarray] = []
            kmax = 0
            for s in range(world):
                idx = np.nonzero(owner == s)[0]
                order = idx[np.argsort(fp1[idx], kind="stable")]
                s1 = fp1[order]
                if s1.shape[0]:
                    _, counts = np.unique(s1, return_counts=True)
                    kmax = max(kmax, int(counts.max()))
                owned1.append(s1)
                owned2.append(fp2[order])
            max_owned = max(max(c.shape[0] for c in owned1), 1)
            stk1 = np.full((world, max_owned), _FP_PAD, dtype=np.uint32)
            stk2 = np.zeros((world, max_owned), dtype=np.uint32)
            n_owned = np.zeros(world, dtype=np.int32)
            for s in range(world):
                n = owned1[s].shape[0]
                stk1[s, :n] = owned1[s]
                stk2[s, :n] = owned2[s]
                n_owned[s] = n
            self.rels.append(_ShardedRelIndex(
                attrs, _shard_put(mesh, axis, stk1),
                _shard_put(mesh, axis, stk2),
                _shard_put(mesh, axis, n_owned), kmax, int(rel.nrows)))

    def state(self) -> List[Dict[str, object]]:
        """Per-shard leaves for the sampler's ``shard_map`` inputs."""
        return [{"fp1": r.fp1, "fp2": r.fp2, "n_owned": r.n_owned}
                for r in self.rels]


# ---------------------------------------------------------------------------
# The catalog
# ---------------------------------------------------------------------------


class ShardedCatalog:
    """Mesh-partitioned stores + per-shard indexes for one union of joins.

    Wraps (or builds) a :class:`~repro.core.backends.jax_backend.JaxBackend`
    — its :class:`DeviceTreeJoin` child indexes and dict-encodings are the
    replicated part — and adds the per-shard partitions: weight-balanced root
    ranges per join and hash-partitioned membership per join.  Relation
    columnar stores are row-range sharded lazily via :meth:`columns_for`.
    """

    def __init__(self, cat: Catalog, joins: Sequence[JoinSpec],
                 mesh: Optional[Mesh] = None, axis: str = SHARD_AXIS,
                 backend: Optional[JaxBackend] = None,
                 join_method: str = "ew", seed: int = 0,
                 use_pallas: Optional[bool] = None):
        self.cat = cat
        self.joins = list(joins)
        self.mesh = mesh if mesh is not None else make_sampler_mesh(axis=axis)
        if axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}: {self.mesh}")
        self.axis = axis
        self.world = int(self.mesh.shape[axis])
        self.backend = backend if backend is not None else JaxBackend(
            cat, self.joins, join_method=join_method, seed=seed,
            use_pallas=use_pallas)
        self.attrs = list(self.backend.attrs)
        self.trees: Dict[str, ShardedTreeJoin] = {
            j.name: ShardedTreeJoin(self.backend.trees[j.name], self.mesh,
                                    axis)
            for j in self.joins}
        self.members: Dict[str, ShardedMembership] = {
            j.name: ShardedMembership(j, self.mesh, axis) for j in self.joins}
        self._col_cache: Dict[str, Dict[str, jax.Array]] = {}

    def shard_bounds(self, rel: Relation) -> np.ndarray:
        """Row-range ownership of one relation's store: ``(world + 1,)``."""
        return row_range_bounds(rel.nrows, self.world)

    def columns_for(self, rel: Relation) -> Dict[str, jax.Array]:
        """The relation's columnar store as ``(world, max_rows)`` device
        shards (row-range partition, zero-padded), one row-range per device."""
        if rel.name not in self._col_cache:
            b = self.shard_bounds(rel)
            max_rows = max(int((b[1:] - b[:-1]).max()), 1)
            shards = {}
            for a, c in rel.columns.items():
                stk = np.zeros((self.world, max_rows), dtype=np.int64)
                for s in range(self.world):
                    lo, hi = int(b[s]), int(b[s + 1])
                    stk[s, :hi - lo] = c[lo:hi]
                shards[a] = _shard_put(self.mesh, self.axis, stk)
            self._col_cache[rel.name] = shards
        return self._col_cache[rel.name]
