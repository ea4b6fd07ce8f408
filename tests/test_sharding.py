"""Sharded execution layer: mesh-partitioned catalog + shard_map'd rounds.

Covers the acceptance bar of the sharding refactor: a 1-shard mesh must
reproduce the unsharded fused engine bit for bit; multi-shard runs (in
subprocesses with a forced host-platform device count, following the repo's
multi-device test idiom) must stay exactly uniform; the on-mesh moment merge
must equal the host ``merge_statistics``; and the serve queue must drain
correctly under concurrent requests.  The distributed wrapper's satellites
(backend forwarding, geometric oversample growth, ``SamplerStats.merge``)
are pinned here too.
"""

import dataclasses
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from _conformance import chi2_p
from repro.core.distributed import (DistributedUnionSampler, merge_statistics,
                                    merge_streams, partition_of)
from repro.core.framework import estimate_union, warmup
from repro.core.overlap import exact_union_size
from repro.core.size_estimation import RunningMean
from repro.core.union_sampler import SamplerStats, SetUnionSampler
from repro.data.workloads import uq1, uq3

TESTS = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(TESTS, "..", "src")


def _run_sub(code: str, devices: int = 4, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([SRC, TESTS])     # _conformance
    # drop any inherited device-count flag (e.g. from the sharded-smoke CI
    # job) so the subprocess sees exactly one
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={devices}"])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# host-side helpers
# ---------------------------------------------------------------------------


def test_row_range_bounds_and_fp_partition():
    from repro.core.sharding import partition_of_fp32, row_range_bounds
    b = row_range_bounds(103, 4)
    assert b[0] == 0 and b[-1] == 103
    assert (np.diff(b) >= 25).all() and (np.diff(b) <= 26).all()
    fp = np.arange(1000, dtype=np.uint32) * np.uint32(2654435761)
    owner = partition_of_fp32(fp, 4)
    assert owner.min() >= 0 and owner.max() <= 3
    # ownership is a partition: deterministic and total
    assert np.array_equal(owner, partition_of_fp32(fp, 4))


def test_sampler_stats_merge_associative():
    a = SamplerStats(iterations=3, cover_rejects=1)
    b = SamplerStats(iterations=5, candidate_draws=7, revisions=2)
    c = SamplerStats(dropped_slots=4)
    left = SamplerStats().merge(a).merge(b).merge(c)
    right = SamplerStats().merge(a).merge(SamplerStats().merge(b).merge(c))
    assert left.as_dict() == right.as_dict()
    assert left.iterations == 8 and left.revisions == 2
    # snapshot is detached
    snap = a.snapshot()
    a.iterations += 100
    assert snap.iterations == 3


def test_merge_streams_uses_stats_merge():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    parts = []
    for rank in range(2):
        d = DistributedUnionSampler(wl.cat, wl.joins, est.cover, rank=rank,
                                    world=2, seed=3)
        parts.append(d.sample(200))
    merged = merge_streams(parts, seed=1)
    assert len(merged) == 400
    total = sum(p.stats.iterations for p in parts)
    assert merged.stats.iterations == total


# ---------------------------------------------------------------------------
# 1-shard mesh == unsharded fused engine, bit for bit
# ---------------------------------------------------------------------------


def test_one_shard_mesh_bitwise_equals_jax_engine():
    from repro.core.sharding import make_sampler_mesh
    wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    plain = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=7,
                            backend="jax", round_batch=1024)
    mesh = make_sampler_mesh(world=1)
    sharded = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=7,
                              backend="jax", round_batch=1024, mesh=mesh)
    a, b = plain.sample(3000), sharded.sample(3000)
    for attr in a.attrs:
        assert np.array_equal(a.rows[attr], b.rows[attr]), attr
    assert np.array_equal(a.home, b.home)
    assert np.array_equal(a.fingerprint, b.fingerprint)


def test_sharded_catalog_world1_degenerates_to_device_engine():
    from repro.core.backends.jax_backend import DeviceJoinMembership
    from repro.core.sharding import ShardedCatalog, make_sampler_mesh
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    scat = ShardedCatalog(wl.cat, wl.joins, mesh=make_sampler_mesh(world=1))
    for j in wl.joins:
        st = scat.trees[j.name]
        assert st.mode == "replicated"
        assert st.store_bounds[0] == 0 and st.store_bounds[-1] == st.tree.n_root
        np.testing.assert_allclose(np.asarray(st.root_prefix)[0],
                                   np.asarray(st.tree.root_wprefix))
        dm = DeviceJoinMembership(j)
        sm = scat.members[j.name]
        assert len(sm.rels) == len(dm.rels)
        for r_s, r_d in zip(sm.rels, dm.rels):
            assert r_s.attrs == r_d[0]
            assert r_s.kmax == r_d[3]
            n = int(np.asarray(r_s.n_owned)[0])
            assert n == r_d[4]
            np.testing.assert_array_equal(np.asarray(r_s.fp1)[0, :n],
                                          np.asarray(r_d[1]))


def test_sharded_catalog_columns_for_roundtrip():
    """Row-range store shards reassemble into the original columns."""
    from repro.core.sharding import ShardedCatalog, make_sampler_mesh
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    scat = ShardedCatalog(wl.cat, wl.joins, mesh=make_sampler_mesh(world=1))
    rel = wl.joins[0].nodes[0].relation
    b = scat.shard_bounds(rel)
    assert b[0] == 0 and b[-1] == rel.nrows
    shards = scat.columns_for(rel)
    assert scat.columns_for(rel) is shards          # cached
    for a, c in rel.columns.items():
        got = np.concatenate([np.asarray(shards[a])[s, :b[s + 1] - b[s]]
                              for s in range(scat.world)])
        np.testing.assert_array_equal(got, c)


# ---------------------------------------------------------------------------
# distributed wrapper satellites
# ---------------------------------------------------------------------------


def test_distributed_forwards_backend_to_inner_sampler():
    wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    d = DistributedUnionSampler(wl.cat, wl.joins, est.cover, rank=0, world=2,
                                backend="jax", round_batch=512, seed=3)
    assert d.inner._engine is not None          # device engine engaged
    ss = d.sample(500)
    assert len(ss) == 500


def test_seed_split_vs_hash_partition_uniformity():
    wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    U = exact_union_size(wl.cat, wl.joins)
    world = 2
    for scheme in ("seed-split", "hash-partition"):
        parts = []
        for rank in range(world):
            d = DistributedUnionSampler(wl.cat, wl.joins, est.cover,
                                        rank=rank, world=world, scheme=scheme,
                                        seed=5)
            parts.append(d.sample(40 * U))
        merged = merge_streams(parts, seed=2)
        if scheme == "hash-partition":
            # per-rank streams are partition-pure
            for rank, p in enumerate(parts):
                assert (partition_of(p.fingerprint, world) == rank).all()
        p_val = chi2_p(merged.matrix(), U)
        assert p_val > 1e-3, f"{scheme} union stream not uniform (p={p_val})"


def test_hash_partition_underfill_error_carries_counts():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    d = DistributedUnionSampler(wl.cat, wl.joins, est.cover, rank=0,
                                world=64, scheme="hash-partition", seed=3)
    with pytest.raises(RuntimeError, match=r"got \d+ of 4000"):
        d.sample(4000, oversample=0.01, max_rounds=1)


def test_hash_partition_geometric_growth_completes():
    """A partition smaller than |U|/world finishes via oversample growth."""
    wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    d = DistributedUnionSampler(wl.cat, wl.joins, est.cover, rank=3, world=4,
                                scheme="hash-partition", seed=9)
    # tiny initial oversample: the fixed-oversample code under-fills every
    # round; geometric growth must still converge within the budget
    ss = d.sample(300, oversample=0.05, max_rounds=16)
    assert len(ss) == 300
    assert (partition_of(ss.fingerprint, 4) == 3).all()


# ---------------------------------------------------------------------------
# multi-device paths (subprocess with forced host device count)
# ---------------------------------------------------------------------------


def test_on_mesh_moment_merge_matches_host_merge_statistics():
    out = _run_sub(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.sharding import make_sampler_mesh, psum_merge_moments
from repro.core.size_estimation import RunningMean
from repro.core.distributed import merge_statistics

world, batch = 4, 64
rng = np.random.default_rng(0)
xs = rng.exponential(5.0, (world, batch))

mesh = make_sampler_mesh(world=world)
def f(x):
    x = x[0]
    mean = jnp.mean(x)
    m2 = jnp.sum((x - mean) ** 2)
    n, gm, gm2 = psum_merge_moments(jnp.int32(x.shape[0]), mean, m2, "shards")
    return n[None], gm[None], gm2[None]
n, gm, gm2 = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("shards"),),
                                   out_specs=P("shards"), check_vma=False))(
    jnp.asarray(xs, jnp.float32))

host_parts = []
for s in range(world):
    r = RunningMean()
    r.update_batch(xs[s])
    host_parts.append(r)
host = merge_statistics(host_parts)
assert int(n[0]) == host.count == world * batch
np.testing.assert_allclose(float(gm[0]), host.mean, rtol=1e-5)
np.testing.assert_allclose(float(gm2[0]), host.m2, rtol=1e-4)
print("OK")
""")
    assert "OK" in out


def test_multi_shard_uniform_and_matches_host_marginal():
    out = _run_sub(r"""
import numpy as np
from _conformance import chi2_p
from repro.core.framework import estimate_union, warmup
from repro.core.overlap import exact_union_size
from repro.core.sharding import ShardedCatalog, make_sampler_mesh
from repro.core.union_sampler import SetUnionSampler
from repro.data.workloads import uq1

wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
U = exact_union_size(wl.cat, wl.joins)
mesh = make_sampler_mesh(world=4)
s = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=11, backend="jax",
                    round_batch=512, mesh=mesh)
N = 120 * U
ss = s.sample(N)
assert len(ss) == N
p = chi2_p(ss.matrix(), U)
assert p > 1e-3, p

host = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3).sample(8000)
fa = np.bincount(host.home, minlength=2) / len(host)
fb = np.bincount(ss.home, minlength=2) / len(ss)
assert np.abs(fa - fb).max() < 0.03, (fa, fb)

# on-mesh ONLINE-UNION refinement smoke
from repro.core.online import OnlineUnionSampler
ou = OnlineUnionSampler(wl.cat, wl.joins, seed=5, phi=512, rw_batch=64,
                        backend="jax", mesh=mesh)
out = ou.sample(100)
assert len(out) == 100
counts = {k: v.count for k, v in ou.estimator.size_stats.items()}
assert all(c % (4 * 64) == 0 and c > 0 for c in counts.values()), counts
print("OK")
""", devices=4, timeout=900)
    assert "OK" in out


def test_multi_shard_cyclic_union_uniform():
    """UQ4's cyclic piece under a 4-shard mesh: residual verification stays
    local (replicated node indexes), cover membership rides the one
    fingerprint exchange, and the union stream stays exactly uniform."""
    out = _run_sub(r"""
import numpy as np
from _conformance import chi2_p
from repro.core.framework import estimate_union, warmup
from repro.core.overlap import exact_union_size
from repro.core.sharding import make_sampler_mesh
from repro.core.union_sampler import SetUnionSampler
from repro.data.workloads import uq4

wl = uq4(scale=0.02, seed=0)
est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
U = exact_union_size(wl.cat, wl.joins)
mesh = make_sampler_mesh(world=4)
s = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=11, backend="jax",
                    round_batch=512, mesh=mesh)
N = 120 * U
ss = s.sample(N)
assert len(ss) == N
p = chi2_p(ss.matrix(), U)
assert p > 1e-3, p
print("OK")
""", devices=4, timeout=900)
    assert "OK" in out


# ---------------------------------------------------------------------------
# serve queue
# ---------------------------------------------------------------------------


def test_serve_queue_drains_under_concurrent_requests():
    from repro.serve import SampleService
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    sampler = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3)
    results = {}
    errors = []
    with SampleService(sampler, batch=512, prefetch=2) as svc:
        def worker(tid, n):
            try:
                results[tid] = svc.request(n, timeout=120)
            except Exception as e:            # pragma: no cover
                errors.append(e)
        threads = [threading.Thread(target=worker, args=(t, 150 + 50 * t))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        assert svc.served == sum(150 + 50 * t for t in range(4))
    # each response has exactly the requested size and consistent columns
    for tid, ss in results.items():
        assert len(ss) == 150 + 50 * tid
        for a in ss.attrs:
            assert ss.rows[a].shape[0] == len(ss)
    # queue slices are disjoint segments of one i.i.d. stream: pooled
    # fingerprints across requests must match the engine's served count
    total = sum(len(ss) for ss in results.values())
    assert total == sum(150 + 50 * t for t in range(4))
    # merged accounting is visible and associative
    st = SamplerStats()
    for ss in results.values():
        st.merge(ss.stats)
    assert st.iterations > 0


def test_service_errors_on_unstarted_and_propagates_engine_failure():
    from repro.serve import SampleService

    class Boom:
        attrs = ["a"]
        stats = SamplerStats()

        def sample(self, n):
            raise ValueError("engine exploded")

    svc = SampleService(Boom(), batch=16, prefetch=1)
    with pytest.raises(RuntimeError, match="not started"):
        svc.request(4)
    with svc:
        with pytest.raises(RuntimeError, match="producer failed"):
            svc.request(4, timeout=10)
