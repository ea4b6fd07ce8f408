"""§8.3 predicates and record-mode membership on the device engine.

Acceptance bar of the predicate tentpole: both §8.3 treatments of the UQ2
regime run inside the fused Algorithm-1 round with host-identical
semantics — chi-square uniformity against the exact filtered universes on
every engine, in both modes (the engine × union matrix,
``test_conformance_*``), the fused device loop bit-equal
to its host twin on a shared trace (``pred_rejects`` included), the device
record engine equivalent to a sequential host dict replay of its captured
rounds (revisions and emission invalidation included), and a 1-device mesh
reproducing the unsharded engine bit for bit under rejection predicates.
"""

import numpy as np
import pytest

from repro.core.backends.jax_backend import (JaxRecordUnionSampler, fp32_np)
from repro.core.framework import estimate_union, warmup
from repro.core.index import Catalog
from repro.core.joins import chain_join
from repro.core.overlap import exact_union_size
from repro.core.predicates import Pred, rejection
from repro.core.union_sampler import SetUnionSampler
from repro.data.tpch import generate
from repro.data.workloads import uq2


@pytest.fixture(scope="module", params=["pushdown", "rejection"])
def uq2_setup(request):
    wl = uq2(scale=0.02, seed=0, pred_mode=request.param)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    U = exact_union_size(wl.cat, wl.joins)
    return request.param, wl, est, U


def test_uq2_rejection_pred_rejects_in_stats_dict(uq2_setup):
    mode, wl, est, U = uq2_setup
    if mode != "rejection":
        pytest.skip("rejection-mode accounting only")
    for backend in ("numpy", "jax"):
        s = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=5,
                            backend=backend, round_batch=1024)
        ss = s.sample(2000)
        d = ss.stats.as_dict()
        assert d["pred_rejects"] == ss.stats.pred_rejects > 0, backend


# ---------------------------------------------------------------------------
# shared trace: fused device loop == host twin, bit for bit, preds included
# ---------------------------------------------------------------------------


def test_fused_device_matches_host_twin_bitwise(uq2_setup):
    mode, wl, est, U = uq2_setup
    kw = dict(seed=9, backend="jax", round_batch=512)
    a = SetUnionSampler(wl.cat, wl.joins, est.cover,
                        fused_rounds="device", **kw).sample(3000)
    b = SetUnionSampler(wl.cat, wl.joins, est.cover,
                        fused_rounds="host", **kw).sample(3000)
    for attr in a.attrs:
        assert np.array_equal(a.rows[attr], b.rows[attr]), attr
    assert np.array_equal(a.home, b.home)
    assert np.array_equal(a.fingerprint, b.fingerprint)
    assert a.stats.as_dict() == b.stats.as_dict()
    if mode == "rejection":
        assert a.stats.pred_rejects > 0


# ---------------------------------------------------------------------------
# 1-device mesh: sharded loop == unsharded under rejection predicates
# ---------------------------------------------------------------------------


def test_uq2_one_shard_mesh_bitwise_equals_jax_engine(uq2_setup):
    from repro.core.sharding import make_sampler_mesh
    mode, wl, est, U = uq2_setup
    if mode != "rejection":
        pytest.skip("sharded predicate path is the rejection lowering")
    plain = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=11,
                            backend="jax", round_batch=1024)
    sharded = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=11,
                              backend="jax", round_batch=1024,
                              mesh=make_sampler_mesh(world=1))
    a, b = plain.sample(3000), sharded.sample(3000)
    for attr in a.attrs:
        assert np.array_equal(a.rows[attr], b.rows[attr]), attr
    assert np.array_equal(a.home, b.home)
    assert a.stats.as_dict() == b.stats.as_dict()
    assert a.stats.pred_rejects > 0


# ---------------------------------------------------------------------------
# record-mode membership on device
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def revision_workload():
    """Two rejection flavours of partsupp ⋈ part whose predicate windows
    overlap on the middle psize quintile: the later cover piece claims
    overlap tuples an earlier piece then re-draws, exercising the record
    engine's revision + emission-invalidation path (not just inserts)."""
    db = generate(0.1, seed=1)
    base = chain_join("PSP", [db["partsupp"], db["part"]], [("pk",)])
    ps = db["part"].columns["psize"]
    lo, hi = int(np.percentile(ps, 40)), int(np.percentile(ps, 60))
    j1 = rejection(base, [Pred("psize", "<=", hi)], name="PSP_LOW")
    j2 = rejection(base, [Pred("psize", ">=", lo)], name="PSP_HIGH")
    cat = Catalog()
    est = estimate_union(warmup(cat, [j1, j2], method="exact").oracle)
    return cat, [j1, j2], est


def test_record_engine_matches_sequential_host_replay(revision_workload):
    """The device record rounds (batched fingerprint-multiset updates) must
    equal a strictly sequential host dict replay of the captured candidate
    stream — same final record dict, same revision/invalidation/cover-reject
    counts, and every emitted row's home settled to its final record home."""
    cat, joins, est = revision_workload
    s = SetUnionSampler(cat, joins, est.cover, membership="record", seed=7,
                        backend="jax", round_batch=64)
    eng = s._engine
    assert isinstance(eng, JaxRecordUnionSampler)
    eng.debug_capture = True
    out = s.sample(1200)
    assert len(out) == 1200
    assert s.stats.revisions > 0          # the interesting path was exercised
    assert s.stats.backtrack_removed > 0

    # sequential replay: feed every captured candidate through a host dict.
    # Cover rejections (home < j) are counted over the whole batch — they are
    # state-independent within a piece (inserts/revisions only set home = j)
    # and the device counts them before applying the take quota.
    attrs = sorted(eng.attrs)
    rec = {}
    rev = rej = inval = 0
    for rd in eng.captured:
        need = rd["need"]
        for j, (rows, acc) in enumerate(rd["pieces"]):
            f1 = fp32_np([rows[a].astype(np.int64) for a in attrs],
                         salt=1).astype(np.uint64)
            f2 = fp32_np([rows[a].astype(np.int64) for a in attrs],
                         salt=2).astype(np.uint64)
            fps = (f1 << np.uint64(32)) | f2
            taken = 0
            for i in np.nonzero(acc)[0]:
                fp = int(fps[i])
                e = rec.get(fp)
                if e is not None and e[0] < j:
                    rej += 1
                    continue
                if taken >= need[j]:
                    continue
                if e is None:
                    rec[fp] = [j, 1]
                elif e[0] > j:
                    rev += 1
                    inval += e[1]
                    rec[fp] = [j, 1]
                else:
                    e[1] += 1
                taken += 1

    assert eng.record_dict() == {k: tuple(v) for k, v in rec.items()}
    assert rev == s.stats.revisions
    assert inval == s.stats.backtrack_removed
    assert rej == s.stats.cover_rejects

    # settled emission: every returned row's home equals its final record home
    dev = eng.record_dict()
    f1 = fp32_np([out.rows[a].astype(np.int64) for a in attrs],
                 salt=1).astype(np.uint64)
    f2 = fp32_np([out.rows[a].astype(np.int64) for a in attrs],
                 salt=2).astype(np.uint64)
    fps = (f1 << np.uint64(32)) | f2
    assert all(dev[int(fp)][0] == h for fp, h in zip(fps, out.home))


def test_record_engine_rejects_mesh(revision_workload):
    cat, joins, est = revision_workload
    from repro.core.sharding import make_sampler_mesh
    with pytest.raises(ValueError, match="record"):
        SetUnionSampler(cat, joins, est.cover, membership="record",
                        backend="jax", mesh=make_sampler_mesh(world=1))
