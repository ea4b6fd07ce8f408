"""Sampler infrastructure: the token pipeline over a union sampler and the
host-side distributed sampling schemes."""

import numpy as np
import pytest

from _conformance import chi2_p
from repro.core.framework import estimate_union, warmup
from repro.core.distributed import (DistributedUnionSampler, merge_statistics,
                                    merge_streams, partition_of)
from repro.core.size_estimation import RunningMean
from repro.data.encode import TokenEncoder
from repro.data.pipeline import UnionSamplePipeline
from repro.data.workloads import uq3


# ---------------------------------------------------------------------------
# pipeline / encoding
# ---------------------------------------------------------------------------


def test_token_encoder_pack_shapes():
    enc = TokenEncoder(["a", "b", "c"], vocab_size=1024)
    rng = np.random.default_rng(0)
    rows = {k: rng.integers(0, 100, 300) for k in "abc"}
    toks, tgts, used = enc.pack(rows, batch=4, seq_len=64)
    assert toks.shape == (4, 64) and tgts.shape == (4, 64)
    assert toks.dtype == np.int32
    assert (toks[:, 0] == 1).all()              # BOS
    assert (toks < 1024).all() and (toks >= 0).all()
    np.testing.assert_array_equal(tgts[:, :-1], toks[:, 1:])


def test_union_pipeline_end_to_end():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    from repro.core.union_sampler import SetUnionSampler
    sampler = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3)
    enc = TokenEncoder(sorted(wl.joins[0].output_attrs), vocab_size=2048)
    pipe = UnionSamplePipeline(sampler, enc, batch=2, seq_len=32)
    toks, tgts = pipe.next_batch()
    assert toks.shape == (2, 32)
    st = pipe.state_dict()
    pipe.load_state_dict(st)
    assert pipe.stats.batches == 1


# ---------------------------------------------------------------------------
# distributed sampling
# ---------------------------------------------------------------------------


def test_seed_split_streams_uniform():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    from repro.core.overlap import exact_union_size
    U = exact_union_size(wl.cat, wl.joins)
    parts = []
    for rank in range(4):
        ds = DistributedUnionSampler(wl.cat, wl.joins, est.cover,
                                     rank=rank, world=4, seed=5)
        parts.append(ds.sample(20 * U))
    merged = merge_streams(parts)
    assert chi2_p(merged.matrix(), U) > 1e-3


def test_hash_partition_disjoint():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    seen = {}
    for rank in range(2):
        ds = DistributedUnionSampler(wl.cat, wl.joins, est.cover, rank=rank,
                                     world=2, scheme="hash-partition", seed=6)
        ss = ds.sample(200)
        pid = partition_of(ss.fingerprint, 2)
        assert (pid == rank).all()
        seen[rank] = {tuple(r) for r in ss.matrix().tolist()}
    assert not (seen[0] & seen[1])


def test_running_mean_merge_associative():
    rng = np.random.default_rng(0)
    xs = rng.standard_normal(1000)
    bulk = RunningMean()
    bulk.update_batch(xs)
    parts = []
    for i in range(4):
        rm = RunningMean()
        rm.update_batch(xs[i * 250:(i + 1) * 250])
        parts.append(rm)
    merged = merge_statistics(parts)
    assert merged.mean == pytest.approx(bulk.mean)
    assert merged.variance == pytest.approx(bulk.variance, rel=1e-9)
