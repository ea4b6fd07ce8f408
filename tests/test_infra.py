"""Infrastructure: checkpointing, fault tolerance, pipeline, distributed
sampling, gradient compression, optimizers."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.framework import estimate_union, warmup
from repro.core.distributed import (DistributedUnionSampler, merge_statistics,
                                    merge_streams, partition_of)
from repro.core.size_estimation import RunningMean
from repro.data.encode import TokenEncoder
from repro.data.pipeline import SyntheticPipeline, UnionSamplePipeline
from repro.data.workloads import uq3
from repro.launch.ft import FTConfig, TrainSupervisor
from repro.train.grad_compress import compress_decompress, init_error_feedback
from repro.train.optimizer import OptConfig, apply_update, init_opt_state


# ---------------------------------------------------------------------------
# checkpointer
# ---------------------------------------------------------------------------


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"step": jnp.asarray(3, jnp.int32),
            "params": {"w": jnp.asarray(rng.standard_normal((4, 5))),
                       "b": jnp.asarray(rng.standard_normal(5))},
            "opt": {"m.w": jnp.zeros((4, 5))}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    st = _state()
    ck.save(3, st, {"rng": [1, 2, 3]})
    assert ck.latest_step() == 3
    got, pp = ck.restore()
    assert pp["rng"] == [1, 2, 3]
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(st["params"]["w"]))


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        st = _state(s)
        st["step"] = jnp.asarray(s)
        ck.save(s, st)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2
    assert ck.latest_step() == 4


def test_checkpoint_corruption_detected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state())
    d = os.path.join(tmp_path, "step_00000001")
    fn = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(d, fn))
    np.save(os.path.join(d, fn), arr + 1)
    with pytest.raises(IOError):
        ck.restore(1)


# ---------------------------------------------------------------------------
# fault-tolerant supervisor
# ---------------------------------------------------------------------------


def test_supervisor_restart_after_failure(tmp_path):
    ck = Checkpointer(str(tmp_path))
    calls = {"n": 0}

    def step_fn(state, batch):
        s = dict(state)
        s["step"] = state["step"] + 1
        s["params"] = {"w": state["params"]["w"] + 1.0}
        return s, {"loss": 0.0}

    def next_batch():
        return {"x": np.zeros(2)}

    failed = {"done": False}

    def injector(step):
        if step == 7 and not failed["done"]:
            failed["done"] = True
            raise RuntimeError("simulated preemption")

    sup = TrainSupervisor(step_fn, next_batch, ck,
                          FTConfig(checkpoint_every=2, max_restarts=3))
    state = {"step": jnp.asarray(0), "params": {"w": jnp.zeros(3)}}
    out = sup.run(state, 10, fail_injector=injector)
    assert int(out["step"]) == 10
    assert sup.stats.restarts == 1
    # params consistent with step count (each step +1, restart resumed from ckpt)
    np.testing.assert_allclose(np.asarray(out["params"]["w"]),
                               np.full(3, 10.0))


def test_supervisor_straggler_skip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    n = {"i": 0}

    def next_batch():
        n["i"] += 1
        return None if n["i"] % 3 == 0 else {"x": 1}  # every 3rd batch late

    def step_fn(state, batch):
        return {"step": state["step"] + 1}, {}

    sup = TrainSupervisor(step_fn, next_batch, ck, FTConfig(checkpoint_every=100))
    out = sup.run({"step": jnp.asarray(0)}, 6)
    assert int(out["step"]) == 6
    assert sup.stats.skipped_batches >= 2


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
    from scipy import stats as sps
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
    mat = merged.matrix()
    uni, counts = np.unique(mat.view([("", mat.dtype)] * mat.shape[1]).ravel(),
                            return_counts=True)
    exp = len(merged) / U
    chi2 = float(((counts - exp) ** 2 / exp).sum()) + (U - uni.shape[0]) * exp
    p = 1 - sps.chi2.cdf(chi2, df=U - 1)
    assert p > 1e-3


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


# ---------------------------------------------------------------------------
# optimizers / grad compression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["adamw", "adafactor"])
def test_optimizer_reduces_quadratic(kind):
    opt = OptConfig(kind=kind, lr=0.1, weight_decay=0.0)
    target = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4)))
    params = {"w": jnp.zeros((8, 4))}
    state = init_opt_state(opt, params)
    step = jnp.zeros((), jnp.int32)
    for i in range(200):
        g = {"w": params["w"] - target}
        params, state = apply_update(opt, params, g, state, step + i)
    assert float(jnp.abs(params["w"] - target).mean()) < 0.05


def test_grad_compress_error_feedback_unbiased():
    rng = np.random.default_rng(0)
    g_true = {"w": jnp.asarray(rng.standard_normal((64, 64)) * 1e-3)}
    state = {"ef": init_error_feedback(g_true)}
    acc = np.zeros((64, 64))
    n = 50
    for _ in range(n):
        out, state = compress_decompress(g_true, state)
        acc += np.asarray(out["w"])
    # error feedback: accumulated compressed grads ≈ accumulated true grads
    np.testing.assert_allclose(acc / n, np.asarray(g_true["w"]),
                               rtol=0.02, atol=1e-6)


def test_compressed_psum_multidevice_subprocess():
    """compressed_psum == psum (within quant error) on a real 4-device mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.train.grad_compress import compressed_psum
mesh = make_mesh((4,), ("d",))
x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 128)), jnp.float32)
def f(x):
    return compressed_psum(x, "d"), jax.lax.psum(x, "d")
got, want = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d"),
                                  out_specs=(P("d"), P("d"))))(x)
err = float(jnp.max(jnp.abs(got - want)))
scale = float(jnp.max(jnp.abs(want)))
assert err <= 0.05 * scale + 1e-5, (err, scale)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_train_step_with_grad_compression():
    """compress_grads=True end-to-end: error feedback state threads through."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import (TrainConfig, init_train_state,
                                        make_train_step)
    cfg = get_smoke_config("minitron-8b")
    tc = TrainConfig(opt=OptConfig(lr=1e-3), total_steps=10, warmup_steps=1,
                     compress_grads=True)
    state = init_train_state(cfg, tc, seed=0)
    assert "ef" in state
    step = jax.jit(make_train_step(cfg, tc))
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(4, cfg.vocab, (2, 64)), jnp.int32),
             "targets": jnp.asarray(rng.integers(4, cfg.vocab, (2, 64)), jnp.int32)}
    s1, m1 = step(state, batch)
    s2, m2 = step(s1, batch)
    assert np.isfinite(float(m2["loss"]))
    # error-feedback buffers are being used (non-zero residuals)
    ef_norm = sum(float(jnp.abs(v).sum()) for v in s2["ef"].values())
    assert ef_norm > 0


def test_microbatch_equivalence():
    """n_microbatches=2 gradients ≈ single-batch gradients (same data)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import (TrainConfig, init_train_state,
                                        make_train_step)
    cfg = get_smoke_config("minitron-8b")
    rng = np.random.default_rng(4)
    batch = {"tokens": jnp.asarray(rng.integers(4, cfg.vocab, (4, 64)), jnp.int32),
             "targets": jnp.asarray(rng.integers(4, cfg.vocab, (4, 64)), jnp.int32)}
    outs = []
    for n_micro in (1, 2):
        tc = TrainConfig(opt=OptConfig(lr=1e-2), total_steps=10,
                         warmup_steps=1, n_microbatches=n_micro)
        state = init_train_state(cfg, tc, seed=0)
        s1, _ = jax.jit(make_train_step(cfg, tc))(state, batch)
        outs.append(np.asarray(s1["params"]["blocks.wq"]))
    # same update direction within bf16 tolerance
    d = np.abs(outs[0] - outs[1]).max()
    scale = np.abs(outs[0]).max()
    assert d <= 0.1 * scale, (d, scale)


def test_moe_shard_map_equivalence_subprocess():
    """shard_map EP MoE == dense MoE (dropless) on a real 8-device mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.moe import MoEDims, moe_ffn, moe_ffn_dist, moe_param_shapes
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
dims = MoEDims(d_model=32, n_experts=8, top_k=2, d_ff=64, capacity_factor=16.0)
params = {k: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
          for k, s in moe_param_shapes(dims).items()}
x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
dense_out, _ = jax.jit(lambda p, x: moe_ffn(p, x, dims, capacity=64))(params, x)
with jax.set_mesh(mesh):
    dist_out, _ = jax.jit(lambda p, x: moe_ffn_dist(p, x, dims))(params, x)
    # production loss shape (transformer.forward_train: loss + 0.01*aux) —
    # a loss that drops aux feeds a symbolic-Zero cotangent into the aux
    # pmean that shard_map must transpose
    def loss(p, x):
        out, aux = moe_ffn_dist(p, x, dims)
        return out.sum() + 0.01 * aux
    g = jax.jit(jax.grad(loss))(params, x)
err = float(jnp.abs(dense_out - dist_out).max())
assert err < 2e-5, err
gn = sum(float(jnp.abs(v).sum()) for v in g.values())
assert np.isfinite(gn) and gn > 0
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
