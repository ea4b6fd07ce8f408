"""Adaptive round planner: EMA budgets, parity, uniformity, cost model.

The planner spends the fused loop's candidate budget where expected yield is
highest — per-piece acceptance EMAs carried as device state, integer budgets
water-filled from owed work minus bank coverage.  Everything it decides is a
pure function of carried *counts*, never sample values, so the uniformity
argument of the shortfall carry is untouched.  Pinned here:

* fixed-point planner arithmetic is bit-identical under numpy and jnp (the
  host twin is the parity oracle for the device carry);
* ``plan="adaptive"`` device loop == host twin, samples *and* stats, across
  calls whose EMAs/banks carry over — unsharded and world=1 sharded;
* chi-square uniformity of adaptive streams on UQ1 (acyclic) and UQ4
  (cyclic) on the 1-device mesh (the unsharded adaptive engine is a column
  of the engine × union matrix, ``test_conformance_device.py``);
* ``SamplerStats.psi()`` / ``samples_emitted`` accounting and the
  per-piece draw/accept counters a waste ratio derives from;
* the ONLINE-UNION host twin (``OnlineUnionSampler(plan="adaptive")``)
  batches fresh draws by the same EMAs and reseeds them at φ-refresh;
* :class:`PlanCache` cost-model fit/suggest determinism and the
  ``round_batch=None`` autotune entry point.
"""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from _conformance import chi2_p
from repro.core import planner
from repro.core.framework import estimate_union, warmup
from repro.core.online import OnlineUnionSampler
from repro.core.overlap import exact_union_size
from repro.core.union_sampler import SamplerStats, SetUnionSampler
from repro.data.workloads import uq1, uq4


def _cover(wl):
    return estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle).cover


def _assert_same_samples(a, b):
    assert a.attrs == b.attrs
    for attr in a.attrs:
        np.testing.assert_array_equal(a.rows[attr], b.rows[attr])
    np.testing.assert_array_equal(a.home, b.home)
    np.testing.assert_array_equal(a.fingerprint, b.fingerprint)


# ---------------------------------------------------------------------------
# fixed-point arithmetic: numpy and jnp agree bit for bit
# ---------------------------------------------------------------------------


def test_budget_and_ema_bitwise_numpy_vs_jnp():
    rng = np.random.default_rng(0)
    for _ in range(50):
        nj = int(rng.integers(1, 6))
        need = rng.integers(0, 1 << 14, nj).astype(np.int32)
        bank = rng.integers(0, 4096, nj).astype(np.int32)
        ema = rng.integers(1, planner.EMA_ONE + 1, nj).astype(np.int32)
        bmax = rng.integers(64, 8192, nj).astype(np.int32)
        dw = np.int32(rng.integers(1, 257))
        b_np = planner.budget_for(need, bank, ema, bmax, dw, np)
        b_j = planner.budget_for(jnp.asarray(need), jnp.asarray(bank),
                                 jnp.asarray(ema), jnp.asarray(bmax),
                                 dw, jnp)
        np.testing.assert_array_equal(np.asarray(b_np, np.int32),
                                      np.asarray(b_j))
        # masked-out pieces draw 0; owed pieces draw at least the floor
        assert (np.asarray(b_np)[np.maximum(need - np.minimum(bank, dw), 0)
                                 == 0] == 0).all()

        drawn = rng.integers(0, 1 << 20, nj).astype(np.int32)
        counts = np.stack([rng.integers(0, d + 1, 4) for d in drawn]
                          ).astype(np.int32)
        shifts = planner.ema_shifts(drawn.tolist())
        e0 = rng.integers(0, planner.EMA_ONE + 1, (nj, 4)).astype(np.int32)
        u_np = planner.ema_update(e0, drawn, counts, shifts, np)
        u_j = planner.ema_update(jnp.asarray(e0), jnp.asarray(drawn),
                                 jnp.asarray(counts), jnp.asarray(shifts),
                                 jnp)
        np.testing.assert_array_equal(np.asarray(u_np, np.int32),
                                      np.asarray(u_j))
        # rates are fractions: EMA state stays inside [0, EMA_ONE + slack]
        assert (np.asarray(u_np) >= 0).all()


def test_ema_converges_toward_observed_rate():
    ema = np.asarray([[planner.EMA_ONE, planner.EMA_ONE, 0, 0]], np.int32)
    drawn = np.asarray([256], np.int32)
    # piece accepts 64/256 = 0.25 of its budget every round
    counts = np.asarray([[64, 256, 0, 0]], np.int32)
    sh = planner.ema_shifts([256])
    for _ in range(64):
        ema = planner.ema_update(ema, drawn, counts, sh, np)
    assert abs(int(ema[0, 0]) - planner.EMA_ONE // 4) <= 8


def test_ema_shifts_prevent_overflow():
    shifts = planner.ema_shifts([8, 4096, 1 << 20])
    for b, s in zip([8, 4096, 1 << 20], shifts):
        assert (b >> s) * planner.EMA_ONE < 2 ** 31


# ---------------------------------------------------------------------------
# adaptive device loop == host twin (EMAs ride the carry across calls)
# ---------------------------------------------------------------------------


def test_adaptive_device_matches_host_twin_bitwise():
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)

    def engine(mode):
        return SetUnionSampler(wl.cat, wl.joins, cover, seed=11,
                               backend="jax", round_batch=128,
                               fused_rounds=mode, plan="adaptive")

    dev, host = engine("device"), engine("host")
    for n in (700, 333, 1025):
        _assert_same_samples(dev.sample(n), host.sample(n))
        assert dev.stats.as_dict() == host.stats.as_dict()


def test_adaptive_sharded_world1_matches_unsharded():
    from repro.core.sharding import make_sampler_mesh
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)

    def engine(mesh, mode="device"):
        return SetUnionSampler(wl.cat, wl.joins, cover, seed=9,
                               backend="jax", round_batch=512, mesh=mesh,
                               fused_rounds=mode, plan="adaptive")

    sharded = engine(make_sampler_mesh(world=1))
    between = engine(make_sampler_mesh(world=1), mode="host")
    plain = engine(None)
    for n in (900, 411):
        a, b, c = sharded.sample(n), between.sample(n), plain.sample(n)
        _assert_same_samples(a, b)
        _assert_same_samples(a, c)
        assert sharded.stats.as_dict() == plain.stats.as_dict()


def test_adaptive_cuts_waste_vs_static():
    """The tentpole's psi story: EMA budgets + wider selection slots spend
    fewer counted candidate draws per emitted sample than the fixed batch."""
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    psis = {}
    for plan in ("static", "adaptive"):
        s = SetUnionSampler(wl.cat, wl.joins, cover, seed=5, backend="jax",
                            round_batch=256, fused_rounds="device", plan=plan)
        s.sample(2000)
        psis[plan] = s.stats.psi()
    assert psis["adaptive"] < psis["static"]


def test_record_engine_rejects_adaptive():
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    with pytest.raises(ValueError, match="record"):
        SetUnionSampler(wl.cat, wl.joins, cover, seed=3, backend="jax",
                        membership="record", plan="adaptive")
    with pytest.raises(ValueError, match="plan"):
        SetUnionSampler(wl.cat, wl.joins, cover, seed=3, backend="jax",
                        plan="bogus")


# ---------------------------------------------------------------------------
# uniformity: budgets depend on counts only, so the stream stays 1/|U|
# ---------------------------------------------------------------------------


def _uniform_p(wl, mesh=None, n_per_cell=120, rb=1024):
    cover = _cover(wl)
    U = exact_union_size(wl.cat, wl.joins)
    s = SetUnionSampler(wl.cat, wl.joins, cover, seed=13, backend="jax",
                        round_batch=rb, mesh=mesh, fused_rounds="device",
                        plan="adaptive")
    ss = s.sample(n_per_cell * U)
    return chi2_p(ss.matrix(), U)


def test_adaptive_uniform_uq1_sharded():
    from repro.core.sharding import make_sampler_mesh
    p = _uniform_p(uq1(scale=0.02, overlap=0.5, seed=1, n_joins=2),
                   mesh=make_sampler_mesh(world=1))
    assert p > 1e-3, p


def test_adaptive_uniform_uq4_sharded():
    from repro.core.sharding import make_sampler_mesh
    p = _uniform_p(uq4(scale=0.01, seed=0),
                   mesh=make_sampler_mesh(world=1))
    assert p > 1e-3, p


# ---------------------------------------------------------------------------
# psi accounting + waste gauge
# ---------------------------------------------------------------------------


def test_psi_helper_and_merge():
    st = SamplerStats(candidate_draws=300, samples_emitted=100)
    assert st.psi() == 3.0
    assert SamplerStats().psi() == 0.0
    merged = st.merge(SamplerStats(candidate_draws=100, samples_emitted=100))
    assert merged.psi() == 2.0


def test_waste_gauge_published():
    from repro import obs
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    if not obs.enabled():
        pytest.skip("obs disabled via REPRO_OBS=off")
    s = SetUnionSampler(wl.cat, wl.joins, cover, seed=3, backend="jax",
                        round_batch=256, fused_rounds="device",
                        plan="adaptive")
    s.sample(1000)
    text = obs.get_registry().render()
    # per piece, waste = 1 - accepts_total / draws_total of the exported
    # counters (what a Prometheus query derives)
    for j in s._engine.order:
        draws = re.search(rf'^repro_engine_piece_draws_total{{join="{j}"}}'
                          r" (\S+)$", text, re.M)
        accepts = re.search(
            rf'^repro_engine_piece_accepts_total{{join="{j}"}} (\S+)$',
            text, re.M)
        assert draws and accepts
        assert 0.0 <= 1.0 - float(accepts.group(1)) / float(draws.group(1)) \
            < 1.0
    assert "repro_engine_piece_ema" in text


# ---------------------------------------------------------------------------
# ONLINE-UNION host twin: EMA-batched fresh draws + φ-refresh reseed
# ---------------------------------------------------------------------------


def test_online_adaptive_emits_and_reseeds():
    wl = uq1(scale=0.05, overlap=0.4, seed=0, n_joins=2)
    s = OnlineUnionSampler(wl.cat, wl.joins, seed=3, phi=300, pool_cap=8,
                           plan="adaptive")
    out = s.sample(800)
    assert out.home.shape[0] == 800
    assert s.stats.samples_emitted == 800
    # PiecePlanner seeded once at init and reseeded at every φ-refresh
    assert s.planner is not None
    assert s.planner.refreshes == 1 + s.refresh_count
    with pytest.raises(ValueError):
        OnlineUnionSampler(wl.cat, wl.joins, seed=3, plan="bogus")


def test_piece_planner_batches_track_acceptance():
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    pl = planner.PiecePlanner(cover, {})
    k0 = pl.suggest_batch(1)
    # persistent rejection drives the EMA down and the batch size up
    for _ in range(32):
        pl.observe(1, drawn=k0, accepted=0)
    assert pl.suggest_batch(1) > k0
    # perfect acceptance drives it back toward 1-2 candidates
    for _ in range(64):
        pl.observe(1, drawn=8, accepted=8)
    assert pl.suggest_batch(1) <= 2


# ---------------------------------------------------------------------------
# host-side cost model: deterministic fit + autotune entry point
# ---------------------------------------------------------------------------


def test_plan_cache_fit_and_suggest():
    pc = planner.PlanCache()
    key = "k1"
    # t_round = 1ms + 1us/slot, 2 slots/rb, ~0.9 emitted per rb slot pair
    for rb in (256, 1024, 4096):
        slots = 2 * rb
        t_round = 1e-3 + 1e-6 * slots
        rounds = 50
        pc.observe(key, rb, slots, rounds, seconds=t_round * rounds,
                   samples=int(0.9 * rb * rounds))
    c0, c1 = pc.fit(key)
    assert c0 == pytest.approx(1e-3, rel=0.05)
    assert c1 == pytest.approx(1e-6, rel=0.05)
    plan = pc.suggest(key)
    # per-round overhead amortises with bigger batches: the model picks the
    # largest candidate once c0 dominates, deterministically
    assert plan == pc.suggest(key)
    assert plan.round_batch == 8192
    assert plan.surplus_cap == 8 * plan.round_batch
    assert plan.drain_window == min(plan.round_batch, 256)


def test_plan_cache_min_displaces_compile_polluted_first_call():
    pc = planner.PlanCache()
    pc.observe("k", 256, 512, 10, seconds=5.0, samples=1000)   # compile hit
    pc.observe("k", 256, 512, 10, seconds=0.5, samples=1000)   # warm
    pc.observe("k", 256, 512, 10, seconds=0.9, samples=1000)   # noise
    (o,) = pc._obs["k"].values()
    assert o.seconds == 0.5


def test_round_batch_none_autotunes_from_cache():
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    planner.PLAN_CACHE.reset()
    # cold cache: falls back to the 4096 default
    s = SetUnionSampler(wl.cat, wl.joins, cover, seed=3, backend="jax",
                        round_batch=None)
    assert s.autotuned_plan is None
    assert s._engine.round_batch == 4096
    # a timed sample() feeds the cache under this catalog's fingerprint...
    s.sample(2000)
    key = planner.plan_key(wl.cat, s.joins, cover)
    assert planner.PLAN_CACHE.fit(key) is not None
    # ...so the next round_batch=None build consults the model
    s2 = SetUnionSampler(wl.cat, wl.joins, cover, seed=3, backend="jax",
                         round_batch=None)
    assert s2.autotuned_plan is not None
    assert s2._engine.round_batch == s2.autotuned_plan.round_batch
    planner.PLAN_CACHE.reset()
