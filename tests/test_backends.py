"""Backend layer: device engine == host engine (distribution + membership).

Covers the acceptance criteria of the backend refactor: the jax backend's
candidate sources, membership oracle, and fused Algorithm-1 rounds must be
distributionally equivalent to the numpy reference on TPC-H-style union
workloads (chains, high-overlap predicate unions, and a branching tree).
The fused rounds' uniformity on every union is the engine × union matrix
(``test_conformance_*``).
"""

import numpy as np
import pytest

from _conformance import chi2_p
from conftest import tiny_db

from repro.core.backends import NumpyBackend, get_backend
from repro.core.backends.base import Backend, CandidateSource, MembershipOracle
from repro.core.backends.jax_backend import (DeviceJoinMembership,
                                             DeviceTreeJoin, JaxBackend,
                                             fp32_np)
from repro.core.framework import estimate_union, warmup
from repro.core.index import Catalog
from repro.core.joins import JoinNode, JoinSpec, chain_join, full_join_matrix
from repro.core.union_sampler import SetUnionSampler
from repro.data.workloads import uq1, uq3, uq4


def _tree_spec(seed=0):
    """Branching (non-chain) acyclic join over the tiny DB."""
    R, S, T = tiny_db(seed)
    S = S.rename({"c": "cs"})
    T = T.rename({"c": "ct", "d": "b"})     # T joins the root on b as well
    return Catalog(), JoinSpec("tree", [
        JoinNode("R", R, None, ()),
        JoinNode("S", S, "R", ("b",)),
        JoinNode("T", T, "R", ("b",)),
    ])


# ---------------------------------------------------------------------------
# protocols / factory
# ---------------------------------------------------------------------------


def test_backend_factory_and_protocols():
    cat, spec = _tree_spec(0)
    for name in ("numpy", "jax"):
        be = get_backend(name, cat, [spec], seed=0)
        assert isinstance(be, Backend)
        assert isinstance(be.source(spec.name), CandidateSource)
        assert isinstance(be.oracle(), MembershipOracle)
    # passing an instance through is the identity
    be = NumpyBackend(cat, [spec])
    assert get_backend(be, cat, [spec]) is be
    with pytest.raises(ValueError):
        get_backend("torch", cat, [spec])


# ---------------------------------------------------------------------------
# candidate source: device tree draws match the exact multiplicity law
# ---------------------------------------------------------------------------


def test_jax_tree_source_distribution():
    cat, spec = _tree_spec(1)
    mat = full_join_matrix(cat, spec)
    be = JaxBackend(cat, [spec], seed=2, device_batch=2048)
    src = be.source(spec.name)
    assert not src.is_empty()
    rows, draws = src.draw(np.random.default_rng(0), 40_000)
    assert draws >= 40_000
    got = np.stack([rows[a] for a in spec.output_attrs], axis=1)
    p = chi2_p(got, mat)
    assert p > 1e-3, f"device tree sampler distribution off (p={p})"


def test_jax_tree_total_weight_matches_host():
    from repro.core.join_sampler import JoinSampler
    cat, spec = _tree_spec(2)
    tree = DeviceTreeJoin(cat, spec)
    host = JoinSampler(cat, spec, method="ew")
    assert tree.total_weight == pytest.approx(host.exact_acyclic_size())


def test_pallas_probe_path_matches_jnp():
    """use_pallas routes range probes through the kernels; same draws."""
    import jax
    cat, spec = _tree_spec(3)
    t_jnp = DeviceTreeJoin(cat, spec, use_pallas=False)
    t_pal = DeviceTreeJoin(cat, spec, use_pallas=True)
    key = jax.random.PRNGKey(0)
    r1, ok1, wok1 = jax.jit(lambda k: t_jnp.draw(k, 256))(key)
    r2, ok2, wok2 = jax.jit(lambda k: t_pal.draw(k, 256))(key)
    assert np.array_equal(np.asarray(ok1), np.asarray(ok2))
    assert np.array_equal(np.asarray(wok1), np.asarray(wok2))
    for a in spec.output_attrs:
        assert np.array_equal(np.asarray(r1[a]), np.asarray(r2[a])), a


def _two_level_chain():
    """A -a- B -b- C: B's index is on the two-level fence search (at least
    three top fences), A's and C's hold one fence chunk."""
    from repro.core.relation import Relation
    from repro.kernels.searchsorted import (FENCE_CHUNK, KEY_BLOCK,
                                            TWO_LEVEL_MIN_CHUNKS)
    rng = np.random.default_rng(3)
    n_b = max(3, TWO_LEVEL_MIN_CHUNKS) * KEY_BLOCK * FENCE_CHUNK + 1_000
    A = Relation("A", {"a": np.arange(600), "x": rng.integers(0, 9, 600)})
    B = Relation("B", {"a": rng.integers(0, 600, n_b),
                       "b": rng.integers(0, 40, n_b)})
    C = Relation("C", {"b": rng.integers(0, 40, 300),
                       "y": rng.integers(0, 5, 300)})
    return Catalog(), chain_join("two_level", [A, B, C], ["a", "b"])


def test_pallas_two_level_probe_path_matches_jnp():
    """An index on the two-level fence search draws as jnp.searchsorted."""
    import jax
    cat, spec = _two_level_chain()
    t_jnp = DeviceTreeJoin(cat, spec, use_pallas=False)
    t_pal = DeviceTreeJoin(cat, spec, use_pallas=True)
    assert [p.levels for p in t_pal._prepped] == [2, 1]
    key = jax.random.PRNGKey(7)
    r1, ok1, _ = jax.jit(lambda k: t_jnp.draw(k, 512))(key)
    r2, ok2, _ = jax.jit(lambda k: t_pal.draw(k, 512))(key)
    assert np.array_equal(np.asarray(ok1), np.asarray(ok2))
    for a in spec.output_attrs:
        assert np.array_equal(np.asarray(r1[a]), np.asarray(r2[a])), a


def test_probe_levels_gauge():
    """repro_engine_probe_levels: 2 for B's index, 1 for C's."""
    from repro import obs
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        cat, spec = _two_level_chain()
        DeviceTreeJoin(cat, spec, use_pallas=True)
        series = reg.snapshot()["repro_engine_probe_levels"]["series"]
    finally:
        obs.set_registry(prev)
    assert series == {(("join", "two_level"), ("node", "B")): 2,
                      (("join", "two_level"), ("node", "C")): 1}


# ---------------------------------------------------------------------------
# membership oracle: device == host, bit for bit
# ---------------------------------------------------------------------------


def test_membership_oracle_matches_host():
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    host = NumpyBackend(wl.cat, wl.joins).oracle()
    dev = JaxBackend(wl.cat, wl.joins).oracle()
    # probe a mix of real union tuples and perturbed non-members
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    s = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3)
    ss = s.sample(500)
    rows = dict(ss.rows)
    names = [j.name for j in wl.joins]
    m_host = host.membership_matrix(rows, names)
    m_dev = dev.membership_matrix(rows, names)
    assert m_host.any(axis=1).all()          # union samples are members
    assert np.array_equal(m_host, m_dev)
    bad = {a: c + 1009 for a, c in rows.items()}
    assert np.array_equal(host.membership_matrix(bad, names),
                          dev.membership_matrix(bad, names))


def test_device_membership_fp_duplicate_window():
    """kmax duplicate handling: colliding fp1 values still verify via fp2."""
    from repro.core.relation import Relation
    rng = np.random.default_rng(0)
    rel = Relation("R", {"a": rng.integers(0, 4, 500),
                         "b": rng.integers(0, 4, 500)})
    spec = chain_join("J", [rel], [])
    dm = DeviceJoinMembership(spec)
    attrs = tuple(sorted(rel.attrs))
    fp1 = fp32_np([rel.columns[a] for a in attrs], salt=1)
    # 500 rows over 16 value pairs: fp1 duplicates guaranteed
    assert dm.rels[0][3] >= 2
    import jax, jax.numpy as jnp
    rows = {a: jnp.asarray(rel.columns[a].astype(np.int32)) for a in rel.attrs}
    assert np.asarray(jax.jit(dm.contains)(rows)).all()


# ---------------------------------------------------------------------------
# fused Algorithm-1 rounds: jax == numpy distribution on union workloads
# ---------------------------------------------------------------------------


def test_set_union_jax_matches_numpy_home_marginal():
    wl = uq1(scale=0.05, overlap=0.5, seed=1, n_joins=2)
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    a = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3).sample(8000)
    b = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=3, backend="jax",
                        round_batch=1024).sample(8000)
    fa = np.bincount(a.home, minlength=2) / len(a)
    fb = np.bincount(b.home, minlength=2) / len(b)
    assert np.abs(fa - fb).max() < 0.03


# ---------------------------------------------------------------------------
# validation / fallbacks
# ---------------------------------------------------------------------------


def test_jax_backend_rejects_unsupported_modes():
    """Mode gates: predicates and membership="record" now run fused; only
    strict_paper_loop (and non-lowerable predicates) stay on the host."""
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    wr = warmup(wl.cat, wl.joins, method="exact")
    est = estimate_union(wr.oracle)
    from repro.core.backends.jax_backend import (JaxRecordUnionSampler,
                                                 JaxUnionSampler)
    from repro.core.predicates import Pred, RejectingPredicate
    s = SetUnionSampler(wl.cat, wl.joins, est.cover, membership="record",
                        backend="jax")
    assert isinstance(s._engine, JaxRecordUnionSampler)
    s = SetUnionSampler(wl.cat, wl.joins, est.cover, backend="jax",
                        predicate=RejectingPredicate([Pred("odate", "<=", 1)]))
    assert isinstance(s._engine, JaxUnionSampler)
    # a predicate outside the int32 comparison domain degrades to the host
    # Algorithm-1 loop (no error) ...
    s = SetUnionSampler(wl.cat, wl.joins, est.cover, backend="jax",
                        predicate=RejectingPredicate(
                            [Pred("odate", "<=", 2 ** 40)]))
    assert s._engine is None
    # ... and strict_paper_loop remains the host-only ablation
    s = SetUnionSampler(wl.cat, wl.joins, est.cover, strict_paper_loop=True,
                        backend="jax")
    assert s._engine is None
    with pytest.raises(ValueError, match="ew"):
        JaxBackend(wl.cat, wl.joins, join_method="eo")


def test_jax_backend_runs_cyclic():
    """Cyclic joins build and draw on device (§8.2 skeleton+residual)."""
    wl = uq4(scale=0.02, seed=0)
    be = JaxBackend(wl.cat, wl.joins)
    assert be.supports_fused_rounds() and not be.degraded
    src = be.source("UQ4_CYC")
    assert src.tree.has_residual and not src.is_empty()
    rows, draws = src.draw(np.random.default_rng(0), 500)
    assert draws >= 500
    # every drawn tuple is a member of the cyclic join (host 128-bit oracle)
    host = NumpyBackend(wl.cat, wl.joins).oracle()
    assert host.contains("UQ4_CYC", rows).all()
    # device membership matrix equals the host's on cyclic joins too
    dev = be.oracle()
    names = [j.name for j in wl.joins]
    assert np.array_equal(host.membership_matrix(rows, names),
                          dev.membership_matrix(rows, names))


def test_mixed_union_degrades_per_join():
    """A union where ONE join trips a device limit degrades that join to the
    host source (one warning) instead of raising for the whole union."""
    from repro.core.relation import Relation
    rng = np.random.default_rng(0)
    big = 1 << 31                            # outside the int32 device domain
    R1 = Relation("R1", {"a": rng.integers(0, 8, 50),
                         "b": rng.integers(0, 8, 50)})
    R2 = Relation("R2", {"a": np.concatenate([rng.integers(0, 8, 49),
                                              np.asarray([big])]),
                         "b": rng.integers(0, 8, 50)})
    j_ok = chain_join("J_OK", [R1], [])
    j_bad = chain_join("J_BAD", [R2], [])
    cat = Catalog()
    with pytest.warns(UserWarning, match="fall back to host"):
        be = JaxBackend(cat, [j_ok, j_bad])
    assert not be.supports_fused_rounds()
    assert set(be.degraded) == {"J_BAD"}
    assert "J_OK" in be.trees                # device-eligible join stays on it
    # both sources still draw; the sampler runs on the host loop
    from repro.core.cover import Cover
    cover = Cover(["J_OK", "J_BAD"], {"J_OK": 50.0, "J_BAD": 50.0},
                  {"J_OK": 50.0, "J_BAD": 50.0})
    with pytest.warns(UserWarning, match="host oracle"):
        s = SetUnionSampler(cat, [j_ok, j_bad], cover, seed=3, backend=be)
        ss = s.sample(300)
    assert len(ss) == 300
    assert s._engine is None                 # fused rounds disabled


def test_online_union_jax_backend_smoke():
    from repro.core.online import OnlineUnionSampler
    wl = uq3(scale=0.01, overlap=0.3, seed=0)
    ou = OnlineUnionSampler(wl.cat, wl.joins, seed=5, phi=512, rw_batch=128,
                            backend="jax")
    ss = ou.sample(200)
    assert len(ss) == 200
