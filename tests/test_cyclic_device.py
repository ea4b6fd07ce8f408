"""Cyclic joins on the device engine (§8.2 skeleton + residual rejection).

Acceptance bar of the cyclic tentpole: the device engine must run cyclic
joins end-to-end with host-identical uniformity — chi-square against the
exact universe on the UQ4 workload for every engine (the engine × union
matrix, ``test_conformance_*``), the residual d/M
accept/reject decision bit-equal to the host reference on a shared
(injected-uniform) trace, residual-rejection accounting present in
``SamplerStats`` on both engines, and a 1-device mesh reproducing the
unsharded fused engine bit for bit on the cyclic union.
"""

import numpy as np
import pytest

from _conformance import chi2_p
from conftest import brute_force_join, tiny_db

from repro.core.backends import NumpyBackend
from repro.core.backends.jax_backend import DeviceTreeJoin, JaxBackend
from repro.core.framework import estimate_union, warmup
from repro.core.index import Catalog
from repro.core.join_sampler import JoinSampler
from repro.core.joins import JoinNode, JoinSpec, chain_join, full_join
from repro.core.overlap import exact_union_size
from repro.core.relation import Relation, combine_columns
from repro.core.union_sampler import SetUnionSampler
from repro.data.workloads import uq4


def _cyclic_spec(seed=0, n_q=40):
    """R(a,b) ⋈_b S(b,c) skeleton + residual Q(a,c,qid) closing the cycle.

    Q holds duplicate (a, c) pairs with multiplicities in {1, 2, 4}, so the
    residual degree d varies, M = 4, and the d/M thresholds (0.25, 0.5, 1.0)
    are exactly representable in both float32 and float64 — the shared-trace
    test can demand bit-equal accept decisions across engines.
    """
    R, S, T = tiny_db(seed)
    rng = np.random.default_rng(seed + 1)
    a = rng.integers(0, 12, n_q)
    c = rng.integers(0, 12, n_q)
    mult = rng.choice([1, 2, 4], size=n_q, p=[0.5, 0.3, 0.2])
    # enforce M == 4 regardless of the random draw
    mult[0] = 4
    Q = Relation("Q", {"a": np.repeat(a, mult), "c": np.repeat(c, mult),
                       "qid": np.arange(int(mult.sum()))})
    spec = JoinSpec("CYC", [
        JoinNode("R", R, None, ()),
        JoinNode("S", S, "R", ("b",)),
        JoinNode("Q", Q, None, ("a", "c"), kind="residual"),
    ])
    return Catalog(), spec


# ---------------------------------------------------------------------------
# single cyclic join: device draws follow the exact multiplicity law
# ---------------------------------------------------------------------------


def test_device_cyclic_source_distribution():
    cat, spec = _cyclic_spec(0)
    truth = brute_force_join(spec)
    assert truth, "degenerate test spec"
    attrs = spec.output_attrs
    mat = np.asarray([[r[a] for a in attrs] for r in truth], dtype=np.int64)
    be = JaxBackend(cat, [spec], seed=2, device_batch=2048)
    src = be.source(spec.name)
    assert src.tree.has_residual
    rows, draws = src.draw(np.random.default_rng(0), 30_000)
    assert draws > 30_000            # residual rejection costs extra draws
    got = np.stack([rows[a] for a in attrs], axis=1)
    p = chi2_p(got, mat)
    assert p > 1e-3, f"device cyclic sampler distribution off (p={p})"
    assert src.pop_residual_rejects() > 0
    assert src.pop_residual_rejects() == 0        # drained


def test_device_cyclic_source_matches_host_distribution():
    """Same chi-square bar for the host source on the same spec (host
    reference sanity for the device comparison)."""
    cat, spec = _cyclic_spec(0)
    truth = brute_force_join(spec)
    attrs = spec.output_attrs
    mat = np.asarray([[r[a] for a in attrs] for r in truth], dtype=np.int64)
    be = NumpyBackend(cat, [spec])
    rows, _ = be.source(spec.name).draw(np.random.default_rng(1), 30_000)
    got = np.stack([rows[a] for a in attrs], axis=1)
    p = chi2_p(got, mat)
    assert p > 1e-3, f"host cyclic sampler distribution off (p={p})"


# ---------------------------------------------------------------------------
# shared trace: device residual accept/reject == host, bit for bit
# ---------------------------------------------------------------------------


def test_residual_rejection_matches_host_on_shared_trace():
    import jax.numpy as jnp
    cat, spec = _cyclic_spec(3)
    host = JoinSampler(cat, spec, method="ew")
    tree = DeviceTreeJoin(cat, spec)
    (ridx, rcfg), = [(i, c) for i, c in enumerate(tree.node_cfgs)
                     if c.kind == "residual"]
    assert rcfg.max_degree == host.edges["Q"].max_degree == 4

    # one shared trace: skeleton tuples drawn once on the host + one shared
    # uniform vector per decision (float32 so both engines compare the same
    # values against the same exactly-representable d/M thresholds)
    skel = JoinSpec("SKEL", [n for n in spec.nodes if n.kind == "tree"])
    rng = np.random.default_rng(7)
    sb = JoinSampler(cat, skel, method="ew").sample_batch(rng, 4096)
    walk_ok = sb.ok
    u_pick = rng.random(4096, dtype=np.float32)
    u_acc = rng.random(4096, dtype=np.float32)

    # host reference: residual range probe + d/M acceptance
    plan = host.edges["Q"]
    key = combine_columns([sb.rows[a] for a in ("a", "c")])
    lo, hi = plan.index.ranges(key)
    d = hi - lo
    ok_h = walk_ok & (d > 0)
    accept_h = ok_h & (u_acc.astype(np.float64)
                       < d / np.float64(plan.max_degree))

    # device: the same rows + the same uniforms through the traced step
    rows_dev = {a: jnp.asarray(c.astype(np.int32))
                for a, c in sb.rows.items()}
    _, ok_d, ratio = tree._residual_step(
        ridx, rcfg, tree.device_arrays()["nodes"][ridx], rows_dev,
        jnp.asarray(walk_ok),
        jnp.ones(4096, jnp.float32), jnp.asarray(u_pick))
    accept_d = np.asarray(ok_d & (jnp.asarray(u_acc) < ratio))

    assert np.array_equal(np.asarray(ok_d), ok_h)
    assert np.array_equal(accept_d, accept_h)
    # the residual-rejection count — walks alive at every edge but killed by
    # the d/M test — is therefore identical too, and non-trivial
    rej_h = int((ok_h & ~accept_h).sum())
    rej_d = int((np.asarray(ok_d) & ~accept_d).sum())
    assert rej_h == rej_d
    assert 0 < rej_h < int(ok_h.sum())


def test_residual_reject_stats_populated_on_both_engines():
    """SamplerStats.residual_rejects counts the d/M kills on both engines."""
    cat, spec = _cyclic_spec(5)
    wide_cols = full_join(cat, spec)
    wide = Relation("WIDE", {a: c[: max(1, c.shape[0] // 2)]
                             for a, c in wide_cols.items()})
    j2 = chain_join("J2", [wide], [])
    joins = [spec, j2]
    est = estimate_union(warmup(cat, joins, method="exact").oracle)
    for backend in ("numpy", "jax"):
        s = SetUnionSampler(cat, joins, est.cover, seed=11, backend=backend,
                            round_batch=1024)
        ss = s.sample(1500)
        assert len(ss) == 1500
        assert ss.stats.residual_rejects > 0, backend
        assert ss.stats.as_dict()["residual_rejects"] == \
            ss.stats.residual_rejects


# ---------------------------------------------------------------------------
# residual kills: skeleton walks turned away at a residual edge, per piece
# ---------------------------------------------------------------------------


def _unique_residual_spec(seed=0, n_q=60):
    """The cycle of :func:`_cyclic_spec` with Q unique on ``(a, c)``: M = 1,
    so a walk the residual turns away is a probe with no match (d = 0),
    never a d/M rejection."""
    R, S, _ = tiny_db(seed)
    pairs = np.random.default_rng(seed + 1).choice(144, size=n_q,
                                                   replace=False)
    Q = Relation("Q", {"a": pairs // 12, "c": pairs % 12,
                       "qid": np.arange(n_q)})
    spec = JoinSpec("CYC1", [
        JoinNode("R", R, None, ()),
        JoinNode("S", S, "R", ("b",)),
        JoinNode("Q", Q, None, ("a", "c"), kind="residual"),
    ])
    return Catalog(), spec


def _piece_stats(cat, spec, mode, n=2000):
    """One-piece engine (no cover rejects) in ``fused_rounds`` ``mode``:
    its per-piece counters and the registry's residual-kill series."""
    from repro import obs
    from repro.core.backends import get_backend
    from repro.core.backends.jax_backend import JaxUnionSampler
    cover = estimate_union(warmup(cat, [spec], method="exact").oracle).cover
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    obs.set_enabled(True)
    try:
        eng = JaxUnionSampler(get_backend("jax", cat, [spec], seed=2), cover,
                              seed=13, round_batch=512, fused_rounds=mode)
        assert len(eng.sample(n)) == n
        series = reg.snapshot()["repro_engine_piece_residual_kills_total"][
            "series"]
    finally:
        obs.set_enabled(None)
        obs.set_registry(prev)
    return eng.piece_stats_dict()[spec.name], series.get(
        (("join", spec.name),), 0), eng


@pytest.mark.parametrize("mode", ["device", "host"])
def test_residual_kills_count_the_probes_with_no_match(mode):
    """M = 1: every walk turned away is a dead residual probe.  The skeleton
    walk never dies (exact EW weights), so draws = accepts + kills, and the
    kill rate is the skeleton's share outside the cyclic join."""
    cat, spec = _unique_residual_spec()
    skel = JoinSpec("SKEL", [n for n in spec.nodes if n.kind == "tree"])
    n_skel = next(iter(full_join(cat, skel).values())).shape[0]
    n_join = len(brute_force_join(spec))
    assert 0 < n_join < n_skel
    st, published, eng = _piece_stats(cat, spec, mode)
    assert st["residual_rejects"] == 0 == eng.stats.residual_rejects
    assert st["residual_kills"] == st["draws"] - st["accepts"] > 0
    assert published == st["residual_kills"]
    assert st["residual_kills"] / st["draws"] == pytest.approx(
        1 - n_join / n_skel, abs=0.05)


def test_residual_kills_also_count_the_d_over_m_rejections():
    """M = 4: kills are the d/M rejections plus the dead probes, and the
    device loop and its host twin count them alike."""
    cat, spec = _cyclic_spec(5)
    dev, _, eng = _piece_stats(cat, spec, "device")
    host, _, _ = _piece_stats(cat, spec, "host")
    assert dev == host
    assert 0 < dev["residual_rejects"] == eng.stats.residual_rejects
    assert dev["residual_kills"] == dev["draws"] - dev["accepts"]
    assert dev["residual_kills"] > dev["residual_rejects"]


def test_residual_kills_read_zero_on_an_acyclic_join():
    R, S, T = tiny_db(0)
    spec = chain_join("RST", [R, S, T], ["b", "c"])
    st, published, _ = _piece_stats(Catalog(), spec, "device")
    assert st["residual_kills"] == 0 == published
    assert st["draws"] > 0


# ---------------------------------------------------------------------------
# UQ4 end-to-end: 1-device mesh bit-for-bit (UQ4's uniformity on every
# engine is the engine × union matrix, ``test_conformance_*``)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def uq4_setup():
    wl = uq4(scale=0.02, seed=0)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    U = exact_union_size(wl.cat, wl.joins)
    return wl, est, U


def test_uq4_one_shard_mesh_bitwise_equals_jax_engine(uq4_setup):
    from repro.core.sharding import make_sampler_mesh
    wl, est, U = uq4_setup
    plain = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=9,
                            backend="jax", round_batch=1024)
    sharded = SetUnionSampler(wl.cat, wl.joins, est.cover, seed=9,
                              backend="jax", round_batch=1024,
                              mesh=make_sampler_mesh(world=1))
    a, b = plain.sample(3000), sharded.sample(3000)
    for attr in a.attrs:
        assert np.array_equal(a.rows[attr], b.rows[attr]), attr
    assert np.array_equal(a.home, b.home)
    assert np.array_equal(a.fingerprint, b.fingerprint)
    assert a.stats.as_dict() == b.stats.as_dict()
    assert np.array_equal(plain._engine.piece_stats,
                          sharded._engine.piece_stats)


def test_uq4_online_refines_on_device(uq4_setup):
    from repro.core.online import OnlineUnionSampler
    wl, est, U = uq4_setup
    ou = OnlineUnionSampler(wl.cat, wl.joins, seed=5, phi=256, rw_batch=64,
                            backend="jax")
    ss = ou.sample(150)
    assert len(ss) == 150
    # φ-refinement observed the cyclic member (wander-join walks hop the
    # residual edge) — its size accumulator has walks
    assert ou.estimator.size_stats["UQ4_CYC"].count > 0


# ---------------------------------------------------------------------------
# residual first, payload later: a cyclic piece narrows to the walks its
# residual accepts before the payload gathers, membership and compaction
# ---------------------------------------------------------------------------


def test_key_walk_picks_the_rows_of_the_full_draw():
    """``walk_keys`` then ``gather_rows`` on every walk gives the rows and
    masks of ``draw`` on the same key, bit for bit."""
    import jax
    cat, spec = _cyclic_spec(3)
    tree = DeviceTreeJoin(cat, spec)
    arrays = tree.device_arrays()
    key = jax.random.PRNGKey(17)
    rows, acc, walk_ok, skel_ok = tree.draw(key, 1024, arrays,
                                            skeleton=True)
    idx, acc2, walk_ok2, skel_ok2 = tree.walk_keys(key, 1024, arrays)
    assert idx.shape == (1024, len(tree.node_cfgs) + 1)
    for a, b in ((acc, acc2), (walk_ok, walk_ok2), (skel_ok, skel_ok2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    got = tree.gather_rows(idx, arrays)
    assert set(got) == set(rows)
    for a in rows:
        assert np.array_equal(np.asarray(got[a]), np.asarray(rows[a])), a
    assert 0 < int(np.asarray(acc).sum()) < 1024


def test_survivor_width_follows_the_survival_share():
    from repro.core.backends.jax_backend import survivor_width
    # Q5 at SF1: about 2.45 % of 5,248 walks survive, mean 129, sigma 11
    assert survivor_width(5248, 0.0245) == 256
    assert survivor_width(3200, 0.0245) == 256
    assert survivor_width(512, 0.0) == 128
    assert survivor_width(512, None) == 512          # unknown share
    assert survivor_width(512, 0.5) == 512           # nothing to gain
    assert survivor_width(256, 0.3) == 256           # capped at the batch


@pytest.fixture(scope="module")
def q5_cycle():
    """The test union ``q5_cycle`` (TPC-H Q5's cycle over three sources),
    the program's joins and exact cover over it, and its union as the set
    of row-id tuples of the reference."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import system
    from bench.reference import tree
    from bench.tests.test_bench_reference import enumerate_join, small
    _cfg, u = small("q5_cycle", 0.002)
    cover = system.build_sampler(u, tree.intersection_sizes(u), seed=0,
                                 round_batch=1024).cover
    union = set().union(*[enumerate_join(u, j) for j in range(len(u.joins))])
    return u, system.program_joins(u), cover, union


def _q5_engine(q5, mode="device", seed=21, round_batch=1024, **kw):
    from repro.core.backends import get_backend
    from repro.core.backends.jax_backend import JaxUnionSampler
    _u, specs, cover, _ = q5
    return JaxUnionSampler(get_backend("jax", Catalog(), specs, seed=2),
                           cover, seed=seed, round_batch=round_batch,
                           fused_rounds=mode, **kw)


def _q5_uniform_p(q5, ss):
    """Every sampled row is a tuple of the union at its home piece (no
    earlier piece holds it); p-value of chi-square over the union's tuples."""
    from bench.reference import tree
    u, _, _, union = q5
    member = tree.Membership(u)
    found, ids = member.row_ids(ss.rows)
    assert found.all(), "sampled a row that is no tuple of the relations"
    inside = member.matrix(found, ids)
    home = np.asarray(ss.home)
    assert inside[np.arange(home.shape[0]), home].all()
    for j in range(inside.shape[1]):
        assert not inside[home == j][:, :j].any(), "row outside its home"
    tuples = np.stack(ids, axis=1)
    assert set(map(tuple, tuples.tolist())) <= union
    return chi2_p(tuples, len(union))


_COUNTED = ("draws", "residual_rejects", "residual_kills", "accepts")


def test_narrow_path_keeps_the_law_and_the_counts(q5_cycle, monkeypatch):
    """The narrowed loop samples the union uniformly and, with no survivor
    past its width, emits the stream of the full-width loop on the same seed
    with the same draws, kills and rejections."""
    from repro.core.backends import jax_backend
    n = 60 * len(q5_cycle[3])
    narrow = _q5_engine(q5_cycle)
    assert all(s < b for s, b in zip(narrow.survivor_widths,
                                     narrow.piece_batches))
    a = narrow.sample(n)
    p = _q5_uniform_p(q5_cycle, a)
    assert p > 1e-3, f"narrowed device loop not uniform on q5_cycle (p={p})"
    monkeypatch.setattr(jax_backend, "survivor_width", lambda b, share: b)
    full = _q5_engine(q5_cycle)
    assert full.survivor_widths == full.piece_batches
    b = full.sample(n)
    got, want = narrow.piece_stats_dict(), full.piece_stats_dict()
    for name in got:
        assert got[name]["residual_overflow"] == 0
        for f in _COUNTED:
            assert got[name][f] == want[name][f], (name, f)
        assert got[name]["residual_kills"] > 0
    assert a.stats.as_dict() == b.stats.as_dict()
    assert np.array_equal(a.matrix(), b.matrix())
    assert np.array_equal(a.home, b.home)


def test_narrow_path_device_loop_equals_its_host_twin(q5_cycle):
    dev = _q5_engine(q5_cycle, "device", seed=5)
    host = _q5_engine(q5_cycle, "host", seed=5)
    assert all(s < b for s, b in zip(dev.survivor_widths, dev.piece_batches))
    for n in (3000, 1500):
        a, b = dev.sample(n), host.sample(n)
        assert np.array_equal(a.matrix(), b.matrix())
        assert np.array_equal(a.home, b.home)
    assert a.stats.as_dict() == b.stats.as_dict()
    assert np.array_equal(dev.piece_stats, host.piece_stats)


def test_overflow_keeps_the_law_and_is_counted(q5_cycle, monkeypatch):
    """A survivor width of 1 drops survivors every round: the stream stays
    uniform, every call completes, and the registry counts the drops.  A
    piece whose one kept survivor an earlier piece holds yields nothing
    that round; a round that overflowed does not count toward the
    dead-piece streak, so no live piece is dropped for it."""
    from repro import obs
    from repro.core.backends import jax_backend
    monkeypatch.setattr(jax_backend, "survivor_width", lambda b, share: 1)
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    obs.set_enabled(True)
    try:
        eng = _q5_engine(q5_cycle, round_batch=256)
        assert eng.survivor_widths == (1,) * len(eng.order)
        calls = [eng.sample(1000) for _ in range(4)]
        snap = reg.snapshot()
    finally:
        obs.set_enabled(None)
        obs.set_registry(prev)
    assert [len(c) for c in calls] == [1000] * 4
    from repro.core.union_sampler import SampleSet
    rows = {a: np.concatenate([c.rows[a] for c in calls])
            for a in calls[0].attrs}
    home = np.concatenate([c.home for c in calls])
    ss = SampleSet(list(calls[0].attrs), rows, home, None, eng.stats)
    p = _q5_uniform_p(q5_cycle, ss)
    assert p > 1e-3, f"overflowing device loop not uniform (p={p})"
    overflow = snap["repro_engine_piece_residual_overflow_total"]["series"]
    assert sum(overflow.values()) > 0
    st = eng.piece_stats_dict()
    assert sum(s["residual_overflow"] for s in st.values()) == \
        sum(overflow.values())
    widths = snap["repro_engine_piece_survivor_width"]["series"]
    assert set(widths.values()) == {1}
    # the drops are spent draws: no target is dropped, and they count as
    # neither cover nor residual rejections (the rest of the walks are the
    # cover rejections and the rare skeleton walk with no match)
    assert eng.stats.dropped_slots == 0
    assert 0 < eng.stats.cover_rejects <= sum(
        s["draws"] - s["residual_kills"] - s["residual_overflow"]
        - s["accepts"] for s in st.values())


@pytest.fixture(scope="module")
def q5_cycle_rejecting(q5_cycle):
    """``q5_cycle`` with a §8.3 rejection predicate on every join (lines of
    quantity at most 15, about 30 % of them): the filtered joins, their
    exact cover, and the filtered union."""
    from bench.tests.test_bench_reference import enumerate_join
    from repro.core.cover import build_cover
    from repro.core.koverlap import OverlapOracle
    from repro.core.predicates import Pred, rejection
    u, specs, _, _ = q5_cycle
    at_line = [r.name for r in u.rels].index("lineitem")
    qty = u.rels[at_line].cols["l_quantity"]
    joins = [{t for t in enumerate_join(u, j) if qty[t[at_line]] <= 15}
             for j in range(len(u.joins))]
    specs = [rejection(s, [Pred("l_quantity", "<=", 15)], name=s.name)
             for s in specs]
    at = {s.name: i for i, s in enumerate(specs)}

    def size(d):
        return float(len(set.intersection(*[joins[at[j.name]] for j in d])))

    cover = build_cover(OverlapOracle(size, lambda j: size([j]), specs))
    return u, specs, cover, set().union(*joins)


def test_narrow_path_sizes_its_width_before_the_rejection_predicate(
        q5_cycle, q5_cycle_rejecting, monkeypatch):
    """The §8.3 predicate runs after the survivors are cut, so the width is
    sized from the share the residual accepts of the unfiltered join, not
    of the filtered one the cover counts.  At a width so sized no survivor
    overflows and no slot is dropped: the stream is the full-width loop's
    on the same seed, uniform over the filtered union."""
    from bench.tests.test_bench_reference import enumerate_join
    from repro.core.backends import jax_backend
    u, _, cover, union = q5_cycle_rejecting
    n = 60 * len(union)
    narrow = _q5_engine(q5_cycle_rejecting, round_batch=16384)
    for j, (name, tree) in enumerate(zip(narrow.order, narrow.trees)):
        m = np.prod([c.max_degree for c in tree.node_cfgs
                     if c.kind == "residual"])
        exact = len(enumerate_join(u, j)) / (tree.total_weight * m)
        share = jax_backend.survival_share(tree, cover.join_sizes[name])
        assert share == pytest.approx(exact, rel=0.15), name
    assert all(s < b for s, b in zip(narrow.survivor_widths,
                                     narrow.piece_batches))
    a = narrow.sample(n)
    p = _q5_uniform_p(q5_cycle_rejecting, a)
    assert p > 1e-3, f"narrowed loop not uniform under a predicate (p={p})"
    monkeypatch.setattr(jax_backend, "survivor_width", lambda b, share: b)
    b = _q5_engine(q5_cycle_rejecting, round_batch=16384).sample(n)
    got = narrow.piece_stats_dict()
    assert all(s["residual_overflow"] == 0 for s in got.values()), got
    assert a.stats.dropped_slots == 0 and a.stats.pred_rejects > 0
    assert a.stats.as_dict() == b.stats.as_dict()
    assert np.array_equal(a.matrix(), b.matrix())
    assert np.array_equal(a.home, b.home)


def test_record_mode_draws_and_reports_full_width(q5_cycle):
    """The record engine's round draws full rows, so its survivor widths,
    and the gauge that publishes them, are its draw widths."""
    from repro import obs
    from repro.core.backends import get_backend
    from repro.core.backends.jax_backend import JaxRecordUnionSampler
    _u, specs, cover, _ = q5_cycle
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    obs.set_enabled(True)
    try:
        eng = JaxRecordUnionSampler(get_backend("jax", Catalog(), specs,
                                                seed=2),
                                    cover, seed=4, round_batch=1024)
        assert len(eng.sample(200)) == 200
        snap = reg.snapshot()
    finally:
        obs.set_enabled(None)
        obs.set_registry(prev)
    assert eng.survivor_widths == eng.piece_batches
    widths = snap["repro_engine_piece_survivor_width"]["series"]
    assert sorted(widths.values()) == sorted(eng.piece_batches)
    assert sum(snap["repro_engine_piece_residual_overflow_total"]
               ["series"].values()) == 0


def test_acyclic_pieces_keep_their_width_and_their_stream():
    """Chain pieces are never narrowed, and their stream on a fixed seed is
    the one the loop gave before narrowing existed (SHA-256 of the samples
    and homes, pinned from that program on this CPU path)."""
    import hashlib
    from repro.core.backends import get_backend
    from repro.core.backends.jax_backend import JaxUnionSampler
    R, S, T = tiny_db(0)
    keep = np.arange(R.nrows) % 3 != 0
    R2 = Relation("R", {a: np.concatenate(
        [c[keep], c[~keep] + (100 if a == "rid" else 0)])
        for a, c in R.columns.items()})
    joins = [chain_join("A", [R, S, T], ["b", "c"]),
             chain_join("B", [R2, S, T], ["b", "c"])]
    cat = Catalog()
    cover = estimate_union(warmup(cat, joins, method="exact").oracle).cover
    for mode in ("device", "host"):
        eng = JaxUnionSampler(get_backend("jax", cat, joins, seed=2), cover,
                              seed=31, round_batch=512, fused_rounds=mode)
        assert eng.survivor_widths == eng.piece_batches == (512, 256)
        ss = eng.sample(3000)
        digest = hashlib.sha256(
            np.ascontiguousarray(ss.matrix()).tobytes()
            + ss.home.astype(np.int64).tobytes()).hexdigest()
        assert digest == ("648af2720e5561f25c9a82a7dd0d2da04f350bdb6e6fd0454"
                          "ef9c9f7158e456b"), mode
        assert eng.piece_stats.tolist() == [[5120, 5120, 0, 0, 0, 1113, 2874],
                                            [2560, 827, 0, 0, 0, 0, 73]]
