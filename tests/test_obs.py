"""Engine-wide telemetry (DESIGN.md §10): metrics core, tracing, endpoints.

Pinned here:

* metrics core — registry get-or-create, labeled children, thread-safe
  increments, quantile interpolation, Prometheus text exposition shape,
  and the ``REPRO_OBS`` kill switch;
* :class:`TraceRing` bounded wrap with monotone sequence numbers;
* :class:`MetricsServer` ``/metrics`` + ``/healthz``;
* ``SamplerStats.merge``/``snapshot`` semantics and the serve queue's
  merged accounting under concurrent producers;
* **parity** — the per-piece carry counters ride in the jitted programs
  unconditionally, so device/host streams stay bitwise identical whether
  telemetry or its profiler spans are on or off, and ``piece_stats``
  itself agrees bit for bit;
* spans and phases — every span the program opens is in ``obs.SPANS``,
  the engine's drain splits exactly into its wait and its assembly, the
  serve histograms take one observation per request, and every op of the
  device loop's body sits in one of the loop phases;
* BENCH ``write_json`` appending runs to ``history`` instead of clobbering;
* ONLINE-UNION exposing its refinement history (``refresh_count``,
  ``last_refresh_at``, trace events) instead of discarding it.
"""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core.backends import get_backend
from repro.core.backends.jax_backend import JaxUnionSampler
from repro.core.framework import estimate_union, warmup
from repro.core.union_sampler import SamplerStats, SetUnionSampler
from repro.data.workloads import uq1
from repro.serve.service import SampleService


@pytest.fixture
def registry():
    """Fresh registry installed as the global one for the test's duration."""
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        yield reg
    finally:
        obs.set_registry(prev)


@pytest.fixture
def obs_on():
    obs.set_enabled(True)
    try:
        yield
    finally:
        obs.set_enabled(None)


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_kind_conflicts(registry):
    c1 = registry.counter("t_total", "help one")
    c2 = registry.counter("t_total")
    assert c1 is c2
    with pytest.raises(ValueError):
        registry.gauge("t_total")           # same name, different kind
    with pytest.raises(ValueError):
        registry.counter("bad name!")       # invalid metric name


def test_counter_labels_and_negative_rejection(registry):
    c = registry.counter("req_total", "requests", labelnames=("join",))
    c.labels("a").inc()
    c.labels("a").inc(2)
    c.labels(join="b").inc(5)
    snap = registry.snapshot()["req_total"]["series"]
    assert snap[(("join", "a"),)] == 3
    assert snap[(("join", "b"),)] == 5
    with pytest.raises(ValueError):
        c.labels("a").inc(-1)


def test_gauge_set_function_pull_time(registry):
    g = registry.gauge("depth", "queue depth")
    box = {"v": 7}
    g.set_function(lambda: box["v"])
    assert registry.snapshot()["depth"]["series"][()] == 7
    box["v"] = 3
    assert registry.snapshot()["depth"]["series"][()] == 3


def test_histogram_quantiles_and_exposition(registry):
    h = registry.histogram("lat_seconds", "latency",
                           buckets=(0.001, 0.01, 0.1, 1.0))
    for v in [0.0005] * 50 + [0.05] * 50:
        h.observe(v)
    assert h.quantile(0.25) <= 0.001
    assert 0.01 <= h.quantile(0.99) <= 0.1
    text = registry.render()
    # cumulative buckets, +Inf terminal, _sum/_count present
    buckets = re.findall(r'lat_seconds_bucket{le="([^"]+)"} (\d+)', text)
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts) and buckets[-1][0] == "+Inf"
    assert counts[-1] == 100
    assert re.search(r"^lat_seconds_count 100$", text, re.M)
    assert "# TYPE lat_seconds histogram" in text


def test_thread_safe_increments(registry):
    c = registry.counter("race_total")

    def work():
        for _ in range(10_000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert registry.snapshot()["race_total"]["series"][()] == 80_000


def test_kill_switch_env_and_override(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "off")
    obs.set_enabled(None)
    assert not obs.enabled()
    obs.set_enabled(True)
    assert obs.enabled()
    obs.set_enabled(None)
    monkeypatch.setenv("REPRO_OBS", "on")
    assert obs.enabled()


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------


def test_trace_ring_wrap_and_seq():
    ring = obs.TraceRing(capacity=4)
    for i in range(10):
        ring.append("tick", i=i)
    assert len(ring) == 4 and ring.total == 10
    evs = ring.events()
    assert [e["i"] for e in evs] == [6, 7, 8, 9]
    assert [e["seq"] for e in evs] == [6, 7, 8, 9]
    assert ring.last()["i"] == 9
    assert ring.events("other") == []


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------


def test_metrics_server_endpoints(registry):
    registry.counter("up_total", "ticks").inc(3)
    with obs.MetricsServer(registry, port=0) as srv:
        with urllib.request.urlopen(f"{srv.url}/metrics") as r:
            body = r.read().decode()
            assert r.status == 200
            assert r.headers["Content-Type"] == obs.PROMETHEUS_CONTENT_TYPE
        assert "up_total 3" in body
        with urllib.request.urlopen(f"{srv.url}/healthz") as r:
            assert r.read().decode().strip() == "ok"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope")


# ---------------------------------------------------------------------------
# SamplerStats merge / snapshot
# ---------------------------------------------------------------------------


def test_sampler_stats_merge_and_snapshot():
    a = SamplerStats(iterations=3, candidate_draws=10, cover_rejects=1)
    b = SamplerStats(iterations=2, candidate_draws=5, reuse_accepts=4)
    snap = a.snapshot()
    out = a.merge(b)
    assert out is a                                  # in-place, returns self
    assert a.iterations == 5 and a.candidate_draws == 15
    assert a.cover_rejects == 1 and a.reuse_accepts == 4
    assert snap.iterations == 3                      # snapshot unaffected
    # associativity on a third operand
    c = SamplerStats(iterations=1)
    lhs = SamplerStats().merge(a).merge(c)
    rhs = SamplerStats().merge(c).merge(a)
    assert lhs.as_dict() == rhs.as_dict()


# ---------------------------------------------------------------------------
# engine parity with telemetry on / off + piece_stats consistency
# ---------------------------------------------------------------------------


def _cover(wl):
    return estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle).cover


def _engine(wl, cover, mode, seed=7):
    backend = get_backend("jax", wl.cat, wl.joins, seed=2)
    return JaxUnionSampler(backend, cover, seed=seed, round_batch=512,
                           fused_rounds=mode)


def _assert_same(a, b):
    for attr in a.attrs:
        np.testing.assert_array_equal(a.rows[attr], b.rows[attr])
    np.testing.assert_array_equal(a.home, b.home)
    np.testing.assert_array_equal(a.fingerprint, b.fingerprint)


def test_parity_unchanged_by_telemetry(registry, monkeypatch):
    """Samples are bitwise identical device vs host, obs on vs off, spans
    (``REPRO_OBS_TRACE=1``) on vs off — the per-piece counters are pure
    extra carry outputs, never inputs, and spans and phase scopes are host
    annotations and op metadata."""
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    streams = {}
    for obs_state, trace in ((True, "1"), (True, ""), (False, "")):
        obs.set_enabled(obs_state)
        monkeypatch.setenv("REPRO_OBS_TRACE", trace)
        assert obs.trace_annotations_enabled() == bool(trace)
        try:
            dev, host = _engine(wl, cover, "device"), _engine(wl, cover, "host")
            for n in (700, 333):
                _assert_same(dev.sample(n), host.sample(n))
            assert dev.stats.as_dict() == host.stats.as_dict()
            assert np.array_equal(dev.piece_stats, host.piece_stats)
            streams[obs_state, trace] = dev.sample(200)
        finally:
            obs.set_enabled(None)
    _assert_same(streams[True, "1"], streams[True, ""])
    _assert_same(streams[True, ""], streams[False, ""])
    # the traced engine published its loop's op phases
    assert "walk/" + cover.order[0] in set(
        obs.op_phases("jit_loop_fn").values())


def test_drain_splits_into_wait_and_assembly(registry, obs_on):
    """Per call, the drain observation is the device wait plus the host
    assembly, to rounding."""
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    s = _engine(wl, _cover(wl), "device")

    def sums():
        snap = registry.snapshot()
        return [snap[name]["series"][()] for name in (
            "repro_engine_drain_seconds", "repro_engine_drain_wait_seconds",
            "repro_engine_assemble_seconds")]

    s.sample(500)
    for n in (700, 1500):
        before = sums()
        s.sample(n)
        drain, wait, asm = [{k: a[k] - b[k] for k in ("sum", "count")}
                            for a, b in zip(sums(), before)]
        assert drain["count"] == wait["count"] == asm["count"] == 1
        assert wait["sum"] > 0 and asm["sum"] > 0
        assert wait["sum"] + asm["sum"] == pytest.approx(drain["sum"],
                                                         rel=1e-9)


def test_spans_are_named_in_spans(monkeypatch):
    """Spans are no-ops while REPRO_OBS_TRACE is off; on, a name outside
    ``obs.SPANS`` is refused, so the constant lists every span."""
    monkeypatch.setenv("REPRO_OBS_TRACE", "")
    with obs.span("not/a/span") as sp:      # off: nothing is checked
        sp.set_metadata(x=1)
    monkeypatch.setenv("REPRO_OBS_TRACE", "1")
    obs.set_enabled(True)
    try:
        with obs.span("repro/serve/request", n=3) as sp:
            sp.set_metadata(batches="0|1")
        with pytest.raises(ValueError):
            obs.span("repro/not_listed")
    finally:
        obs.set_enabled(None)
    assert len(set(obs.SPANS)) == len(obs.SPANS)
    assert all(n.startswith("repro/") for n in obs.SPANS)


@pytest.mark.parametrize("op_name,phase", [
    ("jit(loop_fn)/while/body/algo1_fused_round/walk/J1/gather", "walk/J1"),
    ("jit(loop_fn)/while/body/algo1_fused_round/member/J2/jit(searchsorted)"
     "/jit(loop_fn)/while/body/algo1_fused_round/member/J2/lt", "member/J2"),
    ("jit(loop_fn)/while/body/select/jit(_threefry_split)/add", "select"),
    ("jit(loop_fn)/while/body/emit/scatter", "emit"),
    ("jit(loop_fn)/while/body/algo1_fused_round/carry/concatenate", "carry"),
    ("jit(loop_fn)/while/body/add", obs.UNSCOPED),
    ("jit(loop_fn)/while/body/algo1_fused_round/select_n", obs.UNSCOPED),
    ("gather", obs.UNSCOPED),
    # a cyclic piece: its residual step opens inside its walk
    ("jit(loop_fn)/while/body/algo1_fused_round/walk/Q5_J0/gather",
     "walk/Q5_J0"),
    ("jit(loop_fn)/while/body/algo1_fused_round/walk/Q5_J0/residual/Q5_J0/"
     "gather", "residual/Q5_J0"),
    ("jit(loop_fn)/while/body/algo1_fused_round/walk/Q5_J0/residual/Q5_J0/"
     "pallas_call/_searchsorted_i32", "residual/Q5_J0"),
    ("jit(loop_fn)/while/body/algo1_fused_round/member/Q5_J1/residual/"
     "Q5_J1/lt", "member/Q5_J1"),
])
def test_phase_of_name_stacks(op_name, phase):
    assert obs.phase_of(op_name) == phase


def test_hlo_op_phases_and_publication():
    text = "\n".join([
        "HloModule jit_toy, entry_computation_layout={()->s32[]}",
        "",
        "ENTRY %main.1 () -> s32[] {",
        '  %gather.3 = s32[8]{0} gather(), metadata={op_name="jit(toy)/'
        'while/body/algo1_fused_round/walk/UQ2_JN/gather" '
        'stack_frame_id=2}',
        "  %constant.1 = s32[] constant(0)",
        '  ROOT %fusion.7 = s32[] fusion(), kind=kLoop, metadata={'
        'op_name="jit(toy)/while/body/emit/add"}',
        "}"])
    phases = obs.hlo_op_phases(text)
    assert phases == {"gather.3": "walk/UQ2_JN", "constant.1": obs.UNSCOPED,
                      "fusion.7": "emit"}
    obs.publish_op_phases(text)
    assert obs.op_phases("jit_toy") == phases
    assert obs.op_phases("jit_other") == {}
    with pytest.raises(ValueError):
        obs.publish_op_phases("not hlo")


@pytest.mark.parametrize("workload", ["uq1", "uq2", "uq4"])
def test_every_loop_body_op_sits_in_a_phase(workload):
    """Lower the device loop of a small UQ1, UQ2 and cyclic UQ4 union: every
    op of the ``while`` body carries one of the phase scopes in its
    ``op_name`` metadata, and a cyclic piece's residual step has its own."""
    import jax
    import jax.numpy as jnp
    from repro.data.workloads import uq2, uq4
    wl = {"uq1": lambda: uq1(scale=0.1, seed=0, n_joins=2),
          "uq2": lambda: uq2(scale=0.05, seed=0),
          "uq4": lambda: uq4(scale=0.02, seed=0)}[workload]()
    eng = _engine(wl, _cover(wl), "device")
    eng._ensure_device_inputs()
    C = 1024
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (eng._init_state(), eng._out_buffer(C), jnp.int32(C),
         eng._probs_base, eng._catalog_args()))
    # the program as lowered, before XLA's passes add ops of their own
    hlo = eng._build_loop(C).lower(*shapes).compiler_ir(
        "hlo").as_hlo_module().to_string()
    body_ops = re.findall(
        r"^\s*(?:ROOT )?%?[\w.-]+ = .*? ([a-z][\w-]*)\(.*?"
        r'op_name="(jit\(loop_fn\)/while/body/[^"]*)"', hlo, re.M)
    assert len(body_ops) > 100
    bare = [(op, name) for op, name in body_ops
            if obs.phase_of(name) == obs.UNSCOPED]
    assert not bare, bare[:10]
    phases = {obs.phase_of(name) for _, name in body_ops}
    for j, tree in zip(eng.order, eng.trees):
        assert {f"walk/{j}", f"compact/{j}"} <= phases
        assert (f"residual/{j}" in phases) == tree.has_residual
    assert {"select", "emit", "carry", f"member/{eng.order[-1]}"} <= phases
    assert any(t.has_residual for t in eng.trees) == (workload == "uq4")


def test_piece_stats_consistency(registry, obs_on):
    """Per-piece draws tie out to the scalar candidate_draws counter, and
    the registry's per-join series mirror piece_stats."""
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    s = _engine(wl, _cover(wl), "device")
    s.sample(800)
    d = s.piece_stats_dict()
    assert sum(v["draws"] for v in d.values()) == s.stats.candidate_draws
    assert all(v["draws"] > 0 for v in d.values())
    assert all(v["accepts"] <= v["draws"] for v in d.values())
    series = registry.snapshot()["repro_engine_piece_draws_total"]["series"]
    for name, v in d.items():
        assert series[(("join", name),)] == v["draws"]


# ---------------------------------------------------------------------------
# serve: merged accounting under concurrent requesters + request metrics
# ---------------------------------------------------------------------------


def test_serve_concurrent_accounting_and_metrics(registry, obs_on):
    wl = uq1(scale=0.02, overlap=0.5, seed=1, n_joins=2)
    cover = _cover(wl)
    s = SetUnionSampler(wl.cat, wl.joins, cover, seed=13, backend="jax",
                        round_batch=1024, fused_rounds="device")
    assert callable(getattr(s, "sample_async", None))
    got, errs = [], []

    def worker(n):
        try:
            got.append(len(svc.request(n)))
        except Exception as e:          # pragma: no cover - diagnostic
            errs.append(e)

    with SampleService(s, batch=1024, prefetch=2) as svc:
        ts = [threading.Thread(target=worker, args=(n,))
              for n in (300, 700, 450, 1100)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        st = svc.stats()
        assert not errs and sorted(got) == [300, 450, 700, 1100]
        assert svc.served == 2550
        # merged accounting equals the single engine's own counters
        assert st.as_dict() == s.stats.as_dict()
    # after stop() the producers are quiesced and the final collector
    # refresh has run — gauges now agree with the engine's settled counters
    snap = registry.snapshot()
    assert snap["repro_serve_requests_total"]["series"][()] == 4
    assert snap["repro_serve_samples_total"]["series"][()] == 2550
    lat = snap["repro_serve_request_seconds"]["series"][()]
    assert lat["count"] == 4 and lat["sum"] > 0
    # the median, as a Prometheus client derives it from the _bucket series
    assert registry.get("repro_serve_request_seconds").quantile(0.5) > 0
    # one queue-wait and one assembly observation per request, each a part
    # of the request's latency
    for part in ("repro_serve_queue_wait_seconds",
                 "repro_serve_assemble_seconds"):
        h = snap[part]["series"][()]
        assert h["count"] == 4 and 0 <= h["sum"] <= lat["sum"]
    # engine stat gauges carry the replica label
    eng = snap["repro_serve_engine_stat"]["series"]
    assert eng[(("replica", "0"), ("field", "candidate_draws"))] \
        == s.stats.candidate_draws


def test_serve_respects_kill_switch(registry):
    obs.set_enabled(False)
    try:
        wl = uq1(scale=0.02, overlap=0.5, seed=1, n_joins=2)
        s = SetUnionSampler(wl.cat, wl.joins, _cover(wl), seed=13,
                            backend="jax", round_batch=1024,
                            fused_rounds="device")
        with SampleService(s, batch=1024, prefetch=1) as svc:
            assert len(svc.request(500)) == 500
        assert "repro_serve_requests_total" not in registry.snapshot()
    finally:
        obs.set_enabled(None)


# ---------------------------------------------------------------------------
# BENCH history append
# ---------------------------------------------------------------------------


def test_write_json_appends_history(tmp_path):
    from benchmarks.common import write_json
    path = str(tmp_path / "BENCH_x.json")
    write_json(path, records=[{"name": "r1", "samples_per_s": 100.0}])
    write_json(path, records=[{"name": "r1", "samples_per_s": 120.0}])
    d = json.loads((tmp_path / "BENCH_x.json").read_text())
    assert [r["samples_per_s"] for r in d["records"]] == [120.0]
    assert len(d["history"]) == 2
    assert [h["records"][0]["samples_per_s"] for h in d["history"]] \
        == [100.0, 120.0]
    assert all(h["git_sha"] for h in d["history"])
    assert d["history"][-1]["ts"]


def test_write_json_migrates_legacy_clobber_files(tmp_path):
    from benchmarks.common import write_json
    path = tmp_path / "BENCH_legacy.json"
    path.write_text(json.dumps(
        {"meta": {"git_sha": "old"},
         "records": [{"name": "r1", "samples_per_s": 50.0}]}))
    write_json(str(path), records=[{"name": "r1", "samples_per_s": 70.0}])
    d = json.loads(path.read_text())
    assert len(d["history"]) == 2
    assert d["history"][0]["git_sha"] == "old"
    assert d["history"][0]["records"][0]["samples_per_s"] == 50.0


# ---------------------------------------------------------------------------
# ONLINE-UNION refinement history
# ---------------------------------------------------------------------------


def test_online_exposes_refinement_history(registry, obs_on):
    from repro.core.online import OnlineUnionSampler
    wl = uq1(scale=0.02, overlap=0.5, seed=0, n_joins=2)
    s = OnlineUnionSampler(wl.cat, wl.joins, seed=3, phi=5)
    assert s.refresh_count == 0 and s.last_refresh_at == -1
    assert s.trace.last("init")["union_size"] > 0
    s.sample(600)
    assert s.refresh_count >= 1
    assert 0 < s.last_refresh_at <= s.stats.iterations
    assert s.backtrack_count == s.stats.backtrack_removed
    ev = s.trace.last("refresh")
    assert ev["at_iteration"] == s.last_refresh_at
    assert set(ev["hist_gap"]) == set(s.names)
    assert isinstance(ev["confident"], bool) and ev["kept"] >= 0
    snap = registry.snapshot()
    assert snap["repro_online_refreshes_total"]["series"][()] \
        == s.refresh_count
    assert snap["repro_online_union_size"]["series"][()] > 0
