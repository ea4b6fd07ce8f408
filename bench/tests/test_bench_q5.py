"""The configuration ``q5_sf1`` (TPC-H Q5's cycle over three variant
sources) on small data (CPU, no accelerator): the reference's counts
against brute-force enumeration of Q5's join, a whole run of its cell
through the harness, the faults and the control the comparison has to
catch, and the readers of the cell's two per-layer metrics.  Scale factors
are those the test union ``q5_cycle`` is tested at."""

import os
import sys

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, harness  # noqa: E402
from bench.control import readings  # noqa: E402
from bench.reference import tree  # noqa: E402
from bench.tests.test_bench_faults import (altered_answer,  # noqa: E402
                                           half_left_out)
from bench.tests.test_bench_reference import (enumerate_join,  # noqa: E402
                                              small)

NAME, CELL = "q5_sf1", "q5_sf1.bulk"
COUNT_SF, RUN_SF, CONTROL_SF = 0.002, 0.002, 0.01


@pytest.fixture(scope="module")
def enumerated():
    cfg, u = small(NAME, COUNT_SF)
    return cfg, u, [enumerate_join(u, j) for j in range(len(u.joins))]


def test_the_join_is_q5s_cycle(enumerated):
    """Every enumerated tuple is a line whose supplier is of its customer's
    nation, in that nation's region: Q5's six join predicates."""
    cfg, u, sets = enumerated
    names = [r.name for r in u.rels]
    assert names == ["region", "nation", "customer", "orders", "lineitem",
                     "supplier"]
    assert len(u.attrs) == cfg["attributes"]["join_row"]
    col = {r.name: r.cols for r in u.rels}
    at = {n: i for i, n in enumerate(names)}
    for t in set.union(*sets):
        row = {n: t[i] for n, i in at.items()}
        nk = col["customer"]["nk"][row["customer"]]
        assert col["supplier"]["nk"][row["supplier"]] == nk
        assert col["nation"]["nk"][row["nation"]] == nk
        assert col["nation"]["rk"][row["nation"]] == \
            col["region"]["rk"][row["region"]]
        assert col["orders"]["ck"][row["orders"]] == \
            col["customer"]["ck"][row["customer"]]
        assert col["lineitem"]["ok"][row["lineitem"]] == \
            col["orders"]["ok"][row["orders"]]
        assert col["lineitem"]["sk"][row["lineitem"]] == \
            col["supplier"]["sk"][row["supplier"]]


def test_counts_match_enumeration(enumerated):
    cfg, u, sets = enumerated
    assert all(sets)
    for s, size in tree.intersection_sizes(u).items():
        assert size == len(set.intersection(*[sets[j] for j in s])), s
    names = [r.name for r in u.rels]
    chk = cfg["check"]
    for rel, bucket in (
            (names.index(chk["marginal_relation"]),
             tree.id_buckets(u, names.index(chk["marginal_relation"]),
                             chk["buckets"])),
            (names.index(chk["position_relation"]),
             tree.position_buckets(u, names.index(chk["position_relation"]),
                                   chk["position_buckets"]))):
        vec = tree.pieces_from(tree.bucket_counts(u, rel, bucket),
                               len(u.joins))
        seen = set()
        for j, v in enumerate(vec):
            mine = sets[j] - seen
            seen |= sets[j]
            want = np.bincount([bucket[t[rel]] for t in mine],
                               minlength=v.shape[0])
            np.testing.assert_array_equal(v, want)


def test_region_and_nation_are_whole_in_every_source():
    cfg, u = small(NAME, COUNT_SF)
    for j in range(len(u.joins)):
        masks = dict(zip([r.name for r in u.rels], u.masks(j)))
        assert masks["region"].all() and masks["nation"].all()
        assert not masks["lineitem"].all()


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (half_left_out, False),
                                           (altered_answer, False)])
def test_run_of_the_cell(bench, fault, correct):
    out, numbers = harness.run_cell(
        ROOT, bench, harness.find_cell(bench, CELL), seed=2 ** 35 + 16,
        seconds=1.0, trace=False, t_start=0.0, chip=False,
        config_override={"scale_factor": RUN_SF, "round_batch": 1024},
        fault=fault)
    assert out["correct"] is correct, numbers


@pytest.fixture(scope="module")
def control_union():
    cfg, mod = harness.load_config(ROOT, NAME)
    cfg = dict(cfg, scale_factor=CONTROL_SF)
    return cfg, mod.build(cfg)


@pytest.mark.parametrize("dtype,correct", [(np.float32, True),
                                           (ml_dtypes.bfloat16, False)])
def test_control(control_union, dtype, correct):
    cfg, u = control_union
    for seed, numbers, _, _ in readings(cfg, u, dtype, 200_000, [3, 4], 100):
        assert check.passed(numbers) == correct, (seed, numbers)


def test_probe_that_misses_the_last_row_of_a_range_is_caught(control_union):
    cfg, u = control_union
    for seed, numbers, _, _ in readings(cfg, u, np.float32, 1_000_000, [5],
                                        100, drop_last=True):
        got = {n: v for n, v, _ in numbers}
        assert got["rows_outside_home"] == 0, numbers
        assert got["position_chi2"] > cfg["limits"]["position_chi2"], numbers


# the readers of the cell's two per-layer metrics, on hand-built contexts

def _loop_hlo(scopes):
    ops = [f'  %fusion.{i} = s32[8]{{0}} fusion(), kind=kLoop, metadata={{'
           f'op_name="jit(loop_fn)/while/body/algo1_fused_round/{scope}/add"}}'
           for i, scope in enumerate(scopes)]
    return "\n".join(["HloModule jit_loop_fn, entry_computation_layout="
                      "{()->s32[]}", "", "ENTRY %main.1 () -> s32[] {"]
                     + ops + ["  ROOT %c.9 = s32[] constant(0)", "}"])


def _phase_ctx():
    from bench.trace_reduce import Reduced
    red = Reduced(window_s=1.0, busy_s=1.0, module_s={"jit_loop_fn": 0.8},
                  module_runs={"jit_loop_fn": 20},
                  op_s={"fusion.0": 0.3, "fusion.1": 0.1, "fusion.2": 0.1},
                  kernel_s=0.0, gaps=[])
    return {"reduced": red, "window": {"rounds": 150.0, "drain_count": 100}}


@pytest.mark.parametrize("scopes,want", [
    (["walk/Q5_J0", "walk/Q5_J0/residual/Q5_J0", "member/Q5_J0"],
     800.0 / 30 * 0.1 / 0.5),
    (["walk/J0", "walk/J0", "member/J0"], None)], ids=["cycle", "chain"])
def test_residual_ms_per_round_reads_the_residual_phase(monkeypatch, scopes,
                                                        want):
    from repro import obs
    from repro.obs import tracing
    monkeypatch.setattr(tracing, "_op_phases", {})
    obs.publish_op_phases(_loop_hlo(scopes))
    got = harness.load_metric(ROOT, "residual_ms_per_round.bulk").read(
        _phase_ctx())
    assert got == (None if want is None else pytest.approx(want))


def test_residual_reject_share_reads_the_registry():
    from repro import obs
    read = harness.load_metric(ROOT, "residual_reject_share.bulk").read
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        assert read({}) is None               # a program without the counter
        draws = reg.counter("repro_engine_piece_draws_total", "", ("join",))
        kills = reg.counter("repro_engine_piece_residual_kills_total", "",
                            ("join",))
        draws.labels(join="A").inc(300)
        draws.labels(join="B").inc(100)
        kills.labels(join="A").inc(270)
        assert read({}) == pytest.approx(270 / 400)
    finally:
        obs.set_registry(prev)
