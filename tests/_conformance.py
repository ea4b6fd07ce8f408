"""The engine × union conformance matrix, and the one chi-square helper.

A deployment is supported when every engine samples its union uniformly and
credits every row to a piece whose join holds it.  This module holds what the
``test_conformance_*`` files share: the small unions (exact enumeration
is cheap), the engines (each built through its public entry point), a draw
cache so each cell builds and draws once per process, and :func:`chi2_p`,
which every chi-square test of the suite uses.

A cell is one (engine, union) pair.  Each draws ``DRAW_PER_TUPLE · |U|``
rows once; the membership test and the uniformity test both read that draw.
A combination the program refuses by design has no engine here; the comment
above :data:`ENGINES` names the check that raises.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence, Tuple, Union

import numpy as np
import pytest
from scipy import stats as sps

from repro.core.framework import estimate_union, warmup
from repro.core.joins import full_join_matrix
from repro.data.workloads import uq1, uq2, uq3, uq4

DRAW_PER_TUPLE = 120        # rows drawn per union tuple
ONLINE_PER_TUPLE = 20       # the same, for the membership-only engines
P_MIN = 1e-3                # the uniformity test's limit on the p-value
SEED = 7
ROUND_BATCH = 2048


def chi2_p(sample: np.ndarray, law: Union[int, np.ndarray]) -> float:
    """p-value of Pearson's chi-square of the rows of ``sample`` (one tuple
    a row) against ``law``.

    ``law`` is either an int n, the uniform law over n tuples (a tuple never
    sampled counts as 0), or a matrix whose rows, repeats included, are the
    multiset the sample should follow; a sampled tuple outside that matrix
    fails the assertion."""
    s_uni, s_counts = np.unique(sample, axis=0, return_counts=True)
    n = sample.shape[0]
    if isinstance(law, (int, np.integer)):
        assert s_counts.shape[0] <= law, "more distinct tuples than the law"
        exp = np.full(int(law), n / int(law))
        obs = np.zeros(int(law))
        obs[:s_counts.shape[0]] = s_counts
    else:
        uni, exp_counts = np.unique(law, axis=0, return_counts=True)
        at = {tuple(t): i for i, t in enumerate(uni.tolist())}
        where = [at.get(tuple(t)) for t in s_uni.tolist()]
        assert None not in where, "sampled a tuple outside the law"
        obs = np.zeros(uni.shape[0])
        obs[where] = s_counts
        exp = n * exp_counts / exp_counts.sum()
    chi2 = float(((obs - exp) ** 2 / exp).sum())
    return float(sps.chi2.sf(chi2, df=exp.shape[0] - 1))


# ---------------------------------------------------------------------------
# unions
# ---------------------------------------------------------------------------


UNIONS: Dict[str, Tuple[Callable, dict]] = {
    "uq1-2": (uq1, dict(scale=0.05, overlap=0.5, seed=1, n_joins=2)),
    "uq1-5": (uq1, dict(scale=0.02, overlap=0.5, seed=1, n_joins=5)),
    "uq2-pushdown": (uq2, dict(scale=0.02, seed=0)),
    "uq2-rejection": (uq2, dict(scale=0.02, seed=0, pred_mode="rejection")),
    "uq3-tree": (uq3, dict(scale=0.01, overlap=0.3, seed=0)),
    "uq4-cyclic": (uq4, dict(scale=0.02, seed=0)),
}


@dataclasses.dataclass
class UnionCase:
    wl: object                      # repro.data.workloads.Workload
    cover: object                   # exact cover, joins in workload order
    attrs: List[str]                # sorted output schema
    tuples: Dict[str, set]          # join name -> its distinct tuples
    universe: set                   # the union U


@functools.lru_cache(maxsize=None)
def union(name: str) -> UnionCase:
    fn, kw = UNIONS[name]
    wl = fn(**kw)
    est = estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle)
    attrs = sorted(wl.joins[0].output_attrs)
    tuples = {j.name: set(map(tuple, full_join_matrix(wl.cat, j, attrs).tolist()))
              for j in wl.joins}
    universe = set().union(*tuples.values())
    # the exact cover's pieces partition U, by Theorem 3 and by Eq. 1
    assert est.union_size_cover == pytest.approx(len(universe)), name
    assert est.union_size_eq1 == pytest.approx(len(universe)), name
    return UnionCase(wl, est.cover, attrs, tuples, universe)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Draw:
    matrix: np.ndarray              # (n, len(attrs)) in the union's attrs order
    home: np.ndarray                # (n,) index into ``order``
    order: List[str]                # the engine's piece order


def _set_union(engine=None, **kw):
    """A ``SetUnionSampler`` draw; ``engine`` names the class of the fused
    engine it must run on (None: the host loop), so a cell whose union
    quietly fell back to the host fails instead of testing the wrong path."""
    def draw(u: UnionCase, n: int):
        from repro.core.union_sampler import SetUnionSampler
        s = SetUnionSampler(u.wl.cat, u.wl.joins, u.cover, seed=SEED,
                            round_batch=ROUND_BATCH, **kw)
        assert type(s._engine).__name__ == (engine or "NoneType")
        return s.sample(n), s.order
    return draw


def _mesh1(u: UnionCase, n: int):
    from repro.core.sharding import make_sampler_mesh
    return _set_union("ShardedUnionSampler", backend="jax",
                      mesh=make_sampler_mesh(world=1))(u, n)


# request sizes of the served engine, cycled until n rows are served
SERVED_REQUESTS = (1, 997, 64, 4096, 313, 2048)


def _served(u: UnionCase, n: int):
    from repro.core.union_sampler import SetUnionSampler
    from repro.serve.service import SampleService
    s = SetUnionSampler(u.wl.cat, u.wl.joins, u.cover, seed=SEED,
                        backend="jax", round_batch=ROUND_BATCH)
    assert type(s._engine).__name__ == "JaxUnionSampler"   # the pipelined path
    parts, got, k = [], 0, 0
    with SampleService(s, batch=ROUND_BATCH, prefetch=2) as svc:
        while got < n:
            m = min(SERVED_REQUESTS[k % len(SERVED_REQUESTS)], n - got)
            parts.append(svc.request(m))
            assert len(parts[-1]) == m
            got, k = got + m, k + 1
    return parts, s.order


def _online(backend: str):
    def draw(u: UnionCase, n: int):
        from repro.core.online import OnlineUnionSampler
        s = OnlineUnionSampler(u.wl.cat, u.wl.joins, seed=SEED,
                               backend=backend)
        return s.sample(n), s.order
    return draw


@dataclasses.dataclass(frozen=True)
class Engine:
    name: str
    draw: Callable
    first_home: bool    # the home is the first cover piece holding the row
    uniform: bool = True    # takes part in the uniformity test
    per_tuple: int = DRAW_PER_TUPLE     # rows drawn per union tuple


# Left out by design, each refused by a check with a test of its own:
#   record mode on the adaptive plan: JaxRecordUnionSampler raises
#     "membership='record' supports plan='static' only"
#     (test_adaptive_planner.py::test_record_engine_rejects_adaptive);
#   record mode on a mesh: SetUnionSampler raises ValueError("... record ...")
#     (test_predicates_device.py::test_record_engine_rejects_mesh).
ENGINES: Dict[str, Engine] = {e.name: e for e in [
    Engine("numpy", _set_union(backend="numpy", membership="probe"), True),
    Engine("numpy-record", _set_union(backend="numpy", membership="record"),
           False),
    Engine("jax", _set_union("JaxUnionSampler", backend="jax"), True),
    Engine("jax-adaptive", _set_union("JaxUnionSampler", backend="jax",
                                      plan="adaptive"), True),
    Engine("jax-record", _set_union("JaxRecordUnionSampler", backend="jax",
                                    membership="record"), False),
    Engine("mesh1", _mesh1, True),
    Engine("served", _served, True),
    # ONLINE-UNION is uniform only once its estimates converge; its own
    # tests hold it to their looser bar, so it joins the membership test
    # only, on a smaller draw: its host-side probes cost milliseconds a call
    # (about 100 s for 120·|U| rows of uq1-5 on jax, on the CPU)
    Engine("online-numpy", _online("numpy"), True, uniform=False,
           per_tuple=ONLINE_PER_TUPLE),
    Engine("online-jax", _online("jax"), True, uniform=False,
           per_tuple=ONLINE_PER_TUPLE),
]}

@functools.lru_cache(maxsize=None)
def draw(engine: str, union_name: str) -> Draw:
    """The cell's one draw of ``per_tuple · |U|`` rows."""
    u = union(union_name)
    n = ENGINES[engine].per_tuple * len(u.universe)
    out, order = ENGINES[engine].draw(u, n)
    parts = out if isinstance(out, list) else [out]
    matrix = np.concatenate([np.stack([np.asarray(p.rows[a], np.int64)
                                       for a in u.attrs], axis=1)
                             for p in parts])
    home = np.concatenate([np.asarray(p.home, np.int64) for p in parts])
    assert matrix.shape[0] == n and home.shape[0] == n
    return Draw(matrix, home, list(order))


def cells(engines: Sequence[str], test: str) -> list:
    """pytest params ``(engine, union)`` of the given engines for ``test``
    (``"member"`` or ``"uniform"``)."""
    return [pytest.param(e, un, id=f"{e}-{un}") for e in engines
            if test == "member" or ENGINES[e].uniform for un in UNIONS]


# ---------------------------------------------------------------------------
# the two checks
# ---------------------------------------------------------------------------


def check_members(engine: str, union_name: str) -> None:
    """Every row is a tuple of U and of its home piece's join; for a
    probe-mode engine the home is also the first cover piece holding it."""
    u = union(union_name)
    d = draw(engine, union_name)
    first = ENGINES[engine].first_home
    for i, (row, h) in enumerate(zip(map(tuple, d.matrix.tolist()),
                                     d.home.tolist())):
        assert row in u.universe, f"row {i} {row} is not in U"
        assert 0 <= h < len(d.order), f"row {i}: home {h} out of range"
        assert row in u.tuples[d.order[h]], \
            f"row {i} {row} is not in its home {d.order[h]}"
        if first:
            earlier = [p for p in d.order[:h] if row in u.tuples[p]]
            assert not earlier, \
                f"row {i} {row} homed at {d.order[h]}, but {earlier[0]} holds it"


def check_uniform(engine: str, union_name: str) -> None:
    u = union(union_name)
    p = chi2_p(draw(engine, union_name).matrix, len(u.universe))
    assert p > P_MIN, f"{engine} not uniform on {union_name} (p={p})"
