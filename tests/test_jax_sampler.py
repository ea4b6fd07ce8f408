"""Device (jitted) chain sampler == host sampler: distribution equivalence."""

import numpy as np
import pytest
from scipy import stats as sps

from _conformance import chi2_p
from conftest import tiny_db

from repro.core.index import Catalog
from repro.core.jax_sampler import JaxChainSampler
from repro.core.joins import chain_join, full_join_matrix
from repro.core.join_sampler import JoinSampler


def _chain(seed=0):
    R, S, T = tiny_db(seed)
    return Catalog(), chain_join(f"RSTj{seed}", [R, S, T], ["b", "c"])


def test_total_weight_matches_host():
    cat, spec = _chain(0)
    js = JaxChainSampler(cat, spec, seed=0)
    host = JoinSampler(cat, spec, method="ew")
    assert js.total_weight == pytest.approx(host.exact_acyclic_size())


def test_jax_sampler_uniform_chi2():
    cat, spec = _chain(1)
    mat = full_join_matrix(cat, spec)
    n_tuples = mat.shape[0]
    js = JaxChainSampler(cat, spec, seed=1)
    N = 60 * n_tuples
    rows = js.sample_uniform(N, batch=4096)
    got = np.stack([rows[a] for a in spec.output_attrs], axis=1)
    assert np.unique(got, axis=0).shape[0] == n_tuples
    p = chi2_p(got, n_tuples)
    assert p > 1e-3, f"jitted sampler not uniform (p={p})"


def test_jax_sampler_matches_host_marginals():
    cat, spec = _chain(2)
    js = JaxChainSampler(cat, spec, seed=2)
    host = JoinSampler(cat, spec, method="ew")
    rng = np.random.default_rng(0)
    N = 4000
    r_j = js.sample_uniform(N, batch=2048)
    r_h, _ = host.sample_uniform(rng, N, batch=2048)
    # same marginal distribution per attribute (two-sample chi-square)
    for a in spec.output_attrs:
        vj, cj = np.unique(r_j[a], return_counts=True)
        vh, ch = np.unique(r_h[a], return_counts=True)
        dom = np.union1d(vj, vh)
        fj = np.zeros(dom.shape[0])
        fh = np.zeros(dom.shape[0])
        fj[np.searchsorted(dom, vj)] = cj
        fh[np.searchsorted(dom, vh)] = ch
        tot = fj + fh
        keep = tot >= 8
        if keep.sum() < 2:
            continue
        chi2 = ((fj[keep] - fh[keep]) ** 2 / tot[keep]).sum()
        p = 1 - sps.chi2.cdf(chi2, df=int(keep.sum()) - 1)
        assert p > 1e-4, f"attr {a}: device/host marginals differ (p={p})"


def test_jax_sampler_rejects_non_chain():
    import numpy as np
    from repro.core.joins import JoinNode, JoinSpec
    from repro.core.relation import Relation
    rng = np.random.default_rng(0)
    R = Relation("R", {"a": rng.integers(0, 4, 10), "b": rng.integers(0, 4, 10)})
    S = Relation("S", {"b": rng.integers(0, 4, 10), "c": rng.integers(0, 4, 10)})
    T = Relation("T", {"b": rng.integers(0, 4, 10), "d": rng.integers(0, 4, 10)})
    tree = JoinSpec("tree", [JoinNode("R", R, None, ()),
                             JoinNode("S", S, "R", ("b",)),
                             JoinNode("T", T, "R", ("b",))])
    with pytest.raises(ValueError):
        JaxChainSampler(Catalog(), tree)
