"""The system under test, built through the program's public API.

The harness hands the program the benchmark's own columns and exact
intersection sizes; it takes back only the sampler, its counters and the
names of its kernels.  The cover comes from an ``OverlapOracle`` over the
reference's exact counts, so the pieces are exact (the program's histogram
warm-up is not on this path).
"""

from __future__ import annotations

from typing import Dict, Tuple

from bench.reference.tree import Union


def program_joins(u: Union) -> list:
    """The program's ``JoinSpec`` of every join, in cover order: the
    reference's relations as ``JoinNode``s on their parents and edges
    (residual relations as residual nodes), variant relations as filtered
    copies, selections through ``pushdown``."""
    from repro.core.joins import JoinNode, JoinSpec
    from repro.core.predicates import Pred, pushdown
    from repro.core.relation import Relation

    def tree_join(name, rels):
        return JoinSpec(name, [
            JoinNode(rels[r.name].name, rels[r.name],
                     None if r.parent is None else rels[r.parent].name,
                     r.edge, r.kind) for r in u.rels])

    base = {r.name: Relation(r.name, r.cols) for r in u.rels}
    shared = tree_join("base", base)
    specs = []
    for jd in u.joins:
        if jd.variants:
            rels = {r.name: base[r.name].filter(jd.variants[r.name],
                                                name=f"{r.name}@{jd.name}")
                    if r.name in jd.variants else base[r.name]
                    for r in u.rels}
            spec = tree_join(jd.name, rels)
        else:
            spec = shared
        if jd.preds:
            spec = pushdown(spec, [Pred(a, op, v) for a, op, v in jd.preds],
                            name=jd.name)
        specs.append(spec)
    return specs


def joins_for(u: Union, config=None) -> list:
    """The join specs a deployment submits: the configuration module's own
    ``program_joins(u)`` where it states a layout of its own (a §5.2 split
    of the reference's relations), else :func:`program_joins`.  Either way
    the joins carry the reference's names, in cover order."""
    own = getattr(config, "program_joins", None)
    specs = own(u) if own is not None else program_joins(u)
    names = [s.name for s in specs]
    if names != [j.name for j in u.joins]:
        raise ValueError(f"program joins {names} are not the reference's "
                         f"{[j.name for j in u.joins]}")
    return specs


def build_sampler(u: Union, sizes: Dict[Tuple[int, ...], int], seed: int,
                  round_batch: int, config=None):
    """``SetUnionSampler(backend="jax")`` over ``u`` with the exact cover;
    ``config`` is the configuration module (see :func:`joins_for`)."""
    from repro.core.cover import build_cover
    from repro.core.index import Catalog
    from repro.core.koverlap import OverlapOracle
    from repro.core.union_sampler import SetUnionSampler

    specs = joins_for(u, config)
    index = {s.name: i for i, s in enumerate(specs)}

    def subset(joins):
        return tuple(sorted(index[j.name] for j in joins))

    oracle = OverlapOracle(lambda d: sizes[subset(d)],
                           lambda j: sizes[subset([j])], specs)
    cover = build_cover(oracle)
    return SetUnionSampler(Catalog(), specs, cover, seed=seed, backend="jax",
                           round_batch=round_batch)


def fallbacks() -> tuple:
    """The program's engine-fallback record so far (counter, events)."""
    from repro import obs
    counter = obs.get_registry().get("repro_engine_fallback_total")
    series = counter.snapshot() if counter is not None else {}
    return sum(series.values()), len(obs.fallback_events())


def engine_faults(sampler, since: tuple, pallas: bool = True) -> list:
    """What the chip smoke test refuses: joins degraded to host draws,
    engine fallbacks fired since ``since`` (a :func:`fallbacks` reading),
    and (on the chip) range probes off the Pallas kernels."""
    out = []
    if sampler.backend.degraded:
        out.append(f"joins degraded to host draws: {sampler.backend.degraded}")
    now = fallbacks()
    if now != since:
        out.append(f"engine fallbacks fired: {now[0] - since[0]} counted, "
                   f"{now[1] - since[1]} events")
    eng = sampler._engine
    if eng is None:
        out.append("no fused device engine was built")
    elif pallas and not all(t.use_pallas for t in eng.trees):
        out.append("range probes are not on the Pallas kernels")
    return out
