"""UQ2 at TPC-H SF1: three chain joins
region ⋈ nation ⋈ supplier ⋈ partsupp ⋈ part over the same relations,
told apart by overlapping selections on ``p_size`` pushed down to ``part``
(arXiv:2303.00940 §9, the Q2 construction taken from Carmeli et al.).

``build`` turns the sizes and selections in ``uq2_sf1.json`` into the
reference's description of the union (numpy columns only).
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union, chain


def build(cfg: dict) -> Union:
    db = tpch.generate(["region", "nation", "supplier", "partsupp", "part"],
                       cfg["scale_factor"], cfg["data_seed"])
    keys = {"region": ("rk",), "nation": ("nk",), "supplier": ("sk",),
            "partsupp": ("pk", "sk"), "part": ("pk",)}
    rels = [Rel(name, db[name], keys[name]) for name in keys]
    joins = [JoinDef(name, {}, [tuple(p) for p in preds])
             for name, preds in cfg["selections"].items()]
    return Union(chain(rels, ["rk", "nk", "sk", "pk"]), joins)
