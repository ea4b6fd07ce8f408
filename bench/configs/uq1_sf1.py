"""UQ1 at TPC-H SF1: the union of five chain joins
nation ⋈ supplier ⋈ customer ⋈ orders ⋈ lineitem over five variant
databases (arXiv:2303.00940 §9).  Variant ``v`` of every relation keeps the
shared first ``overlap`` of its rows and ``keep_rest`` of the others.

``build`` turns the sizes in ``uq1_sf1.json`` into the reference's
description of the union (numpy columns only); the harness hands the same
columns to the program.
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union, chain


def build(cfg: dict) -> Union:
    sf, seed = cfg["scale_factor"], cfg["data_seed"]
    db = tpch.generate(["nation", "supplier", "customer", "orders",
                        "lineitem"], sf, seed)
    keys = {"nation": ("nk",), "supplier": ("sk",), "customer": ("ck",),
            "orders": ("ok",), "lineitem": ("ok", "ln")}
    rels = [Rel(name, db[name], keys[name]) for name in keys]
    n = cfg["joins"]
    masks = {r.name: tpch.variant_masks(r.nrows, n, cfg["overlap"],
                                        cfg["keep_rest"], seed + 17 + i)
             for i, r in enumerate(rels)}
    joins = [JoinDef(f"UQ1_J{v}", {r.name: masks[r.name][v] for r in rels},
                     []) for v in range(n)]
    return Union(chain(rels, ["nk", "nk", "ck", "ok"]), joins)
