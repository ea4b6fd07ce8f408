"""TPC-H Q5's join at SF1, served as a union over three variant sources.

Q5 (TPC-H 3.0.1 clause 2.4.5) joins region, nation, customer, orders,
lineitem and supplier on ``c_custkey = o_custkey``, ``l_orderkey =
o_orderkey``, ``l_suppkey = s_suppkey``, ``c_nationkey = s_nationkey``,
``s_nationkey = n_nationkey`` and ``n_regionkey = r_regionkey``: a cycle.
It is sampled as arXiv:2303.00940 §8.2 says, a walk over the acyclic
skeleton region ⋈ nation ⋈ customer ⋈ orders ⋈ lineitem (on ``rk``,
``nk``, ``ck``, ``ok``), then acceptance against the residual relation
supplier on ``(sk, nk)``: the supplier of a line must be of its customer's
nation.  ``l_suppkey`` is renamed ``sk`` so that the edge is a natural join.

Each source keeps the shared first ``overlap`` of customer, orders,
lineitem and supplier and ``keep_rest`` of the rest, as UQ1's variants do;
region and nation, dimension tables of 5 and 25 rows, are whole in every
source.
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union

VARIANT = ("customer", "orders", "lineitem", "supplier")


def build(cfg: dict) -> Union:
    sf, seed = cfg["scale_factor"], cfg["data_seed"]
    db = tpch.generate(["region", "nation", "customer", "orders",
                        "lineitem", "supplier"], sf, seed)
    li = {("sk" if a == "l_suppkey" else a): c
          for a, c in db["lineitem"].items()}
    rels = [Rel("region", db["region"], ("rk",)),
            Rel("nation", db["nation"], ("nk",), "region", ("rk",)),
            Rel("customer", db["customer"], ("ck",), "nation", ("nk",)),
            Rel("orders", db["orders"], ("ok",), "customer", ("ck",)),
            Rel("lineitem", li, ("ok", "ln"), "orders", ("ok",)),
            Rel("supplier", db["supplier"], ("sk",), None, ("sk", "nk"),
                "residual")]
    n = cfg["joins"]
    masks = {r.name: tpch.variant_masks(r.nrows, n, cfg["overlap"],
                                        cfg["keep_rest"], seed + 17 + i)
             for i, r in enumerate(rels) if r.name in VARIANT}
    return Union(rels, [JoinDef(f"Q5_J{v}",
                                {name: m[v] for name, m in masks.items()}, [])
                        for v in range(n)])
