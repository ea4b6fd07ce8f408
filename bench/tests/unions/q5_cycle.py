"""Test union: the cycle of TPC-H Q5 in skeleton and residual form.

customer ⋈ orders ⋈ lineitem is the tree (on ``ck``, then ``ok``);
supplier is the residual relation joined on ``(sk, nk)``: lineitem
produces ``sk`` and customer ``nk``, so the supplier of a line must be of
the customer's nation.  ``joins`` variant copies keep the shared first
``overlap`` of every relation and ``keep_rest`` of the rest.
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union


def build(cfg: dict) -> Union:
    sf, seed = cfg["scale_factor"], cfg["data_seed"]
    db = tpch.generate(["customer", "orders", "lineitem", "supplier"], sf,
                       seed)
    li = {("sk" if a == "l_suppkey" else a): c
          for a, c in db["lineitem"].items()}
    rels = [Rel("customer", db["customer"], ("ck",)),
            Rel("orders", db["orders"], ("ok",), "customer", ("ck",)),
            Rel("lineitem", li, ("ok", "ln"), "orders", ("ok",)),
            Rel("supplier", db["supplier"], ("sk",), None, ("sk", "nk"),
                "residual")]
    n = cfg["joins"]
    masks = {r.name: tpch.variant_masks(r.nrows, n, cfg["overlap"],
                                        cfg["keep_rest"], seed + 17 + i)
             for i, r in enumerate(rels)}
    return Union(rels, [JoinDef(f"Q5_J{v}",
                                {r.name: masks[r.name][v] for r in rels}, [])
                        for v in range(n)])
