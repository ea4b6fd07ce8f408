"""Test union: a branching join tree with a composite edge.

partsupp is the root; part (on ``pk``), supplier (on ``sk``) and lineitem
(on ``(pk, sk)``, the composite key of partsupp) are its children, and
orders hangs under lineitem on ``ok``.  ``joins`` variant copies keep the
shared first ``overlap`` of every relation and ``keep_rest`` of the rest;
one more join keeps every row under a selection pushed down to part.
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union


def build(cfg: dict) -> Union:
    sf, seed = cfg["scale_factor"], cfg["data_seed"]
    db = tpch.generate(["partsupp", "part", "supplier", "lineitem", "orders"],
                       sf, seed)
    li = {{"l_partkey": "pk", "l_suppkey": "sk"}.get(a, a): c
          for a, c in db["lineitem"].items()}
    rels = [Rel("partsupp", db["partsupp"], ("pk", "sk")),
            Rel("part", db["part"], ("pk",), "partsupp", ("pk",)),
            Rel("supplier", db["supplier"], ("sk",), "partsupp", ("sk",)),
            Rel("lineitem", li, ("ok", "ln"), "partsupp", ("pk", "sk")),
            Rel("orders", db["orders"], ("ok",), "lineitem", ("ok",))]
    n = cfg["joins"]
    masks = {r.name: tpch.variant_masks(r.nrows, n, cfg["overlap"],
                                        cfg["keep_rest"], seed + 17 + i)
             for i, r in enumerate(rels)}
    joins = [JoinDef(f"PS_J{v}", {r.name: masks[r.name][v] for r in rels}, [])
             for v in range(n)]
    joins.append(JoinDef("PS_SEL", {}, [tuple(cfg["selection"])]))
    return Union(rels, joins)
