"""Test union: UQ3's shape (arXiv:2303.00940 §9, §5.2 splitting).

The reference holds customer ⋈ orders on ``ck`` over three variant copies.
The program is given each copy in another layout, as UQ3's sources hold
it: ``UQ3_JA`` as a branching tree over vertical splits of both relations
(``cust_a`` with children ``cust_b`` and ``ord_a``, ``ord_b`` under
``ord_a``), ``UQ3_JB`` as a chain over customer and split orders, and
``UQ3_JC`` as the plain chain.  Every layout has the same output schema.
"""

from bench import tpch
from bench.reference.tree import JoinDef, Rel, Union, chain

CUST_A = ["ck", "c_name", "c_address", "nk"]
ORD_A = ["ok", "ck"]


def build(cfg: dict) -> Union:
    sf, seed = cfg["scale_factor"], cfg["data_seed"]
    db = tpch.generate(["customer", "orders"], sf, seed)
    rels = [Rel("customer", db["customer"], ("ck",)),
            Rel("orders", db["orders"], ("ok",))]
    masks = {r.name: tpch.variant_masks(r.nrows, cfg["joins"], cfg["overlap"],
                                        cfg["keep_rest"], seed + 17 + i)
             for i, r in enumerate(rels)}
    joins = [JoinDef(name, {r.name: masks[r.name][v] for r in rels}, [])
             for v, name in enumerate(["UQ3_JA", "UQ3_JB", "UQ3_JC"])]
    return Union(chain(rels, ["ck"]), joins)


def program_joins(u: Union) -> list:
    from repro.core.joins import JoinNode, JoinSpec, chain_join
    from repro.core.relation import Relation

    def split(rel, jd, first):
        r = Relation(f"{rel.name}@{jd.name}",
                     {a: c[jd.variants[rel.name]] for a, c in rel.cols.items()})
        key = list(rel.key)
        rest = key + [a for a in rel.cols if a not in first]
        return r, r.project(first, name=f"{r.name}|a"), r.project(
            rest, name=f"{r.name}|b")

    cust, ords = u.rel("customer"), u.rel("orders")
    out = []
    for jd in u.joins:
        c, ca, cb = split(cust, jd, CUST_A)
        o, oa, ob = split(ords, jd, ORD_A)
        if jd.name == "UQ3_JA":
            out.append(JoinSpec(jd.name, [
                JoinNode(ca.name, ca, None, ()),
                JoinNode(cb.name, cb, ca.name, ("ck",)),
                JoinNode(oa.name, oa, ca.name, ("ck",)),
                JoinNode(ob.name, ob, oa.name, ("ok",))]))
        elif jd.name == "UQ3_JB":
            out.append(chain_join(jd.name, [c, oa, ob], [("ck",), ("ok",)]))
        else:
            out.append(chain_join(jd.name, [c, o], [("ck",)]))
    return out
