"""TPC-H data for the benchmark, every column of the specification.

The benchmark keeps its own generator so that a change to the program cannot
move the data it is measured on.  Keys follow the repository's TPC-H-lite
generator (``data/tpch.py``); the other columns are those of TPC-H 3.0.1
clause 1.4, with the value domains of clause 4.2.3, integer-encoded so that
the device engine can hold them (non-negative int32):

* text (names, addresses, phones, comments) as a 31-bit dictionary code;
  a name that the specification forms from the key (``Supplier#000000042``)
  takes the key as its code;
* a choice from a fixed list (segment, priority, ship mode, ...) as its
  index in the list;
* money as cents, shifted by 99,999 where the domain reaches below zero
  (``s_acctbal``, ``c_acctbal``);
* dates as days since 1992-01-01.

Three things differ from the original generator besides the columns:

* each relation draws from its own random stream (keyed by the relation's
  name), so a configuration generates only the relations it reads and still
  gets the same values as one that reads them all; the join keys and
  ``p_size`` are the first draws of their stream;
* ``lineitem`` is generated in ``ok`` order, so its row id is
  ``offset[ok] + ln`` and a reference can find a row without an index;
* :func:`variant_masks` keeps an exact number of rows per variant, so array
  shapes are the same for every data seed.

Everything is numpy; nothing here imports the program.  Keys are ``arange``
row ids (``lineitem``: ``(ok, ln)``, ``partsupp``: ``(pk, sk)``), so every
row is distinct.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

Columns = Dict[str, np.ndarray]

# rows per relation at scale factor 1 (the original's BASES x 100)
SF1_ROWS = dict(region=5, nation=25, supplier=10_000, part=200_000,
                partsupp=800_000, customer=150_000, orders=1_500_000,
                lineitem=6_000_000)

_STREAM = {name: i for i, name in enumerate(SF1_ROWS)}

TEXT = 2 ** 31 - 1          # dictionary codes of text columns: [0, TEXT)
ACCTBAL = (-99_999, 999_999)     # cents (clause 4.2.3: -999.99 .. 9,999.99)
CURRENT_DATE = 1263         # 1995-06-17, in days since 1992-01-01
LAST_ORDER_DATE = 2405      # 1998-12-31 less 151 days


def _rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), _STREAM[name]])


def row_counts(scale_factor: float) -> Dict[str, int]:
    n = {k: max(int(v * scale_factor), 3) for k, v in SF1_ROWS.items()}
    n["region"], n["nation"] = 5, 25
    return n


def _text(r: np.random.Generator, n: int) -> np.ndarray:
    return r.integers(0, TEXT, n)


def _acctbal(r: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = ACCTBAL
    return r.integers(lo, hi + 1, n) - lo


def retail_price(pk: np.ndarray) -> np.ndarray:
    """``p_retailprice`` in cents, a function of the part key (4.2.3)."""
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)


def _orders(r: np.random.Generator, n: Dict[str, int],
            scale_factor: float) -> Columns:
    m = n["orders"]
    ck = r.integers(0, n["customer"], m)
    date = r.integers(0, LAST_ORDER_DATE + 1, m)
    # every line ships 1..121 days after its order; the status follows
    # (F: all shipped by the current date, O: none, P: the days between)
    status = np.where(date + 121 <= CURRENT_DATE, 0,
                      np.where(date + 1 > CURRENT_DATE, 1, 2))
    return {"ok": np.arange(m), "ck": ck, "o_orderstatus": status,
            "o_totalprice": r.integers(90_000, 50 * 209_900 * 7 + 1, m),
            "o_orderdate": date, "o_orderpriority": r.integers(0, 5, m),
            "o_clerk": r.integers(0, max(int(1000 * scale_factor), 1), m),
            "o_shippriority": np.zeros(m, np.int64), "o_comment": _text(r, m)}


def _lineitem(r: np.random.Generator, n: Dict[str, int],
              order_date: np.ndarray) -> Columns:
    m = n["lineitem"]
    ok = np.sort(r.integers(0, n["orders"], m))
    start = np.searchsorted(ok, np.arange(n["orders"]))
    pk = r.integers(0, n["part"], m)
    sk = r.integers(0, n["supplier"], m)
    qty = r.integers(1, 51, m)
    ship = order_date[ok] + r.integers(1, 122, m)
    commit = order_date[ok] + r.integers(30, 91, m)
    receipt = ship + r.integers(1, 31, m)
    # returnflag R or A (0, 1) once received by the current date, else N (2)
    flag = np.where(receipt <= CURRENT_DATE, r.integers(0, 2, m), 2)
    return {"ok": ok, "ln": np.arange(m) - start[ok],
            "l_partkey": pk, "l_suppkey": sk, "l_quantity": qty,
            "l_extendedprice": qty * retail_price(pk),
            "l_discount": r.integers(0, 11, m), "l_tax": r.integers(0, 9, m),
            "l_returnflag": flag,
            "l_linestatus": (ship > CURRENT_DATE).astype(np.int64),
            "l_shipdate": ship, "l_commitdate": commit,
            "l_receiptdate": receipt, "l_shipinstruct": r.integers(0, 4, m),
            "l_shipmode": r.integers(0, 7, m), "l_comment": _text(r, m)}


def generate(names: List[str], scale_factor: float, seed: int
             ) -> Dict[str, Columns]:
    """The named relations at ``scale_factor`` from data seed ``seed``."""
    n = row_counts(scale_factor)
    out: Dict[str, Columns] = {}
    for name in names:
        r = _rng(seed, name)
        k = n[name]
        if name == "region":
            out[name] = {"rk": np.arange(k), "r_name": np.arange(k),
                         "r_comment": _text(r, k)}
        elif name == "nation":
            rk = r.integers(0, n["region"], k)
            out[name] = {"nk": np.arange(k), "n_name": np.arange(k), "rk": rk,
                         "n_comment": _text(r, k)}
        elif name in ("supplier", "customer"):
            nk = r.integers(0, n["nation"], k)
            p = name[0]
            cols = {f"{p}k": np.arange(k), f"{p}_name": np.arange(k),
                    f"{p}_address": _text(r, k), "nk": nk,
                    f"{p}_phone": _text(r, k), f"{p}_acctbal": _acctbal(r, k)}
            if name == "customer":
                cols["c_mktsegment"] = r.integers(0, 5, k)
            cols[f"{p}_comment"] = _text(r, k)
            out[name] = cols
        elif name == "part":
            size = r.integers(1, 51, k)
            ptype = r.integers(0, 150, k)
            mfgr = r.integers(1, 6, k)
            pk = np.arange(k)
            out[name] = {"pk": pk, "p_name": _text(r, k), "p_mfgr": mfgr,
                         "p_brand": 10 * mfgr + r.integers(1, 6, k),
                         "p_type": ptype, "p_size": size,
                         "p_container": r.integers(0, 40, k),
                         "p_retailprice": retail_price(pk),
                         "p_comment": _text(r, k)}
        elif name == "partsupp":
            pairs = r.choice(n["part"] * n["supplier"],
                             size=min(k, n["part"] * n["supplier"]),
                             replace=False)
            m = pairs.shape[0]
            out[name] = {"pk": pairs // n["supplier"],
                         "sk": pairs % n["supplier"],
                         "ps_availqty": r.integers(1, 10_000, m),
                         "ps_supplycost": r.integers(100, 100_001, m),
                         "ps_comment": _text(r, m)}
        elif name == "orders":
            out[name] = _orders(r, n, scale_factor)
        elif name == "lineitem":
            dates = _orders(_rng(seed, "orders"), n,
                            scale_factor)["o_orderdate"]
            out[name] = _lineitem(r, n, dates)
        else:
            raise KeyError(f"no TPC-H relation {name!r}")
    return out


def variant_masks(nrows: int, n_variants: int, overlap: float,
                  keep_rest: float, seed: int) -> List[np.ndarray]:
    """Row masks of variant copies of one relation.

    Every variant keeps the first ``round(overlap * nrows)`` rows and exactly
    ``round(keep_rest * rest)`` of the other ``rest`` rows, chosen at random:
    the original keeps each of those with probability ``keep_rest``.
    """
    rng = np.random.default_rng(seed)
    core = int(round(nrows * overlap))
    rest = nrows - core
    k = int(round(rest * keep_rest))
    out = []
    for _ in range(n_variants):
        keep = np.zeros(nrows, dtype=bool)
        keep[:core] = True
        keep[core + rng.choice(rest, size=k, replace=False)] = True
        out.append(keep)
    return out
