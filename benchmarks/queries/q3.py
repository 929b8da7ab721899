"""Plain reference of TPC-H Q3 (customer, orders, lineitem): a mask over
customer keys, a mask over orders, revenue by order, the ten largest.
Revenue of one order is under 2^53, so the exact integer sums fit.
`control` is the same with float32 revenue."""

import numpy as np

from . import _common as c
from harness.datagen import DICTIONARIES


def _top(tables, p, dtype):
    cust, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    date = c.days(p["date"])
    seg = DICTIONARIES["c_mktsegment"].index(p["segment"])
    in_seg = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    in_seg[cust["c_custkey"][cust["c_mktsegment"] == seg]] = True
    o_ok = (orders["o_orderdate"] < date) & in_seg[orders["o_custkey"]]
    l_idx = np.flatnonzero(li["l_shipdate"] > date)
    # orders' keys are sorted (a primary key, loaded in key order)
    pos = np.searchsorted(orders["o_orderkey"], li["l_orderkey"][l_idx])
    keep = o_ok[pos]
    l_idx, pos = l_idx[keep], pos[keep]
    rev = (li["l_extendedprice"][l_idx].astype(dtype)
           * (100 - li["l_discount"][l_idx].astype(dtype)))
    if dtype is np.int64:
        by_order = np.zeros(len(o_ok), dtype=np.int64)
        np.add.at(by_order, pos, rev)
    else:
        by_order = np.bincount(pos, weights=rev, minlength=len(o_ok)
                               ).astype(dtype)
    hit = np.unique(pos)
    order = sorted(hit.tolist(), key=lambda i: (
        -float(by_order[i]) if dtype is not np.int64 else -int(by_order[i]),
        int(orders["o_orderdate"][i]), int(orders["o_orderkey"][i])))[:10]
    return [(int(orders["o_orderkey"][i]), by_order[i],
             c.iso(orders["o_orderdate"][i]),
             int(orders["o_shippriority"][i])) for i in order]


def reference(tables, p):
    return [(k, c.dec(int(r), 4), d, s) for k, r, d, s in
            _top(tables, p, np.int64)]


def control(tables, p):
    return [(k, c.from_float(float(r) / 1e4, 4), d, s) for k, r, d, s in
            _top(tables, p, np.float32)]
