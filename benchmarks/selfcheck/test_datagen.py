"""The generator against dbgen's column rules, at a small scale factor."""

import numpy as np

from generators import tpch as g

SF = 0.02


def _all(seed):
    orders, lines = [], []
    for o, l in g.order_blocks(SF, seed):
        orders.append(o)
        lines.append(l)
    cat = lambda parts: {k: np.concatenate([p[k] for p in parts])  # noqa: E731
                         for k in parts[0]}
    return cat(orders), cat(lines)


def test_the_same_seed_gives_the_same_arrays_and_another_seed_others():
    a, b = _all(2**31 + 7), _all(2**31 + 7)
    for t in (0, 1):
        for k in a[t]:
            assert np.array_equal(a[t][k], b[t][k]), k
    c = _all(8)
    assert not np.array_equal(a[1]["l_extendedprice"][:1000],
                              c[1]["l_extendedprice"][:1000])
    assert np.array_equal(g.customer(SF, 5)["c_mktsegment"],
                          g.customer(SF, 5)["c_mktsegment"])


def test_orders_follow_dbgens_rules():
    orders, lines = _all(11)
    n = g.table_rows("orders", SF)
    assert len(orders["o_orderkey"]) == n
    k = orders["o_orderkey"].astype(np.int64)
    # sparse keys: of every 32, the first 8; ascending, so a primary key
    assert ((k - 1) % 32 < 8).all() and (np.diff(k) > 0).all()
    assert k[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33]
    # no order of a customer whose key is divisible by 3
    assert (orders["o_custkey"] % 3 != 0).all()
    assert orders["o_custkey"].min() >= 1
    assert orders["o_custkey"].max() <= g.table_rows("customer", SF)
    assert orders["o_orderdate"].min() >= g.START_DATE
    assert orders["o_orderdate"].max() <= g.LAST_ORDER_DATE
    assert (orders["o_shippriority"] == 0).all()


def test_lineitem_follows_dbgens_rules():
    orders, li = _all(12)
    keys, counts = np.unique(li["l_orderkey"], return_counts=True)
    # 1 to 7 lines an order, every order has some, rows in key order
    assert np.array_equal(keys, orders["o_orderkey"])
    assert counts.min() == 1 and counts.max() == 7
    assert abs(counts.mean() - 4.0) < 0.05
    assert (np.diff(li["l_orderkey"].astype(np.int64)) >= 0).all()
    odate = np.repeat(orders["o_orderdate"], counts)
    lag = li["l_shipdate"].astype(np.int32) - odate
    assert lag.min() == 1 and lag.max() == 121
    assert li["l_quantity"].min() == 100 and li["l_quantity"].max() == 5000
    assert li["l_discount"].min() == 0 and li["l_discount"].max() == 10
    assert li["l_tax"].min() == 0 and li["l_tax"].max() == 8
    # extended price = quantity x a retail price of 900.00 .. 2098.99
    unit = li["l_extendedprice"] / (li["l_quantity"] // 100)
    assert unit.min() >= 90000 and unit.max() <= 209900
    # line status O exactly when shipped after 1995-06-17
    assert np.array_equal(li["l_linestatus"] == 1,
                          li["l_shipdate"] > g.CURRENT_DATE)
    # four Q1 groups: A/F, N/F, N/O, R/F, with N/F rare; never A/O or R/O
    gid = li["l_returnflag"] * 2 + li["l_linestatus"]
    groups, n = np.unique(gid, return_counts=True)
    assert groups.tolist() == [0, 2, 3, 4]
    share = dict(zip(groups.tolist(), (n / n.sum()).tolist()))
    assert share[2] < 0.01 < share[0]
    assert abs(share[0] - share[4]) < 0.01 and share[3] > 0.45


def test_retail_price_is_clause_4_2_3s_formula():
    assert g.retail_price_cents(np.array([1, 10, 1000, 200000])).tolist() == [
        90000 + 0 + 100, 90000 + 1 + 1000, 90000 + 100 + 0,
        90000 + 20000 + 0]
