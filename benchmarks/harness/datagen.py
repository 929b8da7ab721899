"""TPC-H tables by dbgen's column rules (specification clause 4.2.3), made
with vectorised numpy from a seed.  Independent of `tidb_tpu/tpch_data.py`.

One stream of orders drives both `orders` and `lineitem`, block by block, so
a lineitem row's ship date follows its order's date as dbgen's does.  Every
column comes back in the narrowest integer type that holds dbgen's range
(the benchmark keeps these for the reference); `load.py` widens them to what
the program's store wants.

Decimals are integers scaled by 100 (cents, hundredths), dates are days
since 1970-01-01, strings are codes into the sorted dictionaries below.
"""

from __future__ import annotations

import numpy as np

START_DATE = int(np.datetime64("1992-01-01").astype("datetime64[D]").astype(int))
#: dbgen's ENDDATE (1998-12-31) less 151 days: the last order date
LAST_ORDER_DATE = int(np.datetime64("1998-08-02").astype("datetime64[D]").astype(int))
#: dbgen's CURRENTDATE: what shipped or was received by then is history
CURRENT_DATE = int(np.datetime64("1995-06-17").astype("datetime64[D]").astype(int))

DICTIONARIES = {
    "l_returnflag": ["A", "N", "R"],
    "l_linestatus": ["F", "O"],
    "c_mktsegment": ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"],
}

ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
PARTS_PER_SF = 200_000
#: orders to a block: about 2^21 lineitem rows
BLOCK_ORDERS = 1 << 19


def table_rows(table: str, sf: float) -> int:
    """Rows of a table at a scale factor; lineitem's is the mean (4 lines an
    order), the true count varies with the seed."""
    return {"orders": int(ORDERS_PER_SF * sf),
            "customer": int(CUSTOMERS_PER_SF * sf),
            "lineitem": int(ORDERS_PER_SF * sf) * 4}[table]


def sparse_order_key(i: np.ndarray) -> np.ndarray:
    """dbgen's mk_sparse: of every 32 keys the first 8 are used."""
    return (i >> 3 << 5) + (i & 7) + 1


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice of clause 4.2.3, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def customer(sf: float, seed: int) -> dict:
    n = table_rows("customer", sf)
    rng = np.random.default_rng([seed, 1])
    return {
        "c_custkey": np.arange(1, n + 1, dtype=np.int32),
        "c_mktsegment": rng.integers(0, 5, n, dtype=np.int8),
    }


def order_blocks(sf: float, seed: int):
    """Yield (orders block, lineitem block) of column dicts, in order-key
    order, lineitem rows grouped by their order."""
    n_orders = table_rows("orders", sf)
    n_cust = table_rows("customer", sf)
    n_part = int(PARTS_PER_SF * sf)
    # the customer keys an order may name: those not divisible by 3
    n_live = n_cust - n_cust // 3
    rng = np.random.default_rng([seed, 2])
    for o0 in range(0, n_orders, BLOCK_ORDERS):
        m = min(BLOCK_ORDERS, n_orders - o0)
        okey = sparse_order_key(np.arange(o0, o0 + m, dtype=np.int64))
        j = rng.integers(0, n_live, m, dtype=np.int64)
        custkey = j + j // 2 + 1  # 1, 2, 4, 5, 7, 8, ...
        odate = rng.integers(START_DATE, LAST_ORDER_DATE + 1, m,
                             dtype=np.int32)
        lines = rng.integers(1, 8, m, dtype=np.int8)
        orders = {
            "o_orderkey": okey.astype(np.int32 if okey[-1] < 2**31
                                      else np.int64),
            "o_custkey": custkey.astype(np.int32),
            "o_orderdate": odate.astype(np.int16),
            "o_shippriority": np.zeros(m, dtype=np.int8),
        }
        k = int(lines.sum())
        quantity = rng.integers(1, 51, k, dtype=np.int32)
        partkey = rng.integers(1, n_part + 1, k, dtype=np.int64)
        shipdate = np.repeat(odate, lines) + rng.integers(
            1, 122, k, dtype=np.int32)
        receipt = shipdate + rng.integers(1, 31, k, dtype=np.int32)
        # R or A once received, N while still out
        returnflag = np.where(receipt <= CURRENT_DATE,
                              rng.integers(0, 2, k, dtype=np.int8) * 2,
                              1).astype(np.int8)
        lineitem = {
            "l_orderkey": np.repeat(orders["o_orderkey"], lines),
            "l_quantity": (quantity * 100).astype(np.int16),
            "l_extendedprice": (quantity * retail_price_cents(partkey)
                                ).astype(np.int32),
            "l_discount": rng.integers(0, 11, k, dtype=np.int8),
            "l_tax": rng.integers(0, 9, k, dtype=np.int8),
            "l_returnflag": returnflag,
            "l_linestatus": (shipdate > CURRENT_DATE).astype(np.int8),
            "l_shipdate": shipdate.astype(np.int16),
        }
        yield orders, lineitem
