"""Star Schema Benchmark tables (P. O'Neil, E. O'Neil, X. Chen, "Star Schema
Benchmark", rev. 3, 2009, section 3: dbgen's rules, derived from TPC-H's),
made with vectorised numpy from a seed.

One fact table, `lineorder`, and four dimensions: `customer`, `supplier`,
`part` and `date`.  Every dimension key is dense from 1 (`date`'s is the
day as yyyymmdd), every foreign key of the fact table is uniform over its
dimension.  The dimensions come whole, then `lineorder` block by block from
one stream of orders (1 to 7 lines an order, each line of an order on the
order's date and customer).

Every column comes back in the narrowest integer type that holds its range
(the benchmark keeps these for the reference); the program's store widens
them to what it wants.  Strings are codes into the sorted dictionaries
below.  Prices are integers, as dbgen writes them: the part's retail price
in cents, the fact's extended price quantity times it.
"""

from __future__ import annotations

import math

import numpy as np

#: TPC-H's 25 nations (specification clause 4.2.3) with their regions
NATIONS = [
    ("ALGERIA", "AFRICA"), ("ARGENTINA", "AMERICA"), ("BRAZIL", "AMERICA"),
    ("CANADA", "AMERICA"), ("EGYPT", "MIDDLE EAST"), ("ETHIOPIA", "AFRICA"),
    ("FRANCE", "EUROPE"), ("GERMANY", "EUROPE"), ("INDIA", "ASIA"),
    ("INDONESIA", "ASIA"), ("IRAN", "MIDDLE EAST"), ("IRAQ", "MIDDLE EAST"),
    ("JAPAN", "ASIA"), ("JORDAN", "MIDDLE EAST"), ("KENYA", "AFRICA"),
    ("MOROCCO", "AFRICA"), ("MOZAMBIQUE", "AFRICA"), ("PERU", "AMERICA"),
    ("CHINA", "ASIA"), ("ROMANIA", "EUROPE"), ("SAUDI ARABIA", "MIDDLE EAST"),
    ("VIETNAM", "ASIA"), ("RUSSIA", "EUROPE"), ("UNITED KINGDOM", "EUROPE"),
    ("UNITED STATES", "AMERICA"),
]
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec"]

#: the date dimension: 2,556 days from 1992-01-01
FIRST_DAY = np.datetime64("1992-01-01")
DATE_ROWS = 2556
#: TPC-H's last order date (1998-12-31 less 151 days)
LAST_ORDER_DAY = np.datetime64("1998-08-02")


def city(nation: str, digit: int) -> str:
    """dbgen's city: the nation's first 9 characters, padded, and a digit."""
    return f"{nation[:9]:<9}{digit}"


def _sorted(values) -> list:
    return sorted(set(values))


_NATION_NAMES = [n for n, _ in NATIONS]
_REGION_NAMES = _sorted(r for _, r in NATIONS)
_CITIES = _sorted(city(n, d) for n in _NATION_NAMES for d in range(10))
_MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
_CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
_BRANDS = [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
           for b in range(1, 41)]
_YEARMONTHS = [f"{MONTHS[m]}{y}" for y in range(1992, 1999)
               for m in range(12)]

DICTIONARIES = {
    "c_city": _CITIES,
    "c_nation": _sorted(_NATION_NAMES),
    "c_region": _REGION_NAMES,
    "s_city": _CITIES,
    "s_nation": _sorted(_NATION_NAMES),
    "s_region": _REGION_NAMES,
    "p_mfgr": _sorted(_MFGRS),
    "p_category": _sorted(_CATEGORIES),
    "p_brand1": _sorted(_BRANDS),
    "d_yearmonth": _sorted(_YEARMONTHS),
}

ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 30_000
SUPPLIERS_PER_SF = 2_000
PARTS_BASE = 200_000
#: orders to a block of lineorder: about 2^21 rows
BLOCK_ORDERS = 1 << 19


def table_rows(table: str, sf: float) -> int:
    """Rows of a dimension at a scale factor (`lineorder`'s varies with the
    seed: 1 to 7 lines for each of SF x 1.5 M orders).  `part` is 200,000
    x floor(1 + log2 SF) from SF1 up, and 200,000 x SF below it (a
    rehearsal's scale; the specification defines none under 1)."""
    if table == "part":
        if sf >= 1:
            return PARTS_BASE * int(math.floor(1 + math.log2(sf)))
        return max(int(PARTS_BASE * sf), 1)
    if table == "date":
        return DATE_ROWS
    per = {"customer": CUSTOMERS_PER_SF, "supplier": SUPPLIERS_PER_SF,
           "lineorder_orders": ORDERS_PER_SF}[table]
    return max(int(per * sf), 1)


def _narrow(a: np.ndarray) -> np.ndarray:
    """`a` in the narrowest signed integer type that holds it."""
    lo, hi = (int(a.min()), int(a.max())) if len(a) else (0, 0)
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return a.astype(dt)
    return a.astype(np.int64)


def _codes(column: str, values) -> np.ndarray:
    """Strings as codes into the column's sorted dictionary."""
    d = DICTIONARIES[column]
    return _narrow(np.searchsorted(np.array(d, dtype=object),
                                   np.asarray(values, dtype=object)))


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's p_retailprice (clause 4.2.3), in cents, as SSB's dbgen
    computes it."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def blocks(tables, sf: float, seed: int):
    """Yield (table, {column: array}) for each of `tables` in load order:
    the four dimensions whole, then `lineorder` block by block.  The
    stream of orders is drawn only where `lineorder` is asked for."""
    for name, make in (("customer", _customer), ("supplier", _supplier),
                       ("part", _part), ("date", _date)):
        if name in tables:
            yield name, make(sf, seed)
    if "lineorder" in tables:
        for block in _lineorder_blocks(sf, seed):
            yield "lineorder", block


def _nation_city(n: int, rng) -> tuple:
    nation = rng.integers(0, len(NATIONS), n)
    digit = rng.integers(0, 10, n)
    names = np.array(_NATION_NAMES, dtype=object)[nation]
    regions = np.array([r for _, r in NATIONS], dtype=object)[nation]
    cities = np.array([city(_NATION_NAMES[i], d)
                       for i in range(len(NATIONS)) for d in range(10)],
                      dtype=object)[nation * 10 + digit]
    return cities, names, regions


def _customer(sf: float, seed: int) -> dict:
    n = table_rows("customer", sf)
    rng = np.random.default_rng([seed, 11])
    cities, nations, regions = _nation_city(n, rng)
    return {
        "c_custkey": _narrow(np.arange(1, n + 1, dtype=np.int64)),
        "c_city": _codes("c_city", cities),
        "c_nation": _codes("c_nation", nations),
        "c_region": _codes("c_region", regions),
    }


def _supplier(sf: float, seed: int) -> dict:
    n = table_rows("supplier", sf)
    rng = np.random.default_rng([seed, 12])
    cities, nations, regions = _nation_city(n, rng)
    return {
        "s_suppkey": _narrow(np.arange(1, n + 1, dtype=np.int64)),
        "s_city": _codes("s_city", cities),
        "s_nation": _codes("s_nation", nations),
        "s_region": _codes("s_region", regions),
    }


def _part(sf: float, seed: int) -> dict:
    n = table_rows("part", sf)
    rng = np.random.default_rng([seed, 13])
    mfgr = rng.integers(1, 6, n)
    cat = rng.integers(1, 6, n)
    brand = rng.integers(1, 41, n)
    mfgrs = np.array([f"MFGR#{m}" for m in range(6)], dtype=object)[mfgr]
    cats = np.array([f"MFGR#{m}{c}" for m in range(6) for c in range(6)],
                    dtype=object)[mfgr * 6 + cat]
    brands = np.array([f"MFGR#{m}{c}{b}" for m in range(6) for c in range(6)
                       for b in range(41)],
                      dtype=object)[(mfgr * 6 + cat) * 41 + brand]
    return {
        "p_partkey": _narrow(np.arange(1, n + 1, dtype=np.int64)),
        "p_mfgr": _codes("p_mfgr", mfgrs),
        "p_category": _codes("p_category", cats),
        "p_brand1": _codes("p_brand1", brands),
    }


def datekey(days: np.ndarray) -> np.ndarray:
    """Days (numpy datetime64[D]) as yyyymmdd integers."""
    y = days.astype("datetime64[Y]").astype(np.int64) + 1970
    m = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    return y * 10000 + m * 100 + d


def _date(_sf: float, _seed: int) -> dict:
    days = FIRST_DAY + np.arange(DATE_ROWS)
    y = days.astype("datetime64[Y]").astype(np.int64) + 1970
    m = days.astype("datetime64[M]").astype(np.int64) % 12
    day_of_year = (days - days.astype("datetime64[Y]")).astype(np.int64)
    ym = np.array([f"{MONTHS[a]}{b}" for a, b in zip(m, y)], dtype=object)
    return {
        "d_datekey": datekey(days).astype(np.int32),
        "d_year": y.astype(np.int16),
        "d_yearmonthnum": (y * 100 + m + 1).astype(np.int32),
        "d_yearmonth": _codes("d_yearmonth", ym),
        "d_weeknuminyear": (day_of_year // 7 + 1).astype(np.int8),
    }


def _lineorder_blocks(sf: float, seed: int):
    n_orders = table_rows("lineorder_orders", sf)
    n_cust = table_rows("customer", sf)
    n_supp = table_rows("supplier", sf)
    n_part = table_rows("part", sf)
    first = int(FIRST_DAY.astype(np.int64))
    last = int(LAST_ORDER_DAY.astype("datetime64[D]").astype(np.int64))
    rng = np.random.default_rng([seed, 14])
    for o0 in range(0, n_orders, BLOCK_ORDERS):
        m = min(BLOCK_ORDERS, n_orders - o0)
        lines = rng.integers(1, 8, m)
        odate = datekey(rng.integers(first, last + 1, m)
                        .astype("datetime64[D]"))
        custkey = rng.integers(1, n_cust + 1, m)
        k = int(lines.sum())
        partkey = rng.integers(1, n_part + 1, k)
        suppkey = rng.integers(1, n_supp + 1, k)
        quantity = rng.integers(1, 51, k)
        discount = rng.integers(0, 11, k)
        price = retail_price_cents(partkey)
        extended = quantity * price
        yield {
            "lo_orderdate": np.repeat(odate, lines).astype(np.int32),
            "lo_custkey": np.repeat(custkey, lines).astype(np.int32),
            "lo_partkey": partkey.astype(np.int32),
            "lo_suppkey": suppkey.astype(np.int32),
            "lo_quantity": quantity.astype(np.int8),
            "lo_extendedprice": extended.astype(np.int32),
            "lo_discount": discount.astype(np.int8),
            # dbgen: revenue = extendedprice * (100 - discount) / PENNIES,
            # supplycost = 6 * retail price / 10, both integer divisions
            "lo_revenue": (extended * (100 - discount) // 100
                           ).astype(np.int32),
            "lo_supplycost": (6 * price // 10).astype(np.int32),
        }
