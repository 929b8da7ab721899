"""A configuration's tables come from the generator it names
(`generators/<name>.py`), through `harness.serve.load` and nothing else.

- Every listed configuration's generator resolves by name and makes every
  column the configuration lists.
- What `serve.load` hands the program is pinned: for each load recorded
  in `recorded/loads.json` (the four TPC-H configurations at SF 0.01 on
  two seeds, and the three-table one at a scale of two blocks of orders),
  the sequence of `bulk_load_arrays` calls (table, rows, the dictionaries)
  and a digest of every array, recorded from the loader as it stood
  before generators were found by name.  A later change to a generator or
  to the loader that moves one number of a cell's data fails here.  The
  pins are the file's keys, so a configuration added later needs no entry
  there.
- A configuration over a schema the TPC-H generator does not make (a star
  of one fact and one unique-key dimension, from a generator this file
  provides) loads, is served over the wire and equals a numpy reference;
  a column or a table its generator does not make stops the load with an
  error that names them.
"""

import hashlib
import importlib
import json
import os
import re
import sys
import types
from decimal import Decimal

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RECORDED = os.path.join(HERE, "recorded", "loads.json")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]
PIN_KEY = re.compile(r"(\S+) sf=([0-9.]+) seed=([0-9]+)")


def pin_key(name, sf, seed) -> str:
    return f"{name} sf={sf} seed={seed}"


def _pins() -> list:
    """(configuration, scale factor, seed) of every recorded load; SF 0.36
    of `tpch-sf1-q3` is 540,000 orders, two blocks."""
    with open(RECORDED) as f:
        keys = list(json.load(f))
    pins = []
    for key in keys:
        name, sf, seed = PIN_KEY.fullmatch(key).groups()
        pins.append((name, float(sf), int(seed)))
        assert pin_key(*pins[-1]) == key, key
    return pins


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _digest(a) -> str:
    a = np.ascontiguousarray(a)
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:24]


def loads_of(load, config, seed, sf) -> list:
    """Every `bulk_load_arrays` call `load(config, seed, sf, log)` makes,
    in order: [table, rows, {column: digest}, {column: dictionary}]."""
    from tidb_tpu.store.blockstore import TableStore

    calls = []
    sound = TableStore.bulk_load_arrays

    def recording(self, arrays, valids=None, ts=0, dictionaries=None):
        assert valids is None
        names = [c.name for c in self.cols]
        calls.append((self.table_id, len(arrays[0]),
                      {n: _digest(a) for n, a in zip(names, arrays)},
                      {names[i]: list(d)
                       for i, d in sorted((dictionaries or {}).items())}))
        return sound(self, arrays, valids, ts, dictionaries)

    mp = pytest.MonkeyPatch()
    mp.setattr(TableStore, "bulk_load_arrays", recording)
    try:
        domain, _tables, _ = load(config, seed, sf, lambda _note: None)
    finally:
        mp.undo()
    schema = domain.catalog.info_schema()
    name_of = {schema.table("test", t).id: t for t in config["tables"]}
    return [[name_of[tid], rows, digests, dicts]
            for tid, rows, digests, dicts in calls]


# ---- every listed configuration's generator ---------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_the_generator_resolves_by_name_and_makes_every_listed_column(name):
    config = _config(name)
    gen = importlib.import_module(f"generators.{config['generator']}")
    want = config["tables"]
    made = {}
    for table, block in gen.blocks(set(want), 0.001, 2**31 + 1):
        assert table in want, table
        made.setdefault(table, set()).update(block)
    assert set(made) == set(want)
    for table, spec in want.items():
        assert set(spec["columns"]) <= made[table], table
        coded = [c for c in spec["columns"] if c in gen.DICTIONARIES]
        for c in coded:
            assert gen.DICTIONARIES[c] == sorted(gen.DICTIONARIES[c]), c


@pytest.mark.parametrize("name, sf, seed", _pins())
def test_the_loader_hands_the_program_the_recorded_arrays(name, sf, seed):
    from harness import serve

    with open(RECORDED) as f:
        recorded = json.load(f)[pin_key(name, sf, seed)]
    got = loads_of(serve.load, _config(name), seed, sf)
    assert [c[:2] for c in got] == [c[:2] for c in recorded]
    assert got == recorded


# ---- a schema the TPC-H generator does not make -----------------------------

STAR_DICTIONARIES = {"f_mode": ["AIR", "MAIL", "RAIL", "SHIP", "TRUCK"]}
DIM_ROWS, FACT_BLOCK = 1000, 10_000


def _star_blocks(tables, sf, seed):
    """A dimension of unique keys 1..DIM_ROWS, then the fact table in two
    blocks whose keys all name a dimension row."""
    n_dim = int(DIM_ROWS * sf)
    rng = np.random.default_rng([seed, 7])
    if "dim" in tables:
        yield "dim", {"d_key": np.arange(1, n_dim + 1, dtype=np.int32),
                      "d_year": rng.integers(1992, 1999, n_dim,
                                             dtype=np.int16)}
    for _ in range(2):
        m = int(FACT_BLOCK * sf)
        block = {"f_dkey": rng.integers(1, n_dim + 1, m, dtype=np.int32),
                 "f_mode": rng.integers(0, 5, m, dtype=np.int8),
                 "f_revenue": rng.integers(100, 10_000_000, m,
                                           dtype=np.int32)}
        if "fact" in tables:
            yield "fact", block


STAR = {
    "name": "star-selfcheck",
    "generator": "star_selfcheck",
    "scale_factor": 1,
    "chips": 1,
    "regions": 4,
    "tables": {
        "dim": {"ddl": "create table dim (d_key bigint primary key, "
                       "d_year bigint)",
                "columns": ["d_key", "d_year"]},
        "fact": {"ddl": "create table fact (f_dkey bigint, f_mode "
                        "varchar(8), f_revenue decimal(15,2))",
                 "columns": ["f_dkey", "f_mode", "f_revenue"]},
    },
    "analyze": ["dim", "fact"],
}
STAR_SQL = ("select d_year, sum(f_revenue) as revenue, count(*) as n "
            "from fact join dim on f_dkey = d_key where f_mode = '{mode}' "
            "group by d_year order by d_year")


def _star_reference(tables, mode):
    dim, fact = tables["dim"], tables["fact"]
    sel = fact["f_mode"] == STAR_DICTIONARIES["f_mode"].index(mode)
    pos = np.searchsorted(dim["d_key"], fact["f_dkey"][sel])
    year = dim["d_year"][pos].astype(np.int64)
    revenue = fact["f_revenue"][sel].astype(np.int64)
    return [(int(y), Decimal(int(revenue[year == y].sum())).scaleb(-2),
             int((year == y).sum())) for y in np.unique(year)]


@pytest.fixture
def star_generator(monkeypatch):
    """`generators.star_selfcheck`, found by name as a file of
    `generators/` would be."""
    importlib.import_module("generators")
    mod = types.ModuleType("generators.star_selfcheck")
    mod.DICTIONARIES = STAR_DICTIONARIES
    mod.blocks = _star_blocks
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


@pytest.mark.parametrize("seed", (3, 2**31 + 47))
def test_a_star_from_its_own_generator_is_served_as_its_reference(
        star_generator, seed):
    from harness import compare, serve

    domain, tables, _ = serve.load(STAR, seed, 1, lambda _note: None)
    assert len(tables["fact"]["f_dkey"]) == 2 * FACT_BLOCK
    sess = domain.new_session()
    sql = STAR_SQL.format(mode="RAIL")
    ops = [r[0] for r in sess.execute("explain " + sql)[0].rows]
    assert any("DeviceJoinReader" in op for op in ops), ops
    srv = serve.Served(domain)
    try:
        for engine in (1, 0):
            cli = srv.client()
            try:
                cli.query(f"set tidb_use_tpu = {engine}")
                for mode in STAR_DICTIONARIES["f_mode"]:
                    _types, got = cli.query(STAR_SQL.format(mode=mode))
                    want = _star_reference(tables, mode)
                    assert len(want) == 7
                    assert compare.same_rows(got, want), (engine, mode,
                                                          got[:2], want[:2])
            finally:
                cli.close()
    finally:
        srv.stop()


def test_a_column_its_generator_does_not_make_is_named(star_generator):
    from harness import serve

    config = json.loads(json.dumps(STAR))
    config["tables"]["fact"]["columns"].append("f_discount")
    config["tables"]["fact"]["ddl"] = config["tables"]["fact"]["ddl"][:-1] \
        + ", f_discount bigint)"
    with pytest.raises(ValueError) as err:
        serve.load(config, 5, 0.1, lambda _note: None)
    for word in ("star_selfcheck", "'fact'", "'f_discount'"):
        assert word in str(err.value)


def test_a_table_its_generator_does_not_make_is_named(star_generator):
    from harness import serve

    config = json.loads(json.dumps(STAR))
    config["tables"]["part"] = {
        "ddl": "create table part (p_partkey bigint primary key)",
        "columns": ["p_partkey"]}
    with pytest.raises(ValueError) as err:
        serve.load(config, 5, 0.1, lambda _note: None)
    for word in ("star_selfcheck", "'part'"):
        assert word in str(err.value)
