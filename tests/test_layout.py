"""Workload-adaptive layout engine + compressed cold tier (ISSUE 10).

Acceptance contract:

- the autotuner CHOOSES {dictionary vs direct encoding, residency
  priority/tier, tile-size bucket} per column from observed stats, and
  the decisions are visible on /status and in
  INFORMATION_SCHEMA.TIDB_TPU_COLUMN_LAYOUT;
- a table whose columns exceed the hot-tier byte cap answers
  Q1/Q6-shaped aggregations, TopN and joins correctly with ZERO
  full-table host reloads after warmup: cold columns are device-resident
  compressed blocks decoded in-register (one `copr.device.execute`, no
  `copr.transfer` span on the steady state) — metric-asserted via
  layout_cold_{hits,loads,promotions,demotions}_total;
- ByteCapCache eviction is value-weighted: lowest-priority victims
  demote to the cold tier before being dropped;
- the chaos site `layout/decompress` fails cold access over to the hot
  tier with identical results;
- layout-class re-tunes are rate-limited (no recompile storms).
"""

import os

import numpy as np
import pytest

from tidb_tpu.chunk import Column
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain
from tidb_tpu.store.fault import always, failpoint
from tidb_tpu.types import ty_int, ty_string

N = 20_000


def _mk_domain(n=N, seed=7):
    d = Domain()
    s = d.new_session()
    s.execute("create table li (a bigint, b bigint, f double,"
              " c varchar(8))")
    s.execute("create table dim (id bigint, nm varchar(8))")
    rng = np.random.default_rng(seed)
    t = d.catalog.info_schema().table("test", "li")
    tags = np.array([f"t{i}" for i in range(6)], dtype=object)
    d.storage.table(t.id).bulk_load_arrays([
        rng.integers(0, 40, n, dtype=np.int64),        # low range: packable
        rng.integers(0, 10**12, n, dtype=np.int64),    # high NDV: direct/hot
        rng.choice([0.01, 0.02, 0.05, 0.07], n),       # low-NDV float
        tags[rng.integers(0, 6, n)],                   # dict string
    ], ts=d.storage.current_ts())
    td = d.catalog.info_schema().table("test", "dim")
    d.storage.table(td.id).bulk_load_arrays([
        np.arange(40, dtype=np.int64),
        np.array([f"n{i % 4}" for i in range(40)], dtype=object),
    ], ts=d.storage.current_ts())
    s.execute("analyze table li")
    return d, s


@pytest.fixture
def layout_env(monkeypatch):
    """Fast re-tunes + guaranteed restoration of the hot cap, tiers and
    tuner state (the LAYOUT engine and caches are process-global).

    EVERY env knob the layout engine reads (`TIDB_TPU_HBM_BYTES`,
    `TIDB_TPU_LAYOUT`, the cache capacities) is snapshotted here and
    restored on teardown — tests mutating layout state outside this
    fixture were a known cross-test flake source (ISSUE 12 hygiene)."""
    from tidb_tpu.copr.parallel import MESH_CACHE
    from tidb_tpu.layout import LAYOUT, coldtier

    monkeypatch.setenv("TIDB_TPU_LAYOUT_RETUNE_S", "0")
    old_cap = MESH_CACHE._c.capacity
    saved = {k: os.environ.get(k)
             for k in ("TIDB_TPU_HBM_BYTES", "TIDB_TPU_LAYOUT")}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    MESH_CACHE._c.capacity = old_cap
    MESH_CACHE.clear()
    coldtier.clear()
    LAYOUT.reset()


def _cpu(sess, sql):
    sess.execute("set tidb_use_tpu = 0")
    try:
        return sess.query(sql)
    finally:
        sess.execute("set tidb_use_tpu = 1")


def _approx_rows(got, want, ctx=""):
    assert len(got) == len(want), (ctx, len(got), len(want))
    for ra, rb in zip(sorted(got, key=str), sorted(want, key=str)):
        for a, b in zip(ra, rb):
            if isinstance(a, float) or isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9), (ctx, ra, rb)
            else:
                assert a == b, (ctx, ra, rb)


def _spans(tr, name):
    out = []

    def walk(sp):
        if sp.name == name:
            out.append(sp)
        for ch in sp.children:
            walk(ch)

    walk(tr.root)
    return out


# ---------------------------------------------------------------------------
# autotuner decisions (unit)
# ---------------------------------------------------------------------------


def test_autotuner_decisions(layout_env):
    from tidb_tpu.layout import LAYOUT, set_hot_cap_bytes

    d, s = _mk_domain()
    store = d.storage.table(
        d.catalog.info_schema().table("test", "li").id)
    # no pressure: everything hot, pow2 tiling
    set_hot_cap_bytes(8 << 30)
    for ci in range(store.n_cols):
        p = LAYOUT.plan_for(store, ci)
        assert p.tier == "hot" and p.tile_bucket == "pow2", (ci, p)
    # squeeze below the table's wire bytes: packable columns flip cold,
    # the un-packable high-NDV column stays hot, tiling goes exact
    set_hot_cap_bytes(100_000)
    pa = LAYOUT.plan_for(store, 0)
    pb = LAYOUT.plan_for(store, 1)
    pf = LAYOUT.plan_for(store, 2)
    pc = LAYOUT.plan_for(store, 3)
    assert pa.tier == "cold" and pa.encoding == "dict" and pa.bits == 8
    assert pb.tier == "hot" and pb.encoding == "direct" and pb.bits == 0
    assert pf.tier == "cold" and 0 < pf.bits <= 4
    assert pc.tier == "cold" and pc.encoding == "dict"
    assert pa.tile_bucket == "exact"
    # residency priority follows observed usage (keys weigh double)
    before = LAYOUT.priority(store.store_uid, 0)
    LAYOUT.observe(store, 0, "agg_key")
    LAYOUT.observe(store, 0, "scan")
    assert LAYOUT.priority(store.store_uid, 0) >= before + 3.0


def test_pack_roundtrip(layout_env):
    import jax

    from tidb_tpu.copr.fusion import decode_packed
    from tidb_tpu.layout.coldtier import pack_codes

    rng = np.random.default_rng(3)
    for bits in (1, 2, 4, 8):
        n = 4096
        codes = rng.integers(0, 1 << bits, n).astype(np.uint8)
        packed = pack_codes(codes, bits)
        dict_vals = (np.arange(1 << bits, dtype=np.int64) * 3 + 5)
        got = jax.jit(
            lambda p, dv: decode_packed(p, dv, bits, n))(packed, dict_vals)
        np.testing.assert_array_equal(
            np.asarray(got), dict_vals[codes.astype(np.int64)])


def test_bytecap_value_weighted_eviction():
    from tidb_tpu.copr.cache import ByteCapCache

    class A:
        def __init__(self, nb):
            self.nbytes = nb

    prio = {"a": 5.0, "b": 1.0, "c": 3.0}
    demoted = []
    c = ByteCapCache(250)
    c.set_policy(priority_fn=lambda k: prio[k[0]],
                 demote_fn=lambda k, v: demoted.append(k[0]))
    c.get_or_load(("a",), lambda: (A(100),))
    c.get_or_load(("b",), lambda: (A(100),))
    # inserting c (100b) overflows: the LOWEST-priority resident ("b")
    # is the victim and flows through the demote hook, not plain drop
    c.get_or_load(("c",), lambda: (A(100),))
    assert demoted == ["b"]
    assert c.peek(("a",)) is not None and c.peek(("b",)) is None


# ---------------------------------------------------------------------------
# cold-tier parity corpus (table > byte cap; dict + direct + delta)
# ---------------------------------------------------------------------------

CORPUS = (
    # Q1 shape: dense agg over packed int key with packed-float filter
    "select a, count(*), sum(b) from li where f < 0.04 group by a",
    # Q6 shape: scalar agg over two cold columns
    "select sum(f) from li where a < 10",
    # sort-mode grouped agg over the dict string column
    "select c, count(*), min(f) from li group by c",
    # topn keyed on a cold column
    "select b from li order by f desc, b desc limit 7",
    # filter stream (cold predicate, hot output column)
    "select b from li where a = 3 and f < 0.02",
)


def test_cold_tier_parity_and_single_dispatch(layout_env):
    from tidb_tpu.layout import set_hot_cap_bytes

    d, s = _mk_domain()
    # delta overlay rides along: committed DML over the cold-pressured
    # base must still merge through the host delta path
    s.execute("insert into li values (3, 77, 0.01, 't1'),"
              " (999, 88, 0.07, 't2')")
    s.execute("delete from li where b = 77 and a = 3 and f = 0.01")
    want = [_cpu(s, q) for q in CORPUS]
    m0 = REGISTRY.snapshot()
    set_hot_cap_bytes(170_000)  # < table wire bytes: b stays hot, rest cold
    for q, w in zip(CORPUS, want):
        _approx_rows(s.query(q), w, q)
        _approx_rows(s.query(q), w, q + " (steady)")  # cold HITS
    m1 = REGISTRY.snapshot()
    assert m1.get("layout_cold_loads_total", 0) > m0.get(
        "layout_cold_loads_total", 0)
    assert m1.get("layout_cold_hits_total", 0) > m0.get(
        "layout_cold_hits_total", 0)
    # steady state: ONE fused dispatch, ZERO host->device transfers —
    # the cold columns are served from device-resident compressed blocks
    s.execute("trace " + CORPUS[0])
    tr = s.last_trace
    assert len(_spans(tr, "copr.device.execute")) == 1
    assert len(_spans(tr, "copr.transfer")) == 0
    # decisions surface in INFORMATION_SCHEMA
    rows = s.query(
        "select column_name, tier, encoding from"
        " information_schema.tidb_tpu_column_layout where tier = 'cold'")
    assert {r[0] for r in rows} >= {"a", "f", "c"}


def test_cold_join_parity(layout_env):
    from tidb_tpu.layout import set_hot_cap_bytes

    d, s = _mk_domain()
    q = ("select nm, count(*), sum(f) from li join dim on a = id"
         " where f < 0.06 group by nm")
    want = _cpu(s, q)
    set_hot_cap_bytes(170_000)
    _approx_rows(s.query(q), want, q)
    _approx_rows(s.query(q), want, q + " (steady)")


def test_fixed_layout_comparator(layout_env, monkeypatch):
    # TIDB_TPU_LAYOUT=0: the pre-layout behavior — everything hot, no
    # cold traffic, results identical (the bench's comparator leg)
    from tidb_tpu.layout import set_hot_cap_bytes

    d, s = _mk_domain()
    q = CORPUS[0]
    want = _cpu(s, q)
    set_hot_cap_bytes(170_000)
    monkeypatch.setenv("TIDB_TPU_LAYOUT", "0")
    m0 = REGISTRY.get("layout_cold_loads_total")
    _approx_rows(s.query(q), want, q)
    assert REGISTRY.get("layout_cold_loads_total") == m0


# ---------------------------------------------------------------------------
# demotion / promotion
# ---------------------------------------------------------------------------


def test_eviction_demotes_then_promotes(layout_env):
    from tidb_tpu.copr.parallel import MESH_CACHE
    from tidb_tpu.layout import COLD_CACHE, set_hot_cap_bytes

    d, s = _mk_domain(n=8192)
    s2 = d.new_session()
    s2.execute("create table other (x bigint, y bigint)")
    rng = np.random.default_rng(5)
    to = d.catalog.info_schema().table("test", "other")
    d.storage.table(to.id).bulk_load_arrays([
        rng.integers(0, 30, 65536, dtype=np.int64),
        rng.integers(0, 10**12, 65536, dtype=np.int64),
    ], ts=d.storage.current_ts())
    q_li = "select a, count(*), min(f) from li group by a"
    want_li = _cpu(s, q_li)
    # cap fits ONE working set: li's columns load hot, then `other`'s
    # big direct column squeezes the hot tier — the packable li column
    # must DEMOTE to cold, not drop
    set_hot_cap_bytes(560_000)
    _approx_rows(s.query(q_li), want_li, "warm")
    m0 = REGISTRY.snapshot()
    s.query("select x, count(*), sum(y) from other group by x")
    m1 = REGISTRY.snapshot()
    assert m1.get("layout_cold_demotions_total", 0) > m0.get(
        "layout_cold_demotions_total", 0)
    # layout follow-up (e): demotion re-encodes ON DEVICE and reads
    # back only the packed codes (8-64x smaller than raw values)
    assert m1.get("layout_demote_code_readback_bytes", 0) > m0.get(
        "layout_demote_code_readback_bytes", 0)
    assert len(COLD_CACHE) > 0
    # the demoted column now serves COLD (hit, no reload), still correct
    _approx_rows(s.query(q_li), want_li, "cold after demote")
    m2 = REGISTRY.snapshot()
    assert m2.get("layout_cold_hits_total", 0) > m1.get(
        "layout_cold_hits_total", 0)
    # capacity returns: the tuner promotes the column back to hot
    set_hot_cap_bytes(8 << 30)
    MESH_CACHE.clear()
    _approx_rows(s.query(q_li), want_li, "promoted")
    m3 = REGISTRY.snapshot()
    assert m3.get("layout_cold_promotions_total", 0) > m2.get(
        "layout_cold_promotions_total", 0)


def test_retune_rate_limit(layout_env, monkeypatch):
    from tidb_tpu.layout import LAYOUT, set_hot_cap_bytes

    # layout_env snapshots/restores the env knobs and caches; this test
    # only needs a SLOW retune window on top of it
    monkeypatch.setenv("TIDB_TPU_LAYOUT_RETUNE_S", "3600")
    d, s = _mk_domain(n=4096)
    store = d.storage.table(
        d.catalog.info_schema().table("test", "li").id)
    set_hot_cap_bytes(10_000)
    p0 = LAYOUT.plan_for(store, 0)
    assert p0.tier == "cold"
    # pressure vanishes immediately: the class flip is SUPPRESSED
    # (rate limit) — no refingerprint storm from a flapping signal
    m0 = REGISTRY.get("layout_retunes_suppressed_total")
    set_hot_cap_bytes(8 << 30)
    p1 = LAYOUT.plan_for(store, 0)
    assert p1.tier == "cold"  # kept the old class
    assert REGISTRY.get("layout_retunes_suppressed_total") > m0


# ---------------------------------------------------------------------------
# chaos: layout/decompress fails over to the hot tier
# ---------------------------------------------------------------------------


def test_chaos_decompress_parity(layout_env):
    from tidb_tpu.layout import set_hot_cap_bytes

    d, s = _mk_domain()
    q = CORPUS[1]
    want = _cpu(s, q)
    set_hot_cap_bytes(170_000)
    m0 = REGISTRY.get("layout_cold_fallbacks_total")
    with failpoint("layout/decompress", always(RuntimeError("chaos"))):
        _approx_rows(s.query(q), want, "decompress chaos")
    assert REGISTRY.get("layout_cold_fallbacks_total") > m0
    # disarmed: the same query comes back on the cold tier
    h0 = REGISTRY.get("layout_cold_hits_total") + REGISTRY.get(
        "layout_cold_loads_total")
    _approx_rows(s.query(q), want, "recovered")
    assert REGISTRY.get("layout_cold_hits_total") + REGISTRY.get(
        "layout_cold_loads_total") > h0


# ---------------------------------------------------------------------------
# /status section
# ---------------------------------------------------------------------------


def test_status_section(layout_env):
    from tidb_tpu.layout import set_hot_cap_bytes, status_section

    d, s = _mk_domain(n=4096)
    set_hot_cap_bytes(10_000)
    s.query("select count(*) from li where a < 5")
    sec = status_section()
    assert sec["enabled"] and sec["hot_cap_bytes"] == 10_000
    assert any(c["tier"] == "cold" for c in sec["columns"])
    assert "layout_cold_loads_total" in sec["metrics"]


# ---------------------------------------------------------------------------
# vectorized row-loop replacements (lint allowlist 9 -> 7)
# ---------------------------------------------------------------------------


def test_group_indices_multicol_vectorized():
    from tidb_tpu.copr.aggstate import group_indices

    ga = Column(ty_int(), np.array([3, 1, 3, 2, 1, 3]),
                np.array([True, True, False, True, True, True]))
    gb = Column(ty_string(),
                np.array(["x", "y", "x", "x", "y", "x"], dtype=object))
    gidx, first, G = group_indices([ga, gb])
    # first-appearance group ids, NULL is its own group — the old
    # row-at-a-time dict contract; the keys are the columns taken at
    # each group's first row
    assert G == 4
    assert gidx.tolist() == [0, 1, 2, 3, 1, 0]
    assert first.tolist() == [0, 1, 2, 3]
    keys = list(zip(ga.take(first).to_pylist(), gb.take(first).to_pylist()))
    assert keys == [(3, "x"), (1, "y"), (None, "x"), (2, "x")]


def test_unique_key_sets_vectorized():
    d = Domain()
    s = d.new_session()
    s.execute("create table u (a bigint, b varchar(8), c bigint,"
              " unique key uk (a, b))")
    s.execute("insert into u values (1, 'x', 10), (2, 'y', 20),"
              " (3, null, 30)")
    # NULL key parts never collide (MySQL unique semantics)
    s.execute("insert into u values (3, null, 31)")
    with pytest.raises(Exception, match="[Dd]uplicate"):
        s.execute("insert into u values (1, 'x', 99)")
    # update onto an existing key also trips the columnar key set
    with pytest.raises(Exception, match="[Dd]uplicate"):
        s.execute("update u set a = 2, b = 'y' where c = 10")
    assert s.query("select count(*) from u")[0][0] == 4
