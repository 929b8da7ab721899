"""Whole-fragment kernel fusion (copr/fusion.py): parity + span counts.

The fusion contract (ISSUE 7 acceptance):

- every fragment shape — filter-only, filter+project, dense agg, scalar
  agg, sort agg, topN, IN-lists, delta-overlay fallback, MPP-fused —
  returns results identical to the CPU oracle;
- steady-state fragments execute as exactly ONE XLA launch per mesh
  dispatch: one `copr.device.execute` span, one packed `copr.readback`,
  zero intermediate host readbacks;
- multi-range fragments run in the same single dispatch (range bounds
  are runtime slots, not program shape) and share one compiled program
  with single-range fragments;
- the chaos site `copr/fusion_split` forces the region splitter to cut
  at every executor boundary in turn and parity still holds (the host
  tail interprets the peeled suffix — never fail the query).
"""

import numpy as np
import pytest

from tidb_tpu.copr.jax_eval import JaxUnsupported
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain
from tidb_tpu.store.fault import failpoint

N = 20_000


@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    s.execute("create table ft (k bigint primary key, g bigint, x double,"
              " c varchar(8), j bigint)")
    rng = np.random.default_rng(23)
    t = d.catalog.info_schema().table("test", "ft")
    tags = np.array([f"t{i:02d}" for i in range(12)], dtype=object)
    d.storage.table(t.id).bulk_load_arrays([
        np.arange(N, dtype=np.int64),
        rng.integers(0, 5, N, dtype=np.int64),
        rng.uniform(0, 100, N),
        tags[rng.integers(0, 12, N)],
        rng.integers(0, 9000, N, dtype=np.int64),  # join key (see MPP test)
    ], ts=d.storage.current_ts())
    s.execute("analyze table ft")
    return s


CORPUS = (
    # filter-only
    "select k from ft where x < 20",
    # filter + device projection
    "select k, x * 2 + 1 from ft where x < 20",
    # dense agg (group keys with known small cardinality)
    "select g, sum(x), count(*), min(x), max(x), avg(x) from ft group by g",
    # scalar agg
    "select sum(x), count(*) from ft where k < 15000",
    # sort-mode agg (float group key: dense codes would truncate)
    "select g, min(k) from ft where x < 60 group by g, c",
    # topn
    "select k, x from ft order by x desc limit 7",
    # IN-list (pow2-bucketed hoisted slots)
    "select count(*) from ft where g in (1, 2, 3)",
    # string dict predicate + agg
    "select count(*), sum(x) from ft where c = 't03'",
)


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

    def walk(s):
        if s.name == name:
            out.append(s)
        for c in s.children:
            walk(c)

    walk(tr.root)
    return out


# ---------------------------------------------------------------------------
# fused-vs-oracle parity across the corpus
# ---------------------------------------------------------------------------


def test_fused_corpus_parity(sess):
    sess.execute("set tidb_use_tpu = 1")
    for sql in CORPUS:
        _approx_rows(sess.query(sql), _cpu(sess, sql), sql)


def test_fused_parity_with_delta_overlay(sess):
    """Committed delta rows ride the CPU interpreter and merge with the
    fused base scan — parity must hold across the overlay."""
    sess.execute("insert into ft values (20001, 1, 50.5, 't01', 11),"
                 " (20002, 4, 3.25, 't07', 222)")
    sess.execute("delete from ft where k = 7")
    try:
        for sql in CORPUS:
            _approx_rows(sess.query(sql), _cpu(sess, sql), f"delta: {sql}")
    finally:
        sess.execute("delete from ft where k > 20000")
        sess.execute("insert into ft values (7, 2, 41.5, 't05', 7)")


# ---------------------------------------------------------------------------
# span-count invariants: one XLA launch per mesh dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sql", [
    "select g, sum(x), count(*), avg(x) from ft group by g",   # Q1 shape
    "select sum(x) from ft where x < 50 and k < 18000",        # Q6 shape
])
def test_steady_state_is_one_device_execute_span(sess, sql):
    sess.execute("set tidb_use_tpu = 1")
    sess.query(sql)            # warm: compile + transfer
    sess.query(sql)            # steady state
    tr = sess.last_trace
    exe = _spans(tr, "copr.device.execute")
    assert len(exe) == 1, [s.name for s in exe]
    # zero intermediate host readbacks: ONE packed readback carries the
    # whole result, nothing crosses the link between fused phases
    rb = _spans(tr, "copr.readback")
    assert len(rb) == 1, len(rb)
    # steady state hits the program cache (no recompiles)
    hits = [s for s in _spans(tr, "copr.compile")
            if (s.attrs or {}).get("cache") == "hit"]
    assert hits
    # ... and no transfers: scan data is device-resident
    assert not _spans(tr, "copr.transfer")


def test_multirange_single_dispatch_shares_program(sess):
    """A 3-range request runs in the SAME single fused dispatch and the
    SAME compiled program as a 1-range one (range bounds are runtime
    parameter slots, never program shape)."""
    from tidb_tpu.copr import parallel as pl
    from tidb_tpu.copr.ir import DAG
    from tidb_tpu.parser import parse_one
    from tidb_tpu.store.kv import CopRequest, KeyRange

    d = sess.domain
    t = d.catalog.info_schema().table("test", "ft")
    store = d.storage.table(t.id)
    phys = sess._plan(parse_one("select sum(x), count(*) from ft"))

    def find_dag(p):
        if getattr(p, "dag", None) is not None:
            return p.dag
        for c in getattr(p, "children", ()) or ():
            r = find_dag(c)
            if r is not None:
                return r
        return None

    dag = find_dag(phys).to_dict()
    ts = d.storage.current_ts()
    spans3 = [(0, 3000), (7000, 7500), (12000, N)]

    def run(ranges):
        req = CopRequest(
            dag=dag, ranges=[KeyRange(t.id, a, b) for a, b in ranges],
            ts=ts, concurrency=1, keep_order=False, streaming=False,
            engine="tpu")
        out = pl.try_run_mesh(d.storage, req)
        assert out is not None, getattr(req, "mesh_reject_reason", None)
        chunks = list(out)
        assert len(chunks) == 1
        c = chunks[0]
        # partial-agg layout: [sum state, count state]
        return float(c.col(0).data[0]), int(c.col(1).data[0])

    x = np.asarray(store.base_chunk([2], 0, store.base_rows).col(0).data)
    deleted, inserted = store.delta_overlay(ts, 0, 1 << 62)

    def expected(ranges):
        tot, cnt = 0.0, 0
        for a, b in ranges:
            bb = min(b, store.base_rows)
            if a < bb:
                idx = np.arange(a, bb)
                keep = ~np.isin(idx, sorted(deleted))
                tot += float(x[a:bb][keep].sum())
                cnt += int(keep.sum())
            for h, row in inserted.items():
                if a <= h < b:
                    tot += float(row[2])
                    cnt += 1
        return tot, cnt

    s1, c1 = run([(0, N)])
    n0 = len(pl._COMPILED)
    s3, c3 = run(spans3)
    assert len(pl._COMPILED) == n0, \
        "range-count change recompiled the fused program"
    w3, n3 = expected(spans3)
    assert s3 == pytest.approx(w3) and c3 == n3
    w1, n1 = expected([(0, N)])
    assert s1 == pytest.approx(w1) and c1 == n1


# ---------------------------------------------------------------------------
# the fallback ladder: chaos-split at every region boundary
# ---------------------------------------------------------------------------


def test_chaos_split_at_every_boundary_keeps_parity(sess):
    """Force the splitter to cut the fused region at each executor
    boundary in turn: the host tail serves the peeled suffix with
    identical results, and the query NEVER fails."""
    sess.execute("set tidb_use_tpu = 1")
    want = {sql: _cpu(sess, sql) for sql in CORPUS}
    for cut_at in (2, 3, 4):
        def force_split(cut=None, boundary=None, _at=cut_at, **ctx):
            if cut is not None and cut >= _at:
                raise JaxUnsupported(f"chaos split at cut {cut}")

        with failpoint("copr/fusion_split", force_split):
            for sql in CORPUS:
                _approx_rows(sess.query(sql), want[sql],
                             f"split@{cut_at}: {sql}")


def test_split_region_runs_device_head_plus_host_tail(sess):
    """A forced split below the aggregation leaves scan+selection fused
    on device and interprets the agg host-side: fusion_splits_total
    grows and results match."""
    sql = "select g, sum(x), count(*) from ft where x < 30 group by g"
    want = _cpu(sess, sql)

    def split_below_agg(cut=None, boundary=None, **ctx):
        if boundary == "AggregationIR":
            raise JaxUnsupported("chaos: agg unfusable")

    s0 = REGISTRY.get("fusion_splits_total")
    with failpoint("copr/fusion_split", split_below_agg):
        got = sess.query(sql)
    _approx_rows(got, want, sql)
    assert REGISTRY.get("fusion_splits_total") > s0


def test_plan_regions_ladder_unit(sess):
    """plan_regions peels an unfusable suffix and keeps scan-layout
    heads only; an all-unfusable fragment raises with the reason."""
    from tidb_tpu.copr.fusion import plan_regions
    from tidb_tpu.copr.ir import DAG
    from tidb_tpu.planner import build  # noqa: F401  (plan machinery)

    d = sess.domain
    t = d.catalog.info_schema().table("test", "ft")
    table = d.storage.table(t.id)
    phys = sess._plan(__import__("tidb_tpu.parser", fromlist=["parse_one"])
                      .parse_one(
        "select g, sum(x) from ft where x < 30 group by g"))

    def dags(p, acc):
        if getattr(p, "dag", None) is not None:
            acc.append(p.dag)
        for c in getattr(p, "children", ()) or ():
            dags(c, acc)
        return acc

    dag = DAG.from_dict(dags(phys, [])[0].to_dict())
    plan = plan_regions(dag, table)
    assert not plan.tail  # fully fused
    # force a split below the agg: head must be scan+selection shaped
    def split(cut=None, boundary=None, **ctx):
        if boundary == "AggregationIR":
            raise JaxUnsupported("forced")

    with failpoint("copr/fusion_split", split):
        plan = plan_regions(dag, table)
    assert plan.tail and plan.an.agg is None
    assert plan.split_reason


# ---------------------------------------------------------------------------
# MPP-fused fragments
# ---------------------------------------------------------------------------


def test_mpp_fused_join_parity_and_span(sess):
    """An MPP shuffle join (scan+filter+exchange+join+partial agg) is
    ONE fused program: parity vs the host hash join and a single
    copr.device.execute inside the mpp.exchange span."""
    d = sess.domain
    sess.execute("create table fo (o_key bigint primary key, o_w double)")
    t = d.catalog.info_schema().table("test", "fo")
    rng = np.random.default_rng(5)
    n_o = 3000
    d.storage.table(t.id).bulk_load_arrays([
        np.arange(n_o, dtype=np.int64),
        rng.uniform(0, 10, n_o),
    ], ts=d.storage.current_ts())
    sess.execute("analyze table fo")
    sql = ("select count(*), sum(x) from ft join fo on j = o_key"
           " where x < 80")
    # (j in [0, 9000), o_key in [0, 3000): ~1/3 of probe rows match;
    # host oracle = allow_mpp off)
    sess.execute("set tidb_use_tpu = 1")
    sess.execute("set tidb_enforce_mpp = 1")
    try:
        m0 = REGISTRY.get("mpp_joins_total")
        got = sess.query(sql)
        served_mpp = REGISTRY.get("mpp_joins_total") > m0
        sess.execute("set tidb_allow_mpp = 0")
        sess.execute("set tidb_enforce_mpp = 0")
        want = sess.query(sql)
        _approx_rows(got, want, sql)
        if served_mpp:
            sess.execute("set tidb_allow_mpp = 1")
            sess.execute("set tidb_enforce_mpp = 1")
            sess.query(sql)
            sess.query(sql)  # steady state
            tr = sess.last_trace
            ex = _spans(tr, "mpp.exchange")
            assert ex, "no exchange span on the MPP rung"
            assert len(_spans(tr, "copr.device.execute")) == 1
    finally:
        sess.execute("set tidb_allow_mpp = 1")
        sess.execute("set tidb_enforce_mpp = 0")


# ---------------------------------------------------------------------------
# serving-layer composition (satellite: LIMIT / IN-list hoisting)
# ---------------------------------------------------------------------------


def test_in_list_lengths_share_program(sess):
    from tidb_tpu.copr import parallel as pl

    sess.execute("set tidb_use_tpu = 1")
    base = "select count(*) from ft where g in ({})"
    sess.query(base.format("0, 1, 2"))   # warm: 3 pads to 4 slots
    n0 = len(pl._COMPILED)
    r4 = sess.query(base.format("1, 2, 3, 4"))
    assert len(pl._COMPILED) == n0, \
        "IN-list length 3 vs 4 compiled two programs"
    _approx_rows(r4, _cpu(sess, base.format("1, 2, 3, 4")), "in4")


def test_microbatch_limits_share_batch_class(sess):
    """`LIMIT 5` and `LIMIT 7` filter statements land in one batch key
    class and return their own exact row counts."""
    from tidb_tpu import serving

    serving.configure(microbatch_window_ms=40.0)
    try:
        import threading

        results = {}

        def run(lim):
            s2 = sess.domain.new_session()
            s2.execute("set tidb_use_tpu = 1")
            results[lim] = s2.query(
                f"select k from ft where x < 90 limit {lim}")

        ts = [threading.Thread(target=run, args=(lim,)) for lim in (5, 7)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(results[5]) == 5 and len(results[7]) == 7
    finally:
        serving.configure(microbatch_window_ms=0.0)


def test_adaptive_window_widens_and_shrinks():
    from tidb_tpu import serving

    serving.configure(microbatch_window_ms=10.0)
    try:
        REGISTRY.set("admission_queue_depth", 0.0)
        idle = serving.effective_window_s()
        assert idle == pytest.approx(0.005)  # shrinks when idle
        REGISTRY.set("admission_queue_depth", 6.0)
        busy = serving.effective_window_s()
        assert busy == pytest.approx(0.040)  # widens under pressure
        REGISTRY.set("admission_queue_depth", 1000.0)
        capped = serving.effective_window_s()
        assert capped == pytest.approx(0.080)  # bounded
        # effective window is exported on /metrics
        assert REGISTRY.get("serving_effective_window_ms") \
            == pytest.approx(80.0)
    finally:
        REGISTRY.set("admission_queue_depth", 0.0)
        serving.configure(microbatch_window_ms=0.0)


# ---------------------------------------------------------------------------
# ISSUE 11 zero-host-tail corpus: computed keys, compound ordering,
# hybrid regions, split-reason labels, the Pallas comparator
# ---------------------------------------------------------------------------

#: shapes that split to a host tail before ISSUE 11 and now fully fuse
HOST_TAIL_CORPUS = (
    # computed string group keys -> device dict-code re-mapping
    "select substr(c, 2, 2), count(*), sum(x) from ft"
    " group by substr(c, 2, 2)",
    "select concat(c, '#'), min(x), max(k) from ft where x < 70"
    " group by concat(c, '#')",
    "select upper(c), count(*) from ft group by upper(c)",
    # multi-column TopN -> packed lexicographic compound key
    "select k, g, x from ft order by g desc, c, k limit 7",
    "select k from ft where x < 50 order by c, k limit 9",
)


def test_host_tail_corpus_fuses_with_parity(sess):
    """The newly-lowered shapes return CPU-oracle results, leave
    fusion_splits_total untouched (zero host tails), and execute as
    exactly ONE copr.device.execute in steady state."""
    sess.execute("set tidb_use_tpu = 1")
    s0 = REGISTRY.get("fusion_splits_total")
    for sql in HOST_TAIL_CORPUS:
        _approx_rows(sess.query(sql), _cpu(sess, sql), sql)
    assert REGISTRY.get("fusion_splits_total") == s0, \
        "a newly-lowered shape still split to a host tail"
    for sql in HOST_TAIL_CORPUS:
        sess.query(sql)
        sess.query(sql)  # steady state
        exe = _spans(sess.last_trace, "copr.device.execute")
        assert len(exe) == 1, (sql, [s.name for s in exe])


def test_host_tail_corpus_vs_unfused_and_pallas_comparators(sess):
    """Parity through BOTH comparators: TIDB_TPU_FUSION=0 (per-tile
    dispatch ladder) and TIDB_TPU_PALLAS=0 (plain-XLA compositions in
    place of the Pallas kernel tier)."""
    import os

    sess.execute("set tidb_use_tpu = 1")
    want = {sql: _cpu(sess, sql) for sql in HOST_TAIL_CORPUS}
    for var in ("TIDB_TPU_FUSION", "TIDB_TPU_PALLAS"):
        prior = os.environ.get(var)
        os.environ[var] = "0"
        try:
            for sql in HOST_TAIL_CORPUS:
                _approx_rows(sess.query(sql), want[sql],
                             f"{var}=0: {sql}")
        finally:
            if prior is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prior


def test_compound_order_split_reason_labelled(sess):
    """An order-by list the packer cannot lower (unbounded float second
    key) still runs — split to a labelled host tail — and the reason
    shows up on the metric, /status-shaped section and
    INFORMATION_SCHEMA.TIDB_TPU_FUSION_SPLITS."""
    sql = "select k from ft where x < 40 order by g, x limit 6"
    want = _cpu(sess, sql)
    r0 = REGISTRY.get("fusion_splits_reason_compound_order_total")
    s0 = REGISTRY.get("fusion_splits_total")
    _approx_rows(sess.query(sql), want, sql)
    assert REGISTRY.get("fusion_splits_total") > s0
    assert REGISTRY.get("fusion_splits_reason_compound_order_total") > r0
    rows = sess.query(
        "select reason, splits from information_schema"
        ".tidb_tpu_fusion_splits")
    by_reason = {r[0]: r[1] for r in rows}
    assert by_reason["compound-order"] >= 1
    assert by_reason["total"] >= sum(
        v for k, v in by_reason.items() if k != "total")


def test_hybrid_projection_head_keeps_device_projection(sess):
    """Hybrid device-partial/host-final regions: a tail AFTER a device
    projection keeps the projection fused (the tail reads the projected
    layout across the boundary) instead of peeling back to scan+sel."""
    import numpy as np

    from tidb_tpu.copr import parallel as pl
    from tidb_tpu.copr.cpu_engine import run_dag_on_chunk
    from tidb_tpu.copr.fusion import plan_regions
    from tidb_tpu.copr.ir import (DAG, ProjectionIR, SelectionIR,
                                  TableScanIR)
    from tidb_tpu.expr.expression import ColumnExpr, Constant, ScalarFunc
    from tidb_tpu.store.kv import CopRequest, KeyRange
    from tidb_tpu.types import FieldType, TypeKind, ty_int

    d = sess.domain
    t = d.catalog.info_schema().table("test", "ft")
    store = d.storage.table(t.id)
    f64 = FieldType(TypeKind.FLOAT)
    i64 = ty_int()
    scan = TableScanIR(t.id, [0, 2], [i64, f64])
    sel = SelectionIR([ScalarFunc(
        "<", [ColumnExpr(1, f64), Constant(30.0, f64)], i64)])
    proj = ProjectionIR([
        ColumnExpr(0, i64),
        ScalarFunc("*", [ColumnExpr(1, f64), Constant(2.0, f64)], f64),
    ])
    # the tail: a selection over the PROJECTED layout (x*2 > 20) — a
    # selection after a projection has no device form, so the splitter
    # must cut here and the head must keep the projection
    tail_sel = SelectionIR([ScalarFunc(
        ">", [ColumnExpr(1, f64), Constant(20.0, f64)], i64)])
    dag = DAG([scan, sel, proj, tail_sel])
    plan = plan_regions(DAG.from_dict(dag.to_dict()), store)
    assert plan.tail and plan.an.projection is not None, \
        "projection peeled out of the hybrid head"
    ts = d.storage.current_ts()
    req = CopRequest(dag=dag.to_dict(),
                     ranges=[KeyRange(t.id, 0, store.base_rows)],
                     ts=ts, concurrency=1, keep_order=False,
                     streaming=False, engine="tpu")
    s0 = REGISTRY.get("fusion_splits_total")
    out = pl.try_run_mesh(d.storage, req)
    assert out is not None, getattr(req, "mesh_reject_reason", None)
    got = [tuple(float(c.col(j).data[i]) for j in range(2))
           for c in out for i in range(c.num_rows)]
    assert REGISTRY.get("fusion_splits_total") > s0
    # oracle: the whole DAG through the CPU interpreter
    base = store.base_chunk([0, 2], 0, store.base_rows)
    ref = run_dag_on_chunk(DAG.from_dict(dag.to_dict()), base)
    want = [tuple(float(ref.col(j).data[i]) for j in range(2))
            for i in range(ref.num_rows)]
    assert sorted(got) == sorted(want)


def test_mesh_agg_overflow_peels_agg_to_host_tail():
    """ROADMAP fusion follow-up (c): a blown sort-agg budget re-enters
    the fused mesh with the AGG peeled to the host tail (scan+selection
    stays device-resident and streamed) instead of dropping the whole
    fragment to the per-tile fan-out rung — parity + mesh_agg_peel
    metric."""
    import os

    from tidb_tpu.session import Domain

    prior = os.environ.get("TIDB_TPU_AGG_OUT")
    os.environ["TIDB_TPU_AGG_OUT"] = "64"
    try:
        d = Domain()
        s = d.new_session()
        s.execute("create table peelt (k bigint, v double, w bigint)")
        t = d.catalog.info_schema().table("test", "peelt")
        rng = np.random.default_rng(5)
        n = 40000
        kvalid = [np.ones(n, np.bool_), None, None]
        kvalid[0][rng.integers(0, n, 500)] = False  # NULLable -> sort agg
        d.storage.table(t.id).bulk_load_arrays(
            [rng.integers(0, 20000, n), rng.uniform(0, 10, n),
             rng.integers(0, 100, n)], kvalid, ts=d.storage.current_ts())
        s.execute("analyze table peelt")
        q = "select k, count(*), sum(v) from peelt where w < 80 group by k"
        m0 = REGISTRY.snapshot().get("mesh_agg_peel_total", 0)
        got = s.query(q)
        assert REGISTRY.snapshot().get("mesh_agg_peel_total", 0) > m0, \
            "sort-agg overflow did not take the agg-peel rung"
        s.execute("set tidb_use_tpu = 0")
        want = s.query(q)
        s.execute("set tidb_use_tpu = 1")

        def key(r):
            return tuple((0, "") if x is None else (1, float(x))
                         for x in r)

        assert sorted(got, key=key) == sorted(want, key=key)
    finally:
        if prior is None:
            os.environ.pop("TIDB_TPU_AGG_OUT", None)
        else:
            os.environ["TIDB_TPU_AGG_OUT"] = prior
