"""Statistics subsystem tests (histograms, CM sketch, selectivity, ANALYZE)."""

import numpy as np
import pytest

from tidb_tpu.session import Domain
from tidb_tpu.statistics import CMSketch, FMSketch, Histogram


class TestHistogram:
    def test_build_and_bounds(self):
        v = np.arange(1000, dtype=np.float64)
        h = Histogram.build(v, null_count=10, n_buckets=16)
        assert h.total == 1000 and h.null_count == 10
        assert h.ndv == 1000
        assert abs(h.less_row_count(500) - 500) < 80
        assert h.between_row_count(100, 200) == pytest.approx(100, abs=80)

    def test_equal_row_count_skew(self):
        v = np.concatenate([np.zeros(900), np.arange(1, 101)]).astype(float)
        h = Histogram.build(v, n_buckets=8)
        assert h.equal_row_count(0.0) > 100  # repeat captures heavy hitter

    def test_empty(self):
        h = Histogram.build(np.zeros(0))
        assert h.row_count() == 0
        assert h.between_row_count(None, None) == 0.0


class TestSketches:
    def test_cmsketch(self):
        rng = np.random.default_rng(3)
        vals = rng.integers(0, 100, 10000, dtype=np.int64)
        cms = CMSketch()
        cms.insert_batch(vals)
        true = int((vals == 42).sum())
        assert abs(cms.query(42) - true) <= max(30, true * 0.3)

    def test_fmsketch(self):
        rng = np.random.default_rng(4)
        vals = rng.integers(0, 5000, 20000, dtype=np.int64)
        fm = FMSketch(max_size=1000)
        fm.insert_batch(vals)
        true_ndv = len(np.unique(vals))
        assert 0.4 * true_ndv < fm.ndv() < 2.5 * true_ndv


class TestAnalyze:
    @pytest.fixture()
    def sess(self):
        s = Domain().new_session()
        s.execute("create table t (a bigint, b double, c varchar(8))")
        rows = ",".join(
            f"({i % 50}, {i * 0.5}, 'k{i % 10}')" for i in range(500)
        )
        s.execute(f"insert into t values {rows}")
        return s

    def test_analyze_builds_stats(self, sess):
        sess.execute("analyze table t")
        t = sess.domain.catalog.info_schema().table("test", "t")
        st = sess.domain.stats.get(t.id)
        assert st is not None and st.row_count == 500
        assert st.columns[0].ndv == 50
        assert st.columns[2].ndv == 10  # dict codes

    def test_selectivity_drives_estimates(self, sess):
        sess.execute("analyze table t")
        rows = sess.query("explain select a from t where a < 10")
        reader = [r for r in rows if "TableReader" in r[0]][0]
        est = float(reader[1])
        assert 50 < est < 200  # true rows = 100

    def test_auto_analyze_after_churn(self, sess):
        sess.execute("analyze table t")
        t = sess.domain.catalog.info_schema().table("test", "t")
        v0 = sess.domain.stats.get(t.id).version
        big = ",".join(f"({i}, 1.0, 'z')" for i in range(400))
        sess.execute(f"insert into t values {big}")
        st = sess.domain.stats.get(t.id)
        assert st.version != v0  # auto-analyze refreshed after heavy churn

    def test_need_auto_analyze_flag(self, sess):
        t = sess.domain.catalog.info_schema().table("test", "t")
        # the insert in the fixture already triggered first-touch auto-analyze
        assert sess.domain.stats.get(t.id) is not None
        sess.domain.stats.drop(t.id)
        assert sess.domain.stats.need_auto_analyze(t.id)  # no stats, rows > 0
        sess.execute("analyze table t")
        assert not sess.domain.stats.need_auto_analyze(t.id)


def _stats_tuple(cs):
    h = cs.hist
    return ([(b.upper, b.lower, b.count, b.repeat) for b in h.buckets],
            h.null_count, h.ndv, h.total, cs.null_count, cs.ndv,
            cs.cms.table.tolist(), cs.cms.count)


@pytest.mark.parametrize("part_rows,dense", [(1 << 24, 1 << 26),
                                             (3000, 1 << 26), (3000, 64)])
def test_analyze_in_parts_gives_the_whole_columns_stats(monkeypatch,
                                                        part_rows, dense):
    """ANALYZE counts an integer column in parts of ANALYZE_PART_ROWS base
    rows and merges their distinct values and counts (densely, or by a
    sort past DENSE_MERGE_RANGE): the histogram, sketch, NDV and NULLs of
    every column are those of the whole column read and sorted at once,
    for blocks of several widths, NULLs, strings, negative values, a
    column of one value, one of NULLs alone and one past 2^53 whose
    neighbours round to one float."""
    from tidb_tpu.statistics import handle as hmod
    from tidb_tpu.store.blockstore import BLOCK_SIZE

    monkeypatch.setattr(hmod, "ANALYZE_PART_ROWS", part_rows)
    monkeypatch.setattr(hmod, "DENSE_MERGE_RANGE", dense)
    dom = Domain()
    s = dom.new_session()
    s.execute("create table t (a bigint, b bigint, c varchar(8), "
              "d bigint, e bigint, f bigint)")
    info = dom.catalog.info_schema().table("test", "t")
    store = dom.storage.table(info.id)
    rng = np.random.default_rng(11)
    for n, wide in ((BLOCK_SIZE + 777, False), (5000, True)):
        a = rng.integers(-50, 50, n) * (1 << 20 if wide else 1)
        b = rng.integers(0, 300, n)
        c = rng.choice(np.array(["x", "yy", "z", "w"], dtype=object), n)
        d = np.full(n, 7)
        e = np.zeros(n, dtype=np.int64)
        f = (1 << 60) + rng.integers(0, 8, n) * (1 if wide else 512)
        store.bulk_load_arrays(
            [a, b, c, d, e, f], ts=dom.storage.current_ts(),
            valids=[None, rng.random(n) > 0.2, None, None,
                    np.zeros(n, dtype=np.bool_), None])
    h = dom.stats
    got = h.analyze_table(info.id)
    n_base = store.base_rows
    for ci in range(store.n_cols):
        assert hmod._counted(store, ci, n_base)
        want = h._column_stats(store, ci, n_base, set(), {}, 64)
        assert _stats_tuple(got.columns[ci]) == _stats_tuple(want), ci
    assert got.columns[1].null_count > 0
    assert got.columns[4].null_count == n_base
    # 2^60 + 0..7 round to one float, 2^60 + 512 x 0..7 to eight
    assert got.columns[5].ndv == 8
