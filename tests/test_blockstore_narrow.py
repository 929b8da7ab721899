"""The host store holds integer columns narrow, block by block.

- `bulk_load_arrays` keeps each block of an integer column (BIGINT,
  DECIMAL, DATE) in the narrowest of int8, int16, int32 and int64 that
  holds the block's values; a partial last block that takes wider values
  widens as it grows; `nbytes()` counts what is held, and the
  `store_base_bytes` gauge sums every live store's.
- Every reader that hands values out gives the column's logical dtype and
  the values an int64 store would: `base_chunk`, `gather_chunk`,
  `read_row`, a committed delta over the base, `compact`, and the
  persister's round trip; `iter_base_blocks(narrow=True)` gives the blocks
  as held.
"""

import numpy as np
import pytest

from tidb_tpu.metrics import REGISTRY, STORE_BASE_BYTES
from tidb_tpu.session import Domain
from tidb_tpu.store.blockstore import BLOCK_SIZE, narrowest

N_SMALL = BLOCK_SIZE + 1000     # one whole block and a partial one
N_WIDE = BLOCK_SIZE             # fills the partial block, starts another


def _columns():
    """(small, wide) loads of (bigint, decimal(15,2) as cents, date as
    days): the first fits int8 / int16, the second needs int32 / int64."""
    rng = np.random.default_rng(5)
    small = [rng.integers(-100, 100, N_SMALL),
             rng.integers(0, 30000, N_SMALL),
             rng.integers(0, 20000, N_SMALL).astype(np.int32)]
    wide = [rng.integers(-(1 << 40), 1 << 40, N_WIDE),
            rng.integers(0, 1 << 31, N_WIDE) * 100,
            rng.integers(-40000, 40000, N_WIDE).astype(np.int32)]
    return small, wide


def _load(domain, sess):
    sess.execute("create table t (a bigint, p decimal(15,2), d date)")
    info = domain.catalog.info_schema().table("test", "t")
    store = domain.storage.table(info.id)
    small, wide = _columns()
    store.bulk_load_arrays(small, ts=domain.storage.current_ts())
    store.bulk_load_arrays(wide, ts=domain.storage.current_ts())
    want = [np.concatenate([s, w]).astype(store.cols[i].ftype.np_dtype)
            for i, (s, w) in enumerate(zip(small, wide))]
    return store, want


@pytest.fixture()
def loaded():
    domain = Domain()
    sess = domain.new_session()
    store, want = _load(domain, sess)
    return domain, sess, store, want


def test_blocks_are_held_narrow_and_widen_as_their_values_need(loaded):
    _d, _s, store, _want = loaded
    held = [[b.dtype for b in blocks] for blocks in store._blocks]
    # block 0 of the first load; block 1, its partial block filled by the
    # second load (promoted); block 2 the second load's rest
    assert held[0] == [np.int8, np.int64, np.int64]
    assert held[1] == [np.int16, np.int64, np.int64]
    assert held[2] == [np.int16, np.int32, np.int32]
    assert store.nbytes() == sum(b.nbytes for blocks in store._blocks
                                 for b in blocks)
    # the device path takes the blocks as they are held
    first = next(store.iter_base_blocks([0, 1, 2], 0, 10, narrow=True))
    assert [a.dtype for a in first[1]] == [np.int8, np.int16, np.int16]
    first = next(store.iter_base_blocks([0, 1, 2], 0, 10))
    assert [a.dtype for a in first[1]] == [np.int64, np.int64, np.int32]


def test_narrowest_holds_the_values_in_the_least_width():
    assert narrowest(np.array([-128, 127])).dtype == np.int8
    assert narrowest(np.array([-129, 0])).dtype == np.int16
    assert narrowest(np.array([0, 1 << 16])).dtype == np.int32
    assert narrowest(np.array([0, 1 << 40])).dtype == np.int64
    assert narrowest(np.array([3, 200], dtype=np.uint8)).dtype == np.int16
    a = np.array([1, 2], dtype=np.int8)
    assert narrowest(a) is a


def test_every_reader_widens_to_the_logical_dtype(loaded):
    _d, _s, store, want = loaded
    n = store.base_rows
    chunk = store.base_chunk([0, 1, 2], 0, n)
    for i in range(3):
        col = chunk.col(i)
        assert col.data.dtype == store.cols[i].ftype.np_dtype
        assert np.array_equal(col.data, want[i])
    # a slice across the promoted block's boundary
    part = store.base_chunk([0, 2], BLOCK_SIZE - 5, BLOCK_SIZE + 1005)
    assert np.array_equal(part.col(0).data,
                          want[0][BLOCK_SIZE - 5: BLOCK_SIZE + 1005])
    handles = np.array([0, 7, BLOCK_SIZE, N_SMALL - 1, N_SMALL, n - 1])
    got = store.gather_chunk([0, 1, 2], handles)
    for i in range(3):
        assert got.col(i).data.dtype == store.cols[i].ftype.np_dtype
        assert np.array_equal(got.col(i).data, want[i][handles])
    for h in handles:
        row = store.read_row(int(h), store.base_ts)
        assert [int(x) for x in row] == [int(w[h]) for w in want]


def test_a_committed_delta_and_compaction_keep_the_values(loaded):
    domain, sess, store, want = loaded
    n = store.base_rows
    # an update past every width the block held, a delete, an insert
    sess.execute(f"update t set a = {1 << 50} where a = {int(want[0][3])}")
    sess.execute(f"delete from t where a = {int(want[0][n - 1])}")
    sess.execute("insert into t values (-5, 1.25, '2001-02-03')")
    expect = sess.query("select count(*), sum(a), sum(p), min(d), max(d), "
                        "max(a) from t")
    assert expect[0][5] == 1 << 50
    store.compact(domain.storage.current_ts())
    assert not store.delta
    assert sess.query("select count(*), sum(a), sum(p), min(d), max(d), "
                      "max(a) from t") == expect
    assert store.nbytes() == sum(b.nbytes for blocks in store._blocks
                                 for b in blocks)
    assert REGISTRY.get(STORE_BASE_BYTES) >= store.nbytes()


def test_the_gauge_follows_each_load(loaded):
    from tidb_tpu.store import blockstore

    domain, _sess, store, _want = loaded
    held = store.nbytes()
    assert REGISTRY.get(STORE_BASE_BYTES) >= held > 0
    store.bulk_load_arrays([np.arange(10), np.arange(10),
                            np.arange(10, dtype=np.int32)],
                           ts=domain.storage.current_ts())
    # ten rows more, in the partial last block's widths (int64, int64,
    # int32), and the gauge is every live store's blocks as held
    assert store.nbytes() == held + 10 * (8 + 8 + 4)
    assert REGISTRY.get(STORE_BASE_BYTES) == sum(
        s.nbytes() for s in list(blockstore._LIVE_STORES))


def test_the_persisters_round_trip_keeps_the_values(tmp_path):
    data_dir = str(tmp_path / "data")
    domain = Domain(data_dir=data_dir)
    sess = domain.new_session()
    store, want = _load(domain, sess)
    sess.execute("update t set p = 2.5 where a = 1")
    expect = sess.query("select count(*), sum(a), sum(p), sum(d) from t")

    again = Domain(data_dir=data_dir)
    info = again.catalog.info_schema().table("test", "t")
    reopened = again.storage.table(info.id)
    got = reopened.base_chunk([0, 1, 2], 0, reopened.base_rows)
    for i in range(3):
        assert np.array_equal(got.col(i).data, want[i])
    # held narrow again, and counted as held
    assert reopened._blocks[0][0].dtype == np.int8
    assert reopened.nbytes() == sum(b.nbytes for blocks in reopened._blocks
                                    for b in blocks)
    assert again.new_session().query(
        "select count(*), sum(a), sum(p), sum(d) from t") == expect
