"""Transactional column-table DML via MVCC delete marks.

round-3 review item 9: column UPDATE/DELETE used to rewrite portions —
non-transactional, destroying time travel. Now deletes are versioned
row-index marks on immutable portions (`storage/portion.py` DeleteMark,
the per-row delete-version stance of the reference's ColumnShard MVCC):
historical snapshots keep the rows, transactions stage marks invisible
to other sessions, and recovery replays marks from the WAL/manifest.
"""

import numpy as np
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.query.engine import QueryError


def _mk(data_dir=None):
    e = QueryEngine(block_rows=1 << 10, data_dir=data_dir)
    e.execute("create table t (id Int64 not null, g Int64 not null, "
              "v Double not null, primary key (id)) with (store = column)")
    e.execute("insert into t (id, g, v) values "
              + ",".join(f"({i},{i % 4},{i * 1.0})" for i in range(1000)))
    return e


def test_delete_preserves_time_travel():
    e = _mk()
    old = e.snapshot()
    plan = e.planner.plan_select(
        __import__("ydb_tpu.sql", fromlist=["parse"]).parse(
            "select count(*) as c from t"))
    e.execute("delete from t where g = 1")
    assert int(e.query("select count(*) as c from t").c[0]) == 750
    # the PRE-delete snapshot still sees every row
    blk = e.executor.execute(plan, old)
    assert int(blk.to_pandas().iloc[0, 0]) == 1000
    # fused-path cache keys on the visible mark set: re-read is consistent
    assert int(e.query("select count(*) as c from t").c[0]) == 750
    s = e.query("select sum(v) as s from t").s[0]
    np.testing.assert_allclose(
        s, sum(i * 1.0 for i in range(1000) if i % 4 != 1), rtol=1e-9)


def test_update_inside_transaction():
    e = _mk()
    s = e.session()
    s.execute("begin")
    s.execute("update t set v = v + 1000 where g = 2")
    # read-your-writes inside the tx
    got = s.query("select count(*) as c from t where v >= 1000").c[0]
    assert int(got) == 250
    # invisible to autocommit readers until commit
    assert int(e.query("select count(*) as c from t "
                       "where v >= 1000").c[0]) == 0
    s.execute("commit")
    assert int(e.query("select count(*) as c from t "
                       "where v >= 1000").c[0]) == 250
    # row count unchanged (update = delete + reinsert, atomically)
    assert int(e.query("select count(*) as c from t").c[0]) == 1000


def test_delete_rollback_restores():
    e = _mk()
    s = e.session()
    s.execute("begin")
    s.execute("delete from t where g = 0")
    assert int(s.query("select count(*) as c from t").c[0]) == 750
    assert int(e.query("select count(*) as c from t").c[0]) == 1000
    s.execute("rollback")
    assert int(e.query("select count(*) as c from t").c[0]) == 1000


def test_conflicting_commit_aborts_tx():
    e = _mk()
    s1, s2 = e.session(), e.session()
    s1.execute("begin")
    s1.execute("delete from t where g = 3")
    # a foreign commit to the same table lands first
    s2.execute("begin")
    s2.execute("update t set v = 0 where id = 0")
    s2.execute("commit")
    with pytest.raises(QueryError, match="optimistic lock"):
        s1.execute("commit")
    # the loser's marks rolled back
    assert int(e.query("select count(*) as c from t").c[0]) == 1000


def test_deletes_survive_restart(tmp_path):
    d = str(tmp_path / "store")
    e = _mk(data_dir=d)
    e.execute("delete from t where id < 100")
    e.execute("update t set v = -1 where id = 500")
    assert int(e.query("select count(*) as c from t").c[0]) == 900

    e2 = QueryEngine(block_rows=1 << 10, data_dir=d)
    assert int(e2.query("select count(*) as c from t").c[0]) == 900
    assert int(e2.query("select count(*) as c from t "
                        "where id < 100").c[0]) == 0
    assert float(e2.query("select v from t where id = 500").v[0]) == -1.0


def test_delete_marks_fold_at_compaction():
    # reclamation: once every active reader/pin is past the marks, the
    # portions rewrite without the dead rows and the marks drop
    e = _mk()
    e.execute("delete from t where g = 1")
    t = e.catalog.table("t")
    folded = t.compact(e._maintenance_watermark())
    assert folded >= 1
    assert sum(len(p.deletes) for s in t.shards for p in s.portions) == 0
    assert sum(p.num_rows for s in t.shards for p in s.portions) == 750
    assert int(e.query("select count(*) as c from t").c[0]) == 750


def test_own_tx_staged_rows_refuse_dml():
    # rows inserted by the same open tx are not yet portions — marking
    # would miss them (and UPDATE would duplicate); refuse loudly
    e = _mk()
    s = e.session()
    s.execute("begin")
    s.execute("insert into t (id, g, v) values (5000, 1, 1.0)")
    with pytest.raises(QueryError, match="same transaction"):
        s.execute("delete from t where id = 5000")
    s.execute("rollback")


def test_delete_then_insert_same_key():
    e = _mk()
    e.execute("delete from t where id = 7")
    e.execute("insert into t (id, g, v) values (7, 9, 77.0)")
    df = e.query("select g, v from t where id = 7")
    assert len(df) == 1 and int(df.g[0]) == 9 and float(df.v[0]) == 77.0
