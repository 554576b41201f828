"""Incremental materialized views: continuous queries over CDC.

The maintainer subscribes to the source table's changefeed and folds
committed deltas into persistent aggregate state (reference: the
`ydb/core/tx/datashard` change-sender path feeding async indexes /
CDC consumers that maintain derived state). Every test here checks the
one invariant that matters: a view read equals a full recompute of the
view query at the same snapshot — including min/max under DELETE, NULL
group keys, and restart from the host mirror.
"""

import os

import numpy as np
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.query.engine import QueryError

SEED = 20240807


def _mk(data_dir=None):
    e = QueryEngine(block_rows=1 << 12, data_dir=data_dir)
    e.execute("create table t (id Int64 not null, g Utf8, a Int64, "
              "b Double, primary key (id)) with (store = row)")
    return e


def _sorted(df, keys):
    return (df.sort_values(keys, na_position="first")
              .reset_index(drop=True)) if len(df) else df


def _assert_same(view_df, base_df, keys):
    assert list(view_df.columns) == list(base_df.columns)
    assert len(view_df) == len(base_df)
    if not len(base_df):
        return
    a, b = _sorted(view_df, keys), _sorted(base_df, keys)
    for c in a.columns:
        va, vb = a[c].to_numpy(), b[c].to_numpy()
        if any(isinstance(x, str) for v in (va, vb) for x in v):
            # a string column: pandas 3 hands a NULL back as NaN, which
            # must not make the column look numeric
            assert [x if isinstance(x, str) else None for x in va] \
                == [x if isinstance(x, str) else None for x in vb], \
                f"column {c}"
            continue
        floaty = any(k == "f" or (k == "O" and any(
            isinstance(x, float) for x in v if x is not None))
            for v, k in ((va, va.dtype.kind), (vb, vb.dtype.kind)))
        if floaty:
            va = np.array([np.nan if x is None else x for x in va],
                          dtype=np.float64)
            vb = np.array([np.nan if x is None else x for x in vb],
                          dtype=np.float64)
            assert np.allclose(va, vb, rtol=1e-9, equal_nan=True), \
                f"column {c}: {va} != {vb}"
        else:
            assert [None if x is None else x for x in a[c].tolist()] \
                == [None if x is None else x for x in b[c].tolist()], \
                f"column {c}"


AGG_SEL = ("select g, count(*) as n, count(b) as nb, sum(a) as s, "
           "min(a) as mn, max(a) as mx, avg(b) as av from t group by g")


def _check(eng, name, sel, keys):
    _assert_same(eng.query(f"select * from {name}"), eng.query(sel), keys)


def _random_dml(eng, rng, rounds=6, live=None):
    """Randomized insert/update/delete batches; `live` tracks ids."""
    if live is None:
        live = set()
    nxt = [max(live) + 1 if live else 0]
    for _ in range(rounds):
        op = rng.choice(3)
        if op == 0 or not live:                           # insert batch
            vals = []
            for _ in range(int(rng.integers(1, 9))):
                i = nxt[0]
                nxt[0] += 1
                live.add(i)
                g = "null" if rng.random() < 0.25 \
                    else f"'g{int(rng.integers(0, 4))}'"
                b = "null" if rng.random() < 0.2 \
                    else f"{float(rng.normal()):.6f}"
                vals.append(f"({i}, {g}, {int(rng.integers(-50, 50))}, {b})")
            eng.execute("insert into t (id, g, a, b) values "
                        + ", ".join(vals))
        elif op == 1:                                     # update batch
            ids = rng.choice(sorted(live),
                             size=min(len(live), 4), replace=False)
            for i in ids:
                eng.execute(f"update t set a = {int(rng.integers(-50, 50))},"
                            f" b = {float(rng.normal()):.6f}"
                            f" where id = {int(i)}")
        else:                                             # delete batch
            ids = rng.choice(sorted(live),
                             size=min(len(live), 3), replace=False)
            for i in ids:
                live.discard(int(i))
                eng.execute(f"delete from t where id = {int(i)}")
    return live


def test_view_agg_differential_randomized():
    eng = _mk()
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    rng = np.random.default_rng(SEED)
    live = set()
    for _ in range(8):
        live = _random_dml(eng, rng, rounds=5, live=live)
        _check(eng, "mv", AGG_SEL, ["g"])
    assert eng.views.get("mv").rebuilds == 0    # pure incremental folding


def test_view_plain_filter_project():
    sel = "select id, a + 1 as a1, g from t where a >= 0"
    eng = _mk()
    eng.execute(f"create materialized view pv as {sel}")
    rng = np.random.default_rng(SEED + 1)
    live = set()
    for _ in range(6):
        live = _random_dml(eng, rng, rounds=4, live=live)
        _check(eng, "pv", sel, ["id"])


def test_view_global_agg():
    sel = ("select count(*) as n, sum(a) as s, min(a) as mn, "
           "avg(b) as av from t")
    eng = _mk()
    eng.execute(f"create materialized view gv as {sel}")
    eng.execute("insert into t (id, g, a, b) values "
                "(1, 'x', 5, 1.5), (2, null, -3, null), (3, 'y', 9, 2.0)")
    _check(eng, "gv", sel, ["n"])
    eng.execute("delete from t where id = 3")       # drop the max
    _check(eng, "gv", sel, ["n"])
    eng.execute("delete from t")                    # empty source
    _check(eng, "gv", sel, ["n"])


def test_view_minmax_under_delete():
    eng = _mk()
    eng.execute("create materialized view mm as "
                "select g, min(a) as mn, max(a) as mx from t group by g")
    eng.execute("insert into t (id, g, a, b) values "
                "(1, 'g', 1, null), (2, 'g', 7, null), (3, 'g', 7, null), "
                "(4, 'g', 3, null)")
    df = eng.query("select * from mm")
    assert df.mn[0] == 1 and df.mx[0] == 7
    eng.execute("delete from t where id = 2")       # one of two max rows
    df = eng.query("select * from mm")
    assert df.mx[0] == 7                            # multiset: 7 survives
    eng.execute("delete from t where id = 3")       # last max row
    df = eng.query("select * from mm")
    assert df.mx[0] == 3
    eng.execute("update t set a = 0 where id = 4")  # shift the min
    df = eng.query("select * from mm")
    assert df.mn[0] == 0 and df.mx[0] == 1
    assert eng.views.get("mm").rebuilds == 0        # no recompute escape


def test_view_tx_commit_atomicity():
    eng = _mk()
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    eng.execute("insert into t (id, g, a, b) values (1, 'g0', 1, 1.0)")
    s = eng.session()
    s.execute("begin")
    s.execute("insert into t (id, g, a, b) values (2, 'g0', 10, 2.0)")
    s.execute("update t set a = 5 where id = 1")
    # uncommitted effects are invisible to the view
    assert eng.query("select n from mv").n[0] == 1
    assert eng.query("select s from mv").s[0] == 1
    s.execute("commit")
    _check(eng, "mv", AGG_SEL, ["g"])
    assert eng.query("select s from mv").s[0] == 15


def test_view_restart_from_mirror(tmp_path):
    root = str(tmp_path / "s")
    eng = _mk(root)
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    rng = np.random.default_rng(SEED + 2)
    live = _random_dml(eng, rng, rounds=8)
    _check(eng, "mv", AGG_SEL, ["g"])
    del eng
    eng2 = QueryEngine(block_rows=1 << 12, data_dir=root)
    v = eng2.views.get("mv")
    assert v is not None and v.rebuilds == 0    # restored, not recomputed
    _check(eng2, "mv", AGG_SEL, ["g"])
    # folding continues after restart
    _random_dml(eng2, rng, rounds=4, live=live)
    _check(eng2, "mv", AGG_SEL, ["g"])


def test_view_drop_frees_state(tmp_path):
    root = str(tmp_path / "s")
    eng = _mk(root)
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    eng.execute("insert into t (id, g, a, b) values (1, 'x', 1, 1.0)")
    assert eng.views.has("mv")
    mirror = os.path.join(root, "__views", "mv.json")
    assert os.path.exists(mirror)
    eng.execute("drop materialized view mv")
    assert not eng.views.has("mv")
    assert not os.path.exists(mirror)
    # the auto-created changefeed topic is unwired and dropped
    with pytest.raises(QueryError, match="unknown topic"):
        eng.topic("__cdc_t")
    # source table is writable and droppable again
    eng.execute("insert into t (id, g, a, b) values (2, 'y', 2, 2.0)")
    eng.execute("drop table t")
    with pytest.raises(QueryError, match="unknown"):
        eng.query("select * from mv")
    eng.execute("drop materialized view if exists mv")   # idempotent
    with pytest.raises(QueryError, match="unknown materialized view"):
        eng.execute("drop materialized view mv")


def test_view_ddl_guards():
    eng = _mk()
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    with pytest.raises(QueryError, match="materialized view"):
        eng.execute("create table mv (x Int64 not null, primary key (x))")
    with pytest.raises(QueryError, match="already"):
        eng.execute(f"create materialized view mv as {AGG_SEL}")
    with pytest.raises(QueryError, match="feeds materialized view"):
        eng.execute("drop table t")
    s = eng.session()
    s.execute("begin")
    with pytest.raises(QueryError, match="transaction"):
        s.execute("create materialized view m2 as select id from t")
    s.execute("rollback")


def test_view_unsupported_shapes_rejected():
    eng = _mk()
    eng.execute("create table u (id Int64 not null, primary key (id)) "
                "with (store = row)")
    for sel in [
        "select id from t order by id",
        "select id from t limit 5",
        "select g, count(*) as n from t group by g having count(*) > 1",
        "select distinct g from t",
        "select t.id from t join u on t.id = u.id",
        "select id from t where a in (select id from u)",
    ]:
        with pytest.raises(QueryError, match="unsupported materialized"):
            eng.execute(f"create materialized view bad as {sel}")
    # column-store sources have no changefeed to fold from
    eng.execute("create table c (id Int64 not null, primary key (id))")
    with pytest.raises(QueryError, match="row-store"):
        eng.execute("create materialized view bad as select id from c")


def test_view_sysview_and_explain():
    eng = _mk()
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    eng.execute("insert into t (id, g, a, b) values "
                "(1, 'x', 1, 1.0), (2, 'y', 2, 2.0)")
    eng.query("select * from mv")               # drain + serve
    df = eng.query('select * from ".sys/materialized_views"')
    row = df[df.name == "mv"].iloc[0]
    assert row.source == "t" and row.kind == "agg"
    assert row.watermark_step > 0 and row.lag_versions == 0
    assert row.state_rows == 2 and not row.degraded
    assert row.folds + row.rebuilds > 0
    text = "\n".join(eng.query("explain select * from mv").plan)
    assert "view mv" in text and "state @ plan_step" in text
    stats = eng.last_stats
    eng.query("select n from mv")
    assert any(v["view"] == "mv" and v["mode"] == "state"
               for v in eng.last_stats.view_serving)


def test_view_escape_degrades(monkeypatch):
    monkeypatch.setenv("YDB_TPU_VIEW_MAX_GROUPS", "8")
    eng = _mk()
    eng.execute("create materialized view mv as "
                "select a, count(*) as n from t group by a")
    before = eng.views.get("mv").rebuilds
    vals = ", ".join(f"({i}, null, {i}, null)" for i in range(64))
    eng.execute(f"insert into t (id, g, a, b) values {vals}")
    sel = "select a, count(*) as n from t group by a"
    _check(eng, "mv", sel, ["a"])               # fallback still correct
    v = eng.views.get("mv")
    assert v.degraded and v.rebuilds > before
    df = eng.query('select * from ".sys/materialized_views"')
    assert bool(df[df.name == "mv"].iloc[0].degraded)


def test_view_fold_batch_cadence(monkeypatch):
    monkeypatch.setenv("YDB_TPU_VIEW_FOLD_BATCH", "1")
    eng = _mk()
    eng.execute(f"create materialized view mv as {AGG_SEL}")
    for i in range(6):
        eng.execute(f"insert into t (id, g, a, b) values "
                    f"({i}, 'g', {i}, 1.0)")
    v = eng.views.get("mv")
    assert v.folds > 0          # write path folded without any read
    _check(eng, "mv", AGG_SEL, ["g"])
