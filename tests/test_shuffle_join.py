"""Distributed shuffle join: partitioned builds over the mesh.

round-3 review item 3: stop replicating join builds to every device. The
build hash-partitions across mesh devices (no device holds the full
build — pinned by construction in `partition_build`) and probe rows
route to their key's owner via one ICI all_to_all
(`parallel/shuffle_join.py`, the `dq_opt_join.cpp` ShuffleJoin +
`dq_tasks_graph.h` stage-boundary analog).
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.parallel import make_mesh
from ydb_tpu.query import QueryEngine


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 10, mesh=make_mesh(8))
    e.execute("create table fact (id Int64 not null, k Int64 not null, "
              "g Int64 not null, v Double not null, primary key (id))")
    e.execute("create table dim (k2 Int64 not null, w Double not null, "
              "primary key (k2))")
    n, m = 20_000, 4_000
    ids = np.arange(n)
    ks = (ids * 7) % m          # some dim keys never hit
    gs = ids % 11
    vs = ids * 0.5
    for lo in range(0, n, 5_000):
        rows = ",".join(f"({i},{k},{g},{v})" for i, k, g, v in
                        zip(ids[lo:lo+5_000], ks[lo:lo+5_000],
                            gs[lo:lo+5_000], vs[lo:lo+5_000]))
        e.execute(f"insert into fact (id, k, g, v) values {rows}")
    rows = ",".join(f"({k},{k * 1.5})" for k in range(0, m, 2))
    e.execute(f"insert into dim (k2, w) values {rows}")
    # force the partitioned path: every build is "too big to broadcast"
    e.executor.dist_broadcast_budget_bytes = 1
    e.fact = pd.DataFrame({"id": ids, "k": ks, "g": gs, "v": vs})
    e.dim = pd.DataFrame({"k2": np.arange(0, m, 2),
                          "w": np.arange(0, m, 2) * 1.5})
    return e


def test_shuffle_inner_join_agg(eng):
    from ydb_tpu.utils.metrics import GLOBAL
    before = GLOBAL.snapshot()
    got = eng.query(
        "select g, count(*) as n, sum(v + w) as s from fact, dim "
        "where k = k2 group by g order by g")
    assert eng.executor.last_path == "distributed-shuffle-join"
    j = eng.fact.merge(eng.dim, left_on="k", right_on="k2")
    w = j.assign(s=j.v + j.w).groupby("g").agg(
        n=("s", "size"), s=("s", "sum")).reset_index()
    assert list(got.g) == list(w.g)
    assert list(got.n) == list(w.n)
    np.testing.assert_allclose(got.s, w.s, rtol=1e-9)
    after = GLOBAL.snapshot()
    assert after.get("executor/shuffle_joins", 0) >= 1
    # the exchange books the rows it was fed under the device that held
    # them: every fact row, one 5 000-row portion on each of four devices
    fed = {d.id: after.get(f"mesh/exchange_rows/shuffle-join/dev{d.id}", 0)
           - before.get(f"mesh/exchange_rows/shuffle-join/dev{d.id}", 0)
           for d in eng.executor.mesh.devices.flat}
    assert sum(fed.values()) == len(eng.fact)
    assert sorted(fed.values())[-4:] == [5_000] * 4


def test_shuffle_semi_join_agg(eng):
    got = eng.query(
        "select g, sum(v) as s from fact where k in (select k2 from dim) "
        "group by g order by g")
    assert eng.executor.last_path == "distributed-shuffle-join"
    f = eng.fact[eng.fact.k.isin(eng.dim.k2)]
    w = f.groupby("g").v.sum().reset_index()
    assert list(got.g) == list(w.g)
    np.testing.assert_allclose(got.s, w.v, rtol=1e-9)


def test_shuffle_anti_join_agg(eng):
    got = eng.query(
        "select g, count(*) as n from fact "
        "where not exists (select * from dim where k2 = k) "
        "group by g order by g")
    assert eng.executor.last_path == "distributed-shuffle-join"
    f = eng.fact[~eng.fact.k.isin(eng.dim.k2)]
    w = f.groupby("g").size().reset_index(name="n")
    assert list(got.g) == list(w.g)
    assert list(got.n) == list(w.n)


def test_shuffle_join_global_agg(eng):
    got = eng.query("select sum(v * w) as s, count(*) as n "
                    "from fact, dim where k = k2")
    assert eng.executor.last_path == "distributed-shuffle-join"
    j = eng.fact.merge(eng.dim, left_on="k", right_on="k2")
    np.testing.assert_allclose(got.s[0], (j.v * j.w).sum(), rtol=1e-9)
    assert got.n[0] == len(j)


def test_no_device_holds_full_build(eng):
    """Pin the partitioning contract: each device's build partition is a
    strict subset (the point of the shuffle join)."""
    from ydb_tpu.parallel.shuffle_join import partition_build
    from ydb_tpu.core.block import HostBlock
    import ydb_tpu.core.dtypes as dt
    from ydb_tpu.core.schema import Column, Schema

    n = 10_000
    schema = Schema([Column("k", dt.DType(dt.Kind.INT64, False)),
                     Column("w", dt.DType(dt.Kind.FLOAT64, False))])
    hb = HostBlock.from_arrays(schema, {
        "k": np.arange(n, dtype=np.int64),
        "w": np.arange(n, dtype=np.float64)})
    arrays, pschema, dicts, bcap = partition_build(hb, "k", ["w"], 8)
    assert int(arrays["ns"].sum()) == n
    assert all(int(c) < n for c in arrays["ns"])      # strict subsets
    # partitions are disjoint by key hash
    seen = set()
    for p in range(8):
        ks = set(arrays["keys"][p][:arrays["ns"][p]].tolist())
        assert not (ks & seen)
        seen |= ks
    assert len(seen) == n


def test_shuffle_join_composite_key(eng):
    """round-4 review #8: composite join keys exchange as combined 64-bit
    hashes — no full-build replication (the broadcast decline is gone)."""
    e = eng
    e.execute("create table cfact (id Int64 not null, a Int64 not null, "
              "b Int64 not null, v Double not null, primary key (id))")
    e.execute("create table cdim (a2 Int64 not null, b2 Int64 not null, "
              "w Double not null, primary key (a2, b2))")
    n = 8_000
    ids = np.arange(n)
    aa, bb = ids % 37, ids % 11
    rows = ",".join(f"({i},{a},{b},{i * 0.25})"
                    for i, a, b in zip(ids, aa, bb))
    e.execute(f"insert into cfact (id, a, b, v) values {rows}")
    pairs = {(a, b): (a * 100 + b) * 0.5
             for a in range(0, 37, 2) for b in range(11)}
    rows = ",".join(f"({a},{b},{w})" for (a, b), w in pairs.items())
    e.execute(f"insert into cdim (a2, b2, w) values {rows}")
    got = e.query("select count(*) as n, sum(v + w) as s from cfact, cdim "
                  "where a = a2 and b = b2")
    assert e.executor.last_path == "distributed-shuffle-join"
    f = pd.DataFrame({"a": aa, "b": bb, "v": ids * 0.25})
    d = pd.DataFrame([(a, b, w) for (a, b), w in pairs.items()],
                     columns=["a2", "b2", "w"])
    j = f.merge(d, left_on=["a", "b"], right_on=["a2", "b2"])
    assert int(got.n[0]) == len(j)
    np.testing.assert_allclose(got.s[0], (j.v + j.w).sum(), rtol=1e-9)


def test_shuffle_join_string_key(eng):
    """Dictionary-encoded join keys: build codes remap into the probe
    dictionary and exchange as ints."""
    e = eng
    e.execute("create table sfact (id Int64 not null, tag Utf8 not null, "
              "v Double not null, primary key (id))")
    e.execute("create table sdim (tag2 Utf8 not null, w Double not null, "
              "primary key (tag2))")
    n = 6_000
    ids = np.arange(n)
    tags = [f"t{i % 97}" for i in ids]
    rows = ",".join(f"({i},'{t}',{i * 0.5})" for i, t in zip(ids, tags))
    e.execute(f"insert into sfact (id, tag, v) values {rows}")
    # dim inserts in a DIFFERENT order → different dictionary codes
    dim = {f"t{k}": k * 2.0 for k in range(96, -1, -3)}
    rows = ",".join(f"('{t}',{w})" for t, w in dim.items())
    e.execute(f"insert into sdim (tag2, w) values {rows}")
    got = e.query("select count(*) as n, sum(v + w) as s "
                  "from sfact, sdim where tag = tag2")
    assert e.executor.last_path == "distributed-shuffle-join"
    f = pd.DataFrame({"tag": tags, "v": ids * 0.5})
    d = pd.DataFrame(list(dim.items()), columns=["tag2", "w"])
    j = f.merge(d, left_on="tag", right_on="tag2")
    assert int(got.n[0]) == len(j)
    np.testing.assert_allclose(got.s[0], (j.v + j.w).sum(), rtol=1e-9)


def test_shuffle_join_q9_shape(eng):
    """The q9 shape: multi-join pipeline whose LAST join is the big
    composite-keyed one — earlier dimension joins broadcast, the big
    build hash-partitions (oracle-checked)."""
    e = eng
    e.execute("create table q9f (id Int64 not null, pk Int64 not null, "
              "sk Int64 not null, g Int64 not null, v Double not null, "
              "primary key (id))")
    e.execute("create table q9d (sk2 Int64 not null, nm Utf8 not null, "
              "primary key (sk2))")
    e.execute("create table q9ps (pk2 Int64 not null, sk3 Int64 not null, "
              "cost Double not null, primary key (pk2, sk3))")
    n = 8_000
    ids = np.arange(n)
    pk, sk, g = ids % 53, ids % 13, ids % 5
    rows = ",".join(f"({i},{p},{s},{q},{i * 0.1})"
                    for i, p, s, q in zip(ids, pk, sk, g))
    e.execute(f"insert into q9f (id, pk, sk, g, v) values {rows}")
    rows = ",".join(f"({s},'n{s % 4}')" for s in range(13))
    e.execute(f"insert into q9d (sk2, nm) values {rows}")
    ps = {(p, s): p + s * 0.25 for p in range(53) for s in range(13)
          if (p + s) % 3 != 0}
    rows = ",".join(f"({p},{s},{c})" for (p, s), c in ps.items())
    e.execute(f"insert into q9ps (pk2, sk3, cost) values {rows}")
    got = e.query(
        "select nm, sum(v - cost) as profit from q9f, q9d, q9ps "
        "where sk = sk2 and pk = pk2 and sk = sk3 "
        "group by nm order by nm")
    assert e.executor.last_path == "distributed-shuffle-join"
    f = pd.DataFrame({"pk": pk, "sk": sk, "v": ids * 0.1})
    dd = pd.DataFrame({"sk2": np.arange(13),
                       "nm": [f"n{s % 4}" for s in range(13)]})
    pp = pd.DataFrame([(p, s, c) for (p, s), c in ps.items()],
                      columns=["pk2", "sk3", "cost"])
    j = f.merge(dd, left_on="sk", right_on="sk2") \
         .merge(pp, left_on=["pk", "sk"], right_on=["pk2", "sk3"])
    w = j.assign(profit=j.v - j.cost).groupby("nm", as_index=False) \
         .profit.sum()
    assert list(got.nm) == list(w.nm)
    np.testing.assert_allclose(got.profit, w.profit, rtol=1e-9)


def test_shuffle_join_string_key_unreferenced_dim_values(eng):
    """Build values ABSENT from the probe dictionary all remap to the
    shared -2 never-match code: they must be dropped pre-exchange, not
    trip the duplicate-key gate into a silent broadcast fallback."""
    e = eng
    e.execute("create table s2fact (id Int64 not null, tag Utf8 not null, "
              "v Double not null, primary key (id))")
    e.execute("create table s2dim (tag2 Utf8 not null, w Double not null, "
              "primary key (tag2))")
    n = 6_000
    ids = np.arange(n)
    tags = [f"t{i % 40}" for i in ids]        # fact uses only t0..t39
    rows = ",".join(f"({i},'{t}',{i * 0.5})" for i, t in zip(ids, tags))
    e.execute(f"insert into s2fact (id, tag, v) values {rows}")
    dim = {f"t{k}": k * 2.0 for k in range(120)}   # 80 values never probed
    rows = ",".join(f"('{t}',{w})" for t, w in dim.items())
    e.execute(f"insert into s2dim (tag2, w) values {rows}")
    got = e.query("select count(*) as n, sum(v + w) as s "
                  "from s2fact, s2dim where tag = tag2")
    assert e.executor.last_path == "distributed-shuffle-join"
    f = pd.DataFrame({"tag": tags, "v": ids * 0.5})
    d = pd.DataFrame(list(dim.items()), columns=["tag2", "w"])
    j = f.merge(d, left_on="tag", right_on="tag2")
    assert int(got.n[0]) == len(j)
    np.testing.assert_allclose(got.s[0], (j.v + j.w).sum(), rtol=1e-9)


def test_shuffle_join_tuning_flip_rebuilds(eng, monkeypatch):
    """Cache-key completeness (graftlint cache-key pass): the executor's
    ShuffleJoin cache keys on the group-by tuning tuple. The SAME SQL
    across a YDB_TPU_GROUPBY_TILE_ROWS flip must build a second
    ShuffleJoin (its traced partial/rest programs are tiled under the
    live knobs) and return the same answer — before the fix the flip
    reused the instance traced under the old settings."""
    sql = ("select g, sum(v * w) as s from fact join dim on k = k2 "
           "group by g order by g")
    monkeypatch.delenv("YDB_TPU_GROUPBY_TILE_ROWS", raising=False)
    out1 = eng.query(sql)
    n0 = len(eng.executor._shuffle_joins)
    assert n0 >= 1
    out_cached = eng.query(sql)
    assert len(eng.executor._shuffle_joins) == n0     # same tuning: hit

    monkeypatch.setenv("YDB_TPU_GROUPBY_TILE_ROWS", "256")
    out2 = eng.query(sql)
    assert len(eng.executor._shuffle_joins) == n0 + 1, \
        "tuning flip must build a fresh ShuffleJoin, not serve the stale one"
    for out in (out_cached, out2):
        assert list(out.g) == list(out1.g)
        np.testing.assert_allclose(out.s, out1.s, rtol=1e-9)
