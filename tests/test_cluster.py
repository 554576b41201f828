"""Two-PROCESS cluster: shard router over worker engines via gRPC.

round-3 review item 4 ("a second process"): worker engine processes each own
a shard of `lineitem` (other tables replicated for co-located joins).
Every SELECT here runs on the DQ path — the router lowers it to a
`dq.StageGraph` (`ydb_tpu/dq/lower.py`) and `DqTaskRunner` executes one
task per (stage, worker) with frames streamed over the exchange
channels; the join tests additionally pin the lowered graph shape
(hash-shuffle edges between worker stages) and the `dq/*` counters.
"""

import numpy as np
import pytest

pytest.importorskip("grpc")

from ydb_tpu.cluster import ShardedCluster  # noqa: E402

from tests.tpch_util import QUERIES, assert_frames_match, oracle  # noqa: E402

SF = 0.002
NW = 2


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from tests.cluster_util import spawn_workers, stop_workers
    root = tmp_path_factory.mktemp("cluster")
    procs, ports = spawn_workers(root, NW, SF)
    c = ShardedCluster([f"127.0.0.1:{port}" for port in ports])
    # topology metadata the DDL path would have recorded: lineitem and
    # orders are SHARDED (cluster_worker splits them by row index — NOT
    # co-partitioned), the dimension tables are replicated
    c.key_columns["lineitem"] = ["l_orderkey", "l_linenumber"]
    c.key_columns["orders"] = ["o_orderkey"]
    c.replicated = {"customer", "nation", "region", "part", "partsupp",
                    "supplier"}
    from ydb_tpu.bench.tpch_gen import TpchData
    c.tpch_data = TpchData(SF)          # same seed → the oracle dataset
    yield c
    stop_workers(procs)


def test_tpch_q1_across_processes(cluster):
    got = cluster.query(QUERIES["q1"])
    want = oracle("q1", cluster.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)


def test_global_agg_across_processes(cluster):
    got = cluster.query(QUERIES["q6"])
    want = oracle("q6", cluster.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True, rtol=1e-9)


def test_join_agg_across_processes(cluster):
    # lineitem AND orders sharded (by row index — NOT co-partitioned):
    # q3 joins them through the worker<->worker hash shuffle, with
    # customer replicated joining worker-locally afterwards
    from ydb_tpu.dq.graph import HASH_SHUFFLE, StageGraph
    graph = cluster.plan(QUERIES["q3"])
    assert isinstance(graph, StageGraph)
    shuffles = [c for c in graph.channels.values()
                if c.kind == HASH_SHUFFLE]
    assert shuffles, "q3 must lower to a hash-shuffle edge"
    assert all(not c.router_bound for c in shuffles)
    got = cluster.query(QUERIES["q3"])
    want = oracle("q3", cluster.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)


def test_shuffle_join_sharded_x_sharded(cluster):
    """round-4 review #3 Done criterion: a 2-process join of two sharded
    tables where NEITHER worker holds the other's shard — rows meet
    through the exchange channels, oracle-checked."""
    import pandas as pd
    # neither worker holds all of orders or all of lineitem
    for t, n_total in (("orders",
                        len(cluster.tpch_data.tables["orders"]["o_orderkey"])),
                       ("lineitem",
                        len(cluster.tpch_data.tables["lineitem"]["l_orderkey"]))):
        per = [int(w.execute(f"select count(*) as c from {t}")["rows"][0][0])
               for w in cluster.workers]
        assert sum(per) == n_total
        assert all(0 < p < n_total for p in per), (t, per)
    sql = ("select o_orderpriority, count(*) as n, sum(l_extendedprice) as s "
           "from lineitem, orders where l_orderkey = o_orderkey "
           "and l_discount > 0.02 group by o_orderpriority "
           "order by o_orderpriority")
    # the DQ lowering co-partitions both sharded sides over a
    # hash-shuffle edge into the join stage, then gathers partial aggs
    from ydb_tpu.dq.graph import HASH_SHUFFLE, UNION_ALL
    from ydb_tpu.utils.metrics import GLOBAL
    graph = cluster.plan(sql)
    kinds = {c.kind for c in graph.channels.values()}
    assert HASH_SHUFFLE in kinds and UNION_ALL in kinds
    stages0 = GLOBAL.get("dq/stages")
    tasks0 = GLOBAL.get("dq/tasks")
    got = cluster.query(sql)
    assert GLOBAL.get("dq/stages") - stages0 == len(graph.stages)
    # one task per (worker stage, worker)
    assert GLOBAL.get("dq/tasks") - tasks0 == \
        sum(NW if s.on == "workers" else 1
            for s in graph.stages if s.on != "router")
    li = pd.DataFrame(cluster.tpch_data.tables["lineitem"])
    od = pd.DataFrame(cluster.tpch_data.tables["orders"])
    j = li[li.l_discount > 0.02].merge(od, left_on="l_orderkey",
                                       right_on="o_orderkey")
    w = j.groupby("o_orderpriority").agg(
        n=("o_orderpriority", "size"),
        s=("l_extendedprice", "sum")).reset_index() \
        .sort_values("o_orderpriority")
    assert list(got.o_orderpriority) == list(w.o_orderpriority)
    assert list(got.n) == list(w.n)
    np.testing.assert_allclose(got.s, w.s, rtol=1e-9)


def test_scan_across_processes(cluster):
    got = cluster.query(
        "select l_orderkey, l_extendedprice from lineitem "
        "where l_quantity > 48 order by l_extendedprice desc, l_orderkey "
        "limit 17")
    import pandas as pd
    li = pd.DataFrame(cluster.tpch_data.tables["lineitem"])
    w = li[li.l_quantity > 48].sort_values(
        ["l_extendedprice", "l_orderkey"], ascending=[False, True]).head(17)
    assert list(got.l_orderkey) == list(w.l_orderkey)
    np.testing.assert_allclose(got.l_extendedprice, w.l_extendedprice)


def test_count_distinct_across_processes(cluster):
    # two-level distinct: workers SELECT DISTINCT, the merge counts —
    # naive partial-count summation would overcount cross-shard dupes
    got = cluster.query(
        "select l_returnflag, count(distinct l_suppkey) as c "
        "from lineitem group by l_returnflag order by l_returnflag")
    import pandas as pd
    li = pd.DataFrame(cluster.tpch_data.tables["lineitem"])
    w = li.groupby("l_returnflag").l_suppkey.nunique().reset_index()
    assert list(got.iloc[:, 0]) == list(w.l_returnflag)
    assert list(got.c) == list(w.l_suppkey)
    # global distinct count
    got = cluster.query("select count(distinct l_partkey) as c "
                        "from lineitem")
    assert int(got.c[0]) == li.l_partkey.nunique()


def test_insert_routing_shards_rows(cluster):
    cluster.execute("create table kv (id Int64 not null, v Int64 not null, "
                    "primary key (id))")
    rows = ", ".join(f"({i}, {i * 10})" for i in range(40))
    cluster.execute(f"insert into kv (id, v) values {rows}")
    got = cluster.query("select count(*) as c, sum(v) as s from kv")
    assert int(got.c[0]) == 40
    assert int(got.s[0]) == sum(i * 10 for i in range(40))
    # rows actually SPLIT across the processes
    per = [int(w.execute("select count(*) as c from kv")["rows"][0][0])
           for w in cluster.workers]
    assert sum(per) == 40
    assert all(0 < n < 40 for n in per), per
    # group-by with having + order over the sharded table
    got = cluster.query(
        "select id % 4 as b, sum(v) as s, avg(v) as a from kv "
        "group by id % 4 having sum(v) > 0 order by s desc")
    import pandas as pd
    kv = pd.DataFrame({"id": np.arange(40), "v": np.arange(40) * 10})
    w = kv.assign(b=kv.id % 4).groupby("b").agg(
        s=("v", "sum"), a=("v", "mean")).reset_index() \
        .sort_values("s", ascending=False)
    assert list(got.b) == list(w.b)
    np.testing.assert_allclose(got.s, w.s)
    np.testing.assert_allclose(got.a, w.a, rtol=1e-9)
