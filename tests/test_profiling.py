"""Cross-worker query profiling: histograms, sampling policy, device-time
attribution, DQ trace propagation, and the profile sysviews.

Reference analogs: per-task/channel stats rolled into the plan
(`TDqTaskRunnerStatsView`, `kqp_executer_stats.cpp`), monlib histogram
counters, and `.sys` views served through the scan path.
"""

import numpy as np
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.utils.metrics import Histogram


# -- histogram bucket/quantile math ----------------------------------------


def test_histogram_single_sample():
    h = Histogram()
    h.record(3.7)
    s = h.snapshot()
    # one sample reports ITSELF at every quantile (clamped to min/max)
    assert s["count"] == 1
    assert s["p50"] == s["p95"] == s["p99"] == s["max"] == 3.7


def test_histogram_quantile_ordering_and_bounds():
    h = Histogram()
    vals = [0.1 * (i + 1) for i in range(1000)]     # 0.1 .. 100 ms
    for v in vals:
        h.record(v)
    s = h.snapshot()
    assert s["count"] == 1000
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # log-bucket interpolation is coarse but must stay in the right
    # decade: true p50 = 50ms, p99 = 99ms
    assert 25 <= s["p50"] <= 75
    assert 50 <= s["p99"] <= 100.0
    assert s["max"] == 100.0


def test_histogram_overflow_bucket():
    h = Histogram()
    h.record(1.0)
    big = Histogram.BASE * Histogram.GROWTH ** (Histogram.N_BUCKETS + 3)
    h.record(big)
    # the overflow bucket is unbounded above: quantiles landing there
    # report the exact observed max, not a bucket midpoint
    assert h.quantile(0.99) == big
    assert h.counts[Histogram.N_BUCKETS] == 1


def test_histogram_zero_and_empty():
    h = Histogram()
    assert h.snapshot() == {"count": 0, "p50": 0.0, "p95": 0.0,
                            "p99": 0.0, "max": 0.0}
    h.record(0.0)
    assert h.quantile(0.5) == 0.0


# -- engine-level sampling + phases ----------------------------------------


def mk_engine():
    e = QueryEngine(block_rows=1 << 13)
    e.execute("create table t (id Int64 not null, v Double not null, "
              "primary key (id))")
    e.execute("insert into t (id, v) values " + ", ".join(
        f"({i}, {i}.5)" for i in range(50)))
    return e


def test_sample_rate_zero_records_nothing_and_matches():
    base = mk_engine()
    want = base.query("select sum(v) as s, count(*) as n from t")
    assert len(base.last_trace) > 0          # default: traced

    quiet = mk_engine()
    quiet.trace_sample = 0.0
    got = quiet.query("select sum(v) as s, count(*) as n from t")
    assert quiet.last_trace == []            # zero spans
    assert quiet.last_stats.phases == {}
    assert list(got.columns) == list(want.columns)
    assert np.array_equal(got.to_numpy(), want.to_numpy())
    # EXPLAIN ANALYZE is forced-sampled even at rate 0 (the user asked
    # for the profile)
    df = quiet.query("explain analyze select count(*) as c from t")
    assert "-- trace:" in "\n".join(df.plan)


def test_fractional_sampling_is_deterministic():
    e = mk_engine()
    e.query("select count(*) as c from t")   # warm: first-run compile
    e.slow_query_ms = float("inf")           # may cross 1s and would
    e._slow_sqls.clear()                     # force-trace every run
    e.trace_sample = 0.25
    traced = 0
    for _ in range(16):
        e.query("select count(*) as c from t")
        traced += bool(e.last_trace)
    assert traced == 4                       # exactly 1 in 4


def test_slow_query_forces_next_trace_and_counters():
    from ydb_tpu.utils.metrics import GLOBAL
    e = mk_engine()
    e.trace_sample = 0.0
    e.slow_query_ms = 0.0                    # everything is "slow"
    before = GLOBAL.get("slow_query/count")
    sql = "select sum(v) as s from t"
    e.query(sql)
    assert GLOBAL.get("slow_query/count") > before
    assert sql in e._slow_sqls
    e.query(sql)                             # forced-sampled now
    assert e.last_trace, "slow statement must be traced on its next run"


def test_phases_and_profile_ring():
    e = mk_engine()
    e.query("select sum(v) as s from t where id > 3")
    ph = e.last_stats.phases
    assert ph.get("dispatch_ms", 0) > 0
    assert "readout_ms" in ph and "device_ms" in ph
    assert ph.get("compile_ms", 0) > 0       # fresh shape compiled
    prof = e.profiles[-1]
    assert prof["sql"].startswith("select sum")
    assert prof["n_spans"] == len(prof["spans"])
    # the statement's phases are its spans' and, after the profile was
    # recorded, its own bookkeeping
    assert ph["bookkeeping_ms"] > 0
    assert prof["phases"] == {k: v for k, v in ph.items()
                              if k != "bookkeeping_ms"}


def test_latency_histograms_on_counters():
    e = mk_engine()
    e.query("select count(*) as c from t")
    c = e.counters()
    assert c["hist/query/latency_ms/count"] >= 1
    assert c["hist/query/latency_ms/p99"] >= c["hist/query/latency_ms/p50"]
    for fam in ("query/parse_ms", "query/plan_ms", "query/execute_ms",
                "dq/stage_ms", "dq/channel_wait_ms", "admission/wait_ms"):
        assert f"hist/{fam}/p50" in c        # always-visible families


# -- DQ propagation + sysview row shapes -----------------------------------


def mk_dq_cluster():
    from ydb_tpu.cluster import ShardedCluster
    from ydb_tpu.dq.runner import LocalWorker
    engines = []
    for wid in range(2):
        e = QueryEngine(block_rows=1 << 13)
        e.execute("create table t (id Int64 not null, k Int64 not null, "
                  "v Double not null, primary key (id))")
        mine = [i for i in range(80) if i % 2 == wid]
        e.execute("insert into t (id, k, v) values " + ", ".join(
            f"({i}, {i % 5}, {i}.5)" for i in mine))
        e.execute("create table u (uid Int64 not null, w Double not null, "
                  "primary key (uid))")
        mine_u = [i for i in range(5) if i % 2 == wid]
        if mine_u:
            e.execute("insert into u (uid, w) values " + ", ".join(
                f"({i}, {i}.0)" for i in mine_u))
        engines.append(e)
    workers = [LocalWorker(e, name=f"w{i}") for i, e in enumerate(engines)]
    c = ShardedCluster(workers, merge_engine=engines[0])
    c.key_columns["t"] = ["id"]
    c.key_columns["u"] = ["uid"]
    return c, engines


def test_dq_trace_assembles_one_cross_worker_tree():
    c, engines = mk_dq_cluster()
    got = c.query("select count(*) as n, sum(w) as s from t, u "
                  "where k = uid")
    assert int(got.n[0]) == 80
    eng = engines[0]
    spans = eng.last_trace
    assert len({s.trace_id for s in spans}) == 1
    names = {s.name for s in spans}
    assert {"dq-query", "dq-stage", "dq-task", "task-exec",
            "output-flush"} <= names
    by_id = {s.span_id: s for s in spans}
    # every span (except the root) parents inside the tree
    roots = [s for s in spans if s.parent_id is None]
    assert len(roots) == 1 and roots[0].name == "dq-query"
    for s in spans:
        if s.parent_id is not None:
            assert s.parent_id in by_id
    # worker task spans from BOTH workers
    workers = {by_id[s.parent_id].attrs.get("worker")
               for s in spans if s.name == "task-exec"}
    assert workers == {"local:w0", "local:w1"}
    # stage stats: channel bytes/rows populated — on whichever plane
    # the shuffle edges lowered to (host frames, or the device
    # collective's ici_bytes under the conftest 8-device mesh)
    stats = list(eng.dq_stage_stats)
    assert stats and sum(r["bytes"] + r.get("ici_bytes", 0)
                         for r in stats) > 0
    assert any(r["worker"] == "router" for r in stats)


def test_dq_profile_records_graph_wall_not_merge_stats():
    """The `.sys/query_profiles` row for a distributed query must carry
    the DQ GRAPH's wall/rows, not the router-merge statement's (or a
    stale previous statement's) numbers."""
    c, engines = mk_dq_cluster()
    got = c.query("select count(*) as n, sum(w) as s from t, u "
                  "where k = uid")
    eng = engines[0]
    prof = eng.profiles[-1]
    assert prof["kind"] == "dq-select"
    assert prof["rows_out"] == len(got) == 1
    # total covers the whole graph: at least the root span's wall
    root = eng.last_trace[0]
    assert prof["total_ms"] >= root.dur_ms * 0.9


def test_nested_statements_do_not_double_count_latency():
    """EXPLAIN ANALYZE re-enters execute(); only the outer statement may
    contribute a query-latency sample, and nested statements must not
    consume the sampling accumulator."""
    from ydb_tpu.utils.metrics import GLOBAL_HIST
    e = mk_engine()
    e.query("select count(*) as c from t")          # warm/compile
    before = GLOBAL_HIST.get("query/latency_ms").count
    e.query("explain analyze select count(*) as c from t")
    after = GLOBAL_HIST.get("query/latency_ms").count
    assert after - before <= 1                       # not 2
    # nested executes don't consume the fractional-rate accumulator:
    # with rate 0.5, alternating user statements sample exactly 1-in-2
    # even when each runs an internal statement
    e.trace_sample = 0.5
    e._trace_acc = 0.0
    e.slow_query_ms = float("inf")           # compile-slow first runs
    e._slow_sqls.clear()                     # must not force-trace
    traced = 0
    for _ in range(8):
        e.query("explain select count(*) as c from t")  # forced (explain)
        e.query("select count(*) as c from t")
        traced += bool(e.last_trace)
    assert traced == 4


def test_span_ids_unique_across_processes_and_int64_safe():
    """The id salt carries the FULL pid (distinct processes → disjoint
    id ranges) and every id stays below 2^63 — trace ids land in int64
    sysview columns, where an overflowing id would crash the scan."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from ydb_tpu.utils.tracing import _ids; "
            "print(next(_ids))")
    a = int(subprocess.check_output([sys.executable, "-c", code],
                                    cwd=repo))
    b = int(subprocess.check_output([sys.executable, "-c", code],
                                    cwd=repo))
    assert (a >> 33) != (b >> 33)       # distinct per-process salts
    assert 0 < a < 2 ** 63 and 0 < b < 2 ** 63


def test_merge_statement_phases_exclude_worker_spans():
    """The router-merge statement's OWN QueryStats.phases must cover
    only its spans — not the worker device spans already ingested into
    the shared trace before it ran."""
    c, engines = mk_dq_cluster()
    c.query("select count(*) as n, sum(w) as s from t, u where k = uid")
    eng = engines[0]
    total_exec = sum(s.dur_ms for s in eng.last_trace
                     if s.name == "task-exec")
    assert total_exec > 0
    merge_stats = [st for st in eng.query_history
                   if "__tmp" in st.sql or "__xj_" in st.sql]
    assert merge_stats, "router merge statement should be in history"
    ph = merge_stats[-1].phases
    # its device window must be its own, far below the workers' total
    assert ph.get("device_ms", 0.0) + ph.get("dispatch_ms", 0.0) \
        < total_exec


def test_dq_explain_analyze_profile_tree():
    c, engines = mk_dq_cluster()
    df = c.query("explain analyze select count(*) as n from t, u "
                 "where k = uid")
    text = "\n".join(df.plan)
    assert "DQ stage graph" in text
    assert "-- stage stats (per task):" in text
    assert "-- trace:" in text and "dq-task" in text
    assert "input-wait" in text


def test_sysview_dq_stage_stats_shape():
    c, engines = mk_dq_cluster()
    c.query("select count(*) as n from t, u where k = uid")
    eng = engines[0]
    df = eng.query('select stage, worker, rows, bytes, frames, plane, '
                   'ici_bytes, exec_ms, '
                   'input_wait_ms, backpressure_wait_ms, attempts '
                   'from ".sys/dq_stage_stats"')
    assert len(df) >= 3                      # ≥2 worker tasks + router
    assert set(df.worker) >= {"local:w0", "local:w1", "router"}
    assert (df.attempts >= 1).all()
    # channel traffic lands on the plane the edge lowered to
    assert df.bytes.sum() + df.ici_bytes.sum() > 0
    # composes with SQL like any table
    agg = eng.query('select worker, sum(rows) as r from '
                    '".sys/dq_stage_stats" group by worker '
                    'order by worker')
    assert len(agg) >= 3


def test_sysview_query_profiles_shape():
    e = mk_engine()
    e.query("select sum(v) as s from t")
    df = e.query('select sql, kind, total_ms, n_spans, dispatch_ms, '
                 'device_ms, readout_ms from ".sys/query_profiles"')
    assert len(df) >= 1
    row = df[df.sql == "select sum(v) as s from t"].iloc[-1]
    assert row.kind == "select"
    assert row.total_ms > 0 and row.n_spans > 0
    assert row.dispatch_ms > 0


def test_channel_writer_stats_and_backpressure():
    from ydb_tpu.cluster.exchange import ChannelWriter
    import pandas as pd
    import time
    landed = []

    def slow_send(peer, frame):
        time.sleep(0.01)
        landed.append((peer, len(frame)))

    w = ChannelWriter("ch", "src", slow_send, n_peers=1, frame_rows=64,
                      inflight_bytes=1024,
                      trace={"trace_id": 7, "parent_span_id": 3,
                             "sampled": True})
    df = pd.DataFrame({"a": np.arange(1000)})
    w.ship(0, df)
    w.close()
    st = w.stats()
    assert st["rows"] == 1000
    assert st["frames"] == len(landed) and st["frames"] > 1
    assert st["bytes"] == sum(n for (_p, n) in landed)
    # tiny in-flight budget + slow sink → the producer stalled
    assert st["backpressure_wait_ms"] > 0
    # trace ctx rides every frame header
    from ydb_tpu.cluster.exchange import unpack_header
    # re-pack one frame to check header content
    hdr_frames = []
    w2 = ChannelWriter("ch2", "s", lambda p, f: hdr_frames.append(f),
                       n_peers=1, trace={"trace_id": 7,
                                         "parent_span_id": 3})
    w2.ship(0, df.head(5))
    w2.close()
    h = unpack_header(hdr_frames[0])
    assert h["trace_id"] == 7 and h["parent_span_id"] == 3
