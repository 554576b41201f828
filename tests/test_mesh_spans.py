"""The mesh lanes seen (PR 28): spans for each step of `distributed`,
`distributed-shuffle-join` and `distributed-map`, their device waits taken
through the executor's one accounting, `shard_map` programs named from the
plan's shape and scoped inside, byte counters of the exchanges, and the
lane's name on the statement's stats.

TPC-H at sf 0.01 over four of the virtual CPU devices, broadcast budget 1
(what `benchmark/configs/tpch-sf1-mesh4.json` sets): Q3, Q9 and Q18 take
the shuffle join. CPU runs: paths, names and counts, never a speed.
"""

import numpy as np
import pytest

from ydb_tpu.bench.tpch_gen import load_tpch
from ydb_tpu.ops.device import bucket_capacity
from ydb_tpu.parallel import make_mesh, shuffle_join
from ydb_tpu.query import QueryEngine
from ydb_tpu.utils import progstats, tracing
from ydb_tpu.utils.metrics import GLOBAL

from tests.tpch_util import QUERIES, assert_frames_match, oracle

SF = 0.01
NDEV = 4
SHUFFLE = "distributed-shuffle-join"
MESH_PHASES = ("mesh_build_ms", "stage_ms", "exchange_ms", "merge_ms")
MESH_SPANS = ("shuffle-join", "mesh-build", "mesh-stage", "mesh-exchange",
              "mesh-merge")
MAP_SQL = ("select l_orderkey, l_extendedprice from lineitem "
           "where l_quantity > 45 and l_discount >= 0.05 "
           "order by l_extendedprice desc, l_orderkey limit 20")
# (statement, the lane it takes with the budget at 1)
LANES = {"distributed": QUERIES["q1"], SHUFFLE: QUERIES["q3"],
         "distributed-map": MAP_SQL}


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(mesh=make_mesh(NDEV))
    e.tpch_data = load_tpch(e.catalog, sf=SF, shards=NDEV)
    e.executor.dist_broadcast_budget_bytes = 1
    return e


def counters_delta(eng, sql: str) -> dict:
    before = GLOBAL.snapshot()
    eng.query(sql)
    return {k: v - before.get(k, 0) for k, v in GLOBAL.snapshot().items()
            if v != before.get(k, 0)}


@pytest.fixture(scope="module")
def warm_q3(eng):
    """Q3's third run: every program built, the build cache filled."""
    for _ in range(2):
        eng.query(QUERIES["q3"])
    delta = counters_delta(eng, QUERIES["q3"])
    return eng.last_stats, list(eng.last_trace), delta


@pytest.mark.parametrize("name", ["q3", "q9", "q18"])
def test_shuffle_lane_agrees_with_the_oracle(eng, name):
    got = eng.query(QUERIES[name])
    assert eng.executor.last_path == SHUFFLE
    assert eng.last_stats.path == SHUFFLE and eng.last_stats.distributed
    want = oracle(name, eng.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)


def test_a_q1_on_the_mesh_keeps_distributed(eng):
    got = eng.query(QUERIES["q1"])
    assert eng.executor.last_path == "distributed"
    want = oracle("q1", eng.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)


# -- spans and phases --------------------------------------------------------


@pytest.mark.parametrize("phase", MESH_PHASES + ("device_ms", "queue_ms",
                                                 "readout_ms"))
def test_warm_statement_has_the_phase(warm_q3, phase):
    stats, _trace, _delta = warm_q3
    assert stats.phases[phase] >= 0.0


def test_phases_cover_a_warm_statement(warm_q3):
    stats, _trace, _delta = warm_q3
    # the engine's wall: `total_ms`, then its own bookkeeping
    unspanned = stats.total_ms + stats.phases["bookkeeping_ms"] \
        - stats.parse_ms - stats.plan_ms - sum(stats.phases.values())
    assert 0.0 <= unspanned < 0.1 * stats.total_ms, (unspanned, stats.phases)


def test_mesh_spans_nest_under_the_lane(warm_q3):
    _stats, trace, _delta = warm_q3
    by_id = {s.span_id: s for s in trace}
    lane = next(s for s in trace if s.name == "shuffle-join")
    assert lane.attrs["ndev"] == NDEV and lane.attrs["build_rows"] > 0
    for name in MESH_SPANS[1:]:
        s = next(s for s in trace if s.name == name)
        assert by_id[s.parent_id] is lane
    # one device wait each: the build pipeline, the stage, the exchange
    # program, the merge
    waits = [by_id[s.parent_id].name for s in trace
             if s.name == "device-execute"]
    assert waits == ["mesh-build", "mesh-stage", "mesh-exchange",
                     "mesh-merge"]
    assert [by_id[s.parent_id].name for s in trace
            if s.name == "readout-transfer"] == ["mesh-merge"]


def test_mesh_build_waits_for_the_chip_in_a_device_execute(warm_q3):
    """The build pipeline's run on the chip is a `device-execute` under
    `mesh-build` (`device_ms`, split into queue and run), not the host's
    partitioning time (`mesh_build_ms` is the span less it)."""
    stats, trace, _delta = warm_q3
    by_id = {s.span_id: s for s in trace}
    build = next(s for s in trace if s.name == "mesh-build")
    wait, = [s for s in trace if s.name == "device-execute"
             and by_id[s.parent_id] is build]
    assert wait.attrs["queue_ms"] + wait.attrs["run_ms"] == \
        pytest.approx(wait.dur_ms, abs=0.5)
    assert stats.phases["mesh_build_ms"] == pytest.approx(
        build.dur_ms - wait.dur_ms, abs=0.01)


class _Recorder:
    names: list = []

    def __init__(self, name):
        _Recorder.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("span", MESH_SPANS)
def test_mesh_span_opens_an_annotation(eng, warm_q3, monkeypatch, span):
    _Recorder.names = []
    monkeypatch.setattr(tracing, "_Annotation", _Recorder)
    eng.query(QUERIES["q3"])
    assert _Recorder.names.count(span) == 1


def test_phase_breakdown_counts_a_nested_phase_once():
    """A phase is its span's OWN time: a `mesh-build` that holds the build
    statement's dispatch and device wait keeps only the rest."""
    def span(i, name, parent, dur, **attrs):
        return tracing.Span(name, 1, i, parent, 0.0, dur, attrs)
    spans = [
        span(1, "shuffle-join", None, 100.0),
        span(2, "mesh-build", 1, 40.0),
        span(3, "fused-attempt", 2, 30.0),            # no phase: seen through
        span(4, "device-dispatch", 3, 5.0),
        span(5, "device-execute", 3, 20.0, run_ms=15.0, queue_ms=5.0),
        span(6, "mesh-exchange", 1, 50.0),
        span(7, "device-execute", 6, 45.0, run_ms=45.0, queue_ms=0.0),
    ]
    got = tracing.phase_breakdown(spans)
    assert got == {"mesh_build_ms": 15.0, "dispatch_ms": 5.0,
                   "device_ms": 60.0, "queue_ms": 5.0, "exchange_ms": 5.0}
    assert sum(got.values()) == 90.0        # the two steps, nothing twice


# -- programs, names and scopes ----------------------------------------------


def mesh_rows():
    return {r["name"]: r for r in progstats.inventory_rows()
            if r["kind"] in ("mesh-sj", "mesh-merge")}


def test_warm_statement_runs_two_named_mesh_programs(warm_q3):
    stats, _trace, delta = warm_q3
    assert delta["prog/executions"] == 2 and "prog/registered" not in delta
    assert delta["prog/device_ms"] > 0
    names = sorted(p["name"] for p in stats.programs["programs"])
    assert len(names) == 2
    assert names[0].startswith("jit_mesh_merge_lineitem_")
    assert names[1].startswith("jit_mesh_sj_lineitem_")
    rows = mesh_rows()
    assert all(rows[n]["execs"] >= 3 and rows[n]["compiles"] == 1
               for n in names)


def mesh_names(stats) -> set:
    return {p["name"] for p in stats.programs["programs"]
            if p["name"].startswith("jit_mesh_")}


def test_program_names_follow_the_shape_not_the_literals(eng, warm_q3):
    q3 = mesh_names(warm_q3[0])
    eng.query(QUERIES["q3"].replace("1995-03-15", "1995-03-20")
              .replace("BUILDING", "MACHINERY"))
    assert eng.executor.last_path == SHUFFLE
    assert mesh_names(eng.last_stats) == q3
    # another statement shape is another name
    eng.query(QUERIES["q18"])
    # (three: its build side, a group-by over lineitem, merges on the mesh)
    q18 = mesh_names(eng.last_stats)
    assert len(q18) == 3 and not q18 & q3


@pytest.mark.parametrize("kind,scopes", [
    ("mesh-sj", ("exchange/bucket", "exchange/all_to_all",
                 "exchange/compact", "shuffle.probe", "partial/groupby")),
    ("mesh-merge", ("partial/groupby", "exchange/bucket",
                    "exchange/all_to_all", "exchange/compact",
                    "merge/groupby"))])
def test_mesh_program_is_scoped_inside(warm_q3, kind, scopes):
    row = next(r for r in mesh_rows().values() if r["kind"] == kind)
    text = progstats.hlo_text(row["program"])
    assert "all-to-all" in text
    for scope in scopes:
        assert f"/{scope}" in text, scope


# -- counters ----------------------------------------------------------------


@pytest.fixture
def exchange_seen(monkeypatch):
    """What a shuffle join's exchange was sized from: the probe side's
    schema and the rows each device counted per target."""
    seen = {"counts": []}
    real_run = shuffle_join.ShuffleJoin.run
    real_counts = shuffle_join._target_counts

    def spy_run(self, per_dev_blocks, *a, **kw):
        seen["schema"] = self.in_schema
        return real_run(self, per_dev_blocks, *a, **kw)

    def spy_counts(*a, **kw):
        seen["counts"].append(real_counts(*a, **kw))
        return seen["counts"][-1]
    monkeypatch.setattr(shuffle_join.ShuffleJoin, "run", spy_run)
    monkeypatch.setattr(shuffle_join, "_target_counts", spy_counts)
    return seen


def wire_by_hand(seen) -> tuple:
    """(counts, bytes a row, bytes on the wire): a segment holds the
    largest count, rounded up a power of two; every device sends NDEV
    segments, one of them to itself; a row is each column's width and its
    validity byte."""
    counts = np.stack([np.asarray(c) for c in seen["counts"]])
    assert counts.shape == (NDEV, NDEV)
    seg = bucket_capacity(int(counts.max()), minimum=128)
    assert seg < 2 * counts.max()          # sized from counted rows
    row = sum(np.dtype(c.dtype.np).itemsize + 1
              for c in seen["schema"].columns)
    return counts, row, NDEV * NDEV * seg * row * (NDEV - 1) // NDEV


def test_exchange_bytes_are_the_segments_shape(eng, warm_q3, exchange_seen):
    delta = counters_delta(eng, QUERIES["q3"])
    counts, _row, wire = wire_by_hand(exchange_seen)
    assert delta["mesh/exchange_bytes/shuffle-join"] == wire
    assert counts.sum() == sum(
        v for k, v in delta.items()
        if k.startswith("mesh/exchange_rows/shuffle-join/"))
    # lineitem is sharded by the hash of l_orderkey the exchange routes
    # by: every probe row already sits on its key's owner, none crosses
    assert counts.sum() == np.trace(counts)
    assert "mesh/exchange_live_bytes/shuffle-join" not in delta
    assert 0 < delta["mesh/exchange_live_bytes/merge"] \
        <= delta["mesh/exchange_bytes/merge"]


def test_rows_that_cross_are_counted_live(eng, exchange_seen):
    """A join on l_partkey: lineitem is sharded by l_orderkey, so about
    three rows of four leave their device."""
    import pandas as pd
    delta = counters_delta(
        eng, "select p_size, count(*) as n, sum(l_quantity) as q "
             "from lineitem, part where l_partkey = p_partkey "
             "group by p_size order by p_size")
    assert eng.executor.last_path == SHUFFLE
    got = eng.last_stats
    counts, row, wire = wire_by_hand(exchange_seen)
    crossing = counts.sum() - np.trace(counts)
    assert 0.6 * counts.sum() < crossing < 0.9 * counts.sum()
    assert delta["mesh/exchange_bytes/shuffle-join"] == wire
    assert delta["mesh/exchange_live_bytes/shuffle-join"] == crossing * row
    li = pd.DataFrame(eng.tpch_data.tables["lineitem"])
    pa = pd.DataFrame(eng.tpch_data.tables["part"])
    want = li.merge(pa, left_on="l_partkey", right_on="p_partkey") \
        .groupby("p_size").l_quantity.agg(["count", "sum"]).reset_index()
    out = eng.query("select p_size, count(*) as n, sum(l_quantity) as q "
                    "from lineitem, part where l_partkey = p_partkey "
                    "group by p_size order by p_size")
    assert got.path == SHUFFLE
    assert list(out.p_size) == list(want.p_size)
    assert list(out.n) == list(want["count"])
    np.testing.assert_allclose(out.q, want["sum"], rtol=1e-12)


@pytest.mark.parametrize("lane", list(LANES))
def test_every_mesh_lane_names_itself(eng, lane):
    from ydb_tpu.server.service import _result_payload
    delta = counters_delta(eng, LANES[lane])
    stats = eng.last_stats
    assert eng.executor.last_path == lane
    assert stats.path == lane and stats.distributed and not stats.fused
    assert delta[f"mesh/statements/{lane}"] == 1
    assert f"path mesh {lane}" in stats.render()
    assert "stage_ms" in stats.phases and stats.phases["merge_ms"] >= 0
    block = eng.execute(LANES[lane])
    assert _result_payload(block, eng.last_stats)["stats"]["path"] == lane
    hist = eng.query("select path from `.sys/query_metrics`")
    assert lane in set(hist["path"])
