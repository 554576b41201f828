"""Names on the device trace (PR 26): program spans as profiler
annotations, fused programs and IR commands named from the plan, the
device wait split into queue and run, the pgwire front's own counters,
and the host-slow log line.

CPU runs: they show names, paths and counters, never a speed.
"""

import glob
import logging
import re

import pytest

from ydb_tpu.bench.tpch_gen import load_tpch
from ydb_tpu.query import QueryEngine
from ydb_tpu.query import engine as engine_mod
from ydb_tpu.query.executor import split_device_wait
from ydb_tpu.utils import progstats, tracing
from ydb_tpu.utils.metrics import GLOBAL

from tests.test_latemat import _gather_sizes
from tests.test_pgwire import PgClient
from tests.tpch_util import QUERIES

SF = 0.002

# (query, the literals a second statement of the same shape carries)
LITERALS = {
    "q1": [("'90'", "'75'")],
    "q6": [("1994-01-01", "1995-01-01"), ("0.05 and 0.07", "0.03 and 0.05"),
           ("< 24", "< 25")],
    "q3": [("1995-03-15", "1995-03-20")],
}
# what the gather / scatter ops of the query's programs must be scoped by
SCOPES = {
    "q1": {"groupby"},
    "q6": set(),
    "q3": {"latemat[", "groupby", "compact", "join0.probe"},
}
# and what none of a program may be: Q1's deferred columns are first read
# while the row positions are still the iota, so the program that does not
# compact first reads them in place (PR 29); Q6's keyless sum plans no
# Compact at all (PR 34): its one program gathers and scatters nothing;
# {query: (program, scopes)}
NO_SCOPES = {"q1": ("jit_lineitem_gs_", ("latemat[",)),
             "q6": ("jit_lineitem_g_", ("latemat[", "compact"))}
PROGRAM_SPANS = ("statement", "parse", "plan", "admission-wait", "execute",
                 "fused-attempt", "join-builds", "fused-prepare",
                 "superblock-upload", "param-upload", "device-dispatch",
                 "device-execute", "readout-transfer", "readout")
# the engine call's own phases: (phase, its span or None)
ENGINE_PHASES = (("prepare_ms", "fused-prepare"),
                 ("param_upload_ms", "param-upload"),
                 ("bookkeeping_ms", None))


def other_literals(q: str) -> str:
    sql = QUERIES[q]
    for old, new in LITERALS[q]:
        assert old in sql
        sql = sql.replace(old, new)
    return sql


def make_engine(seed: int = 19920101) -> QueryEngine:
    eng = QueryEngine()
    load_tpch(eng.catalog, sf=SF, seed=seed)
    return eng


@pytest.fixture(scope="module")
def eng():
    return make_engine()


@pytest.fixture(scope="module")
def eng2():
    return make_engine(seed=7)


def fused_programs(eng, sql: str) -> dict:
    """{key: name} of the fused programs two runs of `sql` executed (the
    second run may re-size its Compact and build another)."""
    out = {}
    for _ in range(2):
        eng.query(sql)
        assert eng.executor.last_path == "fused"
        for p in eng.last_stats.programs["programs"]:
            out[p["key"]] = p["name"]
    return out


# -- (a) one span mechanism, on the profiler's clock ------------------------


class _Recorder:
    names: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.names.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Recorder.names = []
    monkeypatch.setattr(tracing, "_Annotation", _Recorder)
    return _Recorder.names


@pytest.mark.parametrize("span", PROGRAM_SPANS)
def test_sampled_span_opens_annotation(eng, annotations, span):
    eng._plan_cache.clear()
    eng.query(QUERIES["q3"])
    assert span in annotations
    # the same names, the same count: one mechanism
    assert annotations.count(span) == \
        sum(1 for s in eng.last_trace if s.name == span)


def test_unsampled_statement_opens_nothing(eng, annotations, monkeypatch):
    monkeypatch.setattr(eng, "trace_sample", 0.0)
    # a statement that compiles is slow, and a slow text is sampled next
    monkeypatch.setattr(eng, "slow_query_ms", 1e9)
    eng._plan_cache.clear()
    sql = other_literals("q1")          # a text the slow-query set lacks
    eng.query(sql)
    eng.query(sql)
    assert annotations == []
    assert eng.last_trace == []
    with eng.tracer.annotate("pg-encode"):
        pass
    assert annotations == []            # follows the last statement


def test_spans_in_profiler_host_plane(eng, tmp_path):
    """The real profiler: a statement run under `jax.profiler.trace`
    leaves its spans in `/host:CPU`."""
    import jax
    from jax.profiler import ProfileData
    eng.query(QUERIES["q3"])            # compiled before the trace
    eng._plan_cache.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.query(QUERIES["q3"])
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    names = set()
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    for span in ("parse", "plan", "join-builds", "superblock-upload",
                 "readout-transfer"):
        assert span in names


def test_xla_scope_is_gone():
    from ydb_tpu.query import executor
    assert not hasattr(executor, "_xla_scope")


def test_admission_wait_is_a_phase(eng):
    eng.query(QUERIES["q6"])
    assert "admission_ms" in eng.last_stats.phases
    assert tracing.PHASE_SPANS["admission-wait"] == "admission_ms"


# -- (a2) the engine call's own time, named ---------------------------------


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_every_phase_of_the_engine_call_is_named(eng, q):
    import time
    for _ in range(2):
        eng.execute(QUERIES[q])             # compiled, planned, cached
    t0 = time.perf_counter()
    eng.execute(QUERIES[q])
    wall = (time.perf_counter() - t0) * 1e3
    st = eng.last_stats
    ph = st.phases
    for phase, span in ENGINE_PHASES:
        assert ph[phase] >= 0
        if span is not None:
            assert tracing.PHASE_SPANS[span] == phase
    assert ph["bookkeeping_ms"] > 0
    assert st.parse_ms + st.plan_ms + sum(ph.values()) <= wall
    prep = next(s for s in eng.last_trace if s.name == "fused-prepare")
    assert prep.attrs["hit"] is True and prep.attrs["compact"] == 0
    up = next(s for s in eng.last_trace if s.name == "param-upload")
    assert up.attrs["n"] >= 0 and up.attrs["bytes"] >= 0
    by_id = {s.span_id: s for s in eng.last_trace}
    assert by_id[up.parent_id] is prep
    assert by_id[prep.parent_id].name == "fused-attempt"


def test_bookkeeping_is_counted_and_annotated(eng, annotations):
    eng.query(QUERIES["q6"])
    b0 = GLOBAL.get("engine/bookkeeping_ms")
    del annotations[:]
    eng.query(QUERIES["q6"])
    assert GLOBAL.get("engine/bookkeeping_ms") - b0 == pytest.approx(
        eng.last_stats.phases["bookkeeping_ms"], abs=0.01)
    assert annotations.count("bookkeeping") == 1
    # no span: it runs after the statement's trace closed
    assert not any(s.name == "bookkeeping" for s in eng.last_trace)


@pytest.mark.parametrize("cache", ["miss", "hit"])
def test_plan_span_times_the_plan_lap_on_a_hit_too(eng, cache):
    if cache == "miss":
        eng._plan_cache.clear()
    else:
        eng.query(QUERIES["q6"])
    eng.query(QUERIES["q6"])
    assert eng.last_stats.plan_cache_hit is (cache == "hit")
    plan, = [s for s in eng.last_trace if s.name == "plan"]
    assert plan.attrs["cache"] == cache
    # the span opens after the lap starts and closes where it is taken
    # (round the read's registration, the path checks, the fingerprint
    # and the lookup or the planner)
    assert 0 < plan.dur_ms <= eng.last_stats.plan_ms + 1e-3
    assert "plan_ms" not in tracing.PHASE_SPANS.values()


def test_a_host_lane_ends_the_plan_span_before_it_runs(eng):
    eng.query("select 1 + 1 as two")
    assert eng.executor.last_path == "literal"
    plan, = [s for s in eng.last_trace if s.name == "plan"]
    assert "cache" not in plan.attrs
    assert eng.last_stats.plan_ms == 0.0


@pytest.fixture
def analyses(monkeypatch):
    """How many times each of a statement's analyses runs."""
    from ydb_tpu.utils import critpath, memledger
    seen = {"extract": 0, "phase_breakdown": 0, "summary": 0}

    def counting(name, real):
        def f(*a, **kw):
            seen[name] += 1
            return real(*a, **kw)
        return f
    monkeypatch.setattr(critpath, "extract",
                        counting("extract", critpath.extract))
    monkeypatch.setattr(tracing, "phase_breakdown",
                        counting("phase_breakdown", tracing.phase_breakdown))
    monkeypatch.setattr(memledger.MemLedger, "summary", counting(
        "summary", memledger.MemLedger.summary))
    return seen


def test_an_outermost_statement_is_analysed_once(eng, analyses):
    eng.query(QUERIES["q6"])
    for k in analyses:
        analyses[k] = 0
    eng.query(QUERIES["q6"])
    assert analyses == {"extract": 1, "phase_breakdown": 1, "summary": 1}
    st = eng.last_stats
    prof = eng.profiles[-1]
    # the one analysis feeds the statement, its profile and the ring
    assert st.critical_path == {k: v for k, v in prof["critical_path"]
                                .items() if k != "segments"}
    assert eng.critpath_stats[-1]["wall_ms"] == st.critical_path["wall_ms"]
    assert st.memory["peak_bytes"] > 0


def test_explain_analyze_still_prints_the_critical_path(eng, analyses):
    df = eng.query("explain analyze " + QUERIES["q6"])
    text = "\n".join(df["plan"])
    assert "-- critical path: " in text and "coverage" in text
    assert "-- phases: " in text and "prepare " in text
    # the inner statement's own analysis, then the outer one's close
    assert analyses["extract"] == 2 and analyses["phase_breakdown"] == 2


@pytest.mark.parametrize("span,cls", [("fused-prepare", "host_lane"),
                                      ("param-upload", "host_transfer"),
                                      ("plan", "host_lane")])
def test_critical_path_classes_the_new_spans(span, cls):
    from ydb_tpu.utils import critpath
    assert critpath.CLASS_BY_NAME[span] == cls


def _reader(name):
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parent.parent / "benchmark" \
        / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("phase", [p for p, _span in ENGINE_PHASES])
def test_the_phases_reader_takes_the_median(phase):
    from types import SimpleNamespace as NS
    read = _reader(phase)

    def ctx(*phases):
        return {"samples": [NS(call=None if p is None else NS(phases=p))
                            for p in phases]}
    assert read(ctx({phase: 3.0}, {phase: 1.0}, {"device_ms": 9.0},
                    None, {phase: 2.0, "device_ms": 7.0})) == 2.0
    # a program without the phase (the parent), or no statement sampled
    assert read(ctx({"device_ms": 9.0}, {}, None)) is None
    assert read(ctx()) is None


# -- (b) programs and IR commands named on the device -----------------------


@pytest.mark.parametrize("q", sorted(LITERALS))
def test_program_name_is_the_plan_shape(eng, eng2, q):
    mains = []
    for e, sql in ((eng, QUERIES[q]), (eng, other_literals(q)),
                   (eng2, QUERIES[q])):
        names = fused_programs(e, sql)
        for name in names.values():
            assert re.fullmatch(r"jit_[a-z0-9_]{1,40}", name), name
        # the statement's own program (a join's builds are programs too)
        mains.append({p["name"] for p in e.last_stats.programs["programs"]
                      if p["name"].startswith("jit_lineitem_")})
    # equal for two literal sets and two engines (over other data),
    # whatever their cache keys are
    assert len(mains[0]) == 1 and mains[0] == mains[1] == mains[2], mains


@pytest.mark.parametrize("q", sorted(SCOPES))
def test_hlo_ops_carry_the_ir_scope(eng, q):
    scoped = set()
    by_name = {}
    for key, name in fused_programs(eng, QUERIES[q]).items():
        text = progstats.hlo_text(key)
        assert text.startswith(f"HloModule {name}")
        by_name[name] = re.findall(
            r' (?:gather|scatter)\(.*op_name="jit\([a-z0-9_]+\)/([^"]*)"',
            text)
        scoped.update(by_name[name])
    for want in SCOPES[q]:
        assert any(want in s for s in scoped), (want, sorted(scoped))
    if q in NO_SCOPES:
        prefix, bad = NO_SCOPES[q]
        assert any(n.startswith(prefix) for n in by_name), sorted(by_name)
        own = [s for n, ss in by_name.items() if n.startswith(prefix)
               for s in ss]
        # Q1's group-by gathers; Q6's program holds no gather at all
        assert bool(own) == bool(SCOPES[q])
        assert not any(b in s for b in bad for s in own), (bad, sorted(own))
    if q == "q6":                       # no second program with a `c` mark
        assert all(n.startswith("jit_lineitem_g_") for n in by_name)
    # kinds and column names, never a literal
    assert not any("1994" in s or "1995" in s or "BUILDING" in s
                   for s in scoped)


def test_q9_keeps_its_name_and_probes_after_its_compact(eng):
    """PR 31 moved Q9's Compact from the end of its steps to directly
    after the `part` semi join. The name is the plan's shape, not the
    sizing, so it is the one the ledger's `breakdown` has had since PR 26;
    the `join2.probe` scope (the composite key's binary search) is still
    there, after the `compact/` scopes: its gathers take the bound's
    index count, `join1.probe`'s the scan's."""
    names = fused_programs(eng, QUERIES["q9"])
    own = [p for p in eng.last_stats.programs["programs"]
           if p["name"].startswith("jit_lineitem_")]
    assert [p["name"] for p in own] == ["jit_lineitem_j4_gsc_346a01"]
    assert names[own[0]["key"]] == own[0]["name"]
    sizes = _gather_sizes(progstats.hlo_text(own[0]["key"]))
    bound, = sizes["compact"]
    assert sizes["join2.probe"] == {bound}
    assert min(sizes["join1.probe"]) > 2 * bound


def test_name_in_sysview_and_explain(eng):
    names = set(fused_programs(eng, QUERIES["q6"]).values())
    inv = eng.query("select program, name from `.sys/compiled_programs` "
                    "where kind = 'fused'")
    assert names <= set(inv["name"])
    plan = "\n".join(eng.query("explain analyze " + QUERIES["q6"])["plan"])
    assert re.search(r"--   fused:[0-9a-f]{12} jit_lineitem_g\w*_[0-9a-f]{6}",
                     plan), plan


@pytest.mark.parametrize("table,want", [
    ("lineitem", "lineitem"), ("__xj_dq0123456789_orders", "tmp"),
    ("Weird-Name.x", "weird_name_x")])
def test_program_name_of_table(table, want):
    from types import SimpleNamespace as NS

    from ydb_tpu.ops import fused, ir
    pipe = NS(pre_program=ir.Program().filter(ir.Col("a")), steps=[],
              partial=None, scan=NS(table=table))
    name = fused.program_name(pipe, None, [], [], (), None, ("a",))
    assert re.fullmatch(rf"{want}_[0-9a-f]{{6}}", name), name
    # a literal does not move the name, a column does
    lit = NS(**{**vars(pipe), "pre_program": ir.Program().filter(
        ir.call("lt", ir.Col("a"), ir.Const(5, None)))})
    lit2 = NS(**{**vars(pipe), "pre_program": ir.Program().filter(
        ir.call("lt", ir.Col("a"), ir.Const(7, None)))})
    col = NS(**{**vars(pipe), "pre_program": ir.Program().filter(
        ir.call("lt", ir.Col("b"), ir.Const(7, None)))})
    args = (None, [], [], (), None, ("a",))
    assert fused.program_name(lit, *args) == fused.program_name(lit2, *args)
    assert fused.program_name(lit, *args) != fused.program_name(col, *args)


# -- (c) device wait split from device run ----------------------------------


@pytest.mark.parametrize("case,args,want", [
    # enqueued on an idle device: all of the wait is the run
    ("no-overlap", (10.0, 10.001, 10.5, 9.0), (0.0, 499.0)),
    # enqueued behind a program that completed at 10.55
    ("full-overlap", (10.01, 10.011, 11.01, 10.55), (539.0, 460.0)),
    # drained long after it completed: the start clamps into the wait
    ("late-drain", (10.0, 12.0, 12.001, 9.0), (0.0, 1.0)),
    # the other program completed after this wait ended (observed late)
    ("late-observer", (10.0, 10.001, 10.5, 10.7), (499.0, 0.0)),
])
def test_split_device_wait(case, args, want):
    queue_ms, run_ms = split_device_wait(*args)
    assert queue_ms == pytest.approx(want[0], abs=1e-6)
    assert run_ms == pytest.approx(want[1], abs=1e-6)
    t_wait, t_done = args[1], args[2]
    assert queue_ms >= 0 and run_ms >= 0
    assert queue_ms + run_ms == pytest.approx((t_done - t_wait) * 1e3)


def test_one_stream_waits_for_nothing(eng):
    eng.query(QUERIES["q6"])
    q0 = GLOBAL.get("prog/queue_ms")
    d0 = GLOBAL.get("prog/device_ms")
    eng.query(QUERIES["q6"])
    st = eng.last_stats
    ph = st.phases
    assert ph["queue_ms"] == pytest.approx(0.0, abs=0.5)
    assert ph["device_ms"] > 0
    span = next(s for s in eng.last_trace if s.name == "device-execute")
    assert span.attrs["queue_ms"] + span.attrs["run_ms"] == \
        pytest.approx(span.dur_ms, abs=0.5)
    # the counters carry the same split
    assert GLOBAL.get("prog/device_ms") - d0 == \
        pytest.approx(span.attrs["run_ms"], abs=0.01)
    assert GLOBAL.get("prog/queue_ms") - q0 == \
        pytest.approx(span.attrs["queue_ms"], abs=0.01)
    assert st.programs["device_ms"] == pytest.approx(ph["device_ms"],
                                                     abs=0.01)
    # disjoint: parse, plan and the phases fit inside the engine's wall,
    # `total_ms` and then the statement's own bookkeeping
    assert st.parse_ms + st.plan_ms + sum(ph.values()) \
        <= st.total_ms + ph["bookkeeping_ms"] + 0.5


def test_phase_breakdown_counts_an_unsplit_span_whole():
    spans = [tracing.Span("device-execute", 1, 1, None, 0.0, dur_ms=7.0),
             tracing.Span("device-execute", 1, 2, None, 9.0, dur_ms=5.0,
                          attrs={"queue_ms": 3.0, "run_ms": 2.0})]
    assert tracing.phase_breakdown(spans) == {"device_ms": 9.0,
                                              "queue_ms": 3.0}


# -- (d) the front, from inside ---------------------------------------------


def test_pgwire_counts_its_own_work(eng, annotations):
    from ydb_tpu.server.pgwire import serve_pg
    keys = ("front/pg/statements", "front/pg/rows", "front/pg/bytes",
            "front/pg/encode_ms")
    srv = serve_pg(eng)
    try:
        c = PgClient(srv.port)
        before = {k: GLOBAL.get(k) for k in keys}
        _cols, rows, _tag = c.query(QUERIES["q1"])
        assert rows
        c.query("create table pg_t (id Int64 not null, primary key (id))")
        delta = {k: GLOBAL.get(k) - before[k] for k in keys}
        c.sock.close()
    finally:
        srv.stop()
    assert delta["front/pg/statements"] == 1     # the DDL holds no rows
    assert delta["front/pg/rows"] == len(rows)
    assert delta["front/pg/bytes"] > 40 * len(rows)
    assert delta["front/pg/encode_ms"] > 0
    assert annotations.count("pg-encode") == 2   # encode, flush


# -- a host-slow statement says where ---------------------------------------


def test_host_slow_statement_logs_its_phases(eng, monkeypatch, caplog):
    # the threshold is pinned in both legs: what a warm Q6 costs the host
    # on a shared CPU runner is not this test's to assert
    monkeypatch.setattr(engine_mod, "HOST_SLOW_MS", 1e9)
    eng.query(QUERIES["q6"])
    n0 = GLOBAL.get("slow_query/host_slow")
    with caplog.at_level(logging.WARNING, logger="ydb_tpu.slow_query"):
        eng.query(QUERIES["q6"])                 # under the threshold
        assert GLOBAL.get("slow_query/host_slow") == n0
        assert not caplog.records
        monkeypatch.setattr(engine_mod, "HOST_SLOW_MS", 0.0)
        eng.query(QUERIES["q6"])
    assert GLOBAL.get("slow_query/host_slow") == n0 + 1
    (rec,) = caplog.records
    line = rec.getMessage()
    assert "select sum(l_extendedprice*l_discount)" in line
    for part in ("wall", "queue_ms", "device_ms", "unspanned"):
        assert part in line


@pytest.mark.parametrize("op,want", [
    ("jit_lineitem_gc_410372(..9580)/fusion.3 fusion:Custom",
     "compact/gather"),
    # the compiler's own name for a fusion has underscores in it
    ("jit_lineitem_gc_410372(..9580)/select_reduce_fusion.1 fusion:Loop",
     "groupby/reduce_sum | reduce: groupby/reduce_sum"),
    ("jit_lineitem_gc_410372(..9580)/sort.5 sort", "compact/sort"),
    ("jit_lineitem_gc_410372(..9580)/fusion.99 fusion:Loop", "?"),
])
def test_op_scopes_names_a_breakdowns_operation(op, want):
    """`scripts/op_scopes.annotate`: a traced line's `device_ops` entry to
    the scopes the program's HLO text gave that instruction."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parent.parent / "scripts" / "op_scopes.py"
    spec = importlib.util.spec_from_file_location("op_scopes", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    programs = {"jit_lineitem_gc_410372": {"ops": {
        "fusion.3": {"scope": "compact/gather", "inner": {}},
        "sort.5": {"scope": "compact/sort", "inner": {}},
        "select_reduce_fusion.1": {
            "scope": "groupby/reduce_sum",
            "inner": {"reduce": ["groupby/reduce_sum"]}},
    }}}
    (_op, sec, scopes), = mod.annotate([[op, 1.5]], programs)
    assert (sec, scopes) == (1.5, want)
