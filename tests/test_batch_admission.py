"""The batched lane admits a batch by what its stacked program holds (PR 36).

`Executor.batched_working_set` is the one mechanism of the lane's gate and
of its reservation: the compiler's `memory_analysis()` for a program that
exists, a bound from the plan before that; the shared inputs count once.
Beside it: the stacked programs exist before the first herd, every
declined statement says why, a member's wait is a span and a phase, and a
herd whose members agree on a literal meets no program of its own.
With the lane off nothing of this runs: the scan cells' cache keys are
pinned to the parent commit's.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from tests.test_batch_lane import _mk_engine, _storm
from ydb_tpu.utils.metrics import COUNTER_REGISTRY, GLOBAL

POINT = "select a, b from t where k = {}"


def _lane(monkeypatch, window=500, max_batch=8, **kw):
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", str(window))
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", str(max_batch))
    return _mk_engine(**kw)


def _delta(names, before):
    return {n: GLOBAL.get(n) - b for n, b in zip(names, before)}


# -- the working set ---------------------------------------------------------


def test_a_compiled_shape_is_admitted_by_the_compilers_figure(monkeypatch):
    """After the first statement of a shape every stacked program exists
    and `batched_working_set` answers `compiled`: arguments + temporaries
    + outputs of that program's inventory entry, not members x estimate."""
    from ydb_tpu.query.admission import batch_reservation_bytes
    from ydb_tpu.utils import progstats
    eng = _lane(monkeypatch)
    eng.query(POINT.format(0))
    plan = eng._plan_cache[POINT.format(0)][1]
    snap = eng.snapshot()
    est = 1 << 20
    for n, bb in ((2, 2), (3, 4), (5, 8), (8, 8)):
        held, how = eng.executor.batched_working_set(plan, snap, n, est)
        assert how == "compiled"
        mems = [r for r in progstats.inventory_rows()
                if r["kind"] == "batched"]
        figures = {r["arg_bytes"] + r["temp_bytes"] + r["out_bytes"]
                   for r in mems}
        assert held in figures
        assert held < batch_reservation_bytes(est, bb)
    # the three sizes hold different amounts: each has its own figure
    assert len({eng.executor.batched_working_set(plan, snap, n, est)[0]
                for n in (2, 4, 8)}) == 3


SHAPES = {
    # shape: (statement, how the lane reckons before a program exists)
    "point": ("select a, b from t where k = {}", "plan"),
    "expression": ("select k, b * 2.0 + a as v from t where k = {}",
                   "plan"),
    "keyless-sum": ("select sum(b * a) as s, count(*) as n from t "
                    "where k < {}", "plan"),
    "joined": ("select w from t join dim on t.a = dim.a where k = {}",
               "plan"),
    # a body that sorts at scan capacity: no bound, members x estimate
    "sorted-groupby": ("select a, sum(b) as s from t where k < {} "
                       "group by a", "members"),
    "order-by": ("select k, b from t where k > {} order by b desc limit 5",
                 "members"),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_plans_bound_lies_above_the_compilers_figure(monkeypatch,
                                                         shape):
    """Before a program exists the lane reckons from the plan: shared
    inputs + member slots x scan slots x `stacked_body_width`. With the
    build-ahead off the first herd compiles inside its dispatch; the
    figure it leaves is under what the bound said (the CPU compiler's
    here; the chip's for Q6 at 64 Mi slots in tests/test_tpu_compile.py)."""
    from ydb_tpu.query.admission import batch_reservation_bytes
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    eng = _lane(monkeypatch, rows=300)
    eng.execute("create table dim (a Int64 not null, w Int64, "
                "primary key (a))")
    eng.execute("insert into dim (a, w) values "
                + ", ".join(f"({i}, {i * 100})" for i in range(7)))
    sql, how_before = SHAPES[shape]
    eng.query(sql.format(7))
    plan = eng._plan_cache[sql.format(7)][1]
    snap, est = eng.snapshot(), 1 << 20
    bound, how = eng.executor.batched_working_set(plan, snap, 8, est)
    assert how == how_before
    if how == "members":
        assert bound == batch_reservation_bytes(est, 8)
    b0 = GLOBAL.get("batch/batches")
    _storm(eng, [sql.format(20 + i) for i in range(8)])
    assert GLOBAL.get("batch/batches") > b0
    held, how = eng.executor.batched_working_set(plan, snap, 8, est)
    assert how == "compiled"
    if how_before == "plan":
        assert est < held <= bound


def test_two_batches_hold_reservations_side_by_side(monkeypatch):
    """The reservation counts the shared scan once: under a budget that
    members x the per-member estimate would have filled alone (and so
    serialized against everything), two sealed batches are admitted
    together and neither waits."""
    from ydb_tpu.query.admission import batch_reservation_bytes
    eng = _lane(monkeypatch, max_batch=4)
    eng.query(POINT.format(0))
    eng.query("select b from t where k = 0")       # a second shape
    one = batch_reservation_bytes(1 << 20, 4)      # what N x est reserved
    eng.admission.budget = one - 1                 # refuses even one
    ex = eng.executor
    real = ex.execute_fused_batched
    inside, release = threading.Barrier(3), threading.Event()
    seen = []

    def held(plan, members, snapshot, info=None):
        seen.append((eng.admission.active, eng.admission.in_flight,
                     dict(info)))
        inside.wait(timeout=20)            # both batches are admitted
        release.wait(timeout=20)
        return real(plan, members, snapshot, info=info)

    monkeypatch.setattr(ex, "execute_fused_batched", held)
    w0 = GLOBAL.get("admission/waits")
    texts = [POINT.format(i) for i in range(4)] \
        + [f"select b from t where k = {i}" for i in range(4)]
    got = {}
    storm = threading.Thread(target=lambda: got.update(_storm(eng, texts)))
    storm.start()
    inside.wait(timeout=20)
    assert eng.admission.active == 2
    both = eng.admission.in_flight
    release.set()
    storm.join()
    assert len(got) == 8 and len(seen) == 2
    assert sum(s[2]["reserved_bytes"] for s in seen) == both
    assert both <= eng.admission.budget < one
    assert all(s[2]["admitted_by"] == "compiled" for s in seen)
    assert GLOBAL.get("admission/waits") == w0
    assert eng.admission.in_flight == 0 and eng.admission.active == 0


# -- why a statement stays on the per-query path -----------------------------


def _decline_case(case, monkeypatch):
    """-> (engine, statement) the lane declines for `case`."""
    if case == "mesh":
        from ydb_tpu.parallel import make_mesh
        from ydb_tpu.query import QueryEngine
        real = QueryEngine.__init__
        monkeypatch.setattr(
            QueryEngine, "__init__",
            lambda self, *a, **kw: real(self, *a, mesh=make_mesh(2), **kw))
    eng = _lane(monkeypatch, window=30)
    sql = POINT.format(3)
    if case == "no-lift":
        # every planned SELECT is lifted; a plan built by hand (a DQ
        # stage's) is not
        import dataclasses
        real_plan = eng.planner.plan_select
        monkeypatch.setattr(
            eng.planner, "plan_select",
            lambda sel: dataclasses.replace(real_plan(sel), lift_sig=None))
    elif case == "subplans":
        sql = "select k from t where b > (select avg(b) from t) and k < 9"
    elif case == "working-set":
        eng.query(sql)
        eng.executor.fused_scan_budget_bytes = 1 << 10
    elif case == "merge-budget":
        eng.executor.merge_budget_bytes = 1 << 10
    elif case == "row-store":
        # a scan whose table offers no source ids (the lane's data
        # identity is built from them)
        def no_ids(name, snap):
            raise AttributeError("no scan sources")
        monkeypatch.setattr(eng._batch_lane, "_table_sig", no_ids)
    return eng, sql


@pytest.mark.parametrize("case", ["no-lift", "subplans", "mesh",
                                  "working-set", "merge-budget",
                                  "row-store"])
def test_a_declined_statement_counts_its_reason(monkeypatch, case):
    assert "batch/declined/*" in COUNTER_REGISTRY
    eng, sql = _decline_case(case, monkeypatch)
    names = ["batch/declined", f"batch/declined/{case}"]
    before = [GLOBAL.get(n) for n in names]
    df = eng.query(sql)
    assert len(df) >= 0
    assert _delta(names, before) == {names[0]: 1, names[1]: 1}
    assert not eng.last_stats.batching
    assert eng.executor.last_path != "fused-batched"


def test_a_shape_that_fits_is_not_declined(monkeypatch):
    eng = _lane(monkeypatch, window=30)
    d0 = GLOBAL.get("batch/declined")
    eng.query(POINT.format(1))
    assert GLOBAL.get("batch/declined") == d0
    assert eng.last_stats.batching["sealed_by"] == "alone"


@pytest.mark.parametrize("probe_ms, sealed_by, coalesced", [
    (2.0, "alone", 1), (5000.0, "full", 2)])
def test_the_alone_probe_is_the_engines_attribute(monkeypatch, probe_ms,
                                                  sealed_by, coalesced):
    """A leader still alone after `batch_alone_probe_ms` runs at once; a
    second member inside the probe buys the window (here it fills the
    group)."""
    eng = _lane(monkeypatch, window=5000, max_batch=2)
    assert eng.batch_alone_probe_ms == 2.0     # the default
    eng.query(POINT.format(0))
    eng.batch_alone_probe_ms = probe_ms
    out = {}

    def late():
        time.sleep(0.25)                   # long after a 2 ms probe
        eng.query(POINT.format(2))
        out["late"] = eng.last_stats.batching
    t = threading.Thread(target=late)
    t.start()
    eng.query(POINT.format(1))
    first = eng.last_stats.batching
    t.join()
    assert (first["sealed_by"], first["coalesced"]) == (sealed_by, coalesced)
    assert out["late"]["coalesced"] == coalesced


# -- spans, phases, counters -------------------------------------------------


def _herd_stats(eng, texts):
    """Run `texts` as one herd; -> [(stats, span names -> attrs)] a member."""
    out, errs = {}, []
    barrier = threading.Barrier(len(texts))

    def one(i, sql):
        try:
            barrier.wait()
            eng.query(sql)
            out[i] = (eng.last_stats,
                      {s.name: dict(s.attrs) for s in eng.last_trace})
        except Exception as e:             # noqa: BLE001
            errs.append(repr(e))
    ts = [threading.Thread(target=one, args=(i, q))
          for i, q in enumerate(texts)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs[:3]
    return [out[i] for i in range(len(texts))]


def test_batch_wait_is_a_span_and_a_phase_on_leader_and_follower(
        monkeypatch):
    from ydb_tpu.utils import tracing
    assert tracing.PHASE_SPANS["batch-wait"] == "batch_wait_ms"
    eng = _lane(monkeypatch, max_batch=4)
    eng.query(POINT.format(0))
    members = _herd_stats(eng, [POINT.format(i) for i in range(1, 5)])
    leaders = [m for m in members if m[0].batching["leader"]]
    followers = [m for m in members if not m[0].batching["leader"]]
    assert len(leaders) == 1 and len(followers) == 3
    for stats, spans in members:
        assert stats.batching["coalesced"] == 4
        assert stats.batching["sealed_by"] == "full"
        assert stats.batching["bb"] == 4
        assert stats.batching["admitted_by"] == "compiled"
        w = spans["batch-wait"]
        assert (w["b"], w["bb"], w["sealed_by"]) == (4, 4, "full")
        assert stats.phases["batch_wait_ms"] >= 0
    stats, spans = leaders[0]
    assert spans["batch-wait"]["leader"] is True
    # the leader's own phases stay what they are, disjoint from the wait
    assert {"admission_ms", "dispatch_ms", "device_ms", "queue_ms",
            "readout_ms"} <= set(stats.phases)
    d = spans["device-dispatch-batched"]
    assert d["b"] == 4 and d["reserved_mb"] >= 0 and d["temp_mb"] >= 0
    assert spans["admission-wait"]["waited"] is False
    for stats, spans in followers:
        assert spans["batch-wait"]["leader"] is False
        assert set(stats.phases) == {"batch_wait_ms"}
        assert "device-dispatch-batched" not in spans
    text = "\n".join(leaders[0][0].render().splitlines())
    assert "sealed by full" in text and "4 member slots (0 pad)" in text
    assert "batch_wait" in text and "(compiled)" in text


def test_explain_analyze_prints_the_span_and_the_block(monkeypatch):
    eng = _lane(monkeypatch, window=30)
    df = eng.query("explain analyze " + POINT.format(5))
    text = "\n".join(df["plan"])
    assert "batching: coalesced 1" in text and "sealed by alone" in text
    assert "batch-wait" in text and "sealed_by=alone" in text
    assert "admission-wait" in text


def test_a_batch_of_eleven_pads_five_slots(monkeypatch):
    for n in ("batch/member_slots", "batch/pad_slots",
              "batch/reserved_bytes", "batch/ahead_compiles"):
        assert n in COUNTER_REGISTRY
    eng = _lane(monkeypatch, max_batch=16)
    eng.query(POINT.format(0))
    names = ["batch/batches", "batch/member_slots", "batch/pad_slots",
             "batch/coalesced_queries", "batch/reservations"]
    before = [GLOBAL.get(n) for n in names]
    r0 = GLOBAL.get("batch/reserved_bytes")
    _storm(eng, [POINT.format(i) for i in range(1, 12)])
    d = _delta(names, before)
    # a straggler may seal a group of its own: every batch pads to its
    # power of two, and the eleven ride 16 slots when they seal as one
    assert d["batch/coalesced_queries"] + (
        d["batch/reservations"] - d["batch/batches"]) == 11
    if d["batch/batches"] == 1 and d["batch/coalesced_queries"] == 11:
        assert (d["batch/member_slots"], d["batch/pad_slots"]) == (16, 5)
    assert d["batch/pad_slots"] == d["batch/member_slots"] \
        - d["batch/coalesced_queries"]
    assert GLOBAL.get("batch/reserved_bytes") > r0


# -- the programs exist before the herd --------------------------------------


def test_build_ahead_leaves_nothing_to_compile_for_the_first_herd(
        monkeypatch):
    eng = _lane(monkeypatch, max_batch=8)
    a0, r0 = (GLOBAL.get("batch/ahead_compiles"),
              GLOBAL.get("prog/registered"))
    eng.query(POINT.format(0))             # one connection's warm-up
    assert GLOBAL.get("batch/ahead_compiles") - a0 == 3     # Bb 2, 4, 8
    assert GLOBAL.get("prog/registered") - r0 == 4          # + its own
    r1 = GLOBAL.get("prog/registered")
    eng.query(POINT.format(1))             # the shape again: nothing new
    assert GLOBAL.get("prog/registered") == r1
    names = ["batch/batches", "batch/trace_errors", "batch/fallbacks",
             "batch/declined", "batch/ahead_compiles"]
    before = [GLOBAL.get(n) for n in names]
    for herd in (8, 5, 3, 2):
        _storm(eng, [POINT.format(10 * herd + i) for i in range(herd)])
    d = _delta(names, before)
    assert d["batch/batches"] >= 4
    assert (d["batch/trace_errors"], d["batch/fallbacks"],
            d["batch/declined"], d["batch/ahead_compiles"]) == (0, 0, 0, 0)
    assert GLOBAL.get("prog/registered") == r1


def test_members_that_agree_on_a_literal_meet_no_new_program(monkeypatch):
    """Which params ride the batch axis is the shape's: a herd whose
    members all ask `a = 3` runs the program the build-ahead made, and
    answers as the lane-off engine does."""
    sql = "select k, b from t where a = {} and k < {}"
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = _mk_engine()
    texts = [sql.format(3, 50 + i) for i in range(8)]
    want = [base.query(q) for q in texts]
    eng = _lane(monkeypatch, max_batch=8)
    eng.query(sql.format(1, 5))
    r1 = GLOBAL.get("prog/registered")
    b0 = GLOBAL.get("batch/batches")
    got = _storm(eng, texts)
    assert GLOBAL.get("batch/batches") > b0
    assert GLOBAL.get("prog/registered") == r1
    for i, w in enumerate(want):
        for c in w.columns:
            assert np.array_equal(got[i][c].to_numpy(), w[c].to_numpy())
    # and the same text sixteen times is still ONE slot, row 0 for all
    m0 = GLOBAL.get("batch/member_slots")
    _storm(eng, [texts[0]] * 8)
    assert GLOBAL.get("batch/member_slots") - m0 <= 2


# -- lane off: the accepted cells' programs ----------------------------------

# digests of `repr(fused cache key)` of Q1's and Q6's programs at sf 0.002
# as the parent commit 04e90d5 gives them (lane off, as every accepted cell)
PARENT_KEYS = ["0a1bede3b81e", "589d6e537b2a"]


@pytest.mark.parametrize("lane", ["off", "on"])
def test_the_scan_cells_keys_and_programs_are_the_parents(monkeypatch, lane):
    from tests.tpch_util import QUERIES
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    monkeypatch.delenv("YDB_TPU_BATCH_WINDOW", raising=False)
    if lane == "on":
        monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "30")
        monkeypatch.setenv("YDB_TPU_BATCH_MAX", "4")
    eng = QueryEngine()
    load_tpch(eng.catalog, sf=0.002)
    assert (eng._batch_lane is not None) == (lane == "on")
    names = []
    for q in ("q1", "q6"):
        eng.query(QUERIES[q])
        assert eng.executor.last_path == "fused"
        names += [p["name"] for p in eng.last_stats.programs["programs"]]
    assert names == ["jit_lineitem_gs_1f6bff", "jit_lineitem_g_410372"]
    keys = [k for k in eng.executor._fused_cache._entries
            if k[0] != "batched"]
    assert sorted(hashlib.sha1(repr(k).encode()).hexdigest()[:12]
                  for k in keys) == PARENT_KEYS
    stacked = [k for k in eng.executor._fused_cache._entries
               if k[0] == "batched"]
    assert len(stacked) == (4 if lane == "on" else 0)   # Bb 2, 4 a shape
    if lane == "off":
        assert not eng.last_stats.batching
    # tests/test_batch_lane.py reads the HLO of every stacked
    # `jit_lineitem_*` program the process inventories: leave none whose
    # engine is gone
    from ydb_tpu.utils import progstats
    progstats.reset_for_tests()
