"""Parameter lifting + multi-query batched dispatch lane (PR-6 tentpole).

Differential discipline: every lane behavior is pinned against the
`YDB_TPU_BATCH_WINDOW=0` per-query path (byte-equal results), and the
lift is pinned against pandas across literal kinds (ints, floats,
dictionary-coded strings, dates, IN lists, LIMIT/OFFSET).
"""

import os
import threading

import numpy as np
import pytest


def _mk_engine(rows: int = 500, **env):
    for k, v in env.items():
        os.environ[k] = str(v)
    from ydb_tpu.query import QueryEngine
    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table t (k Int64 not null, a Int64, b Double, "
                "s Utf8, d Date, primary key (k))")
    eng.execute("insert into t (k, a, b, s, d) values "
                + ", ".join(
                    f"({i}, {i % 7}, {i * 0.5}, "
                    f"'tag{i % 5}', date '2024-01-{i % 28 + 1:02d}')"
                    for i in range(rows)))
    return eng


@pytest.fixture
def no_batch_env(monkeypatch):
    monkeypatch.delenv("YDB_TPU_BATCH_WINDOW", raising=False)


# -- lift correctness across literal kinds ---------------------------------


def test_lift_differential_literal_kinds(no_batch_env):
    """Lifted statements answer what pandas computes over the table
    `_mk_engine` loads — across int/float/string/date literals, IN
    lists, arithmetic folds, and LIMIT/OFFSET (the lifted-__lim2
    clamp)."""
    import pandas as pd
    rows = 500
    i = np.arange(rows)
    t = pd.DataFrame({
        "k": i, "a": i % 7, "b": i * 0.5,
        "s": [f"tag{x % 5}" for x in i],
        "d": pd.to_datetime([f"2024-01-{x % 28 + 1:02d}" for x in i])})
    cases = [
        ("select a, b from t where k = 17", t[t.k == 17][["a", "b"]]),
        ("select count(*) as c from t where b > 42.25",
         pd.DataFrame({"c": [(t.b > 42.25).sum()]})),
        ("select k from t where s = 'tag3' order by k limit 6",
         t[t.s == "tag3"].sort_values("k")[["k"]].head(6)),
        ("select count(*) as c from t where d >= date '2024-01-15'",
         pd.DataFrame({"c": [(t.d >= "2024-01-15").sum()]})),
        ("select k from t where a in (1, 3, 5) order by k limit 7 offset 2",
         t[t.a.isin([1, 3, 5])].sort_values("k")[["k"]].iloc[2:9]),
        ("select a, sum(b) as sb from t where k >= 2 + 3 group by a "
         "order by a",
         t[t.k >= 5].groupby("a").agg(sb=("b", "sum")).reset_index()),
        ("select k from t where s = 'zzz-absent'", t[t.k < 0][["k"]]),
    ]
    eng = _mk_engine(rows)
    for q, want in cases:
        got = eng.query(q)
        assert list(got.columns) == list(want.columns), q
        for c in got.columns:
            # b = i * 0.5 and its per-group sums are exact in a double
            assert np.array_equal(got[c].to_numpy(),
                                  want[c].to_numpy()), (q, c)


def test_lift_shares_program_across_literal_kinds(no_batch_env):
    """One executable per SHAPE, whatever the literal kind varies."""
    eng = _mk_engine()
    pairs = [
        ("select b from t where k = 3", "select b from t where k = 250"),
        ("select count(*) as c from t where b > 1.5",
         "select count(*) as c from t where b > 99.0"),
        ("select k from t where s = 'tag1' order by k limit 3",
         "select k from t where s = 'tag4' order by k limit 5"),
        ("select count(*) as c from t where d < date '2024-01-10'",
         "select count(*) as c from t where d < date '2024-01-20'"),
    ]
    for qa, qb in pairs:
        eng.query(qa)
        n = len(eng.executor._fused_cache)
        eng.query(qb)
        assert len(eng.executor._fused_cache) == n, (qa, qb)


def test_lift_keeps_pruning_and_plan_quality(no_batch_env):
    """The lift runs AFTER planning: scan pruning still carries the
    concrete literal (portion skipping is unchanged), only the compiled
    programs are value-free."""
    from ydb_tpu.sql import parse
    eng = _mk_engine()
    plan = eng.planner.plan_select(parse("select b from t where k = 42"))
    assert plan.lift_names, "point-lookup literal must lift"
    assert plan.lift_sig is not None
    assert plan.pipeline.scan.prune, "prune keeps the concrete literal"
    assert any(v == 42 for (_c, _op, v) in plan.pipeline.scan.prune)
    # and the lifted value rides in plan.params
    assert any(v == 42 for v in (plan.params[n] for n in plan.lift_names))


# -- batched dispatch lane --------------------------------------------------


def _storm(eng, texts, n_threads=None):
    results = {}
    errs = []
    barrier = threading.Barrier(len(texts))

    def one(i, sql):
        try:
            barrier.wait()
            results[i] = eng.query(sql)
        except Exception as e:             # noqa: BLE001
            errs.append((i, repr(e)))
    threads = [threading.Thread(target=one, args=(i, q))
               for i, q in enumerate(texts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:3]
    return results


def test_batch_byte_equal_with_lane_off(monkeypatch):
    """The A/B gate in miniature: the same literal-varying storm through
    a window=0 engine and a window>0 engine produces identical frames,
    and the lane engine actually coalesced."""
    texts = [f"select a, b from t where k = {i}" for i in range(12)]
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = _mk_engine()
    base.query(texts[0])
    want = {i: base.query(q) for i, q in enumerate(texts)}
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "12")
    eng = _mk_engine()
    eng.query(texts[0])                    # warm per-query path
    got = _storm(eng, texts)
    for i in range(len(texts)):
        for c in want[i].columns:
            assert np.array_equal(got[i][c].to_numpy(),
                                  want[i][c].to_numpy()), (i, c)
    c = eng.counters()
    assert c["batch/batches"] >= 1
    assert c["batch/coalesced_queries"] >= len(texts) - 2
    assert c["batch/max_size"] >= 2


def test_batch_single_admission_reservation(monkeypatch):
    """The admission double-charge fix: a coalesced batch takes ONE
    reservation (batch/reservations counts them) and releases it fully —
    not N nominal-slot reservations racing the pipeline window."""
    from ydb_tpu.query.admission import batch_reservation_bytes
    # ~N x the per-member estimate: the vmapped execution materializes
    # one cap-sized intermediate copy per member
    assert batch_reservation_bytes(10 << 20, 8) == 8 * (10 << 20)
    assert batch_reservation_bytes(100, 8) == 100 + 7 * (1 << 20)
    assert batch_reservation_bytes(10 << 20, 1) == 10 << 20

    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "8")
    eng = _mk_engine()
    eng.query("select a, b from t where k = 0")
    from ydb_tpu.utils.metrics import GLOBAL
    r0 = GLOBAL.get("batch/reservations")
    b0 = GLOBAL.get("batch/batches")
    s0 = GLOBAL.get("batch/singles")
    f0 = GLOBAL.get("batch/fallbacks")
    _storm(eng, [f"select a, b from t where k = {i}" for i in range(8)])
    c = eng.counters()
    batches = c["batch/batches"] - b0
    assert batches >= 1
    # the invariant under test: EXACTLY one reservation per sealed group
    # (a batched group of N members charges once, not N times)
    groups = (c["batch/batches"] - b0) + (c["batch/singles"] - s0) \
        + (c["batch/fallbacks"] - f0)
    assert c["batch/reservations"] - r0 == groups
    assert groups < 8, "8 members must not make 8 solo reservations"
    assert eng.admission.in_flight == 0
    assert eng.admission.active == 0


def test_batch_groups_respect_data_identity(monkeypatch):
    """Members must see IDENTICAL visible data to share an execution: a
    commit between two snapshots changes the src-id signature and the
    group key with it."""
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "50")
    eng = _mk_engine()
    from ydb_tpu.sql import parse
    plan = eng.planner.plan_select(parse("select b from t where k = 1"))
    lane = eng._batch_lane
    snap1 = eng.snapshot()
    k1 = lane._group_key(plan, snap1, 1 << 20)
    assert k1 is not None
    eng.execute("insert into t (k, a, b, s, d) values "
                "(9001, 1, 1.0, 'tag0', date '2024-02-01')")
    snap2 = eng.snapshot()
    k2 = lane._group_key(plan, snap2, 1 << 20)
    assert k2 is not None and k2 != k1
    # members whose BUILD literals differ must split groups too
    eng.execute("create table dim (a Int64 not null, w Int64, "
                "primary key (a))")
    eng.execute("insert into dim (a, w) values (1, 10), (2, 20), (3, 30)")
    pa = eng.planner.plan_select(parse(
        "select w from t join dim on t.a = dim.a where dim.w > 15 "
        "and k = 1"))
    pb = eng.planner.plan_select(parse(
        "select w from t join dim on t.a = dim.a where dim.w > 25 "
        "and k = 1"))
    assert pa.lift_sig == pb.lift_sig
    snap = eng.snapshot()
    ka = lane._group_key(pa, snap, 1 << 20)
    kb = lane._group_key(pb, snap, 1 << 20)
    assert ka is not None and kb is not None and ka != kb


def test_batch_dedup_identical_texts(monkeypatch):
    """A same-text storm (every member identical) runs ONE execution and
    every member reads slice 0 — no batch-wide duplicated compute."""
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "6")
    eng = _mk_engine()
    sql = "select a, sum(b) as sb from t group by a order by a"
    want = eng.query(sql)
    got = _storm(eng, [sql] * 6)
    for i in range(6):
        assert np.array_equal(got[i].sb.to_numpy(), want.sb.to_numpy())
    c = eng.counters()
    assert c["batch/batches"] >= 1


def test_batch_joined_shape_coalesces(monkeypatch):
    """A probe-side literal under a broadcast join batches (the build is
    batch-invariant and broadcasts); results match the lane-off path."""
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = _mk_engine()
    base.execute("create table dim (a Int64 not null, w Int64, "
                 "primary key (a))")
    base.execute("insert into dim (a, w) values "
                 + ", ".join(f"({i}, {i * 100})" for i in range(7)))
    texts = [f"select w from t join dim on t.a = dim.a where k = {i}"
             for i in range(8)]
    want = {i: base.query(q) for i, q in enumerate(texts)}
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "8")
    eng = _mk_engine()
    eng.execute("create table dim (a Int64 not null, w Int64, "
                "primary key (a))")
    eng.execute("insert into dim (a, w) values "
                + ", ".join(f"({i}, {i * 100})" for i in range(7)))
    eng.query(texts[0])
    got = _storm(eng, texts)
    for i in range(8):
        assert np.array_equal(got[i].w.to_numpy(),
                              want[i].w.to_numpy()), i
    assert eng.counters()["batch/coalesced_queries"] >= 2


def test_batch_build_param_divergence_splits_groups(monkeypatch):
    """Build fragments execute ONCE per batch with the leader's values —
    members whose build-side runtime params differ in ANY way (lifted
    consts AND pool-array params like string IN-list LUTs) must not
    share a group, and literal-shape drift the sig can't see (integer
    IN lists of different lengths) must decline, not mis-batch. Pinned
    as a concurrent differential against the lane-off path."""
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = _mk_engine()
    base.execute("create table dim (a Int64 not null, nm Utf8, w Int64, "
                 "primary key (a))")
    base.execute("insert into dim (a, nm, w) values "
                 + ", ".join(f"({i}, 'n{i}', {i * 100})"
                             for i in range(7)))
    texts = []
    for i in range(4):
        # build-side STRING IN list varies by member (pool LUT arrays)
        texts.append(
            f"select w from t join dim on t.a = dim.a "
            f"where dim.nm in ('n{i}', 'n{i + 1}') and k = {i + 1}")
    # probe-side integer IN lists of DIFFERENT lengths (shape drift)
    texts.append("select k from t where a in (1, 2) order by k limit 4")
    texts.append("select k from t where a in (1, 2, 3) order by k limit 4")
    want = {i: base.query(q) for i, q in enumerate(texts)}
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", str(len(texts)))
    eng = _mk_engine()
    eng.execute("create table dim (a Int64 not null, nm Utf8, w Int64, "
                "primary key (a))")
    eng.execute("insert into dim (a, nm, w) values "
                + ", ".join(f"({i}, 'n{i}', {i * 100})" for i in range(7)))
    for q in texts:
        eng.query(q)                       # warm + sequential differential
    got = _storm(eng, texts)
    for i in range(len(texts)):
        for c in want[i].columns:
            assert np.array_equal(got[i][c].to_numpy(),
                                  want[i][c].to_numpy()), (i, texts[i])


def test_batch_zero_literal_limit_variants(monkeypatch):
    """Members with NO lifted literals that differ only in LIMIT/OFFSET
    share a shape sig (same capacity bucket) — the batched execution
    must clamp per member via the always-lifted __lim2, never bake the
    leader's value (the review-caught coalescing bug: 'limit 5' silently
    got the leader's 3 rows)."""
    texts = ["select k from t order by k limit 3",
             "select k from t order by k limit 5",
             "select k from t order by k limit 4 offset 2",
             "select k from t order by k limit 3"]
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = _mk_engine(rows=64)
    want = {i: base.query(q) for i, q in enumerate(texts)}
    assert [len(w) for w in want.values()] == [3, 5, 4, 3]
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "4")
    eng = _mk_engine(rows=64)
    for q in texts:
        eng.query(q)
    got = _storm(eng, texts)
    for i in range(len(texts)):
        assert np.array_equal(got[i].k.to_numpy(),
                              want[i].k.to_numpy()), (i, texts[i])
    assert eng.counters()["batch/coalesced_queries"] >= 2


def test_batch_explain_analyze_block(monkeypatch):
    """EXPLAIN ANALYZE surfaces the per-statement batching block."""
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "30")
    eng = _mk_engine(rows=64)
    df = eng.query("explain analyze select a, b from t where k = 5")
    text = "\n".join(df["plan"])
    assert "batching: coalesced" in text


def test_batch_q1_reads_deferred_columns_in_place(monkeypatch):
    """Two same-shape TPC-H Q1 statements with different DELTA literals
    through `build_fused_batched_fn` answer as two single statements do,
    and the vmapped body reads Q1's deferred columns in place (nothing
    moves a row before the group-by references them): the batched
    program holds no `latemat[` gather."""
    import re

    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils import progstats

    from tests.tpch_util import QUERIES
    texts = [QUERIES["q1"], QUERIES["q1"].replace("'90'", "'75'")]

    def engine():
        e = QueryEngine()
        load_tpch(e.catalog, sf=0.002)
        return e

    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "0")
    base = engine()
    want = [base.query(q) for q in texts]
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "500")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", "2")
    eng = engine()
    eng.query(texts[0])                    # warm per-query path
    c0 = eng.counters()
    got = _storm(eng, texts)
    c1 = eng.counters()
    assert c1["batch/batches"] == c0.get("batch/batches", 0) + 1
    # the one batched dispatch counts its six in-place reads once
    assert c1["latemat/direct_cols"] - c0["latemat/direct_cols"] == 6
    assert c1.get("latemat/gathered_cols", 0) \
        == c0.get("latemat/gathered_cols", 0)
    for i, w in enumerate(want):
        assert list(got[i].columns) == list(w.columns)
        for c in w.columns:
            assert np.array_equal(got[i][c].to_numpy(), w[c].to_numpy()), \
                (i, c)
    assert not want[0].equals(want[1])     # the literals do differ
    batched = [r["program"] for r in progstats.inventory_rows()
               if r["kind"] == "batched"
               and r["name"].startswith("jit_lineitem_")]
    # the inventory is the process's: a row of an engine that is gone
    # (another test's, on this worker) has no live handle and reads ''
    live = [t for t in map(progstats.hlo_text, batched) if t]
    assert live
    for text in live:
        assert not re.findall(r' gather\(.*op_name="[^"]*latemat\[', text)
