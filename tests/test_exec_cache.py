"""Executable-cache lifecycle: the process-wide live-executable budget
(`ops/exec_cache.py`) and the cross-query build cache
(`query/build_cache.py`).

The r4 suite segfaulted the XLA client by accumulating compiled
executables and worked around it by clearing every cache between
queries; these tests pin the real fix — one LRU budget over all
compiled-program caches — and the soak proves a long-lived engine holds
a flat working set across many distinct query shapes.
"""

import numpy as np
import pytest

from ydb_tpu.ops.exec_cache import ExecCache, _Budget

_AGGS = ("sum(a)", "min(a)", "max(a)", "sum(b)", "min(b)", "max(b)",
         "count(a)")


def _distinct_shape(i: int, table: str, where: str) -> str:
    """Statement `i` (< 128) of a family whose members differ in
    STRUCTURE — bit j of `i` adds `_AGGS[j]` to the select list — so
    each is a distinct executable: parameter lifting collapses literal
    variants of one shape, never these."""
    extra = "".join(f", {agg} as x{j}" for j, agg in enumerate(_AGGS)
                    if i >> j & 1)
    return f"select count(*) as n{extra} from {table} where {where}"


def test_lru_within_one_cache():
    b = _Budget(3)
    c = ExecCache("t", b)
    c["a"], c["b"], c["c"] = 1, 2, 3
    assert c.get("a") == 1             # refresh a
    c["d"] = 4                         # evicts b (globally oldest)
    assert "b" not in c and "a" in c and "c" in c and "d" in c
    assert c.evictions == 1


def test_budget_spans_caches_globally_lru():
    b = _Budget(3)
    c1, c2 = ExecCache("one", b), ExecCache("two", b)
    c1["x"] = 1
    c2["y"] = 2
    c1["z"] = 3
    c2["w"] = 4                        # evicts c1["x"] — oldest anywhere
    assert "x" not in c1 and "y" in c2 and "z" in c1 and "w" in c2
    assert b.total() == 3


def test_get_refresh_protects_across_caches():
    b = _Budget(2)
    c1, c2 = ExecCache("one", b), ExecCache("two", b)
    c1["x"] = 1
    c2["y"] = 2
    assert c1.get("x") == 1            # x newer than y now
    c1["z"] = 3                        # evicts y, not x
    assert "x" in c1 and "y" not in c2


def test_engine_soak_live_executables_bounded():
    """Many distinct query shapes through ONE engine: the live-executable
    count stays under the global budget and results stay correct (the
    r4 segfault scenario, minus the segfault). The statements differ in
    structure (`_distinct_shape`) so they really are distinct
    executables — the storm-shares-one-program property has its own pin
    below."""
    from ydb_tpu.ops.exec_cache import GLOBAL_BUDGET, live_executables
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table s (k Int64 not null, a Int64, b Double, "
                "c Int64, primary key (k))")
    rows = ", ".join(f"({i}, {i % 7}, {i * 0.5}, {i % 3})"
                     for i in range(200))
    eng.execute(f"insert into s (k, a, b, c) values {rows}")

    old_max = GLOBAL_BUDGET.max_entries
    GLOBAL_BUDGET.max_entries = 24
    try:
        # every distinct select list is a distinct program fingerprint
        # → a distinct compiled executable per query shape
        for i in range(60):
            n = eng.query(_distinct_shape(
                i, "s", f"a = {i % 11} and k >= {i}")).n[0]
            expect = sum(1 for k in range(200)
                         if k % 7 == i % 11 and k >= i)
            assert n == expect, (i, n, expect)
            assert live_executables() <= 24
    finally:
        GLOBAL_BUDGET.max_entries = old_max


def test_eviction_releases_executables():
    """Evicted/overwritten/cleared entries must RELEASE their compiled
    executables (clear_cache), not just drop the reference — the
    lifecycle leak behind the r5 full-suite SIGSEGV."""
    class FakeExec:
        def __init__(self):
            self.cleared = 0

        def clear_cache(self):
            self.cleared += 1

    b = _Budget(2)
    c = ExecCache("t", b)
    e1, e2, e3, e4 = FakeExec(), FakeExec(), FakeExec(), FakeExec()
    c["a"], c["b"] = e1, e2
    c["c"] = e3                        # evicts e1
    assert e1.cleared == 1 and e2.cleared == 0
    c["c"] = e4                        # overwrite releases e3
    assert e3.cleared == 1
    assert c.released == 2
    c.clear()
    assert e2.cleared == 1 and e4.cleared == 1
    assert c.released == 4
    # composite entries (tuples, one level of object attrs) release too
    class Holder:
        def __init__(self, fn):
            self.fn = fn
    b2 = _Budget(1)
    c2 = ExecCache("t2", b2)
    inner1, inner2 = FakeExec(), FakeExec()
    c2["x"] = (inner1, Holder(inner2), "schema")
    c2["y"] = FakeExec()               # evicts the composite
    assert inner1.cleared == 1 and inner2.cleared == 1


def test_evicted_program_recompile_is_miss_not_hit():
    """The eviction-accounting companion of the PR-4 spurious-evict fix
    (overwrite-in-place must NOT evict — pinned above in
    test_eviction_releases_executables): a real LRU eviction must
    surface in the program inventory (`prog/evicted`, the entry
    persisting marked `evicted`), and re-running the evicted shape must
    count a ProgramCache MISS that re-records compile_ms — never a
    hit against a released executable."""
    from ydb_tpu.ops.exec_cache import GLOBAL_BUDGET
    from ydb_tpu.ops.xla_exec import _GLOBAL_CACHE
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils import progstats
    from ydb_tpu.utils.metrics import GLOBAL

    # the inventory is process-global; scope the state assertions below
    # to THIS test's programs, not leftovers from earlier suites
    progstats.reset_for_tests()
    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table ev (k Int64 not null, a Int64, b Double, "
                "primary key (k))")
    eng.execute("insert into ev (k, a, b) values "
                + ", ".join(f"({i}, {i % 5}, {i * 0.5})"
                            for i in range(120)))
    # portioned path → per-stage ProgramCache programs
    eng.executor.enable_fused = False
    old_max = GLOBAL_BUDGET.max_entries
    GLOBAL_BUDGET.max_entries = 4
    try:
        base = "select count(*) as n from ev where a = 0"
        assert int(eng.query(base).n[0]) == 24
        ev0 = GLOBAL.get("prog/evicted")
        # flood with structurally distinct shapes (→ distinct
        # programs) until the base query's programs are LRU victims
        for i in range(1, 9):
            eng.query(_distinct_shape(
                i, "ev", f"a = {i % 5} and k >= {i * 7}"))
        assert GLOBAL.get("prog/evicted") > ev0, \
            "LRU evictions must emit prog/evicted"
        evicted = [r for r in progstats.inventory_rows()
                   if r["kind"] == "program" and r["state"] == "evicted"]
        assert evicted, "evicted entries must persist in the inventory"
        h0, m0 = _GLOBAL_CACHE.hits, _GLOBAL_CACHE.misses
        assert int(eng.query(base).n[0]) == 24
        assert _GLOBAL_CACHE.misses > m0, \
            "re-running an evicted shape must MISS and recompile"
        # at least one program re-registered: compiles grew past 1 with
        # its eviction history kept
        recompiled = [r for r in progstats.inventory_rows()
                      if r["kind"] == "program" and r["compiles"] >= 2
                      and r["evictions"] >= 1]
        assert recompiled, "recompile must re-record in the inventory"
        assert all(r["state"] == "live" for r in recompiled)
    finally:
        GLOBAL_BUDGET.max_entries = old_max
        eng.executor.enable_fused = True


def test_literal_storm_compiles_one_program():
    """THE param-lifting regression pin (the PR-6 tentpole vs the Weak #3
    executable-accumulation class): a 64-query literal-varying
    point-lookup storm — every statement a distinct SQL text — compiles
    EXACTLY ONE fused program after warmup, the per-stage ProgramCache
    takes zero new misses, and the exec-cache footprint stays flat.
    Before lifting, every distinct literal was a distinct program
    fingerprint: 64 clients = 64 executables of cache pressure."""
    from ydb_tpu.ops.exec_cache import live_executables
    from ydb_tpu.ops.xla_exec import _GLOBAL_CACHE
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table pt (k Int64 not null, a Int64, b Double, "
                "primary key (k))")
    eng.execute("insert into pt (k, a, b) values "
                + ", ".join(f"({i}, {i % 7}, {i * 0.5})"
                            for i in range(200)))
    warm = eng.query("select a, b from pt where k = 0")
    assert warm.a[0] == 0
    fused0 = len(eng.executor._fused_cache)
    prog_misses0 = _GLOBAL_CACHE.misses
    live0 = live_executables()
    for i in range(1, 64):
        df = eng.query(f"select a, b from pt where k = {i}")
        assert df.a[0] == i % 7 and abs(df.b[0] - i * 0.5) < 1e-9, i
    assert len(eng.executor._fused_cache) == fused0, \
        "literal variants must share ONE compiled fused program"
    assert _GLOBAL_CACHE.misses == prog_misses0
    assert live_executables() == live0, "exec-cache size must stay flat"
    # the lifted-LIMIT bucket shares too: limit 3 and limit 5 both live
    # inside the 128-row bucket → one executable, distinct results
    df3 = eng.query("select k from pt where a = 1 order by k limit 3")
    n1 = len(eng.executor._fused_cache)
    df5 = eng.query("select k from pt where a = 1 order by k limit 5")
    assert len(eng.executor._fused_cache) == n1
    assert list(df3.k) == [1, 8, 15] and list(df5.k) == [1, 8, 15, 22, 29]


@pytest.mark.slow
def test_soak_compile_twice_the_lru_cap_releases():
    """Soak (marked slow): compile 2× the LRU cap of DISTINCT query
    shapes in ONE process — the live-executable count stays under the
    cap, evictions actually release (released counter tracks them), and
    results stay correct throughout. The full-suite-SIGSEGV scenario,
    run deliberately. The statements differ in structure
    (`_distinct_shape`): parameter lifting would collapse literal
    variants into one shape and starve the eviction path this soak
    exists to exercise."""
    from ydb_tpu.ops.exec_cache import GLOBAL_BUDGET, live_executables
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table soak (k Int64 not null, a Int64, b Double, "
                "primary key (k))")
    eng.execute("insert into soak (k, a, b) values "
                + ", ".join(f"({i}, {i % 13}, {i * 0.25})"
                            for i in range(300)))
    old_max = GLOBAL_BUDGET.max_entries
    cap = 40
    GLOBAL_BUDGET.max_entries = cap
    released_before = sum(
        c.released for ref in GLOBAL_BUDGET._caches
        if (c := ref()) is not None)
    try:
        for i in range(2 * cap):
            # distinct select lists → distinct program fingerprints →
            # distinct compiled executables
            got = eng.query(_distinct_shape(
                i, "soak", f"a = {i % 13} and k >= {i * 3}"))
            expect = [k for k in range(300)
                      if k % 13 == i % 13 and k >= i * 3]
            assert int(got.n[0]) == len(expect), i
            assert live_executables() <= cap, i
        released_after = sum(
            c.released for ref in GLOBAL_BUDGET._caches
            if (c := ref()) is not None)
        assert released_after > released_before
    finally:
        GLOBAL_BUDGET.max_entries = old_max


# a deployment may still export a lever this engine no longer reads: the
# value it gave the losing side (1 for the legacy lowering, 0 for the rest)
RETIRED = (("YDB_TPU_GROUPBY_LEGACY", "1"), ("YDB_TPU_GATHER_BATCH_CAP", "0"),
           ("YDB_TPU_BOUNDS", "0"), ("YDB_TPU_PARAM_LIFT", "0"),
           ("YDB_TPU_SHAPE_BUCKETS", "0"))


@pytest.mark.parametrize("name,value", RETIRED, ids=[n for n, _ in RETIRED])
def test_retired_lever_is_ignored(name, value, monkeypatch):
    """With a retired variable set, `groupby_tuning()`, the plan
    fingerprint and a join + group-by's answer are what they are with it
    unset, and nothing recompiles — not the statement, not a literal
    variant of it (one shape, one executable), over a fact table of 5
    sources (the ladder pads 5 to 6)."""
    import pandas as pd

    from ydb_tpu.ops.xla_exec import groupby_tuning
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils.metrics import GLOBAL

    monkeypatch.delenv(name, raising=False)
    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table f (id Int64 not null, k Int64 not null, "
                "val Double not null, primary key (id)) "
                "with (store = column)")
    eng.execute("create table d (k Int64 not null, grp Int64 not null, "
                "a Int64 not null, primary key (k)) with (store = column)")
    ids = np.arange(5 * 256, dtype=np.int64)
    f = pd.DataFrame({"id": ids, "k": ids % 50, "val": ids * 0.5})
    k = np.arange(50, dtype=np.int64)
    d = pd.DataFrame({"k": k, "grp": k % 9, "a": k * 2})
    for chunk in np.split(ids, 5):
        eng.catalog.table("f").bulk_upsert(f.iloc[chunk],
                                           eng._next_version())
    eng.catalog.table("d").bulk_upsert(d, eng._next_version())
    for t in ("f", "d"):
        eng.catalog.table(t).indexate()

    def sql(lit):
        return ("select f.k as k, grp, a, count(*) as c, sum(val) as s "
                f"from f join d on f.k = d.k where val > {lit} "
                "group by f.k, grp, a order by k")

    tuning = groupby_tuning()
    want = eng.query(sql(10.5))
    fp = eng._plan_cache[sql(10.5)][0]
    compiled = GLOBAL.get("prog/registered")

    monkeypatch.setenv(name, value)
    assert groupby_tuning() == tuning
    got = eng.query(sql(10.5))
    assert eng._plan_cache[sql(10.5)][0] == fp
    pd.testing.assert_frame_equal(got, want)
    eng.query(sql(99.25))
    assert GLOBAL.get("prog/registered") == compiled


def test_build_cache_hit_and_invalidation():
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table f (k Int64 not null, d Int64, v Double, "
                "primary key (k))")
    eng.execute("create table d (d Int64 not null, tag Utf8, "
                "primary key (d))")
    eng.execute("insert into d (d, tag) values (0, 'x'), (1, 'y')")
    eng.execute("insert into f (k, d, v) values "
                + ", ".join(f"({i}, {i % 2}, {i * 1.0})" for i in range(50)))
    sql = ("select tag, sum(v) as s from f join d on f.d = d.d "
           "group by tag order by tag")
    bc = eng.executor.build_cache
    df1 = eng.query(sql)
    m0, h0 = bc.misses, bc.hits
    df2 = eng.query(sql)
    assert bc.hits > h0, "second run must hit the build cache"
    assert list(df1.s) == list(df2.s)
    # a write to the BUILD table invalidates (src-id keying)
    eng.execute("insert into d (d, tag) values (2, 'z')")
    eng.execute("insert into f (k, d, v) values (100, 2, 10.0)")
    df3 = eng.query(sql)
    assert bc.misses > m0
    assert list(df3.tag) == ["x", "y", "z"]
    # pandas oracle for the final state
    import pandas as pd
    f = pd.DataFrame({"d": [i % 2 for i in range(50)] + [2],
                      "v": [i * 1.0 for i in range(50)] + [10.0]})
    dd = pd.DataFrame({"d": [0, 1, 2], "tag": ["x", "y", "z"]})
    want = (f.merge(dd, on="d").groupby("tag").v.sum()
            .reset_index().sort_values("tag"))
    assert np.allclose(df3.s.to_numpy(), want.v.to_numpy())


def test_build_cache_respects_probe_dictionary():
    """Two tables joining the same build over DIFFERENT probe
    dictionaries must not share the remapped entry."""
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    for t in ("p1", "p2"):
        eng.execute(f"create table {t} (k Int64 not null, s Utf8, "
                    f"primary key (k))")
    eng.execute("create table dim (s Utf8 not null, w Int64, "
                "primary key (s))")
    eng.execute("insert into dim (s, w) values ('a', 1), ('b', 2)")
    eng.execute("insert into p1 (k, s) values (1, 'a'), (2, 'b')")
    # p2's dictionary encodes in a different order
    eng.execute("insert into p2 (k, s) values (1, 'b'), (2, 'a')")
    q = "select sum(w) as t from {p} join dim on {p}.s = dim.s where k = 1"
    assert eng.query(q.format(p="p1")).t[0] == 1
    assert eng.query(q.format(p="p2")).t[0] == 2
