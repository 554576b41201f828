"""Late materialization (`query/latemat.py`, YDB_TPU_LATE_MAT): the
differential contract and the device-compaction escape hatches.

The lever moves row-ids, not bytes — deferred join payloads thread
(row-id, match) pairs through the byte-heavy middle of a fused plan and
materialize ONCE at the bound-sized tail; selective pipelines compact
from scan capacity down to a ladder-quantized bound (`ir.Compact`).
None of that may change a single output byte:

  * on/off byte-equal across string payloads (dictionary remap at the
    tail), nullable payloads (validity planes ride the row-id gather),
    duplicate-heavy joins (the portioned path strips deferral), LIMIT
    tails, and 0-row pipelines;
  * a forged-low compact bound trips the LOUD full-capacity rerun
    (`latemat/compact_overflow_reruns`) — never a silent truncation;
  * lever flips replan + recompile (the lever rides the plan-cache
    fingerprint and every program cache key) instead of reusing
    shape-mismatched artifacts, and repeated runs mint no new programs
    (the sticky compact capacity pins cache churn);
  * a deferred SCAN column first referenced while the row positions are
    still the iota is read in place (`latemat/direct_cols`: TPC-H Q1's
    six); once a compact, compress, sort or limit has moved rows it is
    gathered at the small shape (`latemat/gathered_cols`);
  * the one Compact sits directly after the last reducing join (PR 31:
    TPC-H Q9 probes `partsupp` and `orders` at the bound), and where no
    join reduces, or the reducing join is the last step, the program is
    the one the end-placed Compact gave, to the byte;
  * a Compact is planned only where what follows it is priced per row
    (PR 34): a keyless aggregate over a filtered scan (TPC-H Q6) keeps
    its mask and sums in place, with no sort and no gather
    (`latemat/compact_skipped_plans`, `compact_skipped=keyless-tail`).

All aggregated columns hold integer-valued doubles, so sums are exact
in float64 regardless of reduction order — capacity changes between the
two lever states cannot excuse an LSB drift.
"""

import re

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.bench.tpch_gen import load_tpch
from ydb_tpu.query import QueryEngine
from ydb_tpu.utils import progstats
from ydb_tpu.utils.metrics import GLOBAL

from tests.tpch_util import QUERIES, assert_frames_match, oracle


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 13)
    rng = np.random.default_rng(11)
    e.execute("create table li (id Int64 not null, k Int64 not null, "
              "flag Int64 not null, qty Double not null, "
              "primary key (id)) with (store = column)")
    e.execute("create table pr (k Int64 not null, name Utf8, "
              "cat Int64 not null, w Double not null, nv Double, "
              "primary key (k)) with (store = column)")
    n, m = 6000, 400
    li = pd.DataFrame({
        "id": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, m, n),
        "flag": rng.integers(0, 10, n),
        # integer-valued doubles: exact under any summation order
        "qty": rng.integers(1, 1000, n).astype(np.float64),
    })
    nv = rng.integers(0, 500, m).astype(np.float64)
    nv[::7] = np.nan                     # nullable payload column
    pr = pd.DataFrame({
        "k": np.arange(m, dtype=np.int64),
        "name": np.array([f"name#{i % 37:02d}" for i in range(m)],
                         dtype=object),
        "cat": rng.integers(0, 9, m),    # duplicate-heavy join key
        "w": rng.integers(1, 100, m).astype(np.float64),
        "nv": nv,
    })
    ver = e._next_version()
    for name, df in (("li", li), ("pr", pr)):
        t = e.catalog.table(name)
        t.bulk_upsert(df, ver)
        t.indexate()
    e.frames = {"li": li, "pr": pr}
    return e


def _byte_equal(a, b):
    assert list(a.columns) == list(b.columns)
    assert len(a) == len(b)
    for col in a.columns:
        xa, xb = a[col].to_numpy(), b[col].to_numpy()
        na, nb = pd.isna(xa), pd.isna(xb)
        assert (na == nb).all(), col
        assert (xa[~na] == xb[~nb]).all(), col


def _explain(eng, sql: str) -> str:
    return "\n".join(eng.query("explain " + sql).iloc[:, 0].astype(str))


# -- the YDB_TPU_LATE_MAT lever: byte-equal differential --------------------


DIFF_QUERIES = [
    # string + numeric emit-only payloads deferred to the LIMIT tail
    "select li.id as id, name, w from li join pr on li.k = pr.k "
    "where flag = 3 order by id limit 50",
    # nullable payload: the validity plane must ride the row-id gather
    "select li.id as id, nv from li join pr on li.k = pr.k "
    "where flag < 2 order by id limit 100",
    # duplicate-heavy build key (fan-out beyond capacity exercises the
    # portioned path, which strips deferral — still byte-equal)
    "select flag, count(*) as c, sum(w) as sw from li "
    "join pr on li.flag = pr.cat group by flag order by flag",
    # LEFT JOIN payload: unmatched probes must stay NULL at the tail
    "select li.id as id, w from li left join pr "
    "on li.k = pr.k where flag = 7 order by id limit 30",
    # aggregation over a deferred-then-materialized payload
    "select name, count(*) as c, sum(qty) as s from li "
    "join pr on li.k = pr.k group by name order by name",
    # 0-row pipeline: nothing survives, tail gathers nothing
    "select li.id as id, name from li join pr on li.k = pr.k "
    "where qty < 0 order by id",
]


@pytest.mark.parametrize("qi", range(len(DIFF_QUERIES)))
def test_latemat_lever_byte_equal(eng, qi, monkeypatch):
    sql = DIFF_QUERIES[qi]
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    off = eng.query(sql)
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    on = eng.query(sql)
    _byte_equal(off, on)


# -- plan surface -----------------------------------------------------------


def test_explain_annotates_deferrals(eng, monkeypatch):
    sql = ("select li.id as id, name, w from li join pr on li.k = pr.k "
           "where flag = 3 order by id limit 50")
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    txt = _explain(eng, sql)
    assert "latemat:" in txt
    assert "(row-id)" in txt
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    assert "latemat:" not in _explain(eng, sql)


def test_deferred_cols_counted(eng, monkeypatch):
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    before = GLOBAL.get("latemat/deferred_cols")
    eng.query("select li.id as id, name, w from li join pr "
              "on li.k = pr.k where flag = 4 order by id limit 20")
    assert GLOBAL.get("latemat/deferred_cols") > before
    assert eng.executor.last_path == "fused"


# -- device compaction ------------------------------------------------------


def test_selective_filter_compacts(eng, monkeypatch):
    """An equality filter the CBO estimates at ~1/10 shrinks the
    pipeline from scan capacity to a ladder rung (counter-visible), and
    the compacted result matches the lever-off bytes."""
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    sql = ("select li.id as id, qty from li join pr on li.k = pr.k "
           "where flag = 5 order by id")
    off = eng.query(sql)
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    before = GLOBAL.get("latemat/compact_plans")
    on = eng.query(sql)
    assert GLOBAL.get("latemat/compact_plans") > before
    assert GLOBAL.get("latemat/compact_capacity_rows") > 0
    _byte_equal(off, on)


def test_forged_low_bound_reruns_loudly(eng, monkeypatch):
    """A compact capacity forged BELOW the live row count must trip the
    device overflow flag and rerun at full capacity — the result is
    complete, the rerun is counted, truncation is never served."""
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    sql = ("select li.k as k, count(*) as c, sum(qty) as s from li "
           "join pr on li.k = pr.k group by li.k order by k")
    off = eng.query(sql)                 # ~6000 live rows pre-group
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    monkeypatch.setattr(eng.executor, "_compact_sizing",
                        lambda _key, pipe, *a: (2048, len(pipe.steps)))
    before = GLOBAL.get("latemat/compact_overflow_reruns")
    on = eng.query(sql)
    assert GLOBAL.get("latemat/compact_overflow_reruns") == before + 1
    _byte_equal(off, on)
    # the measured-live memo taught the sizing: a rerun at honest
    # capacity leaves live counts >= the forged bound behind
    assert max(eng.executor._compact_memo.values(), default=0) > 2048


def test_zero_row_pipeline_compacts_to_floor(eng, monkeypatch):
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    before = GLOBAL.get("latemat/compact_overflow_reruns")
    got = eng.query("select li.id as id, name from li join pr "
                    "on li.k = pr.k where qty < 0 order by id")
    assert len(got) == 0
    assert GLOBAL.get("latemat/compact_overflow_reruns") == before


# -- program-cache churn ----------------------------------------------------


def test_repeat_runs_mint_no_new_programs(eng, monkeypatch):
    """The sticky compact capacity + ladder quantization pin cache
    churn: re-running a compacted statement reuses the compiled
    program, and a lever flip mints exactly one program per state."""
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    sql = ("select li.id as id, w from li join pr on li.k = pr.k "
           "where flag = 6 order by id limit 25")
    eng.query(sql)
    n0 = len(eng.executor._fused_cache)
    for _ in range(3):
        eng.query(sql)
    assert len(eng.executor._fused_cache) == n0
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    eng.query(sql)
    n_off = len(eng.executor._fused_cache)
    assert n_off >= n0          # the off-state program is its own entry
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    eng.query(sql)
    assert len(eng.executor._fused_cache) == n_off, \
        "lever flip back must reuse the on-state program"


# -- a deferred scan column is read in place while nothing has moved --------
# (CPU runs: the traced program and its counters, never a speed)


@pytest.fixture(scope="module")
def tpch():
    e = QueryEngine()
    e.tpch_data = load_tpch(e.catalog, sf=0.002)
    return e


def _latemat_reads(eng, sql: str):
    """One statement's (`latemat/direct_cols`, `latemat/gathered_cols`)
    deltas, and {program name: its gathers scoped `latemat[`}."""
    names = ("latemat/direct_cols", "latemat/gathered_cols")
    before = [GLOBAL.get(n) for n in names]
    got = eng.query(sql)
    assert eng.executor.last_path == "fused"
    delta = tuple(GLOBAL.get(n) - b for n, b in zip(names, before))
    gathers = {}
    for p in eng.last_stats.programs["programs"]:
        text = progstats.hlo_text(p["key"])
        assert text.startswith(f"HloModule {p['name']}")
        gathers[p["name"]] = re.findall(
            r' gather\(.*op_name="[^"]*/(latemat\[[^"]*)"', text)
    return got, delta, gathers


def _lever_off(eng, sql: str, monkeypatch):
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "0")
    off = eng.query(sql)
    monkeypatch.setenv("YDB_TPU_LATE_MAT", "1")
    return off


def test_q1_reads_its_deferred_columns_in_place(tpch, monkeypatch):
    """Q1's pre-program only narrows the mask: the group-by's six
    columns are first referenced while `__lmpos` is still the iota, so
    the program holds no `latemat[` gather (ten identity gathers of
    6.29 M indices at SF1)."""
    off = _lever_off(tpch, QUERIES["q1"], monkeypatch)
    assert "latemat: 6 deferred" in _explain(tpch, QUERIES["q1"])
    tpch.query(QUERIES["q1"])
    tpch.query(QUERIES["q1"])
    on, (direct, gathered), gathers = _latemat_reads(tpch, QUERIES["q1"])
    assert (direct, gathered) == (6, 0)
    (name, found), = gathers.items()
    assert re.fullmatch(r"jit_lineitem_gs_[0-9a-f]{6}", name)
    assert found == []
    _byte_equal(off, on)


def test_q6_sums_in_place_and_plans_no_compact(tpch, monkeypatch):
    """Q6's tail is a keyless masked sum: its estimate qualifies for a
    Compact (126 of 16 384 slots) and the tail declines it, so
    `l_extendedprice` is first read while `__lmpos` is still the iota:
    no `compact/sort` of every position, no gather (five at the bound at
    SF1), the program Q1's shape has."""
    off = _lever_off(tpch, QUERIES["q6"], monkeypatch)
    assert "latemat: 1 deferred" in _explain(tpch, QUERIES["q6"])
    tpch.query(QUERIES["q6"])
    names = ("latemat/compact_skipped_plans", "latemat/compact_plans")
    before = [GLOBAL.get(n) for n in names]
    on, (direct, gathered), gathers = _latemat_reads(tpch, QUERIES["q6"])
    assert [GLOBAL.get(n) - b for n, b in zip(names, before)] == [1, 0]
    assert (direct, gathered) == (1, 0)
    (name, found), = gathers.items()
    assert re.fullmatch(r"jit_lineitem_g_[0-9a-f]{6}", name)   # no `c`
    assert found == []
    text = progstats.hlo_text(_own_program(tpch)["key"])
    assert not re.search(r" (?:sort|gather|scatter)\(", text)
    assert _attempt(tpch) == {"compact_skipped": "keyless-tail"}
    analyzed = "\n".join(tpch.query("explain analyze "
                                    + QUERIES["q6"])["plan"])
    assert re.search(r"fused-attempt: .* compact_skipped=keyless-tail$",
                     analyzed, flags=re.M), analyzed
    # the same rows, added in the scan's order and not the bound's
    assert list(off.columns) == list(on.columns)
    np.testing.assert_allclose(on.to_numpy(), off.to_numpy(), rtol=1e-12)


# Q6's predicates over a tail that does gather: a sorted group-by
_Q6_BY_ORDER = QUERIES["q6"].replace(
    "select sum(", "select l_orderkey, sum(") \
    + " group by l_orderkey order by l_orderkey"

MOVED_ROWS = {
    # (statement, deferred scan columns of its own program, byte-equal)
    # a Compact before the first reference (no join; Q6 itself stood here
    # until its keyless tail declined the Compact, PR 34); a group's sum
    # over the compacted rows may add in another order than over the
    # scan's, so no bytes to compare
    "compact": (_Q6_BY_ORDER, 2, False),
    # a join, then the Compact
    "join-compact": (QUERIES["q3"], 2, True),
    # no filter, no Compact: the tail's compress and LIMIT slice
    "limit": ("select l_extendedprice from lineitem limit 5", 1, True),
}


@pytest.mark.parametrize("case", sorted(MOVED_ROWS))
def test_moved_rows_still_gather_at_the_small_shape(tpch, monkeypatch,
                                                    case):
    sql, ncols, exact = MOVED_ROWS[case]
    off = _lever_off(tpch, sql, monkeypatch)
    tpch.query(sql)
    tpch.query(sql)                      # builds and sizing settled
    on, (direct, gathered), gathers = _latemat_reads(tpch, sql)
    assert (direct, gathered) == (0, ncols)
    (name, found), = gathers.items()     # builds are cached by now
    assert name.startswith("jit_lineitem_")
    assert len(found) == ncols and all(f.endswith("/gather")
                                       for f in found)
    if exact:
        _byte_equal(off, on)
    else:
        assert list(off.columns) == list(on.columns)
        for col in off.columns:
            np.testing.assert_allclose(on[col].to_numpy(),
                                       off[col].to_numpy(), rtol=1e-12)


def test_q1_forged_low_compact_reruns_loudly(tpch, monkeypatch):
    """Q1 under a Compact forged below its live rows: the compacted
    program gathers the six columns at the bound, overflows, and the
    rerun at full capacity reads them in place; the answer is the same."""
    off = _lever_off(tpch, QUERIES["q1"], monkeypatch)
    monkeypatch.setattr(tpch.executor, "_compact_sizing",
                        lambda _key, pipe, *a: (2048, len(pipe.steps)))
    before = GLOBAL.get("latemat/compact_overflow_reruns")
    on, (direct, gathered), gathers = _latemat_reads(tpch, QUERIES["q1"])
    assert GLOBAL.get("latemat/compact_overflow_reruns") == before + 1
    assert (direct, gathered) == (6, 6)
    assert sorted(len(g) for g in gathers.values()) == [0, 6]
    _byte_equal(off, on)


# -- the Compact sits where the rows fall, not after the last join ----------
# (PR 31; CPU runs: positions, shapes, counters and answers, never a speed)


def _attempt(eng) -> dict:
    """Attributes of the statement's own `fused-attempt` span (a build
    side that runs fused opens its own, further down the tree)."""
    return next(s.attrs for s in eng.last_trace if s.name == "fused-attempt")


def _gather_sizes(text: str) -> dict:
    """{scope: {indices}} of an optimized HLO text's gathers: the first
    `jax.named_scope` under the module's name (`join2.probe`,
    `join0.payload[d1.w]`, `compact`), and how many indices its gathers
    take (the output's leading dimension)."""
    sizes: dict = {}
    for n, scope in re.findall(
            r' = \w+\[(\d+)[,\]][^\n]* gather\([^\n]*'
            r'op_name="jit\([a-z0-9_]+\)/([^"/]*)', text):
        sizes.setdefault(scope, set()).add(int(n))
    return sizes


def _own_program(eng, table: str = "lineitem") -> dict:
    own, = [p for p in eng.last_stats.programs["programs"]
            if p["name"].startswith(f"jit_{table}_")]
    return own


def _forge_lineitem(executor, monkeypatch, cap: int):
    """Forge the statement's own Compact to `cap`, where the sizing put
    it (at the end where the sizing refused one); a build side keeps
    what it was given."""
    real = executor._compact_sizing

    def forged(base_key, pipe, *a):
        got_cap, at = real(base_key, pipe, *a)
        if pipe.scan.table != "lineitem":
            return got_cap, at
        return cap, len(pipe.steps) if at is None else at

    monkeypatch.setattr(executor, "_compact_sizing", forged)


@pytest.mark.parametrize("case", ["position", "oracle", "overflow",
                                  "counter"])
def test_q9_compacts_after_the_part_semi_join(tpch, monkeypatch, case):
    """Q9's steps: supplier (inner, unfiltered), part (semi, `p_name
    like`: the one reducing join), partsupp by the hashed composite key
    (the binary search), the hash's verification, orders. The Compact
    sits after the second of the five."""
    sql = QUERIES["q9"]
    tpch.query(sql)
    tpch.query(sql)                      # builds cached, sizing settled
    names = ("latemat/compact_early_plans", "latemat/compact_plans",
             "latemat/compact_overflow_reruns")
    before = [GLOBAL.get(n) for n in names]
    if case == "overflow":
        _forge_lineitem(tpch.executor, monkeypatch, 128)
    got = tpch.query(sql)
    assert tpch.executor.last_path == "fused"
    early, plans, reruns = (GLOBAL.get(n) - b
                            for n, b in zip(names, before))
    at, cap = _attempt(tpch)["compact_at"], _attempt(tpch)["compact_cap"]
    want = oracle("q9", tpch.tpch_data)
    want.columns = list(got.columns)
    if case == "position":
        sizes = _gather_sizes(progstats.hlo_text(_own_program(tpch)["key"]))
        plan = _explain(tpch, sql)
        joins = re.findall(r"^  (\w+) JOIN probe=(\S+)", plan, flags=re.M)
        assert [k for k, _p in joins] == ["INNER", "LEFT_SEMI", "INNER",
                                          "INNER"], plan
        assert joins[1][1] == "lineitem.l_partkey"
        assert at == 2                   # of 5 steps: 4 joins, 1 program
        # the probes before the Compact run at scan capacity, the
        # composite key's twenty search steps and the orders probe at
        # the bound, and so does every payload gather
        cap0, = sizes["join0.probe"]
        assert cap < cap0 // 2
        assert sizes["join1.probe"] == {cap0}
        assert sizes["join2.probe"] == sizes["join3.probe"] == {cap}
        assert all(ns == {cap} for sc, ns in sizes.items()
                   if ".payload[" in sc or sc.startswith("latemat[")), sizes
        analyzed = "\n".join(tpch.query("explain analyze " + sql)["plan"])
        assert re.search(rf"fused-attempt: .* compact_cap={cap} "
                         r"compact_at=2$", analyzed, flags=re.M), analyzed
    elif case == "oracle":
        assert reruns == 0
        assert_frames_match(got, want, ordered=True)
    elif case == "overflow":
        # overflows where it sits, after the semi join, and the rerun at
        # full capacity carries no Compact
        assert (at, cap) == (2, 128)
        assert (early, plans, reruns) == (1, 1, 1)
        assert_frames_match(got, want, ordered=True)
    else:
        assert (early, plans, reruns) == (1, 1, 0)
        for other in ("q3", "q18"):
            tpch.query(QUERIES[other])
            tpch.query(QUERIES[other])
            b = GLOBAL.get("latemat/compact_early_plans")
            tpch.query(QUERIES[other])
            assert GLOBAL.get("latemat/compact_early_plans") == b, other


class _BuildSpy:
    """Records what the executor hands `ops/fused.build_fused_fn` and
    `fused_cache_key` for a statement's own program, and the arguments
    its fill would capture the executable with."""

    def __init__(self, executor, monkeypatch):
        from ydb_tpu.ops import fused as F
        self.real_build, self.real_key = F.build_fused_fn, F.fused_cache_key
        self.builds, self.keys, self.args = [], [], []
        real_fill = executor._fused_fill

        def build(pipe, *a, **kw):
            if pipe.scan.table == "lineitem":
                self.builds.append(((pipe,) + a, kw))
            return self.real_build(pipe, *a, **kw)

        def key(plan, *a, **kw):
            if plan.pipeline.scan.table == "lineitem" \
                    and kw.get("compact_cap"):
                self.keys.append(((plan,) + a, kw))
            return self.real_key(plan, *a, **kw)

        def fill(kind, key_, builder, capture_args, **kw):
            self.args.append(capture_args)
            return real_fill(kind, key_, builder, capture_args, **kw)

        monkeypatch.setattr(F, "build_fused_fn", build)
        monkeypatch.setattr(F, "fused_cache_key", key)
        monkeypatch.setattr(executor, "_fused_fill", fill)

    def lowered(self, call: tuple, **override) -> str:
        a, kw = call
        fn, _box = self.real_build(*a, **{**kw, **override})
        # the statement's own fill is the last one (its builds' come first)
        return fn.lower(*self.args[-1]).as_text()


@pytest.mark.parametrize("q,early", [("q1", False), ("q6", False),
                                     ("q3", False), ("q18", False),
                                     ("q9", True)])
def test_end_position_is_the_program_it_was(tpch, monkeypatch, q, early):
    """Where no join reduces (Q1, Q6) or the reducing join is the last
    step (Q3, Q18) the position is the end, and cache key, module name
    and lowered text are what `compact_at=None` (the only placement
    before PR 31) gives: the scan cell and two thirds of the join cell
    compile nothing new. Q9 is the control: there they differ."""
    sql = QUERIES[q]
    tpch.query(sql)
    tpch.query(sql)                      # builds cached, sizing settled
    if q in ("q1", "q6"):
        # Q1 and Q6 compact on a forged bound only: their tails (a
        # 6-bucket one-hot, a keyless sum) decline the Compact
        _forge_lineitem(tpch.executor, monkeypatch, 2048)
    tpch.executor._fused_cache.clear()
    spy = _BuildSpy(tpch.executor, monkeypatch)
    tpch.query(sql)
    call = next(c for c in spy.builds if c[1].get("compact_prog"))
    (pipe, *_rest), kw = call
    assert (kw["compact_at"] < len(pipe.steps)) == early
    here, at_end = spy.lowered(call), spy.lowered(call, compact_at=None)
    name, = set(re.findall(r"module @(jit_\w+)", here))
    assert re.fullmatch(r"jit_lineitem_\w*c_[0-9a-f]{6}", name)
    assert f"module @{name} " in at_end
    ka, kkw = spy.keys[-1]
    key_here = spy.real_key(*ka, **kkw)
    key_end = spy.real_key(*ka, **{**kkw, "compact_at": None})
    assert kkw["compact_at"] == kw["compact_at"]
    if early:
        assert here != at_end and key_here != key_end
        assert key_here[:-2] == key_end[:-2]
        assert key_here[-2] == key_end[-2] + (kw["compact_at"],)
    else:
        assert here == at_end and key_here == key_end


@pytest.fixture(scope="module")
def star():
    """A fact table whose FIRST join reduces (`d1` filtered to a ninth),
    followed by kinds the Compact never preceded: an inner join with a
    deferred payload (`d3`) and a LEFT join probed by a nullable key with
    keys past its build (`d2`)."""
    e = QueryEngine(block_rows=1 << 13)
    rng = np.random.default_rng(31)
    e.execute("create table fact (id Int64 not null, a Int64 not null, "
              "b Int64, c Int64 not null, qty Double not null, "
              "primary key (id)) with (store = column)")
    e.execute("create table d1 (k Int64 not null, cat Int64 not null, "
              "w Double not null, primary key (k)) with (store = column)")
    e.execute("create table d2 (k Int64 not null, name Utf8, nv Double, "
              "primary key (k)) with (store = column)")
    e.execute("create table d3 (k Int64 not null, z Double not null, "
              "primary key (k)) with (store = column)")
    n, m = 6000, 400
    b = pd.array(rng.integers(0, 500, n), dtype="Int64")   # 400..499: no row
    b[::5] = pd.NA
    nv = rng.integers(0, 500, m).astype(np.float64)
    nv[::7] = np.nan
    frames = {
        "fact": pd.DataFrame({
            "id": np.arange(n, dtype=np.int64),
            "a": rng.integers(0, m, n), "b": b,
            "c": rng.integers(0, 50, n),
            "qty": rng.integers(1, 1000, n).astype(np.float64)}),
        "d1": pd.DataFrame({
            "k": np.arange(m, dtype=np.int64),
            "cat": rng.integers(0, 9, m),
            "w": rng.integers(1, 100, m).astype(np.float64)}),
        "d2": pd.DataFrame({
            "k": np.arange(m, dtype=np.int64),
            "name": np.array([f"name#{i % 37:02d}" for i in range(m)],
                             dtype=object),
            "nv": nv}),
        "d3": pd.DataFrame({
            "k": np.arange(50, dtype=np.int64),
            "z": rng.integers(1, 9, 50).astype(np.float64)}),
    }
    ver = e._next_version()
    for name, df in frames.items():
        t = e.catalog.table(name)
        t.bulk_upsert(df, ver)
        t.indexate()
    e.frames = frames
    return e


_STAR_FROM = ("from fact join d1 on fact.a = d1.k "
              "left join d2 on fact.b = d2.k join d3 on fact.c = d3.k "
              "where d1.cat = 3 ")
STAR = {
    "rows": "select fact.id as id, w, name, nv, z " + _STAR_FROM
            + "order by id",
    "grouped": "select name, count(*) as c, sum(qty * w) as s, "
               "sum(z) as sz " + _STAR_FROM + "group by name order by name",
}


def _star_oracle(frames: dict, shape: str) -> pd.DataFrame:
    f, d1, d2, d3 = (frames[t] for t in ("fact", "d1", "d2", "d3"))
    j = f.merge(d1[d1["cat"] == 3], left_on="a", right_on="k") \
        .merge(d2, how="left", left_on="b", right_on="k",
               suffixes=("", "_d2")) \
        .merge(d3, left_on="c", right_on="k", suffixes=("", "_d3"))
    if shape == "rows":
        return j.sort_values("id")[["id", "w", "name", "nv", "z"]]
    j = j.assign(s=j["qty"] * j["w"])
    g = j.groupby("name", dropna=False).agg(
        c=("id", "size"), s=("s", "sum"), sz=("z", "sum")).reset_index()
    # NULL names sort first, as the engine's ascending order puts them
    return g.sort_values("name", na_position="first")


def _assert_star(got: pd.DataFrame, want: pd.DataFrame):
    assert list(got.columns) == list(want.columns) and len(got) == len(want)
    for col in got.columns:
        g, w = got[col].to_numpy(), want[col].to_numpy()
        assert (pd.isna(g) == pd.isna(w)).all(), col
        keep = ~pd.isna(g)
        assert (g[keep] == w[keep]).all(), col   # integer-valued doubles


@pytest.mark.parametrize("shape", sorted(STAR))
def test_compact_before_deferred_left_and_null_keyed_joins(star, monkeypatch,
                                                           shape):
    sql = STAR[shape]
    want = _star_oracle(star.frames, shape)
    star.query(sql)                      # builds cached
    before = GLOBAL.get("latemat/compact_early_plans")
    got = star.query(sql)
    assert star.executor.last_path == "fused"
    assert GLOBAL.get("latemat/compact_early_plans") == before + 1
    at, cap = _attempt(star)["compact_at"], _attempt(star)["compact_cap"]
    sizes = _gather_sizes(progstats.hlo_text(
        _own_program(star, "fact")["key"]))
    plan = _explain(star, sql)
    assert re.findall(r"^  (\w+) JOIN probe=(\S+)", plan, flags=re.M) == [
        ("INNER", "fact.a"), ("INNER", "fact.c"), ("LEFT", "fact.b")], plan
    assert at == 1                       # after d1, before d3 and d2
    _assert_star(got, want)
    # `__lmr0` / `__lmf0` rode the Compact: d1's payload is gathered by
    # the compacted row ids, at the bound, and so is every later join's
    assert sizes["join0.probe"] == {1 << 13}
    assert sizes["join1.probe"] == sizes["join2.probe"] == {cap}
    assert sizes["join0.payload[d1.w]"] == {cap}
    assert all(ns == {cap} for sc, ns in sizes.items() if ".payload[" in sc)
    # and the statement without a Compact gives the same rows
    real = star.executor._try_execute_fused
    monkeypatch.setattr(
        star.executor, "_try_execute_fused",
        lambda *a, **k: real(*a, **{**k, "_no_compact": True}))
    plain = star.query(sql)
    assert GLOBAL.get("latemat/compact_early_plans") == before + 1
    _assert_star(plain, want)
    _byte_equal(plain, got)


# -- a Compact is planned only where its tail gathers (PR 34) ---------------
# (CPU runs: the decision, the plan's span, keys and counters, never a speed)


def _tail_case(steps=(), partial=None, metas=(), at=None):
    from types import SimpleNamespace as NS
    steps = [("join", NS()) if st == "join" else ("program", st)
             for st in steps]
    return (NS(steps=steps, partial=partial), list(metas),
            len(steps) if at is None else at)


def _tail_cases() -> dict:
    from ydb_tpu.core.dtypes import DType, Kind
    from ydb_tpu.ops import ir, xla_exec as X
    f64 = DType(Kind.FLOAT64, False)

    def over(col, keys=(), **kw):
        return ir.Program([
            ir.Assign("x", ir.call("mul", ir.Col("a"), ir.Col(col))),
            ir.GroupBy(tuple(keys), (ir.Agg("s", "sum", "x"),), **kw)])

    keyless = over("b")
    late = {"late": True, "payload_names": ("p.w",)}
    semi = {"late": False, "payload_names": ()}
    reads_w = ir.Program([ir.Filter(ir.call(
        "lt", ir.Col("p.w"), ir.Const(3.0, f64)))])
    assert X._INPLACE_BUCKETS == 64      # priced: PERF.md round 34
    return {
        # id: ((steps, partial, metas, at), the reason or None)
        "keyless-no-join": (_tail_case(partial=keyless), "keyless-tail"),
        "keyless-after-semi-join": (_tail_case(
            ["join", ir.Program([ir.Filter(ir.Col("f"))])], keyless,
            [semi], at=1), "keyless-tail"),
        "keyless-over-late-payload": (_tail_case(
            ["join"], over("p.w"), [late]), None),
        "late-payload-unread-by-tail": (_tail_case(
            ["join"], keyless, [late]), "keyless-tail"),
        "late-payload-read-before-position": (_tail_case(
            ["join", reads_w], keyless, [late]), "keyless-tail"),
        "late-payload-read-after-position": (_tail_case(
            ["join", reads_w], keyless, [late], at=1), None),
        "join-after-position": (_tail_case(
            ["join", "join"], keyless, [semi, semi], at=1), None),
        "sorted-groupby": (_tail_case(partial=over("b", ["k"])), None),
        "medium-domain-groupby": (_tail_case(
            partial=over("b", ["k"], key_domains=(4000,))), None),
        "small-domain-12-buckets": (_tail_case(
            partial=over("b", ["k"], key_domains=(11,))),
            "small-domain-tail"),
        "small-domain-64-buckets": (_tail_case(
            partial=over("b", ["k"], key_domains=(7, 7))),
            "small-domain-tail"),
        "small-domain-65-buckets": (_tail_case(
            partial=over("b", ["k"], key_domains=(64,))), None),
        # no NULL slot for a key that cannot be NULL: the product shrinks,
        # and with it the route (`xla_exec.bucket_layout`)
        "small-domain-49-buckets-non-null": (_tail_case(
            partial=over("b", ["k", "j"], key_domains=(7, 7),
                         key_nullable=(False, False))),
            "small-domain-tail"),
        "small-domain-72-buckets-nullable": (_tail_case(
            partial=over("b", ["k", "j"], key_domains=(7, 8))), None),
        "small-domain-64-buckets-one-non-null": (_tail_case(
            partial=over("b", ["k", "j"], key_domains=(7, 8),
                         key_nullable=(True, False))),
            "small-domain-tail"),
        "small-domain-non-null-from-medium": (_tail_case(
            partial=over("b", ["k", "j"], key_domains=(16, 32),
                         key_nullable=(False, False))), None),
        "groupby-in-a-step": (_tail_case(
            [over("b", ["k"])], keyless, at=0), None),
        "rows-out": (_tail_case(), None),
    }


@pytest.mark.parametrize("case", sorted(_tail_cases()))
def test_tail_decision_table(case):
    """`latemat.tail_reads_in_place`: the one input `_compact_sizing` has
    beside the row estimate. One function says how a group-by lowers
    (`xla_exec.groupby_route`), for the trace and for this."""
    from ydb_tpu.ops import ir, xla_exec as X
    from ydb_tpu.query import latemat
    (pipe, metas, at), want = _tail_cases()[case]
    assert latemat.tail_reads_in_place(pipe, metas, at) == want
    for cmd in (c for c in (pipe.partial.commands if pipe.partial else ())
                if isinstance(c, ir.GroupBy)):
        route, nb = X.groupby_route(cmd)
        assert (X.reads_in_place(cmd) is not None) == (
            route == "keyless"
            or (route == "small-domain" and nb <= X._INPLACE_BUCKETS))


@pytest.mark.parametrize("domains,nullable,non_null", [
    ((7, 8), ("small-domain", 72), ("small-domain", 56)),
    ((16, 32), ("medium-domain", 561), ("small-domain", 512)),
    ((255, 256), ("sorted", 0), ("medium-domain", 65280)),
], ids=["in-place", "medium-to-small", "sorted-to-medium"])
def test_null_slots_move_the_route(domains, nullable, non_null):
    """Keys that cannot be NULL take no NULL slot, and the smaller bucket
    product may move the group-by to a cheaper lowering by the route's own
    bounds (`_INPLACE_BUCKETS`, `_SMALL_DOMAIN_BUCKETS`,
    `_SCATTER_MAX_BUCKETS`)."""
    from ydb_tpu.ops import ir, xla_exec as X
    aggs = (ir.Agg("s", "sum", "x"),)
    for key_nullable, want in (((), nullable), ((False, False), non_null)):
        cmd = ir.GroupBy(("k", "j"), aggs, key_domains=domains,
                         key_nullable=key_nullable)
        assert X.groupby_route(cmd) == want
    assert (X.reads_in_place(cmd) is not None) == (domains == (7, 8))


@pytest.fixture(scope="module")
def tpch_fresh():
    """An engine no other test has forged, sized or warmed."""
    e = QueryEngine()
    e.tpch_data = load_tpch(e.catalog, sf=0.002)
    return e


_Q6_ROWS = QUERIES["q6"].replace(
    "select sum(l_extendedprice*l_discount) as revenue",
    "select l_orderkey, l_extendedprice") \
    + " order by l_orderkey, l_extendedprice"

PLANNED = {
    # id: (statement, compact_skipped or None, digest of the statement's
    # own `fused_cache_key` as the parent commit 7665ebc gives it, with
    # `ir.GroupBy.key_nullable` in each program's fingerprint)
    "q6": (QUERIES["q6"], "keyless-tail", None),
    "q1-twelve-buckets": (QUERIES["q1"], "small-domain-tail", None),
    "q14-keyless-over-late-payload": (QUERIES["q14"], None, None),
    "sorted-groupby": (_Q6_BY_ORDER, None, None),
    "rows-out-order-by": (_Q6_ROWS, None, None),
    "q3": (QUERIES["q3"], None, "cd24aefe4ef3"),
    "q9": (QUERIES["q9"], None, "cd2e623d2b93"),
    "q18": (QUERIES["q18"], None, "1b758b1b7498"),
}


@pytest.mark.parametrize("case", sorted(PLANNED))
def test_compact_is_planned_by_the_tail(tpch_fresh, monkeypatch, case):
    """Real plans through `_compact_sizing`: every statement's estimate
    qualifies (under half the scan capacity); only the keyless tail with
    no deferred join payload, and Q1's 6-bucket one-hot, decline. Q3 /
    Q9 / Q18 (the join cell) keep
    their cache keys to the byte: the rule changed no plan there."""
    import hashlib
    eng = tpch_fresh
    sql, skipped, digest = PLANNED[case]
    eng.query(sql)
    eng.query(sql)                       # builds cached, sizing settled
    spy = _BuildSpy(eng.executor, monkeypatch)
    names = ("latemat/compact_skipped_plans", "latemat/compact_plans")
    before = [GLOBAL.get(n) for n in names]
    eng.query(sql)
    assert eng.executor.last_path == "fused"
    delta = [GLOBAL.get(n) - b for n, b in zip(names, before)]
    attrs, own = _attempt(eng), _own_program(eng)
    if skipped:
        assert delta == [1, 0]
        assert attrs == {"compact_skipped": skipped}
        assert re.fullmatch(r"jit_lineitem_(?:j\d+_)?[gsl]+_[0-9a-f]{6}",
                            own["name"])   # no `c` among the marks
    else:
        assert delta == [0, 1]
        assert set(attrs) == {"compact_cap", "compact_at"}
        assert re.fullmatch(r"jit_lineitem_(?:j\d+_)?[gsl]*c_[0-9a-f]{6}",
                            own["name"])
    if digest:
        ka, kkw = spy.keys[-1]           # the key with the Compact in it
        key = spy.real_key(*ka, **kkw)
        assert hashlib.sha1(repr(key).encode()).hexdigest()[:12] == digest


@pytest.mark.parametrize("q", ["q6", "q3"])
def test_warm_registers_the_key_the_dispatch_hits(monkeypatch, q):
    """The compile-ahead thunk and the dispatch read one decision: the
    warm builds Q6's program without a Compact (Q3's with one) and the
    statement then finds it; no second `lineitem` program is built."""
    eng = QueryEngine()
    load_tpch(eng.catalog, sf=0.002)
    ex = eng.executor
    real_fill, fills, live = ex._fused_fill, [], []

    def fill(kind, key, builder, capture_args, **kw):
        if any(c[0].startswith("lineitem.") for c in key[1]):
            fills.append((key, kw.get("source", "fresh")))
        return real_fill(kind, key, builder, capture_args, **kw)

    def warm_now(plan, params, snapshot):
        if plan.pipeline.scan.table == "lineitem":
            assert ex._fused_warm(plan, dict(params), snapshot)
            live.append(len(ex._fused_cache))
        return False

    monkeypatch.setattr(ex, "_fused_fill", fill)
    monkeypatch.setattr(ex, "compile_ahead", warm_now)
    hits = GLOBAL.get("prog/compile_ahead_hits")
    eng.query(QUERIES[q])
    assert eng.executor.last_path == "fused"
    assert GLOBAL.get("prog/compile_ahead_hits") == hits + 1
    # one fill, the warm's; the dispatch found its key live
    (key, source), = fills
    assert source == "compile_ahead" and key in ex._fused_cache
    assert live == [len(ex._fused_cache)]
    name = _own_program(eng)["name"]
    assert ("c_" in name) == (q == "q3"), name
    assert (key[-2] == ("compact", 0)) == (q == "q6")


@pytest.mark.parametrize("counters,want", [
    ({"prog/executions": 180.0}, None),            # a program without them
    ({"latemat/direct_cols": 9900.0, "latemat/gathered_cols": 1651.0},
     100.0 * 9900 / 11551),                        # Q1 6/0 beside Q6 0/1
    ({"latemat/gathered_cols": 54.0}, 0.0),        # every build cached
])
def test_latemat_direct_pct_reader(counters, want):
    """The benchmark's reader of the two counters: a share of what was
    counted, `None` (left out of the line) where nothing was."""
    import importlib.util
    from pathlib import Path
    path = (Path(__file__).resolve().parent.parent / "benchmark" / "metrics"
            / "latemat_direct_pct.py")
    spec = importlib.util.spec_from_file_location("latemat_direct_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.read({"window_counters": counters})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
