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
    gathered at the small shape (`latemat/gathered_cols`).

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

from tests.tpch_util import QUERIES


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
                        lambda *a, **k: 2048)
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
    load_tpch(e.catalog, sf=0.002)
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
    tpch.query(QUERIES["q1"])   # the first may overflow a Compact and rerun
    on, (direct, gathered), gathers = _latemat_reads(tpch, QUERIES["q1"])
    assert (direct, gathered) == (6, 0)
    (name, found), = gathers.items()
    assert re.fullmatch(r"jit_lineitem_gs_[0-9a-f]{6}", name)
    assert found == []
    _byte_equal(off, on)


MOVED_ROWS = {
    # (statement, deferred scan columns of its own program, byte-equal)
    # a Compact before the first reference; a sum over the compacted rows
    # adds in another order than over the scan's, so no bytes to compare
    "compact": (QUERIES["q6"], 1, False),
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
                        lambda *a, **k: 2048)
    before = GLOBAL.get("latemat/compact_overflow_reruns")
    on, (direct, gathered), gathers = _latemat_reads(tpch, QUERIES["q1"])
    assert GLOBAL.get("latemat/compact_overflow_reruns") == before + 1
    assert (direct, gathered) == (6, 6)
    assert sorted(len(g) for g in gathers.values()) == [0, 6]
    _byte_equal(off, on)


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
