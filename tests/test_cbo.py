"""Cost-based join ordering v1: statistics + selectivity estimates.

round-3 review item 5: join order was a PK-edge spanning tree ranked by RAW
table size. Now `query/stats.py` estimates post-predicate cardinality
(NDV from dictionaries/spans, range selectivity from portion min/max) and
the planner ranks fact choice and build attachment by it — EXPLAIN shows
the estimates.
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.query import stats as S


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 12)
    # big: 60k rows, joins small on k; small: 3k rows
    e.execute("create table big (id Int64 not null, k Int64 not null, "
              "d Int32 not null, v Double not null, primary key (id))")
    e.execute("create table small (k2 Int64 not null, w Double not null, "
              "primary key (k2))")
    n = 60_000
    ids = np.arange(n)
    rows = ",".join(f"({i},{i % 3000},{i % 365},{i * 0.5})"
                    for i in ids)
    for lo in range(0, n, 15_000):
        chunk = ",".join(f"({i},{i % 3000},{i % 365},{i * 0.5})"
                         for i in ids[lo:lo + 15_000])
        e.execute(f"insert into big (id, k, d, v) values {chunk}")
    e.execute("insert into small (k2, w) values "
              + ",".join(f"({k},{k * 2.0})" for k in range(3000)))
    e.big = pd.DataFrame({"id": ids, "k": ids % 3000, "d": ids % 365,
                          "v": ids * 0.5})
    e.small = pd.DataFrame({"k2": np.arange(3000),
                            "w": np.arange(3000) * 2.0})
    return e


def test_stats_primitives(eng):
    t = eng.catalog.table("big")
    assert S.table_rows(t) == 60_000
    lo, hi = S.column_minmax(t, "d")
    assert (lo, hi) == (0, 364)
    # pk NDV = rows; int NDV bounded by span
    assert S.column_ndv(t, "id") == 60_000
    assert S.column_ndv(t, "d") == 365


def test_selectivity_shapes(eng):
    from ydb_tpu.sql import parse
    t = eng.catalog.table("big")

    def sel(pred_sql):
        stmt = parse(f"select id from big where {pred_sql}")
        return S.predicate_selectivity(stmt.where, "big", t)

    assert sel("d = 7") == pytest.approx(1 / 365)
    assert sel("d < 36") == pytest.approx(36 / 364, rel=0.1)
    assert sel("d between 10 and 45") == pytest.approx(35 / 364, rel=0.2)
    assert sel("d in (1, 2, 3)") == pytest.approx(3 / 365)


def test_filtered_big_becomes_build_side(eng):
    """A hard equality on the big table's pk collapses its estimate to ~1
    row — the small table must drive the scan, the filtered big table
    becomes the broadcast build despite 20x raw size."""
    plan_txt = eng.explain(
        "select count(*) as c from big, small "
        "where big.k = small.k2 and big.id = 17")
    first_scan = [ln for ln in plan_txt.splitlines() if "Scan" in ln][0]
    assert "Scan small" in first_scan, plan_txt
    assert "est_rows=1" in plan_txt
    # and the answer is right either way
    got = eng.query("select count(*) as c from big, small "
                    "where big.k = small.k2 and big.id = 17")
    assert got.c[0] == 1


def test_unfiltered_big_drives(eng):
    plan_txt = eng.explain(
        "select small.k2, sum(big.v) as s from big, small "
        "where big.k = small.k2 group by small.k2")
    first_scan = [ln for ln in plan_txt.splitlines() if "Scan" in ln][0]
    assert "Scan big" in first_scan, plan_txt
    got = eng.query("select sum(v) as s from big, small "
                    "where big.k = small.k2")
    np.testing.assert_allclose(got.s[0], eng.big.v.sum(), rtol=1e-9)


def test_explain_shows_estimates(eng):
    txt = eng.explain("select count(*) as c from big where d < 10")
    assert "est_rows=" in txt
