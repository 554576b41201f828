"""Device window lane vs the pandas lane — differential parity.

Every supported spec shape runs twice over the same data: once forced
through `ops/window_dev.py` (window_device_min_rows=0) and once through
the host pandas lane; frames must match exactly. The soul of the test
strategy in SURVEY §4: lowering-vs-oracle differential over randomized
inputs.
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.utils.config import Config
from ydb_tpu.utils.metrics import GLOBAL


def _mk_engine(dev: bool):
    cfg = Config()
    cfg.window_device_min_rows = 0 if dev else (1 << 62)
    e = QueryEngine(block_rows=1 << 12, config=cfg)
    rng = np.random.default_rng(7)
    n = 3000
    g = rng.integers(0, 12, n)
    h = rng.integers(0, 4, n)
    v = np.round(rng.normal(100, 30, n), 3)
    d = rng.integers(0, 1000, n)
    tags = np.array(["aa", "bb", "cc", "dd"], dtype=object)[
        rng.integers(0, 4, n)]
    nullmask = rng.random(n) < 0.15
    e.execute("create table w (k Int64 not null, g Int64 not null, "
              "h Int64 not null, v Double, d Int64 not null, tag Utf8, "
              "primary key (k))")
    rows = []
    for i in range(n):
        vv = "null" if nullmask[i] else f"{v[i]}"
        rows.append(f"({i}, {g[i]}, {h[i]}, {vv}, {d[i]}, '{tags[i]}')")
    for lo in range(0, n, 500):
        e.execute("insert into w (k, g, h, v, d, tag) values "
                  + ", ".join(rows[lo:lo + 500]))
    return e


@pytest.fixture(scope="module")
def engines():
    return _mk_engine(True), _mk_engine(False)


CASES = [
    # ranking family, multi-key partition + order
    "select k, row_number() over (partition by g order by d, k) as rn, "
    "rank() over (partition by g order by h) as rk, "
    "dense_rank() over (partition by g order by h) as drk from w",
    # running aggregates (SQL default frame with ORDER BY)
    "select k, sum(v) over (partition by g order by k) as rs, "
    "count(v) over (partition by g order by k) as rc, "
    "avg(v) over (partition by g order by k) as ra from w",
    # whole-partition aggregates
    "select k, sum(v) over (partition by g) as ts, "
    "min(v) over (partition by g) as tmin, "
    "max(v) over (partition by g) as tmax, "
    "count(*) over (partition by g) as tc from w",
    # running min/max
    "select k, min(v) over (partition by g order by k) as rmin, "
    "max(v) over (partition by g order by k) as rmax from w",
    # ROWS BETWEEN frames (moving aggregates)
    "select k, sum(v) over (partition by g order by k "
    "rows between 3 preceding and current row) as mv3, "
    "avg(v) over (partition by g order by k "
    "rows between 2 preceding and 2 following) as ctr from w",
    # lead / lag, incl. a string column and an explicit offset
    "select k, lag(v) over (partition by g order by k) as pv, "
    "lead(v, 2) over (partition by g order by k) as nv2, "
    "lag(tag) over (partition by g order by k) as ptag from w",
    # no partition (global window)
    "select k, row_number() over (order by d desc, k) as rn, "
    "sum(v) over (order by k) as rs from w",
    # string partition key + descending order
    "select k, row_number() over (partition by tag order by v desc, k) "
    "as rn from w",
    # window result inside an expression (post pass)
    "select k, v * 100.0 / sum(v) over (partition by g) as share "
    "from w where v is not null",
]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_device_matches_pandas(engines, case):
    dev, host = engines
    sql = CASES[case] + " order by k limit 500"
    before = GLOBAL.get("engine/window_device_rows")
    got = dev.query(sql)
    after = GLOBAL.get("engine/window_device_rows")
    assert after > before, "device lane was not taken"
    want = host.query(sql)
    assert list(got.columns) == list(want.columns)
    for c in got.columns:
        a, b = got[c], want[c]
        if not (pd.api.types.is_numeric_dtype(a)
                and pd.api.types.is_numeric_dtype(b)):
            assert [x if isinstance(x, str) else None for x in a] \
                == [x if isinstance(x, str) else None for x in b], c
        else:
            an, bn = a.to_numpy(np.float64, na_value=np.nan), \
                b.to_numpy(np.float64, na_value=np.nan)
            assert np.allclose(an, bn, rtol=1e-9, equal_nan=True), \
                (c, an[:10], bn[:10])


def test_device_lane_zero_host_rows(engines):
    """The Done criterion (round-4 review #6): a supported window query on
    the device lane leaves the pandas host-lane counter untouched."""
    dev, _host = engines
    h0 = GLOBAL.get("engine/host_lane/window_rows")
    dev.query("select k, sum(v) over (partition by g order by k) as rs "
              "from w order by k limit 10")
    assert GLOBAL.get("engine/host_lane/window_rows") == h0


def test_unsupported_spec_falls_back(engines):
    dev, host = engines
    # bounded min/max frame: declined by the device lane, answered by
    # the pandas lane — identically
    sql = ("select k, min(v) over (partition by g order by k "
           "rows between 2 preceding and current row) as m from w "
           "order by k limit 50")
    got, want = dev.query(sql), host.query(sql)
    assert np.allclose(got.m.to_numpy(np.float64, na_value=np.nan),
                       want.m.to_numpy(np.float64, na_value=np.nan),
                       equal_nan=True)


FINAL_CASES = [
    # order by window output desc + passthrough tiebreak
    "select k, g, sum(v) over (partition by g order by k) as rs from w "
    "order by rs desc, k limit 37",
    # order by passthrough (nullable double!) asc — engine NULLS FIRST
    "select k, v, row_number() over (partition by g order by k) as rn "
    "from w order by v, k limit 25",
    # string passthrough order key + offset
    "select k, tag, rank() over (partition by tag order by d) as rk "
    "from w order by tag desc, k limit 19 offset 7",
    # multi-key: window output asc + string + desc int
    "select k, tag, d, lag(v) over (partition by g order by k) as pv "
    "from w order by d desc, tag, k limit 11",
]


@pytest.mark.parametrize("case", range(len(FINAL_CASES)))
def test_device_final_sort_limit(engines, case):
    """The ORDER BY + LIMIT pushdown (r5 egress lever) must agree with
    the host tail exactly — including NULL placement and offsets."""
    dev, host = engines
    sql = FINAL_CASES[case]
    before = GLOBAL.get("engine/window_device_rows")
    push0 = GLOBAL.get("engine/window_device_pushdown")
    got = dev.query(sql)
    assert GLOBAL.get("engine/window_device_rows") > before
    assert GLOBAL.get("engine/window_device_pushdown") > push0, \
        "ORDER BY/LIMIT pushdown did not engage"
    want = host.query(sql)
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for c in got.columns:
        a, b = got[c], want[c]
        if not (pd.api.types.is_numeric_dtype(a)
                and pd.api.types.is_numeric_dtype(b)):
            assert [x if isinstance(x, str) else None for x in a] \
                == [x if isinstance(x, str) else None for x in b], c
        else:
            an = a.to_numpy(np.float64, na_value=np.nan)
            bn = b.to_numpy(np.float64, na_value=np.nan)
            assert np.allclose(an, bn, rtol=1e-9, equal_nan=True), \
                (c, an[:8], bn[:8])


def test_final_sort_string_window_output(engines):
    """ORDER BY a string-valued window output (lag of a dict column):
    must sort LEXICOGRAPHICALLY, not by dictionary insertion codes
    (review r5) — and NULLs take the engine's null-smallest placement."""
    dev, host = engines
    sql = ("select k, lag(tag) over (partition by g order by k) as pt "
           "from w order by pt desc, k limit 23")
    push0 = GLOBAL.get("engine/window_device_pushdown")
    got = dev.query(sql)
    assert GLOBAL.get("engine/window_device_pushdown") > push0
    want = host.query(sql)
    assert [x if isinstance(x, str) else None for x in got.pt] \
        == [x if isinstance(x, str) else None for x in want.pt]
    assert list(got.k) == list(want.k)
