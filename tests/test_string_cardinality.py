"""String lane at dictionary-degenerate cardinality.

round-3 review item 6: everything string rides host dictionaries — fine at
low cardinality, degenerate for ClickBench URL columns. This pins the
high-cardinality path: bulk factorize encoding, VECTORIZED dictionary
predicates (LIKE / startswith / contains via the pandas C str engine,
the hyperscan/re2-UDF seat), memoized lexicographic sort ranks, and
group-by over near-unique string keys — all against pandas oracles.
"""

import time

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.bench.clickbench_gen import load_hits
from ydb_tpu.query import QueryEngine

N = 300_000
CARD = 150_000          # distinct URLs ~ half the rows


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 16)
    raw = load_hits(e.catalog, n_rows=N, portion_rows=1 << 16,
                    url_cardinality=CARD)
    e.raw = raw
    return e


def test_dictionary_is_degenerate(eng):
    d = eng.catalog.table("hits").dictionaries["URL"]
    assert len(d) > CARD * 0.5          # genuinely high cardinality


def test_like_over_high_cardinality(eng):
    df = pd.DataFrame({"URL": eng.raw["URL"]})
    t0 = time.perf_counter()
    got = eng.query("select count(*) as c from hits "
                    "where URL like '%cars%'")
    dt = time.perf_counter() - t0
    want = int(df.URL.str.contains("cars").sum())
    assert int(got.c[0]) == want
    # vectorized lane: a per-value Python loop at this cardinality costs
    # multiple seconds; the pandas str engine stays well under
    assert dt < 30, f"LIKE took {dt:.1f}s"


def test_startswith_contains(eng):
    got = eng.query("select count(*) as c from hits "
                    "where startswith(URL, 'http://example.com/cars')")
    df = pd.DataFrame({"URL": eng.raw["URL"]})
    assert int(got.c[0]) == int(
        df.URL.str.startswith("http://example.com/cars").sum())
    got2 = eng.query("select count(*) as c from hits "
                     "where contains_string(Title, 'page')")
    t = pd.Series(eng.raw["Title"])
    assert int(got2.c[0]) == int(t.str.contains("page", regex=False).sum())


def test_groupby_near_unique_strings(eng):
    got = eng.query(
        "select URL, count(*) as c from hits group by URL "
        "order by c desc, URL limit 10")
    df = pd.DataFrame({"URL": eng.raw["URL"]})
    w = df.groupby("URL").size().reset_index(name="c")
    w = w.sort_values(["c", "URL"], ascending=[False, True],
                      kind="stable").head(10)
    assert list(got.URL) == list(w.URL)
    assert list(got.c) == list(w.c)


def test_order_by_high_cardinality_string(eng):
    # memoized sort ranks: second run must not redo the big argsort
    d = eng.catalog.table("hits").dictionaries["URL"]
    got = eng.query("select URL from hits order by URL limit 5")
    assert d._ranks is not None
    memo = d._ranks
    got2 = eng.query("select URL from hits order by URL desc limit 5")
    assert d._ranks is memo             # reused, not recomputed
    u = np.sort(np.unique(eng.raw["URL"].astype(str)))
    df = pd.DataFrame({"URL": eng.raw["URL"].astype(str)})
    first = df.sort_values("URL", kind="stable").head(5)
    assert list(got.URL) == list(first.URL)
    assert list(got2.URL)[0] == u[-1]
