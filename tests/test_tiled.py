"""Tiled fused execution + aggregation-state spill vs pandas oracles.

The scan-bigger-than-HBM discipline (round-3 review item 1): budgets are
forced tiny so the full TPC-H suite streams through the tiled path
(`executor._execute_fused_tiled`) — multiple stacked-source tiles, one
dispatch each — and high-cardinality group-bys exercise the host-DRAM
partitioned merge (`ops/spill.py`, the `mkql_wide_combine.cpp:338-600`
InMemory→Spilling→ProcessSpilled analog).
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.bench.tpch_gen import load_tpch
from ydb_tpu.query import QueryEngine

from tests.tpch_util import QUERIES, assert_frames_match, oracle

SF = 0.01


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 10)
    data = load_tpch(e.catalog, sf=SF, shards=2, portion_rows=1 << 10)
    e.tpch_data = data
    # lineitem at SF 0.01 is ~60k rows over ~60 portions; these budgets
    # force multi-tile streaming on every lineitem/orders scan
    e.executor.fused_scan_budget_bytes = 1 << 18
    e.executor.tile_budget_bytes = 1 << 20
    return e


# every query whose scan these budgets tile: all but q13, whose largest
# scan fits one fused dispatch (no lane declines fusion for a LUT's
# density: a build without a LUT probes by binary search in the trace)
TILED = ["q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8", "q9", "q10",
         "q11", "q12", "q14", "q15", "q16", "q17", "q18", "q19", "q20",
         "q21", "q22"]


@pytest.mark.parametrize("name", TILED)
def test_tpch_tiled(eng, name):
    got = eng.query(QUERIES[name])
    want = oracle(name, eng.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)
    assert eng.executor.last_path.startswith("fused-tiled"), \
        eng.executor.last_path


def test_tiled_spill_high_cardinality(eng):
    # group by l_orderkey (unbounded domain) with a merge budget far under
    # the partial-state size → host-DRAM partitioned merge
    from ydb_tpu.utils.metrics import GLOBAL
    old = eng.executor.merge_budget_bytes
    eng.executor.merge_budget_bytes = 1 << 14
    try:
        before = GLOBAL.snapshot().get("executor/spilled_rows", 0)
        got = eng.query(
            "select l_orderkey, sum(l_quantity) as q from lineitem "
            "group by l_orderkey order by q desc, l_orderkey limit 25")
        assert eng.executor.last_path == "fused-tiled-spill"
        assert GLOBAL.snapshot()["executor/spilled_rows"] > before
        li = pd.DataFrame({
            "l_orderkey": eng.tpch_data.tables["lineitem"]["l_orderkey"],
            "l_quantity": eng.tpch_data.tables["lineitem"]["l_quantity"]})
        w = li.groupby("l_orderkey").l_quantity.sum().reset_index()
        w = w.sort_values(["l_quantity", "l_orderkey"],
                          ascending=[False, True], kind="stable").head(25)
        assert list(got.l_orderkey) == list(w.l_orderkey)
        np.testing.assert_allclose(got.q, w.l_quantity, rtol=1e-9)
    finally:
        eng.executor.merge_budget_bytes = old


def test_tiled_union_no_sort(eng):
    old = eng.executor.merge_budget_bytes
    eng.executor.merge_budget_bytes = 1 << 14
    try:
        got = eng.query("select l_orderkey, l_quantity from lineitem "
                        "where l_quantity >= 49")
    finally:
        eng.executor.merge_budget_bytes = old
    assert eng.executor.last_path == "fused-tiled-union"
    li = eng.tpch_data.tables["lineitem"]
    mask = li["l_quantity"] >= 49
    want = pd.DataFrame({"l_orderkey": li["l_orderkey"][mask],
                         "l_quantity": li["l_quantity"][mask]})
    got2 = got.sort_values(["l_orderkey", "l_quantity"]).reset_index(drop=True)
    want2 = want.sort_values(["l_orderkey", "l_quantity"]).reset_index(drop=True)
    assert len(got2) == len(want2)
    assert list(got2.l_orderkey) == list(want2.l_orderkey)
    np.testing.assert_allclose(got2.l_quantity, want2.l_quantity)
