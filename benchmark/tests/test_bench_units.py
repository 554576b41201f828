"""The yardstick's own arithmetic: least bytes, the trace reduction on the
recorded trace, the comparison, the traffic generator."""

import math
from pathlib import Path

import numpy as np
import pandas as pd

import compare
import least_bytes
import traffic
from conftest import BENCH
from tpch_gen import TPCH_COLUMNS

ROWS = {"lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000,
        "part": 200_000, "supplier": 10_000, "partsupp": 800_000,
        "nation": 25, "region": 5}


def _bytes(query: str) -> int:
    mod = traffic.load_module("queries/tpch", query)
    return least_bytes.query_bytes(mod.TABLES, TPCH_COLUMNS, ROWS)


def test_least_bytes_of_q1_and_q6_by_hand():
    # q1: two flags (dictionary codes, 4 B each), four doubles, one date
    assert _bytes("q1") == 6_000_000 * (4 + 4 + 8 + 8 + 8 + 8 + 4)
    # q6: three doubles and one date
    assert _bytes("q6") == 6_000_000 * (8 + 8 + 8 + 4)
    # q3 reads three tables once each
    assert _bytes("q3") == 150_000 * (8 + 4) + 1_500_000 * (8 + 8 + 4 + 4) \
        + 6_000_000 * (8 + 8 + 8 + 4)


def test_trace_reduction_on_the_recorded_trace():
    import trace_reduce
    r = trace_reduce.reduce_trace(str(Path(__file__).parent / "data"
                                      / "tiny.xplane.pb"))
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 1.0
    # three executions of one program: a sort, a matmul fusion, copies
    names = [n for n, _s in r["device_ops"]]
    assert names and "sort" in names[0] and names[0].startswith("jit_")
    assert all(len(n) <= 100 for n in names)
    assert abs(sum(s for _n, s in r["device_ops"]) - r["busy_s"]) \
        < 0.05 * r["busy_s"]
    # the gaps carry the names the recording planted, and add up to the
    # idle part of the window
    gaps = dict(r["idle_gaps"])
    assert {"between-queries", "front"} <= set(gaps)
    assert any("eng.query" in n or "readout" in n or "dispatch" in n
               for n in gaps)
    assert math.isclose(sum(gaps.values()), r["window_s"] - r["busy_s"],
                        rel_tol=0.02)


def test_short_op_names():
    import trace_reduce
    text = ("%fusion.3 = (f32[163840]{0:T(1024)S(1)}, f32[163840]{0:T(1024)"
            "S(1)}) fusion(f32[163840]{0:T(1024)S(1)} %broadcast_in_dim.21), "
            "kind=kCustom, calls=%fused_computation.3")
    assert trace_reduce.short_op(text) == "fusion.3 fusion:Custom"
    assert trace_reduce.short_op(
        "%copy-done = f32[8]{0:T(8)} copy-done((f32[8]{0}, u32[]) %cs)") \
        == "copy-done copy-done"


def test_answer_gap_reads_exact_and_float_cells_apart():
    want = pd.DataFrame({"k": np.array([1, 2], dtype=np.int64),
                         "s": ["a", "b"], "v": [1.0, 2.0]})
    rows = [["1", "a", "1.0"], ["2", "b", repr(2.0 * (1 + 1e-12))]]
    ok, gap = compare.answer_gap(["k", "s", "v"], rows, want)
    assert ok and 0.5e-12 < gap < 2e-12
    assert compare.answer_gap(["k", "s", "v"], rows[:1], want)[0] is False
    rows[0][1] = "x"
    assert compare.answer_gap(["k", "s", "v"], rows, want)[0] is False
    rows[0][1], rows[1][2] = "a", None
    assert compare.answer_gap(["k", "s", "v"], rows, want)[0] is False


def test_every_seed_gets_the_same_work_in_another_order():
    mix = traffic.read_json(BENCH / "workloads" / "tpch-sf1.join.json")
    shapes = []
    for seed in (1, 2**31 + 5, 987654321):
        _mods, items = traffic.build_items(mix, seed)
        assert len({it.params for it in items if it.query == "q3"}) == 2
        # Q18 gets its whole range on every seed, lowest threshold first:
        # the warm-up meets the largest semi-join before the others
        assert [dict(it.params)["quantity"] for it in items
                if it.query == "q18"] == [248, 249, 250, 251, 252]
        plans = [traffic.stream_plan(mix, items, seed, i) for i in range(2)]
        assert plans[0][0].query != plans[1][0].query
        for plan in plans:
            assert set(plan) == set(items) and len(plan) == 15
        shapes.append([[it.query for it in plan] for plan in plans])
        again = traffic.build_items(mix, seed)[1]
        assert [i.sql for i in again] == [i.sql for i in items]
    assert shapes[0] == shapes[1] == shapes[2]
