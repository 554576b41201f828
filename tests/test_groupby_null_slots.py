"""A bounded-domain group-by gives a key a NULL bucket slot only where the
key can be NULL (`ir.GroupBy.key_nullable`, `xla_exec.bucket_layout`).

The slots left out can never hold a row, so every answer, NULL group and
group order is the one the full layout gives: checked here against a numpy
reference on both bucket lowerings (the one-hot reduction and the
scatter), and on TPC-H Q1 through the engine. CPU runs: answers, routes
and trace counters, never a speed."""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.core import dtypes as dt
from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops import xla_exec as X

N = 3000
AGGS = (ir.Agg("n", "count_all"), ir.Agg("cv", "count", "w"),
        ir.Agg("sv", "sum", "v"), ir.Agg("sw", "sum", "w"),
        ir.Agg("mn", "min", "v"), ir.Agg("mx", "max", "w"))


def _block(case: str, seed: int = 7):
    """(block, per-key (domain, nullable), per-key codes with -1 = NULL).
    Key `a`: int32 codes in [0, 3); key `b`: int32 codes in [0, 4), or a
    dictionary string of four values. Values `v` (float64 quarters: every
    sum exact) and `w` (nullable int64)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 3, N).astype(np.int32)
    b = rng.integers(0, 4, N).astype(np.int32)
    v = rng.integers(-400, 400, N) / 4.0
    w = rng.integers(-50, 50, N).astype(np.int64)
    w_valid = rng.random(N) > 0.2
    a_null = rng.random(N) < 0.1
    b_null = rng.random(N) < 0.15
    a_nullable = case in ("nullable", "mixed")
    b_nullable = case in ("nullable", "string-null-code")
    valids = {"w": w_valid}
    if a_nullable:
        valids["a"] = ~a_null
    if case == "nullable":
        valids["b"] = ~b_null
    cols = [Column("a", dt.DType(dt.Kind.INT32, a_nullable)),
            Column("b", dt.DType(dt.Kind.INT32, b_nullable)),
            Column("v", dt.FLOAT64), Column("w", dt.INT64)]
    if case == "string-null-code":
        strs = pd.Series(np.array(["d", "a", "c", "b"], dtype=object)[b])
        strs[b_null] = None
        sblock = HostBlock.from_pandas(pd.DataFrame({"b": strs}))
        cd = sblock.columns["b"]
        cols[1] = Column("b", sblock.schema.dtype("b"))
        assert cols[1].dtype.is_string and cols[1].dtype.nullable
        block = HostBlock.from_arrays(
            Schema(cols), {"a": a, "b": np.zeros(N, np.int32), "v": v,
                           "w": w}, valids)
        # a NULL string is code -1 with no validity mask
        block.columns["b"] = ColumnData(cd.data, None, cd.dictionary)
        b = cd.data.astype(np.int32)
        assert (b[b_null] == -1).all() and (b[~b_null] >= 0).all()
    else:
        block = HostBlock.from_arrays(
            Schema(cols), {"a": a, "b": b, "v": v, "w": w}, valids)
        b = np.where(b_null, -1, b) if case == "nullable" else b
    a = np.where(a_null, -1, a) if a_nullable else a
    return block, ((3, a_nullable), (4, b_nullable)), (a, b)


def _reference(codes, block) -> pd.DataFrame:
    """Groups in bucket order: the first key varies fastest, NULL (-1)
    before every value."""
    a, b = codes
    v = block.columns["v"].data
    w = block.columns["w"].data
    wv = block.columns["w"].valid
    rows = []
    for gb, ga in sorted({(int(y), int(x)) for x, y in zip(a, b)}):
        m = (a == ga) & (b == gb)
        mw = m & wv
        rows.append({"a": ga, "b": gb, "n": int(m.sum()),
                     "cv": int(mw.sum()), "sv": float(v[m].sum()),
                     "sw": int(w[mw].sum()) if mw.any() else None,
                     "mn": float(v[m].min()),
                     "mx": int(w[mw].max()) if mw.any() else None})
    return pd.DataFrame(rows)


def _decoded(out: HostBlock) -> pd.DataFrame:
    """The lowering's answer with keys as codes (-1 = NULL)."""
    df = {}
    for k in ("a", "b"):
        cd = out.columns[k]
        code = cd.data.astype(np.int64)
        if cd.valid is not None:
            code = np.where(cd.valid, code, -1)
        df[k] = code
    for name in ("n", "cv", "sv", "sw", "mn", "mx"):
        cd = out.columns[name]
        vals = list(cd.data[:out.length].tolist())
        if cd.valid is not None:
            vals = [x if ok else None
                    for x, ok in zip(vals, cd.valid[:out.length])]
        df[name] = vals
    df["a"], df["b"] = df["a"][:out.length], df["b"][:out.length]
    return pd.DataFrame(df)


# route: the domains declared for the keys. `small-domain` declares the
# data's own; `medium-domain` declares key b's domain 300 wide (its values
# stay in [0, 4)), which puts the bucket product past the one-hot bound.
ROUTES = {"small-domain": 1, "medium-domain": 75}
CASES = ("non-null", "nullable", "mixed", "string-null-code")


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("case", CASES)
def test_null_slots(case, route):
    block, keys, codes = _block(case)
    (da, na), (db, nb) = keys
    db *= ROUTES[route]
    cmd = ir.GroupBy(("a", "b"), AGGS, key_domains=(da, db),
                     key_nullable=(na, nb))
    nbuckets, strides, offsets = X.bucket_layout(cmd)
    assert nbuckets == (da + na) * (db + nb)
    assert strides == (1, da + na) and offsets == (int(na), int(nb))
    assert X.groupby_route(cmd) == (route, nbuckets)

    X.groupby_trace_reset()
    out = X.run_program(ir.Program([cmd]), block, cache=X.ProgramCache())
    trace = X.groupby_trace_snapshot()
    assert trace.get("null_slots_elided", 0) == (not na) + (not nb)
    if route == "small-domain":
        assert trace["onehot_buckets_max"] == nbuckets
    else:
        assert "onehot_buckets_max" not in trace

    got, want = _decoded(out), _reference(codes, block)
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    if case == "string-null-code":
        # the NULL group first, then the dictionary's code order
        values = block.columns["b"].dictionary.values_array()
        assert out.to_pandas()["b"].fillna("NULL").tolist() == [
            "NULL" if c < 0 else values[c] for c in want["b"]]


def test_null_slot_is_part_of_the_cache_key():
    """A program traced for a non-null key is never served to the same
    group-by over a key that can be NULL: the field is in the program's
    fingerprint, and each answer is its own layout's."""
    block, _keys, codes = _block("non-null")
    cache = X.ProgramCache()
    outs = []
    for nullable in ((False, False), (True, True)):
        cmd = ir.GroupBy(("a", "b"), AGGS, key_domains=(3, 4),
                         key_nullable=nullable)
        outs.append(X.run_program(ir.Program([cmd]), block, cache=cache))
    assert cache.misses == 2 and cache.hits == 0
    for out in outs:
        pd.testing.assert_frame_equal(_decoded(out),
                                      _reference(codes, block),
                                      check_dtype=False)


def test_non_null_key_ignores_an_all_true_mask():
    """A key that cannot be NULL may still carry a mask, true on every
    live row (the merge of partials gives every column one; an inner
    join's payload carries its matched flag): the layout has no NULL slot
    and the answer is the key's own."""
    block, _keys, codes = _block("non-null")
    masked = HostBlock.from_arrays(
        block.schema, {n: c.data for n, c in block.columns.items()},
        {"a": np.ones(N, bool), "b": np.ones(N, bool),
         "w": block.columns["w"].valid})
    cmd = ir.GroupBy(("a", "b"), AGGS, key_domains=(3, 4),
                     key_nullable=(False, False))
    out = X.run_program(ir.Program([cmd]), masked, cache=X.ProgramCache())
    pd.testing.assert_frame_equal(_decoded(out), _reference(codes, block),
                                  check_dtype=False)


def test_a_window_reports_its_own_widest_one_hot():
    """A statement's trace window reports the widest one-hot IT traced,
    though a wider one traced before it on the same thread; the wider
    watermark comes back when the window closes."""
    block, _keys, _codes = _block("non-null")
    X.groupby_trace_reset()
    for nullable in ((True, True), (False, False)):      # 20, then 12
        mark = X.groupby_trace_mark()
        cmd = ir.GroupBy(("a", "b"), AGGS, key_domains=(3, 4),
                         key_nullable=nullable)
        X.run_program(ir.Program([cmd]), block, cache=X.ProgramCache())
        delta = X.groupby_trace_delta(mark)
        assert delta["onehot_buckets_max"] == X.bucket_layout(cmd)[0]
    assert X.groupby_trace_snapshot()["onehot_buckets_max"] == 20
    assert X.groupby_trace_delta(X.groupby_trace_mark()) == {}


def test_q1_reduces_over_six_buckets():
    """TPC-H Q1 groups by two NOT NULL scan columns (domains 3 and 2): six
    buckets where the NULL slots made twelve, on both its GroupBys."""
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.sql.parser import parse
    from tpch_util import QUERIES, assert_frames_match, oracle

    eng = QueryEngine()
    data = load_tpch(eng.catalog, sf=0.002)
    plan = eng.planner.plan_select(parse(QUERIES["q1"]))
    gbs = [c for p in (plan.pipeline.partial, plan.final_program)
           for c in p.commands if isinstance(c, ir.GroupBy)]
    assert len(gbs) == 2
    for gb in gbs:
        assert gb.key_nullable == (False, False)
        assert X.groupby_route(gb) == ("small-domain", 6)
        assert gb.out_bound == 6
    X.groupby_trace_reset()
    text = "\n".join(eng.query("explain analyze " + QUERIES["q1"])["plan"])
    assert "one-hot buckets max 6 |" in text, text
    assert "null slots elided 4" in text, text
    assert_frames_match(eng.query(QUERIES["q1"]), oracle("q1", data),
                        ordered=True)
