"""Round-8 tiled, late-materialized sorted group-by: differential tests
vs the numpy oracle (`ops/numpy_exec`) under forced-tiny tile budgets.

`YDB_TPU_GROUPBY_TILE_ROWS` forces many tiles at test scale (blocks pad
to the 8192-row capacity bucket, so tile_rows=1024 → 8 tiles); the knob
is part of every compiled-program cache key, so in-process env flips
recompile rather than reuse a differently-tiled trace. Cases pin the
tile-boundary hazards: one group spanning a tile boundary, all rows one
group, mostly-empty tiles, skewed group sizes, nullable-int and
NaN-float keys, 0-row input, batched / per-column gather byte-equality,
and the `out_bound` late-materialization contract.
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.core import dtypes as dt
from ydb_tpu.core.block import HostBlock
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir, numpy_exec, xla_exec
from ydb_tpu.ops.ir import Agg, Col, Const, call

ALL_AGGS = [Agg("cnt", "count_all"), Agg("c", "count", "v"),
            Agg("s", "sum", "v"), Agg("mn", "min", "v"),
            Agg("mx", "max", "v"), Agg("sm", "some", "v")]


def _block(keys: dict, v, v_valid=None, extra_valids=None):
    cols = []
    arrays = {}
    valids = dict(extra_valids or {})
    for name, arr in keys.items():
        arr = np.asarray(arr)
        kind = {np.dtype(np.int64): dt.INT64, np.dtype(np.int32): dt.INT32,
                np.dtype(np.float64): dt.FLOAT64}[arr.dtype]
        nullable = name in valids
        cols.append(Column(name, dt.DType(kind.kind, nullable)))
        arrays[name] = arr
    cols.append(Column("v", dt.DType(dt.Kind.FLOAT64,
                                     v_valid is not None)))
    arrays["v"] = np.asarray(v, np.float64)
    if v_valid is not None:
        valids["v"] = np.asarray(v_valid, bool)
    return HostBlock.from_arrays(Schema(cols), arrays, valids)


def _set_tiny(monkeypatch, tile_rows="1024"):
    monkeypatch.setenv("YDB_TPU_GROUPBY_TILE_ROWS", tile_rows)


def _run_both(program, block, sort_by):
    oracle = numpy_exec.run_program(program, block)
    device = xla_exec.run_program(program, block)
    do, dd = oracle.to_pandas(), device.to_pandas()
    assert list(do.columns) == list(dd.columns)
    assert len(do) == len(dd)
    do = do.sort_values(sort_by).reset_index(drop=True)
    dd = dd.sort_values(sort_by).reset_index(drop=True)
    for col in do.columns:
        a, b = do[col].to_numpy(), dd[col].to_numpy()
        na, nb = pd.isna(a), pd.isna(b)
        assert (na == nb).all(), f"null mismatch in {col}"
        af = pd.to_numeric(pd.Series(a[~na])).to_numpy(np.float64)
        bf = pd.to_numeric(pd.Series(b[~nb])).to_numpy(np.float64)
        np.testing.assert_allclose(af, bf, rtol=1e-9, atol=1e-9,
                                   err_msg=col)
    return device


def test_group_spans_tile_boundary(monkeypatch, rng):
    # 16 groups of ~500 rows over an 8192-cap block with 1024-row tiles:
    # in key-sorted order nearly every group crosses a tile seam
    _set_tiny(monkeypatch)
    n = 8000
    k = (np.arange(n, dtype=np.int64) // 500)
    perm = rng.permutation(n)
    b = _block({"k": k[perm]}, rng.normal(size=n) * 50,
               v_valid=rng.random(n) > 0.1)
    p = ir.Program().group_by(["k"], ALL_AGGS)
    _run_both(p, b, ["k"])


def test_all_rows_one_group(monkeypatch, rng):
    _set_tiny(monkeypatch)
    n = 5000
    b = _block({"k": np.zeros(n, np.int64)}, rng.normal(size=n))
    p = ir.Program().group_by(["k"], ALL_AGGS)
    _run_both(p, b, ["k"])


def test_empty_tiles(monkeypatch, rng):
    # 40 live rows in an 8192 capacity with 64-row tiles: 127 of 128
    # tiles carry only padding
    _set_tiny(monkeypatch, tile_rows="64")
    n = 40
    b = _block({"k": rng.integers(0, 5, n)}, rng.normal(size=n))
    p = ir.Program().group_by(["k"], ALL_AGGS)
    _run_both(p, b, ["k"])


def test_skewed_partitions(monkeypatch, rng):
    # 90% of rows in one group + a long tail of singletons — the sorted
    # order concentrates one giant segment across many tiles
    _set_tiny(monkeypatch)
    n = 6000
    k = np.where(rng.random(n) < 0.9, 7, np.arange(n) + 100).astype(np.int64)
    b = _block({"k": k}, rng.normal(size=n), v_valid=rng.random(n) > 0.2)
    p = ir.Program().group_by(["k"], ALL_AGGS)
    _run_both(p, b, ["k"])


def test_nullable_int_and_nan_float_keys(monkeypatch, rng):
    _set_tiny(monkeypatch)
    n = 4000
    ki = rng.integers(-3, 3, n)
    kf = rng.choice([0.5, -1.25, np.nan, 2.0], n)
    b = _block({"ki": ki, "kf": kf}, rng.normal(size=n),
               v_valid=rng.random(n) > 0.15,
               extra_valids={"ki": rng.random(n) > 0.2})
    p = ir.Program().group_by(["ki", "kf"], ALL_AGGS)
    _run_both(p, b, ["ki", "kf"])


def test_zero_rows(monkeypatch):
    _set_tiny(monkeypatch)
    b = _block({"k": np.zeros(0, np.int64)}, np.zeros(0))
    p = ir.Program().group_by(["k"], ALL_AGGS)
    dev = _run_both(p, b, ["k"])
    assert dev.length == 0


def test_filter_then_group(monkeypatch, rng):
    # selection mask upstream of the group-by: inactive rows must sort
    # out of every tile's live range
    _set_tiny(monkeypatch)
    n = 7000
    b = _block({"k": rng.integers(0, 40, n)}, rng.normal(size=n))
    p = (ir.Program()
         .filter(call("gt", Col("v"), Const(0.0, dt.FLOAT64)))
         .group_by(["k"], [Agg("cnt", "count_all"), Agg("s", "sum", "v"),
                           Agg("mn", "min", "v")]))
    _run_both(p, b, ["k"])


def test_batched_vs_unbatched_byte_equal(monkeypatch, rng):
    # the per-column side of `_GATHER_BATCH_ROWS` (a 1-row threshold: no
    # tile is that small) and the batched side (the default) must give
    # byte-identical results (gathers are exact — stacking then slicing
    # changes nothing)
    n = 6000
    k = rng.integers(0, 300, n)
    v = rng.normal(size=n) * 1e6
    vv = rng.random(n) > 0.1
    w = rng.normal(size=n)
    cols = Schema([Column("k", dt.INT64),
                   Column("v", dt.DType(dt.Kind.FLOAT64, True)),
                   Column("w", dt.FLOAT64)])
    b = HostBlock.from_arrays(cols, {"k": k, "v": v, "w": w}, {"v": vv})
    # two f64 sum args + validity → both the value and endpoint batches
    # engage when the cap allows
    p = ir.Program().group_by(["k"], [
        Agg("s1", "sum", "v"), Agg("s2", "sum", "w"),
        Agg("mn", "min", "v"), Agg("mx", "max", "w"),
        Agg("c", "count", "v")])
    _set_tiny(monkeypatch)
    outs, batched = {}, {}
    for rows in (1, xla_exec._GATHER_BATCH_ROWS):
        # the constant rides no cache key: a cache of its own per side
        monkeypatch.setattr(xla_exec, "_GATHER_BATCH_ROWS", rows)
        xla_exec.groupby_trace_reset()
        outs[rows] = xla_exec.run_program(p, b,
                                          cache=xla_exec.ProgramCache())
        batched[rows] = xla_exec.groupby_trace_snapshot().get(
            "batched_gathers", 0)
    (a, z), (na, nz) = outs.values(), batched.values()
    assert na == 0 and nz >= 1
    assert a.length == z.length
    for name in a.schema.names:
        ca, cz = a.columns[name], z.columns[name]
        assert ca.data.dtype == cz.data.dtype
        assert np.array_equal(ca.data[:a.length], cz.data[:z.length]), name
        va = ca.valid[:a.length] if ca.valid is not None else None
        vz = cz.valid[:z.length] if cz.valid is not None else None
        assert (va is None) == (vz is None)
        if va is not None:
            assert np.array_equal(va, vz), name


def test_out_bound_shrinks_output_capacity(monkeypatch, rng):
    # a PROVEN bound late-materializes per-group outputs at a small
    # bucket: correctness unchanged, device output capacity = the bound's
    # bucket instead of scan capacity
    from ydb_tpu.ops.device import to_device
    from ydb_tpu.ops.xla_exec import run_on_device
    _set_tiny(monkeypatch)
    n = 6000
    b = _block({"k": rng.integers(0, 150, n)}, rng.normal(size=n))
    p = ir.Program().group_by(["k"], ALL_AGGS, out_bound=200)
    _run_both(p, b, ["k"])
    out = run_on_device(p, to_device(b))
    assert out.capacity == 256       # bucket_capacity(200, minimum=128)
    assert int(out.length) <= 150


def test_trace_counters(monkeypatch, rng):
    # forced-tiny tiles + a proven group bound (how real tail plans run:
    # planner domain products / executor join bounds): the trace must
    # report tiling active, NO gather above the tile budget — value
    # gathers are tile-sized, per-group gathers bound-sized — and
    # batched gathers engaged
    from ydb_tpu.utils.metrics import GLOBAL
    _set_tiny(monkeypatch, tile_rows="2048")
    n = 6000
    k = rng.integers(0, 500, n)
    b = _block({"k": k}, rng.normal(size=n), v_valid=rng.random(n) > 0.1)
    p = ir.Program().group_by(["k"], ALL_AGGS, out_bound=600)
    xla_exec.groupby_trace_reset()
    before = GLOBAL.get("groupby/gather_ops")
    xla_exec.run_program(p, b)
    tr = xla_exec.groupby_trace_snapshot()
    assert tr.get("traces", 0) >= 1
    assert tr.get("tiles", 0) >= 4           # 8192-cap / 2048-row tiles
    assert tr.get("value_gather_rows_max", 0) <= 2048
    assert tr.get("gather_ops", 0) == 0      # nothing above the budget
    assert GLOBAL.get("groupby/gather_ops") == before
    assert tr.get("batched_gathers", 0) >= 1  # validity/endpoint batches


def test_engine_tiny_tiles_vs_pandas(monkeypatch, rng):
    # end-to-end: q3-shaped SQL through the engine (fused path + the
    # executor's join-derived out_bound) under forced-tiny tiles
    from ydb_tpu.query import QueryEngine
    _set_tiny(monkeypatch, tile_rows="1024")
    eng = QueryEngine(block_rows=1 << 13)
    eng.execute("create table f (id Int64 not null, k Int64 not null, "
                "val Double not null, primary key (id)) "
                "with (store = column)")
    eng.execute("create table d (k Int64 not null, grp Int64 not null, "
                "primary key (k)) with (store = column)")
    n, m = 6000, 500
    f = pd.DataFrame({"id": np.arange(n, dtype=np.int64),
                      "k": rng.integers(0, m, n),
                      "val": rng.normal(size=n) * 100})
    d = pd.DataFrame({"k": np.arange(m, dtype=np.int64),
                      "grp": rng.integers(0, 9, m)})
    ver = eng._next_version()
    for name, df in (("f", f), ("d", d)):
        t = eng.catalog.table(name)
        t.bulk_upsert(df, ver)
        t.indexate()
    got = eng.query("select f.k as k, grp, sum(val) as s, count(*) as c "
                    "from f join d on f.k = d.k "
                    "group by f.k, grp order by k")
    j = f.merge(d, on="k")
    want = (j.groupby(["k", "grp"], as_index=False)
            .agg(s=("val", "sum"), c=("val", "count"))
            .sort_values("k").reset_index(drop=True))
    assert len(got) == len(want)
    np.testing.assert_allclose(got["s"].to_numpy(), want["s"].to_numpy(),
                               rtol=1e-9)
    assert (got["c"].to_numpy().astype(np.int64)
            == want["c"].to_numpy().astype(np.int64)).all()
    # the unique-keyed inner join proves ngroups <= dim rows
    from ydb_tpu.utils.metrics import GLOBAL
    assert GLOBAL.get("groupby/join_bounded_plans") >= 1
    assert (eng.last_stats.groupby or {}).get("tiles", 0) >= 2
