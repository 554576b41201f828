"""Bounds lattice (`query/bounds.py`): derivation units, the executor
carry rewrite's functional-dependency verification, eager aggregation,
and the bound-shaped plans' agreement with pandas.

Three layers, mirroring the lattice's trust tiers:

  * derivation units — per-node bound rules (scan, filter pass-through,
    unique-build row preservation, unknown-multiplicity products, LIMIT,
    group-by domain products, unknown → capacity) on hand-built plans;
  * plan rewrites — the executor's carry-key demotion (trivial join-key
    determinant AND the measured `dataset_distinct` verification, with a
    non-functional-dependency negative), and the planner's eager
    aggregation of LEFT JOIN builds (q13's expanding-probe retirement);
  * the differential — bound-shaped plans (carried keys, join bounds,
    eager aggregation) on tile-boundary / skew / 0-row shapes must
    answer what pandas computes over the same frames.

The q8/q10/q18 regression pins run the real queries at test scale and
assert the fused path (no fallback class) with finite stamped bounds.
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.core import dtypes as dt
from ydb_tpu.ops import ir
from ydb_tpu.query import bounds as BD
from ydb_tpu.query import QueryEngine
from ydb_tpu.utils.metrics import GLOBAL


# -- engine fixture ---------------------------------------------------------


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(block_rows=1 << 13)
    rng = np.random.default_rng(7)
    e.execute("create table f (id Int64 not null, k Int64 not null, "
              "val Double not null, primary key (id)) "
              "with (store = column)")
    e.execute("create table d (k Int64 not null, grp Int64 not null, "
              "a Int64 not null, b Int64 not null, c Int64 not null, "
              "primary key (k)) with (store = column)")
    n, m = 6000, 500
    f = pd.DataFrame({"id": np.arange(n, dtype=np.int64),
                      "k": rng.integers(0, m, n),
                      "val": rng.normal(size=n) * 100})
    # a = 2k is a bijection of the PK (a → anything holds); b, c are
    # small-modulus projections (b does NOT determine c and vice versa)
    d = pd.DataFrame({"k": np.arange(m, dtype=np.int64),
                      "grp": rng.integers(0, 9, m),
                      "a": np.arange(m, dtype=np.int64) * 2,
                      "b": np.arange(m, dtype=np.int64) % 3,
                      "c": np.arange(m, dtype=np.int64) % 5})
    ver = e._next_version()
    for name, df in (("f", f), ("d", d)):
        t = e.catalog.table(name)
        t.bulk_upsert(df, ver)
        t.indexate()
    e.frames = {"f": f, "d": d}
    return e


def _plan(eng, sql):
    from ydb_tpu.sql.parser import parse
    return eng.planner.plan_select(parse(sql))


def _explain(eng, sql: str) -> str:
    return "\n".join(eng.query("explain " + sql).iloc[:, 0].astype(str))


# -- derivation units -------------------------------------------------------


def test_scan_bound_is_row_count(eng):
    p = _plan(eng, "select k from f")
    assert p.pipeline.out_bound == 6000
    assert p.out_bound == 6000


def test_filter_is_pass_through(eng):
    # selectivity ≤ 1: a filter never raises the bound, never zeroes it
    p = _plan(eng, "select k from f where val > 0")
    assert p.pipeline.out_bound == 6000


def test_limit_bounds_result(eng):
    p = _plan(eng, "select k from f order by k limit 7")
    assert p.out_bound == 7
    assert p.pipeline.out_bound == 6000   # pre-sort stream unchanged


def test_unique_build_preserves_rows(eng):
    # d.k is the declared PK → the inner probe is row-preserving
    p = _plan(eng, "select f.k as k, grp from f join d on f.k = d.k")
    assert p.pipeline.out_bound == 6000


def test_unknown_multiplicity_is_product(eng):
    # join on a NON-unique build column (with payload demanded, so it
    # stays a real inner join): the lattice falls back to the product of
    # both sides (never an understatement)
    p = _plan(eng, "select f.k as k2, d.a as da from f "
                   "join d on f.k = d.grp")
    assert p.pipeline.out_bound == 6000 * 500


def test_semi_join_never_expands(eng):
    # a payload-free join plans as a semi probe — row bound unchanged
    p = _plan(eng, "select f.k as k2 from f join d on f.k = d.grp")
    assert p.pipeline.out_bound == 6000


def test_groupby_domain_product():
    gb = ir.GroupBy(("x", "y"), (ir.Agg("c", "count_all"),),
                    key_domains=(3, 4))
    # (dom+1) per key: one extra slot for NULL
    assert BD.groupby_bound(gb) == 20
    assert BD.groupby_bound(
        ir.GroupBy(("x",), (), key_domains=(), out_bound=128)) == 128
    assert BD.groupby_bound(ir.GroupBy((), ())) == 1


def test_groupby_domain_product_without_null_slots():
    # a key that cannot be NULL takes no NULL slot: 3 x 4, where a
    # nullable pair takes 4 x 5
    aggs = (ir.Agg("c", "count_all"),)
    assert BD.groupby_bound(ir.GroupBy(("x", "y"), aggs, key_domains=(3, 4),
                                       key_nullable=(False, False))) == 12
    assert BD.groupby_bound(ir.GroupBy(("x", "y"), aggs, key_domains=(3, 4),
                                       key_nullable=(True, True))) == 20
    assert BD.groupby_bound(ir.GroupBy(("x", "y"), aggs, key_domains=(3, 4),
                                       key_nullable=(True, False))) == 16


def test_unknown_groupby_is_capacity():
    gb = ir.GroupBy(("x",), (ir.Agg("c", "count_all"),))
    assert BD.groupby_bound(gb) == 0
    prog = ir.Program()
    prog.commands.append(gb)
    # unknown group count: ngroups ≤ input rows (pass-through)
    assert BD.program_bound(prog, 1234) == 1234
    assert BD.program_bound(prog, 0) == 0


def test_prune_tightens_scan_bound(eng):
    # the id PK carries portion min/max stats; a range predicate the
    # planner turns into scan.prune must tighten the stats-only bound
    p = _plan(eng, "select k from f where id < 0")
    assert p.pipeline.out_bound < 6000


def test_build_bytes_bound_caps_limit_build(eng):
    # a LIMIT-bounded build materializes at its OUTPUT cardinality:
    # admission reserves bound × row-width, not the driving scan
    import types
    build = _plan(eng, "select k from d order by k limit 10")
    step = types.SimpleNamespace(build=build)
    bb = BD.build_bytes_bound(eng.catalog, step)
    assert bb == 10 * 8                # 10 rows × one non-null Int64
    full = _plan(eng, "select k from d")
    step2 = types.SimpleNamespace(build=full)
    assert BD.build_bytes_bound(eng.catalog, step2) == 500 * 8


def test_explain_bounds_line(eng):
    txt = _explain(eng, "select f.k as k, grp, sum(val) as s from f "
                   "join d on f.k = d.k group by f.k, grp")
    assert "-- bounds:" in txt


# -- executor carry rewrite -------------------------------------------------


def _oracle_groupby(eng, keys, aggs):
    j = eng.frames["f"].merge(eng.frames["d"], on="k")
    return (j.groupby(keys, as_index=False).agg(**aggs)
            .sort_values(keys).reset_index(drop=True))


def test_carry_trivial_join_key_determinant(eng):
    # keys {probe key, payload}: the unique build key determines every
    # payload column — grp demotes to a carried key, and the group-by
    # sorts on ONE key column
    before = GLOBAL.get("bounds/carry_rewrites")
    got = eng.query("select f.k as k, grp, sum(val) as s, count(*) as c "
                    "from f join d on f.k = d.k group by f.k, grp "
                    "order by k")
    assert GLOBAL.get("bounds/carry_rewrites") > before
    want = _oracle_groupby(eng, ["k"], dict(
        grp=("grp", "first"), s=("val", "sum"), c=("val", "count")))
    assert len(got) == len(want)
    np.testing.assert_allclose(got["s"].to_numpy(), want["s"].to_numpy(),
                               rtol=1e-9)
    assert (got["grp"].to_numpy().astype(np.int64)
            == want["grp"].to_numpy().astype(np.int64)).all()


def test_carry_measured_fd_determinant(eng):
    # keys {a, b} are BOTH payloads (no join key among them): a is a
    # bijection of the PK, so distinct(a) == distinct((a, b)) on the
    # materialized build — the measured check proves a → b and b carries
    before = GLOBAL.get("bounds/fd_verified")
    got = eng.query("select a, b, count(*) as c from f "
                    "join d on f.k = d.k group by a, b order by a")
    assert GLOBAL.get("bounds/fd_verified") > before
    want = _oracle_groupby(eng, ["a"], dict(b=("b", "first"),
                                            c=("val", "count")))
    assert len(got) == len(want)
    assert (got["b"].to_numpy().astype(np.int64)
            == want["b"].to_numpy().astype(np.int64)).all()
    assert (got["c"].to_numpy().astype(np.int64)
            == want["c"].to_numpy().astype(np.int64)).all()


def test_no_false_fd_carry(eng):
    # b (mod 3) does not determine c (mod 5) and vice versa: the measured
    # check must refuse a determinant, keys stay in the sort identity,
    # and all 15 (b, c) groups survive
    got = eng.query("select b, c, count(*) as cnt from f "
                    "join d on f.k = d.k group by b, c order by b, c")
    want = _oracle_groupby(eng, ["b", "c"], dict(cnt=("val", "count")))
    assert len(got) == len(want) == 15
    assert (got["cnt"].to_numpy().astype(np.int64)
            == want["cnt"].to_numpy().astype(np.int64)).all()


def test_dataset_distinct_null_canonical():
    # NULLs form ONE value; -0.0 == 0.0; all NaNs equal — mirrors the
    # numpy group-by oracle's canonicalization
    from ydb_tpu.core.block import HostBlock
    from ydb_tpu.core.schema import Column, Schema
    sch = Schema([Column("x", dt.DType(dt.Kind.FLOAT64, True))])
    b = HostBlock.from_arrays(
        sch, {"x": np.array([0.0, -0.0, np.nan, np.nan, 1.0, 9.0])},
        {"x": np.array([True, True, True, True, True, False])})
    # values: {0.0, nan, 1.0, NULL} → 4 distinct
    assert BD.dataset_distinct(b, ["x"]) == 4


# -- eager aggregation ------------------------------------------------------


@pytest.fixture(scope="module")
def eng13():
    e = QueryEngine(block_rows=1 << 13)
    rng = np.random.default_rng(13)
    e.execute("create table cust (ck Int64 not null, seg Int64 not null, "
              "primary key (ck)) with (store = column)")
    e.execute("create table ords (ok Int64 not null, ck Int64 not null, "
              "flag Int64 not null, amt Double not null, "
              "primary key (ok)) with (store = column)")
    nc, no = 800, 7000
    cust = pd.DataFrame({"ck": np.arange(nc, dtype=np.int64),
                         "seg": rng.integers(0, 5, nc)})
    # ~12% of customers have no orders at all (the count-0 class)
    owners = rng.integers(0, int(nc * 0.88), no)
    ords = pd.DataFrame({"ok": np.arange(no, dtype=np.int64),
                         "ck": owners,
                         "flag": rng.integers(0, 4, no),
                         "amt": rng.normal(size=no) * 10})
    ver = e._next_version()
    for name, df in (("cust", cust), ("ords", ords)):
        t = e.catalog.table(name)
        t.bulk_upsert(df, ver)
        t.indexate()
    e.frames = {"cust": cust, "ords": ords}
    return e


Q13_SHAPE = ("select c_count, count(*) as custdist from ("
             "  select cust.ck as ck, count(ords.ok) as c_count"
             "  from cust left join ords"
             "    on cust.ck = ords.ck and ords.flag <> 3"
             "  group by cust.ck) as co "
             "group by c_count order by custdist desc, c_count desc")


def _q13_oracle(eng13):
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    o = od[od.flag != 3]
    j = cu.merge(o, on="ck", how="left")
    per = j.groupby("ck").ok.count().reset_index(name="c_count")
    g = per.groupby("c_count").size().reset_index(name="custdist")
    return g.sort_values(["custdist", "c_count"],
                         ascending=[False, False], kind="stable")


def test_eager_agg_count_left_join(eng13):
    before = GLOBAL.get("bounds/eager_agg_rewrites")
    got = eng13.query(Q13_SHAPE)
    assert GLOBAL.get("bounds/eager_agg_rewrites") > before
    want = _q13_oracle(eng13).reset_index(drop=True)
    assert len(got) == len(want)
    assert (got["c_count"].to_numpy().astype(np.int64)
            == want["c_count"].to_numpy().astype(np.int64)).all()
    assert (got["custdist"].to_numpy().astype(np.int64)
            == want["custdist"].to_numpy().astype(np.int64)).all()


def test_eager_agg_inner_stays_fused(eng13):
    # the rewritten inner query takes the fused path — the expanding
    # duplicate-key probe (portioned-path cliff) no longer exists
    eng13.query("select cust.ck as ck, count(ords.ok) as c_count "
                "from cust left join ords on cust.ck = ords.ck "
                "group by cust.ck")
    assert eng13.executor.last_path == "fused"


def test_eager_agg_sum_min_max(eng13):
    got = eng13.query(
        "select seg, sum(ords.amt) as s, min(ords.amt) as mn, "
        "max(ords.amt) as mx from cust left join ords "
        "on cust.ck = ords.ck group by seg order by seg")
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    j = cu.merge(od, on="ck", how="left")
    want = (j.groupby("seg", as_index=False)
            .agg(s=("amt", "sum"), mn=("amt", "min"), mx=("amt", "max"))
            .sort_values("seg").reset_index(drop=True))
    np.testing.assert_allclose(got["s"].to_numpy(), want["s"].to_numpy(),
                               rtol=1e-9)
    np.testing.assert_allclose(got["mn"].to_numpy(), want["mn"].to_numpy())
    np.testing.assert_allclose(got["mx"].to_numpy(), want["mx"].to_numpy())


def test_eager_agg_guard_payload_use(eng13):
    # selecting a payload column OUTSIDE an aggregate voids the rewrite
    # (the expanding join must survive) — results stay correct
    before = GLOBAL.get("bounds/eager_agg_rewrites")
    got = eng13.query("select ords.flag as fl, count(ords.ok) as c "
                      "from cust left join ords on cust.ck = ords.ck "
                      "group by ords.flag order by fl")
    assert GLOBAL.get("bounds/eager_agg_rewrites") == before
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    j = cu.merge(od, on="ck", how="left")
    want = (j.groupby("flag", dropna=False).ok.count()
            .reset_index(name="c"))
    assert len(got) == len(want)


def test_eager_agg_guard_probe_side_aggregates(eng13):
    # count(*) / sum(probe.col) see k copies of each matched probe row
    # in the expanding join — a rewrite that makes the probe
    # row-preserving would silently lose the duplication factor, so the
    # spec must disqualify (the live bug the medium review caught)
    before = GLOBAL.get("bounds/eager_agg_rewrites")
    got = eng13.query(
        "select cust.ck as ck, count(*) as n, count(ords.ok) as c, "
        "sum(seg) as sp from cust left join ords on cust.ck = ords.ck "
        "group by cust.ck order by ck")
    assert GLOBAL.get("bounds/eager_agg_rewrites") == before
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    j = cu.merge(od, on="ck", how="left")
    want = (j.groupby("ck").agg(n=("ck", "size"), c=("ok", "count"),
                                sp=("seg", "sum")).reset_index()
            .sort_values("ck").reset_index(drop=True))
    for col in ("n", "c", "sp"):
        assert (got[col].to_numpy().astype(np.int64)
                == want[col].to_numpy().astype(np.int64)).all(), col


def test_eager_agg_probe_minmax_still_rewrites(eng13):
    # min/max of a probe column is multiplicity-INSENSITIVE (duplicates
    # of the same probe row cannot change a min/max) — the rewrite may
    # keep firing around it
    before = GLOBAL.get("bounds/eager_agg_rewrites")
    got = eng13.query(
        "select cust.ck as ck, count(ords.ok) as c, max(seg) as ms "
        "from cust left join ords on cust.ck = ords.ck "
        "group by cust.ck order by ck")
    assert GLOBAL.get("bounds/eager_agg_rewrites") > before
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    j = cu.merge(od, on="ck", how="left")
    want = (j.groupby("ck").agg(c=("ok", "count"), ms=("seg", "max"))
            .reset_index().sort_values("ck").reset_index(drop=True))
    for col in ("c", "ms"):
        assert (got[col].to_numpy().astype(np.int64)
                == want[col].to_numpy().astype(np.int64)).all(), col


def test_eager_agg_count_keeps_uint64(eng13):
    # the rewritten count merges as sum(coalesce(...)) — the outer cast
    # must restore count's uint64 result type so the rewrite cannot flip
    # the output schema, only the plan shape
    got = eng13.query(
        "select cust.ck as ck, count(ords.ok) as c from cust "
        "left join ords on cust.ck = ords.ck group by cust.ck "
        "order by ck")
    assert got["c"].dtype == np.uint64
    cu, od = eng13.frames["cust"], eng13.frames["ords"]
    want = (cu.merge(od, on="ck", how="left").groupby("ck")["ok"].count()
            .reset_index(name="c").sort_values("ck"))
    assert (got["ck"].to_numpy() == want["ck"].to_numpy()).all()
    assert (got["c"].to_numpy() == want["c"].to_numpy()).all()


# -- bound-shaped plans against pandas --------------------------------------


def _matches(got, want):
    """Exact for integers and NULLs, 1e-9 relative for float sums."""
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for col in got.columns:
        xa, xb = got[col].to_numpy(), want[col].to_numpy()
        na, nb = pd.isna(xa), pd.isna(xb)
        assert (na == nb).all(), col
        if xb.dtype.kind == "f":
            np.testing.assert_allclose(xa[~na].astype(np.float64),
                                       xb[~nb], rtol=1e-9, err_msg=col)
        else:
            assert (xa[~na] == xb[~nb]).all(), col


def _joined(frames):
    return frames["f"].merge(frames["d"], on="k")


def _want_carried(frames):
    return (_joined(frames).groupby(["k", "grp", "a"])
            .agg(s=("val", "sum"), c=("val", "size")).reset_index()
            .sort_values("k").reset_index(drop=True))


def _want_one_giant_group(frames):
    return (_joined(frames).groupby("b")
            .agg(c=("val", "size"), s=("val", "sum")).reset_index()
            .sort_values("b").reset_index(drop=True))


def _want_empty(frames):
    j = _joined(frames)
    return (j[j.val > 1e12].groupby("k").agg(c=("val", "size"))
            .reset_index())


def _want_eager(frames):
    j = frames["d"][["k"]].merge(frames["f"], on="k", how="left")
    return (j.groupby("k").agg(c=("id", "count")).reset_index()
            .sort_values("k").head(40).reset_index(drop=True))


DIFF_QUERIES = [
    # carried keys + join bound (skewed: most rows in few groups)
    ("select f.k as k, grp, a, sum(val) as s, count(*) as c from f "
     "join d on f.k = d.k group by f.k, grp, a order by k",
     _want_carried),
    # tile-boundary shape: one giant group (all rows through one bucket)
    ("select b, count(*) as c, sum(val) as s from f "
     "join d on f.k = d.k group by b order by b", _want_one_giant_group),
    # 0-row: nothing survives the filter
    ("select f.k as k, count(*) as c from f join d on f.k = d.k "
     "where val > 1e12 group by f.k order by k", _want_empty),
    # eager-agg shape over the same store (LEFT JOIN d's dup-free key is
    # the DEGENERATE eager case)
    ("select d.k as k, count(f.id) as c from d left join f "
     "on d.k = f.k group by d.k order by k limit 40", _want_eager),
]


@pytest.mark.parametrize("qi", range(len(DIFF_QUERIES)))
def test_bounded_plans_match_pandas(eng, qi):
    sql, want = DIFF_QUERIES[qi]
    _matches(eng.query(sql), want(eng.frames))


# -- q8/q10/q18 regression: the fallback class is retired -------------------


@pytest.fixture(scope="module")
def tpch_eng():
    from ydb_tpu.bench.tpch_gen import load_tpch
    e = QueryEngine(block_rows=1 << 13)
    e.tpch_data = load_tpch(e.catalog, sf=0.002, shards=2,
                            portion_rows=1 << 13)
    return e


@pytest.mark.parametrize("name", ["q8", "q10", "q18"])
def test_fallback_class_runs_fused(tpch_eng, name):
    from tests.tpch_util import QUERIES, assert_frames_match, oracle
    got = tpch_eng.query(QUERIES[name])
    assert tpch_eng.executor.last_path == "fused", name
    want = oracle(name, tpch_eng.tpch_data)
    want.columns = list(got.columns)
    assert_frames_match(got, want, ordered=True)


def test_q10_plan_carries_finite_bounds(tpch_eng):
    from tests.tpch_util import QUERIES
    txt = _explain(tpch_eng, QUERIES["q10"])
    assert "-- bounds:" in txt
    assert "pipeline ≤" in txt


# -- the static inputs downstream consumers are declared on ----------------


def test_dq_channel_out_bound_stamped_on_limit_pushdown():
    # `Channel.out_bound` is ROADMAP item 1's declared static input for
    # planned redistribution (the current materialized-frame ICI
    # exchange deliberately ignores it) — pin that the lowering keeps
    # stamping it, or item 1 starts from nothing
    from ydb_tpu.dq.lower import DqTopology, lower_select
    from ydb_tpu.sql.parser import parse

    g = lower_select(
        parse("select id, v from t order by v limit 7 offset 2"),
        DqTopology(n_workers=2, replicated=set(),
                   key_columns={"t": ["id"]}),
        lambda t: ["id", "k", "v"])
    (ch,) = g.channels.values()
    assert ch.out_bound == 9           # limit + offset per producer


def test_build_cache_accounts_fd_block():
    # the retained FD-verification host block must ride the BuildCache
    # byte budget — unaccounted pins would grow host RSS past it
    from ydb_tpu.core.block import HostBlock
    from ydb_tpu.ops import join as J
    from ydb_tpu.query.build_cache import _entry_bytes

    block = HostBlock.from_pandas(pd.DataFrame({
        "k": np.arange(64, dtype=np.int64),
        "grp": np.arange(64, dtype=np.int64) % 5}))
    bt = J.build(block, "k", ["grp"], keep_fd=True)
    assert bt.fd_block is not None     # unique-keyed build, lattice on
    # a join-only consumer (no multi-key group-by) never pins one
    assert J.build(block, "k", ["grp"]).fd_block is None
    fd_bytes = sum(int(cd.data.nbytes)
                   for cd in bt.fd_block.columns.values())
    assert fd_bytes > 0
    lean = _entry_bytes(J.BuildTable(
        bt.keys_sorted, bt.n, bt.payload, bt.payload_valid, bt.schema,
        bt.dictionaries, bt.unique, bt.lut, bt.lut_base))
    assert _entry_bytes(bt) == lean + fd_bytes
