"""Tests for broadcast join (searchsorted MapJoin) and device sort/top-k."""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from ydb_tpu.core import dtypes as dt
from ydb_tpu.core.block import HostBlock
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import join as mj
from ydb_tpu.ops.device import to_device, to_host
from ydb_tpu.ops.sort import sort_block
from ydb_tpu.ops.xla_exec import compress_block


def _dim_block(n=100):
    return HostBlock.from_pandas(pd.DataFrame({
        "pk": np.arange(n, dtype=np.int64) * 10,
        "name": [f"item{i}" for i in range(n)],
        "price": np.arange(n, dtype=np.float64) * 1.5,
    }))


def _fact_block(rng, n=5000, dim_n=100):
    keys = rng.integers(0, dim_n * 2, n) * 10  # half miss
    return HostBlock.from_pandas(pd.DataFrame({
        "fk": keys.astype(np.int64),
        "qty": rng.integers(1, 10, n).astype(np.int64),
    }))


def test_inner_join_matches_pandas(rng):
    dim, fact = _dim_block(), _fact_block(rng)
    table = mj.build(dim, "pk", ["name", "price"])
    assert table.unique
    out, sel = mj.probe(to_device(fact), table, "fk", kind="inner")
    res = to_host(compress_block(out, sel)).to_pandas()

    expect = fact.to_pandas().merge(
        dim.to_pandas(), left_on="fk", right_on="pk")[["fk", "qty", "name", "price"]]
    res_s = res.sort_values(["fk", "qty"]).reset_index(drop=True)
    exp_s = expect.sort_values(["fk", "qty"]).reset_index(drop=True)
    assert len(res_s) == len(exp_s)
    np.testing.assert_array_equal(res_s["fk"].to_numpy(), exp_s["fk"].to_numpy())
    np.testing.assert_allclose(
        res_s["price"].to_numpy(np.float64), exp_s["price"].to_numpy(np.float64))
    assert (res_s["name"] == exp_s["name"]).all()


def test_left_join_nulls(rng):
    dim, fact = _dim_block(), _fact_block(rng)
    table = mj.build(dim, "pk", ["price"])
    out, sel = mj.probe(to_device(fact), table, "fk", kind="left")
    res = to_host(compress_block(out, sel)).to_pandas()
    assert len(res) == fact.length
    missing = res["price"].isna()
    assert missing.any()
    assert (res.loc[missing, "fk"].to_numpy() >= 1000).all()


def test_semi_anti_join(rng):
    dim, fact = _dim_block(), _fact_block(rng)
    table = mj.build(dim, "pk", [])
    dfact = to_device(fact)
    _, sel_semi = mj.probe(dfact, table, "fk", kind="left_semi")
    _, sel_anti = mj.probe(dfact, table, "fk", kind="left_anti")
    n_semi = to_host(compress_block(dfact, sel_semi)).length
    n_anti = to_host(compress_block(dfact, sel_anti)).length
    assert n_semi + n_anti == fact.length
    assert n_semi == int((fact.columns["fk"].data < 1000).sum())


def test_sort_topk(rng):
    n = 3000
    b = HostBlock.from_pandas(pd.DataFrame({
        "x": rng.integers(0, 1000, n).astype(np.int64),
        "y": rng.normal(size=n),
    }))
    d = sort_block(to_device(b), [("x", False, False), ("y", True, False)], limit=50)
    res = to_host(d).to_pandas()
    exp = b.to_pandas().sort_values(["x", "y"], ascending=[False, True]).head(50)
    np.testing.assert_array_equal(res["x"].to_numpy(), exp["x"].to_numpy())
    np.testing.assert_allclose(res["y"].to_numpy(np.float64),
                               exp["y"].to_numpy(np.float64))


def test_sort_nulls_last(rng):
    b = HostBlock.from_pandas(pd.DataFrame({
        "x": [3.0, None, 1.0, 2.0, None],
    }))
    d = sort_block(to_device(b), [("x", True, False)])
    res = to_host(d).to_pandas()
    vals = res["x"].tolist()
    assert vals[:3] == [1.0, 2.0, 3.0]
    assert pd.isna(vals[3]) and pd.isna(vals[4])


def _dup_build_block(rng, n_keys=40, avg_dup=3):
    ks, names, prices = [], [], []
    i = 0
    for k in range(n_keys):
        for _ in range(int(rng.integers(0, avg_dup * 2 + 1))):  # 0..6 dups
            ks.append(k * 10)
            names.append(f"v{i}")
            prices.append(float(i) * 0.5)
            i += 1
    return HostBlock.from_pandas(pd.DataFrame({
        "pk": np.array(ks, dtype=np.int64),
        "name": names,
        "price": np.array(prices, dtype=np.float64),
    }))


def test_expand_inner_join_duplicates(rng):
    dim = _dup_build_block(rng)
    fact = _fact_block(rng, n=3000, dim_n=60)
    table = mj.build(dim, "pk", ["name", "price"])
    assert not table.unique
    out = mj.probe_expand(to_device(fact), table, "fk", kind="inner")
    res = to_host(out).to_pandas()
    expect = fact.to_pandas().merge(
        dim.to_pandas(), left_on="fk", right_on="pk")[
        ["fk", "qty", "name", "price"]]
    res_s = res.sort_values(["fk", "qty", "name"]).reset_index(drop=True)
    exp_s = expect.sort_values(["fk", "qty", "name"]).reset_index(drop=True)
    assert len(res_s) == len(exp_s)
    np.testing.assert_array_equal(res_s["fk"].to_numpy(),
                                  exp_s["fk"].to_numpy())
    np.testing.assert_allclose(res_s["price"].to_numpy(np.float64),
                               exp_s["price"].to_numpy(np.float64))
    assert (res_s["name"] == exp_s["name"]).all()


def test_expand_left_join_duplicates(rng):
    dim = _dup_build_block(rng)
    fact = _fact_block(rng, n=2000, dim_n=60)
    table = mj.build(dim, "pk", ["price"])
    out = mj.probe_expand(to_device(fact), table, "fk", kind="left")
    res = to_host(out).to_pandas()
    expect = fact.to_pandas().merge(
        dim.to_pandas()[["pk", "price"]], left_on="fk", right_on="pk",
        how="left")[["fk", "qty", "price"]]
    assert len(res) == len(expect)
    res_s = res.sort_values(["fk", "qty", "price"]).reset_index(drop=True)
    exp_s = expect.sort_values(["fk", "qty", "price"]).reset_index(drop=True)
    np.testing.assert_array_equal(res_s["fk"].to_numpy(),
                                  exp_s["fk"].to_numpy())
    got_p = res_s["price"].to_numpy(np.float64)
    want_p = exp_s["price"].to_numpy(np.float64)
    both_nan = np.isnan(got_p) & np.isnan(want_p)
    np.testing.assert_allclose(got_p[~both_nan], want_p[~both_nan])


def test_expand_join_null_probe_keys(rng):
    # NULL probe keys never match: dropped by inner, null-extended by left
    schema = Schema([Column("fk", dt.DType(dt.Kind.INT64, True)),
                     Column("qty", dt.DType(dt.Kind.INT64, False))])
    fk = np.array([0, 10, 10, 99], dtype=np.int64)
    valid = np.array([True, True, False, True])
    fact = HostBlock.from_arrays(
        schema, {"fk": fk, "qty": np.arange(4, dtype=np.int64)},
        valids={"fk": valid})
    dim = HostBlock.from_pandas(pd.DataFrame({
        "pk": np.array([10, 10], dtype=np.int64),
        "price": np.array([1.0, 2.0])}))
    table = mj.build(dim, "pk", ["price"])
    inner = to_host(mj.probe_expand(to_device(fact), table, "fk", "inner"))
    assert inner.length == 2 and list(inner.to_pandas().qty) == [1, 1]
    left = to_host(mj.probe_expand(to_device(fact), table, "fk", "left"))
    df = left.to_pandas().sort_values(["qty", "price"]).reset_index(drop=True)
    assert len(df) == 5  # rows 0,2,3 null-extended + two matches for row 1


# -- existence builds: a direct-address LUT whatever the density ----------

_Q18_SPAN = 1_500_000     # TPC-H SF1's orderkeys, 1..1 500 000


def _key_block(keys, valid=None, payload=False):
    cols = [Column("k", dt.DType(dt.Kind.INT64, valid is not None))]
    arrays = {"k": np.asarray(keys, dtype=np.int64)}
    if payload:
        cols.append(Column("p", dt.FLOAT64))
        arrays["p"] = arrays["k"] * 0.5
    return HostBlock.from_arrays(Schema(cols), arrays,
                                 valids={} if valid is None
                                 else {"k": np.asarray(valid)})


def _q18_keys(seed=18, n=6000):
    # the `having sum(l_quantity) > q` set: ~6k orderkeys over a 1.5M span
    rng = np.random.default_rng(seed)
    keys = rng.choice(np.arange(3, _Q18_SPAN), n - 2, replace=False)
    return np.concatenate([[1, _Q18_SPAN], keys])


@pytest.mark.parametrize("case,keys,existence,payload,lut_len", [
    # Q18's semi join: sparse (6k keys, 64 x 6k < span) yet one gather
    ("existence-sparse", "q18", True, False, 1 << 21),
    # the same keys carrying a payload keep the 64x density cap
    ("payload-sparse", "q18", False, True, None),
    # past the absolute budget no kind gets a LUT
    ("existence-past-budget", "wide", True, False, None),
    ("payload-past-budget", "wide", False, True, None),
])
def test_existence_build_lut_is_bounded_by_the_budget_alone(
        case, keys, existence, payload, lut_len):
    from ydb_tpu.utils.metrics import GLOBAL
    ks = _q18_keys() if keys == "q18" else \
        np.array([0, 7, mj._LUT_SPAN_BUDGET], np.int64)
    before = GLOBAL.snapshot()
    bt = mj.build(_key_block(ks, payload=payload), "k",
                  ["p"] if payload else [], existence=existence)
    after = GLOBAL.snapshot()
    delta = {n: after.get(n, 0) - before.get(n, 0)
             for n in ("join/lut_builds", "join/bsearch_builds",
                       "join/existence_lut_builds")}
    if lut_len is None:
        assert bt.lut is None
        assert delta == {"join/lut_builds": 0, "join/bsearch_builds": 1,
                         "join/existence_lut_builds": 0}
    else:
        assert bt.lut.shape[0] == lut_len and bt.lut_base == 1
        assert delta == {"join/lut_builds": 1, "join/bsearch_builds": 0,
                         "join/existence_lut_builds": 1}
    if keys == "q18":
        assert bt.keys_sorted.shape[0] == 8192  # the bsearch input stays


_EDGE_PROBES = np.array([
    0, -1, -5, np.iinfo(np.int64).min + 1,          # below lut_base, negative
    _Q18_SPAN + 1, _Q18_SPAN + 600_000, 1 << 21, 1 << 40,   # past the span
    np.iinfo(np.int64).max,                          # the padding sentinel
    1, _Q18_SPAN, 2], dtype=np.int64)               # both ends, a miss


@pytest.mark.parametrize("kind,not_in,build_null", [
    ("left_semi", False, False),
    ("left_anti", False, False),
    ("left_anti", True, False),         # NOT IN, no NULL in the set
    ("left_anti", True, True),          # NOT IN over a set holding NULL
    ("mark", False, False),
])
def test_lut_probe_equals_bsearch_probe(kind, not_in, build_null):
    """The fused probe through an existence build's LUT selects, marks and
    gathers what the binary search over the same build does, and what the
    SQL semantics say, for probe keys below lut_base, past the span,
    negative, NULL and inactive."""
    from ydb_tpu.ops import fused as F
    keys = _q18_keys()
    valid = None
    if build_null:
        keys, valid = np.append(keys, 777), np.append(
            np.ones(len(keys), bool), False)
    payload = kind == "mark"
    block = _key_block(keys, valid, payload=payload)
    pnames = ["p"] if payload else []
    lut_bt = mj.build(block, "k", pnames, existence=True)
    bs_bt = mj.build(block, "k", pnames, existence=False)
    assert lut_bt.lut is not None and bs_bt.lut is None
    for bt in (lut_bt, bs_bt):
        bt.anti_has_null = build_null      # what the executor's check sets

    rng = np.random.default_rng(37)
    probe = np.concatenate([_EDGE_PROBES, rng.choice(keys, 3000),
                            rng.integers(-10, _Q18_SPAN + 10, 5000)])
    pvalid = np.ones(len(probe), bool)
    pvalid[rng.choice(len(probe), 300, replace=False)] = False   # NULL
    pvalid[len(_EDGE_PROBES) - 1] = False
    active = np.ones(len(probe), bool)
    active[rng.choice(len(probe), 200, replace=False)] = False
    env = {"fk": (jnp.asarray(probe), jnp.asarray(pvalid))}
    meta = {"probe_key": "fk", "kind": kind, "src_names": tuple(pnames),
            "payload_names": tuple(pnames), "mark_col": "m",
            "not_in": not_in}

    def run(bt, bsearch):
        out_env, out_sel = jax.jit(
            lambda e, s, b: mj.probe_lut_traced(
                e, s, b, dict(meta, bsearch=bsearch)))(
            env, jnp.asarray(active), F.build_traced_inputs(bt))
        return np.asarray(out_sel), {
            n: tuple(np.asarray(a) for a in dv)
            for n, dv in out_env.items() if n != "fk"}

    sel_lut, cols_lut = run(lut_bt, False)
    sel_bs, cols_bs = run(bs_bt, True)
    np.testing.assert_array_equal(sel_lut, sel_bs)
    assert cols_lut.keys() == cols_bs.keys()
    for n in cols_lut:
        found = cols_lut["m"][0] if kind == "mark" else None
        for a, b in zip(cols_lut[n], cols_bs[n]):
            if n == "p":        # payload data is only defined where found
                a, b = np.where(found, a, 0), np.where(found, b, 0)
            np.testing.assert_array_equal(a, b)

    member = np.isin(probe, keys[:len(_q18_keys())]) & pvalid & active
    want = {"left_semi": member, "mark": active,
            "left_anti": ~member & active
            & (pvalid if not_in else True)
            & (not build_null)}[kind]
    np.testing.assert_array_equal(sel_lut, want)
    if kind == "mark":
        np.testing.assert_array_equal(cols_lut["m"][0], member)
        np.testing.assert_array_equal(
            np.where(member, cols_lut["p"][0], 0.0),
            np.where(member, probe * 0.5, 0.0))


# -- sort_total: the radix lowering equals the one wide lax.sort -----------


_F64_EDGES = np.array([
    1e39, -1e39, 1e100, 3e200, -2e60, 1e300, -1e300, 1e-40, -1e-40,
    1e-300, np.finfo(np.float64).max, -np.finfo(np.float64).max,
    np.finfo(np.float64).tiny, -np.finfo(np.float64).tiny,
    np.nextafter(np.finfo(np.float64).max, 0), np.nextafter(1e39, 0)])


def _sort_total_cases():
    rng = np.random.default_rng(7)
    n = 1 << 14
    f64 = rng.normal(size=n) * 10.0 ** rng.integers(-30, 30, size=n)
    f64[:50], f64[50:80], f64[80:100] = np.nan, 0.0, -0.0
    f64[100:110], f64[110:120] = np.inf, -np.inf
    f64[200:1200] = np.repeat(rng.normal(size=10), 100)      # long ties
    ulp = 1.0 + rng.integers(0, 4, size=n) * 2.0 ** -52     # last-bit keys
    # every binade of the double range (random bit patterns), the edges
    # of the float32 range and of the double range, and last-bit
    # neighbours far below float32's smallest number
    wide = rng.integers(0, 2 ** 64, size=n, dtype=np.uint64).view(np.float64)
    wide = wide.copy()
    wide[:len(_F64_EDGES)] = _F64_EDGES
    wide[100:1100] = 1e-30 * (1 + rng.integers(0, 4, 1000) * 2.0 ** -52)
    wide[1100:2100] = 3e200 * (1 + rng.integers(0, 4, 1000) * 2.0 ** -52)
    wide[2100:2200] = np.repeat(_F64_EDGES[:10], 10)
    # denormals: XLA reads them as zero in `=` and `<`, so they tie with
    # zero here (the comparison flushes them the same way)
    den = f64.copy()
    den[:3000] = rng.choice([0.0, -0.0, 5e-324, -5e-324, 1e-310, -1e-310,
                             2e-308], size=3000)
    i64 = rng.integers(-2 ** 62, 2 ** 62, size=n)
    i64[:100] = rng.integers(-3, 3, size=100)
    i64[100], i64[101] = np.iinfo(np.int64).max, np.iinfo(np.int64).min
    u64 = rng.integers(0, 2 ** 63, size=n).astype(np.uint64) * 2 + 1
    i32 = rng.integers(-5, 5, size=n).astype(np.int32)
    f32 = rng.normal(size=n).astype(np.float32)
    f32[:10], f32[10:20] = np.nan, -0.0
    u32 = rng.integers(0, 2 ** 32, size=n).astype(np.uint32)
    return {"f64": [f64], "f64_ulp": [ulp], "f64_desc": [-f64],
            "f64_wide": [wide], "f64_wide_desc": [-wide],
            "f64_denormal": [den], "f64_small_n": [wide[:300]],
            "i64": [i64], "i64_desc": [~i64], "u64": [u64], "f32": [f32],
            "u32": [u32], "i32": [i32], "mixed": [i32, f64, i64],
            "mixed_wide": [i32, wide, i64], "mixed32": [i32, i32 % 2, f32, u32],
            "mixed_small_n": [i32[:100], wide[:100], i64[:100]]}


def _flush_denormals(k):
    if k.dtype != np.float64:
        return k
    return np.where(np.abs(k) < np.finfo(np.float64).tiny, 0.0, k)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", list(_sort_total_cases()))
def test_sort_total_radix_equals_wide_sort(case, jit):
    """`sort_total` sorts word by word (what the TPU compiler can build
    in seconds); the permutation and the sorted keys must be exactly
    those of one stable `lax.sort` over every key — NaNs last and equal,
    -0 == +0, 64-bit extremes, last-bit doubles, and the whole double
    range: beyond float32's exponent range on both sides, ±DBL_MAX, the
    smallest normal. Denormal doubles tie with zero."""
    import jax
    import jax.numpy as jnp

    from ydb_tpu.ops import xla_exec as X

    raw = _sort_total_cases()[case]
    keys = [jnp.asarray(k) for k in raw]
    n = keys[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    fn = jax.jit(X.sort_total) if jit else X.sort_total
    got = fn(keys, iota)
    want_perm = np.asarray(jax.lax.sort(
        [jnp.asarray(_flush_denormals(k)) for k in raw] + [iota],
        num_keys=len(keys) + 1)[-1])
    assert len(got) == len(keys) + 1
    np.testing.assert_array_equal(np.asarray(got[-1]), want_perm)
    for g, k in zip(got, raw):
        np.testing.assert_array_equal(np.asarray(g), k[want_perm])


def test_sort_and_group_by_doubles_beyond_float32_through_sql():
    """GROUP BY and ORDER BY over a Double column whose values lie
    outside float32's exponent range, at a size past every small-sort
    shortcut, through the engine: the groups and the order are pandas'."""
    import pandas as pd

    from ydb_tpu.query import QueryEngine

    vals = np.array([1e39, 1e100, 3e200, -2e60, -1e39, 1e-40, 1e-300,
                     -3e-200, 1.5, np.nextafter(3e200, np.inf)])
    n = 20_000
    rng = np.random.default_rng(3)
    df = pd.DataFrame({"id": np.arange(n, dtype=np.int64),
                       "v": vals[rng.integers(0, len(vals), size=n)]})
    eng = QueryEngine()
    eng.execute("create table t (id Int64 not null, v Double, "
                "primary key (id)) with (store = column)")
    eng.catalog.table("t").bulk_upsert(df, eng._next_version())
    got = eng.query("select v, count(*) as n from t group by v order by v")
    want = (df.groupby("v").size().rename("n").reset_index()
            .sort_values("v").reset_index(drop=True))
    assert len(got) == len(vals)
    np.testing.assert_array_equal(got.v.to_numpy(), want.v.to_numpy())
    np.testing.assert_array_equal(got.n.to_numpy(), want.n.to_numpy())
    srt = eng.query("select id, v from t order by v desc, id")
    ref = df.sort_values(["v", "id"], ascending=[False, True])
    np.testing.assert_array_equal(srt.id.to_numpy(), ref.id.to_numpy())
    np.testing.assert_array_equal(srt.v.to_numpy(), ref.v.to_numpy())


@pytest.mark.parametrize("n", [1, 7, 512, 513, 5000, (1 << 18) + 3])
def test_blocked_cumsum_matches_numpy(n):
    """`xla_exec.cumsum` (the blocked float scan the TPU compiler can
    build) against numpy's sequential prefix sum; ints pass through."""
    import jax.numpy as jnp

    from ydb_tpu.ops import xla_exec as X

    rng = np.random.default_rng(n)
    x = rng.normal(size=n) * 10.0 ** rng.integers(-3, 6, size=n)
    got = np.asarray(X.cumsum(jnp.asarray(x)))
    assert got.dtype == np.float64 and got.shape == (n,)
    np.testing.assert_allclose(got, np.cumsum(x), rtol=1e-10,
                               atol=1e-6 * np.abs(x).max())
    ints = rng.integers(-9, 9, size=n)
    np.testing.assert_array_equal(
        np.asarray(X.cumsum(jnp.asarray(ints))), np.cumsum(ints))


def test_q18_existence_luts_count_once_a_set_and_share_one_program():
    """Q18's semi join at every quantity of the join cell (248..252) gets
    an existence LUT, one build a literal set, and every set runs the one
    program its shape compiled: the LUT is sized by the key span, which
    the literal does not move."""
    from tests.tpch_util import QUERIES
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils.metrics import GLOBAL

    eng = QueryEngine(block_rows=1 << 16)
    data = load_tpch(eng.catalog, sf=0.01)
    li = pd.DataFrame(data.tables["lineitem"])
    qty = li.groupby("l_orderkey").l_quantity.sum()

    def count(name):
        return GLOBAL.snapshot().get(name, 0)

    def run(q):
        got = eng.query(QUERIES["q18"].replace("> 250", f"> {q}"))
        assert eng.executor.last_path == "fused"
        want = qty[qty > q]
        assert 0 < len(want) * 64 < want.index.max() - want.index.min()
        assert sorted(got.o_orderkey) == sorted(want.index)
        np.testing.assert_array_equal(
            got.set_index("o_orderkey").total_qty.loc[want.index], want)

    before = count("join/existence_lut_builds")
    run(248)
    # the shape's first statement and its compile-ahead thunk may both
    # miss the build cache (PERF.md section 7: they upload twice too)
    assert count("join/existence_lut_builds") - before in (1, 2)
    run(248)
    programs = count("prog/registered")
    for q in (249, 250, 251, 252):
        before = count("join/existence_lut_builds")
        run(q)
        assert count("join/existence_lut_builds") - before == 1
    assert count("prog/registered") == programs
    plan = "\n".join(eng.query(
        "explain analyze " + QUERIES["q18"])["plan"])
    assert "probe=lut,lut lut_span=16384,16384" in plan
