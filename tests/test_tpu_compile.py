"""The TPU compiler's verdict on the main path's programs, without a chip.

libtpu compiles for a chip that is DESCRIBED, not attached
(`topologies.get_topology_desc`), so what the v5e compiler refuses — or
needs minutes for — fails here, in tier-1, instead of in the first chip
run. These are the step-1 rehearsal compiles of PR 22 that take seconds:
the programs `chip_smoke.py` runs are built by the same code at SF1
shapes (`scripts/tpu_rehearse.py` re-does the full-size rehearsal).

Nothing runs: there is no device to hold an array, so every argument is a
`jax.ShapeDtypeStruct` placed on the described device(s). A compile that
passes is not a chip run and is never reported as one.

The topology is described inside a module-scoped fixture — never while a
module is imported: only one process may hold libtpu, and under xdist
every worker imports every test file. All of these tests live in this one
file for the same reason, and start no child process.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import __graft_entry__ as graft
from ydb_tpu.core import dtypes as dt
from ydb_tpu.ops import ir
from ydb_tpu.ops.device import bucket_capacity
from ydb_tpu.ops.xla_exec import ProgramCache

SF1_LINEITEM_ROWS = 6_000_409            # BENCH_r18.json suites.sf1
# the ONLY wall-clock check of this file, and a generous one (the runner
# shares its cores with five other xdist workers): each sort the TPU
# compiler builds costs 15-40 s (PERF.md round 22) and these programs
# hold one to three; before the repairs of PR 22 the same programs took
# 3-10 minutes or did not finish, which is the cliff this catches
COMPILE_BUDGET_S = 240.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run warns and
    recompiles): keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, *args):
    """(compiled, seconds) of `fn` lowered for the shardings in `args`."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 16 * 2 ** 30, "program does not fit one v5e chip's 16 GB"
    assert took < COMPILE_BUDGET_S, f"TPU compile took {took:.0f}s"
    return compiled, took


def _as_spec(x, sharding, shape=None):
    """The shape of a captured program argument, placed on `sharding`
    (`shape`: rebuilt at another size); a non-array passes through."""
    if not hasattr(x, "shape"):
        return x
    return jax.ShapeDtypeStruct(
        x.shape if shape is None else shape, x.dtype, sharding=sharding,
        weak_type=bool(getattr(x, "weak_type", False)))


def _block_args(sig, cap, sharding):
    """(arrays, valids, length, params) shapes of a ProgramCache program."""
    arrays = {n: jax.ShapeDtypeStruct((cap,), dt.DType(dt.Kind(k), nu).np,
                                      sharding=sharding)
              for (n, k, nu) in sig}
    valids = {n: jax.ShapeDtypeStruct((cap,), np.bool_, sharding=sharding)
              for (n, _k, nu) in sig if nu}
    length = jax.ShapeDtypeStruct((), np.int32, sharding=sharding)
    return arrays, valids, length, {}


def test_q1_partial_at_sf1_capacity(one_chip, no_compile_cache):
    """`entry()`'s program — the Q1 filter → expressions → grouped
    aggregation — at the capacity bucket of SF1's whole lineitem."""
    partial, _final = graft._q1_programs()
    cap = bucket_capacity(SF1_LINEITEM_ROWS)
    assert cap >= SF1_LINEITEM_ROWS
    sig = (("l_shipdate", "date32", False), ("l_quantity", "float64", False),
           ("l_extendedprice", "float64", False),
           ("l_discount", "float64", False), ("l_tax", "float64", False),
           ("l_returnflag", "string", False),
           ("l_linestatus", "string", False))
    fn = ProgramCache._build(partial, sig, cap)
    _compile(fn, *_block_args(sig, cap, one_chip))


def test_groupby_tile_sorted(one_chip, no_compile_cache):
    """The unbounded-domain (sorted) group-by at one tile: a 64-bit key
    sort, the boundary compaction, float64 prefix sums."""
    from ydb_tpu.ops.xla_exec import groupby_tuning
    tile_rows = groupby_tuning()[0]
    prog = ir.Program().group_by(
        ["k"], [ir.Agg("s", "sum", "v"), ir.Agg("n", "count_all")])
    sig = (("k", "int64", False), ("v", "float64", False))
    fn = ProgramCache._build(prog, sig, tile_rows)
    _compile(fn, *_block_args(sig, tile_rows, one_chip))


def test_float64_prefix_sum_and_wide_sort_compile(one_chip,
                                                  no_compile_cache):
    """The two compile-time cliffs PR 22 found on the v5e, guarded where
    they were repaired: `jnp.cumsum` of float64 took the compiler 3-7
    minutes at any length, a six-key sort with a float64 key did not
    finish in ten. `xla_exec.cumsum` / `sort_total` are accepted inside
    `_compile`'s one budget (seconds to tens of seconds)."""
    from ydb_tpu.ops import xla_exec as X
    n = 1 << 20

    def vec(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    _compile(jax.jit(X.cumsum), vec(jnp.float64))

    def order_by(nulls, revenue, date, key):
        iota = jnp.arange(n, dtype=jnp.int32)
        return X.sort_total([nulls, -revenue, date, key], iota)[-1]

    _compile(jax.jit(order_by), vec(jnp.int32), vec(jnp.float64),
             vec(jnp.int32), vec(jnp.int64))


def test_compact_at_scan_capacity(one_chip, no_compile_cache):
    """`ir.Compact`: filter at scan capacity, compact to a small bound."""
    cap = bucket_capacity(SF1_LINEITEM_ROWS)
    prog = ir.Program()
    prog.filter(ir.call("le", ir.Col("d"),
                        ir.Const(10471, dt.DType(dt.Kind.DATE32, False))))
    prog.compact(1 << 18, bound=200_000)
    sig = (("d", "date32", False), ("k", "int64", False),
           ("v", "float64", True))
    fn = ProgramCache._build(prog, sig, cap)
    compiled, _s = _compile(fn, *_block_args(sig, cap, one_chip))
    out_d, _out_v, _len = compiled.out_info
    assert out_d["k"].shape == (1 << 18,)


@pytest.mark.parametrize("sources", [6, 64])
def test_keyless_tail_in_place_at_scan_capacity(one_chip, no_compile_cache,
                                                monkeypatch, sources):
    """Q6 as the fused path builds it since PR 34 — no `ir.Compact`, the
    keyless sum over the selection mask, `l_extendedprice` read in place —
    rebuilt at SF1's (6 x 1 Mi slots) and SF10's (64 x 1 Mi) scan
    capacity: the chip's compiler leaves no sort and no gather in it."""
    import re

    from tests.tpch_util import QUERIES
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.ops import fused as F
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine()
    load_tpch(eng.catalog, sf=0.002)
    built, filled = [], []
    real_build, real_fill = F.build_fused_fn, eng.executor._fused_fill

    def build(pipe, final, scan_cols, K, CAP, *a, **kw):
        built.append((pipe, final, scan_cols, a, kw))
        return real_build(pipe, final, scan_cols, K, CAP, *a, **kw)

    def fill(kind, key, builder, capture_args, **kw):
        filled.append(capture_args)
        return real_fill(kind, key, builder, capture_args, **kw)

    monkeypatch.setattr(F, "build_fused_fn", build)
    monkeypatch.setattr(eng.executor, "_fused_fill", fill)
    eng.query(QUERIES["q6"])
    assert eng.executor.last_path == "fused"
    (pipe, final, scan_cols, a, kw), = built
    assert kw["compact_prog"] is None
    K, CAP = sources, 1 << 20
    fn, _box = real_build(pipe, final, scan_cols, K, CAP, *a, **kw)

    def spec(x):                         # superblock (K, CAP), lengths (K,)
        return _as_spec(x, one_chip,
                        ((), (K,), (K, CAP))[getattr(x, "ndim", 0)])

    compiled, _s = _compile(fn, *jax.tree_util.tree_map(spec, filled[-1]))
    text = compiled.as_text()
    assert "jit_lineitem_g_" in text
    assert not re.search(r" (?:sort|gather|scatter)\(", text)


def test_batched_q6_at_sf10_capacity_is_what_admission_reserves(
        one_chip, no_compile_cache, monkeypatch):
    """The stacked Q6 program (`build_fused_batched_fn`: sixteen members
    over ONE shared superblock) rebuilt at SF10's scan capacity, 64
    sources x 1 Mi slots: the chip's compiler takes it, leaves no sort,
    gather or scatter in it, and what it holds (arguments + temporaries
    + outputs, the figure the lane admits a compiled shape by) lets TWO
    such batches stand side by side under the default admission budget
    where sixteen times the scan estimate let none; the bound the lane
    uses before the first compile lies above the compiler's figure."""
    import re

    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.ops import fused as F
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.query.admission import (
        batch_reservation_bytes, estimate_plan_bytes, stacked_body_width,
    )
    from ydb_tpu.storage.mvcc import MAX_SNAPSHOT
    from tests.tpch_util import QUERIES

    B = 16
    monkeypatch.setenv("YDB_TPU_BATCH_WINDOW", "50")
    monkeypatch.setenv("YDB_TPU_BATCH_MAX", str(B))
    eng = QueryEngine()
    load_tpch(eng.catalog, sf=0.002)
    built, filled = [], []
    real_build, real_fill = F.build_fused_batched_fn, eng.executor._fused_fill

    def build(pipe, final, scan_cols, K, CAP, *a, **kw):
        built.append((pipe, final, scan_cols, a, kw))
        return real_build(pipe, final, scan_cols, K, CAP, *a, **kw)

    def fill(kind, key, builder, capture_args, **kw):
        filled.append((kind, capture_args))
        return real_fill(kind, key, builder, capture_args, **kw)

    monkeypatch.setattr(F, "build_fused_batched_fn", build)
    monkeypatch.setattr(eng.executor, "_fused_fill", fill)
    eng.query(QUERIES["q6"])     # the first statement builds Bb = 2 .. 16
    pipe, final, scan_cols, a, kw = built[-1]
    assert a[-1] == B                        # axis_size
    kind, (arrays, valids, lengths, builds, params) = filled[-1]
    assert kind == "batched" and builds == []
    K, CAP = 64, 1 << 20
    fn, _box = real_build(pipe, final, scan_cols, K, CAP, *a, **kw)

    def as_specs(tree, shape=None):
        return jax.tree_util.tree_map(
            lambda x: _as_spec(x, one_chip, shape), tree)

    compiled, _s = _compile(fn, as_specs(arrays, (K, CAP)),
                            as_specs(valids, (K, CAP)),
                            as_specs(lengths, (K,)), [], as_specs(params))
    text = compiled.as_text()
    assert "jit_lineitem_gb_" in text
    assert not re.search(r" (?:sort|gather|scatter)\(", text)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    scan = K * CAP * 28                      # Q6's four columns, shared
    assert mem.argument_size_in_bytes == pytest.approx(scan, rel=0.01)
    # two batches side by side under the default 10 GiB, and the gate's
    # 6 GiB for one; B x the scan estimate passes both
    assert 2 * held <= eng.admission.budget
    assert held <= eng.executor.fused_scan_budget_bytes
    assert batch_reservation_bytes(scan, B) > eng.admission.budget
    # the bound before the first compile is above the compiler's figure
    plan = next(iter(eng._plan_cache.values()))[1]
    bound = scan + B * K * CAP * stacked_body_width(eng.catalog, plan)
    assert held <= bound
    assert estimate_plan_bytes(eng.catalog, plan, MAX_SNAPSHOT) > 0
    from ydb_tpu.utils import progstats
    progstats.reset_for_tests()      # no inventory row outlives its engine
    print(f"batched q6 K={K} B={B}: args {mem.argument_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} out {mem.output_size_in_bytes} "
          f"bound {bound} compile {_s:.1f}s")


def test_fused_join_program(one_chip, no_compile_cache, monkeypatch):
    """A fused join + group-by + sort + limit program of the q3 class, as
    `ops/fused.py` builds it: captured at the AOT seam in a small CPU
    run, re-lowered for the chip at the shapes it was built for."""
    from tests.tpch_util import QUERIES
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils import progstats

    captured = []
    real = progstats.capture

    def spy(kind, key, jit_fn, args, *a, **kw):
        captured.append((kind, jit_fn, args))
        return real(kind, key, jit_fn, args, *a, **kw)

    monkeypatch.setattr(progstats, "capture", spy)
    eng = QueryEngine()
    load_tpch(eng.catalog, sf=0.01)
    eng.query(QUERIES["q3"])
    assert eng.executor.last_path == "fused"
    fused = [(fn, args) for kind, fn, args in captured if kind == "fused"]
    assert fused, "q3 built no fused program"
    # the statement's main program is the one with the most inputs
    fn, args = max(fused,
                   key=lambda fa: len(jax.tree_util.tree_leaves(fa[1])))

    _compile(fn, *jax.tree_util.tree_map(
        lambda x: _as_spec(x, one_chip), args))


def test_four_device_shuffle_has_all_to_all(topo, no_compile_cache):
    """The mesh lane's partial-agg → hash shuffle → merge step over the
    four described chips: the exchange must lower to an all-to-all."""
    from jax.sharding import Mesh

    from ydb_tpu.parallel import DistributedAgg
    from ydb_tpu.parallel.shuffle import AXIS

    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    partial, final = graft._q1_programs()
    schema, _blocks = graft._lineitem_blocks(0.001, 4)
    dag = DistributedAgg(partial, final, schema, mesh)
    cap = 1 << 13
    fn, _holder = dag._build(cap, (), ())
    sh2 = NamedSharding(mesh, P(AXIS, None))
    arrays = {c.name: jax.ShapeDtypeStruct((4, cap), c.dtype.np,
                                           sharding=sh2) for c in schema}
    lengths = jax.ShapeDtypeStruct((4,), jnp.int32,
                                   sharding=NamedSharding(mesh, P(AXIS)))
    compiled, _s = _compile(fn, arrays, {}, lengths, {})
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
