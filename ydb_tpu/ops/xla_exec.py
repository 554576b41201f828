"""XLA lowering of SSA programs — the TPU data plane.

Each (program, input-signature, capacity-bucket) pair compiles once to a
single fused XLA computation via ``jax.jit`` and is cached — the analog of
the reference's MiniKQL pattern cache (compile-once, run-per-block,
`ydb/library/yql/minikql/computation/mkql_computation_pattern_cache.h:56`)
with XLA playing the role of the LLVM codegen path
(`ydb/library/yql/minikql/codegen/`).

Design constraints honored for the TPU:
  * static shapes only — blocks are padded to power-of-two capacity
    buckets; the true row count rides as a traced scalar and every
    reduction masks by ``iota < length``;
  * no data-dependent control flow — filters keep selection masks
    (`TColumnFilter` semantics) instead of gathering;
  * GroupBy avoids scatter ops: global aggregates are plain masked
    reductions; bounded key domains use a chunked one-hot 2-D reduction
    (an MXU/VPU-friendly "aggregation as reduction over buckets");
    unbounded domains sort (keys + row-id only — wide multi-operand
    sorts explode XLA compile time) and aggregate with cumulative-sum
    differences at segment boundaries;
  * f64 accumulation for SQL sum semantics (TPU emulates f64; precision
    verified against the numpy oracle in tests).

Design note (see PERF.md): the operator designs here (and the whole-query
fusion in `ydb_tpu/ops/fused.py`) keep a query at one dispatch with zero
scatters in the steady state. That rule was calibrated in July 2026 on an
installation that no longer exists (a large fixed latency per dispatch
after the first readout, a surcharge per *scatter* op); neither cost is
measured on the current chip yet. What IS measured there (PERF.md round
22) is the TPU compiler's time: sorts and float64 prefix sums dominate it,
hence `sort_total` and `cumsum` below. And (PERF.md round 27) that a
scatter is priced by its updates, 67 ns each for a float64 column, a
gather by its indices, 9-12 ns each a 32-bit stream, and a one-word
6.29 M-row sort at about 10 ms: so `ir.Compact` (`compact_env`) sorts the
live positions once and gathers each column at the bound, and writes
nothing of scan width per column. And (PERF.md round 34) that a masked
reduction over the scan costs the same rows whether 2 % or 100 % of them
are live and less than that sort alone: so a keyless aggregate plans no
Compact in front of it (`reads_in_place`; TPC-H Q6 keeps its mask and
sums where the scan left the rows).
"""

from __future__ import annotations

import os
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.dtypes import DType, Kind
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops.device import DeviceBlock, bucket_capacity, to_device, to_host
from ydb_tpu.ops.kernels import KERNELS


# --------------------------------------------------------------------------
# traced helpers
# --------------------------------------------------------------------------


def _sort_operand(x):
    """A lax.sort-comparable operand for a key column, in its natural domain.

    No bitcast tricks: the TPU x64 emulation pass cannot rewrite
    f64<->s64 bitcasts, and ``lax.sort`` already provides a total order for
    float and unsigned operands natively."""
    if x.dtype in (jnp.float64, jnp.float32, jnp.uint64):
        return x
    if x.dtype in (jnp.bool_, jnp.int8, jnp.int16, jnp.int32):
        # one 32-bit comparator word, not two: the TPU compiler's time
        # for a sort grows faster than linearly in the words compared
        return x.astype(jnp.int32)
    return x.astype(jnp.int64)


def _zero_like_operand(x):
    return jnp.zeros((), x.dtype)


def _f32_word(h):
    """float32 → int32 whose signed order is `lax.sort`'s float order:
    -0 == +0, every NaN equal and last."""
    h = jnp.where(h == 0, jnp.float32(0), h)
    h = jnp.where(jnp.isnan(h), jnp.float32(np.nan), h)
    b = jax.lax.bitcast_convert_type(h, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _u32_word(u):
    """uint32 → int32 with the same order (flip the top bit)."""
    return jax.lax.bitcast_convert_type(u ^ jnp.uint32(1 << 31), jnp.int32)


_F64_STEPS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _f64_words(x) -> list:
    """float64 → three int32 words in `lax.sort`'s float order (-0 == +0,
    every NaN equal and last), exact for every normal double; denormals
    tie with zero. |x| = y * 2**e with y in [1, 2), found by exact
    power-of-two scalings (a binary search over the binade); word 0 packs
    e with the top 20 mantissa bits, and the rest of y — an exact
    remainder in [0, 1) — splits into two float32 residuals, each
    rounding monotone. That holds the 32 bits a double has left, and
    both halves of the float32 pair the TPU emulates a float64 with.
    Comparisons, selects, exact multiplies, converts and 32-bit bitcasts
    only: the TPU's x64 pass refuses f64<->s64 bitcasts, which is what
    `jnp.frexp` is made of. Steps beyond the platform's exponent range
    compare false and pass the value through."""
    ax = jnp.abs(x)
    inf = ax == np.inf
    # denormals tie with zero BY CONSTRUCTION: XLA flushes them in some
    # ops and not in others (CPU eager vs. fused), so no arithmetic sees
    # them reliably; the engine's own `=`/`<` already read them as zero
    scaled = (ax >= np.finfo(np.float64).tiny) & (ax > 0) & ~inf
    y = jnp.where(scaled, ax, 1.0)
    e = jnp.zeros(x.shape, jnp.int32)
    for k in _F64_STEPS:                  # y >= 2 comes down into [1, 2)
        big = y >= 2.0 ** k
        y = jnp.where(big, y * 2.0 ** -k, y)
        e = jnp.where(big, e + k, e)
    for k in _F64_STEPS:                  # y < 1 comes up into [1, 2)
        small = y < 2.0 ** (1 - k)
        y = jnp.where(small, y * 2.0 ** k, y)
        e = jnp.where(small, e - k, e)
    t = (y - 1.0) * 2.0 ** 20
    top = t.astype(jnp.int32)             # any monotone integer part will
    rest = t - top.astype(x.dtype)        # do: the remainder is exact
    mid = rest.astype(jnp.float32)
    low = (rest - mid.astype(x.dtype)).astype(jnp.float32)
    # 0 → 0; finite → (binade + 1023) * 2**20 + top, under inf's 2047 * 2**20
    w0 = jnp.where(scaled, ((e + 1023) << 20) + top,
                   jnp.where(inf, 2047 << 20, 0))
    neg = x < 0
    w0 = jnp.where(neg, -w0, w0)
    w0 = jnp.where(jnp.isnan(x), np.iinfo(np.int32).max, w0)
    return [w0, _f32_word(jnp.where(neg, -mid, mid)),
            _f32_word(jnp.where(neg, -low, low))]


def _key_words(x) -> list:
    """A sort key as int32 words, most significant first, whose
    lexicographic signed order is the key's own order. 64-bit integers
    split arithmetically (the TPU's x64 pass rewrites shifts, not
    bitcasts); a float64 by `_f64_words`."""
    d = x.dtype
    if d == jnp.float32:
        return [_f32_word(x)]
    if d == jnp.float64:
        return _f64_words(x)
    if d == jnp.uint32:
        return [_u32_word(x)]
    if d == jnp.uint64:
        return [_u32_word((x >> jnp.uint64(32)).astype(jnp.uint32)),
                _u32_word(x.astype(jnp.uint32))]
    if d == jnp.int64:
        return [(x >> jnp.int64(32)).astype(jnp.int32),
                _u32_word(x.astype(jnp.uint32))]
    return [x.astype(jnp.int32)]


def sort_total(keys: list, iota):
    """Sort rows by `keys` (most significant first) with `iota` as the
    LAST key; returns the sorted keys and, last, the permutation.

    The TPU compiler's time for one wide `lax.sort` explodes with the
    words its comparator reads: ~4 s for one int32 word at 1 M rows, 72 s
    for (i32, i64, i32), 168 s for six int32 words, minutes for anything
    holding a float64, and a stable sort costs a word more (PERF.md round
    22). So the sort runs as a least-significant-word-first radix at
    every size: ONE unstable two-word sort inside a `fori_loop` over the
    keys' int32 words — compiled once whatever the number and width of
    the keys, and no two rows ever compare equal. The permutation is the
    wide stable sort's, denormal doubles aside (`_f64_words`)."""
    keys = list(keys)
    words = [w for k in keys for w in _key_words(k)]
    if len(words) == 1:
        perm = jax.lax.sort([words[0], iota], num_keys=2,
                            is_stable=False)[1]
    else:
        stack = jnp.stack(words[::-1])       # least significant first

        def one_word(i, perm):
            # rows in their current order, keyed by word i; the position
            # as second key keeps the order the earlier words gave
            return jax.lax.sort((stack[i][perm], iota, perm), num_keys=2,
                                is_stable=False)[2]

        perm = jax.lax.fori_loop(0, len(words), one_word, iota)
    return [k[perm] for k in keys] + [perm]


def stable_argsort(keys):
    """`jnp.argsort(keys)` (stable) as a two-key total-order sort."""
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return sort_total([keys], iota)[1]


_CUMSUM_BLOCK = 512


def cumsum(x):
    """Inclusive prefix sum of a 1-D array. Integers take `jnp.cumsum`.
    A float64 `jnp.cumsum` lowers on the TPU to a reduce-window whose
    emulated-f64 body the compiler needs 3-7 MINUTES for at any length
    (PERF.md round 22); floats therefore scan in blocks: shifted adds
    inside rows of `_CUMSUM_BLOCK`, the same scan over the row totals,
    one broadcast add. log2(block) static adds per level, compiles in
    about a second, one lowering on every platform."""
    n = x.shape[0]
    if n == 0 or not np.issubdtype(np.dtype(x.dtype), np.floating):
        return jnp.cumsum(x)
    width = min(n, _CUMSUM_BLOCK)
    rows = -(-n // width)
    m = jnp.pad(x, (0, rows * width - n)).reshape(rows, width)
    k = 1
    while k < width:
        m = m + jnp.pad(m[:, :-k], ((0, 0), (k, 0)))
        k *= 2
    if rows > 1:
        tot = m[:, -1]
        m = m + (cumsum(tot) - tot)[:, None]
    return m.reshape(-1)[:n]


def _eval(expr, env, params, cap):
    if isinstance(expr, ir.Col):
        return env[expr.name]
    if isinstance(expr, ir.Const):
        return jnp.full((cap,), expr.value, dtype=expr.dtype.np), None
    if isinstance(expr, ir.Param):
        val = params[expr.name]
        if expr.is_array:
            return val, None
        return jnp.full((cap,), val, dtype=expr.dtype.np), None
    if isinstance(expr, ir.Call):
        k = KERNELS[expr.op]
        args = [_eval(a, env, params, cap) for a in expr.args]
        extra = expr.extra_dict()
        if k.null_mode == "custom":
            return k.impl_nv(jnp, args, extra)
        data = k.impl(jnp, [a[0] for a in args], extra)
        valid = None
        for _, v in args:
            if v is not None:
                valid = v if valid is None else (valid & v)
        return data, valid
    raise TypeError(f"bad expr {expr!r}")


_F64_MIN, _F64_MAX = -np.inf, np.inf


def _sentinel(dtype, for_min: bool):
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf if for_min else -np.inf, dtype=dtype)
    info = np.iinfo(dtype)
    return np.array(info.max if for_min else info.min, dtype=dtype)


_SMALL_DOMAIN_BUCKETS = 1 << 9     # one-hot 2-D reduction path bound
_CHUNK_W = 64                      # buckets per one-hot chunk
# a small-domain group-by of at most this many buckets (one one-hot chunk)
# counts as read in place (`reads_in_place`): it costs buckets x rows over
# the masked scan, a Compact in front of it a sort of every position and a
# gather a column before buckets x bound. Priced on the v5e by
# `scripts/compact_micro.py --sections tail` (PERF.md round 34; Q1's eleven
# aggregates, 10 % of 6 Mi / 64 Mi slots live): in place 6.8 / 70.5 ms at
# 12 buckets, 16.7 / 183.8 at 64, 47.2 / 541.8 at 192, 123.3 / 1 437 at
# 512; behind the Compact 78.8-95.8 / 2 472-2 685: they cross near 375
# buckets at 6 Mi and not under 512 at 64 Mi. At 64 buckets in place is
# also under the cheapest Compact measured (Q6's: 1.8 % live, two columns,
# 18.7 / 314.8 ms), so it wins whatever the filter keeps.
_INPLACE_BUCKETS = _CHUNK_W
_SCATTER_MAX_BUCKETS = 1 << 16    # medium-domain single-scatter path bound
# per-dtype batched (multi-column 2-D) gathers are emitted only while one
# op reads at most this many rows; above it each column gathers alone
# (byte-identical results). An (m, rows) gather past ~4M is the shape the
# platform's compiler wedged on (PERF.md round-5).
_GATHER_BATCH_ROWS = 1 << 22


# --------------------------------------------------------------------------
# sorted group-by tuning + trace-time instrumentation
# --------------------------------------------------------------------------


def groupby_tuning() -> tuple:  # lint: tuning-provider
    """(tile_rows, late_mat) resolved from the environment.

    * YDB_TPU_GROUPBY_TILE_ROWS — value-column gathers inside the sorted
      group-by split into tiles of at most this many rows (default 4M:
      the largest size at which 2-D gathers compile on the platform's
      remote TPU compiler — PERF.md round-5/8; tiny values force many
      tiles for tests);

    * YDB_TPU_LATE_MAT — the late-materialization lever
      (`late_mat_enabled`): fused traces thread row-id vectors instead
      of payload columns and may carry a bound-sized `ir.Compact`;
      riding here keys every compiled program on the lever, so a flip
      recompiles instead of serving a deferral-shaped trace.

    The tuple is a component of every compiled-program cache key
    (ProgramCache, fused/tile/finalize/dist-agg keys), so flipping a knob
    recompiles instead of serving a trace built under other settings."""
    tile_rows = 1 << 22
    try:
        tile_rows = int(os.environ.get("YDB_TPU_GROUPBY_TILE_ROWS", "")
                        or tile_rows)
    except ValueError:
        pass
    return (max(tile_rows, 8), late_mat_enabled())


def late_mat_enabled() -> bool:  # lint: tuning-provider
    """YDB_TPU_LATE_MAT — default ON. The late-materialization lever:
    the fused path carries compact row-id vectors instead of payload
    columns through the byte-heavy middle of a plan (probe gathers
    defer to their first reference or to a bound-sized tail gather) and
    compacts intermediates to ladder-quantized bounds (`ir.Compact`).
    `=0` restores the eager-gather path byte-equal (the A/B lever for
    `scripts/latemat_gate.py`); it rides every affected compiled-program
    cache key via `groupby_tuning`."""
    return os.environ.get("YDB_TPU_LATE_MAT", "") not in ("0",)


class _TraceStats(threading.local):
    """Per-thread accumulator of trace-time group-by/sort op counts —
    the engine snapshots it per statement into QueryStats (EXPLAIN
    ANALYZE); the same increments also land on the process /counters
    registry under groupby/* and sort/*. Counts accrue at TRACE time:
    a compile-cache hit re-runs no tracing, so deltas are only visible
    for freshly compiled shapes (exactly what the CI gate wants)."""

    def __init__(self):
        self.stats: dict = {}


_TRACE = _TraceStats()


def groupby_trace_reset() -> None:
    _TRACE.stats = {}


def groupby_trace_snapshot() -> dict:
    return dict(_TRACE.stats)


def groupby_trace_mark() -> dict:
    """Opaque marker for a delta window (`groupby_trace_delta`). The
    engine brackets each statement with mark/delta instead of
    reset/snapshot: the thread-local is never cleared mid-statement, so
    a NESTED statement on the same thread (the DQ router's merge stage
    re-enters `engine.query`) cannot wipe the outer statement's window —
    its traces simply fold into the outer delta. The `*_max` watermarks
    restart with the window (the delta puts the earlier ones back), so
    a window reports the widest shape IT traced, not only one wider
    than every earlier statement's on this thread."""
    st = _TRACE.stats
    mark = dict(st)
    for k in [k for k in st if k.endswith("_max")]:
        del st[k]
    return mark


def groupby_trace_fold(delta: dict) -> None:
    """Fold a trace delta captured on ANOTHER thread into this thread's
    window. The compile-ahead lane builds (traces) fused programs on a
    background worker, so the build-time gauges land in that thread's
    accumulator; the statement that consumes the warmed entry folds the
    parked delta here so its EXPLAIN ANALYZE / QueryStats window reports
    the build it triggered — without this, a warmed statement looks like
    it traced nothing."""
    st = _TRACE.stats
    for k, v in delta.items():
        if k.endswith("_max"):
            if v > st.get(k, -1):
                st[k] = v
        else:
            st[k] = st.get(k, 0) + v


def groupby_trace_delta(mark: dict) -> dict:
    """Trace activity since `mark`: counters subtract; `*_max`
    watermarks report the highest value set inside the window (a
    statement that traced nothing yields {}), then take back the
    window's enclosing value where that is higher."""
    st = _TRACE.stats
    out = {}
    for k, v in st.items():
        if k.endswith("_max"):
            out[k] = v
        else:
            d = v - mark.get(k, 0)
            if d:
                out[k] = d
    for k, v in mark.items():
        if k.endswith("_max") and v > st.get(k, -1):
            st[k] = v
    return out


def _t_inc(name: str, by: int = 1, ns: str = "groupby") -> None:
    from ydb_tpu.utils.metrics import GLOBAL
    _TRACE.stats[name] = _TRACE.stats.get(name, 0) + by
    # lint: allow-counters(groupby/* + sort/* trace names, all registered)
    GLOBAL.inc(f"{ns}/{name}", by)


def _t_max(name: str, value: int, ns: str = "groupby") -> None:
    from ydb_tpu.utils.metrics import GLOBAL
    if value > _TRACE.stats.get(name, -1):
        _TRACE.stats[name] = value
    # lint: allow-counters(groupby/* + sort/* trace names, all registered)
    GLOBAL.set_max(f"{ns}/{name}", value)


def _b_inc(name: str, by: int = 1) -> None:
    """Bounds-lattice trace counter: lands on /counters under bounds/*
    and in the per-statement trace window under a `bounds_` prefix (the
    engine splits the delta into stats.groupby vs stats.bounds)."""
    from ydb_tpu.utils.metrics import GLOBAL
    key = "bounds_" + name
    _TRACE.stats[key] = _TRACE.stats.get(key, 0) + by
    # lint: allow-counters(bounds/* trace names, all registered)
    GLOBAL.inc(f"bounds/{name}", by)


def _count_gather(rows: int, tile_budget: int, value: bool = False,
                  batched: bool = False, ops: int = 1) -> None:
    """Record `ops` traced gather ops of `rows` output rows each.

    `groupby/gather_ops` counts only gathers ABOVE the tile-row budget —
    the ~30 ms full-capacity ops the tiled/late-materialized lowering
    exists to eliminate (each such op on the measured platform costs the
    same as a whole tile's batch). `gather_ops_total` counts everything."""
    _t_inc("gather_ops_total", ops)
    if rows > tile_budget:
        _t_inc("gather_ops", ops)
    if batched:
        _t_inc("batched_gathers", ops)
    if value:
        _t_max("value_gather_rows_max", rows)


def record_sort(rows: int, operands: int) -> None:
    """Called from every multi-operand device sort lowering (group-by and
    ORDER BY alike): high-watermark of rows and operand count — the two
    axes of the lax.sort compile cliff (PERF.md)."""
    _t_max("rows_max", rows, ns="sort")
    _t_max("operands_max", operands, ns="sort")


def _acc_dtype(d):
    if np.issubdtype(np.dtype(d.dtype), np.floating):
        return jnp.float64
    if d.dtype == jnp.uint64:
        return jnp.uint64
    return jnp.int64


def _groupby_global(cmd: ir.GroupBy, env, active, iota):
    """Keyless GROUP BY: plain masked reductions — one output row, no sort,
    no scatter (the BlockCombineAll analog, `mkql_block_agg.cpp`)."""
    new_env = {}
    for a in cmd.aggs:
        if a.func == "count_all":
            data = jnp.sum(active.astype(jnp.uint64))
            new_env[a.out] = (data[None], None)
            continue
        d, v = env[a.arg]
        m = active if v is None else (active & v)
        if a.func == "count":
            new_env[a.out] = (jnp.sum(m.astype(jnp.uint64))[None], None)
            continue
        any_valid = jnp.any(m)[None]
        if a.func == "sum":
            data = jnp.sum(jnp.where(m, d, 0).astype(_acc_dtype(d)))[None]
            new_env[a.out] = (data, any_valid)
        elif a.func in ("min", "max"):
            sent = _sentinel(np.dtype(d.dtype), a.func == "min")
            red = jnp.min if a.func == "min" else jnp.max
            data = red(jnp.where(m, d, sent))[None]
            data = jnp.where(any_valid, data, jnp.zeros((), d.dtype))
            new_env[a.out] = (data, any_valid)
        elif a.func == "some":
            firstpos = jnp.min(jnp.where(m, iota, len(iota)))
            data = d[jnp.clip(firstpos, 0, len(iota) - 1)][None]
            new_env[a.out] = (data, any_valid)
        else:
            raise ValueError(a.func)
    return new_env, jnp.int32(1)


def bucket_layout(cmd: ir.GroupBy) -> Optional[tuple]:
    """(buckets, strides, offsets): the mixed-radix bucket id of a
    bounded-domain group-by, or None where a key is unbounded. A key
    that can be NULL (`ir.GroupBy.key_nullable`; all, where the field is
    empty) takes `domain + 1` slots, NULL first (offset 1); one that
    cannot takes `domain` (offset 0). The one place that says it: the
    lowering (`_bucket_ids`, `_emit_bucket_groups`), the route
    (`groupby_route`) and every bound sized from it call this."""
    doms = cmd.key_domains
    if not cmd.keys or not doms or not all(d > 0 for d in doms):
        return None
    nullable = cmd.key_nullable or (True,) * len(doms)
    nb, strides, offsets = 1, [], []
    for dom, null in zip(doms, nullable):
        strides.append(nb)
        offsets.append(int(bool(null)))
        nb *= dom + offsets[-1]
    return nb, tuple(strides), tuple(offsets)


def _bucket_ids(cmd: ir.GroupBy, env, cap):
    """(bucket id per row, buckets): the mixed-radix id `bucket_layout`
    says; counts the NULL slots the layout left out."""
    nbuckets, strides, offsets = bucket_layout(cmd)
    kid = jnp.zeros((cap,), jnp.int32)
    for kname, dom, st, off in zip(cmd.keys, cmd.key_domains, strides,
                                   offsets):
        d, v = env[kname]
        if off:
            code = d.astype(jnp.int32) + 1      # -1 (null string code) → 0
            if v is not None:
                code = jnp.where(v, code, 0)    # SQL: one NULL group
            code = jnp.clip(code, 0, dom)
        else:
            # NULL in no live row: a mask the key may still carry (the
            # all-true one `Executor._finalize` gives every merged column,
            # an inner join payload's matched flag) is true wherever a
            # row is live, so the code alone places the row
            code = jnp.clip(d.astype(jnp.int32), 0, dom - 1)
            _t_inc("null_slots_elided")
        kid = kid + code * st
    return kid, nbuckets


def _groupby_small_domain(cmd: ir.GroupBy, env, schema: Schema, sel,
                          length, cap):
    """Bounded-domain aggregation as a chunked one-hot 2-D reduction — the
    BlockCombineHashed analog (`mkql_block_agg.cpp`) built entirely from
    elementwise ops + axis-0 reductions (XLA fuses the one-hot expansion
    into the reduction; nothing materializes, nothing scatters)."""
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = (iota < length) if sel is None else ((iota < length) & sel)
    kid, nbuckets = _bucket_ids(cmd, env, cap)
    _t_max("onehot_buckets_max", nbuckets)

    chunks: dict[str, list] = {a.out: [] for a in cmd.aggs}
    valid_chunks: dict[str, list] = {}
    present_chunks = []
    first_chunks = []                  # leader row per bucket (carry keys)
    for c0 in range(0, nbuckets, _CHUNK_W):
        w = min(_CHUNK_W, nbuckets - c0)
        ids = c0 + jnp.arange(w, dtype=jnp.int32)
        oh = (kid[:, None] == ids[None, :]) & active[:, None]
        present_chunks.append(jnp.any(oh, axis=0))
        if cmd.carry_keys:
            first_chunks.append(
                jnp.min(jnp.where(oh, iota[:, None], cap), axis=0))
        for a in cmd.aggs:
            if a.func == "count_all":
                chunks[a.out].append(jnp.sum(oh.astype(jnp.uint64), axis=0))
                continue
            d, v = env[a.arg]
            m = oh if v is None else (oh & v[:, None])
            if a.func == "count":
                chunks[a.out].append(jnp.sum(m.astype(jnp.uint64), axis=0))
                continue
            any_valid = jnp.any(m, axis=0)
            valid_chunks.setdefault(a.out, []).append(any_valid)
            if a.func == "sum":
                acc = jnp.where(m, d[:, None], 0).astype(_acc_dtype(d))
                chunks[a.out].append(jnp.sum(acc, axis=0))
            elif a.func in ("min", "max"):
                sent = _sentinel(np.dtype(d.dtype), a.func == "min")
                red = jnp.min if a.func == "min" else jnp.max
                data = red(jnp.where(m, d[:, None], sent), axis=0)
                chunks[a.out].append(
                    jnp.where(any_valid, data, jnp.zeros((), d.dtype)))
            elif a.func == "some":
                firstpos = jnp.min(jnp.where(m, iota[:, None], cap), axis=0)
                chunks[a.out].append(d[jnp.clip(firstpos, 0, cap - 1)])
            else:
                raise ValueError(a.func)

    new_env = {}
    for a in cmd.aggs:
        data = jnp.concatenate(chunks[a.out])
        v = valid_chunks.get(a.out)
        new_env[a.out] = (data, jnp.concatenate(v) if v is not None else None)
    present = jnp.concatenate(present_chunks)
    firstpos = jnp.concatenate(first_chunks) if first_chunks else None
    return _emit_bucket_groups(cmd, env, schema, new_env, present, cap,
                               firstpos)


def _emit_bucket_groups(cmd: ir.GroupBy, env, schema: Schema, new_env,
                        present, cap, firstpos=None):
    """Shared bounded-domain epilogue: rebuild key columns from bucket ids,
    then compact non-empty buckets to the front of a SMALL capacity bucket
    (compress sorts; doing it over the scan capacity would cost a full
    cap-sized argsort for a handful of groups). `firstpos`: leader row id
    per bucket, required when the command carries functionally-determined
    keys (their per-group value gathers from the leader row)."""
    nbuckets, strides, offsets = bucket_layout(cmd)
    _b_inc("proven_rows", bucket_capacity(nbuckets, minimum=128))
    _b_inc("capacity_rows", cap)
    _b_inc("bounded_groupbys")
    if cmd.carry_keys:
        _b_inc("carried_keys", len(cmd.carry_keys))
    bucket_ids = jnp.arange(nbuckets, dtype=jnp.int32)
    for kname, dom, st, off in zip(cmd.keys, cmd.key_domains, strides,
                                   offsets):
        code = (bucket_ids // st) % (dom + off) - off
        d, _v = env[kname]
        kd = code.astype(jnp.int32).astype(d.dtype)
        kv = code >= 0
        dt = schema.dtype(kname)
        new_env[kname] = (kd, kv if dt.nullable else None)
    for kname in cmd.carry_keys:
        d, v = env[kname]
        safe = jnp.clip(firstpos, 0, cap - 1)
        kd = d[safe]
        dt = schema.dtype(kname)
        if dt.nullable:
            kv = (v[safe] if v is not None
                  else jnp.ones((nbuckets,), jnp.bool_))
            new_env[kname] = (kd, kv & present)
        else:
            new_env[kname] = (kd, None)

    out_cap = bucket_capacity(nbuckets, minimum=128)
    pad = out_cap - nbuckets
    padded = {}
    for name, (d, v) in new_env.items():
        dp = jnp.pad(d, (0, pad)) if pad > 0 else d[:out_cap]
        vp = None
        if v is not None:
            vp = jnp.pad(v, (0, pad)) if pad > 0 else v[:out_cap]
        padded[name] = (dp, vp)
    present_p = jnp.pad(present, (0, pad)) if pad > 0 else present[:out_cap]
    return compress(padded, jnp.int32(nbuckets), present_p, out_cap)


def _groupby_medium_domain(cmd: ir.GroupBy, env, schema: Schema, sel,
                           length, cap):
    """Bounded domains too wide for the one-hot path: one scatter-reduce
    per aggregate into a bucket array (each scatter pays the platform's
    post-readout scatter tax exactly once per aggregate)."""
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = (iota < length) if sel is None else ((iota < length) & sel)
    kid, nbuckets = _bucket_ids(cmd, env, cap)
    seg_safe = jnp.where(active, kid, nbuckets)
    nseg = nbuckets + 1                         # +1 garbage bucket

    new_env = {}
    for a in cmd.aggs:
        if a.func == "count_all":
            data = jax.ops.segment_sum(active.astype(jnp.uint64), seg_safe,
                                       nseg)
            new_env[a.out] = (data[:nbuckets], None)
            continue
        d, v = env[a.arg]
        m = active if v is None else (active & v)
        if a.func == "count":
            data = jax.ops.segment_sum(m.astype(jnp.uint64), seg_safe, nseg)
            new_env[a.out] = (data[:nbuckets], None)
            continue
        cnt = jax.ops.segment_sum(m.astype(jnp.int32), seg_safe, nseg)
        any_valid = (cnt > 0)[:nbuckets]
        if a.func == "sum":
            acc = jnp.where(m, d, 0).astype(_acc_dtype(d))
            data = jax.ops.segment_sum(acc, seg_safe, nseg)[:nbuckets]
            new_env[a.out] = (data, any_valid)
        elif a.func in ("min", "max"):
            sent = _sentinel(np.dtype(d.dtype), a.func == "min")
            masked = jnp.where(m, d, sent)
            fn = jax.ops.segment_min if a.func == "min" else jax.ops.segment_max
            data = fn(masked, seg_safe, nseg)[:nbuckets]
            data = jnp.where(any_valid, data, jnp.zeros((), d.dtype))
            new_env[a.out] = (data, any_valid)
        elif a.func == "some":
            pos = jnp.where(m, iota, cap)
            firstpos = jax.ops.segment_min(pos, seg_safe, nseg)[:nbuckets]
            data = d[jnp.clip(firstpos, 0, cap - 1)]
            new_env[a.out] = (data, any_valid)
        else:
            raise ValueError(a.func)

    present = jax.ops.segment_sum(active.astype(jnp.int32), seg_safe,
                                  nseg)[:nbuckets] > 0
    firstpos = None
    if cmd.carry_keys:
        pos = jnp.where(active, iota, cap)
        firstpos = jax.ops.segment_min(pos, seg_safe, nseg)[:nbuckets]
    return _emit_bucket_groups(cmd, env, schema, new_env, present, cap,
                               firstpos)


def _gather_sorted(cols: dict, perm, cap: int, tiles: int,
                   tile_budget: int) -> dict:
    """Materialize env columns in key-sorted order: the ONLY place value
    columns are gathered at row-level granularity on the sorted path.

    Tiled: the permutation splits into `tiles` static slices so no single
    gather op exceeds cap/tiles rows — below the platform's ~4M 2-D-gather
    compiler wedge (PERF.md round-5), which also re-unlocks the reverted
    per-dtype BATCHED gather: all requested columns of one dtype fold into
    one (m, tile) gather per tile (measured cost of a 2-8 column 2-D
    gather equals ONE column's). `_GATHER_BATCH_ROWS` gates the batch by
    tile rows (per-column gathers above it — byte-identical results)."""
    T = cap // tiles
    by_dt: dict = {}
    for name, arr in cols.items():
        by_dt.setdefault(str(arr.dtype), []).append(name)
    out = {}
    for _dt, names in by_dt.items():
        arrs = [cols[n] for n in names]
        m = len(arrs)
        if m > 1 and T <= _GATHER_BATCH_ROWS:
            stacked = jnp.stack(arrs)                    # (m, cap)
            pieces = [stacked[:, perm[p * T:(p + 1) * T]]
                      for p in range(tiles)]             # (m, T) each
            _count_gather(T, tile_budget, value=True, batched=True,
                          ops=tiles)
            full = jnp.concatenate(pieces, axis=1) if tiles > 1 \
                else pieces[0]
            for i, n in enumerate(names):
                out[n] = full[i]
        else:
            for n, arr in zip(names, arrs):
                pieces = [arr[perm[p * T:(p + 1) * T]]
                          for p in range(tiles)]
                _count_gather(T, tile_budget, value=True, ops=tiles)
                out[n] = jnp.concatenate(pieces) if tiles > 1 else pieces[0]
    return out


def _csum_diffs(per_rows: list, starts, ends, oc: int,
                tile_budget: int) -> list:
    """Per-group sums of sorted per-row arrays via cumulative-sum
    endpoints, evaluated at OUTPUT capacity: diff = c[end] − c[start] +
    v[start]. The cumsums stay 1-D (cheap on the platform; only 2-D ones
    wedge); the endpoint gathers batch per accumulation dtype — one
    (m, oc) gather triple instead of 3 gathers per aggregate.
    `_GATHER_BATCH_ROWS` gates the batch by oc exactly as `_gather_sorted`
    gates by tile rows: with no proven out_bound oc == scan capacity, and
    an (m, cap) 2-D gather is the ~4M compiler-wedge shape this module
    exists to avoid.

    Precision: for a tiny group inside a huge total the cancellation
    costs ~(total / group_sum)·1e-16 relative error — acceptable for SQL
    doubles and the test oracles' 1e-6 tolerances."""
    out: list = [None] * len(per_rows)
    groups: dict = {}
    for i, pr in enumerate(per_rows):
        groups.setdefault(str(pr.dtype), []).append(i)
    for _dt, idxs in groups.items():
        csums = [cumsum(per_rows[i]) for i in idxs]
        if len(idxs) > 1 and oc <= _GATHER_BATCH_ROWS:
            cs = jnp.stack(csums)                        # (m, cap)
            fs = jnp.stack([per_rows[i] for i in idxs])
            ce, cst, f0 = cs[:, ends], cs[:, starts], fs[:, starts]
            _count_gather(oc, tile_budget, batched=True, ops=3)
            for k, i in enumerate(idxs):
                out[i] = ce[k] - cst[k] + f0[k]
        else:
            for c, i in zip(csums, idxs):
                out[i] = c[ends] - c[starts] + per_rows[i][starts]
                _count_gather(oc, tile_budget, ops=3)
    return out


def _segment_scan(vals, boundary, kind: str):
    """Running min/max within key segments of a sorted block: an
    associative scan over (value, segment-start flag) pairs — log-depth
    elementwise, NO scatter (a scatter-reduce per min/max aggregate cost
    ~70-100 ms, the platform's most taxed op class). Read at segment END
    positions it yields the whole-segment reduction."""
    combine = jnp.minimum if kind == "min" else jnp.maximum

    def op(a, b):
        av, ab = a
        bv, bb = b
        return (jnp.where(bb, bv, combine(av, bv)), ab | bb)

    out, _flags = jax.lax.associative_scan(op, (vals, boundary))
    return out


def _trace_group_by_sorted(cmd: ir.GroupBy, env, schema: Schema, sel,
                           length, cap):
    """Unbounded-domain aggregation, round-8 shape: ONE key sort, then a
    pre-aggregate → tile → LATE-MATERIALIZE pipeline, still inside one
    dispatch (the WideCombiner workhorse, `mkql_wide_combine.cpp`, in the
    partition-then-combine decomposition of DrJAX, arxiv 2403.07128):

      * the sort carries only key encodings + the row permutation (wide
        multi-operand sorts explode XLA compile time — PERF.md);
      * per-row value materialization (the former 15-20 sequential ~30 ms
        full-capacity gathers) happens tiled at ≤ YDB_TPU_GROUPBY_TILE_ROWS
        rows per op and per-dtype batched (`_gather_sorted`), and ONLY for
        columns that truly need sorted per-row values (sum/min/max data,
        nullable-arg validity);
      * everything per-GROUP — key values, csum endpoints, min/max scan
        reads, `some` values — gathers at OUTPUT capacity: ngroups slots,
        statically bounded by `cmd.out_bound` when the planner/executor
        can prove one (key-domain products, inner-join build cardinality),
        the scan capacity otherwise;
      * min/max/some use a segmented associative scan (`_segment_scan`)
        instead of scatter-reduces — the sorted path is now scatter-FREE.

    `cmd.out_bound` is a PROVEN upper bound on ngroups: an understated
    value would silently drop groups, so only guaranteed sources may set
    it. Precision of the csum diffs: see `_csum_diffs`."""
    tile_budget, _lm = groupby_tuning()
    tiles = 1
    while cap // tiles > tile_budget and cap % (tiles * 2) == 0 \
            and cap // tiles > 1:
        tiles *= 2
    _t_inc("traces")
    _t_inc("tiles", tiles)
    _t_max("sort_rows_max", cap)

    iota = jnp.arange(cap, dtype=jnp.int32)
    row_mask = iota < length
    active = row_mask if sel is None else (row_mask & sel)

    inactive = (~active).astype(jnp.int32)
    sort_keys = [inactive]
    for kname in cmd.keys:
        d, v = env[kname]
        enc = _sort_operand(d)
        if v is not None:
            # nullable keys carry a validity operand so NULLs form one
            # group; non-nullable keys contribute only their encoding —
            # a constant all-ones operand sorts nothing, and each
            # operand at scan capacity is real wall time (PERF round-16)
            enc = jnp.where(v, enc, _zero_like_operand(enc))
            sort_keys.append(v.astype(jnp.int32))
        sort_keys.append(enc)
    record_sort(cap, len(sort_keys) + 1)
    # iota as the last key → deterministic total order, and the sort output
    # IS the permutation (no carried operands)
    out = sort_total(sort_keys, iota)
    inactive_s = out[0]
    keyparts_s = out[1:-1]
    perm = out[-1]

    active_s = inactive_s == 0
    changed = jnp.zeros((cap,), jnp.bool_)
    for kp in keyparts_s:
        prev = jnp.concatenate([kp[:1], kp[:-1]])
        neq = kp != prev
        if np.issubdtype(np.dtype(kp.dtype), np.floating):
            # NaN != NaN would split every NaN row into its own group;
            # lax.sort places NaNs adjacently, so treat them as equal
            neq = neq & ~(jnp.isnan(kp) & jnp.isnan(prev))
        changed = changed | neq
    boundary = active_s & ((iota == 0) | changed)
    ngroups = jnp.sum(boundary.astype(jnp.int32))
    nactive = jnp.sum(active_s.astype(jnp.int32))

    # output capacity: the late-materialization granularity. Everything
    # per-group below gathers at `oc` slots, not scan capacity.
    oc = cap
    if cmd.out_bound:
        oc = min(bucket_capacity(max(int(cmd.out_bound), 1), minimum=128),
                 cap)

    # compact segment-start row indices to the front: starts[i] = sorted-row
    # index where group i begins (argsort = 2-operand sort)
    record_sort(cap, 2)
    starts = stable_argsort(jnp.where(boundary, iota, jnp.int32(cap)))[:oc]
    gi = jnp.arange(oc, dtype=jnp.int32)
    next_start = jnp.concatenate([starts[1:], jnp.full((1,), cap, jnp.int32)])
    # group i ends at the next group's start − 1; the LAST live group ends
    # at nactive − 1. ngroups ≤ oc is guaranteed (out_bound contract), so
    # slicing starts to oc cannot orphan a live group's end.
    ends = jnp.where(gi + 1 < ngroups, next_start - 1, nactive - 1)
    ends = jnp.clip(ends, 0, cap - 1)
    live = gi < ngroups

    # group-leader original row ids: ONE oc-sized gather shared by every
    # late-materialized column (keys, CARRIED keys, `some` values)
    lead = perm[jnp.clip(starts, 0, cap - 1)]
    _count_gather(oc, tile_budget)

    # bounds-lattice gauges: per-group allocation (oc) vs the scan
    # capacity it replaced, and how many grouping columns the carry
    # rewrite kept OUT of the sort identity
    _b_inc("proven_rows", oc)
    _b_inc("capacity_rows", cap)
    if cmd.out_bound:
        _b_inc("bounded_groupbys")
    if cmd.carry_keys:
        _b_inc("carried_keys", len(cmd.carry_keys))

    new_env = {}
    # carried keys materialize EXACTLY like keys — value at the group
    # leader row — their per-group constancy is the carry contract
    for kname in list(cmd.keys) + list(cmd.carry_keys):
        d, v = env[kname]
        kd = d[lead]
        _count_gather(oc, tile_budget)
        dt = schema.dtype(kname)
        if dt.nullable:
            if v is not None:
                kv = v[lead]
                _count_gather(oc, tile_budget)
            else:
                kv = jnp.ones((oc,), jnp.bool_)
            new_env[kname] = (kd, kv & live)
        else:
            new_env[kname] = (kd, None)

    # ---- sorted per-row materialization: only what aggregation truly
    # needs (sum/min/max data; validity of nullable args)
    need_data, need_valid = [], []
    for a in cmd.aggs:
        if a.func == "count_all":
            continue
        if env[a.arg][1] is not None:
            need_valid.append(a.arg)
        if a.func in ("sum", "min", "max"):
            need_data.append(a.arg)
    data_s = _gather_sorted(
        {n: env[n][0] for n in dict.fromkeys(need_data)}, perm, cap, tiles,
        tile_budget)
    valid_s = _gather_sorted(
        {n: env[n][1] for n in dict.fromkeys(need_valid)}, perm, cap, tiles,
        tile_budget)

    # ---- phase 1: register every cumulative-sum job so endpoint gathers
    # batch per dtype across aggregates
    jobs: list = []

    def job(per_row) -> int:
        jobs.append(per_row)
        return len(jobs) - 1

    agg_plan = []
    for a in cmd.aggs:
        if a.func == "count_all":
            agg_plan.append(("count", a, job(active_s.astype(jnp.uint64)),
                             None, None))
            continue
        v = valid_s.get(a.arg)
        m = active_s if v is None else (active_s & v)
        if a.func == "count":
            agg_plan.append(("count", a, job(m.astype(jnp.uint64)), None,
                             None))
            continue
        cnt_j = job(m.astype(jnp.int64))
        if a.func == "sum":
            d = data_s[a.arg]
            acc = jnp.where(m, d, 0).astype(_acc_dtype(d))
            agg_plan.append(("sum", a, job(acc), cnt_j, None))
        elif a.func in ("min", "max", "some"):
            agg_plan.append((a.func, a, None, cnt_j, m))
        else:
            raise ValueError(a.func)

    diffs = _csum_diffs(jobs, starts, ends, oc, tile_budget)

    # ---- phase 2: assemble per-group outputs at oc capacity
    for (kind, a, data_j, cnt_j, m) in agg_plan:
        if kind == "count":
            new_env[a.out] = (jnp.where(live, diffs[data_j], 0), None)
            continue
        cnt = diffs[cnt_j]
        any_valid = (cnt > 0) & live
        if kind == "sum":
            new_env[a.out] = (diffs[data_j], any_valid)
        elif kind in ("min", "max"):
            d = data_s[a.arg]
            sent = _sentinel(np.dtype(d.dtype), kind == "min")
            masked = jnp.where(m, d, sent)
            data = _segment_scan(masked, boundary, kind)[ends]
            _count_gather(oc, tile_budget)
            data = jnp.where(any_valid, data, jnp.zeros((), d.dtype))
            new_env[a.out] = (data, any_valid)
        else:  # some: first valid value — late-materialized at oc
            pos = jnp.where(m, iota, cap)
            firstpos = _segment_scan(pos, boundary, "min")[ends]
            _count_gather(oc, tile_budget)
            rowid = perm[jnp.clip(firstpos, 0, cap - 1)]
            _count_gather(oc, tile_budget)
            data = env[a.arg][0][rowid]
            _count_gather(oc, tile_budget)
            new_env[a.out] = (data, any_valid)
    return new_env, ngroups.astype(jnp.int32)


def groupby_route(cmd: ir.GroupBy) -> tuple:
    """(route, buckets): which lowering `_trace_group_by` gives `cmd`:
    `keyless` (1 bucket), `small-domain`, `medium-domain` (their bucket
    count), or `sorted` (0: unbounded). The one place that decides it:
    the trace dispatches on it and `Executor._compact_sizing` prices a
    pipeline's tail by it (`reads_in_place`)."""
    if not cmd.keys:
        return "keyless", 1
    layout = bucket_layout(cmd)
    if layout is not None:
        nb = layout[0]
        if nb <= _SMALL_DOMAIN_BUCKETS:
            return "small-domain", nb
        if nb + 1 <= _SCATTER_MAX_BUCKETS:
            return "medium-domain", nb
    return "sorted", 0


def reads_in_place(cmd: ir.GroupBy) -> Optional[str]:
    """The name of `cmd`'s route where it reads each live row once, where
    the scan left it, and moves nothing per row: masked reductions over
    the selection mask (keyless; a one-hot of at most `_INPLACE_BUCKETS`
    buckets) cost the same rows whether 2 % or 100 % are live, and less
    than the Compact in front would (a sort of every position, a gather
    a column). None where the lowering gathers or scatters per row
    (medium-domain, sorted) and is priced by the rows it is given, or
    its buckets x rows pass what the Compact costs."""
    route, nb = groupby_route(cmd)
    if route == "keyless" or (route == "small-domain"
                              and nb <= _INPLACE_BUCKETS):
        return route
    return None


def _trace_group_by(cmd: ir.GroupBy, env, schema: Schema, sel, length, cap):
    """GroupBy dispatch (`groupby_route`): keyless → plain reductions; small
    bounded domains → one-hot 2-D reduction; medium bounded →
    scatter-reduce; unbounded → sort-based. Returns (new_env, new_length)."""
    route, _nb = groupby_route(cmd)
    if route == "keyless":
        iota = jnp.arange(cap, dtype=jnp.int32)
        active = (iota < length) if sel is None else ((iota < length) & sel)
        return _groupby_global(cmd, env, active, iota)
    if route == "small-domain":
        return _groupby_small_domain(cmd, env, schema, sel, length, cap)
    if route == "medium-domain":
        return _groupby_medium_domain(cmd, env, schema, sel, length, cap)
    return _trace_group_by_sorted(cmd, env, schema, sel, length, cap)


def _trace_program(program: ir.Program, in_schema_cols, cap, env, length,
                   params, sel=None, aux=None, passthrough=()):
    """env: name -> (data, valid|None); returns (env, length, sel, schema).
    `sel` seeds the selection mask (fused pipelines thread it between
    programs instead of compressing).

    `aux`: out-of-band scalar box filled by `ir.Compact` (live count +
    overflow flag — the executor's loud-rerun input; scalars cannot ride
    the row-shaped env). `passthrough`: helper column names (the fused
    late-materialization row-id vectors) that survive Projections and
    whose projected names may be ABSENT from env (deferred columns stay
    deferred through a projection); callers that pass no passthrough
    keep the strict behavior."""
    schema = Schema(list(in_schema_cols))
    for cmd in program.commands:
        # HLO metadata only (`op_name`): a device operation then says which
        # IR command it came from — a kind and a column name, no literal
        scope = f"assign[{cmd.name}]" if isinstance(cmd, ir.Assign) \
            else type(cmd).__name__.lower()
        with jax.named_scope(scope):
            if isinstance(cmd, ir.Assign):
                data, valid = _eval(cmd.expr, env, params, cap)
                env[cmd.name] = (data, valid)
                dt = ir.infer_expr(cmd.expr, schema)
                schema = Schema([c for c in schema.columns
                                 if c.name != cmd.name]
                                + [Column(cmd.name, dt)])
            elif isinstance(cmd, ir.Filter):
                data, valid = _eval(cmd.pred, env, params, cap)
                mask = data if valid is None else (data & valid)
                sel = mask if sel is None else (sel & mask)
            elif isinstance(cmd, ir.GroupBy):
                env, length = _trace_group_by(cmd, env, schema, sel, length,
                                              cap)
                # the scatter path shrinks the working capacity to a small
                # bucket; subsequent commands trace at the new size
                if env:
                    cap = next(iter(env.values()))[0].shape[0]
                schema = ir.infer_schema(ir.Program([cmd]), schema)
                sel = None
            elif isinstance(cmd, ir.Projection):
                schema = schema.select(list(cmd.names))
                if passthrough:
                    new_env = {nm: env[nm] for nm in cmd.names
                               if nm in env}
                    for h in passthrough:
                        if h in env:
                            new_env[h] = env[h]
                    env = new_env
                else:
                    env = {nm: env[nm] for nm in cmd.names}
            elif isinstance(cmd, ir.Compact):
                env, length, sel, live, ovf = compact_env(env, length, sel,
                                                          cap, cmd.cap)
                cap = cmd.cap
                if aux is not None:
                    aux["compact_live"] = live
                    aux["compact_ovf"] = ovf
            else:
                raise TypeError(f"bad command {cmd!r}")
    return env, length, sel, schema


def compress(env, length, sel, cap):
    """BlockCompress: compact selected rows to the front (stable).

    Analog of `mkql_block_compress.cpp`. Sort by (dropped, position)."""
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = (iota < length) if sel is None else ((iota < length) & sel)
    keys = jnp.where(active, iota, jnp.int32(cap))
    order = stable_argsort(keys)
    new_len = jnp.sum(active.astype(jnp.int32))
    new_env = {}
    for name, (d, v) in env.items():
        new_env[name] = (d[order], v[order] if v is not None else None)
    return new_env, new_len


def compact_env(env, length, sel, cap, new_cap: int):
    """`ir.Compact` lowering: stable-compress selected rows to the front
    of a `new_cap`-sized buffer — downstream operators compile at the
    small shape. The source row of every kept slot is found ONCE, by
    one single-operand int32 sort: a live row's key is its own position,
    a dead row's is `cap`, so the first `new_cap` sorted keys ARE the
    source rows, in scan order (live keys are distinct: the order is
    the stable one whatever the sort does with ties). Each column and
    validity plane is then one gather of `new_cap` indices; nothing of
    width `cap` is written per column (a scatter is priced by its
    updates: `cap` of them cost 417-548 ms a float64 column at SF1, the
    sort 10 ms; PERF.md round 27). Returns (env', length', sel', live,
    overflow): `live` is the true selected count and `overflow = live >
    new_cap` — the host-side loud-rerun signal; rows beyond `new_cap`
    ARE dropped from env', so a result produced under overflow must be
    discarded, never served. Slots at or past `length'` are masked by
    `sel'` and hold a copy of row `cap - 1` (the clamped key of a dead
    row)."""
    if new_cap > cap:
        raise ValueError(f"Compact to {new_cap} rows from {cap}: it shrinks")
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = (iota < length) if sel is None else ((iota < length) & sel)
    live = jnp.sum(active.astype(jnp.int32))
    ovf = live > jnp.int32(new_cap)
    keys = jnp.where(active, iota, jnp.int32(cap))
    src = jnp.minimum(jax.lax.sort(keys)[:new_cap], jnp.int32(cap - 1))
    new_env = {}
    for name, (d, v) in env.items():
        new_env[name] = (d[src], v[src] if v is not None else None)
    new_len = jnp.minimum(live, jnp.int32(new_cap))
    new_sel = jnp.arange(new_cap, dtype=jnp.int32) < new_len
    return new_env, new_len, new_sel, live, ovf


# --------------------------------------------------------------------------
# compiled-program cache
# --------------------------------------------------------------------------


class ProgramCache:
    """(program fp, signature, capacity) -> jitted fn. Pattern-cache
    analog; entries draw on the process-wide live-executable budget
    (`ops/exec_cache.py`)."""

    def __init__(self):
        from ydb_tpu.ops.exec_cache import ExecCache
        from ydb_tpu.utils import progstats
        self._cache = ExecCache("program")
        # eviction surfaces in the program inventory: the entry persists
        # in `.sys/compiled_programs` marked `evicted`, and a re-compile
        # of the key counts a MISS that re-records compile_ms
        self._cache.on_evict = \
            lambda key: progstats.mark_evicted("program", key)
        self.hits = 0
        self.misses = 0

    def get(self, program: ir.Program, sig, cap, param_names):
        # groupby tuning is part of the identity: a program traced under
        # one tile setting must not serve another (tests flip the env
        # knob in-process)
        key = (program.fingerprint(), sig, cap, param_names,
               groupby_tuning())
        # observability levers cannot stale a program: they choose how
        # the identical trace is dispatched/recorded, not what it computes
        # lint: allow-cache-key(progstats/memledger/critpath observe only)
        fn = self._cache.get(key)
        if fn is None:
            self.misses += 1
            fn = self._timed_fill(key, self._build(program, sig, cap))
        else:
            self.hits += 1
            from ydb_tpu.utils import progstats
            progstats.record_hit(getattr(fn, "key_id", None))
        return fn

    def _timed_fill(self, key, built):
        """Cache-fill wrapper: jax.jit compiles lazily on the FIRST
        invocation, so the fill stores a thin shim that times that call
        (trace + XLA compile + first run) and records it as this
        program's compile_ms; later calls pay one flag check. With the
        program observatory on (`utils/progstats`, the default), the
        first call compiles via the explicit AOT path instead —
        lower().compile(), ONE trace + ONE compile like the lazy path —
        capturing the executable's cost/memory analysis, and steady-
        state calls dispatch through the AOT handle. The shim delegates
        `clear_cache` to whichever target holds the executable so
        ExecCache eviction releases it (a bare closure would silently
        defeat the release-on-evict lifecycle), and it never overwrites
        the cache entry — an overwrite would spuriously release."""
        import threading as _threading
        import time as _time

        from ydb_tpu.utils import progstats
        timed = [False]
        target = [built]               # swapped to the AOT handle once
        mu = _threading.Lock()

        def shim(*a, **kw):
            if timed[0]:
                return target[0](*a, **kw)
            with mu:
                first = not timed[0]
                timed[0] = True
            if not first:
                # lost the first-call race: don't double-count compiles
                return target[0](*a, **kw)
            from ydb_tpu.utils.metrics import GLOBAL
            t0 = _time.perf_counter()
            if progstats.enabled():
                target[0] = progstats.capture("program", key, built, a)
            out = target[0](*a, **kw)
            ms = (_time.perf_counter() - t0) * 1000.0
            GLOBAL.inc("program_cache/compiles")
            GLOBAL.inc("program_cache/compile_ms", ms)
            return out

        def _clear():
            t = target[0]
            cc = getattr(t, "clear_cache", None)
            if callable(cc):
                cc()                   # the handle clears built too
            if t is not built:
                built.clear_cache()

        shim.clear_cache = _clear
        # the inventory id rides the shim so a later cache HIT can be
        # attributed without re-hashing the key
        shim.key_id = progstats.key_id("program", key) \
            if progstats.enabled() else None
        self._cache[key] = shim
        return shim

    @staticmethod
    def _build(program: ir.Program, sig, cap):
        in_cols = [Column(name, DType(Kind(kind), nullable))
                   for (name, kind, nullable) in sig]

        @partial(jax.jit, static_argnames=())
        def fn(arrays, valids, length, params):
            env = {}
            for c in in_cols:
                env[c.name] = (arrays[c.name], valids.get(c.name))
            env, length, sel, schema = _trace_program(
                program, in_cols, cap, env, length, params)
            if sel is not None:  # statically known: no Filter → already compact
                out_cap = next(iter(env.values()))[0].shape[0] if env else cap
                env, length = compress(env, length, sel, out_cap)
            out_d = {nm: env[nm][0] for nm in schema.names}
            out_v = {nm: env[nm][1] for nm in schema.names if env[nm][1] is not None}
            return out_d, out_v, length

        return fn


_GLOBAL_CACHE = ProgramCache()


@partial(jax.jit, static_argnames=("names",))
def _compress_jit(arrays, valids, length, sel, names):
    env = {n: (arrays[n], valids.get(n)) for n in names}
    cap = arrays[names[0]].shape[0]
    env, new_len = compress(env, length, sel, cap)
    out_d = {n: env[n][0] for n in names}
    out_v = {n: env[n][1] for n in names if env[n][1] is not None}
    return out_d, out_v, new_len


def compress_block(dblock: DeviceBlock, sel) -> DeviceBlock:
    """Apply a selection mask, compacting survivors to the block front."""
    names = tuple(dblock.schema.names)
    out_d, out_v, new_len = _compress_jit(
        dblock.arrays, dblock.valids, dblock.length, sel, names)
    return DeviceBlock(dblock.schema, out_d, out_v, new_len, dblock.capacity,
                       dict(dblock.dictionaries))


def run_on_device(program: ir.Program, dblock: DeviceBlock,
                  params: Optional[dict] = None,
                  cache: Optional[ProgramCache] = None) -> DeviceBlock:
    """Run a compiled program over a device-resident block."""
    cache = cache or _GLOBAL_CACHE
    params = params or {}
    dev_params = {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
                  for k, v in params.items()}
    fn = cache.get(program, dblock.sig(), dblock.capacity,
                   tuple(sorted(params.keys())))
    out_d, out_v, length = fn(dblock.arrays, dblock.valids, dblock.length,
                              dev_params)
    out_schema = ir.infer_schema(program, dblock.schema)
    dicts = {n: d for n, d in dblock.dictionaries.items() if out_schema.has(n)}
    out_cap = (next(iter(out_d.values())).shape[0] if out_d
               else dblock.capacity)
    return DeviceBlock(out_schema, out_d, out_v, length, out_cap, dicts)


def run_program(program: ir.Program, block: HostBlock,
                params: Optional[dict] = None,
                cache: Optional[ProgramCache] = None) -> HostBlock:
    """Host-convenience entry: pad → device → compiled program → HostBlock."""
    return to_host(run_on_device(program, to_device(block), params, cache))
