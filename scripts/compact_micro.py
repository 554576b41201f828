#!/usr/bin/env python3
"""What `ir.Compact` costs on the ambient device: the per-column dropping
scatter `xla_exec.compact_env` had until PR 27, beside three ways of
finding each kept slot's source row ONCE and gathering at the bound.

    chiprun --timeout 1500 -- python scripts/compact_micro.py

Per bound (`--new-caps`, scan capacity `--cap`): compile seconds and the
median run time of

  * `scatter`       one `.at[tgt].set(a, mode="drop")` of `cap` updates
                    per column and validity plane (what shipped before);
  * `sort`          `lax.sort(where(active, iota, cap))[:new_cap]`, then
                    one gather of `new_cap` indices a column;
  * `iota_scatter`  the same dropping scatter, of the int32 `iota` alone,
                    then the gathers;
  * `searchsorted`  `arange(1, new_cap + 1)` searched in the prefix sum of
                    the live mask, then the gathers;

and of `xla_exec.compact_env` as it ships, over one int32 column and one
float64 column with a validity plane (a float64 is two float32 streams on
the TPU, which is what made the scatter dear). About four fifths of each
bound is live, rows chosen from a seed. Every candidate's kept slots must
equal the scatter's, bit for bit.

Then (`--sections tail`, PR 34) whether an aggregate tail wants a Compact
in front of it at all, at each `--tail-caps` scan capacity: the engine's
own group-by lowering (`xla_exec._trace_group_by`) over the masked scan
(`inplace`) beside `compact_env` of the columns it reads to the ladder
rung over the live rows and the same lowering at that bound (`compact`):

  * `q6`         three range predicates, 1.8 % live, a keyless
                 `sum(price * discount)`;
  * `q1[B]`      one date predicate, `--live-pct` live, Q1's eleven
                 partial aggregates over four float64 columns into B
                 buckets (`--buckets`; Q1 has 12): the one-hot reduction
                 costs buckets x rows in place, buckets x bound behind the
                 Compact's sort and gathers.

The two routes' sums must agree to 1e-12 (they add in another order), the
counts exactly. `Executor._compact_sizing` declines the Compact where
`inplace` wins (`xla_exec.reads_in_place`). The compile cache is off, so
compile seconds are the compiler's. One JSON line a reading goes to
`chiprun_out/compact_micro.jsonl` as it is taken. No cell runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sections", default="compact,tail")
    ap.add_argument("--cap", type=int, default=6_291_456)
    ap.add_argument("--new-caps", default="57344,163840,262144,3145728")
    ap.add_argument("--tail-caps", default="6291456,67108864")
    ap.add_argument("--buckets", default="12,64,192,512")
    ap.add_argument("--live-pct", type=float, default=10.0)
    ap.add_argument("--runs", type=int, default=7)
    ap.add_argument("--seed", type=int, default=27)
    args = ap.parse_args()

    import jax

    import ydb_tpu  # noqa: F401 — x64 on, as the engine runs

    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"[micro] platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/compact_micro.jsonl", "a")

    def emit(**rec):
        rec.update(platform=dev.platform)
        line = json.dumps(rec)
        print("[micro] " + line, flush=True)
        out.write(line + "\n")
        out.flush()

    ok = True
    sections = args.sections.split(",")
    if "compact" in sections:
        ok = compact_section(args, emit) and ok
    if "tail" in sections:
        for cap in [int(s) for s in args.tail_caps.split(",")]:
            ok = tail_section(args, cap, emit) and ok
    emit(what="done", ok=ok)
    return 0 if ok else 1


def timed(fn, static, inputs, runs: int):
    """(compile seconds, the first result, run times in ms)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn, static_argnums=tuple(range(len(static)))).lower(
        *static, *inputs).compile()
    compile_s = time.perf_counter() - t0
    res = jax.block_until_ready(compiled(*inputs))
    ms = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*inputs))
        ms.append((time.perf_counter() - t0) * 1e3)
    return compile_s, res, ms


def compact_section(args, emit) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ydb_tpu.ops import xla_exec as X

    cap = args.cap
    rng = np.random.default_rng(args.seed)

    def gathered(src, cols):
        src = jnp.minimum(src, jnp.int32(cap - 1))
        return tuple(a[src] for a in cols)

    def rank_of(active):
        return jnp.cumsum(active.astype(jnp.int32))

    def scatter(new_cap, active, *cols):
        tgt = jnp.where(active, rank_of(active) - 1, jnp.int32(new_cap))
        return tuple(jnp.zeros((new_cap,), a.dtype).at[tgt].set(a, mode="drop")
                     for a in cols)

    def by_sort(new_cap, active, *cols):
        iota = jnp.arange(cap, dtype=jnp.int32)
        keys = jnp.where(active, iota, jnp.int32(cap))
        return gathered(jax.lax.sort(keys)[:new_cap], cols)

    def by_iota_scatter(new_cap, active, *cols):
        iota = jnp.arange(cap, dtype=jnp.int32)
        tgt = jnp.where(active, rank_of(active) - 1, jnp.int32(new_cap))
        src = jnp.full((new_cap,), cap, jnp.int32).at[tgt].set(
            iota, mode="drop")
        return gathered(src, cols)

    def by_searchsorted(new_cap, active, *cols):
        want = jnp.arange(1, new_cap + 1, dtype=jnp.int32)
        src = jnp.searchsorted(rank_of(active), want, side="left")
        return gathered(src.astype(jnp.int32), cols)

    def shipped(new_cap, active, i32, f64, valid):
        env, *_ = X.compact_env({"i": (i32, None), "f": (f64, valid)},
                                jnp.int32(cap), active, cap, new_cap)
        return env["i"][0], env["f"][0], env["f"][1]

    lowerings = [("scatter", scatter), ("sort", by_sort),
                 ("iota_scatter", by_iota_scatter),
                 ("searchsorted", by_searchsorted),
                 ("compact_env", shipped)]

    i32 = jax.device_put(rng.integers(-2 ** 31, 2 ** 31, size=cap,
                                      dtype=np.int64).astype(np.int32))
    f64 = jax.device_put(rng.normal(size=cap) * 1e3)
    valid = jax.device_put(rng.random(cap) < 0.9)
    ok = True
    for new_cap in [int(s) for s in args.new_caps.split(",")]:
        live = min(new_cap * 4 // 5, cap)
        mask = np.zeros(cap, dtype=bool)
        mask[rng.choice(cap, size=live, replace=False)] = True
        active = jax.device_put(mask)
        ref = None
        for what, fn in lowerings:
            compile_s, res, ms = timed(fn, (new_cap,),
                                       (active, i32, f64, valid), args.runs)
            kept = [np.asarray(a)[:live] for a in res]
            if ref is None:
                ref = kept
            same = all(np.array_equal(a, b) for a, b in zip(kept, ref))
            ok = ok and same
            emit(what=what, cap=cap, new_cap=new_cap, live=live,
                 compile_s=round(compile_s, 2),
                 run_ms_median=statistics.median(ms), run_ms_min=min(ms),
                 run_ms_max=max(ms), runs=args.runs, equals_scatter=same)
    return ok


def tail_section(args, cap: int, emit) -> bool:
    """An aggregate tail in place over `cap` masked slots, and behind a
    Compact to the rung over its live rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ydb_tpu.core.dtypes import DType, Kind
    from ydb_tpu.core.schema import Column, Schema
    from ydb_tpu.ops import ir
    from ydb_tpu.ops import xla_exec as X
    from ydb_tpu.progstore import buckets as shape_buckets

    rng = np.random.default_rng([args.seed, cap])
    f64 = DType(Kind.FLOAT64, False)
    # dbgen's domains: a ship date over seven years, a discount of 0..10
    # hundredths, a quantity of 1..50, a tax of 0..8 hundredths
    date = jax.device_put(rng.integers(0, 2557, cap).astype(np.int32))
    qty = jax.device_put(rng.integers(1, 51, cap).astype(np.float64))
    price = jax.device_put(np.round(rng.random(cap) * 1e5, 2))
    disc = jax.device_put(rng.integers(0, 11, cap) / 100.0)
    tax = jax.device_put(rng.integers(0, 9, cap) / 100.0)

    def q6_mask(date, qty, disc):
        return ((date >= 365) & (date < 730) & (disc >= 0.05)
                & (disc <= 0.07) & (qty < 24))

    q6 = ir.GroupBy((), (ir.Agg("rev", "sum", "x"),))
    q6_schema = Schema([Column("x", f64)])

    def behind(new_cap, env, active):
        """(env, length, mask, capacity) as the tail meets them: the scan's,
        or `compact_env`'s at `new_cap`."""
        if not new_cap:
            return env, jnp.int32(cap), active, cap
        env, length, active, _live, _ovf = X.compact_env(
            env, jnp.int32(cap), active, cap, new_cap)
        return env, length, active, new_cap

    def q6_route(new_cap, date, qty, price, disc):
        env, length, active, c = behind(
            new_cap, {"p": (price, None), "d": (disc, None)},
            q6_mask(date, qty, disc))
        env["x"] = (env["p"][0] * env["d"][0], None)
        got, _n = X._trace_group_by(q6, env, q6_schema, active, length, c)
        return got["rev"][0]

    def rung(live: int) -> int:
        return shape_buckets.bucket_segment(max(int(live * 1.25) + 1, 1024))

    ok = True

    def price_both(what, route, inputs, live, **rec):
        nonlocal ok
        ref = None
        for name, new_cap in (("inplace", 0), ("compact", rung(live))):
            try:
                compile_s, res, ms = timed(route, (new_cap,), inputs,
                                           args.runs)
            except Exception as e:       # noqa: BLE001 — a refusal is a reading
                ok = False
                emit(what=f"{what}/{name}", cap=cap, new_cap=new_cap,
                     live=live, refused=f"{type(e).__name__}: {e}"[:300],
                     **rec)
                continue
            flat = np.concatenate([np.asarray(a, dtype=np.float64).ravel()
                                   for a in jax.tree_util.tree_leaves(res)])
            if ref is None:
                ref = flat
            same = bool(np.allclose(flat, ref, rtol=1e-12, atol=0))
            ok = ok and same
            emit(what=f"{what}/{name}", cap=cap, new_cap=new_cap, live=live,
                 compile_s=round(compile_s, 2),
                 run_ms_median=statistics.median(ms), run_ms_min=min(ms),
                 run_ms_max=max(ms), runs=args.runs, equals_inplace=same,
                 **rec)

    live = int(np.asarray(q6_mask(date, qty, disc)).sum())
    price_both("q6", q6_route, (date, qty, price, disc), live)

    cut = int(2557 * args.live_pct / 100.0)
    live = int((np.asarray(date) <= cut).sum())
    aggs = (ir.Agg("s0", "sum", "q"), ir.Agg("s1", "sum", "p"),
            ir.Agg("s2", "sum", "x2"), ir.Agg("s3", "sum", "x3"),
            ir.Agg("s4", "sum", "q"), ir.Agg("c4", "count", "q"),
            ir.Agg("s5", "sum", "p"), ir.Agg("c5", "count", "p"),
            ir.Agg("s6", "sum", "d"), ir.Agg("c6", "count", "d"),
            ir.Agg("n", "count_all"))
    for nb in [int(s) for s in args.buckets.split(",")]:
        # one key of `nb - 1` codes and its NULL slot: `nb` buckets
        key = jax.device_put(rng.integers(0, nb - 1, cap).astype(np.int32))
        cmd = ir.GroupBy(("k",), aggs, key_domains=(nb - 1,), out_bound=nb)
        schema = Schema([Column("k", DType(Kind.INT32, False))]
                        + [Column(n, f64) for n in ("q", "p", "d", "t", "x2", "x3")])
        assert X.groupby_route(cmd) == ("small-domain", nb)

        def q1_route(new_cap, date, key, qty, price, disc, tax,
                     cmd=cmd, schema=schema):
            env, length, active, c = behind(
                new_cap, {"k": (key, None), "q": (qty, None),
                          "p": (price, None), "d": (disc, None),
                          "t": (tax, None)}, date <= cut)
            env["x2"] = (env["p"][0] * (1 - env["d"][0]), None)
            env["x3"] = (env["x2"][0] * (1 + env["t"][0]), None)
            got, n = X._trace_group_by(cmd, env, schema, active, length, c)
            return [got[a.out][0] for a in aggs], n

        price_both(f"q1[{nb}]", q1_route,
                   (date, key, qty, price, disc, tax), live, buckets=nb)
    return ok


if __name__ == "__main__":
    sys.exit(main())
