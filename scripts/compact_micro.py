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
equal the scatter's, bit for bit. The compile cache is off, so compile
seconds are the compiler's. One JSON line a reading goes to
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
    ap.add_argument("--cap", type=int, default=6_291_456)
    ap.add_argument("--new-caps", default="57344,163840,262144,3145728")
    ap.add_argument("--runs", type=int, default=7)
    ap.add_argument("--seed", type=int, default=27)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ydb_tpu  # noqa: F401 — x64 on, as the engine runs
    from ydb_tpu.ops import xla_exec as X

    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"[micro] platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/compact_micro.jsonl", "a")
    cap = args.cap
    rng = np.random.default_rng(args.seed)

    def emit(**rec):
        rec.update(platform=dev.platform, cap=cap)
        line = json.dumps(rec)
        print("[micro] " + line, flush=True)
        out.write(line + "\n")
        out.flush()

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
            t0 = time.perf_counter()
            compiled = jax.jit(fn, static_argnums=0).lower(
                new_cap, active, i32, f64, valid).compile()
            compile_s = time.perf_counter() - t0
            res = jax.block_until_ready(compiled(active, i32, f64, valid))
            ms = []
            for _ in range(args.runs):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(active, i32, f64, valid))
                ms.append((time.perf_counter() - t0) * 1e3)
            kept = [np.asarray(a)[:live] for a in res]
            if ref is None:
                ref = kept
            same = all(np.array_equal(a, b) for a, b in zip(kept, ref))
            ok = ok and same
            emit(what=what, new_cap=new_cap, live=live,
                 compile_s=round(compile_s, 2),
                 run_ms_median=statistics.median(ms), run_ms_min=min(ms),
                 run_ms_max=max(ms), runs=args.runs, equals_scatter=same)
    emit(what="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
