#!/usr/bin/env python3
"""What `xla_exec.sort_total` and `xla_exec.cumsum` cost on the ambient
device, next to the primitives they replaced (PR 22).

    chiprun --timeout 1800 -- python scripts/sort_micro.py

Per size (1 M and 4 M rows, 4 096 for the small end) and key type: compile
seconds and the median run time of the radix `sort_total` and of ONE wide
unstable `lax.sort` over (key, row id); the same for the blocked `cumsum`
and `jnp.cumsum` on float64. The permutations must agree (the data holds
the float32 and double range's edges, and keys computed on the device),
the prefix sums to 1e-9. The
compile cache is off, so compile seconds are the compiler's. The wide
sorts and `jnp.cumsum` take the TPU compiler minutes: they run last, in
order of cost, and are skipped once `--budget-s` is spent. One JSON line
per reading goes to `chiprun_out/sort_micro.jsonl` as it is taken. Exit 1
when a permutation or a sum differs: on the v5e the 4 M float64 case does
(12 keys near 1e-30 tie, PERF.md section 7; PR 22's reading).
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
    ap.add_argument("--budget-s", type=float, default=1300.0)
    ap.add_argument("--sizes", default="1048576,4194304")
    ap.add_argument("--small", type=int, default=4096)   # >= 3000
    ap.add_argument("--runs", type=int, default=7)
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
    out = open("chiprun_out/sort_micro.jsonl", "a")
    t_start = time.perf_counter()
    rng = np.random.default_rng(22)

    def emit(**rec):
        rec["platform"] = dev.platform
        line = json.dumps(rec)
        print("[micro] " + line, flush=True)
        out.write(line + "\n")
        out.flush()

    built = {}

    def measure(what, n, fn, *arrs):
        """Result of `fn`; emits its compile seconds and median run ms."""
        key = (fn, tuple((a.shape, a.dtype) for a in arrs))
        if key not in built:
            t0 = time.perf_counter()
            built[key] = (jax.jit(fn).lower(*arrs).compile(),
                          time.perf_counter() - t0)
        compiled, compile_s = built[key]
        res = jax.block_until_ready(compiled(*arrs))       # warm-up
        ms = []
        for _ in range(args.runs):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*arrs))
            ms.append((time.perf_counter() - t0) * 1e3)
        emit(what=what, n=n, compile_s=round(compile_s, 2),
             run_ms_median=statistics.median(ms), run_ms_min=min(ms),
             run_ms_max=max(ms), runs=args.runs)
        return res

    def keys_of(kind, n):
        if kind == "i64":
            return rng.integers(-2 ** 62, 2 ** 62, size=n)
        f = rng.normal(size=n) * 10.0 ** rng.integers(-30, 30, size=n)
        edges = np.array([1e39, -1e39, 1e100, 3e200, -2e60, 1e-40, 1e-300,
                          3.4e38, -3.4e38, 1.2e-38, np.inf, -np.inf, np.nan,
                          0.0, -0.0, 1.0, np.nextafter(1.0, 2.0)])
        f[:len(edges)] = edges
        f[100:1100] = 1e-30 * (1 + rng.integers(0, 4, 1000) * 2.0 ** -52)
        f[2000:3000] = np.repeat(rng.normal(size=10), 100)   # long ties
        return f

    def radix(k):
        return X.sort_total([k], jnp.arange(k.shape[0], dtype=jnp.int32))[-1]

    def wide(k):
        iota = jnp.arange(k.shape[0], dtype=jnp.int32)
        return jax.lax.sort([X._sort_operand(k), iota], num_keys=2,
                            is_stable=False)[-1]

    sizes = [int(s) for s in args.sizes.split(",")]
    data = {(kind, n): jax.device_put(keys_of(kind, n))
            for n in [args.small] + sizes for kind in ("i64", "f64")}
    # keys COMPUTED on the device: where float64 is emulated they carry
    # bits no loaded double has, and the words must still order them
    computed = jax.jit(lambda k: k * (1.0 / 3.0) + k * k * 1e-7)
    for n in [args.small, sizes[0]]:
        data["f64 computed", n] = computed(data["f64", n])
    vals = {n: jax.device_put(rng.normal(size=n) * 1e3) for n in sizes}
    perms, sums = {}, {}

    # what ships first: it is cheap to compile
    for (kind, n), k in data.items():
        perms[kind, n] = measure(f"sort_total radix {kind}", n, radix, k)
    for n, v in vals.items():
        sums[n] = measure("cumsum blocked f64", n, X.cumsum, v)

    def spent():
        return time.perf_counter() - t_start

    late = [(f"lax.sort wide {kind}", n, wide, data[kind, n], (kind, n))
            for (kind, n) in data]
    late += [("jnp.cumsum f64", n, jnp.cumsum, vals[n], n) for n in sizes]
    ok = True
    for what, n, fn, arr, ref in late:
        if spent() > args.budget_s:
            emit(what=what, n=n, skipped=f"budget spent ({spent():.0f}s)")
            continue
        res = measure(what, n, fn, arr)
        if fn is wide:
            differ = np.flatnonzero(np.asarray(res) != np.asarray(perms[ref]))
            same = differ.size == 0
            host = np.asarray(arr)
            emit(what=f"radix == wide permutation {ref[0]}", n=n, equal=same,
                 differ=int(differ.size),
                 wide_keys=[repr(x) for x in host[np.asarray(res)[differ[:6]]]],
                 radix_keys=[repr(x) for x in
                             host[np.asarray(perms[ref])[differ[:6]]]])
            ok = ok and same
        else:
            err = float(jnp.max(jnp.abs(res - sums[ref]))
                        / jnp.max(jnp.abs(res)))
            emit(what="blocked vs jnp.cumsum max error / max |sum|", n=n,
                 error=err)
            ok = ok and err < 1e-9
    emit(what="done", seconds=round(spent(), 1), ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
