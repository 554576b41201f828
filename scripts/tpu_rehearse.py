#!/usr/bin/env python3
"""Ask the TPU compiler about the smoke's programs — without a chip.

    JAX_PLATFORMS=cpu python scripts/tpu_rehearse.py [--sf 1] [--out FILE]

Runs `chip_smoke.py`'s embedded phase on the CPU at `--sf` (the load takes
seconds), records every jitted program the engine hands to its AOT seam
(`utils/progstats.capture`) with its argument shapes, then re-lowers each
for ONE described v5e chip (`topologies.get_topology_desc`) and compiles
it there. Per program: accepted or the compiler's message, compile
seconds, `memory_analysis()` bytes, sort/scatter counts. What the compiler
refuses or needs minutes for shows here, at no chip time
(on-chip-measurement guide, section 2; PERF.md round 22 has the first
reading). Nothing runs on a TPU: no number printed here is a device
metric. `tests/test_tpu_compile.py` keeps the cheap ones as tier-1 tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=19920101)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import numpy as np

    import chip_smoke
    from tests.tpch_util import QUERIES
    from ydb_tpu.utils import progstats

    captured = []
    current = ["?"]
    real_capture = progstats.capture

    def spy(kind, key, jit_fn, cargs, *a, **kw):
        leaves, tree = jax.tree_util.tree_flatten(cargs)
        captured.append((current[0], kind, jit_fn, tree, leaves))
        return real_capture(kind, key, jit_fn, cargs, *a, **kw)

    progstats.capture = spy
    chip_smoke.REQUIRED_PLATFORM = "cpu"        # a rehearsal, not a run
    eng, data = chip_smoke.load(args.sf, args.seed)
    real_query = eng.query
    by_sql = {sql: name for name, sql in QUERIES.items()}

    def tagged(sql):
        current[0] = by_sql.get(sql, "?")
        return real_query(sql)

    eng.query = tagged
    chip_smoke.embedded_phase(eng, data)
    progstats.capture = real_capture

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        if not hasattr(x, "shape"):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip,
            weak_type=bool(getattr(x, "weak_type", False)))

    records = []
    for query, kind, fn, tree, leaves in captured:
        shapes = sorted({(tuple(x.shape), str(x.dtype)) for x in leaves
                         if hasattr(x, "shape")},
                        key=lambda t: -int(np.prod(t[0])))[:3]
        rec = {"query": query, "kind": kind,
               "largest_args": [f"{s}:{d}" for s, d in shapes]}
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(*jax.tree_util.tree_unflatten(
                tree, [spec(x) for x in leaves])).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            rec.update(accepted=True,
                       compile_s=round(time.perf_counter() - t0, 2),
                       arg_bytes=mem.argument_size_in_bytes,
                       temp_bytes=mem.temp_size_in_bytes,
                       out_bytes=mem.output_size_in_bytes,
                       sorts=text.count(" sort("),
                       scatters=text.count(" scatter("))
        except Exception as e:                 # noqa: BLE001 — the verdict
            rec.update(accepted=False,
                       compile_s=round(time.perf_counter() - t0, 2),
                       message=str(e)[:2000])
        print(json.dumps(rec), flush=True)
        records.append(rec)

    total = sum(r["compile_s"] for r in records)
    refused = [r for r in records if not r["accepted"]]
    print(f"[rehearse] {len(records)} programs, {len(refused)} refused, "
          f"{total:.0f}s of TPU-compiler time (sandbox CPU, not a device "
          f"metric)", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    return 1 if refused else 0


if __name__ == "__main__":
    sys.exit(main())
