#!/usr/bin/env python3
"""Ask the TPU compiler about a mesh cell's `shard_map` programs — without
a chip.

    JAX_PLATFORMS=cpu python scripts/mesh_rehearse.py \
        --workload tpch-sf1-mesh4.shuffle [--sf 1] [--seed 7] [--out FILE]

Builds the cell's engine over four virtual CPU devices the way
`benchmark/run.py` does (its loader, its configuration, its statements from
`--seed`), runs each statement once, and records every program the mesh
lanes hand to the AOT seam (`utils/progstats.capture`, kinds `mesh-sj` and
`mesh-merge`) with the object that built it and its argument shapes. Each
is then rebuilt over a mesh of the four DESCRIBED chips of a v5e host
(`topologies.get_topology_desc`) and compiled there. Per program: accepted
or the compiler's message, compile seconds, `memory_analysis()` bytes a
device, counts of collectives and sorts (on-chip-measurement guide,
section 2). `--scopes FILE` also writes what `scripts/op_scopes.py` writes
on the chip, from the TPU compiler's HLO of these compiles (a four-chip
call for it alone costs more than it tells), and `--result` prints a traced
run's `device_ops` with their scopes; an operation it cannot find says so
(`?`). Nothing runs on a TPU: no number printed here is a device metric.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "benchmark"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

MESH_KINDS = ("mesh-sj", "mesh-merge")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--statements", type=int, default=None,
                    help="run only the first N of the cell's statements "
                         "(one of a shape is enough to meet its programs)")
    ap.add_argument("--out", default="")
    ap.add_argument("--scopes", default="",
                    help="write {module: {ops: ...}} as op_scopes.py does")
    ap.add_argument("--result", default=None,
                    help="a traced run's result line (a JSON file)")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import devices
    devices.REQUIRED_PLATFORM = "cpu"          # a rehearsal, not a run
    import run as bench_run
    import traffic
    from ydb_tpu.parallel import shuffle, shuffle_join
    from ydb_tpu.parallel.collective import AXIS
    from ydb_tpu.utils import progstats

    # who built what: `_build` runs right before the capture of its program
    built: list = []
    for cls in (shuffle.DistributedAgg, shuffle_join.ShuffleJoin):
        real = cls._build

        def spy_build(self, *a, _real=real):
            built.append((self, a))
            return _real(self, *a)
        cls._build = spy_build
    captured = []
    real_capture = progstats.capture

    def spy_capture(kind, key, jit_fn, cargs, *a, **kw):
        if kind in MESH_KINDS:
            captured.append((kind, jit_fn.__name__, built[-1], cargs))
        return real_capture(kind, key, jit_fn, cargs, *a, **kw)
    progstats.capture = spy_capture

    bench = traffic.read_json(ROOT / "BENCHMARK.json")
    _cell, cfg_entry = bench_run.find_cell(bench, args.workload)
    cfg = traffic.read_json(ROOT / cfg_entry["file"])
    if args.sf is not None:
        cfg["sf"] = args.sf
    mix = traffic.read_json(ROOT / "benchmark" / "workloads"
                            / f"{args.workload}.json")
    import ydb_tpu                              # noqa: F401 — x64
    eng = bench_run.build_engine(cfg)
    t0 = time.perf_counter()
    traffic.load_module("loaders", cfg["loader"]).load(eng, cfg, args.seed)
    _mods, items = traffic.build_items(mix, args.seed)
    items = items[:args.statements]
    for it in items:
        eng.execute(it.sql)
    print(f"[rehearse] {len(items)} statements at sf {cfg['sf']} on "
          f"{eng.executor.last_path}: {time.perf_counter() - t0:.0f}s, "
          f"{len(captured)} mesh programs", flush=True)

    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), (AXIS,))

    def spec(x):
        if not hasattr(x, "shape"):
            return x
        sh = getattr(x, "sharding", None)
        pspec = sh.spec if isinstance(sh, NamedSharding) else P()
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, pspec))

    sys.path.insert(0, str(ROOT / "scripts"))
    import op_scopes
    records, programs = [], {}
    for kind, name, (obj, build_args), cargs in captured:
        clone = copy.copy(obj)
        clone.mesh, clone._fns = mesh, {}
        fn, _holder = clone._build(*build_args)
        leaves = jax.tree_util.tree_leaves(cargs)
        rec = {"kind": kind, "name": name, "largest_args": [
            f"{s}:{d}" for s, d in sorted(
                {(tuple(x.shape), str(x.dtype)) for x in leaves},
                key=lambda t: -int(np.prod(t[0])))[:3]]}
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(
                *jax.tree_util.tree_map(spec, cargs)).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            programs[f"jit_{name}"] = {"ops": op_scopes.parse_hlo(text)}
            rec.update(accepted=True,
                       compile_s=round(time.perf_counter() - t0, 2),
                       arg_bytes=mem.argument_size_in_bytes,
                       temp_bytes=mem.temp_size_in_bytes,
                       out_bytes=mem.output_size_in_bytes,
                       all_to_all=text.count(" all-to-all("),
                       all_gather=text.count(" all-gather("),
                       sorts=text.count(" sort("))
        except Exception as e:                 # noqa: BLE001 — the verdict
            rec.update(accepted=False,
                       compile_s=round(time.perf_counter() - t0, 2),
                       message=str(e)[:2000])
        print(json.dumps(rec), flush=True)
        records.append(rec)
    refused = [r for r in records if not r["accepted"]]
    print(f"[rehearse] {len(records)} mesh programs, {len(refused)} refused "
          f"(TPU compiler on this sandbox's CPU, not a device metric)",
          flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    if args.scopes:
        with open(args.scopes, "w") as f:
            json.dump(programs, f, indent=1)
    if args.result:
        line = Path(args.result).read_text().strip().splitlines()[-1]
        ops = json.loads(line)["breakdown"]["device_ops"]
        for op, sec, scopes in op_scopes.annotate(ops, programs):
            print(f"{sec:9.3f}s  {op}\n            {scopes}")
    return 1 if refused or not records else 0


if __name__ == "__main__":
    sys.exit(main())
