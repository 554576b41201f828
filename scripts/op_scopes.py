#!/usr/bin/env python3
"""Which line of the plan is a device operation? — a device trace names an
operation `jit_lineitem_gs_1f6bff(..9634)/fusion.3`; the program's optimized
HLO text says which `jax.named_scope` (IR command, join step, deferred
gather) the instructions inside `%fusion.3` came from.

    python scripts/op_scopes.py --workload tpch-sf1.scan --seed 7 \
        --out chiprun_out/scopes_scan.json [--result traced_result.json]

Builds the cell's engine the way `benchmark/run.py` does (its loader, its
configuration, its statements from `--seed`, two passes, so the programs
are the ones the cell runs and come out of the same compile cache), then
writes for every live fused program, and for the `shard_map` programs of
the mesh lanes (`jit_mesh_sj_<table>_<digest>`, `jit_mesh_merge_...`), its
module name and, per fusion / sort / while / collective instruction, its
own `op_name` and the scopes of what it fuses (`exchange/bucket`,
`exchange/all_to_all`, `exchange/compact`, `shuffle.probe`, `rest/...`,
`partial/...`, `merge/...` in a mesh program). `--result`: a traced run's result line; its `breakdown.device_ops`
are printed with their scopes beside them.

Tooling, not a measurement: reads the benchmark's files, edits none, and
reports no time of its own. Runs on whatever platform JAX finds (fusion
numbers are the TPU compiler's only on the TPU).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "benchmark"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_CALLS = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# `jit(name)/`, and inside a mesh program `jit(name)/shard_map/`
_JIT = re.compile(r"^jit\([\w.\-]+\)/(?:(?:jit\()?shard_map\)?/)?")
# what a reader of a breakdown asks about; the rest is elementwise
HEAVY = ("gather", "scatter", "sort", "reduce", "reduce-window",
         "dynamic-slice", "dynamic-update-slice", "while")
# a collective may be split into `-start` / `-done` by the TPU compiler
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce",
               "collective-permute")
# the kinds `utils/progstats` inventories the named programs under
KINDS = ("fused", "batched", "mesh-sj", "mesh-merge")


def _heavy(opcode: str) -> bool:
    return opcode in HEAVY or opcode.startswith(COLLECTIVES)


def _scope(line: str) -> str:
    m = _OP_NAME.search(line)
    return _JIT.sub("", m.group(1)) if m else ""


def parse_hlo(text: str) -> dict:
    """{instruction: {"opcode", "scope", "inner": {opcode: [scope, ...]}}}
    for the fusions, sorts, whiles and other HEAVY instructions of every
    computation; `inner` lists the scopes of the HEAVY instructions a
    fusion (or a while body, a sort comparator) holds."""
    bodies: dict = {}                # computation -> [(name, opcode, line)]
    cur = None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            cur = bodies.setdefault(h.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            op = _OPCODE.search(" " + line.split(" = ", 1)[1])
            cur.append((m.group(1), op.group(1) if op else "", line))
    out: dict = {}
    for instrs in bodies.values():
        for name, opcode, line in instrs:
            if opcode != "fusion" and not _heavy(opcode):
                continue
            inner: dict = {}
            for callee in _CALLS.findall(line):
                for _n, op2, line2 in bodies.get(callee, ()):
                    if _heavy(op2):
                        scopes = inner.setdefault(op2, [])
                        s = _scope(line2)
                        if s and s not in scopes:
                            scopes.append(s)
            out[name] = {"opcode": opcode, "scope": _scope(line),
                         "inner": inner}
    return out


def build_and_run(workload: str, seed: int, sf: float | None = None):
    """The cell's engine, loaded and warmed the way `run.py` sets up
    (`sf`: a rehearsal's scale factor in the configuration's place)."""
    import run as bench_run
    import traffic
    bench = traffic.read_json(ROOT / "BENCHMARK.json")
    _cell, cfg_entry = bench_run.find_cell(bench, workload)
    cfg = traffic.read_json(ROOT / cfg_entry["file"])
    if sf is not None:
        cfg["sf"] = sf
    mix = traffic.read_json(ROOT / "benchmark" / "workloads"
                            / f"{workload}.json")
    import os
    for k, v in (cfg.get("env") or {}).items():
        os.environ[k] = str(v)           # the configuration's levers
    import ydb_tpu                       # noqa: F401 — x64, cache dir
    eng = bench_run.build_engine(cfg)
    loader = traffic.load_module("loaders", cfg["loader"])
    loader.load(eng, cfg, seed)
    _mods, items = traffic.build_items(mix, seed)
    for _ in range(2):
        for it in items:
            eng.execute(it.sql)
    return eng


def annotate(device_ops: list, programs: dict) -> list:
    """[[op, seconds]] of a breakdown -> [[op, seconds, scopes]]."""
    out = []
    for op, sec in device_ops:
        module, _, rest = op.partition("/")
        prog = programs.get(re.sub(r"[(_]\.\..*$", "", module), {})
        # `fusion.3 fusion:Custom`, and the compiler's own names with
        # underscores in them: `select_reduce_fusion.1 fusion:Loop`
        ops = prog.get("ops", {})
        info = ops.get(rest.split(" ")[0]) \
            or ops.get(re.split(r"[ _]", rest)[0])
        if info is None:
            scopes = "?"
        else:
            scopes = info["scope"] or "-"
            for op2, ss in info["inner"].items():
                scopes += f" | {op2}: " + ", ".join(ss)
        out.append([op, sec, scopes])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--sf", type=float, default=None,
                    help="rehearse at this scale factor (CPU)")
    ap.add_argument("--hlo-dir", default=None,
                    help="also write each program's HLO text there (.gz)")
    ap.add_argument("--result", default=None,
                    help="a traced run's result line (a JSON file)")
    args = ap.parse_args(argv)
    from ydb_tpu.utils import progstats
    # the engine keeps the programs live
    eng = build_and_run(args.workload, args.seed, args.sf)
    programs = {}
    for row in progstats.inventory_rows():
        text = progstats.hlo_text(row["program"])
        if row["kind"] not in KINDS or not text:
            continue
        if args.hlo_dir:
            import gzip
            Path(args.hlo_dir).mkdir(parents=True, exist_ok=True)
            with gzip.open(Path(args.hlo_dir) / f"{row['name']}.txt.gz",
                           "wt") as f:
                f.write(text)
        # programs of one shape (two literal sets, two Compact sizes)
        # share a name and a numbering: the first one's operations stand
        # for all, each key keeps its own count. The batched lane's
        # stacked programs share a name across member-slot buckets and
        # are built smallest first: the LAST one's (the full bucket, what
        # a herd runs) stand for them
        prog = programs.setdefault(row["name"], {"keys": [],
                                                 "ops": parse_hlo(text)})
        if row["kind"] == "batched":
            prog["ops"] = parse_hlo(text)
        prog["keys"].append({"key": row["program"], "execs": row["execs"],
                             "device_ms_max": row["device_ms_max"]})
    del eng
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(programs, indent=1))
    print(f"{len(programs)} programs -> {args.out}")
    if args.result:
        lines = Path(args.result).read_text().strip().splitlines()
        ops = json.loads(lines[-1])["breakdown"]["device_ops"] \
            if lines else []
        for op, sec, scopes in annotate(ops, programs):
            print(f"{sec:9.3f}s  {op}\n            {scopes}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
