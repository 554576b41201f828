#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that needs the cell's chips as TPUs (or exits non-zero before
it loads anything), sets up, measures for `--seconds`, decides `correct`
against the plain references, and prints ONE last line of JSON on standard
output: `correct`, `attempted`, `failed`, `metrics`, `device`, in a traced
run `breakdown`, and last `compared`, the numbers `correct` was decided on,
each beside its limit. Everything else is on earlier lines.

Driven by data: the cell, its configuration, its queries, its front, its
loop and every metric are files found by the names in `BENCHMARK.json`
(see README.md). One process: the server's threads and the clients'
threads live in it, because the process that imports JAX holds the chip.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse                          # noqa: E402
import gc                                # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import tempfile                          # noqa: E402
from pathlib import Path                 # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(HERE), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import devices                           # noqa: E402
import traffic                           # noqa: E402

MAX_WARM_PASSES = 4


class SetupFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SetupFailure(msg)


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    check(name in cells, f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, cfg


def metrics_of(bench: dict, section: str, cell: str) -> list[dict]:
    """The metrics of `section` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


class XlaProgramCount:
    """XLA's own count of programs built in this process: backend compiles
    and loads from the persistent cache, by JAX's monitoring events."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.cache_loads = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1
        elif event == self.CACHE_HIT:
            self.cache_loads += 1

    @property
    def built(self) -> int:
        return self.compiles + self.cache_loads


def counters_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def check_native() -> None:
    from ydb_tpu import native
    ok = native.available()
    say(f"native.available()={ok} g++={shutil.which('g++')}")
    check(ok or shutil.which("g++") is None,
          "native blob IO layer unavailable on a machine that has g++")


def check_superblocks_on_device(eng) -> int:
    """Every scan superblock the fused programs read sits on the required
    platform (copied from `chip_smoke.py`)."""
    cache = eng.executor.device_cache
    with cache._mu:
        held = [(k, d, v) for k, (d, v, _n) in cache._entries.items()
                if k[0] == "sbc"]
    check(held, "no scan superblock is resident after the warm-up")
    for key, d, v in held:
        for a in (d, v):
            check(a is None or devices.on_required_platform(a),
                  f"superblock column {key[-1]} is not on "
                  f"{devices.REQUIRED_PLATFORM}")
    return len(held)


def build_engine(cfg: dict):
    """The engine the configuration's file describes: `chips` > 1 builds
    it over `make_mesh(chips)`; `engine_attrs` are dotted attributes set on
    it; `env` levers were exported before the program was imported."""
    from ydb_tpu.query import QueryEngine
    mesh = None
    if int(cfg.get("chips", 1)) > 1:
        from ydb_tpu.parallel import make_mesh
        mesh = make_mesh(int(cfg["chips"]))
        check(mesh.devices.size == int(cfg["chips"]),
              f"mesh holds {mesh.devices.size} device(s)")
    eng = QueryEngine(mesh=mesh)
    for dotted, value in (cfg.get("engine_attrs") or {}).items():
        obj = eng
        *path, leaf = dotted.split(".")
        for part in path:
            obj = getattr(obj, part)
        check(hasattr(obj, leaf), f"engine has no attribute {dotted!r}")
        setattr(obj, leaf, value)
    return eng


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             overrides: dict | None = None,
             keep_trace: str | None = None) -> dict:
    """The whole run; returns the result line as a dict. `overrides`
    replaces keys of the configuration (tests rehearse at a small scale
    factor with it; the command line has no way to it)."""
    bench = traffic.read_json(ROOT / "BENCHMARK.json")
    cell, cfg_entry = find_cell(bench, workload)
    cfg = traffic.read_json(ROOT / cfg_entry["file"])
    cfg.update(overrides or {})
    mix = traffic.read_json(HERE / "workloads" / f"{workload}.json")
    check(mix["config"] == cell["config"],
          f"{workload}.json names config {mix['config']!r}")
    chips = int(cell["chips"])
    check(int(cfg.get("chips", 1)) == chips,
          "the configuration's chips differ from the cell's")

    # -- the device, before anything is loaded --------------------------------
    for k, v in (cfg.get("env") or {}).items():
        os.environ[k] = str(v)
    dev = devices.require(chips)
    say(f"{devices.versions()} platform={dev['platform']} "
        f"device_kind={dev['kind']!r} count={dev['count']}")
    import jax
    import ydb_tpu                              # noqa: F401 — x64, cache dir
    from jax.profiler import TraceAnnotation
    from ydb_tpu.utils.metrics import GLOBAL
    say(f"compile cache: {jax.config.jax_compilation_cache_dir or 'off'}")
    xla = XlaProgramCount()
    check_native()
    phase = {"imports_s": time.perf_counter() - T_PROCESS}
    setup_c0 = GLOBAL.snapshot()

    # -- load (an acknowledged write) and the front ---------------------------
    t = time.perf_counter()
    from proxy import EngineProxy, match
    eng = build_engine(cfg)
    loader = traffic.load_module("loaders", cfg["loader"])
    data = loader.load(eng, cfg, seed)
    want_rows = loader.row_counts(data)
    phase["load_s"] = time.perf_counter() - t
    say(f"load sf={cfg['sf']} seed={seed}: {sum(want_rows.values())} rows "
        f"in {phase['load_s']:.2f}s")

    proxy = EngineProxy(eng)
    front = traffic.load_module("fronts", mix["front"]).start(proxy)
    clients: list = []
    trace_dir = None
    try:
        admin = front.connect(timeout=float(cfg.get("setup_timeout_s", 1100)))
        clients.append(admin)
        for table, n in want_rows.items():
            _c, rows, _t = admin.query(f"select count(*) as n from {table}")
            check(int(rows[0][0]) == n,
                  f"{table}: loaded {n} rows, the front counts {rows[0][0]}")

        # -- the cell's list, warmed until a whole pass builds nothing -------
        mods, items = traffic.build_items(mix, seed)
        say("items: " + "; ".join(
            f"{it.query}[{it.set_no}] {dict(it.params)}" for it in items))
        t = time.perf_counter()
        for n_pass in range(1, MAX_WARM_PASSES + 1):
            r0, x0 = GLOBAL.get("prog/registered"), xla.built
            t_pass = time.perf_counter()
            for it in items:
                admin.query(it.sql)
            registered = int(GLOBAL.get("prog/registered") - r0)
            say(f"warm pass {n_pass}: {time.perf_counter() - t_pass:.2f}s "
                f"registered={registered} xla_built={xla.built - x0}")
            if n_pass >= 2 and registered == 0 and xla.built == x0:
                break
        else:
            raise SetupFailure(f"programs were still being built in warm "
                               f"pass {MAX_WARM_PASSES}")
        phase["warm_s"] = time.perf_counter() - t
        warm_calls = proxy.take_calls()
        paths = {c.path for c in warm_calls if c.kind == "select"
                 and "count(*) as n from" not in c.sql}
        check(paths == {mix["expected_path"]},
              f"queries ran on {sorted(paths)}, the cell names "
              f"{mix['expected_path']!r}")
        if mix["expected_path"] == "fused":
            say(f"{check_superblocks_on_device(eng)} superblock columns on "
                f"{devices.REQUIRED_PLATFORM}")

        streams = int(mix["streams"])
        plans = [traffic.stream_plan(mix, items, seed, i)
                 for i in range(streams)]
        for _ in range(streams):
            clients.append(front.connect())
        setup_counters = counters_delta(setup_c0, GLOBAL.snapshot())
        gc.collect()
        gc.freeze()
        gc.disable()       # no collector pause inside the window

        # -- the window ----------------------------------------------------------
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        loop = traffic.load_module("loops", mix["loop"])
        window_c0, x0 = GLOBAL.snapshot(), (xla.compiles, xla.cache_loads)
        setup_s = time.perf_counter() - T_PROCESS
        with TraceAnnotation("window"):
            t_start, t_end, samples = loop.run(
                [_Annotated(c) for c in clients[1:]], plans, seconds, mix)
        window_counters = counters_delta(window_c0, GLOBAL.snapshot())
        xla_in_window = (xla.compiles - x0[0], xla.cache_loads - x0[1])
        if trace:
            jax.profiler.stop_trace()
        window_s = t_end - t_start
        match(samples, proxy.take_calls())
        memory_peak = devices.memory_peak_bytes(chips)
    finally:
        for c in clients:
            c.close()
        front.stop()

    # -- free the program's state, then the references ----------------------
    del proxy, eng, front
    gc.enable()
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    import compare
    from refutil import Frames
    frames = Frames(data.tables)
    memo: dict = {}

    def reference_of(item):
        if item not in memo:
            memo[item] = mods[item.query].reference(frames, dict(item.params))
        return memo[item]

    verdict = compare.judge(samples, reference_of)
    phase["reference_s"] = time.perf_counter() - t

    # -- metrics ---------------------------------------------------------------
    import least_bytes
    schema = getattr(loader, "SCHEMA", None)
    done = [s for s in samples if s.error is None]
    ctx = {
        "samples": samples, "window_s": window_s, "setup_s": setup_s,
        "seconds": seconds, "setup_counters": setup_counters,
        "window_counters": window_counters, "trace": None,
        "hbm_bytes_per_s": None, "least_bytes": None,
    }
    if schema is not None:
        ctx["least_bytes"] = sum(least_bytes.query_bytes(
            mods[s.item.query].TABLES, schema, want_rows) for s in done)
    breakdown = None
    if trace:
        import trace_reduce
        t = time.perf_counter()
        xplane = trace_reduce.find_xplane(trace_dir)
        tr = trace_reduce.reduce_trace(xplane)
        phase["trace_reduce_s"] = time.perf_counter() - t
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if tr["window_s"] is None:
            tr["window_s"] = window_s
        ctx["trace"] = tr
        if tr["busy_s"] is not None:
            ctx["hbm_bytes_per_s"] = chips * devices.peak(
                dev["kind"], "hbm_bytes_per_s")
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, workload):
        value = traffic.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- earlier lines: what a reader of the log wants beside the result -----
    import statistics
    say("phases: " + " ".join(f"{k}={v:.2f}" for k, v in phase.items())
        + f" setup_s={setup_s:.2f} window_s={window_s:.3f}")
    for q in mix["queries"]:
        lat = [s.latency_ms for s in done if s.item.query == q]
        eng_ms = [(s.call.t1 - s.call.t0) * 1e3 for s in done
                  if s.item.query == q and s.call is not None]
        if lat:
            say(f"class {q}: n={len(lat)} median={statistics.median(lat):.1f}ms "
                f"min={min(lat):.1f} max={max(lat):.1f} engine_median="
                f"{statistics.median(eng_ms) if eng_ms else float('nan'):.1f}ms")
    say(f"compiles_in_window: prog/registered="
        f"{int(window_counters.get('prog/registered', 0))} "
        f"xla_compiles={xla_in_window[0]} xla_cache_loads={xla_in_window[1]}; "
        f"set-up: xla_compiles+loads={xla.built - sum(xla_in_window)} "
        f"prog/compile_ms={setup_counters.get('prog/compile_ms', 0):.0f}")
    say(f"memory_peak_bytes={memory_peak} least_bytes={ctx['least_bytes']}")

    device = dict(dev, memory_peak_bytes=memory_peak)
    if trace and ctx["trace"]["busy_s"] is not None:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    result = {"correct": verdict["correct"], "attempted": len(samples),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = verdict["compared"]
    return result


class _Annotated:
    """A client whose statements show in the profiler's trace."""

    def __init__(self, client):
        self._client = client

    def query(self, sql: str):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("client.query"):
            return self._client.query(sql)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also copy the .xplane.pb there")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), keep_trace=args.keep_trace)
    except BaseException as e:                 # noqa: BLE001 — every phase
        if isinstance(e, SystemExit) and e.code in (0, None):
            raise
        import traceback
        traceback.print_exc()
        print(f"[bench] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    for name, c in result["compared"].items():
        print(f"[bench] compared {name}: value={c['value']!r} "
              f"limit={c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
