#!/usr/bin/env python3
"""Standing proof that the served SQL path starts and answers on the chip.

    python chip_smoke.py              # one TPU chip, TPC-H SF1
    python chip_smoke.py --chips 4    # the mesh phase only, four chips

One process, the entry points a user calls, every phase fatal:

  1. `jax.devices()` must report a TPU (and, with `--chips 4`, four of
     them) — otherwise the script exits non-zero before any data loads;
  2. TPC-H at `--sf` generated from `--seed` into a `QueryEngine`;
  3. embedded: q1, q6, q3, q9, q18 twice each, each compared with the
     pandas oracle of `tests/tpch_util.py`, each on the path named in
     `EXPECTED_PATH`, scan superblocks resident on the TPU;
  4. served: gRPC and pgwire fronts on threads over the same engine,
     q1/q6 through both equal the embedded frames, row-table inserts read
     back by primary key, Health answers the TPU and GOOD.

With `--chips 4` only the mesh phase runs: the engine over a four-device
mesh, Q1 and a forced shuffle join against the pandas oracle, the
sharded inputs on four distinct devices.

The LAST line of stdout is `{"ok": true, "device": {...}}` and is printed
only when every phase passed. Timings on earlier lines are observations.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time

# The platform every phase must find. Tests rehearse the phases on the CPU
# by monkeypatching this name; no option or environment variable reaches it.
REQUIRED_PLATFORM = "tpu"

SMOKE_QUERIES = ("q1", "q6", "q3", "q9", "q18")
# `BENCH_r18.json` `paths`: all 22 fused at SF1. A query that is meant to
# take another path on the chip is named here — never "any path".
EXPECTED_PATH = {name: "fused" for name in SMOKE_QUERIES}
SERVED_QUERIES = ("q1", "q6")

_SHUFFLE_JOIN_SQL = (
    "select l_returnflag, count(*) as n, sum(l_extendedprice - "
    "o_totalprice * 0.0001) as s from lineitem, orders "
    "where l_orderkey = o_orderkey and l_quantity < 30 "
    "group by l_returnflag order by l_returnflag")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_devices(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is `chips` TPUs."""
    import jax
    import jaxlib
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                          # noqa: BLE001 — report only
        libtpu = "not installed"
    say(f"[smoke] jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu} platform={dev['platform']} "
        f"device_kind={dev['kind']!r} count={dev['count']}")
    check(dev["platform"] == REQUIRED_PLATFORM,
          f"platform is {dev['platform']!r}, need {REQUIRED_PLATFORM!r}: "
          "this script does not run on anything else")
    check(dev["count"] >= chips,
          f"{dev['count']} device(s), need {chips}")
    return dev


def check_native() -> None:
    from ydb_tpu import native
    ok = native.available()
    say(f"[smoke] native.available()={ok} g++={shutil.which('g++')}")
    check(ok or shutil.which("g++") is None,
          "native blob IO layer unavailable on a machine that has g++")


def load(sf: float, seed: int, shards: int = 1, mesh=None):
    from ydb_tpu.bench.tpch_gen import load_tpch
    from ydb_tpu.query import QueryEngine
    t0 = time.perf_counter()
    eng = QueryEngine(mesh=mesh)
    # one portion per shard on the mesh (SF1: 1.5 M lineitem rows per
    # shard), so the round-robin placement gives each device one shard
    data = load_tpch(eng.catalog, sf=sf, shards=shards, seed=seed,
                     portion_rows=1 << (20 if mesh is None else 21))
    rows = {n: t.num_rows for n, t in eng.catalog.tables.items()}
    say(f"[smoke] load sf={sf} seed={seed} shards={shards}: "
        f"lineitem={rows['lineitem']} total={sum(rows.values())} rows "
        f"in {time.perf_counter() - t0:.2f}s")
    return eng, data


def _oracle(name: str, data, got):
    from tests.tpch_util import oracle
    want = oracle(name, data)
    want.columns = list(got.columns)          # labels match by position
    return want


def _on_required_platform(arr) -> bool:
    return all(d.platform == REQUIRED_PLATFORM for d in arr.devices())


def check_superblocks_on_device(eng) -> int:
    """Every scan superblock the fused programs read sits on the TPU."""
    cache = eng.executor.device_cache
    with cache._mu:
        held = [(k, d, v) for k, (d, v, _n) in cache._entries.items()
                if k[0] == "sbc"]
    check(held, "no scan superblock is resident after the fused queries")
    for key, d, v in held:
        for a in (d, v):
            if a is not None and not _on_required_platform(a):
                raise SmokeFailure(
                    f"superblock column {key[-1]} is on {a.devices()}")
    return len(held)


def embedded_phase(eng, data) -> dict:
    """q1/q6/q3/q9/q18 twice each against the oracle; {name: frame}."""
    from tests.tpch_util import QUERIES, assert_frames_match
    from ydb_tpu.utils.metrics import GLOBAL
    frames = {}
    for name in SMOKE_QUERIES:
        c0, m0 = GLOBAL.get("prog/registered"), GLOBAL.get("prog/compile_ms")
        t0 = time.perf_counter()
        got = eng.query(QUERIES[name])
        first_ms = (time.perf_counter() - t0) * 1e3
        path = eng.executor.last_path
        compiles = int(GLOBAL.get("prog/registered") - c0)
        compile_ms = GLOBAL.get("prog/compile_ms") - m0
        c1 = GLOBAL.get("prog/registered")
        t0 = time.perf_counter()
        again = eng.query(QUERIES[name])
        second_ms = (time.perf_counter() - t0) * 1e3
        recompiles = int(GLOBAL.get("prog/registered") - c1)
        say(f"[smoke] embedded {name}: path={path} rows={len(got)} "
            f"first={first_ms:.1f}ms second={second_ms:.1f}ms "
            f"compiles={compiles} compile={compile_ms:.0f}ms "
            f"second_run_compiles={recompiles}")
        check(path == EXPECTED_PATH[name] and
              eng.executor.last_path == EXPECTED_PATH[name],
              f"{name} ran on path {path!r}, expected "
              f"{EXPECTED_PATH[name]!r}")
        want = _oracle(name, data, got)
        assert_frames_match(got, want, ordered=True)
        assert_frames_match(again, want, ordered=True)
        frames[name] = got
    n = check_superblocks_on_device(eng)
    say(f"[smoke] embedded: {n} superblock columns on "
        f"{REQUIRED_PLATFORM}")
    return frames


def _pg_frame(cols, rows, like):
    """Text rows of the pgwire front typed after the embedded frame."""
    import numpy as np
    import pandas as pd
    out = {}
    for i, c in enumerate(cols):
        vals = [r[i] for r in rows]
        kind = like[like.columns[i]].to_numpy().dtype.kind
        if kind == "f":
            out[c] = np.array([np.nan if v is None else float(v)
                               for v in vals], dtype=np.float64)
        elif kind in "iu":
            out[c] = np.array([int(v) for v in vals], dtype=np.int64)
        else:
            out[c] = np.array(vals, dtype=object)
    return pd.DataFrame(out, columns=list(cols))


def served_phase(eng, frames: dict) -> None:
    """gRPC + pgwire fronts on threads over the same engine (the way
    `cli.cmd_server` starts them); clients speak the wire only."""
    from tests.test_pgwire import PgClient
    from tests.tpch_util import QUERIES, assert_frames_match
    from ydb_tpu.server import Client, serve
    from ydb_tpu.server.pgwire import serve_pg

    t0 = time.perf_counter()
    server, port = serve(eng, port=0)
    pg = None
    try:
        pg = serve_pg(eng, port=0)
        grpc_c = Client(f"127.0.0.1:{port}", session_id="smoke")
        pg_c = PgClient(pg.port)
        pg_c.sock.settimeout(600)
        try:
            for name in SERVED_QUERIES:
                want = frames[name]
                t1 = time.perf_counter()
                got = grpc_c.query(QUERIES[name])
                g_ms = (time.perf_counter() - t1) * 1e3
                got.columns = list(want.columns)
                assert_frames_match(got, want, ordered=True)
                t1 = time.perf_counter()
                cols, rows, _tag = pg_c.query(QUERIES[name])
                p_ms = (time.perf_counter() - t1) * 1e3
                check(len(cols) == len(want.columns),
                      f"pgwire {name}: {len(cols)} columns")
                pgf = _pg_frame(list(want.columns), rows, want)
                assert_frames_match(pgf, want, ordered=True)
                say(f"[smoke] served {name}: grpc={g_ms:.1f}ms "
                    f"pgwire={p_ms:.1f}ms rows={len(want)}")

            # acknowledged writes are read back, one by one, by key
            pg_c.query("create table smoke_kv (k Int64 not null, v Double, "
                       "tag Utf8, primary key (k)) with (store = row)")
            written = {k: (k * 1.5, f"row{k}") for k in (1, 7, 42, 1000003)}
            for k, (v, tag) in written.items():
                _c, _r, ack = pg_c.query(
                    f"insert into smoke_kv (k, v, tag) "
                    f"values ({k}, {v!r}, '{tag}')")
                check(ack == "INSERT 0 1", f"insert k={k} answered {ack!r}")
            for k, (v, tag) in written.items():
                _c, rows, _t = pg_c.query(
                    f"select k, v, tag from smoke_kv where k = {k}")
                check(len(rows) == 1 and int(rows[0][0]) == k
                      and float(rows[0][1]) == v and rows[0][2] == tag,
                      f"pgwire read-back of k={k} gave {rows!r}")
                back = grpc_c.query(
                    f"select k, v, tag from smoke_kv where k = {k}")
                check(len(back) == 1 and int(back.k[0]) == k
                      and float(back.v[0]) == v and back.tag[0] == tag,
                      f"gRPC read-back of k={k} gave {back!r}")
            pg_c.query("drop table smoke_kv")
            say(f"[smoke] served: {len(written)} acknowledged inserts read "
                f"back by key through pgwire and gRPC")

            h = grpc_c.health()
            say(f"[smoke] health: {json.dumps(h, sort_keys=True)}")
            check(h.get("platform") == REQUIRED_PLATFORM,
                  f"Health platform is {h.get('platform')!r}")
            check(h.get("status") == "GOOD",
                  f"Health status is {h.get('status')!r}: "
                  f"{h.get('issues')}")
        finally:
            pg_c.close()
            grpc_c._channel.close()
    finally:
        server.stop(grace=1)
        if pg is not None:
            pg.stop()
    say(f"[smoke] served phase {time.perf_counter() - t0:.2f}s")


def _shuffle_join_oracle(data):
    from tests.tpch_util import frames
    f = frames(data)
    li, od = f["lineitem"], f["orders"]
    j = li[li.l_quantity < 30].merge(
        od[["o_orderkey", "o_totalprice"]], left_on="l_orderkey",
        right_on="o_orderkey")
    return j.assign(s=j.l_extendedprice - j.o_totalprice * 0.0001) \
        .groupby("l_returnflag", as_index=False) \
        .agg(n=("s", "size"), s=("s", "sum")) \
        .sort_values("l_returnflag")


def check_mesh_placement(eng, chips: int, label: str, before: dict) -> None:
    """Where the last mesh statement's data sat: the scanned lineitem
    portions spread over `chips` distinct devices with about 1/chips of
    the rows each, and every exchange fed from each of them alike."""
    from ydb_tpu.storage.mvcc import MAX_SNAPSHOT

    table = eng.catalog.table("lineitem")
    rows_of = {p.id: p.num_rows for sh in table.shards
               for p in sh.scan_sources(MAX_SNAPSHOT, None)[0]}
    cache = eng.executor.device_cache
    with cache._mu:
        held = [(k, d) for k, (d, _v, _n) in cache._entries.items()
                if len(k) == 3 and k[0] in rows_of and k[1] == "l_quantity"]
    per_dev: dict = {}
    for (pid, _col, _dev_id), d in held:
        (dev,) = d.devices()
        per_dev[dev] = per_dev.get(dev, 0) + rows_of[pid]
    total = sum(rows_of.values())
    say(f"[smoke] mesh {label}: scanned rows per device "
        f"{ {str(d): n for d, n in per_dev.items()} } of {total}")
    check(len(per_dev) == chips and
          all(d.platform == REQUIRED_PLATFORM for d in per_dev),
          f"{label}: scan portions sit on {len(per_dev)} device(s) "
          f"{sorted(map(str, per_dev))}, need {chips} distinct "
          f"{REQUIRED_PLATFORM} devices")
    check(sum(per_dev.values()) == total and
          all(abs(n / total - 1 / chips) < 0.25 / chips
              for n in per_dev.values()),
          f"{label}: rows per device {sorted(per_dev.values())} are not "
          f"about 1/{chips} of {total} each")

    # rows each exchange of this statement was fed, by the device that
    # held them: the delta of the `mesh/exchange_rows/<kind>/dev<id>`
    # counters since `before`
    after = _exchange_rows()
    exchanges: dict = {}
    for name, rows in after.items():
        kind, dev = name.rsplit("/", 1)
        delta = int(rows - before.get(name, 0))
        if delta:
            exchanges.setdefault(kind, {})[dev] = delta
    check(exchanges, f"{label}: no mesh exchange ran")
    mesh_devs = {f"dev{d.id}" for d in eng.executor.mesh.devices.flat}
    for kind, live in exchanges.items():
        say(f"[smoke] mesh {label}: {kind} exchange input rows per device "
            f"{live}")
        check(set(live) == mesh_devs and len(live) == chips,
              f"{label}: {kind} input sat on {sorted(live)}, need every "
              f"one of {sorted(mesh_devs)}")
        check(all(abs(n / sum(live.values()) - 1 / chips) < 0.25 / chips
                  for n in live.values()),
              f"{label}: {kind} rows per device {live} are not about "
              f"1/{chips} each")


def _exchange_rows() -> dict:
    from ydb_tpu.utils.metrics import GLOBAL
    prefix = "mesh/exchange_rows/"
    return {k[len(prefix):]: v for k, v in GLOBAL.snapshot().items()
            if k.startswith(prefix)}


def mesh_phase(sf: float, seed: int, chips: int) -> None:
    """Four chips: the executor's mesh lanes against the pandas oracle."""
    from tests.tpch_util import QUERIES, assert_frames_match
    from ydb_tpu.parallel import make_mesh

    mesh = make_mesh(chips)
    check(mesh.devices.size == chips and
          len({d.id for d in mesh.devices.flat}) == chips,
          f"mesh holds {mesh.devices.size} device(s), need {chips}")
    eng, data = load(sf, seed, shards=chips, mesh=mesh)

    for label, sql, want_path, oracle_fn in (
            ("q1", QUERIES["q1"], "distributed",
             lambda got: _oracle("q1", data, got)),
            ("shuffle-join", _SHUFFLE_JOIN_SQL, "distributed-shuffle-join",
             lambda got: _shuffle_join_oracle(data))):
        if label == "shuffle-join":
            # no device may hold the whole build: force the exchange
            eng.executor.dist_broadcast_budget_bytes = 1
        before = _exchange_rows()
        t0 = time.perf_counter()
        got = eng.query(sql)
        first_ms = (time.perf_counter() - t0) * 1e3
        path = eng.executor.last_path
        check_mesh_placement(eng, chips, label, before)
        t0 = time.perf_counter()
        again = eng.query(sql)
        second_ms = (time.perf_counter() - t0) * 1e3
        say(f"[smoke] mesh {label}: path={path} rows={len(got)} "
            f"first={first_ms:.1f}ms second={second_ms:.1f}ms")
        check(path == want_path, f"{label} ran on {path!r}, expected "
                                 f"{want_path!r}")
        want = oracle_fn(got)
        want.columns = list(got.columns)
        assert_frames_match(got, want, ordered=True)
        assert_frames_match(again, want, ordered=True)


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=19920101,
                    help="data generator seed")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the mesh phase, on four chips")
    args = ap.parse_args(argv)

    t_all = time.perf_counter()
    dev = require_devices(args.chips)
    import ydb_tpu  # noqa: F401 — x64 + compile-cache placement
    import jax
    say(f"[smoke] compile cache: "
        f"{jax.config.jax_compilation_cache_dir or 'off'}")
    check_native()

    if args.chips == 4:
        t0 = time.perf_counter()
        mesh_phase(args.sf, args.seed, args.chips)
        say(f"[smoke] mesh phase {time.perf_counter() - t0:.2f}s")
    else:
        eng, data = load(args.sf, args.seed)
        t0 = time.perf_counter()
        frames = embedded_phase(eng, data)
        say(f"[smoke] embedded phase {time.perf_counter() - t0:.2f}s")
        served_phase(eng, frames)
    say(f"[smoke] wall {time.perf_counter() - t_all:.2f}s")
    return dev


def main(argv=None) -> int:
    try:
        dev = run(argv)
    except BaseException as e:                 # noqa: BLE001 — every phase
        if isinstance(e, SystemExit) and e.code in (0, None):
            raise                              # argparse --help
        import traceback
        traceback.print_exc()
        print(f"[smoke] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
