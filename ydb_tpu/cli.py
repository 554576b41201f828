"""Command-line interface — server, interactive SQL, benchmark workloads.

The `ydb` CLI analog (`ydb/public/lib/ydb_cli`): `server` plays `ydbd
server`, `sql` the query client, and `workload tpch init/run` the
benchmark runner (`commands/tpch.h:9-66`, shared runner
`benchmark_utils.cpp` — per-query times + geomean).

    python -m ydb_tpu.cli server --data-dir /path --port 2136
    python -m ydb_tpu.cli sql "select 1 as x" [--endpoint host:port]
    python -m ydb_tpu.cli workload tpch init --sf 0.1 [--data-dir /path]
    python -m ydb_tpu.cli workload tpch run [--queries q1,q6] [--repeat 3]
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


def _embedded_engine(args):
    from ydb_tpu.query import QueryEngine
    return QueryEngine(data_dir=getattr(args, "data_dir", None))


def cmd_server(args) -> int:
    from ydb_tpu.server import serve
    eng = _embedded_engine(args)
    server, port = serve(eng, port=args.port)
    print(f"ydb_tpu server listening on 127.0.0.1:{port} "
          f"(data_dir={args.data_dir})", flush=True)
    fronts = []
    try:
        if args.pg_port is not None:
            from ydb_tpu.server.pgwire import serve_pg
            pg = serve_pg(eng, port=args.pg_port)
            fronts.append(pg)
            print(f"pgwire listening on 127.0.0.1:{pg.port}", flush=True)
        if args.http_port is not None:
            from ydb_tpu.server.http import serve_http
            h = serve_http(eng, port=args.http_port)
            fronts.append(h)
            print(f"http listening on 127.0.0.1:{h.port}", flush=True)
        if args.kafka_port is not None:
            from ydb_tpu.server.kafka import serve_kafka
            k = serve_kafka(eng, port=args.kafka_port, auto_create=True)
            fronts.append(k)
            print(f"kafka listening on 127.0.0.1:{k.port}", flush=True)
        server.wait_for_termination()
    except KeyboardInterrupt:
        pass
    finally:
        # a bind failure in a LATER front must not leave the gRPC server
        # (non-daemon threads) holding the process open with nothing
        # serving what was asked
        server.stop(grace=1)
        for fr in fronts:
            fr.stop()
    return 0


def cmd_sql(args) -> int:
    if args.endpoint:
        from ydb_tpu.server import Client
        client = Client(args.endpoint)
        df = client.query(args.query)
    else:
        df = _embedded_engine(args).query(args.query)
    print(df.to_string(index=False))
    return 0


def _ensure_repo_on_path() -> None:
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (repo, "."):
        if p not in sys.path:
            sys.path.insert(0, p)


def _tpch_loader(catalog, sf):
    from ydb_tpu.bench.tpch_gen import load_tpch
    load_tpch(catalog, sf=sf)


def _clickbench_loader(catalog, sf):
    from ydb_tpu.bench.clickbench_gen import load_hits
    load_hits(catalog, n_rows=max(1000, int(sf * 1e6)))


def _tpcds_loader(catalog, sf):
    from ydb_tpu.bench.tpcds_gen import load_tpcds
    load_tpcds(catalog, sf=sf)


# workload name -> (fact table, loader, queries module)
WORKLOADS = {
    "tpch": ("lineitem", _tpch_loader, "tests.tpch_util"),
    "clickbench": ("hits", _clickbench_loader, "tests.clickbench_util"),
    "tpcds": ("store_sales", _tpcds_loader, "tests.tpcds_util"),
}


def _workload_queries(workload: str, names):
    import importlib
    _ensure_repo_on_path()
    qs = importlib.import_module(WORKLOADS[workload][2]).QUERIES
    if names:
        return {n: qs[n] for n in names}
    return dict(qs)


def _load_workload(eng, workload: str, args) -> None:
    fact, loader, _qm = WORKLOADS[workload]
    if not eng.catalog.has(fact):
        loader(eng.catalog, args.sf)


def cmd_workload_init(args) -> int:
    eng = _embedded_engine(args)
    t0 = time.perf_counter()
    _load_workload(eng, args.workload, args)
    fact = WORKLOADS[args.workload][0]
    rows = eng.catalog.table(fact).num_rows
    print(f"loaded {args.workload} sf={args.sf}: {rows} {fact} rows "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)
    if args.data_dir:
        print(f"durable at {args.data_dir}")
    return 0


def cmd_workload_run(args) -> int:
    queries = _workload_queries(
        args.workload, args.queries.split(",") if args.queries else None)
    if args.endpoint:
        from ydb_tpu.server import Client
        runner = Client(args.endpoint).query
        eng = None
    else:
        eng = _embedded_engine(args)
        _load_workload(eng, args.workload, args)
        runner = eng.query

    times = {}
    failed = []
    for name, q in queries.items():
        try:
            runner(q)                       # warm-up (compile)
            best = math.inf
            for _ in range(args.repeat):
                t0 = time.perf_counter()
                runner(q)
                best = min(best, time.perf_counter() - t0)
            times[name] = best
            print(f"{name:>5}: {best * 1000:9.1f} ms", flush=True)
        except Exception as e:              # noqa: BLE001 — benchmark runner
            print(f"{name:>5}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
    if times:
        geo = math.exp(sum(math.log(t) for t in times.values())
                       / len(times))
        print(f"geomean over {len(times)} queries: {geo * 1000:.1f} ms")
        print(json.dumps({"metric": f"{args.workload}_geomean_ms",
                          "value": round(geo * 1000, 1),
                          "queries": len(times)}))
    if failed:
        print(f"{len(failed)} of {len(queries)} queries FAILED: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ydb_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("server", help="run the gRPC query service")
    ps.add_argument("--port", type=int, default=2136)
    ps.add_argument("--pg-port", type=int, default=None,
                    help="also serve the PostgreSQL wire protocol")
    ps.add_argument("--http-port", type=int, default=None,
                    help="also serve the HTTP/JSON API")
    ps.add_argument("--kafka-port", type=int, default=None,
                    help="also serve the Kafka wire protocol (topics)")
    ps.add_argument("--data-dir", default=None)
    ps.set_defaults(fn=cmd_server)

    pq = sub.add_parser("sql", help="run one SQL statement")
    pq.add_argument("query")
    pq.add_argument("--endpoint", default=None,
                    help="host:port of a server (default: embedded engine)")
    pq.add_argument("--data-dir", default=None)
    pq.set_defaults(fn=cmd_sql)

    pw = sub.add_parser("workload", help="benchmark workloads")
    wsub = pw.add_subparsers(dest="workload", required=True)
    for wname in ("tpch", "clickbench", "tpcds"):
        pt = wsub.add_parser(wname)
        tsub = pt.add_subparsers(dest="action", required=True)
        ti = tsub.add_parser("init")
        ti.add_argument("--sf", type=float, default=0.1)
        ti.add_argument("--data-dir", default=None)
        ti.set_defaults(fn=cmd_workload_init)
        tr = tsub.add_parser("run")
        tr.add_argument("--queries", default=None,
                        help="comma list, e.g. q1,q6")
        tr.add_argument("--repeat", type=int, default=3)
        tr.add_argument("--sf", type=float, default=0.1)
        tr.add_argument("--endpoint", default=None)
        tr.add_argument("--data-dir", default=None)
        tr.set_defaults(fn=cmd_workload_run)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
