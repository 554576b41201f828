"""System views: virtual `.sys/...` tables served through the scan path.

The reference exposes cluster/runtime state as virtual tables under
`.sys` (`ydb/core/sys_view/common/schema.h`: partition_stats,
query_metrics_one_minute, top_queries_by_duration_*, …), deliberately
served through the SAME scan protocol as user tables
(`sys_view/scan.cpp`) so every SQL feature composes with them. Same
stance here: a sysview materializes to a transient column table at plan
time and the normal engine executes the query over it — joins, filters,
aggregates and EXPLAIN all work on `.sys` views for free.
"""

from __future__ import annotations

import pandas as pd

from ydb_tpu.core.block import HostBlock

PREFIX = ".sys/"

VIEWS = ("tables", "partition_stats", "counters", "query_metrics",
         "top_queries_by_duration", "dq_stage_stats", "query_profiles",
         "cluster_nodes", "query_memory", "device_transfers",
         "query_critical_path", "compiled_programs", "progstore",
         "materialized_views")


def is_sysview(name: str) -> bool:
    return name.startswith(PREFIX)


def sysview_block(engine, name: str) -> HostBlock:
    view = name[len(PREFIX):]
    if view == "tables":
        rows = [{
            "table_name": n,
            "store": getattr(t, "store_kind", "column"),
            "shards": len(getattr(t, "shards", [])) or 1,
            "rows": int(t.num_rows),
            "data_version": int(getattr(t, "data_version", 0)),
        } for n, t in sorted(engine.catalog.tables.items())
            if not getattr(t, "transient", False)]
        return _block(rows, [("table_name", str), ("store", str),
                             ("shards", "int64"), ("rows", "int64"),
                             ("data_version", "int64")])
    if view == "partition_stats":
        rows = []
        for n, t in sorted(engine.catalog.tables.items()):
            if getattr(t, "transient", False) \
                    or getattr(t, "store_kind", "column") == "row":
                continue
            for s in t.shards:
                rows.append({
                    "table_name": n, "shard_id": s.shard_id,
                    "portions": len(s.portions),
                    "rows": int(sum(p.num_rows for p in s.portions)),
                    "staged_inserts": len(s.inserts),
                })
        return _block(rows, [("table_name", str), ("shard_id", "int64"),
                             ("portions", "int64"), ("rows", "int64"),
                             ("staged_inserts", "int64")])
    if view == "counters":
        snap = engine.counters()
        rows = [{"counter": k, "value": float(v)}
                for k, v in snap.items()]
        return _block(rows, [("counter", str), ("value", "float64")])
    if view in ("query_metrics", "top_queries_by_duration"):
        hist = list(engine.query_history)
        if view == "top_queries_by_duration":
            hist = sorted(hist, key=lambda s: -s.total_ms)[:32]
        rows = [{
            "sql": st.sql, "kind": st.kind,
            "total_ms": st.total_ms, "parse_ms": st.parse_ms,
            "plan_ms": st.plan_ms, "execute_ms": st.execute_ms,
            "rows_out": int(st.rows_out),
            "path": st.path or "portioned",
            "cache_hit": bool(st.plan_cache_hit),
        } for st in hist]
        return _block(rows, [("sql", str), ("kind", str),
                             ("total_ms", "float64"),
                             ("parse_ms", "float64"),
                             ("plan_ms", "float64"),
                             ("execute_ms", "float64"),
                             ("rows_out", "int64"), ("path", str),
                             ("cache_hit", "bool")])
    if view == "dq_stage_stats":
        # per-(stage, worker) task stats of recent DQ graph runs — the
        # TDqTaskRunnerStatsView seat (filled by dq/runner.py)
        rows = [{
            "trace_id": int(r.get("trace_id", 0)),
            "graph": r.get("graph", ""), "stage": r.get("stage", ""),
            "worker": r.get("worker", ""), "state": r.get("state", ""),
            "attempts": int(r.get("attempts", 0)),
            "channel": str(r.get("channel", "")),
            "rows": int(r.get("rows", 0)),
            "bytes": int(r.get("bytes", 0)),
            "frames": int(r.get("frames", 0)),
            "plane": str(r.get("plane", "host")),
            "ici_bytes": int(r.get("ici_bytes", 0)),
            "pad_live_bytes": int(r.get("pad_live_bytes", 0)),
            "pad_padded_bytes": int(r.get("pad_padded_bytes", 0)),
            "pad_efficiency": float(r.get("pad_efficiency", 0.0) or 0.0),
            "exec_ms": float(r.get("exec_ms", 0.0)),
            "flush_ms": float(r.get("flush_ms", 0.0)),
            "input_wait_ms": float(r.get("input_wait_ms", 0.0)),
            "backpressure_wait_ms": float(
                r.get("backpressure_wait_ms", 0.0)),
        } for r in list(getattr(engine, "dq_stage_stats", []))]
        return _block(rows, [("trace_id", "int64"), ("graph", str),
                             ("stage", str), ("worker", str),
                             ("state", str), ("attempts", "int64"),
                             ("channel", str),
                             ("rows", "int64"), ("bytes", "int64"),
                             ("frames", "int64"), ("plane", str),
                             ("ici_bytes", "int64"),
                             ("pad_live_bytes", "int64"),
                             ("pad_padded_bytes", "int64"),
                             ("pad_efficiency", "float64"),
                             ("exec_ms", "float64"),
                             ("flush_ms", "float64"),
                             ("input_wait_ms", "float64"),
                             ("backpressure_wait_ms", "float64")])
    if view == "query_profiles":
        # the last-N assembled profiles (sampled statements + DQ runs):
        # wall, span count, and the device-timeline phase rollup
        rows = []
        for p in list(getattr(engine, "profiles", [])):
            ph = p.get("phases") or {}
            rows.append({
                "trace_id": int(p.get("trace_id", 0)),
                "sql": p.get("sql", ""), "kind": p.get("kind", ""),
                "total_ms": float(p.get("total_ms", 0.0)),
                "rows_out": int(p.get("rows_out", 0)),
                "n_spans": int(p.get("n_spans", 0)),
                "n_stages": len(p.get("stages") or []),
                "compile_ms": float(ph.get("compile_ms", 0.0)),
                "build_ms": float(ph.get("build_ms", 0.0)),
                "upload_ms": float(ph.get("upload_ms", 0.0)),
                "dispatch_ms": float(ph.get("dispatch_ms", 0.0)),
                "device_ms": float(ph.get("device_ms", 0.0)),
                "readout_ms": float(ph.get("readout_ms", 0.0)),
            })
        return _block(rows, [("trace_id", "int64"), ("sql", str),
                             ("kind", str), ("total_ms", "float64"),
                             ("rows_out", "int64"), ("n_spans", "int64"),
                             ("n_stages", "int64"),
                             ("compile_ms", "float64"),
                             ("build_ms", "float64"),
                             ("upload_ms", "float64"),
                             ("dispatch_ms", "float64"),
                             ("device_ms", "float64"),
                             ("readout_ms", "float64")])
    if view == "cluster_nodes":
        # Hive membership/placement (the `ds_clusters`/nodes sysview
        # seat): one row per registered worker, lease liveness included.
        # Empty when no Hive is attached to this engine — the view
        # exists on every node, the CONTROL PLANE lives on one.
        hive = getattr(engine, "hive", None)
        if hive is not None:
            # membership-level sweep only: the view must not show
            # expired leases as alive, but a monitoring SELECT must
            # never trigger re-placement DATA MOVEMENT (hive.sweep()
            # replays shard images; the query path owns that)
            hive.membership.sweep()
        rows = [{
            "node_id": r["node_id"], "endpoint": r["endpoint"],
            "state": r["state"],
            "lease_ms_left": float(r["lease_ms_left"]),
            "heartbeats": int(r["heartbeats"]),
            "capacity": float(r["capacity"]),
            "load": float(r["load"]), "shards": r["shards"],
            "stale": bool(r["stale"]),
        } for r in (hive.rows() if hive is not None else [])]
        return _block(rows, [("node_id", str), ("endpoint", str),
                             ("state", str),
                             ("lease_ms_left", "float64"),
                             ("heartbeats", "int64"),
                             ("capacity", "float64"),
                             ("load", "float64"), ("shards", str),
                             ("stale", "bool")])
    if view == "query_memory":
        # per-statement resource-ledger rollups (engine.memory_stats,
        # filled when a statement's ledger closes — utils/memledger.py):
        # the bytes companion of `query_metrics`
        rows = [{
            "sql": r.get("sql", ""), "kind": r.get("kind", ""),
            "peak_bytes": int(r.get("peak_bytes", 0)),
            "alloc_bytes": int(r.get("alloc_bytes", 0)),
            "live_bytes": int(r.get("live_bytes", 0)),
            "padded_bytes": int(r.get("padded_bytes", 0)),
            "waste_bytes": int(r.get("waste_bytes", 0)),
            "pad_efficiency": float(r.get("pad_efficiency") or 0.0),
            "transfers": int(r.get("transfers", 0)),
            "transfer_bytes": int(r.get("transfer_bytes", 0)),
            "to_pandas_in_plan": int(r.get("to_pandas_in_plan", 0)),
            "admission_est_bytes":
                int(r.get("admission_est_bytes") or 0),
            "est_error_pct": float(r.get("est_error_pct") or 0.0),
        } for r in list(getattr(engine, "memory_stats", []))]
        return _block(rows, [("sql", str), ("kind", str),
                             ("peak_bytes", "int64"),
                             ("alloc_bytes", "int64"),
                             ("live_bytes", "int64"),
                             ("padded_bytes", "int64"),
                             ("waste_bytes", "int64"),
                             ("pad_efficiency", "float64"),
                             ("transfers", "int64"),
                             ("transfer_bytes", "int64"),
                             ("to_pandas_in_plan", "int64"),
                             ("admission_est_bytes", "int64"),
                             ("est_error_pct", "float64")])
    if view == "query_critical_path":
        # per-statement critical-path rollups (engine.critpath_stats,
        # utils/critpath.py): the blocking-chain class decomposition —
        # which chain of spans bounded each query's wall, by class.
        # Empty under YDB_TPU_CRITPATH=0.
        rows = [{
            "trace_id": int(r.get("trace_id", 0)),
            "sql": r.get("sql", ""), "kind": r.get("kind", ""),
            "wall_ms": float(r.get("wall_ms", 0.0)),
            "coverage": float(r.get("coverage", 0.0)),
            "connected": bool(r.get("connected", False)),
            "non_device_ms": float(r.get("non_device_ms", 0.0)),
            "device_execute_ms": float(r.get("device_execute_ms", 0.0)),
            "compile_ms": float(r.get("compile_ms", 0.0)),
            "host_transfer_ms": float(r.get("host_transfer_ms", 0.0)),
            "host_lane_ms": float(r.get("host_lane_ms", 0.0)),
            "channel_wait_ms": float(r.get("channel_wait_ms", 0.0)),
            "admission_wait_ms": float(r.get("admission_wait_ms", 0.0)),
            "scheduler_gap_ms": float(r.get("scheduler_gap_ms", 0.0)),
            "dominant_span": r.get("dominant_span", ""),
            "dominant_class": r.get("dominant_class", ""),
            "dominant_ms": float(r.get("dominant_ms", 0.0)),
        } for r in list(getattr(engine, "critpath_stats", []))]
        return _block(rows, [("trace_id", "int64"), ("sql", str),
                             ("kind", str), ("wall_ms", "float64"),
                             ("coverage", "float64"),
                             ("connected", "bool"),
                             ("non_device_ms", "float64"),
                             ("device_execute_ms", "float64"),
                             ("compile_ms", "float64"),
                             ("host_transfer_ms", "float64"),
                             ("host_lane_ms", "float64"),
                             ("channel_wait_ms", "float64"),
                             ("admission_wait_ms", "float64"),
                             ("scheduler_gap_ms", "float64"),
                             ("dominant_span", str),
                             ("dominant_class", str),
                             ("dominant_ms", "float64")])
    if view == "compiled_programs":
        # the compiled-program inventory (utils/progstats.py, process-
        # wide like device_transfers): one row per captured executable —
        # cache hit/miss/eviction counts, compile wall, the XLA cost +
        # memory analysis, cumulative measured device ms and the
        # roofline verdict. `name` is the XLA module's name, as a device
        # trace shows it (`jit_lineitem_g_ab12cd`); `device_ms` is run
        # WITHOUT wait: time queued behind another statement's program
        # is `prog/queue_ms`. Evicted entries persist marked `evicted`;
        # `cost` is an explicit 'unavailable' where the backend
        # withholds analysis (never fabricated zeros). Empty under
        # YDB_TPU_PROGSTATS=0.
        from ydb_tpu.utils.progstats import inventory_rows
        rows = [{
            "program": r["program"], "name": r["name"],
            "kind": r["kind"],
            "state": r["state"], "source": r["source"],
            "hits": int(r["hits"]),
            "misses": int(r["misses"]),
            "evictions": int(r["evictions"]),
            "compiles": int(r["compiles"]),
            "compile_ms": float(r["compile_ms"]),
            "cost": r["cost"],
            "flops": float(r["flops"]),
            "transcendentals": float(r["transcendentals"]),
            "bytes_accessed": float(r["bytes_accessed"]),
            "output_bytes": float(r["output_bytes"]),
            "hlo_ops": int(r["hlo_ops"]),
            "arg_bytes": int(r["arg_bytes"]),
            "out_bytes": int(r["out_bytes"]),
            "temp_bytes": int(r["temp_bytes"]),
            "code_bytes": int(r["code_bytes"]),
            "execs": int(r["execs"]),
            "device_ms": float(r["device_ms"]),
            "device_ms_max": float(r["device_ms_max"]),
            "achieved_gflops": float(r["achieved_gflops"]),
            "achieved_gbps": float(r["achieved_gbps"]),
            "intensity": float(r["intensity"]),
            "utilization_pct": float(r["utilization_pct"]),
            "bound_class": r["bound_class"],
        } for r in inventory_rows()]
        return _block(rows, [("program", str), ("name", str),
                             ("kind", str),
                             ("state", str), ("source", str),
                             ("hits", "int64"),
                             ("misses", "int64"),
                             ("evictions", "int64"),
                             ("compiles", "int64"),
                             ("compile_ms", "float64"), ("cost", str),
                             ("flops", "float64"),
                             ("transcendentals", "float64"),
                             ("bytes_accessed", "float64"),
                             ("output_bytes", "float64"),
                             ("hlo_ops", "int64"),
                             ("arg_bytes", "int64"),
                             ("out_bytes", "int64"),
                             ("temp_bytes", "int64"),
                             ("code_bytes", "int64"),
                             ("execs", "int64"),
                             ("device_ms", "float64"),
                             ("device_ms_max", "float64"),
                             ("achieved_gflops", "float64"),
                             ("achieved_gbps", "float64"),
                             ("intensity", "float64"),
                             ("utilization_pct", "float64"),
                             ("bound_class", str)])
    if view == "progstore":
        # the persistent compiled-program store (ydb_tpu/progstore):
        # one row — index size, on-disk footprint, per-kind entry
        # counts, this process's load/save activity, the cumulative
        # store counters, and the admission backlog the compile-ahead
        # lane overlaps with. A disabled store reports root='' with
        # zero entries (never a fabricated store).
        from ydb_tpu.progstore import store as _pstore
        st = _pstore.stats()
        bl = engine.admission.backlog() \
            if hasattr(engine, "admission") else {}
        rows = [{
            "root": st["root"], "entries": int(st["entries"]),
            "objects": int(st["objects"]),
            "object_bytes": int(st["object_bytes"]),
            "fused": int(st["kinds"].get("fused", 0)),
            "batched": int(st["kinds"].get("batched", 0)),
            "program": int(st["kinds"].get("program", 0)),
            "loads": int(st["loads"]), "saves": int(st["saves"]),
            "hits": int(st["hits"]), "misses": int(st["misses"]),
            "writes": int(st["writes"]), "corrupt": int(st["corrupt"]),
            "refused": int(st["refused"]), "errors": int(st["errors"]),
            "env": st["env"], "device": st["device"],
            "admission_active": int(bl.get("active", 0)),
            "admission_in_flight_bytes":
                int(bl.get("in_flight_bytes", 0)),
        }]
        return _block(rows, [("root", str), ("entries", "int64"),
                             ("objects", "int64"),
                             ("object_bytes", "int64"),
                             ("fused", "int64"), ("batched", "int64"),
                             ("program", "int64"), ("loads", "int64"),
                             ("saves", "int64"), ("hits", "int64"),
                             ("misses", "int64"), ("writes", "int64"),
                             ("corrupt", "int64"),
                             ("refused", "int64"), ("errors", "int64"),
                             ("env", str), ("device", str),
                             ("admission_active", "int64"),
                             ("admission_in_flight_bytes", "int64")])
    if view == "materialized_views":
        # the continuous-query registry (ydb_tpu/views/): one row per
        # view — source, CDC topic, the watermark plan_step its state is
        # exact at, current lag in coordinator steps, state size, fold/
        # rebuild activity, and the degraded flag (permanent base-query
        # fallback after the bounds escape)
        rows = [{
            "name": r["name"], "source": r["source"],
            "kind": r["kind"], "topic": r["topic"],
            "watermark_step": int(r["watermark_step"]),
            "lag_versions": int(r["lag_versions"]),
            "state_rows": int(r["state_rows"]),
            "state_bytes": int(r["state_bytes"]),
            "folds": int(r["folds"]), "rebuilds": int(r["rebuilds"]),
            "degraded": bool(r["degraded"]),
        } for r in engine.views.sysview_rows()]
        return _block(rows, [("name", str), ("source", str),
                             ("kind", str), ("topic", str),
                             ("watermark_step", "int64"),
                             ("lag_versions", "int64"),
                             ("state_rows", "int64"),
                             ("state_bytes", "int64"),
                             ("folds", "int64"), ("rebuilds", "int64"),
                             ("degraded", "bool")])
    if view == "device_transfers":
        # the host-transfer flight recorder's recent-transfer ring
        # (utils/memledger.py, process-wide): one row per recorded
        # device→host readback — plus device→device stage handoffs
        # (`device_to_device` true), which never cross the link —
        # newest last
        from ydb_tpu.utils.memledger import transfer_ring
        rows = [{
            "seq": int(r["seq"]), "site": r["site"],
            "bytes": int(r["bytes"]), "count": int(r["count"]),
            "boundary": bool(r["boundary"]),
            "to_pandas_in_plan": bool(r["to_pandas_in_plan"]),
            "device_to_device": bool(r.get("device_to_device", False)),
        } for r in transfer_ring()]
        return _block(rows, [("seq", "int64"), ("site", str),
                             ("bytes", "int64"), ("count", "int64"),
                             ("boundary", "bool"),
                             ("to_pandas_in_plan", "bool"),
                             ("device_to_device", "bool")])
    raise KeyError(f"unknown system view {name!r} "
                   f"(have: {', '.join(PREFIX + v for v in VIEWS)})")


def _block(rows: list, spec: list) -> HostBlock:
    """Typed block even when empty (object-dtype inference would fail)."""
    df = pd.DataFrame(rows, columns=[n for (n, _) in spec])
    for n, dtype in spec:
        if dtype is str:
            df[n] = df[n].astype(object).where(df[n].notna(), "")
        else:
            df[n] = df[n].fillna(0).astype(dtype)
    return HostBlock.from_pandas(df)
