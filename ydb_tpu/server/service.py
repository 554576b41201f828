"""gRPC query service — the public API surface.

The reference exposes Ydb.* gRPC services (`ydb/public/api/grpc/
ydb_query_v1.proto` QueryService.ExecuteQuery, routed by
`grpc_services/grpc_request_proxy.cpp` into KQP). This server keeps the
same shape — a network QueryService speaking gRPC — with JSON message
bodies via custom (de)serializers instead of generated protobuf stubs
(grpc-python supports arbitrary serializers; the wire protocol is still
HTTP/2 gRPC framing).

Methods (service `ydb_tpu.QueryService`):
  ExecuteQuery  {sql, session_id?} → {columns, rows, stats} | {error}
                session_id scopes interactive transactions (BEGIN/COMMIT
                land on that session's state, the session-actor model)
  Counters      {} → {counters}
  Ping          {} → {ok: true}

Statement execution is serialized under one lock: the engine's caches and
the single TPU dispatch stream are not thread-safe, and the reference
likewise runs a session's statements sequentially.
"""

from __future__ import annotations

import json
import os
import threading
from concurrent import futures


def _ser(obj) -> bytes:
    return json.dumps(obj).encode()


def _deser(data: bytes):
    return json.loads(data.decode()) if data else {}


SERVICE = "ydb_tpu.QueryService"

# every shuffle temp the router materializes via ChannelOpen carries this
# prefix (`cluster/router.py` temp_of) — the channel RPCs refuse to touch
# tables outside the namespace, so a (even authed) caller can never drop
# or replace a real user table through the exchange plane
SHUFFLE_TMP_PREFIX = "__xj_"


def _frame_rows(df) -> list:
    """JSON-safe row lists (NaN/NaT → None, numpy scalars unboxed)."""
    rows = []
    for row in df.itertuples(index=False):
        out = []
        for v in row:
            if v is None or (isinstance(v, float) and v != v):
                out.append(None)
            elif hasattr(v, "item"):
                out.append(v.item())
            else:
                out.append(v)
        rows.append(out)
    return rows


def _result_payload(block, stats) -> dict:
    df = block.to_pandas()
    rows = _frame_rows(df)
    return {
        "columns": list(df.columns),
        "rows": rows,
        "stats": {
            "total_ms": stats.total_ms,
            "rows_out": stats.rows_out,
            "plan_cache_hit": stats.plan_cache_hit,
            "path": stats.path or "portioned",
        } if stats is not None else {},
    }


def health_snapshot(engine) -> dict:
    """The engine-level health payload, shared by the gRPC Health RPC
    and LocalWorker.health so the two surfaces cannot drift (graftlint
    rpc-surface discipline). Lock-free by design — a liveness probe
    must answer while a long query holds the execution lock. Callers
    layer their transport-specific fields (sessions, uptime) on top."""
    import jax
    tables = [n for n, t in list(engine.catalog.tables.items())
              if not getattr(t, "transient", False)]
    issues = []
    try:
        devs = jax.devices()
        platform = devs[0].platform if devs else "none"
    except Exception as e:                   # noqa: BLE001
        platform, issues = "unavailable", [f"device: {e}"]
    return {
        "status": "GOOD" if not issues else "DEGRADED",
        "issues": issues,
        "tables": len(tables),
        "topics": len(engine.topics),
        "durable": engine.catalog.store is not None,
        "platform": platform,
    }


MAX_SESSIONS = 256

import time as _time  # noqa: E402

_STARTED = _time.monotonic()


class QueryServicer:
    def __init__(self, engine, max_sessions: int = MAX_SESSIONS,
                 token: str = ""):
        import os
        import threading
        from collections import OrderedDict
        self.engine = engine
        # the engine locks its own write path internally now; SELECTs run
        # concurrently across the gRPC thread pool over MVCC snapshots.
        # This lock only guards the servicer's session table.
        self._lock = threading.Lock()
        self._sessions: "OrderedDict" = OrderedDict()   # guarded-by: _lock
        self._max_sessions = max_sessions
        # minimal bearer auth (ydb/core/security token check, radically
        # simplified): empty = open access; Ping/Health stay open (probes)
        self._token = token or os.environ.get("YDB_TPU_AUTH_TOKEN", "")
        # concurrent-RPC gauge: worker threads drive the engine's query
        # pipeline directly, so this also shows how many RPCs genuinely
        # overlap dispatch/readout (exported with engine.counters())
        self._rpc_mu = threading.Lock()
        self._rpc_inflight = 0           # guarded-by: _rpc_mu

    def _rpc_enter(self, gauge: str) -> None:
        from ydb_tpu.utils.metrics import GLOBAL
        with self._rpc_mu:
            self._rpc_inflight += 1
            # lint: allow-counters(gauge = server/rpc_in_flight, registered)
            GLOBAL.set(gauge, self._rpc_inflight)

    def _rpc_exit(self, gauge: str) -> None:
        from ydb_tpu.utils.metrics import GLOBAL
        with self._rpc_mu:
            self._rpc_inflight -= 1
            # lint: allow-counters(gauge = server/rpc_in_flight, registered)
            GLOBAL.set(gauge, self._rpc_inflight)

    def _authed(self, request) -> bool:
        import hmac
        return not self._token or hmac.compare_digest(
            str(request.get("token", "")), self._token)

    def _session_locked(self, session_id):
        """Resolve-or-create a session. `_locked`: the CALLER holds
        `_lock` — gRPC pool threads resolve sessions concurrently, and
        unlocked two requests with one fresh session_id both built an
        engine session (the loser leaked, staged tx and all) while the
        LRU popitem raced close_session's pop. The lock is taken at the
        call site (not here) so the resolve stays one acquisition on
        the per-RPC hot path — the convention graftlint's locks pass
        checks on both sides."""
        if not session_id:
            return None                      # default (autocommit) session
        s = self._sessions.get(session_id)
        if s is None:
            s = self.engine.session()
            self._sessions[session_id] = s
            # bounded session table: evict the least-recently-used
            # idle session (rolling back any open tx) — abandoned
            # clients must not pin staged writes forever
            while len(self._sessions) > self._max_sessions:
                _sid, old = self._sessions.popitem(last=False)
                if old.tx is not None:
                    old.rollback()
        else:
            self._sessions.move_to_end(session_id)
        return s

    def close_session(self, request, context):
        sid = request.get("session_id")
        with self._lock:
            s = self._sessions.pop(sid, None)
            if s is not None and s.tx is not None:
                s.rollback()
        return {"ok": True}

    def execute_query(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        sql = request.get("sql", "")
        # each worker thread drives the engine's dispatch→readout
        # pipeline end to end; concurrent RPCs overlap inside the engine
        # (bounded by engine.pipeline_window + memory admission)
        self._rpc_enter("server/rpc_in_flight")
        try:
            with self._lock:
                session = self._session_locked(request.get("session_id"))
            block = self.engine.execute(sql, session=session)
            stats = getattr(self.engine, "last_stats", None)
            return _result_payload(block, stats)
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}
        finally:
            self._rpc_exit("server/rpc_in_flight")

    def counters(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        return {"counters": self.engine.counters()}

    def prog_store_stats(self, request, context):
        """Persistent program-store snapshot (the zero-compile serving
        surface): store inventory + hit/miss/corrupt/refused counters +
        the admission backlog a compile-ahead fill overlaps with. The
        warm-start workflow polls this after restart to confirm every
        dispatched shape came from disk."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        try:
            from ydb_tpu.progstore import store as prog_store
            snap = prog_store.stats()
            snap["admission"] = self.engine.admission.backlog()
            return {"store": snap}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    # -- worker<->worker exchange (DQ channel data plane) ------------------
    #
    # The DQ task runner (`ydb_tpu/dq/runner.py`) drives stage graphs:
    # DqRunTask runs one task — a stage SQL whose output routes over the
    # task's channels (hash-shuffled / broadcast to peers' ExchangePut as
    # binary frames, or collected in the response for router-bound
    # channels); ChannelOpen materializes a drained channel as a
    # transient table so the next stage is ordinary local SQL over
    # co-partitioned data; DqTasks lists task states (pending → running
    # → finished/failed) for observability.

    @property
    def exchange(self):
        from ydb_tpu.cluster.exchange import ExchangeBuffer
        buf = getattr(self, "_exchange", None)
        if buf is None:
            buf = self._exchange = ExchangeBuffer()
        return buf

    def exchange_put(self, request: bytes, context):
        import hmac

        from ydb_tpu.cluster.exchange import unpack_frame, unpack_header
        try:
            # auth BEFORE deserialization: the npz payload allows pickle
            # (trusted-cluster format) — only the JSON header may be
            # parsed pre-auth
            header = unpack_header(request)
            if self._token and not hmac.compare_digest(
                    str(header.get("token", "")), self._token):
                return {"error": "Unauthenticated: invalid or missing "
                                 "token"}
            header, df = unpack_frame(request)
            # (src, seq)-deduplicated: a retried put whose first attempt
            # landed (reply lost) is dropped — idempotent redelivery
            fresh = self.exchange.put(header["channel"], df, len(request),
                                      src=str(header.get("src", "")),
                                      seq=header.get("seq"))
            return {"ok": True, "rows": len(df), "dup": not fresh}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def dq_run_task(self, request, context):
        """Run one DQ task (stage program + output channel routing) —
        the task-control RPC of the stage/task/channel runtime
        (`ydb_tpu/dq/task.py` holds the shared execution core)."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        from collections import OrderedDict

        from ydb_tpu.dq import task as dq_task
        tid = str(request.get("task_id", ""))
        with self._lock:
            tasks = self.__dict__.setdefault("_dq_tasks", OrderedDict())
            rec = tasks.setdefault(tid, {"stage": request.get("stage", ""),
                                         "attempts": 0})
            rec["state"] = "running"
            rec["attempts"] += 1
            tasks.move_to_end(tid)
            while len(tasks) > 512:          # bounded task table
                tasks.popitem(last=False)
        try:
            if any(o.get("plane") == "ici"
                   for o in request.get("outputs") or []):
                # an ICI-plane edge only lowers between in-process mesh
                # workers; a gRPC worker has no shared mesh to ride and
                # no way to ship a by-reference frame — refuse loudly so
                # the runner's host-plane fallback takes over (state
                # stamped failed like every other error path: the task
                # table must never show a phantom running task)
                msg = ("IciPlaneError: ici-plane task sent to a gRPC "
                       "worker (no shared mesh)")
                with self._lock:
                    rec["state"] = "failed"
                    rec["error"] = msg
                return {"error": msg}

            def send(out, p, frame):
                ExchangeClient(out["peers"][p]).put(frame)

            resp = dq_task.run_task(
                self.engine, request["sql"], request.get("outputs") or [],
                str(request.get("src", "")), send, token=self._token,
                trace=request.get("trace"))
            if "collected_df" in resp:
                df = resp.pop("collected_df")
                resp["collected"] = {"columns": list(df.columns),
                                     "rows": _frame_rows(df)}
            with self._lock:
                rec["state"] = "finished"
            return resp
        except Exception as e:               # noqa: BLE001 — wire boundary
            with self._lock:
                rec["state"] = "failed"
                rec["error"] = f"{type(e).__name__}: {e}"
            return {"error": f"{type(e).__name__}: {e}"}

    def dq_tasks(self, request, context):
        """Task table snapshot (state machine observability)."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        with self._lock:
            # per-record copies: running task threads mutate the inner
            # dicts under the same lock, so the reply serializes a
            # consistent snapshot instead of racing json.dumps
            tasks = {k: dict(v)
                     for k, v in (self.__dict__.get("_dq_tasks")
                                  or {}).items()}
        return {"tasks": tasks}

    def channel_open(self, request, context):
        """Materialize a drained channel as a transient local table."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        from ydb_tpu.dq.task import materialize_channel
        try:
            name = request["table"]
            if not str(name).startswith(SHUFFLE_TMP_PREFIX):
                # drop the channel's queued frames too: a refused open
                # must not leave them parked in the exchange buffer
                # forever (repeated rejected opens would leak unbounded
                # server memory)
                self.exchange.drop(request.get("channel", ""))
                return {"error": f"ChannelOpen: table {name!r} is outside "
                                 f"the {SHUFFLE_TMP_PREFIX}* shuffle-temp "
                                 "namespace"}
            stats = materialize_channel(self.engine, self.exchange,
                                        request["channel"], name,
                                        request.get("columns"))
            return {"ok": True, **stats}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    # -- distributed two-phase commit (cluster/dtx.py) ---------------------

    @property
    def _dtx_journal(self):
        from ydb_tpu.cluster.dtx import DtxJournal
        j = getattr(self, "_dtx_j", None)
        if j is None:
            store = self.engine.catalog.store
            root = store.root if store is not None else None
            if root is None:
                return None              # no durability: 2PC refuses
            j = self._dtx_j = DtxJournal(os.path.join(root, "dtx.jsonl"))
        return j

    def _maybe_crash(self, request, point: str) -> None:
        """Test-only fault injection (the nemesis hook the reference's
        test runtime provides via event interception): honored only when
        the worker opted in via YDB_TPU_TEST_FAULTS=1."""
        if os.environ.get("YDB_TPU_TEST_FAULTS") == "1" \
                and request.get("crash_point") == point:
            os._exit(137)

    def tx_prepare(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        j = self._dtx_journal
        if j is None:
            return {"error": "2PC needs a durable worker (no data_dir)"}
        gtx = request["gtx"]
        sqls = request["sqls"]
        s = None
        try:
            s = self.engine.session()
            s.execute("begin")
            for sql in sqls:
                s.execute(sql)
            j.append({"op": "prepared", "gtx": gtx, "sqls": sqls})
            self._maybe_crash(request, "after_prepare")
            with self._lock:
                self.__dict__.setdefault("_dtx_live", {})[gtx] = s
            return {"ok": True}
        except Exception as e:               # noqa: BLE001 — wire boundary
            # roll the partial session back: a leaked open tx pins its
            # coordinator snapshot (blocking compaction) and holds
            # staged writes forever
            if s is not None and s.tx is not None:
                try:
                    s.rollback()
                except Exception:            # noqa: BLE001
                    pass
            return {"error": f"{type(e).__name__}: {e}"}

    def tx_decide(self, request, context):
        """Phase 2 on a LIVE worker: apply the decision to the held
        session, then mark done."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        j = self._dtx_journal
        gtx = request["gtx"]
        decision = request["decision"]
        try:
            with self._lock:
                s = self.__dict__.setdefault("_dtx_live", {}).pop(gtx, None)
            self._maybe_crash(request, "before_apply")
            if s is not None:
                if decision == "commit":
                    s.commit()
                else:
                    s.rollback()
            elif decision == "commit":
                # no live session (restarted since prepare): re-execute
                # from the journal — upsert idempotence
                return self.tx_resolve(request, context)
            self._maybe_crash(request, "after_apply")
            j.append({"op": "done", "gtx": gtx, "decision": decision})
            return {"ok": True}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def tx_resolve(self, request, context):
        """Recovery: the router re-delivers the durable decision for an
        in-doubt gtx. Commit re-executes the logged statements (UPSERT
        idempotence — safe whether or not the crashed apply landed);
        abort just closes the record (staged writes died with the
        process)."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        j = self._dtx_journal
        gtx = request["gtx"]
        decision = request["decision"]
        try:
            # a still-live prepared session (prepare succeeded but the
            # reply was lost) resolves like a decide — never leak it
            with self._lock:
                live = self.__dict__.setdefault("_dtx_live", {}).pop(
                    gtx, None)
            if live is not None:
                if decision == "commit":
                    live.commit()
                else:
                    live.rollback()
                j.append({"op": "done", "gtx": gtx, "decision": decision})
                return {"ok": True, "state": "resolved-live"}
            rec = j.in_doubt().get(gtx)
            if rec is None:
                return {"ok": True, "state": "already-done"}
            if decision == "commit":
                s = self.engine.session()
                s.execute("begin")
                try:
                    for sql in rec["sqls"]:
                        s.execute(sql)
                    s.commit()
                except Exception:
                    if s.tx is not None:
                        s.rollback()
                    raise
            j.append({"op": "done", "gtx": gtx, "decision": decision})
            return {"ok": True, "state": "resolved"}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def tx_in_doubt(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        j = self._dtx_journal
        return {"gtx": sorted(j.in_doubt()) if j is not None else []}

    def channel_close(self, request, context):
        # auth like every other mutating RPC (the r5 version skipped the
        # check — an unauthenticated client could drop arbitrary tables)
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        try:
            tables = [str(n) for n in request.get("tables", [])]
            bad = [n for n in tables
                   if not n.startswith(SHUFFLE_TMP_PREFIX)]
            # same invariant as ChannelOpen: a durable table squatting in
            # the namespace is not ours to clobber either
            durable = [n for n in tables
                       if n not in bad and self.engine.catalog.has(n)
                       and not getattr(self.engine.catalog.table(n),
                                       "transient", False)]
            if bad or durable:
                # refuse ALL table drops (the exchange plane only ever
                # owns __xj_* transient temps) — but still free the
                # request's channel buffers: close is the cleanup RPC,
                # and a refusal must not leave frames parked forever
                for ch in request.get("channels", []):
                    self.exchange.drop(ch)
                return {"error": f"ChannelClose: refusing "
                                 f"{bad + durable} — outside the "
                                 f"{SHUFFLE_TMP_PREFIX}* shuffle-temp "
                                 "namespace or non-transient"}
            for name in tables:
                if self.engine.catalog.has(name):
                    self.engine.catalog.drop_table(name)
            for ch in request.get("channels", []):
                self.exchange.drop(ch)
            return {"ok": True}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    # -- Hive control plane (ydb_tpu/hive/) --------------------------------
    #
    # The server hosting the Hive (engine.hive attached — typically a
    # router candidate) serves membership: workers push HiveRegister
    # once and HiveHeartbeat at lease/3 (`hive/agent.py`); HiveNodes is
    # the ops-facing snapshot (`.sys/cluster_nodes` serves the same rows
    # through SQL). HiveAdoptShard runs on WORKERS: the Hive's failover
    # tells a survivor to replay a dead peer's shard image into its own
    # tables (`hive/adopt.py`).

    def _hive(self):
        return getattr(self.engine, "hive", None)

    def hive_register(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        hive = self._hive()
        if hive is None:
            return {"error": "no Hive hosted on this node"}
        try:
            return hive.register_worker(
                endpoint=str(request.get("endpoint", "")),
                node_id=str(request.get("node_id", "")),
                capacity=float(request.get("capacity", 1.0)),
                shards=list(request.get("shards") or []))
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def hive_heartbeat(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        hive = self._hive()
        if hive is None:
            return {"error": "no Hive hosted on this node"}
        try:
            load = request.get("load")
            return hive.heartbeat(str(request.get("node_id", "")),
                                  load=None if load is None
                                  else float(load))
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def hive_nodes(self, request, context):
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        hive = self._hive()
        if hive is None:
            return {"error": "no Hive hosted on this node"}
        # membership-level sweep only, like `.sys/cluster_nodes`: a
        # monitoring poll must show expired leases as dead but must
        # never trigger re-placement data movement inline
        hive.membership.sweep()
        return {"nodes": hive.rows(), "epoch": hive.epoch}

    def hive_adopt_shard(self, request, context):
        """Replay a shard image (a dead peer's standby mirror root) into
        this worker's tables — the re-placement data plane."""
        if not self._authed(request):
            return {"error": "Unauthenticated: invalid or missing token"}
        from ydb_tpu.hive.adopt import adopt_shard
        try:
            root = str(request["root"])
            copied = adopt_shard(self.engine, root,
                                 request.get("tables"))
            return {"ok": True, "copied": copied}
        except Exception as e:               # noqa: BLE001 — wire boundary
            return {"error": f"{type(e).__name__}: {e}"}

    def ping(self, request, context):
        return {"ok": True}

    def health(self, request, context):
        """Aggregated health (the health_check.cpp analog): engine
        liveness, storage mode, device platform, and basic capacity.
        Deliberately LOCK-FREE — a liveness probe must answer while a
        long query holds the execution lock, and reading approximate
        counts needs no consistency."""
        import time
        return {
            **health_snapshot(self.engine),
            "sessions": len(self._sessions),
            "uptime_s": round(time.monotonic() - _STARTED, 1),
        }


def serve(engine, port: int = 2136, max_workers: int = 8,
          token: str = ""):
    """Start the gRPC server; returns (server, bound_port). `token`
    (or $YDB_TPU_AUTH_TOKEN): require it on query/counters calls."""
    import grpc

    servicer = QueryServicer(engine, token=token)
    handlers = {
        "ExecuteQuery": grpc.unary_unary_rpc_method_handler(
            servicer.execute_query, request_deserializer=_deser,
            response_serializer=_ser),
        "Counters": grpc.unary_unary_rpc_method_handler(
            servicer.counters, request_deserializer=_deser,
            response_serializer=_ser),
        "ProgStoreStats": grpc.unary_unary_rpc_method_handler(
            servicer.prog_store_stats, request_deserializer=_deser,
            response_serializer=_ser),
        "Ping": grpc.unary_unary_rpc_method_handler(
            servicer.ping, request_deserializer=_deser,
            response_serializer=_ser),
        "CloseSession": grpc.unary_unary_rpc_method_handler(
            servicer.close_session, request_deserializer=_deser,
            response_serializer=_ser),
        "Health": grpc.unary_unary_rpc_method_handler(
            servicer.health, request_deserializer=_deser,
            response_serializer=_ser),
        # exchange data plane: binary request frames (npz), JSON replies
        "ExchangePut": grpc.unary_unary_rpc_method_handler(
            servicer.exchange_put, request_deserializer=lambda b: b,
            response_serializer=_ser),
        "DqRunTask": grpc.unary_unary_rpc_method_handler(
            servicer.dq_run_task, request_deserializer=_deser,
            response_serializer=_ser),
        "DqTasks": grpc.unary_unary_rpc_method_handler(
            servicer.dq_tasks, request_deserializer=_deser,
            response_serializer=_ser),
        "ChannelOpen": grpc.unary_unary_rpc_method_handler(
            servicer.channel_open, request_deserializer=_deser,
            response_serializer=_ser),
        "ChannelClose": grpc.unary_unary_rpc_method_handler(
            servicer.channel_close, request_deserializer=_deser,
            response_serializer=_ser),
        "TxPrepare": grpc.unary_unary_rpc_method_handler(
            servicer.tx_prepare, request_deserializer=_deser,
            response_serializer=_ser),
        "TxDecide": grpc.unary_unary_rpc_method_handler(
            servicer.tx_decide, request_deserializer=_deser,
            response_serializer=_ser),
        "TxResolve": grpc.unary_unary_rpc_method_handler(
            servicer.tx_resolve, request_deserializer=_deser,
            response_serializer=_ser),
        "TxInDoubt": grpc.unary_unary_rpc_method_handler(
            servicer.tx_in_doubt, request_deserializer=_deser,
            response_serializer=_ser),
        # Hive control plane: membership (on the Hive host) + shard
        # adoption (on workers)
        "HiveRegister": grpc.unary_unary_rpc_method_handler(
            servicer.hive_register, request_deserializer=_deser,
            response_serializer=_ser),
        "HiveHeartbeat": grpc.unary_unary_rpc_method_handler(
            servicer.hive_heartbeat, request_deserializer=_deser,
            response_serializer=_ser),
        "HiveNodes": grpc.unary_unary_rpc_method_handler(
            servicer.hive_nodes, request_deserializer=_deser,
            response_serializer=_ser),
        "HiveAdoptShard": grpc.unary_unary_rpc_method_handler(
            servicer.hive_adopt_shard, request_deserializer=_deser,
            response_serializer=_ser),
    }
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[("grpc.max_send_message_length", 256 << 20),
                 ("grpc.max_receive_message_length", 256 << 20)])
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),))
    bound = server.add_insecure_port(f"127.0.0.1:{port}")
    server.start()
    return server, bound


class ExchangeClient:
    """Data-plane client: ships one binary channel frame to a peer."""

    _channels: dict = {}
    _mu = threading.Lock()

    def __init__(self, endpoint: str):
        import grpc
        # channel reuse: a shuffle sends many frames to few peers — a
        # fresh HTTP/2 connection per frame would dominate small shuffles
        with ExchangeClient._mu:
            ch = ExchangeClient._channels.get(endpoint)
            if ch is None:
                ch = grpc.insecure_channel(endpoint, options=[
                    ("grpc.max_send_message_length", 256 << 20),
                    ("grpc.max_receive_message_length", 256 << 20)])
                ExchangeClient._channels[endpoint] = ch
        self._put = ch.unary_unary(
            f"/{SERVICE}/ExchangePut",
            request_serializer=lambda b: b,
            response_deserializer=_deser)

    def put(self, frame: bytes) -> dict:
        resp = self._put(frame)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp


class Client:
    """Minimal SDK client (the ydb-sdk QueryClient analog)."""

    def __init__(self, endpoint: str, session_id: str = "",
                 token: str = ""):
        import grpc

        self.endpoint = endpoint
        self.token = token
        # same max-message override as the server: DqRunTask responses
        # carry router-bound collected frames that can exceed gRPC's
        # stock 4 MiB cap
        self._channel = grpc.insecure_channel(
            endpoint,
            options=[("grpc.max_send_message_length", 256 << 20),
                     ("grpc.max_receive_message_length", 256 << 20)])
        self._exec = self._channel.unary_unary(
            f"/{SERVICE}/ExecuteQuery", request_serializer=_ser,
            response_deserializer=_deser)
        self._counters = self._channel.unary_unary(
            f"/{SERVICE}/Counters", request_serializer=_ser,
            response_deserializer=_deser)
        self._prog_store_stats = self._channel.unary_unary(
            f"/{SERVICE}/ProgStoreStats", request_serializer=_ser,
            response_deserializer=_deser)
        self._ping = self._channel.unary_unary(
            f"/{SERVICE}/Ping", request_serializer=_ser,
            response_deserializer=_deser)
        self._health = self._channel.unary_unary(
            f"/{SERVICE}/Health", request_serializer=_ser,
            response_deserializer=_deser)
        self._dq_run = self._channel.unary_unary(
            f"/{SERVICE}/DqRunTask", request_serializer=_ser,
            response_deserializer=_deser)
        self._dq_tasks = self._channel.unary_unary(
            f"/{SERVICE}/DqTasks", request_serializer=_ser,
            response_deserializer=_deser)
        self._chopen = self._channel.unary_unary(
            f"/{SERVICE}/ChannelOpen", request_serializer=_ser,
            response_deserializer=_deser)
        self._chclose = self._channel.unary_unary(
            f"/{SERVICE}/ChannelClose", request_serializer=_ser,
            response_deserializer=_deser)
        self.session_id = session_id

    def execute(self, sql: str) -> dict:
        resp = self._exec({"sql": sql, "session_id": self.session_id,
                           "token": self.token})
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp

    def query(self, sql: str):
        """Execute and return a pandas DataFrame."""
        import pandas as pd

        resp = self.execute(sql)
        return pd.DataFrame(resp["rows"], columns=resp["columns"])

    def counters(self) -> dict:
        resp = self._counters({"token": self.token})
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["counters"]

    def prog_store_stats(self) -> dict:
        resp = self._prog_store_stats({"token": self.token})
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["store"]

    def dq_run_task(self, task_id: str, stage: str, sql: str,
                    outputs: list, src: str = "",
                    timeout: float = None, trace: dict = None) -> dict:
        """Run one DQ task (stage program + channel routing) on the
        worker; blocks until the task's frames are delivered. `trace`:
        the propagated {trace_id, parent_span_id, sampled} context —
        the worker records its spans against it and ships them back in
        `resp["profile"]`."""
        resp = self._dq_run({"task_id": task_id, "stage": stage,
                             "sql": sql, "outputs": list(outputs),
                             "src": src, "token": self.token,
                             "trace": trace},
                            timeout=timeout)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp

    def dq_tasks(self, timeout: float = None) -> dict:
        resp = self._dq_tasks({"token": self.token}, timeout=timeout)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["tasks"]

    def channel_open(self, channel: str, table: str,
                     columns=None, timeout: float = None) -> dict:
        resp = self._chopen({"channel": channel, "table": table,
                             "columns": columns, "token": self.token},
                            timeout=timeout)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp

    def channel_close(self, tables=(), channels=(),
                      timeout: float = None) -> dict:
        return self._chclose({"tables": list(tables),
                              "channels": list(channels),
                              "token": self.token}, timeout=timeout)

    def _dtx_call(self, method: str, body: dict) -> dict:
        stubs = self.__dict__.setdefault("_dtx_stubs", {})
        call = stubs.get(method)
        if call is None:
            call = stubs[method] = self._channel.unary_unary(
                f"/{SERVICE}/{method}", request_serializer=_ser,
                response_deserializer=_deser)
        resp = call({**body, "token": self.token})
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp

    def tx_prepare(self, gtx: str, sqls: list, **extra) -> dict:
        return self._dtx_call("TxPrepare",
                              {"gtx": gtx, "sqls": sqls, **extra})

    def tx_decide(self, gtx: str, decision: str, **extra) -> dict:
        return self._dtx_call("TxDecide",
                              {"gtx": gtx, "decision": decision, **extra})

    def tx_resolve(self, gtx: str, decision: str) -> dict:
        return self._dtx_call("TxResolve",
                              {"gtx": gtx, "decision": decision})

    def tx_in_doubt(self) -> list:
        return self._dtx_call("TxInDoubt", {})["gtx"]

    # -- Hive control plane -------------------------------------------------

    def _hive_call(self, method: str, body: dict,
                   timeout: float = None) -> dict:
        stubs = self.__dict__.setdefault("_hive_stubs", {})
        call = stubs.get(method)
        if call is None:
            call = stubs[method] = self._channel.unary_unary(
                f"/{SERVICE}/{method}", request_serializer=_ser,
                response_deserializer=_deser)
        resp = call({**body, "token": self.token}, timeout=timeout)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp

    def hive_register(self, endpoint: str, node_id: str = "",
                      capacity: float = 1.0, shards=(),
                      timeout: float = None) -> dict:
        return self._hive_call("HiveRegister",
                               {"endpoint": endpoint, "node_id": node_id,
                                "capacity": capacity,
                                "shards": list(shards)}, timeout=timeout)

    def hive_heartbeat(self, node_id: str, load: float = None,
                       timeout: float = None) -> dict:
        return self._hive_call("HiveHeartbeat",
                               {"node_id": node_id, "load": load},
                               timeout=timeout)

    def hive_nodes(self, timeout: float = None) -> dict:
        return self._hive_call("HiveNodes", {}, timeout=timeout)

    def hive_adopt_shard(self, root: str, tables=None,
                         timeout: float = None) -> dict:
        return self._hive_call("HiveAdoptShard",
                               {"root": root, "tables": tables},
                               timeout=timeout)

    def ping(self, timeout: float = None) -> bool:
        return bool(self._ping({}, timeout=timeout).get("ok"))

    def health(self) -> dict:
        return self._health({})

    def close(self) -> None:
        if self.session_id:
            try:
                self._channel.unary_unary(
                    f"/{SERVICE}/CloseSession", request_serializer=_ser,
                    response_deserializer=_deser)(
                        {"session_id": self.session_id})
            except Exception:                # noqa: BLE001 — best effort
                pass
        self._channel.close()
