"""Query engine front door: SQL text in, result blocks out.

Plays the role of the KQP session actor + compile service
(`kqp_session_actor.cpp:455` CompileQuery → `ExecutePhyTx`): parses, plans
(with a fingerprint-keyed plan cache), executes, and applies DDL/DML against
the catalog. Interactive transactions (BEGIN/COMMIT/ROLLBACK with
optimistic locks) live in `ydb_tpu/tx`; `engine.session()` opens
concurrent sessions over the shared engine.
"""

from __future__ import annotations

import logging
import os
import time
from contextlib import ExitStack, nullcontext
from contextlib import contextmanager as _contextmanager
from typing import Optional

import numpy as np

from ydb_tpu.core.block import HostBlock
from ydb_tpu.query.binder import BindError, sql_type_to_dtype, parse_date_literal
from ydb_tpu.query.executor import Executor
from ydb_tpu.query.plan import QueryPlan, explain
from ydb_tpu.query.planner import PlanError, Planner
from ydb_tpu.scheme.catalog import Catalog
from ydb_tpu.sql import ast, parse
from ydb_tpu.storage.mvcc import Snapshot, WriteVersion
from ydb_tpu.core.schema import Column, Schema


_LOG = logging.getLogger("ydb_tpu.slow_query")

# host share of a statement's wall (wall less device queue and run) past
# which it logs its phases; steady state is a few ms
HOST_SLOW_MS = 100.0


class QueryError(Exception):
    pass


class QueryEngine:
    def __init__(self, catalog: Optional[Catalog] = None,
                 block_rows: Optional[int] = None, mesh=None,
                 data_dir: Optional[str] = None, config=None,
                 replica=None):
        """`mesh`: a jax.sharding.Mesh for distributed execution — scans are
        row-partitioned across its devices and aggregation boundaries become
        ICI hash shuffles (`ydb_tpu.parallel.make_mesh(n)` builds one).

        `data_dir`: durable root. An existing catalog there is recovered
        (portions + WAL replay, `storage/persist.py`); otherwise a fresh
        durable catalog is created. MVCC plan steps resume past the last
        committed step so recovered versions stay ordered.

        `config`: a `ydb_tpu.utils.config.Config` (YAML-loadable, with
        selector overrides + feature flags); explicit arguments win over
        it."""
        import threading
        from ydb_tpu.utils.config import Config
        self.config = config or Config.load()
        # WRITE lock: mutations (DML, DDL, tx control, topic ops) from any
        # front serialize here; SELECTs run lock-free over MVCC snapshots
        # (the r3 design held this around EVERY statement — concurrency
        # item of round-3 review). RLock: DML bodies re-enter execute() for
        # their SELECT subflows. Network fronts must NOT wrap execute()
        # in this themselves anymore — the engine takes it internally.
        self.lock = threading.RLock()
        block_rows = block_rows if block_rows is not None \
            else self.config.block_rows
        data_dir = data_dir if data_dir is not None \
            else self.config.data_dir
        restored_step = 0
        if data_dir is not None and catalog is None:
            from ydb_tpu.storage.persist import Store
            sink = None
            if replica is not None:
                # synchronous standby mirror (cluster/replica.py):
                # every durable mutation ships before acknowledgement
                from ydb_tpu.cluster.replica import make_sink
                sink = make_sink(replica)
            store = Store(data_dir, replica=sink)
            if os.path.exists(os.path.join(data_dir, "catalog.json")):
                catalog, restored_step = store.load()
                # pre-existing data + fresh standby: full initial sync
                # (delta shipping alone would reference blobs the
                # standby never saw)
                store.sync_replica()
            else:
                catalog = Catalog(store=store)
                store.save_catalog(catalog)
        self.catalog = catalog or Catalog()
        self.planner = Planner(self.catalog)
        self.executor = Executor(self.catalog, block_rows, mesh=mesh)
        self.executor.enable_fused = self.config.flag("enable_fused")
        # budget priority: explicit env var > config (file or object) >
        # built-in default (the executor ctor already consumed the env)
        if "YDB_TPU_GRACE_BUDGET" not in os.environ:
            self.executor.grace_budget_bytes = \
                self.config.grace_budget_bytes
        from ydb_tpu.tx import Coordinator, Session
        self.coordinator = Coordinator(start_step=max(1, restored_step))
        # the engine's own statements run through a default session
        # (autocommit unless BEGIN is issued on it); `session()` opens
        # additional concurrent sessions
        self._default_session = Session(self)
        # plan cache (compile-service LRU analog, `kqp_compile_service.cpp:411`):
        # keyed by SQL text, validated against the (uid, data_version) of
        # every table the statement references — plans snapshot dictionary
        # domains at plan time, so any commit to a referenced table
        # invalidates only that statement's entry, not the whole cache
        self._plan_cache: dict = {}
        self.plan_cache_hits = 0
        import itertools as _it
        self._tmp_ids = _it.count()      # thread-safe temp-name allocator
        # device-memory admission (kqp_rm_service.h:68 analog): SELECTs
        # reserve their scan+build estimate before dispatch
        from ydb_tpu.query.admission import MemoryAdmission
        from ydb_tpu.storage.device_cache import DEFAULT_BUDGET
        self.admission = MemoryAdmission(
            int(os.environ.get("YDB_TPU_ADMISSION_BUDGET", DEFAULT_BUDGET)),
            timeout_s=float(os.environ.get("YDB_TPU_ADMISSION_TIMEOUT",
                                           60.0)))
        # per-statement stats ring — the `.sys/query_metrics` /
        # top-queries source (query_metrics_one_minute analog)
        from collections import deque
        self.query_history = deque(maxlen=256)
        # topics + changefeeds (PersQueue / change_exchange analogs,
        # ydb_tpu/storage/topic.py); durable under <root>/__topics
        self.topics: dict = {}
        self._changefeeds: dict = {}    # table -> topic name
        self._cdc_since: dict = {}      # table -> plan_step at enable
        if self.catalog.store is not None:
            self._load_topics()
        self._reconcile_changefeeds()
        # materialized views (ydb_tpu/views/): continuous queries over
        # the changefeeds above; loaded AFTER topics + healing so the
        # consumers resume against a consistent topic tail
        from ydb_tpu.views import ViewManager
        self.views = ViewManager(self)
        self.views.load()
        self._view_tls = threading.local()   # per-read serving notes
        # tracing (Wilson analog, utils/tracing.py): span tree per
        # statement, rendered by EXPLAIN ANALYZE; `trace_to_topic()`
        # wires the OTLP-uploader seat
        from ydb_tpu.utils.tracing import Tracer
        self.tracer = Tracer()
        self.executor.tracer = self.tracer
        # cluster control plane (ydb_tpu/hive/): a router candidate that
        # hosts the Hive attaches it here — the server's HiveRegister/
        # HiveHeartbeat RPCs and the `.sys/cluster_nodes` sysview both
        # read it; None on ordinary workers
        self.hive = None
        # admission-time trace sampling (jaeger_tracing sampler analog):
        # YDB_TPU_TRACE_SAMPLE in [0, 1] — 1 (default) traces every
        # statement, 0 records zero spans (results byte-identical),
        # fractions sample deterministically 1-in-1/rate. Statements
        # whose text previously blew the slow-query threshold are
        # FORCED-sampled regardless of rate, so the profile of a known
        # offender is always captured on its next run.
        self.trace_sample = min(1.0, max(0.0, float(
            os.environ.get("YDB_TPU_TRACE_SAMPLE", "1") or 0)))
        self.slow_query_ms = float(
            os.environ.get("YDB_TPU_SLOW_QUERY_MS", "1000"))
        self._slow_sqls: dict = {}       # guarded-by: _trace_mu
        self._trace_mu = threading.Lock()
        self._trace_acc = 0.0            # guarded-by: _trace_mu
        # assembled query profiles, last-N ring (`.sys/query_profiles`):
        # one record per SAMPLED outermost statement — sql, wall,
        # phase breakdown, and the full cross-worker span tree
        from collections import deque as _deque
        self.profiles = _deque(maxlen=int(
            os.environ.get("YDB_TPU_PROFILE_RING", "64")))
        # per-(stage, worker) DQ execution stats ring
        # (`.sys/dq_stage_stats`) — the TDqTaskRunnerStatsView seat;
        # filled by DqTaskRunner when this engine drives a stage graph
        self.dq_stage_stats = _deque(maxlen=int(
            os.environ.get("YDB_TPU_DQ_STATS_RING", "256")))
        # per-statement resource-ledger rollups, last-N ring
        # (`.sys/query_memory`): peak device bytes, padding account,
        # host transfers, admission calibration — one row per closed
        # ledger (utils/memledger.py; empty under YDB_TPU_MEMLEDGER=0)
        self.memory_stats = _deque(maxlen=int(
            os.environ.get("YDB_TPU_MEMORY_RING", "256")))
        # per-statement critical-path rollups, last-N ring
        # (`.sys/query_critical_path`): one row per extracted path —
        # per-class milliseconds, coverage, the dominant span
        # (utils/critpath.py; empty under YDB_TPU_CRITPATH=0)
        self.critpath_stats = _deque(maxlen=int(
            os.environ.get("YDB_TPU_CRITPATH_RING", "256")))
        # per-statement result metadata is THREAD-LOCAL: concurrent
        # sessions must each see their own stats/trace/rows-affected
        self._tls = threading.local()
        # in-flight lock-free reads register their snapshot plan step so
        # auto-compaction's watermark never restamps portions a running
        # SELECT still needs (autocommit snapshots are not coordinator-
        # pinned; explicit txs pin theirs)
        from collections import Counter as _Counter
        self._active_reads = _Counter()  # guarded-by: _reads_mu
        self._reads_mu = threading.Lock()
        # admission rate limiting (Kesus/quoter analog): meter the
        # "queries" resource via engine.quoter.set_quota(...)
        from ydb_tpu.utils.quota import Quoter
        self.quoter = Quoter()
        # concurrent-query pipeline (the continuous-batching discipline):
        # SELECT dispatch (plan → compile-cache → device enqueue) and
        # readout (the one pytree device_get) are separate phases, so
        # query N+1 dispatches while query N drains D2H instead of both
        # paying the full post-readout dispatch cliff serially (PERF.md).
        # The window bounds dispatched-but-undrained queries: each holds
        # its result buffers (plus admission reservation) in device
        # memory until drained.
        self.pipeline_window = max(1, int(os.environ.get(
            "YDB_TPU_PIPELINE_WINDOW", self.config.pipeline_window)))
        self._pipe_sem = threading.BoundedSemaphore(self.pipeline_window)
        self._pipe_mu = threading.Lock()
        self._pipe_inflight = 0          # guarded-by: _pipe_mu
        # multi-query batched dispatch lane (query/batch_lane.py): with
        # YDB_TPU_BATCH_WINDOW=<ms> > 0, same-shape SELECTs arriving
        # inside the window coalesce into ONE stacked fused execution
        # (one dispatch + one readout + one admission reservation for B
        # clients). 0 = off, byte-identical to the per-query path.
        self.batch_window_ms = float(
            os.environ.get("YDB_TPU_BATCH_WINDOW", "0") or 0)
        # how long a group's leader waits for a SECOND member before it
        # runs alone on the per-query program (the lane reads it at every
        # seal). An attribute a deployment's configuration may state, not
        # an environment lever.
        self.batch_alone_probe_ms = 2.0
        self._batch_lane = None
        if self.batch_window_ms > 0:
            from ydb_tpu.query.batch_lane import BatchLane
            self._batch_lane = BatchLane(
                self, self.batch_window_ms / 1000.0,
                max_batch=int(os.environ.get("YDB_TPU_BATCH_MAX", "64")))

    # -- per-thread statement metadata -------------------------------------

    @property
    def last_stats(self):
        return getattr(self._tls, "last_stats", None)

    @last_stats.setter
    def last_stats(self, v):
        self._tls.last_stats = v

    @property
    def last_rows_affected(self) -> int:
        return getattr(self._tls, "last_rows_affected", 0)

    @last_rows_affected.setter
    def last_rows_affected(self, v: int):
        self._tls.last_rows_affected = v

    @property
    def last_trace(self):
        return getattr(self._tls, "last_trace", [])

    @last_trace.setter
    def last_trace(self, v):
        self._tls.last_trace = v

    # -- in-flight read registry (compaction safety floor) -----------------

    def _enter_read(self, plan_step: int) -> None:
        with self._reads_mu:
            self._active_reads[plan_step] += 1

    def _register_read(self):
        """Atomically take an autocommit read snapshot AND register it in
        the active-read floor. Taking the snapshot first and registering
        after (the r4 shape) left a gap where a commit + auto-compaction
        could restamp portions the snapshot still needed (ADVICE r4):
        under `_reads_mu`, any maintenance watermark computed before this
        registration was bounded by an older published step, so portions
        this snapshot sees are never restamped past it."""
        with self._reads_mu:
            snap = self.coordinator.read_snapshot()
            self._active_reads[snap.plan_step] += 1
        return snap

    def _exit_read(self, plan_step: int) -> None:
        with self._reads_mu:
            self._active_reads[plan_step] -= 1
            if self._active_reads[plan_step] <= 0:
                del self._active_reads[plan_step]

    def _maintenance_watermark(self) -> int:
        """Highest plan step background compaction may restamp up to:
        bounded by pinned tx snapshots (coordinator) AND every in-flight
        lock-free read."""
        w = self.coordinator.safe_watermark()
        with self._reads_mu:
            if self._active_reads:
                w = min(w, min(self._active_reads))
        return w

    # -- versions (coordinator time, ydb_tpu/tx/coordinator.py) ------------

    @property
    def _plan_step(self) -> int:
        return self.coordinator.last_plan_step

    def _next_version(self) -> WriteVersion:
        """A plan step published immediately — for callers that commit to
        storage directly (tests, loaders) with no reader able to observe
        the mid-apply state they create. Statement paths use
        `_commit_step` so the watermark trails the apply."""
        version = self.coordinator.propose(0)
        self.coordinator.publish(version.plan_step)
        return version

    @_contextmanager
    def _commit_step(self, tx_id: int = 0):
        """Propose→apply→publish envelope. The coordinator grants the plan
        step on entry; the read watermark advances only when the body's
        in-memory apply (stamps + delete marks) has finished, so lock-free
        SELECTs snapshotting mid-commit never observe a torn multi-shard
        apply. Publish runs in `finally` — a failed apply must not wedge
        the watermark (storage-level intent journals own partial-failure
        atomicity)."""
        version = self.coordinator.propose(tx_id)
        try:
            yield version
        finally:
            self.coordinator.publish(version.plan_step)

    def snapshot(self) -> Snapshot:
        return self.coordinator.read_snapshot()

    def session(self):
        """Open an interactive session (BEGIN/COMMIT/ROLLBACK scope)."""
        from ydb_tpu.tx import Session
        return Session(self)

    def register_udf(self, name: str, fn, returns: str = "string",
                     min_args: int = 1, max_args: int = 8) -> None:
        """Register a scalar UDF (`query/udf.py`): `fn(str_or_None,
        *literal_args)` evaluated once per DISTINCT dictionary value,
        gathered on device through a LUT. `returns`: string | int64 |
        float64 | bool."""
        self.catalog.udfs.register(name, fn, returns, min_args, max_args)

    # -- topics / changefeeds (PersQueue + change_exchange analogs) --------

    def create_topic(self, name: str, partitions: int = 1):
        import re as _re
        from ydb_tpu.storage.topic import Topic
        if not _re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name):
            # the name becomes a directory under <root>/__topics — '/'
            # or '..' would escape it
            raise QueryError(f"invalid topic name {name!r}")
        if partitions < 1:
            raise QueryError("a topic needs at least one partition")
        with self.lock:
            if name in self.topics:
                raise QueryError(f"topic {name!r} already exists")
            self.topics[name] = Topic(name, partitions,
                                      self._topic_root(name))
            self._save_topics()
            return self.topics[name]

    def topic(self, name: str):
        t = self.topics.get(name)
        if t is None:
            raise QueryError(f"unknown topic {name!r}")
        return t

    def drop_topic(self, name: str) -> None:
        with self.lock:
            self.topic(name)
            if name in self._changefeeds.values():
                raise QueryError(f"topic {name!r} feeds a changefeed")
            del self.topics[name]
            root = self._topic_root(name)
            if root is not None and os.path.isdir(root):
                import shutil
                shutil.rmtree(root)
            self._save_topics()

    def enable_changefeed(self, table_name: str, topic_name: str) -> None:
        """Publish the row table's committed mutations into the topic
        (CDC; per-pk partition ordering)."""
        from ydb_tpu.storage.topic import ChangefeedSink
        with self.lock:
            if not self.catalog.has(table_name):
                raise QueryError(f"unknown table {table_name!r}")
            t = self._table(table_name)
            if getattr(t, "store_kind", "column") != "row":
                raise QueryError("changefeeds are row-store only for now")
            t.changefeed = ChangefeedSink(self.topic(topic_name),
                                          table_name, t.key_columns)
            self._changefeeds[table_name] = topic_name
            # publication floor: commits at or below this step predate
            # the changefeed and must not be re-emitted by replay healing
            self._cdc_since[table_name] = self.coordinator.last_plan_step
            self._save_topics()

    def _topic_root(self, name: str):
        if self.catalog.store is None:
            return None
        return os.path.join(self.catalog.store.root, "__topics", name)

    def _save_topics(self) -> None:
        if self.catalog.store is None:
            return
        from ydb_tpu.storage.persist import _atomic_json
        _atomic_json(
            os.path.join(self.catalog.store.root, "topics.json"),
            {"topics": {n: len(t.partitions)
                        for n, t in self.topics.items()},
             "changefeeds": {t: {"topic": n,
                                 "since": self._cdc_since.get(t, 0)}
                             for t, n in self._changefeeds.items()}})

    def _load_topics(self) -> None:
        import json as _json
        from ydb_tpu.storage.topic import ChangefeedSink, Topic
        path = os.path.join(self.catalog.store.root, "topics.json")
        if not os.path.exists(path):
            return
        with open(path) as f:
            meta = _json.load(f)
        for n, parts in meta.get("topics", {}).items():
            self.topics[n] = Topic(n, parts, self._topic_root(n))
        for table_name, cf in meta.get("changefeeds", {}).items():
            # legacy format stored a bare topic name; treat its floor as
            # "now" so replay healing never republishes history
            topic_name = cf["topic"] if isinstance(cf, dict) else cf
            since = cf.get("since", 0) if isinstance(cf, dict) \
                else self.coordinator.last_plan_step
            if self.catalog.has(table_name) and topic_name in self.topics:
                t = self.catalog.table(table_name)
                t.changefeed = ChangefeedSink(
                    self.topics[topic_name], table_name, t.key_columns)
                self._changefeeds[table_name] = topic_name
                self._cdc_since[table_name] = int(since)

    def _reconcile_changefeeds(self) -> None:
        """Heal torn topic tails after recovery: re-emit the row-WAL
        replay events through each wired changefeed. The deterministic
        producer seq_no dedups everything already published, so only a
        tail lost to a crash between the row-WAL fsync and the topic
        append lands again — exactly once, in commit order."""
        for table_name in self._changefeeds:
            t = self.catalog.table(table_name)
            log = getattr(t, "_replay_log", None)
            since = self._cdc_since.get(table_name, 0)
            if t.changefeed is None or not log:
                continue
            for version, events in log:
                if events and version.plan_step > since:
                    t.changefeed.emit(events, version)
        for t in self.catalog.tables.values():
            if getattr(t, "_replay_log", None) is not None:
                t._replay_log = None

    # -- entry -------------------------------------------------------------

    _AUDITED_KINDS = frozenset((
        "createtable", "droptable", "altertable", "createindex",
        "dropindex", "insert", "update", "delete", "begin", "commit",
        "rollback", "creatematerializedview", "dropmaterializedview"))

    def execute(self, sql: str, session=None,
                _internal: bool = False) -> HostBlock:
        """`_internal`: a re-entrant call from inside another statement
        (EXPLAIN ANALYZE, forced rollback) — already admitted and audited
        by its enclosing statement, so the quoter and audit skip it."""
        if not _internal and not self.quoter.acquire("queries"):
            from ydb_tpu.utils.metrics import GLOBAL
            GLOBAL.inc("engine/throttled")
            raise QueryError("query rate limit exceeded (quoter: the "
                             "'queries' resource bucket is empty)")
        session = session or self._default_session
        # per-session statement serialization (SESSION_BUSY analog —
        # concurrency comes from many sessions, not one)
        ctx = session._mu if session is not self._default_session \
            else nullcontext()
        outermost = self.tracer._state().depth == 0
        # the sampling decision (and its accumulator/forced-slow side
        # effects) applies to OUTERMOST statements only — a nested
        # begin_trace inherits the open trace's decision anyway
        self.tracer.begin_trace(
            sampled=self._sample_decision(sql) if outermost else True)
        kind_box: list = []
        ok = False
        # resource ledger (utils/memledger.py): one per OUTERMOST
        # statement on this thread — a nested execute (EXPLAIN ANALYZE,
        # DQ router merge) contributes to the enclosing ledger
        from ydb_tpu.utils import memledger, progstats
        led = memledger.open_statement()
        # program-execution accumulator (utils/progstats.py): same
        # outermost-statement discipline — feeds QueryStats.programs and
        # the EXPLAIN ANALYZE `-- programs:` block
        pst = progstats.open_statement()
        try:
            with ctx, self.tracer.span("statement", sql=sql[:60]):
                block = self._execute_traced(sql, session, kind_box)
            ok = True
            return block
        finally:
            self.last_trace = self.tracer.end_trace()
            kind = kind_box[0] if kind_box else ""
            # the statement's own analysis and records, after its trace
            # closed: an outermost read's phase `bookkeeping_ms` (from
            # `_finish_stats` to here), the `bookkeeping` annotation on
            # the profiler's clock
            with self.tracer.annotate("bookkeeping") if outermost \
                    else nullcontext():
                self._close_statement(sql, kind, pst, led, outermost)
                if not _internal:
                    self._audit(sql, ok, kind)
            if outermost:
                self._note_bookkeeping()

    def _close_statement(self, sql: str, kind: str, pst, led,
                         outermost: bool) -> None:
        """Close the program accumulator and the resource ledger the
        statement owns, and analyse an OUTERMOST statement once, over its
        closed trace: the phases, the ledger and program roll-ups and the
        critical path land on its `QueryStats` (a nested statement had
        them from `_finish_stats`) and, the same figures, in its profile."""
        from ydb_tpu.utils import memledger, progstats
        if pst is not None:
            progstats.close_statement(pst)
        memory = None
        if led is not None:
            memledger.close_statement(led)
            memory = led.summary()
            self._record_memory(sql, kind, memory)
        if not outermost:
            return
        st = self.last_stats
        phases = cp = None
        if getattr(st, "_book_t0", None) is not None:
            # latency histograms and slow-query notes count USER
            # statements: not a worker's DQ stage program
            cp = self._analyse(st, self.last_trace, memory, pst,
                               user=not self.executor.dq_stage_depth)
            phases = st.phases
        # profiles record USER statements: a DQ stage program run
        # through a legacy (context-free) caller is still internal
        if self.last_trace and not self.executor.dq_stage_depth:
            self._record_profile(sql, self.last_trace, memory=memory,
                                 phases=phases, cp=cp)

    def _note_bookkeeping(self) -> None:
        """Close an outermost read's `bookkeeping_ms`: the time from
        `_finish_stats` (where `total_ms` stops) to the end of its
        `execute`, counted in `engine/bookkeeping_ms` and, for a sampled
        statement, written into its `phases` (a new dict: the profile
        keeps the spans' own)."""
        st = self.last_stats
        t0 = getattr(st, "_book_t0", None)
        if t0 is None:
            return
        st._book_t0 = None
        ms = round((time.perf_counter() - t0) * 1000.0, 3)
        from ydb_tpu.utils.metrics import GLOBAL
        GLOBAL.inc("engine/bookkeeping_ms", ms)
        if self.last_trace:
            st.phases = {**st.phases, "bookkeeping_ms": ms}

    def _sample_decision(self, sql: str) -> bool:
        """Admission-time trace sampling: rate-based, with forced-on for
        EXPLAIN (the user asked for the profile) and for statements whose
        text previously exceeded the slow-query threshold. Nested
        (internal) statements inherit the enclosing decision — this is
        only consulted for the thread's OUTERMOST begin_trace."""
        if self.trace_sample >= 1.0:
            return True
        if sql.lstrip()[:7].lower() == "explain":
            return True
        if sql in self._slow_sqls:
            from ydb_tpu.utils.metrics import GLOBAL
            GLOBAL.inc("trace/forced_slow")
            return True
        if self.trace_sample <= 0.0:
            return False
        with self._trace_mu:
            self._trace_acc += self.trace_sample
            if self._trace_acc >= 1.0:
                self._trace_acc -= 1.0
                return True
        return False

    def _record_memory(self, sql: str, kind: str, s: dict) -> None:
        """Append one closed ledger's summary to the `.sys/query_memory`
        ring. Statements that never touched the device (DDL, constant
        SELECTs) are skipped — a ring of zero rows would bury the
        queries this view exists to rank."""
        if not (s["peak_bytes"] or s["transfers"] or s["padded_bytes"]):
            return
        self.memory_stats.append({
            "sql": sql, "kind": kind,
            "peak_bytes": s["peak_bytes"],
            "alloc_bytes": s["alloc_bytes"],
            "live_bytes": s["live_bytes"],
            "padded_bytes": s["padded_bytes"],
            "waste_bytes": s["waste_bytes"],
            "pad_efficiency": s["pad_efficiency"],
            "transfers": s["transfers"],
            "transfer_bytes": s["transfer_bytes"],
            "to_pandas_in_plan": s["to_pandas_in_plan"],
            "admission_est_bytes": s["admission_est_bytes"],
            "est_error_pct": s["est_error_pct"],
        })

    def _record_profile(self, sql: str, spans: list,
                        stage_stats: list = None, total_ms: float = None,
                        rows_out: int = None, kind: str = None,
                        memory: dict = None, phases: dict = None,
                        cp: dict = None) -> None:
        """Append one assembled profile to the last-N ring
        (`.sys/query_profiles`): the span tree plus its device-timeline
        rollup. `stage_stats`: the DQ runner's per-(stage, worker) rows
        for distributed queries. total_ms/rows_out/kind overrides: the
        router passes the DQ wall explicitly — for a distributed query
        `last_stats` holds only the router-MERGE statement's numbers
        (or a previous statement's, when the final stage had no merge
        SQL), not the graph's. `phases` / `cp`: the statement's closing
        analysis (`_analyse`) of these spans, computed here without it."""
        from ydb_tpu.utils.tracing import phase_breakdown
        if phases is None:
            phases = phase_breakdown(spans)
            cp = self._critical_path(spans, memory)
        st = self.last_stats
        # last_stats is only trustworthy when it belongs to THIS
        # statement and finished: a statement that raised before (or
        # inside) stats assembly leaves the PREVIOUS statement's record
        # in the thread-local — attributing its wall/kind/rows to this
        # profile row would fabricate exactly the numbers this view
        # exists to make reliable
        mine = st is not None and getattr(st, "sql", None) == sql
        finished = mine and getattr(st, "total_ms", 0.0) > 0.0
        rec = {
            "trace_id": spans[0].trace_id,
            "sql": sql,
            "kind": kind if kind is not None
            else (st.kind if mine else "error"),
            "total_ms": total_ms if total_ms is not None
            else (st.total_ms if finished
                  else round(spans[0].dur_ms, 3)),
            "rows_out": rows_out if rows_out is not None
            else (int(st.rows_out) if mine else 0),
            "phases": phases,
            "n_spans": len(spans),
            "spans": [s.to_dict() for s in spans],
            "stages": list(stage_stats or []),
        }
        # the critical path (utils/critpath.py): which chain of segments
        # actually bounded this query's wall — classified, counted
        # (`crit/*`), ringed (`.sys/query_critical_path`), and stored on
        # the profile for the `/trace/<id>` timeline export. Lever-gated:
        # YDB_TPU_CRITPATH=0 freezes all of it (`cp` is then None)
        if cp is not None:
            from ydb_tpu.utils import critpath
            rec["critical_path"] = cp
            critpath.record_counters(cp)
            self.critpath_stats.append({
                "trace_id": rec["trace_id"], "sql": sql,
                "kind": rec["kind"], "wall_ms": cp["wall_ms"],
                "coverage": cp["coverage"],
                "connected": cp["connected"],
                "non_device_ms": cp["non_device_ms"],
                "dominant_span": cp["dominant_span"],
                "dominant_class": cp["dominant_class"],
                "dominant_ms": cp["dominant_ms"],
                **{f"{cls}_ms": cp["classes"].get(cls, 0.0)
                   for cls in critpath.CLASSES},
            })
        self.profiles.append(rec)

    @staticmethod
    def _critical_path(spans, memory) -> Optional[dict]:
        """`critpath.extract` over a statement's spans, joined with its
        ledger's bytes; None under YDB_TPU_CRITPATH=0 or where the
        analysis fails (it must never fail a query)."""
        from ydb_tpu.utils import critpath
        if not critpath.enabled():
            return None
        try:
            return critpath.extract(spans, memory=memory)
        except Exception:                    # noqa: BLE001 — analysis
            return None

    def _audit(self, sql: str, ok: bool, kind: str) -> None:
        """Audit trail for mutating statements (the ydb/core/audit sink):
        CRC-framed records in <root>/audit.bin, replayable like any WAL.
        SELECTs are not audited (matching the reference's default); the
        kind comes from THIS statement's parse (not last_stats, which a
        nested execute may have reassigned)."""
        if kind not in self._AUDITED_KINDS or self.catalog.store is None:
            return
        import time as _time
        from ydb_tpu.storage import blobfile as _B
        try:
            _B.wal_append(
                os.path.join(self.catalog.store.root, "audit.bin"),
                {"ts": _time.time(), "kind": kind, "sql": sql[:500],
                 "status": "ok" if ok else "error",
                 "rows": int(getattr(self, "last_rows_affected", 0))},
                sync=False)
        except OSError:
            pass    # auditing must not fail the statement

    def trace_to_topic(self, topic_name: str) -> None:
        """Export finished traces into a topic (the OTLP uploader seat,
        `wilson_uploader.cpp`): each trace is one message, schema-
        stamped. `v: 2` + `timebase: "router"` declare that every
        span's start_ms is already rebased onto THIS engine's tracer
        clock (cross-worker spans via the DqRunTask clock-offset
        estimate) — v1 messages shipped raw worker-local clocks, which
        downstream consumers could not compare across workers."""
        t = self.topic(topic_name)
        self.tracer.sink = lambda spans: t.write(
            {"v": 2, "timebase": "router", "spans": spans})

    def _execute_traced(self, sql: str, session=None,
                        kind_box: Optional[list] = None) -> HostBlock:
        from ydb_tpu.utils.metrics import GLOBAL, QueryStats, Timer
        session = session or self._default_session
        t = Timer()
        stats = QueryStats(sql=sql)
        # per-statement group-by trace window (thread-local): whatever
        # sorted group-bys THIS statement freshly compiles lands in
        # stats.groupby for EXPLAIN ANALYZE / query history. Mark/delta,
        # not reset/snapshot — a nested same-thread statement (DQ router
        # merge stage) must not wipe the outer statement's window
        from ydb_tpu.ops.xla_exec import groupby_trace_mark
        stats._gb_mark = groupby_trace_mark()
        # span-window mark: THIS statement's phase breakdown must only
        # cover spans recorded from here on — a nested statement (the DQ
        # router merge) shares the trace with already-ingested worker
        # spans whose device time is NOT this statement's
        stats._span_mark = len(self.tracer.spans)
        with self.tracer.span("parse"):
            stmt = parse(sql)
        stats.parse_ms = t.lap()
        stats.kind = type(stmt).__name__.lower()
        if kind_box is not None:
            kind_box.append(stats.kind)
        self.last_rows_affected = 0
        GLOBAL.inc("engine/statements")
        self.last_stats = stats
        tx = session.tx
        snap = tx.snapshot if tx is not None else self.snapshot()
        try:
            from ydb_tpu.tx import TxAborted, TxCommitTorn
            if isinstance(stmt, (ast.Begin, ast.Commit, ast.Rollback)):
                with self.lock:
                    try:
                        if isinstance(stmt, ast.Begin):
                            session.begin()
                        elif isinstance(stmt, ast.Commit):
                            session.commit()
                        else:
                            session.rollback()
                    except (TxAborted, TxCommitTorn) as e:
                        # TxCommitTorn keeps its "internal: ... torn"
                        # message — SQL clients see the distinct error
                        # text; session-API clients get the distinct type
                        raise QueryError(str(e)) from e
                    if isinstance(stmt, ast.Commit):
                        # a tx commit lands its CDC events at stamp time —
                        # give lagging views a chance to fold off-read
                        for vt in list(self.views._by_source):
                            self.views.on_commit(vt)
                return _unit_block()
            if isinstance(stmt, ast.Explain):
                return self._explain_stmt(stmt, session)
            if isinstance(stmt, (ast.SetOp, ast.Select)):
                # the `plan` span runs with the `plan_ms` lap, from here to
                # the plan in hand (a cached one too: attr `cache`);
                # `_execute_read` ends it there, or where a host lane takes
                # the statement
                with ExitStack() as planning:
                    plan_sp = planning.enter_context(self.tracer.span("plan"))

                    def end_plan(**attrs):
                        plan_sp.attrs.update(attrs)
                        planning.close()
                    # read locks FIRST — every select path (fused, windowed,
                    # set-op, materialized) must register conflicts
                    names = self._referenced_tables(stmt)
                    stats.tables = sorted(names)
                    if tx is not None:
                        for name in names:
                            if self.catalog.has(name):
                                tx.lock(self.catalog.table(name))
                    # register the snapshot: auto-compaction must not restamp
                    # portions this lock-free read still scans. Autocommit
                    # reads re-take the snapshot ATOMICALLY with registration;
                    # tx snapshots are already coordinator-pinned, so their
                    # registration has no gap to race.
                    if tx is None:
                        snap = self._register_read()
                    else:
                        self._enter_read(snap.plan_step)
                    try:
                        return self._execute_read(stmt, sql, snap, stats, t,
                                                  end_plan)
                    finally:
                        self._exit_read(snap.plan_step)
            # everything below mutates shared state — one writer at a time
            # (readers above run lock-free over their MVCC snapshots)
            with self.lock:   # noqa: SIM117
                # re-take the autocommit snapshot UNDER the lock: two
                # UPDATE v = v + 1 statements that both snapshotted before
                # serializing here would otherwise read the same state and
                # lose an update
                snap = tx.snapshot if tx is not None else self.snapshot()
                if isinstance(stmt, ast.CreateTable):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    return self._create_table(stmt)
                if isinstance(stmt, ast.CreateMaterializedView):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    from ydb_tpu.views import UnsupportedView
                    try:
                        self.views.create(stmt.name, stmt.query, stmt.sql)
                    except UnsupportedView as e:
                        raise QueryError(
                            f"unsupported materialized view: {e}") from e
                    return _unit_block()
                if isinstance(stmt, ast.DropMaterializedView):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    self.views.drop(stmt.name, stmt.if_exists)
                    return _unit_block()
                if isinstance(stmt, ast.DropTable):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    if stmt.if_exists and not self.catalog.has(stmt.name):
                        return _unit_block()
                    deps = self.views.on_table(stmt.name)
                    if deps:
                        raise QueryError(
                            f"table {stmt.name!r} feeds materialized "
                            "view(s): "
                            + ", ".join(sorted(v.name for v in deps)))
                    self.catalog.drop_table(stmt.name)
                    if self._changefeeds.pop(stmt.name, None) is not None:
                        self._cdc_since.pop(stmt.name, None)
                        self._save_topics()   # else the topic stays pinned
                    return _unit_block()
                if isinstance(stmt, ast.AlterTable):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    return self._alter_table(stmt)
                if isinstance(stmt, (ast.CreateIndex, ast.DropIndex)):
                    if tx is not None:
                        raise QueryError("DDL inside a transaction is not "
                                         "supported")
                    if not self.catalog.has(stmt.table):
                        raise QueryError(f"unknown table {stmt.table!r}")
                    t = self._table(stmt.table)
                    if getattr(t, "store_kind", "column") != "row":
                        raise QueryError(
                            "secondary indexes are row-store only (column "
                            "tables index via per-portion min/max stats)")
                    try:
                        if isinstance(stmt, ast.CreateIndex):
                            t.create_index(stmt.name, stmt.column)
                        else:
                            t.drop_index(stmt.name)
                    except ValueError as e:
                        raise QueryError(str(e)) from e
                    if self.catalog.store is not None:
                        self.catalog.store.save_catalog(self.catalog)
                    return _unit_block()
                if isinstance(stmt, ast.Insert):
                    return self._insert(stmt, snap, tx)
                if isinstance(stmt, ast.Update):
                    return self._update(stmt, snap, tx)
                if isinstance(stmt, ast.Delete):
                    return self._delete(stmt, snap, tx)
                raise QueryError(
                    f"unsupported statement {type(stmt).__name__}")
        except (BindError, PlanError) as e:
            raise QueryError(str(e)) from e

    def _execute_read(self, stmt, sql: str, snap, stats, t,
                      end_plan) -> HostBlock:
        """SELECT / set-op execution — lock-free, runs concurrently.
        `end_plan(**attrs)` ends the statement's `plan` span."""
        from ydb_tpu.utils.metrics import GLOBAL
        # collect this read's view-serving decisions (thread-local:
        # reads run concurrently) for QueryStats / EXPLAIN ANALYZE
        self._view_tls.notes = []
        if isinstance(stmt, ast.SetOp):
            end_plan()
            block = self._execute_set_op(stmt, snap)
            self.executor.last_path = "set-op"
            self._finish_stats(stats, t, block)
            return block
        from ydb_tpu.query import window as W
        if W.has_window(stmt):
            end_plan()
            block = self._execute_windowed(stmt, snap)
            self._finish_stats(stats, t, block)
            return block
        if stmt.relation is None:
            end_plan()
            block = self._select_without_from(stmt, snap)
            self.executor.last_path = "literal"
            self._finish_stats(stats, t, block)
            return block
        if self._needs_materialize(stmt):
            end_plan()
            block = self._execute_materialized(stmt, snap)
            self._finish_stats(stats, t, block)
            return block
        from ydb_tpu.ops.xla_exec import late_mat_enabled
        # the late-mat lever changes plan STRUCTURE (latemat
        # annotations) — it must invalidate cached plans like a schema
        # change
        fp = (self._table_fingerprint(stmt, stats.tables),
              late_mat_enabled())
        cached = self._plan_cache.get(sql) \
            if self.config.flag("enable_plan_cache") else None
        if cached is not None and cached[0] == fp:
            plan = cached[1]
            self.plan_cache_hits += 1
            stats.plan_cache_hit = True
            GLOBAL.inc("engine/plan_cache_hits")
        else:
            plan = self.planner.plan_select(stmt)
            if self.config.flag("enable_plan_cache"):
                self._plan_cache[sql] = (fp, plan)
            GLOBAL.inc("engine/plan_cache_misses")
        end_plan(cache="hit" if stats.plan_cache_hit else "miss")
        stats.plan_ms = t.lap()
        # memory admission (kqp_rm_service analog): reserve the
        # scan+build estimate; oversubscribed queries queue here
        from ydb_tpu.query.admission import (
            AdmissionTimeout, estimate_plan_bytes,
        )
        # floor: even column-less scans (count(*)) reserve a
        # nominal slot so admission can actually bound concurrency
        est = max(estimate_plan_bytes(self.catalog, plan, snap), 1 << 20)
        # admission calibration: the ledger compares this estimate to
        # the measured peak at close (`admission/est_error_pct`)
        from ydb_tpu.utils import memledger
        memledger.note_admission(est)
        # compile-ahead lane (ydb_tpu/progstore): a novel plan shape
        # starts its fused program fill on the background pool NOW —
        # store deserialize or fresh AOT compile, single-flight deduped
        # with the dispatch below — overlapped with the window/admission
        # wait it would otherwise serialize behind
        self.executor.compile_ahead(plan, plan.params, snap)
        try:
            block = None
            if self._batch_lane is not None:
                # batched dispatch lane: same-shape arrivals coalesce
                # into one stacked execution (window + admission handled
                # by the batch leader — members hold neither)
                block = self._batch_lane.try_run(plan, snap, est, stats)
            if block is None:
                block = self._dispatch_and_drain(plan, snap, est)
        except AdmissionTimeout as e:
            raise QueryError(str(e)) from e
        self._finish_stats(stats, t, block)
        return block

    def _dispatch_and_drain(self, plan, snap, est: int) -> HostBlock:
        """The concurrent query pipeline: a *dispatch phase* (plan →
        compile-cache hit → device enqueue, `Executor.execute_async`)
        followed by a *readout phase* that resolves the device-result
        future lock-free — so while this query drains D2H, the next
        one's dispatch is already in flight (overlapped dispatches
        pipeline ~35 ms → ~10 ms on the measured hardware, PERF.md).

        The admission reservation spans BOTH phases (result buffers
        live in device memory until drained), and `pipeline_window`
        bounds dispatched-but-undrained queries on top of the byte
        budget."""
        # window slot FIRST, byte reservation second: a query parked
        # behind the window must not sit on admission bytes it isn't
        # using (that would shed concurrent large queries with spurious
        # AdmissionTimeouts). Sem holders waiting on admission shed via
        # its deadline and release the slot — no circular wait — and the
        # slot wait itself is BOUNDED by the same deadline, so a window
        # saturated by admission-queued queries sheds instead of
        # head-of-line blocking every later SELECT indefinitely.
        from contextlib import ExitStack
        with ExitStack() as held:
            self._admission_wait(held, est)
            return self._dispatch_drain_admitted(plan, snap, est)

    def _admission_wait(self, held, est: int) -> None:
        """Take a pipeline-window slot and `est` bytes of the admission
        budget into the ExitStack `held`, both waits under ONE span
        (critical-path extraction classes it admission_wait;
        `phases["admission_ms"]`), so a statement that queued here says so
        instead of leaving a gap. `in_flight_mb`: what others held
        reserved when this one arrived; `waited`: whether it queued for a
        slot or for bytes. The per-query path's, and a batch leader's."""
        from ydb_tpu.query.admission import AdmissionTimeout
        from ydb_tpu.utils.metrics import GLOBAL
        with self.tracer.span(
                "admission-wait", admitted_mb=est >> 20,
                in_flight_mb=self.admission.in_flight >> 20) as sp:
            waited = not self._pipe_sem.acquire(blocking=False)
            if waited and not self._pipe_sem.acquire(
                    timeout=self.admission.timeout_s):
                GLOBAL.inc("pipeline/window_timeouts")
                raise AdmissionTimeout(
                    f"pipeline window saturated: "
                    f"{self.pipeline_window} queries "
                    "dispatched-or-queued for longer than the "
                    "admission deadline")
            held.callback(self._pipe_sem.release)
            waited |= held.enter_context(self.admission.admit(est))
            sp.attrs["waited"] = waited

    def _dispatch_drain_admitted(self, plan, snap, est: int) -> HostBlock:
        """Body of the pipeline once the window slot + byte reservation
        are held: dispatch, account the in-flight overlap, drain."""
        from ydb_tpu.utils.metrics import GLOBAL, Timer
        entered = False
        try:
            with self.tracer.span("execute", admitted_mb=est >> 20):
                fut = self.executor.execute_async(plan, snap)
            with self._pipe_mu:
                self._pipe_inflight += 1
                entered = True
                if self._pipe_inflight > 1:
                    # another query was dispatched and undrained when
                    # this one entered: the pipeline genuinely
                    # overlapped (the counter the threaded throughput
                    # test asserts on)
                    GLOBAL.inc("pipeline/overlap_hits")
                GLOBAL.set("pipeline/in_flight", self._pipe_inflight)
            GLOBAL.inc("pipeline/dispatched")
            t_read = Timer()
            with self.tracer.span("readout"):
                block = fut.result()
            GLOBAL.inc("pipeline/readout_ms", t_read.ms())
            return block
        finally:
            if entered:
                with self._pipe_mu:
                    self._pipe_inflight -= 1
                    GLOBAL.set("pipeline/in_flight", self._pipe_inflight)

    def _select_without_from(self, sel: ast.Select,
                             snap: Optional[Snapshot] = None) -> HostBlock:
        """Constant SELECT (`select 1 + 1 as x`): fold each item host-side
        — one row, no scan (the literal-executer analog). Scalar
        subqueries evaluate first (the q88 report shape: a row of
        independent counts)."""
        from ydb_tpu.core import dtypes as dt
        from ydb_tpu.core.dictionary import Dictionary
        from ydb_tpu.query.binder import _try_fold

        def eval_subs(e):
            import dataclasses
            if isinstance(e, ast.ScalarSubquery):
                blk = self._run_select(e.query, snap)
                if len(blk.schema.names) != 1:
                    raise QueryError("scalar subquery must select one "
                                     "column")
                if blk.length > 1:
                    raise QueryError("scalar subquery returned "
                                     f"{blk.length} rows")
                if blk.length == 0:
                    return ast.Literal(None)     # SQL: empty → NULL
                v = blk.to_pandas().iloc[0, 0]
                if v is None or (isinstance(v, float) and np.isnan(v)):
                    return ast.Literal(None)
                if hasattr(v, "item"):
                    v = v.item()   # numpy scalar → python
                return ast.Literal(v)
            if not hasattr(e, "__dataclass_fields__"):
                return e

            def rw(v):
                if isinstance(v, tuple):
                    return tuple(rw(x) for x in v)
                if hasattr(v, "__dataclass_fields__"):
                    return eval_subs(v)
                return v
            return dataclasses.replace(
                e, **{fld: rw(getattr(e, fld))
                      for fld in e.__dataclass_fields__})

        cols, arrays, valids, dicts = [], {}, {}, {}
        for i, item in enumerate(sel.items):
            expr2 = eval_subs(item.expr)
            if isinstance(expr2, ast.Literal) and expr2.value is None:
                name = item.alias or f"column{i}"
                cols.append(Column(name, dt.DType(dt.Kind.INT64, True)))
                arrays[name] = np.zeros(1, np.int64)
                valids[name] = np.zeros(1, bool)
                continue
            folded = _try_fold(expr2)
            if folded is None:
                raise QueryError(
                    "SELECT without FROM supports constant expressions only")
            name = item.alias or f"column{i}"
            v = folded.value
            if v is None:
                cols.append(Column(name, dt.DType(dt.Kind.INT64, True)))
                arrays[name] = np.zeros(1, np.int64)
                valids[name] = np.zeros(1, bool)
            elif isinstance(v, bool):
                cols.append(Column(name, dt.DType(dt.Kind.BOOL, False)))
                arrays[name] = np.array([v])
            elif isinstance(v, int):
                cols.append(Column(name, dt.DType(dt.Kind.INT64, False)))
                arrays[name] = np.array([v], np.int64)
            elif isinstance(v, float):
                cols.append(Column(name, dt.DType(dt.Kind.FLOAT64, False)))
                arrays[name] = np.array([v], np.float64)
            else:
                d = Dictionary()
                cols.append(Column(name, dt.DType(dt.Kind.STRING, False)))
                arrays[name] = d.encode([str(v)])
                dicts[name] = d
        return HostBlock.from_arrays(Schema(cols), arrays, valids, dicts)

    def _finish_stats(self, stats, t, block) -> None:
        from ydb_tpu.ops.xla_exec import groupby_trace_delta
        from ydb_tpu.utils.metrics import GLOBAL
        stats.execute_ms = t.lap()
        stats.total_ms = stats.parse_ms + stats.plan_ms + stats.execute_ms
        stats.rows_out = block.length
        stats.path = self.executor.last_path
        stats.fused = stats.path == "fused"
        stats.distributed = stats.path.startswith("distributed")
        stats.view_serving = getattr(self._view_tls, "notes", None) or []
        delta = groupby_trace_delta(getattr(stats, "_gb_mark", {}))
        # the bounds-lattice gauges ride the same trace window under a
        # `bounds_` prefix — split them into their own stats surface
        stats.bounds = {k[len("bounds_"):]: v for k, v in delta.items()
                        if k.startswith("bounds_")}
        stats.groupby = {k: v for k, v in delta.items()
                         if not k.startswith("bounds_")}
        GLOBAL.inc("engine/rows_out", block.length)
        GLOBAL.inc("engine/queries")
        self.query_history.append(stats)
        if self.tracer._state().depth <= 1:
            # an outermost statement is analysed once, at its close
            # (`_close_statement`), over its whole trace: its
            # `bookkeeping_ms` runs from here, where `total_ms` stops
            stats._book_t0 = t.t0
            return
        # a nested statement (EXPLAIN ANALYZE's inner one, the DQ router
        # merge) now, over its own span window: it shares the trace with
        # spans that are not its own. The window is rooted under a CLOSED
        # copy of its still-open statement span: un-spanned statement-
        # interior time (binder work, dictionary predicate evaluation,
        # CTE/derived-table materialization — the q13 host lane) then
        # classifies as the statement's host_lane self-time instead of
        # vanishing into a virtual-root scheduler gap
        from ydb_tpu.utils import memledger, progstats
        window = []
        if self.tracer.sampled:
            window = self.tracer.spans[getattr(stats, "_span_mark", 0):]
            stk = self.tracer._stack
            if stk:
                import dataclasses as _dc
                window = [_dc.replace(
                    stk[-1],
                    dur_ms=self.tracer._now() - stk[-1].start_ms)] \
                    + window
        # the resource ledger as of NOW (the enclosing statement's, or
        # its own, which closes in execute() after this returns)
        led = memledger.current()
        self._analyse(stats, window,
                      led.summary() if led is not None else None,
                      progstats.current(), user=False)

    def _analyse(self, stats, spans: list, memory, programs,
                 user: bool) -> Optional[dict]:
        """A finished statement's own analysis: `phases` (the phase
        spans' own times), the resource-ledger and program roll-ups
        (`programs`: the statement's `progstats` accumulator, the
        `-- programs:` block), and the critical path over `spans` (the
        EXPLAIN ANALYZE `-- critical path:` source), which it returns:
        None where the statement was unsampled or the lever is off.
        `user`: a user statement, counted once in the latency histograms
        and the slow-query notes (not a nested internal statement, whose
        second, cheaper sample would drag p50 down and double count, nor
        a worker's DQ stage program)."""
        from ydb_tpu.utils import critpath
        from ydb_tpu.utils.metrics import GLOBAL_HIST
        from ydb_tpu.utils.tracing import phase_breakdown
        if memory is not None:
            stats.memory = memory
        if programs is not None:
            stats.programs = programs.summary()
        cp = None
        if spans:
            stats.phases = phase_breakdown(spans)
            cp = self._critical_path(spans, stats.memory)
            if cp is not None:
                stats.critical_path = critpath.summarize(cp)
        if user:
            GLOBAL_HIST.observe("query/latency_ms", stats.total_ms)
            GLOBAL_HIST.observe("query/parse_ms", stats.parse_ms)
            GLOBAL_HIST.observe("query/plan_ms", stats.plan_ms)
            GLOBAL_HIST.observe("query/execute_ms", stats.execute_ms)
            # slow-query bookkeeping is USER-statement-scoped too: DQ
            # stage/merge SQL embeds per-query uuid temp names that can
            # never match a future run — remembering them would churn
            # the bounded forced-trace set and inflate slow_query/*
            self._note_slow(stats.sql, stats.total_ms, stats.kind)
            self._note_host_slow(stats)
        return cp

    def _note_slow(self, sql: str, total_ms: float, kind: str) -> None:
        """Slow-query log counter family + the forced-sampling set: a
        statement over the threshold is counted, and its TEXT is
        remembered so its next run is traced even at sample rate 0."""
        if total_ms < self.slow_query_ms or not sql:
            return
        from ydb_tpu.utils.metrics import GLOBAL
        GLOBAL.inc("slow_query/count")
        GLOBAL.inc(f"slow_query/{kind or 'other'}")
        GLOBAL.set_max("slow_query/worst_ms", total_ms)
        with self._trace_mu:
            if len(self._slow_sqls) >= 256 and sql not in self._slow_sqls:
                # bounded: drop the least-slow remembered offender
                victim = min(self._slow_sqls, key=self._slow_sqls.get)
                del self._slow_sqls[victim]
            self._slow_sqls[sql] = max(self._slow_sqls.get(sql, 0.0),
                                       total_ms)

    def _note_host_slow(self, stats) -> None:
        """A statement the HOST held up says where: one log line (text
        prefix, wall, phases, the wall no span covers) and
        `slow_query/host_slow` when its wall less the device's share
        (`queue_ms`, `device_ms`, a batched member's `batch_wait_ms`)
        passes `HOST_SLOW_MS`. Not at
        `slow_query_ms`: a statement whose program runs a second is not
        slow on the host. Sampled statements only: an unsampled one has
        no phases to take the device's share from."""
        ph = stats.phases
        if not ph:
            return
        # a batched member's wait for its group is its group's device
        # work (and the window), not the host holding it up
        host_ms = stats.total_ms - ph.get("queue_ms", 0.0) \
            - ph.get("device_ms", 0.0) - ph.get("batch_wait_ms", 0.0)
        if host_ms < HOST_SLOW_MS:
            return
        from ydb_tpu.utils.metrics import GLOBAL
        GLOBAL.inc("slow_query/host_slow")
        unspanned = stats.total_ms - stats.parse_ms - stats.plan_ms \
            - sum(ph.values())
        _LOG.warning(
            "host-slow statement %r: wall %.1f ms, host %.1f ms (parse "
            "%.1f, plan %.1f, phases %s), unspanned %.1f ms",
            stats.sql[:60], stats.total_ms, host_ms, stats.parse_ms,
            stats.plan_ms, ph, unspanned)

    def counters(self) -> dict:
        """Live counter snapshot (the /counters endpoint payload)."""
        from ydb_tpu.ops.xla_exec import _GLOBAL_CACHE
        from ydb_tpu.utils.metrics import GLOBAL, GLOBAL_HIST, HIST_FAMILIES
        c = GLOBAL.snapshot()
        c.update(GLOBAL_HIST.snapshot())
        # the fixed histogram families are always visible (zeros before
        # the first observation), like the counter families below
        for fam in HIST_FAMILIES:
            for q in ("count", "p50", "p95", "p99", "max"):
                c.setdefault(f"hist/{fam}/{q}", 0)
        c.update({
            "engine/plan_cache_size": len(self._plan_cache),
            "executor/fused_plans": len(self.executor._fused_cache),
            "device_cache/hits": self.executor.device_cache.hits,
            "device_cache/misses": self.executor.device_cache.misses,
            "device_cache/bytes": self.executor.device_cache.bytes,
            "program_cache/hits": _GLOBAL_CACHE.hits,
            "program_cache/misses": _GLOBAL_CACHE.misses,
            "coordinator/plan_step": self.coordinator.last_plan_step,
            "pipeline/window": self.pipeline_window,
            "batch/window_ms": self.batch_window_ms,
        })
        # always-visible counters (zero before the first SELECT / fresh
        # compile), so dashboards/probes never see missing keys — the
        # set is the registry's [viz] marks, one source of truth
        from ydb_tpu.utils.metrics import ALWAYS_VISIBLE
        for k in ALWAYS_VISIBLE:
            c.setdefault(k, 0)
        c.setdefault("trace/sample_rate", self.trace_sample)
        c.setdefault("trace/profiles_held", len(self.profiles))
        return c

    def prewarm(self, tables=None) -> int:
        """Upload table columns into the HBM cache ahead of queries (the
        buffer-pool warmup analog; see `Executor.prewarm`)."""
        return self.executor.prewarm(tables)

    def _explain_stmt(self, stmt: ast.Explain, session) -> HostBlock:
        """EXPLAIN [ANALYZE] — plan text (+ live execution stats), the
        `kqp_query_plan.cpp` plan-with-stats analog."""
        from ydb_tpu.core.dictionary import Dictionary
        from ydb_tpu.core import dtypes as dt
        if self._needs_materialize(stmt.query):
            # CTE/derived-table stages materialize at run time; their
            # sub-plans depend on intermediate results
            lines = ["(materialized CTE/derived-table stages; run EXPLAIN "
                     "ANALYZE for live stats)"]
        elif stmt.query.relation is None:
            lines = ["(constant SELECT — literal executer, no scan)"]
        else:
            try:
                lines = explain(
                    self.planner.plan_select(stmt.query)).split("\n")
            except (BindError, PlanError, KeyError) as e:
                raise QueryError(str(e)) from e
        if isinstance(stmt.query, ast.Select):
            # serving-mode probe (no fold): which way would this read go
            snap = self.snapshot()
            for name in sorted(self._referenced_tables(stmt.query)):
                view = self.views.get(name)
                if view is not None:
                    mode = view.peek_mode(snap)
                    serving = (f"state @ plan_step {view.watermark}"
                               if mode == "state"
                               else f"base-query fallback ({mode})")
                    lines.append(
                        f"-- view {name}: watermark plan_step="
                        f"{view.watermark}, serving={serving}")
        if stmt.analyze:
            block = self.execute(stmt.sql, session=session, _internal=True)
            lines += self.last_stats.render().split("\n")
            tr = self.tracer.render()
            if tr:
                lines += ["-- trace:"] + tr.split("\n")
        d = Dictionary()
        codes = d.encode(lines)
        schema = Schema([Column("plan", dt.DType(dt.Kind.STRING, False))])
        return HostBlock.from_arrays(schema, {"plan": codes},
                                     dictionaries={"plan": d})

    def _run_select(self, sel,
                    snap: Optional[Snapshot] = None) -> HostBlock:
        """Execute an in-memory Select/SetOp AST (DML subflows, CTE
        bodies, window inner queries) — no text-keyed plan cache."""
        from ydb_tpu.query import window as W
        snap = snap or self.snapshot()
        if isinstance(sel, ast.SetOp):
            return self._execute_set_op(sel, snap)
        if W.has_window(sel):
            return self._execute_windowed(sel, snap)
        if self._needs_materialize(sel):
            return self._execute_materialized(sel, snap)
        plan = self.planner.plan_select(sel)
        return self.executor.execute(plan, snap)

    def _execute_set_op(self, stmt: ast.SetOp,
                        snap: Optional[Snapshot] = None) -> HostBlock:
        """UNION / UNION ALL: CTEs materialize once (visible to every
        arm), arms run through the normal device path, the combine (and
        dedup for UNION) runs host-side."""
        from ydb_tpu.query import window as W
        snap = snap or self.snapshot()
        temps: list = []
        try:
            rewritten = self._rewrite_sel(stmt, {}, temps, snap)
            # combine/dedup is host pandas work: spanned so it ranks as
            # host_lane on the critical path (arms' device spans nest
            # inside and classify themselves)
            with self.tracer.span("setop-host-lane"):
                df = self._eval_setop_df(rewritten, snap)
                try:
                    df = W.apply_order_limit(df, stmt.order_by,
                                             stmt.limit, stmt.offset)
                except ValueError as e:
                    raise QueryError(str(e)) from e
            return HostBlock.from_pandas(df)
        finally:
            for tn in temps:
                if self.catalog.has(tn):
                    self.catalog.drop_table(tn)

    def _eval_setop_df(self, node, snap):
        """Evaluate an already-rewritten SetOp tree to a pandas frame."""
        import pandas as pd
        if isinstance(node, ast.SetOp):
            left = self._eval_setop_df(node.left, snap)
            right = self._eval_setop_df(node.right, snap)
            if len(left.columns) != len(right.columns):
                raise QueryError("UNION arms have different arity")
            right.columns = left.columns
            # the combined frame is the actual host job — guard it too
            # (N arms each under the limit can still combine over it);
            # count=False: rows were already counted at their leaf arms
            self._host_lane_guard(len(left) + len(right), "setop",
                                  count=False)
            if node.op in ("union", "union_all"):
                out = pd.concat([left, right], ignore_index=True)
                if node.op == "union":
                    out = out.drop_duplicates(ignore_index=True)
                return out
            cols = list(left.columns)

            def counts(lf, rf, how):
                """Per-distinct-row multiplicities of both arms."""
                lc = lf.groupby(cols, dropna=False).size() \
                       .rename("__l").reset_index()
                rc = rf.groupby(cols, dropna=False).size() \
                       .rename("__r").reset_index()
                return lc.merge(rc, on=cols, how=how)

            if node.op == "intersect":
                return left.drop_duplicates().merge(
                    right.drop_duplicates(), on=cols, how="inner") \
                    .reset_index(drop=True)
            if node.op == "intersect_all":
                m = counts(left, right, "inner")
                reps = np.minimum(m["__l"], m["__r"]).to_numpy()
            elif node.op == "except":
                m = left.drop_duplicates().merge(
                    right.drop_duplicates(), on=cols, how="left",
                    indicator=True)
                return m[m["_merge"] == "left_only"][cols] \
                    .reset_index(drop=True)
            else:                    # except_all: multiplicity difference
                m = counts(left, right, "left")
                reps = np.maximum(m["__l"] - m["__r"].fillna(0), 0) \
                    .astype(int).to_numpy()
            return m[cols].loc[m.index.repeat(reps)] \
                          .reset_index(drop=True)
        arm = self._run_select(node, snap)
        self._host_lane_guard(arm.length, "setop")
        return arm.to_pandas()

    def _host_lane_guard(self, rows: int, lane: str,
                         count: bool = True) -> None:
        """Host pandas lanes (windows, set-op combine) degrade loudly: a
        counter records the rows crossing to host (`count=False` for
        re-checks of already-counted rows, e.g. set-op combine levels),
        and frames above the configured limit refuse instead of silently
        becoming single-core pandas jobs."""
        from ydb_tpu.utils.metrics import GLOBAL
        if count:
            GLOBAL.inc(f"engine/host_lane/{lane}_rows", rows)
        if rows > self.config.host_lane_max_rows:
            raise QueryError(
                f"{lane} host-fallback lane refused a {rows}-row frame "
                f"(host_lane_max_rows={self.config.host_lane_max_rows}; "
                f"raise it in config to accept the single-core cost)")

    def _execute_windowed(self, sel: ast.Select,
                          snap: Optional[Snapshot] = None) -> HostBlock:
        """Window functions: the inner query (scan/filter/join/agg) runs
        on the device; the window pass runs host-side over its (usually
        post-aggregation) result — see `ydb_tpu/query/window.py`."""
        from ydb_tpu.query import window as W
        snap = snap or self.snapshot()
        try:
            inner, outer, post = W.split_windowed(sel)
        except ValueError as e:
            raise QueryError(str(e)) from e
        inner_block = self._run_select(inner, snap)
        df = None
        device_ok = self.config.flag("enable_device_windows") \
            and inner_block.length >= self.config.window_device_min_rows
        if device_ok and post is None and not sel.distinct \
                and sel.limit is not None:
            # final ORDER BY + LIMIT pushable: every output leaves the
            # device sliced to offset+limit rows (O(rows) egress was the
            # dominant window cost — PERF.md r5)
            fs = self._final_sort_spec(sel, outer)
            if fs is not None:
                with self.tracer.span("window-device",
                                      rows=inner_block.length):
                    done = self._windows_on_device(inner_block, outer,
                                                   final_sort=fs,
                                                   limit=sel.limit,
                                                   offset=sel.offset
                                                   or 0)
                if done is not None:
                    lo = sel.offset or 0
                    return HostBlock.from_pandas(
                        done.iloc[lo:lo + sel.limit]
                        .reset_index(drop=True))
        if device_ok:
            with self.tracer.span("window-device",
                                  rows=inner_block.length):
                df = self._windows_on_device(inner_block, outer)
        if df is None:
            self._host_lane_guard(inner_block.length, "window")
            try:
                # its own span so the single-core pandas lane ranks as
                # host_lane on the critical path (the q13 class), not
                # as unattributed statement self-time
                with self.tracer.span("window-host-lane",
                                      rows=inner_block.length):
                    df = W.compute_windows(inner_block.to_pandas(),
                                           outer)
            except ValueError as e:
                raise QueryError(str(e)) from e
        if post is not None:
            # window results used INSIDE expressions: evaluate the
            # rewritten items as a second pass over the computed frame.
            # NULL-bearing numeric columns come back from to_pandas as
            # object dtype — coerce them back, or from_pandas would
            # classify them as STRING and the post arithmetic would run
            # on dictionary codes
            import pandas as pd
            win_cols = {p["alias"] for k, p in outer if k == "win"}
            for c in df.columns:
                if df[c].dtype != object:
                    continue
                numeric = c in win_cols or (
                    inner_block.schema.has(c)
                    and not inner_block.schema.dtype(c).is_string)
                if numeric:
                    df[c] = pd.to_numeric(df[c])
            temps: list = []
            try:
                tname = self._register_temp(HostBlock.from_pandas(df),
                                            temps, snap)
                final = ast.Select(items=post,
                                   relation=ast.TableRef(tname))
                df = self._run_select(final, snap).to_pandas()
            finally:
                for tn in temps:
                    if self.catalog.has(tn):
                        self.catalog.drop_table(tn)
        if sel.distinct:
            df = df.drop_duplicates(ignore_index=True)
        try:
            df = W.apply_order_limit(df, sel.order_by, sel.limit,
                                     sel.offset)
        except ValueError as e:
            raise QueryError(str(e)) from e
        return HostBlock.from_pandas(df)

    def _final_sort_spec(self, sel, outer):
        """[(output name, ascending)] when every ORDER BY key is a plain
        output-column reference with default NULL placement; None
        otherwise (the host tail handles the exotic cases)."""
        names = set()
        for kind, payload in outer:
            names.add(payload if kind == "col" else payload["alias"])
        fs = []
        for o in sel.order_by:
            if not isinstance(o.expr, ast.Name) \
                    or o.expr.parts[-1] not in names \
                    or o.nulls_first is not None:
                return None
            fs.append((o.expr.parts[-1], o.ascending))
        return fs

    def _windows_on_device(self, inner_block: HostBlock, outer,
                           final_sort=None, limit=None, offset=0):
        """Device window lane (`ops/window_dev.py`): every spec computed
        in one scatter-free jitted program — sort, segment boundaries,
        prefix-scan formulas — with a single device→host transfer for
        all outputs (sliced to offset+limit rows when the final sort
        pushes down). Returns the assembled frame, or None when a spec
        requires the pandas lane (which then counts its host rows)."""
        import pandas as pd

        from ydb_tpu.ops.window_dev import compute_windows_device
        from ydb_tpu.utils.metrics import GLOBAL
        try:
            dev = compute_windows_device(inner_block, outer,
                                         final_sort=final_sort,
                                         limit=limit, offset=offset)
        except Exception:                # noqa: BLE001 — lane, not law
            GLOBAL.inc("engine/window_device_errors")
            return None
        if dev is None:
            return None
        GLOBAL.inc("engine/window_device_rows", inner_block.length)
        if final_sort is not None:
            GLOBAL.inc("engine/window_device_pushdown")

        def series(vals, valid, dic):
            if dic is not None:
                s = pd.Series(dic.decode(vals), dtype=object)
            else:
                s = pd.Series(vals)
            if valid is not None and not valid.all():
                s = s.where(pd.Series(valid))
            return s

        if final_sort is not None:
            sliced, _n = dev
            cols = {}
            for kind, payload in outer:
                name = payload if kind == "col" else payload["alias"]
                cols[name] = series(*sliced[name])
            return pd.DataFrame(cols)
        base = inner_block.to_pandas()
        cols = {}
        for kind, payload in outer:
            if kind == "col":
                cols[payload] = base[payload]
            else:
                cols[payload["alias"]] = series(*dev[payload["alias"]])
        return pd.DataFrame(cols)

    def explain(self, sql: str) -> str:
        stmt = parse(sql)
        if not isinstance(stmt, ast.Select):
            raise QueryError("EXPLAIN supports SELECT only")
        return explain(self.planner.plan_select(stmt))

    def query(self, sql: str):
        """Execute and return a pandas DataFrame (tests / CLI)."""
        return self.execute(sql).to_pandas()

    def _table_fingerprint(self, sel: ast.Select, names=None):
        """(name, uid, data_version) of every table the statement touches —
        the plan-cache validity key (reference keys its compile cache on
        query text + schema version, `kqp_compile_service.cpp:411`).
        `names`: pass an already-computed `_referenced_tables` set so the
        hot SELECT path walks the AST once, not twice."""
        out = []
        for n in sorted(names if names is not None
                        else self._referenced_tables(sel)):
            if self.catalog.has(n):
                t = self.catalog.table(n)
                out.append((n, t.uid, t.data_version))
        return tuple(out)

    def _referenced_tables(self, sel: ast.Select) -> set:
        """Every table name the statement touches (plan-cache keys and
        transaction read-lock acquisition)."""
        names: set = set()

        def walk_sel(s):
            if isinstance(s, ast.SetOp):
                walk_sel(s.left)
                walk_sel(s.right)
                return
            for (_n, body) in s.ctes:
                walk_sel(body)
            if s.relation is not None:
                walk_rel(s.relation)
            for e in ([i.expr for i in s.items] + [s.where, s.having]
                      + list(s.group_by) + [o.expr for o in s.order_by]):
                walk_expr(e)

        def walk_rel(r):
            if isinstance(r, ast.TableRef):
                names.add(r.name)
            elif isinstance(r, ast.Join):
                walk_rel(r.left)
                walk_rel(r.right)
                walk_expr(r.on)
            elif isinstance(r, ast.SubqueryRef):
                walk_sel(r.query)

        def walk_expr(e):
            if e is None or not hasattr(e, "__dataclass_fields__"):
                return
            if isinstance(e, (ast.Exists, ast.InSubquery, ast.ScalarSubquery)):
                walk_sel(e.query)
                if isinstance(e, ast.InSubquery):
                    walk_expr(e.arg)
                return
            def walk_val(v):
                if isinstance(v, tuple):
                    for x in v:
                        walk_val(x)
                else:
                    walk_expr(v)

            for f in e.__dataclass_fields__:
                walk_val(getattr(e, f))

        walk_sel(sel)
        return names

    # -- CTE / derived-table materialization -------------------------------
    #
    # WITH bodies and FROM subqueries materialize into transient column
    # tables before the outer statement plans — the stage-materialization
    # strategy of DQ precompute stages (`dq_opt_phy_finalizing.cpp`
    # DqBuildStages: a stage result becomes the next stage's source).

    def _needs_materialize(self, sel) -> bool:
        if isinstance(sel, ast.SetOp):
            return True
        if sel.ctes:
            return True
        from ydb_tpu.scheme import sysview as SV
        refs = self._referenced_tables(sel)
        if any(SV.is_sysview(n) for n in refs):
            return True               # `.sys/...` materializes at plan time
        if any(self.views.has(n) for n in refs):
            return True               # view reads serve from folded state

        def rel_has(r):
            if isinstance(r, ast.SubqueryRef):
                return True
            if isinstance(r, ast.Join):
                return rel_has(r.left) or rel_has(r.right)
            return False

        def expr_has(e):
            if e is None or not hasattr(e, "__dataclass_fields__"):
                return False
            if isinstance(e, (ast.Exists, ast.InSubquery, ast.ScalarSubquery)):
                sub = self._needs_materialize(e.query)
                if isinstance(e, ast.InSubquery):
                    return sub or expr_has(e.arg)
                return sub

            def any_in(v):
                if isinstance(v, tuple):
                    return any(any_in(x) for x in v)
                return expr_has(v)

            return any(any_in(getattr(e, f))
                       for f in e.__dataclass_fields__)

        if sel.relation is not None and rel_has(sel.relation):
            return True
        for e in ([i.expr for i in sel.items] + [sel.where, sel.having]
                  + list(sel.group_by) + [o.expr for o in sel.order_by]):
            if expr_has(e):
                return True
        return False

    def _execute_materialized(self, sel: ast.Select,
                              snap: Optional[Snapshot] = None) -> HostBlock:
        snap = snap or self.snapshot()
        temps: list = []
        try:
            sel2 = self._rewrite_sel(sel, {}, temps, snap)
            plan = self.planner.plan_select(sel2)
            return self.executor.execute(plan, snap)
        finally:
            for t in temps:
                if self.catalog.has(t):
                    self.catalog.drop_table(t)

    def _rewrite_sel(self, sel, cte_map: dict,
                     temps: list, snap: Optional[Snapshot] = None):
        if isinstance(sel, ast.SetOp):
            cte_map = dict(cte_map)
            for (name, body) in sel.ctes:
                cte_map[name] = self._materialize(
                    self._rewrite_sel(body, cte_map, temps, snap), temps,
                    snap)
            out = ast.SetOp(
                sel.op,
                self._rewrite_sel(sel.left, cte_map, temps, snap),
                self._rewrite_sel(sel.right, cte_map, temps, snap),
                sel.order_by, sel.limit, sel.offset)
            return out
        cte_map = dict(cte_map)
        for (name, body) in sel.ctes:
            cte_map[name] = self._materialize(
                self._rewrite_sel(body, cte_map, temps, snap), temps,
                snap)

        def rewrite_rel(r):
            if isinstance(r, ast.TableRef):
                t = cte_map.get(r.name)
                if t is not None:
                    return ast.TableRef(t, r.alias or r.name)
                view = self.views.get(r.name)
                if view is not None:
                    vsnap = snap or self.snapshot()
                    blk, mode = view.serve(vsnap)
                    notes = getattr(self._view_tls, "notes", None)
                    if notes is not None:
                        notes.append({"view": r.name, "mode": mode,
                                      "watermark": view.watermark})
                    if blk is not None:
                        tname = self._register_temp(blk, temps, vsnap)
                        return ast.TableRef(tname, r.alias or r.name)
                    # base-query fallback: materialize the defining
                    # SELECT at this read's snapshot
                    from ydb_tpu.sql.parser import parse
                    sub = self._rewrite_sel(parse(view.vp.sql), {},
                                            temps, vsnap)
                    tname = self._materialize(sub, temps, vsnap)
                    return ast.TableRef(tname, r.alias or r.name)
                from ydb_tpu.scheme import sysview as SV
                if SV.is_sysview(r.name):
                    try:
                        blk = SV.sysview_block(self, r.name)
                    except KeyError as e:
                        raise QueryError(str(e.args[0])) from e
                    tname = self._register_temp(blk, temps, snap)
                    return ast.TableRef(tname, r.alias or "sys")
                return r
            if isinstance(r, ast.Join):
                return ast.Join(r.kind, rewrite_rel(r.left),
                                rewrite_rel(r.right),
                                rewrite_expr(r.on))
            if isinstance(r, ast.SubqueryRef):
                t = self._materialize(
                    self._rewrite_sel(r.query, cte_map, temps, snap), temps,
                    snap)
                return ast.TableRef(t, r.alias)   # Select OR SetOp body
            return r

        def rewrite_expr(e):
            import dataclasses
            if e is None or not hasattr(e, "__dataclass_fields__"):
                return e
            if isinstance(e, (ast.Exists, ast.InSubquery,
                              ast.ScalarSubquery)):
                q = self._rewrite_sel(e.query, cte_map, temps, snap)
                if isinstance(q, ast.SetOp):
                    # plan over a materialized temp: the planner only
                    # decorrelates plain selects (explicit column items —
                    # Star would lose the planner's naming contract)
                    tname = self._materialize(q, temps, snap)
                    cols = self.catalog.table(tname).schema.names
                    q = ast.Select(
                        items=[ast.SelectItem(ast.Name((c,)), c)
                               for c in cols],
                        relation=ast.TableRef(tname))
                kw = {"query": q}
                if isinstance(e, ast.InSubquery):
                    kw["arg"] = rewrite_expr(e.arg)
                return dataclasses.replace(e, **kw)

            def rw(v):
                if isinstance(v, tuple):
                    return tuple(rw(x) for x in v)
                return rewrite_expr(v)

            kw = {f: rw(getattr(e, f)) for f in e.__dataclass_fields__}
            return dataclasses.replace(e, **kw)

        out = ast.Select(**{**sel.__dict__})
        out.ctes = []
        if out.relation is not None:
            out.relation = rewrite_rel(out.relation)
        out.where = rewrite_expr(out.where)
        out.having = rewrite_expr(out.having)
        out.items = [ast.SelectItem(rewrite_expr(i.expr), i.alias)
                     for i in out.items]
        out.group_by = [rewrite_expr(g) for g in out.group_by]
        out.order_by = [ast.OrderItem(rewrite_expr(o.expr), o.ascending,
                                      o.nulls_first) for o in out.order_by]
        return out

    def _materialize(self, sel, temps: list,
                     snap: Optional[Snapshot] = None) -> str:
        """Materialize an already-rewritten Select or SetOp into a
        transient table; returns its name."""
        from ydb_tpu.query import window as W
        snap = snap or self.snapshot()
        if isinstance(sel, ast.SetOp):
            df = self._eval_setop_df(sel, snap)
            try:
                df = W.apply_order_limit(df, sel.order_by, sel.limit,
                                         sel.offset)
            except ValueError as e:
                raise QueryError(str(e)) from e
            block = HostBlock.from_pandas(df)
        elif W.has_window(sel):
            block = self._execute_windowed(sel, snap)
        else:
            block = self.executor.execute(self.planner.plan_select(sel),
                                          snap)
        return self._register_temp(block, temps, snap)

    def _register_temp(self, block: HostBlock, temps: list,
                       snap: Optional[Snapshot] = None) -> str:
        snap = snap or self.snapshot()
        tname = f"__tmp{next(self._tmp_ids)}"
        # temps inherit the engine's block size: the default (1<<20) would
        # jit-compile every downstream program at 1M-row capacity even for
        # tiny CTE results
        t = self.catalog.create_table(tname, block.schema,
                                      [block.schema.names[0]], shards=1,
                                      portion_rows=self.executor.block_rows,
                                      transient=True)
        t.dictionaries = {n: cd.dictionary
                          for n, cd in block.columns.items()
                          if cd.dictionary is not None}
        if block.length:
            # committed INSIDE the driving snapshot (tx snapshots are
            # pinned — a fresh coordinator step would be invisible); the
            # temp is private and dropped right after, so the early
            # version leaks nowhere
            t.commit(t.write(block), WriteVersion(snap.plan_step, 0))
            t.indexate()
        temps.append(tname)
        return tname

    # -- DDL / DML ---------------------------------------------------------

    def _create_table(self, stmt: ast.CreateTable) -> HostBlock:
        if self.catalog.has(stmt.name):
            if stmt.if_not_exists:
                return _unit_block()
            raise QueryError(f"table {stmt.name!r} already exists")
        if self.views.has(stmt.name):
            raise QueryError(
                f"{stmt.name!r} already names a materialized view")
        cols = [Column(name, sql_type_to_dtype(ty, not_null))
                for (name, ty, not_null) in stmt.columns]
        pk = stmt.primary_key or [cols[0].name]
        schema = Schema(cols)
        if stmt.ttl_days and not stmt.ttl_column:
            raise QueryError("ttl_days needs ttl_column")
        if stmt.ttl_column:            # validate BEFORE creating anything
            from ydb_tpu.core.dtypes import Kind as _K
            if not schema.has(stmt.ttl_column):
                raise QueryError(f"unknown TTL column {stmt.ttl_column!r}")
            if schema.dtype(stmt.ttl_column).kind not in (_K.DATE32,
                                                          _K.INT64):
                raise QueryError("TTL column must be Date or Int64 "
                                 "(unix seconds)")
            if stmt.ttl_days <= 0:
                raise QueryError("ttl_days must be positive")
        t = self.catalog.create_table(stmt.name, schema, pk,
                                      shards=max(1, stmt.partition_count),
                                      store_kind=stmt.store)
        serial_cols = [n for (n, ty, _nn) in stmt.columns
                       if ty.lower() in ("serial", "bigserial")]
        if serial_cols:
            t.serial_next = {c: 1 for c in serial_cols}
        if stmt.ttl_column:
            t.ttl = (stmt.ttl_column, stmt.ttl_days)
        if (serial_cols or stmt.ttl_column) \
                and self.catalog.store is not None:
            self.catalog.store.save_catalog(self.catalog)
        return _unit_block()

    def run_ttl(self, now: Optional[float] = None) -> dict:
        """Evict expired rows from every TTL-configured table (the
        background `ttl.cpp` change in the reference — here an explicit
        maintenance entry point, like `indexate`). `now`: unix seconds
        (defaults to wall clock; tests pass a fixed value). Returns
        {table: rows evicted}."""
        import datetime as _dt
        import time as _time
        from ydb_tpu.core.dtypes import Kind as _K
        now = _time.time() if now is None else now
        out = {}
        for name in list(self.catalog.tables):
            t = self.catalog.table(name)
            ttl = getattr(t, "ttl", None)
            if not ttl or getattr(t, "transient", False):
                continue
            col, days = ttl
            if t.schema.dtype(col).kind is _K.DATE32:
                cutoff_days = int(now // 86400) - days
                d = _dt.date(1970, 1, 1) + _dt.timedelta(days=cutoff_days)
                pred = f"{col} < date '{d.isoformat()}'"
            else:
                pred = f"{col} < {int(now) - days * 86400}"
            self.execute(f"delete from {name} where {pred}",
                         _internal=True)
            out[name] = self.last_rows_affected
            from ydb_tpu.utils.metrics import GLOBAL
            GLOBAL.inc("engine/ttl_evicted", self.last_rows_affected)
        return out

    def _table(self, name: str):
        """Catalog lookup with a user-facing error (not a raw KeyError)."""
        try:
            return self.catalog.table(name)
        except KeyError as e:
            raise QueryError(str(e.args[0])) from e

    def _alter_table(self, stmt: ast.AlterTable) -> HostBlock:
        """ADD/DROP COLUMN (the schemeshard alter-table suboperation
        analog): schema evolves in place, old portions serve nulls for
        added columns, the plan cache invalidates via data_version."""
        if not self.catalog.has(stmt.name):
            raise QueryError(f"unknown table {stmt.name!r}")
        t = self._table(stmt.name)
        if stmt.action == "add":
            if t.schema.has(stmt.column):
                raise QueryError(
                    f"column {stmt.column!r} already exists")
            if stmt.not_null and (
                    t.num_rows > 0
                    or getattr(t, "store_kind", "column") == "row"):
                # existing rows have no value for it; row tables replay
                # their full mutation log at boot, so even an empty one
                # cannot prove future replays satisfy NOT NULL
                raise QueryError(
                    "ADD COLUMN NOT NULL needs an empty column table "
                    "(no default-value backfill yet)")
            if stmt.col_type.lower() in ("serial", "bigserial"):
                raise QueryError("ADD COLUMN Serial is not supported "
                                 "(sequences initialize at CREATE TABLE)")
            col = Column(stmt.column,
                         sql_type_to_dtype(stmt.col_type, stmt.not_null))
            t.add_column(col)
        else:
            if not t.schema.has(stmt.column):
                raise QueryError(f"unknown column {stmt.column!r}")
            if stmt.column in t.key_columns \
                    or stmt.column in (t.partition_by or []):
                raise QueryError(
                    f"cannot drop key/partition column {stmt.column!r}")
            ttl = getattr(t, "ttl", None)
            if ttl is not None and ttl[0] == stmt.column:
                raise QueryError(
                    f"column {stmt.column!r} is the TTL column")
            serial = getattr(t, "serial_next", None)
            if serial is not None:
                serial.pop(stmt.column, None)
            try:
                t.drop_column(stmt.column)
            except ValueError as e:     # e.g. column still indexed
                raise QueryError(str(e)) from e
        if self.catalog.store is not None:
            self.catalog.store.save_catalog(self.catalog)
            self.catalog.store.save_dictionaries(t)
        return _unit_block()

    def _insert(self, stmt: ast.Insert, snap=None, tx=None) -> HostBlock:
        table = self._table(stmt.table)
        if tx is not None:
            # a blind VALUES insert/upsert only WRITES the target:
            # pk-granular write locks (row stores) or commuting appends
            # (column stores) — duplicate-pk races are caught by the
            # point-conflict check at commit. INSERT ... SELECT may READ
            # the target (self-reference) and its source reads aren't
            # separately locked, so it keeps the table-granular lock.
            tx.lock(table, read=stmt.query is not None)
        if stmt.query is not None:
            return self._insert_select(stmt, table, snap, tx)
        names = stmt.columns or table.schema.names
        data: dict[str, list] = {n: [] for n in names}
        from ydb_tpu.query.binder import _try_fold
        for row in stmt.rows:
            if len(row) != len(names):
                raise QueryError("VALUES arity mismatch")
            for n, lit in zip(names, row):
                if isinstance(lit, ast.Literal) and lit.value is None:
                    data[n].append(None)
                    continue
                folded = _try_fold(lit)   # literals, -x, DATE '...', CAST
                if folded is None:
                    raise QueryError("VALUES must be constant expressions")
                data[n].append(folded.value)

        # SERIAL columns omitted from the column list draw from the
        # table's sequence (the sequenceshard analog); counters persist
        # via the catalog and heal from data maxima at recovery
        serial = getattr(table, "serial_next", None)
        if serial:
            n_rows = len(stmt.rows)
            changed = False
            for c, nxt in list(serial.items()):
                if c not in data:
                    data[c] = list(range(nxt, nxt + n_rows))
                    names = list(names) + [c]
                    serial[c] = nxt + n_rows
                    changed = True
                else:
                    # explicit values advance the counter past their max
                    # (same-session duplicates, not just post-restart heal)
                    mx = max((int(v) for v in data[c] if v is not None),
                             default=0)
                    if mx >= serial[c]:
                        serial[c] = mx + 1
                        changed = True
            if changed and self.catalog.store is not None:
                self.catalog.store.save_catalog(self.catalog)

        if getattr(table, "store_kind", "column") == "row":
            ops = []
            for i in range(len(stmt.rows)):
                ops.append((stmt.mode, {n: data[n][i] for n in names}))
            try:
                self._apply_row_ops(table, ops, tx)
                self.last_rows_affected = len(ops)
            except ValueError as e:
                raise QueryError(str(e)) from e
            return _unit_block()

        arrays, valids = {}, {}
        n_rows = len(stmt.rows)
        for c in table.schema:
            if c.name in data:
                vals = data[c.name]
                mask = np.array([v is not None for v in vals])
                if c.dtype.is_string:
                    codes = table.dictionaries[c.name].encode(
                        [None if v is None else str(v) for v in vals])
                    arrays[c.name] = codes
                else:
                    arrays[c.name] = np.array(
                        [0 if v is None else v for v in vals], dtype=c.dtype.np)
                if not mask.all():
                    if not c.dtype.nullable:
                        raise QueryError(f"NULL in NOT NULL column {c.name}")
                    valids[c.name] = mask
            else:
                if not c.dtype.nullable:
                    raise QueryError(f"missing NOT NULL column {c.name}")
                arrays[c.name] = np.zeros(n_rows, dtype=c.dtype.np)
                valids[c.name] = np.zeros(n_rows, dtype=bool)
        block = HostBlock.from_arrays(table.schema, arrays, valids,
                                      dict(table.dictionaries))
        if tx is not None:
            writes = table.write(block, tx=tx.tx_id)
            tx.col_writes.append((table, writes))
            tx.note_self_bump(table)   # staged write bumps data_version
            self.last_rows_affected = block.length
            return _unit_block()
        writes = table.write(block)
        with self._commit_step() as version:
            table.commit(writes, version)
        self.last_rows_affected = block.length
        table.indexate(self._maintenance_watermark(),
                       compact=self.config.flag("enable_auto_compaction"))
        self._maybe_split(table)
        return _unit_block()

    def _maybe_split(self, table) -> None:
        """Auto-split trigger at commit points (the table-stats split of
        `schemeshard__table_stats.cpp`, collapsed to a row threshold)."""
        if not getattr(table, "maybe_split", None):
            return
        if table.maybe_split(self.config.shard_split_rows):
            from ydb_tpu.utils.metrics import GLOBAL
            GLOBAL.inc("engine/shard_splits")
            if self.catalog.store is not None:
                self.catalog.store.save_catalog(self.catalog)

    def _apply_row_ops(self, table, ops, tx) -> None:
        """Row-table mutation: immediate at a fresh version (autocommit)
        or staged under the open transaction."""
        if not ops:
            return
        if tx is not None:
            table.apply(ops, None, durable=False, tx=tx.tx_id)
            tx.row_writes.append((table, ops))
            # pk-granular write lock: a tx that only WRITES this table
            # validates point conflicts on these keys, not the whole
            # table's data_version
            tx.note_self_bump(table, write_pks=table.pks_of_ops(ops))
        else:
            with self._commit_step() as version:
                table.apply(ops, version)
            # threshold-fold for this table's views: keeps read-time
            # drains to one small tail (non-blocking, no-op without views)
            self.views.on_commit(table.name)


    # -- UPDATE / DELETE ---------------------------------------------------
    #
    # Row tables (DataShard analog): evaluate the WHERE through the normal
    # query path, then apply point mutations on the version chains — MVCC
    # snapshots keep seeing the old rows.
    #
    # Column tables: evaluated the same way, then applied as MVCC delete
    # marks on immutable portions (storage/portion.py DeleteMark) — time
    # travel preserved, transactional staging supported; UPDATE commits
    # its marks and re-inserts through one intent-journal record.

    def _update(self, stmt: ast.Update, snap=None, tx=None) -> HostBlock:
        table = self._table(stmt.table)
        if tx is not None:
            tx.lock(table)
        set_cols = [c for (c, _e) in stmt.assignments]
        for c in set_cols:
            if c in table.key_columns:
                raise QueryError("UPDATE of primary key columns is not "
                                 "supported (DELETE + INSERT)")
        # constant assignments (incl. string literals, which the binder
        # cannot type outside comparisons) apply directly; computed
        # expressions evaluate through the query path
        from ydb_tpu.query.binder import _try_fold
        const_vals: dict = {}
        computed: list = []
        for (c, e) in stmt.assignments:
            if isinstance(e, ast.Literal) and e.value is None:
                const_vals[c] = None
                continue
            folded = _try_fold(e)
            if folded is not None:
                const_vals[c] = folded.value
            else:
                computed.append((c, e))

        if getattr(table, "store_kind", "column") == "row":
            items = [ast.SelectItem(ast.Name((k,)), k)
                     for k in table.key_columns]
            items += [ast.SelectItem(e, f"__set_{c}")
                      for (c, e) in computed]
            df = self._run_select(ast.Select(
                items=items, relation=ast.TableRef(stmt.table),
                where=stmt.where), snap).to_pandas()
            ops = []
            for row in df.to_dict("records"):
                vals = {k: _native(row[k]) for k in table.key_columns}
                vals.update(const_vals)
                vals.update({c: _native(row[f"__set_{c}"])
                             for (c, _e) in computed})
                ops.append(("upsert", vals))
            self._apply_row_ops(table, ops, tx)
            self.last_rows_affected = len(ops)
            return _unit_block()
        # column table: select full updated rows at the snapshot, mark the
        # originals deleted (MVCC delete marks — historical snapshots keep
        # the old rows), re-insert the new versions at the same commit
        items = [ast.SelectItem(ast.Name((c,)), c)
                 for c in table.schema.names]
        items += [ast.SelectItem(e, f"__set_{c}") for (c, e) in computed]
        df = self._run_select(ast.Select(
            items=items, relation=ast.TableRef(stmt.table),
            where=stmt.where), snap).to_pandas()
        for (c, _e) in computed:
            df[c] = df.pop(f"__set_{c}")
        for c, v in const_vals.items():
            df[c] = v
        hits = self._column_delete_hits(table, stmt.where, snap)
        n_hits = sum(len(rows) for (_s, _p, rows) in hits)
        if tx is not None:
            if n_hits != len(df):
                # portion hits only cover indexed rows: a mismatch means
                # the predicate matched rows STAGED by this same open tx
                # (indexation cannot convert them) — marking would miss
                # them and the re-insert would duplicate
                raise QueryError(
                    "UPDATE of rows inserted in the same transaction is "
                    "not supported yet (commit the insert first)")
            if not len(df):
                self.last_rows_affected = 0
                return _unit_block()
            handles = table.stage_deletes(hits, tx.tx_id)
            if handles:
                tx.note_self_bump(table)      # stage_deletes bump
                tx.col_deletes.append((table, handles))
            block = HostBlock.from_pandas(
                df[list(table.schema.names)], schema=table.schema,
                dictionaries=table.dictionaries)
            writes = table.write(block, tx=tx.tx_id)
            tx.col_writes.append((table, writes))
            tx.note_self_bump(table)  # staged write bump
        else:
            if not len(df):
                self.last_rows_affected = 0
                return _unit_block()
            block = HostBlock.from_pandas(
                df[list(table.schema.names)], schema=table.schema,
                dictionaries=table.dictionaries)
            writes = table.write(block)
            # marks + new rows in ONE commit (one intent record): a crash
            # must never leave a pure delete or a duplicating insert
            with self._commit_step() as version:
                table.commit(writes, version, deletes=hits)
            table.indexate(self._maintenance_watermark(),
                           compact=self.config.flag(
                               "enable_auto_compaction"))
        self.last_rows_affected = len(df)
        return _unit_block()

    def _delete(self, stmt: ast.Delete, snap=None, tx=None) -> HostBlock:
        table = self._table(stmt.table)
        if tx is not None:
            tx.lock(table)
        if getattr(table, "store_kind", "column") == "row":
            items = [ast.SelectItem(ast.Name((k,)), k)
                     for k in table.key_columns]
            df = self._run_select(ast.Select(
                items=items, relation=ast.TableRef(stmt.table),
                where=stmt.where), snap).to_pandas()
            ops = [("delete", {k: _native(row[k])
                               for k in table.key_columns})
                   for row in df.to_dict("records")]
            self._apply_row_ops(table, ops, tx)
            self.last_rows_affected = len(ops)
            return _unit_block()
        hits = self._column_delete_hits(table, stmt.where, snap)
        n = sum(len(rows) for (_s, _p, rows) in hits)
        if tx is not None:
            cnt = int(self._run_select(ast.Select(
                items=[ast.SelectItem(
                    ast.FuncCall("count", (), star=True), "c")],
                relation=ast.TableRef(stmt.table),
                where=stmt.where), snap).to_pandas().iloc[0, 0])
            if n != cnt:
                raise QueryError(
                    "DELETE of rows inserted in the same transaction is "
                    "not supported yet (commit the insert first)")
            handles = table.stage_deletes(hits, tx.tx_id)
            if handles:
                tx.note_self_bump(table)
                tx.col_deletes.append((table, handles))
        elif hits:
            with self._commit_step() as version:
                table.apply_deletes(hits, version)
        self.last_rows_affected = n
        return _unit_block()

    def _column_delete_hits(self, table, where, snap=None) -> list:
        """Matching rows per portion at the snapshot: [(shard, portion,
        row indices)] — the input of the MVCC delete-mark path (the r3
        portion-rewrite delete destroyed time travel; marks preserve it)."""
        keys = table.key_columns
        pks = self._run_select(ast.Select(
            items=[ast.SelectItem(ast.Name((k,)), k) for k in keys],
            relation=ast.TableRef(table.name),
            where=where), snap).to_pandas().drop_duplicates()
        if pks.empty:
            return []
        # inserts → portions first: marks attach to portions (staged
        # inserts are transient; indexation makes them markable)
        table.indexate(self._maintenance_watermark(),
                       compact=self.config.flag("enable_auto_compaction"))
        snap = snap or self.snapshot()
        hits = []
        for shard in table.shards:
            for p in shard.portions:
                if not snap.includes(p.version):
                    continue
                kdf = p.block.select(keys).to_pandas()
                kdf["__pos"] = np.arange(len(kdf))
                hit = kdf.merge(pks, on=keys, how="inner")["__pos"] \
                         .to_numpy()
                dead = p.visible_dead(snap)
                if dead is not None:
                    hit = np.setdiff1d(hit, dead)
                if len(hit):
                    hits.append((shard, p, hit))
        return hits

    def _insert_select(self, stmt: ast.Insert, table, snap=None,
                       tx=None) -> HostBlock:
        block = self._run_select(stmt.query, snap)
        df = block.to_pandas()
        self.last_rows_affected = len(df)
        names = stmt.columns or table.schema.names
        if len(df.columns) != len(names):
            raise QueryError("INSERT ... SELECT arity mismatch")
        df.columns = names
        # SERIAL columns draw from the sequence here too (the VALUES path
        # does the same); explicit values advance the counter
        serial = getattr(table, "serial_next", None)
        if serial:
            changed = False
            for c, nxt in list(serial.items()):
                if c not in df.columns:
                    df[c] = range(nxt, nxt + len(df))
                    names = list(names) + [c]
                    serial[c] = nxt + len(df)
                    changed = True
                else:
                    vals = [int(v) for v in df[c] if v is not None]
                    mx = max(vals, default=0)
                    if mx >= serial[c]:
                        serial[c] = mx + 1
                        changed = True
            if changed and self.catalog.store is not None:
                self.catalog.store.save_catalog(self.catalog)
        if getattr(table, "store_kind", "column") == "row":
            # ops carry only the named columns — "upsert" must keep the
            # unmentioned ones, so no null-filling here (apply() enforces
            # NOT NULL for genuinely absent values)
            ops = [(stmt.mode, {c: _native(v) for c, v in row.items()})
                   for row in df.to_dict("records")]
            try:
                self._apply_row_ops(table, ops, tx)
            except ValueError as e:
                raise QueryError(str(e)) from e
            return _unit_block()
        # null-fill unspecified columns (the VALUES path's semantics)
        for c in table.schema:
            if c.name not in df.columns:
                if not c.dtype.nullable:
                    raise QueryError(f"missing NOT NULL column {c.name}")
                df[c.name] = None
        df = df[list(table.schema.names)]
        if tx is not None and len(df):
            from ydb_tpu.core.block import HostBlock as _HB
            blk = _HB.from_pandas(df, schema=table.schema,
                                  dictionaries=table.dictionaries)
            writes = table.write(blk, tx=tx.tx_id)
            tx.col_writes.append((table, writes))
            tx.note_self_bump(table)   # staged write bumps data_version
            return _unit_block()
        if len(df):
            with self._commit_step() as version:
                table.bulk_upsert(df, version)
        return _unit_block()


def _native(v):
    """pandas cell → python native (None for NA; unwrap numpy scalars)."""
    import pandas as pd
    if v is None or (isinstance(v, float) and v != v):
        return None
    try:
        if pd.isna(v):
            return None
    except (TypeError, ValueError):
        pass
    return v.item() if hasattr(v, "item") else v


def _unit_block() -> HostBlock:
    return HostBlock(Schema([]), {}, 0)
