"""Physical plan executor (single-node).

The analog of the KQP scan-executer + compute-actor run loop
(`kqp_scan_executer.cpp`, `dq_compute_actor_impl.h:295`): streams per-portion
device blocks (HBM column cache) through the device-compiled pipeline
(pushdown program → broadcast-join probes → partial aggregation), then runs
ONE fused device program for the whole final stage — device-side concat of
the partials, merge GroupBy, HAVING, output expressions, sort and limit —
so a query costs K partial dispatches + 1 finalize dispatch + 1 transfer,
not a host round-trip per stage.
"""

from __future__ import annotations

from contextlib import ExitStack
from functools import partial as _partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops import join as J
from ydb_tpu.ops.device import (
    DeviceBlock, DeviceResultFuture, bucket_capacity, to_device, to_host,
    to_host_async,
)
from ydb_tpu.ops.sort import sort_env
from ydb_tpu.ops.xla_exec import (
    _trace_program, compress, compress_block, groupby_tuning, run_on_device,
)
from ydb_tpu.progstore import buckets as shape_buckets
from ydb_tpu.progstore import compile_ahead as ca_lane
from ydb_tpu.query.plan import JoinStep, Pipeline, QueryPlan, SortKey
from ydb_tpu.storage.mvcc import MAX_SNAPSHOT, Snapshot
from ydb_tpu.utils import progstats

DEFAULT_BLOCK_ROWS = 1 << 20


def split_device_wait(t_enqueued: float, t_wait: float, t_done: float,
                      last_done: float) -> tuple:
    """Split one wait for the device, [t_wait, t_done], into the part
    spent behind another statement's program and this program's own run:
    -> (queue_ms, run_ms), disjoint, their sum the wait.

    The device runs one program at a time in the order they were
    enqueued, so this one started when it was enqueued or when the
    program before it completed, whichever came later. `last_done` is
    the last completion the executor observed before this one. A
    host-clock estimate: exact to a thread wake-up while statements
    drain promptly; a result drained long after its program completed
    reads as wait none, run none (the start is clamped into the wait)."""
    run_start = min(max(t_enqueued, last_done, t_wait), t_done)
    return (run_start - t_wait) * 1000.0, (t_done - run_start) * 1000.0


def unpruned(plan: QueryPlan) -> QueryPlan:
    """The plan as a stacked execution runs it (`query/batch_lane.py`):
    scan pruning stripped — its outcome is literal-dependent and cannot
    partition a shared scan."""
    import dataclasses
    pipe = plan.pipeline
    return dataclasses.replace(plan, pipeline=dataclasses.replace(
        pipe, scan=dataclasses.replace(pipe.scan, prune=[])))


def batch_bucket(n_members: int) -> int:
    """Member slots a stacked program runs `n_members` in: the next
    power of two, one executable a bucket."""
    bb = 1
    while bb < n_members:
        bb *= 2
    return bb


def _fused_evict_hook(key) -> None:
    """Map a fused-cache eviction back to its program-inventory kind:
    batched-lane entries key on a ("batched", ...) tuple, everything
    else captured from this cache is a fused program (tile entries are
    not inventoried — mark_evicted on an unknown key is a no-op)."""
    kind = "batched" if isinstance(key, tuple) and key \
        and key[0] == "batched" else "fused"
    progstats.mark_evicted(kind, key)


def _count_latemat_reads(layout_box: dict) -> None:
    """Count, once per fused or batched dispatch, how the program's trace
    read its deferred scan columns: in place (`__lmpos` still the iota)
    or gathered through moved rows. A stored program that predates the
    counts leaves none."""
    lm_read = layout_box.get("latemat")
    if lm_read:
        from ydb_tpu.utils.metrics import GLOBAL
        GLOBAL.inc("latemat/direct_cols", lm_read["direct"])
        GLOBAL.inc("latemat/gathered_cols", lm_read["gathered"])


def _note_probes(sp, builds: list) -> None:
    """The `join-builds` span's attrs, one entry a build in step order:
    `probe` (`lut`, `bsearch` or `partitioned`) and `lut_span`, the LUT's
    entries (0 without one)."""
    if not builds:
        return
    luts = [getattr(bt, "lut", None) for bt in builds]
    sp.attrs["probe"] = ",".join(
        "partitioned" if isinstance(bt, J.PartitionedBuild)
        else "bsearch" if lut is None else "lut"
        for bt, lut in zip(builds, luts))
    sp.attrs["lut_span"] = ",".join(
        "0" if lut is None else str(lut.shape[0]) for lut in luts)


class Executor:
    def __init__(self, catalog, block_rows: int = DEFAULT_BLOCK_ROWS,
                 device_cache=None, mesh=None):
        from ydb_tpu.storage.device_cache import DeviceColumnCache
        from ydb_tpu.ops.exec_cache import ExecCache
        self.catalog = catalog
        self.block_rows = block_rows
        self.device_cache = device_cache or DeviceColumnCache()
        # compiled-program caches share one process-wide live-executable
        # budget with LRU eviction (ops/exec_cache.py) — unbounded dicts
        # here accumulated executables until the platform compile service
        # wedged (r4 cleared them manually between queries)
        self._finalize_cache = ExecCache("finalize")
        self._fused_cache = ExecCache("fused")
        # LRU evictions of fused/batched programs surface in the
        # program inventory (`.sys/compiled_programs`, state=evicted) —
        # the cache keys carry a "batched" head for lane entries, so
        # the kind is recovered from the key itself
        self._fused_cache.on_evict = _fused_evict_hook
        # device mesh for distributed execution (None / size-1 mesh →
        # single-device). The analog of the KQP task graph + DQ hash-shuffle
        # channels (`dq_tasks_graph.h:43`): scans are row-partitioned across
        # mesh devices, the partial→final aggregation boundary is an ICI
        # all_to_all hash shuffle.
        self.mesh = mesh
        self._dist_aggs = ExecCache("dist-agg")
        self._shuffle_joins = ExecCache("shuffle-join")
        # feature flag (utils/config.py): the whole-query single-dispatch
        # path; off = always the portioned streaming path (debug lever)
        self.enable_fused = True
        # engine-provided tracer (utils/tracing.Tracer) — None = no spans
        self.tracer = None
        # when the last program completion was observed (perf_counter):
        # what `_await_device` splits a statement's device wait by
        import threading as _threading
        self._done_mu = _threading.Lock()
        self._last_done = 0.0             # guarded-by: _done_mu
        # which path the last execute() took (THREAD-LOCAL — concurrent
        # sessions each observe their own):
        # fused | fused-tiled[...] | portioned | distributed | literal
        self._tls = _threading.local()
        # build sides above this estimate hash-partition into a GraceJoin
        # (host-DRAM partitions probed one at a time — the spill budget)
        import os as _os
        self.grace_budget_bytes = int(
            _os.environ.get("YDB_TPU_GRACE_BUDGET", 1 << 29))
        # scans whose stacked superblock estimate exceeds this stream
        # through the tiled fused path instead of residing in HBM
        self.fused_scan_budget_bytes = int(
            _os.environ.get("YDB_TPU_FUSED_SCAN_BUDGET", 6 << 30))
        # HBM bytes per scan tile on the tiled path (2 tiles in flight)
        self.tile_budget_bytes = int(
            _os.environ.get("YDB_TPU_TILE_BUDGET", 1 << 30))
        # partial-agg states above this estimate spill to host DRAM and
        # merge per key-hash partition (WideCombiner ProcessSpilled analog)
        self.merge_budget_bytes = int(
            _os.environ.get("YDB_TPU_MERGE_BUDGET", 1 << 30))
        # mesh joins: build sides above this estimate hash-partition across
        # devices (shuffle join) instead of replicating to every device
        self.dist_broadcast_budget_bytes = int(
            _os.environ.get("YDB_TPU_DIST_BROADCAST_BUDGET", 256 << 20))
        # mesh exchanges (the shuffle join's probe rows, the partials'
        # merge): a segment is sized from the rows COUNTED or PROVEN for
        # it, rounded up a power of two and never under this many rows
        # (the floor bounds the shapes, so the compiles, of small
        # exchanges)
        self.mesh_min_segment_rows = 128
        # fused-program complexity cap: plans with more join steps than
        # this stream portioned — a 7-join whole-query program has been
        # observed to SIGSEGV the platform's TPU compiler service
        self.fuse_max_joins = int(
            _os.environ.get("YDB_TPU_FUSE_MAX_JOINS", 6))
        # cross-query join-build cache (query/build_cache.py): finished
        # device-resident BuildTables keyed by build-plan fingerprint +
        # visible data + probe dictionary — the r4 profile's dominant
        # slow-query cost was per-query build re-execution + LUT re-upload
        from ydb_tpu.query.build_cache import BuildCache
        self.build_cache = BuildCache(int(
            _os.environ.get("YDB_TPU_BUILD_CACHE_BUDGET", 2 << 30)),
            device_cache=self.device_cache)
        # single-flight dedup for fused/batched program fills: a client
        # storm on a fresh shape compiles ONCE (one leader traces and
        # compiles, followers block on its future and share the handle)
        # — the compile-ahead lane launches through the same flight so a
        # background warm and a synchronous dispatch never double-compile
        self._sflight = ca_lane.SingleFlight()
        # (table, data_version, lift_sig) triples the compile-ahead lane
        # has already warmed — a repeated statement must not re-walk plan
        # setup on the background pool every time it runs
        self._warm_seen: set = set()
        self._warm_mu = _threading.Lock()
        # launched compile-ahead thunks that have not ended yet, by the
        # same triple: the batched lane's first statement of a shape
        # waits here rather than upload the shape's columns beside the
        # thunk (guarded-by: _warm_mu)
        self._warm_pending: dict = {}
        # triples whose stacked programs `warm_batched` has built
        # (guarded-by: _warm_mu)
        self._batched_warm_seen: set = set()
        # (lift_sig, table uid, data_version, Bb) -> the compiler's
        # argument + temporary + output bytes of that stacked program:
        # what the lane's gate and reservation read. Plain dict: values
        # are ints, reads/writes are GIL-atomic.
        self._batched_bytes: dict = {}
        # build-time trace deltas parked by the compile-ahead worker,
        # keyed by (kind, cache key): the thread-local groupby/bounds
        # gauges a background build records would otherwise vanish —
        # the FIRST foreground statement to consume the warmed entry
        # folds them into its own window (guarded-by: _warm_mu)
        self._trace_debt: dict = {}
        # trace+compile wall-ms of warm-lane builds, parked the same
        # way: the statement that consumes the warmed entry reports the
        # build it triggered in its `compile_ms` phase — byte-equal
        # with the lane off, where the same statement compiles inline
        # (guarded-by: _warm_mu)
        self._compile_debt: dict = {}
        # bound-sized compaction (late materialization): measured live
        # row counts per compact-free fused key, monotone max — an
        # overflow rerun teaches every future sizing of the same shape.
        # Plain dict: values are ints, reads/writes are GIL-atomic.
        self._compact_memo: dict = {}
        # chosen compact capacities per compact-free fused key — sticky
        # so within-headroom data growth reuses the compiled program
        self._compact_caps: dict = {}
    # DQ task-graph runtime (`ydb_tpu/dq/`): >0 while THIS THREAD is
    # running a statement as a stage program of a distributed task — the
    # worker's share of a multi-process graph, or the 1-worker degenerate
    # case. Thread-local: a worker serving a DQ task concurrently with a
    # plain query on another thread must not count the plain query.
    # Counted on /counters (`dq/local_stage_execs`) so workers show
    # their stage traffic.
    @property
    def dq_stage_depth(self) -> int:
        return getattr(self._tls, "dq_stage_depth", 0)

    @dq_stage_depth.setter
    def dq_stage_depth(self, v: int):
        self._tls.dq_stage_depth = v

    # device-resident stage spine: while True on THIS THREAD, a fused
    # statement's result is handed back as a `DeviceStageBlock` (device
    # arrays by reference, host readback deferred) instead of being
    # drained through `fetch_fused_result`. Armed by `dq/task.py` around
    # stage statements so multi-stage plans flow device→device; plain
    # client statements never see it.
    @property
    def dq_device_capture(self) -> bool:
        return getattr(self._tls, "dq_device_capture", False)

    @dq_device_capture.setter
    def dq_device_capture(self, v: bool):
        self._tls.dq_device_capture = v

    @property
    def last_path(self) -> str:
        return getattr(self._tls, "last_path", "")

    @last_path.setter
    def last_path(self, v: str):
        self._tls.last_path = v

    def _span(self, name: str, **attrs):
        if self.tracer is not None:
            return self.tracer.span(name, **attrs)
        from ydb_tpu.utils.tracing import _NullSpanCtx
        return _NullSpanCtx()   # yields a throwaway span (attrs writable)

    def _superblock(self, table, storage_names, rename, snapshot, prune,
                    sources, src_ids, Kb: int):
        """The `superblock-upload` span round the cache's stack of the
        statement's scan columns; its attrs say what this touch of the
        table cost: `bytes` stacked and uploaded now (0 on a resident
        table), `columns`, `sources`, `hit`."""
        info: dict = {}
        with self._span("superblock-upload", columns=len(storage_names),
                        sources=len(sources)) as sp:
            sb = self.device_cache.superblock(
                table, storage_names, rename, snapshot, prune, sources,
                src_ids, pad_to=Kb, info=info)
            sp.attrs.update(info)
        return sb

    def _await_device(self, outputs, t_enqueued: float, prog_kid,
                      fresh: bool) -> None:
        """The `device-execute` span: block until the dispatched program's
        outputs are ready, and split the wait where it ends into queue
        (behind another statement's program) and run — the span's
        `queue_ms` / `run_ms` attrs, `prog/queue_ms`, and the run joined
        to the program's compiler-reported flops/bytes (roofline)."""
        import time as _time

        from ydb_tpu.utils.metrics import GLOBAL
        with self._span("device-execute") as sp:
            t_wait = _time.perf_counter()
            jax.block_until_ready(outputs)
            t_done = _time.perf_counter()
            with self._done_mu:
                last_done = self._last_done
                self._last_done = max(last_done, t_done)
            queue_ms, run_ms = split_device_wait(t_enqueued, t_wait,
                                                 t_done, last_done)
            sp.attrs["queue_ms"] = round(queue_ms, 3)
            sp.attrs["run_ms"] = round(run_ms, 3)
        GLOBAL.inc("prog/queue_ms", queue_ms)
        progstats.record_exec(prog_kid, run_ms, fresh=fresh)

    def _upload_params(self, params: dict, builds: list):
        """The `param-upload` span: the device puts made before every
        fused or stacked dispatch, the statement's array literals (IN
        lists, rank LUTs, members' stacked literals; scalars ride the
        call) and each build's three scalar inputs. Attrs `n` (arrays
        put) and `bytes`. -> (device params, build inputs)."""
        from ydb_tpu.ops import fused as F
        with self._span("param-upload") as sp:
            dev_params = {k: (jnp.asarray(v) if isinstance(v, np.ndarray)
                              else v) for k, v in params.items()}
            build_inputs = [F.build_traced_inputs(bt) for bt in builds]
            puts = [dev_params[k] for k, v in params.items()
                    if isinstance(v, np.ndarray)] \
                + [bi[k] for bi in build_inputs
                   for k in ("lut_base", "n", "has_null")]
            sp.attrs["n"] = len(puts)
            sp.attrs["bytes"] = sum(int(a.nbytes) for a in puts)
        return dev_params, build_inputs

    def _await_stage(self, blocks: list, t_enqueued: float) -> None:
        """The wait for programs that ran block by block, enqueued since
        `t_enqueued`: a mesh lane's per-device prefix programs, which run
        on every device at once, or a shuffle join's build pipeline. ONE
        `device-execute` for them all. They are the per-block programs of
        `ops/xla_exec.ProgramCache`, many to a stage, so the run is
        joined to no single program's inventory entry."""
        self._await_device([(b.arrays, b.valids, b.length) for b in blocks],
                           t_enqueued, None, False)

    def _took_mesh_lane(self, lane: str) -> None:
        """A statement finished on a mesh lane: `last_path` (set last, a
        build side's own statement has set it before) and the lane's
        counter."""
        from ydb_tpu.utils.metrics import GLOBAL
        self.last_path = lane
        # lint: allow-counters(mesh/statements/* registered)
        GLOBAL.inc(f"mesh/statements/{lane}")

    # -- cache warmup ------------------------------------------------------

    def prewarm(self, tables=None, snapshot: Snapshot = MAX_SNAPSHOT) -> int:
        """Upload every column of the given tables (default: all) into the
        HBM superblock cache — the buffer-pool warmup analog
        (`ydb/core/tablet_flat` shared cache fills on demand; here warmup
        matters doubly because this platform's host→device link degrades
        ~20x after the first device→host readout, so uploads queued
        before any result is fetched run at full bandwidth — PERF.md).

        Returns the number of bytes resident in the cache afterwards.
        Tables whose stacked estimate exceeds the fused-scan budget are
        skipped (they will stream through the tiled path anyway)."""
        from ydb_tpu.storage.device_cache import (
            enumerate_scan_sources, estimate_scan_bytes,
        )
        names = tables if tables is not None else list(self.catalog.tables)
        for tname in names:
            table = self.catalog.table(tname)
            storage_names = list(table.schema.names)
            try:
                sources, _ids = enumerate_scan_sources(table, snapshot, None)
            except AttributeError:       # row tables scan uncached
                continue
            if not sources:
                continue
            Kb = shape_buckets.bucket_sources(len(sources))
            est = estimate_scan_bytes(sources, storage_names, pad_to=Kb)
            if est > self.fused_scan_budget_bytes:
                continue
            self.device_cache.superblock(table, storage_names, {}, snapshot,
                                         None, sources, _ids, pad_to=Kb)
        return self.device_cache.bytes

    # -- entry -------------------------------------------------------------

    def execute(self, plan: QueryPlan,
                snapshot: Snapshot = MAX_SNAPSHOT) -> HostBlock:
        return self.execute_async(plan, snapshot).result()

    def execute_async(self, plan: QueryPlan,
                      snapshot: Snapshot = MAX_SNAPSHOT
                      ) -> DeviceResultFuture:
        """Dispatch phase of a SELECT: plan → compile-cache hit → device
        enqueue, WITHOUT blocking on the device→host readout. Returns a
        `DeviceResultFuture` whose `result()` performs the single pytree
        `device_get` (plus host unpack / projection) — the engine drains
        it lock-free, so query N+1 dispatches while query N's result
        crosses the link (the ~35 ms post-readout dispatch cliff
        pipelines down to ~10 ms when overlapped, PERF.md). Paths that
        must materialize host-side mid-flight (distributed, tiled,
        spill) resolve eagerly and return a completed future."""
        if self.dq_stage_depth:
            from ydb_tpu.utils.metrics import GLOBAL
            GLOBAL.inc("dq/local_stage_execs")
        params = dict(plan.params)
        # precompute stage: uncorrelated scalar subqueries → params
        for (pname, subplan) in plan.init_subplans:
            sub = self.execute(subplan, snapshot)
            if sub.length > 1:
                raise RuntimeError("scalar subquery produced more than one row")
            col = sub.columns[sub.schema.names[0]]
            if sub.length == 0 or (col.valid is not None
                                   and not col.valid[0]):
                # NULL scalar: typed zero placeholder + validity companion
                # (the binder wraps nullable params in if(valid, v, null))
                params[pname] = np.zeros((), col.data.dtype)[()]
                params[pname + "__valid"] = False
            else:
                params[pname] = col.data[0]
                params[pname + "__valid"] = True

        if self.mesh is not None and self.mesh.devices.size > 1:
            if self._can_distribute(plan):
                prebuilt: dict = {}
                sj = self._try_execute_shuffle_join(plan, params, snapshot,
                                                    prebuilt)
                if sj is not None:
                    self._took_mesh_lane("distributed-shuffle-join")
                    return DeviceResultFuture.completed(
                        self._project_output(sj, plan.output))
                merged = self._execute_distributed(plan, params, snapshot,
                                                   prebuilt)
                self._took_mesh_lane("distributed")
                return DeviceResultFuture.completed(
                    self._project_output(merged, plan.output))
            if self._can_distribute_map(plan, snapshot):
                merged = self._execute_distributed_map(plan, params,
                                                       snapshot)
                self._took_mesh_lane("distributed-map")
                return DeviceResultFuture.completed(
                    self._project_output(merged, plan.output))

        with self._span("fused-attempt") as attempt:
            fused = self._try_execute_fused(plan, params, snapshot,
                                            defer=True, attempt=attempt) \
                if self.enable_fused else None
        if isinstance(fused, tuple):           # tiled path: (kind, block)
            kind, block = fused
            self.last_path = kind
            return DeviceResultFuture.completed(
                self._project_output(block, plan.output))
        if isinstance(fused, DeviceResultFuture):
            self.last_path = "fused"
            return fused.map(
                lambda b: self._project_output(b, plan.output))

        # fused path declined: it may have prepared the join builds already
        self.last_path = "portioned"
        partials = self._run_pipeline(plan.pipeline, params, snapshot,
                                      builds=fused)
        fut = self._finalize(plan, partials, params, defer=True)
        return fut.map(lambda b: self._project_output(b, plan.output))

    # -- fused whole-query path --------------------------------------------

    def _try_execute_fused(self, plan: QueryPlan, params: dict,
                           snapshot: Snapshot, defer: bool = False,
                           _no_compact: bool = False, attempt=None):
        """Run the query as ONE fused device program (`ops/fused.py`) when
        its shape allows: single device, joins unique-keyed where
        payloads attach (expanding duplicate-key probes need a
        data-dependent output capacity, so they stay on the portioned
        path). Probes use a direct-address LUT when the build has one,
        an unrolled binary search otherwise (sparse spans, float keys).

        Returns the merged HostBlock on success (`defer=True`: a
        `DeviceResultFuture` deferring the single-pytree readout — the
        pipeline dispatch/readout seam); on fallback, the list of
        prepared join BuildTables (for `_run_pipeline` to reuse) or None
        if none were prepared. `attempt`: the statement's `fused-attempt`
        span, which takes the Compact's `compact_cap` / `compact_at`."""
        from ydb_tpu.ops import fused as F

        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)

        # builds + fusability checks FIRST — the superblock stack/upload is
        # the expensive part and must not run for plans that always take
        # the portioned path
        join_steps = [step for kind, step in pipe.steps if kind == "join"]
        if len(join_steps) > self.fuse_max_joins:
            return None                  # program-complexity cap
        with self._span("join-builds", n=len(join_steps)) as sp:
            builds = self._prepare_builds(pipe, params, snapshot)
            _note_probes(sp, builds)
        for step, bt in zip(join_steps, builds):
            if isinstance(bt, J.PartitionedBuild) or (
                    not bt.unique and step.kind in ("inner", "left", "mark")):
                return builds   # partitioned / expanding probe

        plan0 = plan            # pre-rewrite plan (the overflow-rerun input)
        # `fused-prepare`: the host's own work from the builds to the
        # dispatch, phase `prepare_ms` (the superblock's and the literals'
        # uploads nest under it as phases of their own); `hit`: the program
        # was cached, `compact`: the Compact's capacity or 0. The tiled
        # lane's run is no preparation: it leaves the span first
        with ExitStack() as preparing:
            prep = preparing.enter_context(self._span("fused-prepare"))
            (plan, pipe, scan_cols, schema, partial_schema, dicts,
             join_metas, late_scan) = self._fused_plan_setup(plan, builds)

            storage_names = [s for (s, _i) in pipe.scan.columns]
            rename = {s: i for (s, i) in pipe.scan.columns}

            # HBM admission: a scan whose stacked superblock would not fit the
            # budget streams through the tiled path instead of OOMing the chip
            from ydb_tpu.storage.device_cache import (
                enumerate_scan_sources, estimate_scan_bytes,
            )
            sources, src_ids = enumerate_scan_sources(table, snapshot,
                                                      pipe.scan.prune or None)
            # shape buckets: quantize the source count so a growing table
            # reuses the bucket's program (zero-length pad rows, masked out
            # by the per-row length vector exactly like short real sources)
            Kb = shape_buckets.bucket_sources(len(sources))
            if sources and estimate_scan_bytes(sources, storage_names,
                                               pad_to=Kb) \
                    > self.fused_scan_budget_bytes:
                preparing.close()
                return self._execute_fused_tiled(
                    plan, params, pipe, sources, scan_cols, builds,
                    join_metas, dicts, partial_schema)

            sb = self._superblock(table, storage_names, rename, snapshot,
                                  pipe.scan.prune or None, sources, src_ids,
                                  Kb)
            if sb is None:
                return builds or None          # empty scan → portioned path
            arrays, valids, lengths, K, CAP, sb_dicts = sb
            sb_valid_names = frozenset(valids.keys())
            dicts.update(sb_dicts)
            # resource ledger: the scan's device working set is the stacked
            # (K, CAP) superblock; live rows come from the host-side source
            # blocks (no device sync)
            from ydb_tpu.utils import memledger
            memledger.record_padded_buffers(
                "superblock", "superblock",
                int(sum(b.length for b in sources)) if sources else 0,
                K * CAP, arrays, valids)

            sort_params, sort_spec, rank_assigns = self._sort_setup_fused(
                plan, schema, dicts)
            all_params = {**params, **sort_params}

            # lifted LIMIT (paramlift plans only): the clamp rides in as the
            # __lim2 device input and the program keys on the limit's
            # capacity bucket — `limit 3` and `limit 5` share one executable
            lift_limit, lim_key = self._lift_limit_setup(plan, all_params)

            builds_sig = tuple(F.build_inputs_sig(bt) for bt in builds)
            base_key = F.fused_cache_key(plan, scan_cols, K, CAP,
                                         sb_valid_names, builds_sig, sort_spec,
                                         rank_assigns,
                                         tuple(sorted(all_params)),
                                         lim_key=lim_key)
            # bound-sized device compaction: when the filters/joins provably
            # collapse the live count, an `ir.Compact` shrinks the pipeline
            # from scan capacity to a ladder-quantized bound directly after
            # the last reducing join, and every later probe, deferred
            # late-mat gather and the partial group-by compile at the small
            # shape; none where what follows reads its rows in place (a
            # keyless aggregate: `latemat.tail_reads_in_place`). Sized from
            # CBO + FK selectivities plus the
            # measured-live memo; an underestimate trips the device overflow
            # flag in `fetch` and the statement reruns WITHOUT the compact —
            # loud and counted, never a silent truncation.
            declined: dict = {}
            compact_cap, compact_at = (None, None) if _no_compact else \
                self._compact_sizing(base_key, pipe, builds, sources, K * CAP,
                                     join_metas, declined)
            compact_prog = None
            key = base_key
            if compact_cap:
                compact_prog = ir.Program([ir.Compact(compact_cap)])
                key = F.fused_cache_key(plan, scan_cols, K, CAP,
                                        sb_valid_names, builds_sig, sort_spec,
                                        rank_assigns,
                                        tuple(sorted(all_params)),
                                        lim_key=lim_key,
                                        compact_cap=compact_cap,
                                        compact_at=compact_at)
            from ydb_tpu.utils.metrics import GLOBAL
            ndeferred = len(late_scan) + sum(
                len(m["payload_names"]) for m in join_metas if m["late"])
            if ndeferred:
                GLOBAL.inc("latemat/deferred_cols", ndeferred)
            if compact_cap:
                GLOBAL.inc("latemat/compact_plans")
                GLOBAL.inc("latemat/compact_capacity_rows", compact_cap)
                if compact_at < len(pipe.steps):
                    GLOBAL.inc("latemat/compact_early_plans")
                if attempt is not None:
                    attempt.attrs.update(compact_cap=compact_cap,
                                         compact_at=compact_at)
            elif declined:
                GLOBAL.inc("latemat/compact_skipped_plans")
                if attempt is not None:
                    attempt.attrs.update(declined)

            def _builder():
                fn, layout_box = F.build_fused_fn(
                    pipe, plan.final_program, scan_cols, K, CAP,
                    sb_valid_names, join_metas, rank_assigns, sort_spec,
                    plan.limit, plan.offset,
                    tuple(dict.fromkeys(n for (n, _lbl) in plan.output)),
                    lift_limit=lift_limit, late_scan=late_scan,
                    compact_prog=compact_prog, compact_at=compact_at)
                keep = list(dict.fromkeys(n for (n, _lbl) in plan.output))
                out_cols = [c for c in schema.columns if c.name in keep] \
                    or list(schema.columns)
                return fn, layout_box, Schema(out_cols)

            entry = self._fused_cache.get(key)
            fresh_compile = entry is None
            if entry is not None:
                fn, layout_box, out_schema = entry
                progstats.record_hit(getattr(fn, "key_id", None))
                self._consume_trace_debt("fused", key)
            else:
                fn = layout_box = out_schema = None

            prep.attrs.update(hit=entry is not None, compact=compact_cap or 0)
            dev_params, build_inputs = self._upload_params(all_params,
                                                           builds)
        with self._span("device-dispatch", k=K, cap=CAP) as dsp:
            import time as _time
            t_disp = _time.perf_counter()
            fill_wait_ms = 0.0
            if fn is None:
                # fresh shapes fill INSIDE the dispatch span (the
                # compile stays at the span front for the critical-path
                # split and the phase breakdown): exec cache → the
                # persistent program store (a deserialize, compile_ms
                # ~= 0) → the program observatory's AOT capture
                # (`utils/progstats.capture` — lower().compile(), ONE
                # trace + ONE compile, cost and memory analysis
                # recorded, the executable serialized back to the
                # store); all under single-flight so a storm on this
                # shape compiles once
                (fn, layout_box, out_schema), fresh_compile = \
                    self._fused_fill(
                        "fused", key, _builder,
                        (arrays, valids, lengths, build_inputs,
                         dev_params))
                fill_wait_ms = (_time.perf_counter() - t_disp) * 1000.0
            data_stacks, valid_stack, length, aux = fn(
                arrays, valids, lengths, build_inputs, dev_params)
            t_enqueued = _time.perf_counter()
            if fresh_compile:
                # jit compiles synchronously inside the first call of a
                # fresh shape (AOT: in capture above); steady-state
                # dispatch is ~async enqueue — the delta IS this
                # program's trace+compile cost
                dsp.attrs["compile_ms"] = round(
                    (_time.perf_counter() - t_disp) * 1000.0, 3)
            else:
                # compile-ahead consumer: the build ran on the lane's
                # worker thread, triggered by THIS statement's own
                # planning — report the parked trace+compile cost here,
                # once, exactly as the lane-off inline compile would.
                # `compile_wait_ms` is the slice of that build the
                # dispatch actually blocked on (the rest overlapped
                # planning): the phase roll-up subtracts the wait, not
                # the whole off-thread build, from dispatch_ms
                with self._warm_mu:
                    warm_ms = self._compile_debt.pop(("fused", key), None)
                if warm_ms is not None:
                    dsp.attrs["compile_ms"] = warm_ms
                    dsp.attrs["compile_wait_ms"] = round(
                        min(fill_wait_ms, warm_ms), 3)
        _count_latemat_reads(layout_box)
        # result buffers live in HBM until the future drains them
        memledger.record_alloc(
            "result_buffers",
            memledger.deep_nbytes((data_stacks, valid_stack)))

        # readout deferred into the result future: the dispatch above is
        # async, and `fetch_fused_result` performs the ONE device→host
        # pytree transfer when the result is consumed — concurrent
        # queries dispatch while this one drains D2H
        out_dicts = {n2: d for n2, d in dicts.items() if out_schema.has(n2)}
        out_dicts.update({n2: d for n2, d in plan.result_dicts.items()
                          if out_schema.has(n2)})
        lo = plan.offset or 0
        limit = plan.limit

        prog_kid = getattr(fn, "key_id", None)
        # stage-spine capture: read the thread-local flag at DISPATCH
        # time (the future may be resolved on another thread). An OFFSET
        # tail would force a host slice anyway, so those plans keep the
        # host readout.
        capture_device = bool(self.dq_device_capture) and not lo

        def fetch() -> HostBlock:
            # split the readout into on-device execute (block_until_ready
            # delta — the program is still running when the future is
            # consumed promptly) and the D2H transfer + host unpack, so
            # the trace attributes device time separately from link time
            self._await_device((data_stacks, valid_stack, length),
                               t_enqueued, prog_kid, fresh_compile)
            if aux:
                # compact live/overflow: 8 bytes of plan metadata the
                # loud-rerun decision needs host-side. The program is
                # already done executing, so these two scalars ride the
                # result drain — part of the readout's ONE boundary
                # transfer, not a second booked host sync
                live, ovf = (int(x) for x in jax.device_get(
                    (aux["compact_live"], aux["compact_ovf"])
                ))  # lint: transfer-ok(compact overflow check — two scalars riding the result drain)
                GLOBAL.inc("latemat/compact_live_rows", live)
                # measured-live memo (monotone max, keyed by the compact-
                # free program identity): future sizings of this shape
                # never undercut an observed live count
                prev_live = self._compact_memo.get(base_key, 0)
                if live > prev_live:
                    self._compact_memo[base_key] = live
                # live/padded account for the compacted shape: measured
                # live rows against the ladder rung every downstream op
                # ran at (unit-width lanes — the ratio is the signal;
                # the capacity-sized buffers this rung REPLACED never
                # entered the ledger, so this entry is the only place
                # the seam's padding collapse is visible)
                memledger.record_pad("compact", live, compact_cap,
                                     live * 8, compact_cap * 8)
                if ovf:
                    # the bound was forged low — rows past compact_cap
                    # were dropped ON DEVICE. Discard this result and
                    # rerun the statement without the compact (full
                    # capacity), loudly counted. Never serve a truncation.
                    GLOBAL.inc("latemat/compact_overflow_reruns")
                    prev_cap = self.dq_device_capture
                    self.dq_device_capture = capture_device
                    try:
                        redo = self._try_execute_fused(
                            plan0, params, snapshot, _no_compact=True)
                    finally:
                        self.dq_device_capture = prev_cap
                    if redo is None or isinstance(redo, (list, tuple)):
                        raise RuntimeError(
                            "compact overflow rerun declined the fused "
                            "path")
                    return redo
            if capture_device:
                # device-resident spine: hand the stage result back as
                # device arrays by reference — the 4-byte length scalar
                # is the ONLY thing that crosses the link (plan
                # metadata, counted as a device handoff, not a host
                # sync; the program is already done executing)
                from ydb_tpu.ops.device import DeviceStageBlock
                n = int(length)
                dev = F.capture_fused_device(data_stacks, valid_stack, n,
                                             layout_box, out_schema,
                                             out_dicts)
                blk = DeviceStageBlock(dev, n)
                memledger.record_device_handoff(
                    "query/executor.py::fused_capture", blk.live_nbytes())
                return blk
            with self._span("readout-transfer"):
                block = F.fetch_fused_result(data_stacks, valid_stack,
                                             length, layout_box,
                                             out_schema, out_dicts)
            return _apply_offset(block, lo, limit)

        fut = DeviceResultFuture(fetch)
        return fut if defer else fut.result()

    def _fused_fill(self, kind: str, key, builder, capture_args,
                    source: str = "fresh", cache: bool = True,
                    warm_lane: bool = False):
        """Single-flight fused/batched program fill. The miss ladder:
        exec cache (a concurrent filler won) → persistent program store
        (deserialize, `compile_ms ~= 0`, the trace-time `layout_box`/
        `out_schema` replayed from the stored extra) → `builder()` +
        AOT capture (the fresh executable — and its layout extra — is
        serialized back into the store inside `capture`).

        Concurrent fillers of the same (kind, key) dedup on one leader:
        the storm case compiles once and every follower shares the
        leader's `(handle, layout_box, out_schema)` triple. Returns
        `(triple, compiled_here)` — `compiled_here` False on every path
        that skipped the trace+compile (cache, store, follower).

        `cache=False`: return without parking the entry (the batched
        lane caches only after its first successful dispatch, so a
        trace-failing shape never wedges a dead entry in the budget).

        `warm_lane=True` (the compile-ahead worker): a fresh build's
        trace-time gauges land in the WORKER's thread-local window, so
        the leader parks its trace delta in `_trace_debt`; the first
        foreground fill of the same key (warm_lane=False) pops it and
        folds it into the consuming statement's window — EXPLAIN
        ANALYZE / `last_stats.bounds` report the build the statement
        triggered, whichever thread ran it."""
        import threading as _threading
        import time as _time

        from ydb_tpu.ops.xla_exec import (groupby_trace_delta,
                                          groupby_trace_mark)

        def _fill():
            ent = self._fused_cache.get(key)
            if ent is not None:
                progstats.record_hit(getattr(ent[0], "key_id", None))
                return ent, False, 0
            loaded = progstats.store_load(kind, key,
                                          lambda: builder()[0])
            if loaded is not None:
                handle, extra = loaded
                ent = (handle, extra["layout_box"], extra["out_schema"])
                if cache:
                    self._fused_cache[key] = ent
                return ent, False, 0
            mark = groupby_trace_mark() if warm_lane else None
            t_build = _time.perf_counter() if warm_lane else 0.0
            fn, layout_box, out_schema = builder()
            handle = progstats.capture(
                kind, key, fn, capture_args, consult_store=False,
                store_extra={"layout_box": layout_box,
                             "out_schema": out_schema}, source=source)
            ent = (handle, layout_box, out_schema)
            if cache:
                self._fused_cache[key] = ent
            if warm_lane:
                debt = groupby_trace_delta(mark)
                ms = round((_time.perf_counter() - t_build) * 1000.0, 3)
                with self._warm_mu:
                    if debt:
                        self._trace_debt[(kind, key)] = debt
                    self._compile_debt[(kind, key)] = ms
            return ent, True, _threading.get_ident()

        ent, compiled_here, leader_tid = \
            self._sflight.run((kind, key), _fill)
        if not warm_lane:
            self._consume_trace_debt(kind, key)
        # a follower that deduped onto another thread's compile did not
        # itself compile — its dispatch span and exec record stay lean
        return ent, compiled_here and \
            leader_tid == _threading.get_ident()

    def _consume_trace_debt(self, kind: str, key) -> None:
        """Fold a compile-ahead build's parked trace delta into the
        CURRENT thread's window — called from every foreground path
        that can consume a warm-lane-filled entry (the direct cache
        hit and the single-flight fill)."""
        if not self._trace_debt:
            return
        with self._warm_mu:
            debt = self._trace_debt.pop((kind, key), None)
        if debt:
            from ydb_tpu.ops.xla_exec import groupby_trace_fold
            groupby_trace_fold(debt)

    # -- compile-ahead lane ------------------------------------------------

    def compile_ahead(self, plan: QueryPlan, params: dict,
                      snapshot: Snapshot) -> bool:
        """Kick a background fill for this plan's fused program while
        the statement waits in the admission queue (`query/engine.py`
        calls this between planning and `admission.admit`). The warm
        thunk mirrors the synchronous fused setup up to the program key
        and then runs the SAME single-flight fill the dispatch path
        uses — store consult first (a warmed shape deserializes,
        near-free), fresh AOT compile otherwise — so when the statement
        clears admission the executable is ready, or in flight with the
        dispatch deduping onto it.

        Plan-level dedup keeps the lane cheap under repeat traffic: one
        launch per (table, data_version, lift_sig); non-lifted plans
        (no value-free identity) and mesh-distributed plans skip the
        lane. Returns True when a background fill was launched."""
        if not (self.enable_fused and ca_lane.enabled()
                and progstats.enabled()):
            return False
        if self.mesh is not None and self.mesh.devices.size > 1:
            return False
        sig = getattr(plan, "lift_sig", None)
        if sig is None:
            return False
        if getattr(plan, "init_subplans", None):
            # scalar-subquery params are computed at dispatch time; the
            # warm thunk would key on an incomplete param set
            return False
        pipe = plan.pipeline
        try:
            table = self.catalog.table(pipe.scan.table)
        except Exception:              # noqa: BLE001 — lane, not law
            return False
        warm_key = (pipe.scan.table, table.data_version, sig)
        with self._warm_mu:
            if warm_key in self._warm_seen:
                return False
            self._warm_seen.add(warm_key)
        params = dict(params)
        import threading as _threading
        ended = _threading.Event()
        with self._warm_mu:
            self._warm_pending[warm_key] = ended

        def _warm():
            try:
                return self._fused_warm(plan, params, snapshot)
            finally:
                with self._warm_mu:
                    self._warm_pending.pop(warm_key, None)
                ended.set()

        launched = self._sflight.launch(("warm",) + warm_key, _warm)
        if not launched:
            with self._warm_mu:
                self._warm_pending.pop(warm_key, None)
            ended.set()
        return launched

    def _fused_warm(self, plan: QueryPlan, params: dict,
                    snapshot: Snapshot) -> bool:
        """Background half of the compile-ahead lane: the fused-path
        setup (builds, plan walk, superblock, key derivation) WITHOUT
        dispatch, landing in the same `_fused_fill` the synchronous
        path uses. Declines exactly where that path declines to fuse —
        a plan the dispatch would stream portioned/tiled must not burn
        background compile on a program nobody will run."""
        from ydb_tpu.ops import fused as F
        from ydb_tpu.storage.device_cache import (
            enumerate_scan_sources, estimate_scan_bytes,
        )
        from ydb_tpu.utils.metrics import GLOBAL

        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)
        join_steps = [step for kind, step in pipe.steps if kind == "join"]
        if len(join_steps) > self.fuse_max_joins:
            return False
        builds = self._prepare_builds(pipe, params, snapshot)
        for step, bt in zip(join_steps, builds):
            if isinstance(bt, J.PartitionedBuild) or (
                    not bt.unique and step.kind in ("inner", "left",
                                                    "mark")):
                return False
        (plan, pipe, scan_cols, schema, partial_schema, dicts,
         join_metas, late_scan) = self._fused_plan_setup(plan, builds)
        storage_names = [s for (s, _i) in pipe.scan.columns]
        rename = {s: i for (s, i) in pipe.scan.columns}
        sources, src_ids = enumerate_scan_sources(table, snapshot,
                                                  pipe.scan.prune or None)
        Kb = shape_buckets.bucket_sources(len(sources))
        if not sources or estimate_scan_bytes(sources, storage_names,
                                              pad_to=Kb) \
                > self.fused_scan_budget_bytes:
            return False                 # empty / tiled-class scan
        sb = self.device_cache.superblock(table, storage_names, rename,
                                          snapshot,
                                          pipe.scan.prune or None,
                                          sources, src_ids, pad_to=Kb)
        if sb is None:
            return False
        arrays, valids, lengths, K, CAP, sb_dicts = sb
        sb_valid_names = frozenset(valids.keys())
        dicts.update(sb_dicts)
        sort_params, sort_spec, rank_assigns = self._sort_setup_fused(
            plan, schema, dicts)
        all_params = {**params, **sort_params}
        lift_limit, lim_key = self._lift_limit_setup(plan, all_params)
        builds_sig = tuple(F.build_inputs_sig(bt) for bt in builds)
        base_key = F.fused_cache_key(plan, scan_cols, K, CAP,
                                     sb_valid_names, builds_sig, sort_spec,
                                     rank_assigns,
                                     tuple(sorted(all_params)),
                                     lim_key=lim_key)
        # MUST mirror the dispatch path's compact sizing exactly — a
        # warm on a different capacity or position would compile a
        # program the dispatch never asks for
        compact_cap, compact_at = self._compact_sizing(
            base_key, pipe, builds, sources, K * CAP, join_metas)
        compact_prog = None
        key = base_key
        if compact_cap:
            compact_prog = ir.Program([ir.Compact(compact_cap)])
            key = F.fused_cache_key(plan, scan_cols, K, CAP,
                                    sb_valid_names, builds_sig, sort_spec,
                                    rank_assigns,
                                    tuple(sorted(all_params)),
                                    lim_key=lim_key,
                                    compact_cap=compact_cap,
                                    compact_at=compact_at)
        if key in self._fused_cache:
            return False                 # already live — nothing to warm

        def _builder():
            fn, layout_box = F.build_fused_fn(
                pipe, plan.final_program, scan_cols, K, CAP, sb_valid_names,
                join_metas, rank_assigns, sort_spec, plan.limit, plan.offset,
                tuple(dict.fromkeys(n for (n, _lbl) in plan.output)),
                lift_limit=lift_limit, late_scan=late_scan,
                compact_prog=compact_prog, compact_at=compact_at)
            keep = list(dict.fromkeys(n for (n, _lbl) in plan.output))
            out_cols = [c for c in schema.columns if c.name in keep] \
                or list(schema.columns)
            return fn, layout_box, Schema(out_cols)

        dev_params = {k: (jnp.asarray(v) if isinstance(v, np.ndarray)
                          else v) for k, v in all_params.items()}
        build_inputs = [F.build_traced_inputs(bt) for bt in builds]
        self._fused_fill(
            "fused", key, _builder,
            (arrays, valids, lengths, build_inputs, dev_params),
            source="compile_ahead", warm_lane=True)
        # the program is ready before its first dispatch — whether it
        # was compiled here or deserialized from the store
        GLOBAL.inc("prog/compile_ahead_hits")
        return True

    def _sort_setup_fused(self, plan: QueryPlan, schema: Schema,
                          dicts: dict):
        """Rank-LUT sort params against the fused pipeline's final schema
        (mirrors `_sort_setup`, which works from partial-output blocks)."""
        from ydb_tpu.core import dtypes as dt
        sort_params, rank_assigns, spec = {}, [], []
        dicts = {**dicts, **plan.result_dicts}
        for j, sk in enumerate(plan.sort):
            dtype = schema.dtype(sk.name)
            dic = dicts.get(sk.name)
            if dtype.is_string and dic is not None:
                ranks = dic.sort_ranks()
                pname = f"__rank{j}"
                sort_params[pname] = ranks
                rank_col = f"__sortrank{j}"
                rank_assigns.append(ir.Assign(rank_col, ir.call(
                    "take_lut", ir.Col(sk.name),
                    ir.Param(pname, dt.DType(dt.Kind.INT32, False),
                             is_array=True))))
                spec.append((rank_col, sk.ascending, sk.nulls_first))
            else:
                spec.append((sk.name, sk.ascending, sk.nulls_first))
        return sort_params, tuple(spec), rank_assigns

    def _fused_plan_setup(self, plan: QueryPlan, builds: list):
        """Shared front half of the fused paths (single-query and
        batched): one schema walk over the pipeline collecting join
        metas (incl. the LUT-vs-bsearch probe choice per build) and
        landing on the final schema, plus the join-derived group-bound
        rewrite. Returns (plan, pipe, scan_cols, schema, partial_schema,
        dicts, join_metas, late_scan) — plan/pipe possibly rewritten
        (copies; a cached plan is never mutated); `late_scan` is the set
        of scan columns the fused body defers behind a row-position
        column (query/latemat.py), empty when the lever is off."""
        from ydb_tpu.core.dtypes import DType, Kind as _K
        from ydb_tpu.ops import fused as F
        from ydb_tpu.ops.xla_exec import late_mat_enabled
        from ydb_tpu.query import latemat

        late = late_mat_enabled()
        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)
        scan_cols = [Column(i, table.schema.dtype(s))
                     for (s, i) in pipe.scan.columns]

        dicts = {}
        join_metas = []
        bi = 0
        schema = Schema(list(scan_cols))
        if pipe.pre_program is not None:
            schema = ir.infer_schema(pipe.pre_program, schema)
        for kind, step in pipe.steps:
            if kind != "join":
                schema = ir.infer_schema(step, schema)
                continue
            bt = builds[bi]
            bi += 1
            payload_cols = []
            for name in bt.schema.names:
                payload_cols.append(
                    Column(name, bt.schema.dtype(name).with_nullable(True)))
                if name in bt.dictionaries:
                    dicts[name] = bt.dictionaries[name]
            if step.kind == "mark":
                payload_cols.append(Column(step.mark_col or "__mark",
                                           DType(_K.BOOL, False)))
            join_metas.append({
                "probe_key": step.probe_key,
                "kind": step.kind,
                "src_names": tuple(bt.schema.names),
                "payload_names": tuple(bt.schema.names),
                "mark_col": step.mark_col,
                "not_in": step.not_in,
                "payload_cols": payload_cols,
                # a payload build's sparse span (or one past the LUT
                # budget) has no LUT; float PROBES must not
                # truncate through an integer LUT — both take the
                # unrolled binary search in the trace
                "bsearch": bt.lut is None
                or schema.dtype(step.probe_key).kind in (_K.FLOAT64,
                                                         _K.FLOAT32),
                # late materialization: inner/left payloads ride as a
                # (build row-id, match) pair and gather at first compute
                # reference or the bound-sized tail; semi/anti/mark
                # produce no payloads to defer
                "late": late and step.kind in ("inner", "left")
                and bool(bt.schema.names),
                "row_col": f"__lmr{bi - 1}",
                "found_col": f"__lmf{bi - 1}",
            })
            schema = F.apply_join_schema(schema, payload_cols)
        if pipe.partial is not None:
            schema = ir.infer_schema(pipe.partial, schema)
        partial_schema = schema            # tile-output schema (pre-final)
        if plan.final_program is not None:
            schema = ir.infer_schema(plan.final_program, schema)

        # join-derived group-bound: when every group key is pinned by an
        # inner/semi join's build side, ngroups ≤ build rows — stamp the
        # sorted group-by with the proven bound so per-group gathers run
        # at output cardinality (the q3/q9/q13 late-materialization win)
        plan, pipe = self._bounded_groupby_rewrite(plan, builds, join_metas)
        late_scan = latemat.deferrable_scan(
            pipe, [c.name for c in scan_cols]) if late else frozenset()
        return plan, pipe, scan_cols, schema, partial_schema, dicts, \
            join_metas, late_scan

    @staticmethod
    def _lift_limit_setup(plan: QueryPlan, all_params=None,
                          force: bool = False):
        """(lift_limit, lim_key) for a fused compile: lifted plans with a
        LIMIT pass limit+offset as the __lim2 device input and key the
        program on its capacity bucket; everything else keeps the baked
        constants (byte-identical compile key to the pre-lift path).

        `force`: the batched lane ALWAYS lifts a LIMIT — its shape sig
        groups on the bucket, so members whose only difference is the
        LIMIT/OFFSET value must still clamp per member (a zero-literal
        `limit 3` and `limit 5` coalesce; baking the leader's value
        would hand every member the leader's row count).
        `all_params`: when given, the leader's __lim2 is injected (the
        batched lane instead injects per member)."""
        from ydb_tpu.ops.fused import LIMIT_PARAM
        if plan.limit is None or not (
                force or getattr(plan, "lift_names", ())):
            return False, None
        lim2 = plan.limit + (plan.offset or 0)
        if all_params is not None:
            all_params[LIMIT_PARAM] = np.int32(lim2)
        return True, ("limB", bucket_capacity(lim2, minimum=128))

    def _compact_sizing(self, base_key, pipe, builds, sources,
                        cap0: int, join_metas: list,
                        declined: Optional[dict] = None) -> tuple:
        """(capacity, position) of the fused pipeline's one `ir.Compact`:
        the ladder-quantized capacity it compacts to, and the number of
        `pipe.steps` entries that run before it — directly after the
        last reducing join, the last JOIN this walk credits with a
        ratio under 1 (q9: after the part-name semi, before the
        partsupp and orders probes); the end of the steps where no join
        lowered the estimate (the scan's own `est_rows` did: a filtered
        scan that returns rows or groups them by a wide key).
        (None, None) when compaction isn't worth a shape (`ir.Compact`
        placement: `ops/fused._fused_body`), and where the estimate is
        but what runs from the position on reads its rows in place
        (`latemat.tail_reads_in_place`; q6's keyless sum: the sort of
        every scan position and five gathers were 79 % of its time at
        SF10, PERF.md round 34). `declined` then takes
        `compact_skipped=<reason>`: the dispatch counts it
        (`latemat/compact_skipped_plans`), the compile-ahead does not.

        The estimate is sizing-quality, not correctness-bearing — the
        device overflow flag catches every underestimate and the
        statement reruns at full capacity (loud). Components:

        * live scan rows, tightened by the CBO's post-local-predicate
          estimate (`ScanSpec.est_rows`) when present;
        * per INNER join against a filtered build, a uniform-FK
          selectivity `min(1, build_rows / base_table_rows)` — the
          Selinger containment assumption (q7's nation-filtered
          supplier ~2/25);
        * per SEMI join whose build key is declared-UNIQUE, coverage
          `min(1, build_rows / key_domain)`: a unique build holds one
          row per covered key, so its cardinality IS the covered-key
          count and the ratio is the uniform-FK survival probability
          (q9's part-name semi keeps ~1/17 of lineitem; q18's
          300-quantity order set keeps ~60 of 1.5M orders). Non-unique
          semi builds deliberately do NOT reduce — there the probe
          survives on key COVERAGE, not build cardinality, and under FK
          fanout even a heavily filtered build covers most probe keys
          (the q4 shape before its subplan build deduped: 63% of
          lineitem rows covered ~98% of orders; applying the raw
          cardinality ratio forged the bound low and burned overflow
          reruns). `_semi_key_domain` picks the denominator: the probe
          key's own table when the probe key is its declared PK (q18's
          o_orderkey → orders), else the build's base table (q9's
          l_partkey probe → part);
        * the measured-live memo (monotone max per compact-free key):
          an observed live count is never undercut again;
        * 25% headroom, floor 1024, quantized UP on the fine segment
          ladder (`progstore/buckets.bucket_segment`) so data growth
          recompiles at ≤1.25x-ratio rungs, not per row count;
        * STICKY per compact-free key: once a capacity is chosen, data
          growth that still fits inside it reuses the compiled program
          (the headroom absorbs within-bucket growth — the shape-bucket
          churn pin stays intact); the capacity re-derives only when
          the estimate outgrows it.

        Only capacities under cap0/2 are worth the reshape."""
        from ydb_tpu.ops.xla_exec import late_mat_enabled
        from ydb_tpu.query import latemat
        if not late_mat_enabled():
            return None, None
        live = float(sum(b.length for b in sources)) if sources else 0.0
        if pipe.scan.est_rows >= 0:
            live = min(live, float(pipe.scan.est_rows))
        est = live
        at = len(pipe.steps)
        bi = 0
        for i, (kind, step) in enumerate(pipe.steps):
            if kind != "join":
                continue
            bt = builds[bi]
            bi += 1
            if step.not_in:
                continue
            dom = 0
            if step.kind == "inner":
                dom = self._build_base_rows(step)
            elif step.kind == "left_semi":
                dom = self._semi_key_domain(step)
            if int(bt.n) < dom:
                est *= float(int(bt.n)) / dom
                at = i + 1
        if pipe.out_bound and not (
                pipe.partial is not None
                and any(isinstance(c, ir.GroupBy)
                        for c in pipe.partial.commands)):
            # a pipeline bound proven at plan time bounds the PRE-partial
            # rows only when no partial group-by sits between
            est = min(est, float(pipe.out_bound))
        est = max(est, float(self._compact_memo.get(base_key, 0)))
        cand = self._compact_caps.get(base_key)
        if cand is None or est > cand:
            cand = shape_buckets.bucket_segment(
                max(int(est * 1.25) + 1, 1024))
            if cand >= cap0 // 2:
                self._compact_caps.pop(base_key, None)
                return None, None
        why = latemat.tail_reads_in_place(pipe, join_metas, at)
        if why is not None:
            if declined is not None:
                declined["compact_skipped"] = why
            return None, None
        self._compact_caps[base_key] = cand
        return cand, at

    def _build_base_rows(self, step: JoinStep) -> int:
        """Unfiltered base-table row count of a join's build side (the
        FK-selectivity denominator); 0 = unknown (no reduction
        assumed). The planner stamps `est_rows` POST-predicate; the
        denominator needs the unfiltered table, so resolve through the
        catalog like the bounds lattice does."""
        build = step.build
        pipe = getattr(build, "pipeline", build)   # QueryPlan | Pipeline
        scan = getattr(pipe, "scan", None)
        if scan is None:
            return 0
        try:
            tbl = self.catalog.table(scan.table)
        except Exception:              # noqa: BLE001 — sizing, not law
            return 0
        return int(getattr(tbl, "num_rows", 0))

    def _semi_key_domain(self, step: JoinStep) -> int:
        """Key-domain denominator for a semi join's coverage estimate,
        or 0 when the build key isn't declared-unique (no reduction —
        see `_compact_sizing`). A probe key that is itself the declared
        single-column PK of its aliased table names the domain exactly
        (q18: o_orderkey → orders rows). A plain-pipeline build whose
        scan PK is the key uses its base table (q9: part filter — every
        base row is one distinct key). A SUBPLAN build probed by a
        non-PK key gets no domain: its scan table counts ROWS, not
        keys, and under FK fanout that denominator forges the estimate
        low (q21's correlated-exists orderkey set over lineitem —
        4 rows per key → a 4x understatement and an overflow rerun)."""
        from ydb_tpu.query import bounds
        from ydb_tpu.query.plan import QueryPlan
        if not bounds._build_key_unique_declared(step, self.catalog):
            return 0
        if "." in step.probe_key:
            alias, col = step.probe_key.split(".", 1)
            try:
                tbl = self.catalog.table(alias)
                if list(tbl.key_columns) == [col]:
                    return int(getattr(tbl, "num_rows", 0))
            except Exception:          # noqa: BLE001 — sizing, not law
                pass
        if not isinstance(step.build, QueryPlan):
            return self._build_base_rows(step)
        return 0

    # -- multi-query batched dispatch --------------------------------------

    def execute_fused_batched(self, plan: QueryPlan, members: list,
                              snapshot: Snapshot, info: dict = None):
        """ONE stacked fused execution for a batch of same-shape queries
        (the inference-serving lane, `query/batch_lane.py`): the shared
        scan superblock and join builds broadcast, each member's lifted
        literals stack along a leading batch axis, and a single vmapped
        executable (`ops/fused.build_fused_batched_fn`) serves the whole
        batch — one dispatch + one device→host readout instead of B.

        `plan`: the leader's plan with scan pruning STRIPPED (pruning is
        literal-dependent and cannot partition a shared execution; the
        lane already verified every member sees identical source sets).
        `members`: [(member_plan, member_params)] — same `lift_sig`,
        verified by the lane. `info`: the lane's note of the group:
        `reserved_bytes`, what its ONE admission reservation holds for
        this dispatch, is read (the span's `reserved_mb`); `bb`, the
        member slots the program ran, is written. Returns [HostBlock]
        projected per member, or None when this shape cannot batch
        (caller falls back to per-member execution)."""
        from ydb_tpu.ops import fused as F
        from ydb_tpu.utils import memledger
        from ydb_tpu.utils.metrics import GLOBAL

        info = {} if info is None else info
        bp = self._batched_prepare(plan, members, snapshot)
        if bp is None:
            return None
        # observability levers cannot stale a program: they choose how
        # the identical trace is dispatched/recorded, not what it computes
        # lint: allow-cache-key(progstats/memledger/critpath observe only)
        cached = self._fused_cache.get(bp.key)
        fresh_compile = cached is None
        if cached is not None:
            fn, layout_box, out_schema = cached
            progstats.record_hit(getattr(fn, "key_id", None))
        else:
            fn = layout_box = out_schema = None
        try:
            with self._span("device-dispatch-batched", k=bp.K, cap=bp.CAP,
                            b=bp.Bb, reserved_mb=info.get(
                                "reserved_bytes", 0) >> 20) as dsp:
                import time as _time
                t_disp = _time.perf_counter()
                if fn is None:
                    # fill for the stacked program too: store consult →
                    # AOT capture, single-flight deduped (compile inside
                    # the dispatch span; a trace error re-raises at the
                    # call below and the lane falls back per-member
                    # exactly as before). cache=False — the entry parks
                    # only after the first successful dispatch.
                    (fn, layout_box, out_schema), fresh_compile = \
                        self._fused_fill("batched", bp.key, bp.builder,
                                         bp.args, cache=False)
                mem = self._note_batched_bytes(bp, fn)
                if mem is not None:
                    dsp.attrs["temp_mb"] = mem["temp_bytes"] >> 20
                # no compact in the batched lane (aux is always empty
                # — `_fused_plan_setup` never hands it a compact_prog)
                data_stacks, valid_stack, length, _aux = fn(*bp.args)
                t_enqueued = _time.perf_counter()
                if fresh_compile:
                    dsp.attrs["compile_ms"] = round(
                        (_time.perf_counter() - t_disp) * 1000.0, 3)
        except Exception:                # noqa: BLE001 — lane, not law
            # a shape the vmapped trace can't batch (or a compile-side
            # failure): fall back to per-member execution rather than
            # failing B clients on an optimization
            GLOBAL.inc("batch/trace_errors")
            return None
        if cached is None:
            # cache only after the first successful dispatch, so a
            # trace-failing shape never parks a dead entry in the budget
            self._fused_cache[bp.key] = (fn, layout_box, out_schema)
        _count_latemat_reads(layout_box)
        B = len(members)
        info["bb"] = bp.Bb
        # the power-of-two axis bucket runs `Bb` member slots for `B`
        # live members; the `Bb - B` repeats of the last member are work
        # the device does for nobody (same-text dedup runs one slot)
        GLOBAL.inc("batch/member_slots", bp.Bb)
        GLOBAL.inc("batch/pad_slots", max(0, bp.Bb - B))
        memledger.record_padded_buffers(
            "batch_lane", "result_buffers", min(B, bp.Bb), bp.Bb,
            (data_stacks, valid_stack))

        out_dicts = {n2: d for n2, d in bp.dicts.items()
                     if out_schema.has(n2)}
        out_dicts.update({n2: d for n2, d in bp.plan.result_dicts.items()
                          if out_schema.has(n2)})
        self._await_device((data_stacks, valid_stack, length), t_enqueued,
                           getattr(fn, "key_id", None), fresh_compile)
        with self._span("readout-transfer", b=len(members)):
            blocks = F.fetch_fused_batch(data_stacks, valid_stack, length,
                                         layout_box, out_schema, out_dicts,
                                         bp.member_rows)
        out = []
        for (mp, _prms), blk in zip(members, blocks):
            blk = _apply_offset(blk, mp.offset or 0, mp.limit)
            out.append(self._project_output(blk, mp.output))
        return out

    def _batched_prepare(self, plan: QueryPlan, members: list,
                         snapshot: Snapshot, ahead_bb: int = 0):
        """A stacked execution up to its program: builds, plan walk,
        superblock, the members' params stacked, the cache key, the
        builder and the arguments. None where this shape cannot batch.

        `ahead_bb`: the build-ahead of one batch-size bucket
        (`warm_batched`): `members` holds ONE statement, whose scalar
        literals stand in for `ahead_bb` members'."""
        from types import SimpleNamespace

        from ydb_tpu.ops import fused as F
        from ydb_tpu.storage.device_cache import (
            enumerate_scan_sources, estimate_scan_bytes,
        )

        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)
        join_steps = [step for kind, step in pipe.steps if kind == "join"]
        if len(join_steps) > self.fuse_max_joins:
            return None
        params0 = dict(members[0][1])
        with self._span("join-builds", n=len(join_steps)) as sp:
            builds = self._prepare_builds(pipe, params0, snapshot)
            _note_probes(sp, builds)
        for step, bt in zip(join_steps, builds):
            if isinstance(bt, J.PartitionedBuild) or (
                    not bt.unique and step.kind in ("inner", "left",
                                                    "mark")):
                return None
        # `fused-prepare` and `param-upload`: the same phases as the
        # per-query path's (`_try_execute_fused`), the builds excepted
        with self._span("fused-prepare", compact=0) as prep:
            lift_sig = getattr(plan, "lift_sig", None)
            (plan, pipe, scan_cols, schema, partial_schema, dicts,
             join_metas, late_scan) = self._fused_plan_setup(plan, builds)

            storage_names = [s for (s, _i) in pipe.scan.columns]
            rename = {s: i for (s, i) in pipe.scan.columns}
            sources, src_ids = enumerate_scan_sources(table, snapshot, None)
            Kb = shape_buckets.bucket_sources(len(sources))
            if not sources or estimate_scan_bytes(sources, storage_names,
                                                  pad_to=Kb) \
                    > self.fused_scan_budget_bytes:
                return None                  # empty / tiled-class scan
            sb = self._superblock(table, storage_names, rename, snapshot, None,
                                  sources, src_ids, Kb)
            if sb is None:
                return None
            arrays, valids, lengths, K, CAP, sb_dicts = sb
            sb_valid_names = frozenset(valids.keys())
            dicts.update(sb_dicts)
            from ydb_tpu.utils import memledger
            memledger.record_padded_buffers(
                "superblock", "superblock",
                int(sum(b.length for b in sources)), K * CAP, arrays, valids)

            sort_params, sort_spec, rank_assigns = self._sort_setup_fused(
                plan, schema, dicts)

            # per-member param dicts (sort params are batch-invariant; a
            # LIMIT always lifts here — see _lift_limit_setup — so each
            # member clamps to ITS OWN limit+offset, not the leader's)
            lift_limit, lim_key = self._lift_limit_setup(plan, force=True)
            mem_params = []
            for (mp, prms) in members:
                p = {**prms, **sort_params}
                if lift_limit:
                    p[F.LIMIT_PARAM] = np.int32(mp.limit + (mp.offset or 0))
                mem_params.append(p)
            names = sorted(mem_params[0])
            for p in mem_params[1:]:
                if sorted(p) != names:
                    return None          # shape drift — lane sig was stale

            # WHICH params ride the batch axis is a property of the shape,
            # not of this batch's values: once any member differs, every
            # numeric SCALAR literal is stacked, whether or not its values
            # happen to agree here (a herd whose sixteen all ask `quantity <
            # 24` must not meet a program of its own, compiled inside the
            # window); arrays (rank LUTs, shared pool arrays, IN lists) stack
            # only where they differ — B device copies of a LUT are not free
            # — and broadcast via in_axes=None otherwise
            B = len(members)
            column = {n: [p[n] for p in mem_params] for n in names}
            differs = {n: not all(_param_values_equal(v[0], x) for x in v[1:])
                       for n, v in column.items()}
            stack_scalars = bool(ahead_bb) or any(differs.values())
            axes, stacked = {}, {}
            for n in names:
                vals = column[n]
                if ahead_bb:
                    vals = vals * ahead_bb
                v0 = np.asarray(vals[0])
                if differs[n] or (stack_scalars and v0.ndim == 0
                                  and v0.dtype.kind in "biuf"):
                    arrs = [np.asarray(v) for v in vals]
                    if any(a.shape != arrs[0].shape or a.dtype != arrs[0].dtype
                           for a in arrs[1:]):
                        # array params whose SHAPES vary with the literal
                        # (integer IN lists) — Param fingerprints carry no
                        # shape, so the sig can't split these; decline
                        return None
                    axes[n] = 0
                    stacked[n] = np.stack(arrs)
                else:
                    axes[n] = None
                    stacked[n] = vals[0]
            mapped = tuple(n for n in names if axes[n] == 0)
            if ahead_bb:
                Bb, member_rows = ahead_bb, []
            elif mapped:
                Bb = batch_bucket(B)
                if Bb > B:
                    pad = Bb - B             # pad by repeating the last member
                    for n in mapped:
                        stacked[n] = np.concatenate(
                            [stacked[n]] + [stacked[n][-1:]] * pad)
                member_rows = list(range(B))
            else:
                # every member identical (a same-text storm): one execution,
                # every member unpacks row 0
                Bb = 1
                member_rows = [0] * B

            builds_sig = tuple(F.build_inputs_sig(bt) for bt in builds)
            base_key = F.fused_cache_key(plan, scan_cols, K, CAP,
                                         sb_valid_names, builds_sig, sort_spec,
                                         rank_assigns, tuple(names),
                                         lim_key=lim_key)
            keep = tuple(dict.fromkeys(n for (n, _lbl) in plan.output))

            def _builder():
                bfn, box = F.build_fused_batched_fn(
                    pipe, plan.final_program, scan_cols, K, CAP,
                    sb_valid_names, join_metas, rank_assigns, sort_spec,
                    plan.limit, plan.offset, keep, dict(axes), Bb,
                    lift_limit=lift_limit, late_scan=late_scan)
                out_cols = [c for c in schema.columns if c.name in keep] \
                    or list(schema.columns)
                return bfn, box, Schema(out_cols)

            key = ("batched", base_key, Bb, mapped)
            prep.attrs["hit"] = key in self._fused_cache
            dev_params, build_inputs = self._upload_params(stacked, builds)
            return SimpleNamespace(
                plan=plan, dicts=dicts, K=K, CAP=CAP, Bb=Bb,
                member_rows=member_rows, builder=_builder, key=key,
                args=(arrays, valids, lengths, build_inputs, dev_params),
                bytes_key=(lift_sig, table.uid, table.data_version, Bb))

    # -- what a stacked program holds ---------------------------------------

    def _note_batched_bytes(self, bp, handle):
        """Remember the compiler's `memory_analysis()` of a stacked
        program (`utils/progstats` records it at the AOT seam) under the
        lane's terms — shape, table version, batch-size bucket — so that
        `batched_working_set` answers from it. Returns the figures, or
        None where the compiler gave none (progstats off)."""
        ent = progstats.inventory_entry(getattr(handle, "key_id", None) or "")
        mem = (ent or {}).get("memory")
        if mem and bp.bytes_key[0] is not None:
            if len(self._batched_bytes) > 256:
                self._batched_bytes.clear()
            self._batched_bytes[bp.bytes_key] = (
                mem["arg_bytes"] + mem["temp_bytes"] + mem["out_bytes"])
        return mem

    def batched_working_set(self, plan: QueryPlan, snapshot: Snapshot,
                            n_members: int, est: int):
        """Device bytes ONE stacked dispatch of `n_members` statements of
        this shape holds: `(bytes, "compiled" | "plan" | "members")`. The
        lane's gate and its reservation both ask here.

        The shared inputs enter a vmapped program ONCE (`in_axes=None`:
        superblock columns, build tables), so the working set is not
        `n_members` scans. For a program that has been compiled it is the
        compiler's own figure: arguments + temporaries + outputs. Before
        that, a bound from the plan: the shared inputs (`est`, or the
        padded superblock where that is more) plus, per member slot and
        per scan slot, the widths of what the body computes
        (`admission.stacked_body_width`): far above what the compiler
        fuses away, so a large shape may be declined until its program
        exists — which `warm_batched` sees to before the first group. A
        body that sorts at scan capacity has no such bound: it is charged
        `n_members` x `est` (`batch_reservation_bytes`), as every shape
        was before, until its program exists."""
        from ydb_tpu.query.admission import (
            batch_reservation_bytes, stacked_body_width,
        )
        from ydb_tpu.storage.device_cache import (
            enumerate_scan_sources, estimate_scan_bytes, scan_capacity,
        )
        Bb = batch_bucket(n_members)
        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)
        fig = self._batched_bytes.get(
            (getattr(plan, "lift_sig", None), table.uid, table.data_version,
             Bb))
        if fig is not None:
            return fig, "compiled"
        sources, _ids = enumerate_scan_sources(table, snapshot, None)
        Kb = shape_buckets.bucket_sources(len(sources))
        scan = estimate_scan_bytes(
            sources, [s for (s, _i) in pipe.scan.columns], pad_to=Kb)
        width = stacked_body_width(self.catalog, plan)
        if width is None:                # the body sorts at scan capacity
            return batch_reservation_bytes(est, n_members), "members"
        return max(int(est), scan) \
            + Bb * scan_capacity(sources, Kb) * width, "plan"

    def warm_batched(self, plan: QueryPlan, snapshot: Snapshot,
                     max_batch: int, timeout_s: float) -> None:
        """The stacked programs exist before the herd arrives: the first
        statement of a shape (per table version) that reaches the lane
        waits for the shape's compile-ahead (`compile_ahead`: superblock
        and single-statement program, so nothing is uploaded twice), then
        builds the `Bb` = 2, 4, .. `max_batch` executables through
        `_fused_fill`'s single flight; statements of the shape that
        arrive meanwhile wait on that flight, later ones pass. A
        deployment's set-up that sends each shape once therefore ends
        with every program built. Off with the compile-ahead lane or
        progstats off (the programs then compile at first use, inside the
        dispatch)."""
        from ydb_tpu.utils.metrics import GLOBAL
        sig = getattr(plan, "lift_sig", None)
        if sig is None or max_batch < 2 or not (
                plan.lift_names or plan.limit is not None) or not (
                ca_lane.enabled() and progstats.enabled()):
            return
        table = self.catalog.table(plan.pipeline.scan.table)
        warm_key = (plan.pipeline.scan.table, table.data_version, sig)
        with self._warm_mu:
            pending = self._warm_pending.get(warm_key)
            done = warm_key in self._batched_warm_seen
        if pending is not None:
            pending.wait(timeout_s)
        if done:
            return

        def _build():
            lane_plan, Bb = unpruned(plan), 2
            while Bb <= batch_bucket(max_batch):
                bp = self._batched_prepare(
                    lane_plan, [(lane_plan, dict(plan.params))], snapshot,
                    ahead_bb=Bb)
                Bb *= 2
                if bp is None or not bp.key[-1]:
                    # the shape cannot batch at all, or carries no
                    # literal members could differ by: such a herd is one
                    # statement run once (the same-text dedup)
                    break
                ent = self._fused_cache.get(bp.key)
                if ent is None:
                    ent, compiled = self._fused_fill(
                        "batched", bp.key, bp.builder, bp.args,
                        source="compile_ahead", cache=False)
                    if not isinstance(ent[0], progstats.ProgramHandle):
                        break            # the trace or the compiler refused
                    self._fused_cache[bp.key] = ent
                    if compiled:
                        GLOBAL.inc("batch/ahead_compiles")
                self._note_batched_bytes(bp, ent[0])
            with self._warm_mu:
                self._batched_warm_seen.add(warm_key)

        try:
            with self._span("batch-build-ahead", max_batch=max_batch):
                self._sflight.run(("warm-batched",) + warm_key, _build)
        except Exception:                # noqa: BLE001 — lane, not law
            # the dispatch path meets the real error with full context
            GLOBAL.inc("prog/compile_ahead_errors")

    def _bounded_groupby_rewrite(self, plan: QueryPlan, builds: list,
                                 join_metas: list):
        """The executor half of the bounds lattice — two rewrites of the
        partial (and matching merge) GroupBy, both from RUNTIME-VERIFIED
        join structure (a false bound drops groups, a false dependency
        merges them — only guaranteed sources qualify):

        * PROVEN `out_bound`: after an INNER probe against a unique-keyed
          build, surviving probe keys are a subset of the build's keys,
          so a group-by whose keys are all drawn from {probe key} ∪ build
          payload has ngroups ≤ build rows (semi joins bound the probe
          key the same way without payloads). Bucket-quantized so data
          growth recompiles at capacity-bucket granularity.

        * CARRY keys: grouping columns functionally determined by a
          smaller determinant stop participating in the group-by sort
          identity — q10's 7-key (16-sort-operand) group-by collapses
          to its 1-key determinant, the keys materializing from
          group leaders like everything else late-materialized. The
          dependency is verified, never assumed: the determinant is the
          join's own key (unique ⇒ determines every payload column), or
          a payload column whose distinct count MEASURED on the
          materialized build equals the full key tuple's (`fd_block`
          retained by `ops/join.build` for exactly this check).

        Names reassigned AFTER the bounding join (later program Assigns,
        later join payloads/mark columns, partial-program Assigns) void
        the guarantee for that join and are excluded. Returns the
        (possibly rewritten) plan and its pipeline; the rewrite copies —
        cached plans are never mutated."""
        import dataclasses as _dc

        from ydb_tpu.utils.metrics import GLOBAL
        pipe = plan.pipeline
        if pipe.partial is None or not pipe.partial.commands:
            return plan, pipe
        gb = pipe.partial.commands[-1]
        if not isinstance(gb, ir.GroupBy) or not gb.keys:
            return plan, pipe
        keys = set(gb.keys)
        partial_assigned = {c.name for c in pipe.partial.commands[:-1]
                            if isinstance(c, ir.Assign)}
        best = None
        cands = []     # (step, bt, allowed, has_payload)
        bi = 0
        for si, (kind, step) in enumerate(pipe.steps):
            if kind != "join":
                continue
            bt = builds[bi]
            meta = join_metas[bi]
            bi += 1
            if step.not_in:
                continue
            if step.kind == "inner" and getattr(bt, "unique", False):
                allowed = {step.probe_key} | set(meta["payload_names"])
                has_payload = True
            elif step.kind == "left_semi":
                allowed = {step.probe_key}
                has_payload = False
            else:
                continue
            # names invalidated downstream of THIS join
            later = set(partial_assigned)
            bj = bi
            for sj in range(si + 1, len(pipe.steps)):
                k2, s2 = pipe.steps[sj]
                if k2 == "join":
                    later |= set(join_metas[bj]["payload_names"])
                    if s2.kind == "mark":
                        later.add(s2.mark_col or "__mark")
                    bj += 1
                else:
                    later |= {c.name for c in s2.commands
                              if isinstance(c, ir.Assign)}
            allowed -= later
            cands.append((step, bt, allowed, has_payload))
            if keys <= allowed:
                n = max(int(bt.n), 1)
                best = n if best is None else min(best, n)

        # -- carry reduction: per bounding join, find one determinant for
        # the keys it contributes and demote the rest to carried keys
        carry: list = []
        claimed: set = set()
        for (step, bt, allowed, has_payload) in cands:
            if not has_payload:
                continue
            gj = [k for k in gb.keys
                  if k in allowed and k not in claimed
                  and k not in carry]
            if len(gj) < 2:
                continue
            det, measured = self._fd_determinant(step, bt, gj)
            if det is None:
                continue
            claimed.add(det)
            for k in gj:
                if k != det:
                    carry.append(k)
            if measured is not None and keys <= allowed:
                # the measured distinct count of the FULL key tuple
                # is an exact ngroups bound for this execution —
                # tighter than build rows
                best = measured if best is None \
                    else min(best, measured)

        bound = gb.out_bound
        if best is not None:
            cand = bucket_capacity(max(best, 1), minimum=128)
            rows = max(int(getattr(self.catalog.table(pipe.scan.table),
                                   "num_rows", 0)), 1)
            if cand < bucket_capacity(rows) \
                    and (not bound or int(bound) > cand):
                # a planner domain-product bound may be far looser than
                # the join bound (10^9-key-product vs an 8k-row build) —
                # keep the tighter of the two
                bound = cand
        if bound == gb.out_bound and not carry:
            return plan, pipe

        kept = tuple(k for k in gb.keys if k not in carry)
        domains, nullable = gb.key_domains, gb.key_nullable
        if carry and domains and len(domains) == len(gb.keys):
            domains = tuple(d for k, d in zip(gb.keys, domains)
                            if k not in carry)
        elif carry:
            domains = ()
        if carry and nullable:
            nullable = tuple(n for k, n in zip(gb.keys, nullable)
                             if k not in carry)
        new_carry = tuple(gb.carry_keys) + tuple(carry)
        gb2 = _dc.replace(gb, keys=kept, key_domains=domains,
                          key_nullable=nullable, out_bound=bound,
                          carry_keys=new_carry)
        pipe = _dc.replace(pipe, partial=ir.Program(
            list(pipe.partial.commands[:-1]) + [gb2]))
        fp = plan.final_program
        if fp is not None and fp.commands \
                and isinstance(fp.commands[0], ir.GroupBy) \
                and fp.commands[0].keys == gb.keys:
            # the merge GroupBy sees the union of partials over the SAME
            # keys — the bound and the carry set transfer verbatim
            fgb0 = fp.commands[0]
            fgb = _dc.replace(
                fgb0, keys=kept, key_domains=domains, key_nullable=nullable,
                carry_keys=tuple(fgb0.carry_keys) + tuple(carry),
                out_bound=bound if (not fgb0.out_bound
                                    or (bound and int(fgb0.out_bound)
                                        > int(bound)))
                else fgb0.out_bound)
            fp = ir.Program([fgb] + list(fp.commands[1:]))
        plan = _dc.replace(plan, pipeline=pipe, final_program=fp)
        GLOBAL.inc("groupby/join_bounded_plans")
        if carry:
            GLOBAL.inc("bounds/carry_rewrites")
        return plan, pipe

    def _fd_determinant(self, step: JoinStep, bt, gj: list):
        """One grouping column that provably determines all of `gj`
        (keys drawn from this unique-keyed build's probe/payload).
        Returns (determinant | None, measured distinct count | None).

        Trivial case: the join key itself is among the keys — a unique
        build key determines every payload column by construction
        (probe == build key on surviving inner rows). Otherwise each
        candidate is VERIFIED on the materialized build block: det → gj
        holds on this dataset iff distinct(det) == distinct(gj-tuple)
        (det ⊆ gj, so equality forces a bijection)."""
        from ydb_tpu.query.bounds import dataset_distinct
        from ydb_tpu.utils.metrics import GLOBAL
        if step.probe_key in gj:
            return step.probe_key, None
        if step.build_key in gj:
            return step.build_key, None
        fdb = getattr(bt, "fd_block", None)
        if fdb is None:
            return None, None
        # map probe-side key names onto build-block columns (the probe
        # key reads the build key's values on surviving inner rows)
        mcols = [step.build_key if k == step.probe_key else k for k in gj]
        if any(c not in fdb.columns for c in mcols):
            return None, None
        memo = getattr(bt, "fd_memo", None)
        if memo is None:
            memo = bt.fd_memo = {}

        def distinct(cols: tuple) -> int:
            got = memo.get(cols)
            if got is None:
                got = memo[cols] = dataset_distinct(fdb, list(cols))
            return got

        GLOBAL.inc("bounds/fd_checks")
        total = distinct(tuple(sorted(mcols)))
        # candidates ordered smallest-encoding-first: a narrow int key
        # beats a wide string code as the surviving sort operand
        order = sorted(zip(gj, mcols),
                       key=lambda km: (fdb.columns[km[1]].data.itemsize,
                                       km[0]))
        for (k, m) in order:
            if distinct((m,)) == total:
                GLOBAL.inc("bounds/fd_verified")
                return k, total
        return None, None

    # -- tiled fused path (scan > HBM budget) ------------------------------

    def _execute_fused_tiled(self, plan: QueryPlan, params: dict, pipe,
                             sources: list, scan_cols: list, builds: list,
                             join_metas: list, build_dicts: dict,
                             partial_schema: Schema):
        """Stream a scan too large for HBM through fixed-size tiles: each
        tile is K_tile stacked sources run through ONE fused
        scan→filter→join→partial dispatch (`ops/fused.build_tile_fn`),
        with two tiles in flight (upload overlaps compute). Partials
        either stay device-resident for the normal finalize, spill to
        host-DRAM key-hash partitions for a per-partition merge
        (`ops/spill.py` — the WideCombiner InMemory→Spilling→
        ProcessSpilled analog, `mkql_wide_combine.cpp:338-600`), or, for
        non-aggregating plans, union host-side with per-tile top-k
        pre-cuts (DqCnMerge-style).

        Returns ("fused-tiled[...]" , HostBlock)."""
        import dataclasses

        from ydb_tpu.ops import fused as F
        from ydb_tpu.ops import spill as SP
        from ydb_tpu.ops.xla_exec import groupby_route
        from ydb_tpu.utils.metrics import GLOBAL

        CAP = max(bucket_capacity(max(b.length, 1)) for b in sources)
        row_bytes = 0
        sb_valid_names = set()
        tile_dicts = dict(build_dicts)
        for (s, internal) in pipe.scan.columns:
            cd0 = sources[0].columns[s]
            row_bytes += cd0.data.itemsize
            if any(b.columns[s].valid is not None for b in sources):
                sb_valid_names.add(internal)
                row_bytes += 1
            if cd0.dictionary is not None:
                tile_dicts[internal] = cd0.dictionary
        K_tile = max(1, int(self.tile_budget_bytes // (CAP * row_bytes)))
        K_tile = min(K_tile, len(sources))
        n_tiles = (len(sources) + K_tile - 1) // K_tile
        tile_cap = K_tile * CAP
        sb_valid_names = frozenset(sb_valid_names)

        # static tile-output capacity: bounded-domain partial group-bys
        # compact to their bucket count; everything else stays tile-sized
        tile_out_cap = tile_cap
        last = pipe.partial.commands[-1] \
            if pipe.partial is not None and pipe.partial.commands else None
        if isinstance(last, ir.GroupBy):
            route, nb = groupby_route(last)
            if route == "keyless":
                tile_out_cap = 1
            elif route != "sorted":
                tile_out_cap = bucket_capacity(nb, minimum=128)
            if last.out_bound:
                # proven ngroups bound (join-derived or an out-of-scatter-
                # range domain product): the sorted lowering emits its
                # per-group outputs at this bucket, so the partials really
                # are this small — don't let a tile-cap-sized estimate
                # trigger a needless host-DRAM spill
                tile_out_cap = min(
                    tile_out_cap,
                    bucket_capacity(max(int(last.out_bound), 1),
                                    minimum=128))
        prow = sum(np.dtype(c.dtype.np).itemsize + 1
                   for c in partial_schema.columns)
        est_partial = n_tiles * min(tile_out_cap, tile_cap) * prow

        fp = plan.final_program
        merge_gb = fp.commands[0] if fp is not None and fp.commands \
            and isinstance(fp.commands[0], ir.GroupBy) else None
        spill = (merge_gb is not None and merge_gb.keys
                 and est_partial > self.merge_budget_bytes)
        union = merge_gb is None and est_partial > self.merge_budget_bytes

        builds_sig = tuple(F.build_inputs_sig(bt) for bt in builds)
        key = F.tile_cache_key(pipe, scan_cols, K_tile, CAP, sb_valid_names,
                               builds_sig, tuple(sorted(params)))
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = F.build_tile_fn(pipe, scan_cols, K_tile, CAP,
                                 sb_valid_names, join_metas)
            self._fused_cache[key] = fn
        build_inputs = [F.build_traced_inputs(bt) for bt in builds]
        dev_params = {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
                      for k, v in params.items()}
        out_dicts = {n: d for n, d in tile_dicts.items()
                     if partial_schema.has(n)}

        GLOBAL.inc("executor/tiled_queries")
        # the tile stacks + resident partials live OUTSIDE the cache's
        # accounting: make room so warm cache + streaming don't OOM HBM
        self.device_cache.reserve(2 * self.tile_budget_bytes
                                  + self.merge_budget_bytes)
        store = None
        if spill:
            P = 1
            while est_partial / P > self.merge_budget_bytes and P < 256:
                P *= 2
            store = SP.PartitionStore(partial_schema, list(merge_gb.keys),
                                      P, out_dicts)

        # union mode: per-tile finalize plans (top-k pre-cut when the
        # query sort-limits, plain program application otherwise)
        lim = None if plan.limit is None else plan.limit + (plan.offset or 0)
        topk = bool(plan.sort) and lim is not None and lim <= (1 << 17)
        out_names = {n for (n, _lbl) in plan.output}
        extra = [(sk.name, sk.name) for sk in plan.sort
                 if sk.name not in out_names]
        if union:
            if topk:
                plan_tile = dataclasses.replace(
                    plan, offset=None, limit=lim, output=plan.output + extra)
            else:
                plan_tile = dataclasses.replace(
                    plan, sort=[], limit=None, offset=None,
                    output=plan.output + extra)

        partials, unions = [], []
        prev = None
        with self._span("tiled-scan", tiles=n_tiles, k_tile=K_tile,
                        spill=bool(spill), union=bool(union)):
            for t in range(n_tiles):
                tile_sources = sources[t * K_tile:(t + 1) * K_tile]
                sb, sbv, lengths = self._stack_tile(
                    tile_sources, pipe.scan.columns, K_tile, CAP,
                    sb_valid_names)
                out_d, out_v, length = fn(sb, sbv, lengths, build_inputs,
                                          dev_params)
                out_d = {n: out_d[n] for n in partial_schema.names}
                out_v = {n: v for n, v in out_v.items()
                         if partial_schema.has(n)}
                cap_t = (next(iter(out_d.values())).shape[0]
                         if out_d else tile_cap)
                dblock = DeviceBlock(partial_schema, out_d, out_v, length,
                                     cap_t, out_dicts)
                if spill:
                    store.feed(dblock)       # syncs → natural backpressure
                elif union:
                    unions.append(self._finalize(plan_tile, [dblock],
                                                 params))
                else:
                    partials.append(dblock)
                    if prev is not None:
                        jax.block_until_ready(prev)
                    prev = out_d

        if spill:
            GLOBAL.inc("executor/spilled_rows", store.spilled_rows)
            GLOBAL.inc("executor/spilled_bytes", store.spilled_bytes)
            return ("fused-tiled-spill",
                    self._merge_spilled(plan, store, params))
        if union:
            u = HostBlock.concat(unions) if len(unions) > 1 else unions[0]
            if topk:
                plan_merge = dataclasses.replace(
                    plan, final_program=None, output=plan.output + extra)
                block = self._finalize(plan_merge, [to_device(u)], params)
            else:
                block = SP.host_sort_limit(
                    u, plan.sort, plan.limit, plan.offset,
                    {**out_dicts, **plan.result_dicts})
            return ("fused-tiled-union", block)
        return ("fused-tiled", self._finalize(plan, partials, params))

    def _stack_tile(self, tile_sources: list, scan_columns: list,
                    K_tile: int, CAP: int, sb_valid_names: frozenset):
        """Host-stack one tile of sources into (K_tile, CAP) arrays and
        upload (async H2D). Short tiles pad with zero-length sources so
        every tile shares one compiled program."""
        lengths = np.zeros(K_tile, np.int32)
        for k, b in enumerate(tile_sources):
            lengths[k] = b.length
        arrays, valids = {}, {}
        for (s, internal) in scan_columns:
            dtype = tile_sources[0].columns[s].data.dtype
            stack = np.zeros((K_tile, CAP), dtype=dtype)
            vstack = np.zeros((K_tile, CAP), np.bool_) \
                if internal in sb_valid_names else None
            for k, b in enumerate(tile_sources):
                cd = b.columns[s]
                stack[k, :b.length] = cd.data
                if vstack is not None:
                    vstack[k, :b.length] = (cd.valid if cd.valid is not None
                                            else True)
            arrays[internal] = jnp.asarray(stack)
            if vstack is not None:
                valids[internal] = jnp.asarray(vstack)
        return arrays, valids, jnp.asarray(lengths)

    def _merge_spilled(self, plan: QueryPlan, store, params: dict):
        """ProcessSpilled: per key-hash partition, concat the spilled
        pieces, run the merge group-by + rest of the final program on
        device, then combine partitions host-side (disjoint key sets) and
        apply ORDER BY / LIMIT on the host."""
        import dataclasses

        from ydb_tpu.ops import spill as SP

        out_names = {n for (n, _lbl) in plan.output}
        extra = [(sk.name, sk.name) for sk in plan.sort
                 if sk.name not in out_names]
        plan_p = dataclasses.replace(plan, sort=[], limit=None, offset=None,
                                     output=plan.output + extra)
        outs = []
        with self._span("spill-merge", parts=store.nparts):
            for p in range(store.nparts):
                hb = store.partition(p)
                if hb.length == 0 and outs:
                    continue
                outs.append(self._finalize(plan_p, [to_device(hb)], params))
        union = HostBlock.concat(outs) if len(outs) > 1 else outs[0]
        return SP.host_sort_limit(
            union, plan.sort, plan.limit, plan.offset,
            {**store.dictionaries, **plan.result_dicts})

    # -- distributed (mesh) path -------------------------------------------

    def _can_distribute(self, plan: QueryPlan) -> bool:
        """Distributable = two-phase aggregation shape: the pipeline ends in
        a partial GroupBy and the final program starts with the merge
        GroupBy (hash-shuffle boundary sits between the two)."""
        pipe = plan.pipeline
        if pipe.partial is None or not pipe.partial.commands:
            return False
        if not isinstance(pipe.partial.commands[-1], ir.GroupBy):
            return False
        fp = plan.final_program
        return (fp is not None and fp.commands
                and isinstance(fp.commands[0], ir.GroupBy))

    def _can_distribute_map(self, plan: QueryPlan,
                            snapshot: Snapshot) -> bool:
        """Map-style distribution (the DqCnMap/UnionAll connection): the
        pipeline has no aggregation boundary — scan/filter/join work
        spreads across devices and per-device results union for the final
        stage. Needs >1 scan source to be worth a fan-out."""
        pipe = plan.pipeline
        if pipe.partial is not None and any(
                isinstance(c, ir.GroupBy) for c in pipe.partial.commands):
            return False
        if plan.final_program is not None and any(
                isinstance(c, ir.GroupBy)
                for c in plan.final_program.commands):
            return False
        return self._scan_source_count(plan, snapshot) > 1

    def _scan_source_count(self, plan: QueryPlan, snapshot: Snapshot) -> int:
        pipe = plan.pipeline
        table = self.catalog.table(pipe.scan.table)
        return sum(len(p) + len(e)
                   for (p, e) in (s.scan_sources(snapshot,
                                                 pipe.scan.prune or None)
                                  for s in table.shards))

    def _execute_distributed_map(self, plan: QueryPlan, params: dict,
                                 snapshot: Snapshot) -> HostBlock:
        """Per-device pipelines (scan → filter → joins), results unioned
        host-side, final stage (exprs/sort/limit) single-device — the
        UnionAll-connection analog for non-aggregating queries.

        Guarded by `_can_distribute_map` (>1 scan source), so at least two
        per-device results always arrive."""
        import time as _time
        nsrc = self._scan_source_count(plan, snapshot)
        # no point replicating builds onto devices that get no blocks
        devs = list(self.mesh.devices.flat)[:max(2, min(
            self.mesh.devices.size, nsrc))]
        with self._span("mesh-stage", ndev=len(devs)):
            builds = self._prepare_builds(plan.pipeline, params, snapshot)
            builds_by_dev = [[J.place(b, d) for b in builds] for d in devs]
            # dispatch every device's pipeline first; transfers afterwards
            # — to_host blocks, and fetching inside the loop would
            # serialize the fan-out this path exists for
            t_enq = _time.perf_counter()
            pending = [self._run_block(plan.pipeline, dblock,
                                       builds_by_dev[di], params)
                       for di, dblock in self._scan_device_blocks(
                           plan.pipeline, snapshot, devices=devs)]
            self._await_stage(pending, t_enq)
        with self._span("mesh-merge"):
            return self._union_distributed_map(plan, pending, params)

    def _union_distributed_map(self, plan: QueryPlan, pending: list,
                               params: dict) -> HostBlock:
        """Tail of the map lane: the per-device results unioned on the
        host, the final stage on one device."""
        lim = None if plan.limit is None else plan.limit + (plan.offset or 0)
        if plan.sort and lim is not None and lim <= (1 << 17):
            # sort-limit queries: per-device partial top-k BEFORE the
            # union, so only ≤lim rows per device cross the link — the
            # DqCnMerge (sorted-merge connection) analog. The offset
            # applies only at the merge (each device must keep its full
            # top-(limit+offset) prefix).
            import dataclasses
            # sort keys must survive the per-device projection or the
            # merge pass cannot re-sort (ORDER BY a column/expr outside
            # the SELECT list); execute()'s final _project_output trims
            # the extras
            out_names = {n for (n, _lbl) in plan.output}
            extra = [(sk.name, sk.name) for sk in plan.sort
                     if sk.name not in out_names]
            plan_local = dataclasses.replace(
                plan, offset=None, limit=lim, output=plan.output + extra)
            outs = [self._finalize(plan_local, [d], params)
                    for d in pending]
            union = HostBlock.concat(outs) if len(outs) > 1 else outs[0]
            plan_merge = dataclasses.replace(
                plan, final_program=None, output=plan.output + extra)
            return self._finalize_read(plan_merge, union, params)
        outs = [to_host(d) for d in pending]
        union = HostBlock.concat(outs) if len(outs) > 1 else outs[0]
        return self._finalize_read(plan, union, params)

    def _execute_distributed(self, plan: QueryPlan, params: dict,
                             snapshot: Snapshot,
                             prebuilt: Optional[dict] = None) -> HostBlock:
        """Scan partitions round-robin across mesh devices, run the full
        per-block pipeline (pushdown → joins → partial agg) on each
        device, hash-shuffle the partials over the mesh, merge, then run
        the remaining final program + sort/limit single-device (post-agg
        tails are small)."""
        per_dev = self._run_mesh_stage(plan.pipeline, params, snapshot,
                                       prebuilt=prebuilt)
        # merge GroupBy runs twice (pre-shuffle local combine + post-shuffle
        # final merge) — merge aggregation is associative, so this is the
        # BlockCombineHashed → BlockMergeFinalizeHashed split
        return self._merge_distributed_partials(plan, per_dev, params)

    def _run_mesh_stage(self, pipe: Pipeline, params: dict,
                        snapshot: Snapshot, until: Optional[int] = None,
                        prebuilt: Optional[dict] = None) -> list:
        """The `mesh-stage` span: broadcast the builds of the joins among
        steps[:until] (all, if None) to every mesh device, run each scan
        block through those steps on the device that holds it (and, with
        no `until`, the partial aggregation), and wait for the devices.
        Returns the output DeviceBlocks per device, at least one each."""
        import time as _time
        devs = list(self.mesh.devices.flat)
        with self._span("mesh-stage", ndev=len(devs)):
            builds = self._prepare_builds(pipe, params, snapshot,
                                          until=until, prebuilt=prebuilt)
            builds_by_dev = [[J.place(b, d) for b in builds] for d in devs]
            per_dev = [[] for _ in devs]
            t_enq = _time.perf_counter()
            for di, dblock in self._scan_device_blocks(pipe, snapshot,
                                                       devices=devs):
                per_dev[di].extend(self._run_block_multi(
                    pipe, dblock, builds_by_dev[di], params, until=until))
            for di, dev in enumerate(devs):
                if not per_dev[di]:
                    empty = to_device(self._empty_scan_block(pipe),
                                      device=dev)
                    per_dev[di].extend(self._run_block_multi(
                        pipe, empty, builds_by_dev[di], params,
                        until=until))
            self._await_stage([b for blks in per_dev for b in blks], t_enq)
        return per_dev

    # -- distributed shuffle join ------------------------------------------

    def _try_execute_shuffle_join(self, plan: QueryPlan, params: dict,
                                  snapshot: Snapshot,
                                  prebuilt: Optional[dict] = None):
        """Shuffle join over the mesh (`dq_opt_join.cpp` ShuffleJoin): the
        LAST join's build side hash-partitions across devices — no device
        holds the full build — and probe rows route to their key's owner
        via one ICI all_to_all (`parallel/shuffle_join.py`). Triggers when
        the build's stats estimate exceeds the broadcast budget; declines
        (→ broadcast path) for shapes the exchange doesn't cover yet:
        float/string keys, composite hash keys, NOT IN, duplicate-key
        inner/left builds, joins followed by further joins."""
        from ydb_tpu.core.dtypes import DType, Kind as _K

        pipe = plan.pipeline
        join_pos = [i for i, (k, _s) in enumerate(pipe.steps)
                    if k == "join"]
        if not join_pos:
            return None
        j = join_pos[-1]
        step = pipe.steps[j][1]
        if step.kind not in ("inner", "left", "left_semi", "left_anti",
                             "mark"):
            return None
        if step.not_in:
            # NOT IN null semantics stay on the broadcast path
            return None

        # cheap stats gate: the build's driving-scan footprint
        bp = getattr(step.build, "pipeline", step.build)
        if not hasattr(bp, "scan"):
            return None
        from ydb_tpu.query.admission import estimate_plan_bytes
        bplan = step.build if isinstance(step.build, QueryPlan) else None
        est = estimate_plan_bytes(
            self.catalog,
            bplan if bplan is not None else QueryPlan(pipeline=step.build),
            snapshot)
        if est <= self.dist_broadcast_budget_bytes:
            return None

        from ydb_tpu.parallel import shuffle_join as SJ
        from ydb_tpu.utils.metrics import GLOBAL
        devs = list(self.mesh.devices.flat)
        ndev = len(devs)
        with self._span("shuffle-join", ndev=ndev) as lane:
            with self._span("mesh-build"):
                parts = self._partition_shuffle_build(
                    pipe, j, step, params, snapshot, prebuilt, ndev)
            if parts is None:
                lane.attrs["declined"] = True
                return None
            barrays, pschema, bdicts, bcap, build_rows = parts
            lane.attrs["build_rows"] = build_rows
            # stage A: pipeline prefix per device (earlier joins broadcast)
            per_dev = self._run_mesh_stage(pipe, params, snapshot, until=j)

            with self._span("mesh-exchange"):
                in_schema = per_dev[0][0].schema
                payload_cols = []
                for name in pschema.names:
                    payload_cols.append(
                        Column(name, pschema.dtype(name).with_nullable(True)))
                if step.kind == "mark":
                    payload_cols.append(Column(step.mark_col or "__mark",
                                               DType(_K.BOOL, False)))
                rest = [s for (k, s) in pipe.steps[j + 1:]]
                # cheap to make (it compiles on its first run): an equal
                # one made before holds the compiled programs.
                # groupby_tuning in the key: the ShuffleJoin traces `rest`
                # and `pipe.partial` (GroupBy lowerings read the tile-rows
                # knob at trace time) — a knob flip must build a fresh
                # join, not reuse a program tiled under old settings
                sj = SJ.ShuffleJoin(self.mesh, in_schema, step.probe_key,
                                    step.kind, payload_cols,
                                    step.mark_col or "__mark", step.not_in,
                                    rest, pipe.partial,
                                    table=pipe.scan.table)
                key = (sj.identity(), groupby_tuning())
                cached = self._shuffle_joins.get(key)
                if cached is None:
                    self._shuffle_joins[key] = sj
                else:
                    sj = cached
                dicts = {}
                for blks in per_dev:
                    for b in blks:
                        dicts.update(b.dictionaries)
                dicts.update(bdicts)
                post_blocks = sj.run(
                    per_dev, barrays, bcap, params, dicts,
                    await_device=self._await_device,
                    min_segment_rows=self.mesh_min_segment_rows)

            GLOBAL.inc("executor/shuffle_joins")
            return self._merge_distributed_partials(
                plan, [[b] for b in post_blocks], params)

    def _partition_shuffle_build(self, pipe: Pipeline, j: int, step,
                                 params: dict, snapshot: Snapshot,
                                 prebuilt: Optional[dict], ndev: int):
        """The `mesh-build` step of the shuffle join: materialize the
        build side of step `j` on the HOST, check its key's shape, and
        hash-partition it `ndev` ways (`shuffle_join.partition_build`).
        Returns (stacked arrays, payload schema, dictionaries, partition
        capacity, build rows), or None where the exchange does not cover
        the shape; every decline hands the block to the broadcast path
        via `prebuilt` so it is never executed twice."""
        import time as _time

        from ydb_tpu.parallel import shuffle_join as SJ
        if isinstance(step.build, QueryPlan):
            built = self.execute(step.build, snapshot)
        else:
            # the chip's run of the build pipeline is a `device-execute`
            # (`device_ms`), not the host's partitioning time
            t_enq = _time.perf_counter()
            pending = self._run_pipeline(step.build, params, snapshot)
            self._await_stage(pending, t_enq)
            built = HostBlock.concat([to_host(d) for d in pending])
        if prebuilt is not None:
            prebuilt[j] = built
        if step.build_hash_keys:
            # composite key: the probe side already computed its combined
            # 64-bit hash into `probe_key` (planner pre-program); hashing
            # the build columns the same way makes the exchange key a
            # plain int64 — per-key equality verification rides in the
            # post-join programs (`rest`), exactly like the broadcast path
            built = _add_hash_column(built, step.build_hash_keys,
                                     step.build_key)
            if prebuilt is not None:
                prebuilt[j] = built
        kcd = built.columns.get(step.build_key)
        if kcd is None or np.issubdtype(kcd.data.dtype, np.floating):
            return None
        if step.anti_null_check:
            # anti/mark semantics with an ACTUALLY-NULL build key: the
            # broadcast path owns the three-valued-logic handling (empty
            # probe rule, or the loud composite NOT IN refusal); the
            # exchange would silently drop the NULLs and change the
            # answer. NULL-free builds shuffle fine.
            cd0 = built.columns.get(step.anti_null_col or step.build_key)
            if cd0 is not None and cd0.valid is not None \
                    and not cd0.valid.all():
                return None
        if kcd.dictionary is not None:
            # dictionary-encoded key: remap build codes into the PROBE
            # side's dictionary (same discipline as `_prepare_join`), so
            # codes exchange as plain comparable ints
            table = self.catalog.table(pipe.scan.table)
            probe_dicts = dict(table.dictionaries)
            for (storage, internal) in pipe.scan.columns:
                if storage in probe_dicts:
                    probe_dicts[internal] = probe_dicts[storage]
            probe_dict = probe_dicts.get(step.probe_key)
            if probe_dict is None:
                return None          # probe dict not derivable here
            if kcd.dictionary is not probe_dict:
                built = _remap_build_codes(built, step.build_key,
                                           probe_dict)
                # build values ABSENT from the probe dictionary remap to
                # the shared -2 never-match code: drop them before the
                # exchange (they can't match anything, and a shared code
                # would trip the duplicate-key uniqueness gate below)
                codes2 = built.columns[step.build_key].data
                if (codes2 == -2).any():
                    built = built.take(np.nonzero(codes2 != -2)[0])
                if prebuilt is not None:
                    prebuilt[j] = built
        # duplicate keys: the exchange probe is first-match only
        if step.kind in ("inner", "left", "mark"):
            enc = built.columns[step.build_key].data
            if len(enc) > 1 and len(np.unique(enc)) != len(enc):
                return None
        barrays, pschema, bdicts, bcap = SJ.partition_build(
            built, step.build_key, list(step.payload), ndev)
        if not pschema.names and step.payload:
            return None
        return barrays, pschema, bdicts, bcap, built.length

    def _merge_distributed_partials(self, plan: QueryPlan, per_dev: list,
                                    params: dict) -> HostBlock:
        """Shared tail of the mesh paths: hash-shuffle merge of per-device
        partial-agg blocks + the rest of the final program."""
        with self._span("mesh-merge"):
            return self._merge_partials(plan, per_dev, params)

    def _merge_partials(self, plan: QueryPlan, per_dev: list,
                        params: dict) -> HostBlock:
        import dataclasses

        from ydb_tpu.parallel.shuffle import DistributedAgg

        ndev = self.mesh.devices.size
        gb = plan.final_program.commands[0]
        merge_prog = ir.Program([gb])
        in_schema = per_dev[0][0].schema
        # bounds lattice: a PROVEN merge group-count bound sizes the
        # shuffle's per-target segments — each producer's partial holds
        # ≤ out_bound groups, so a bound-bucket segment cannot overflow
        # (replacing the full-capacity pad; the 2112.01075 stance)
        seg_rows = 0
        if gb.out_bound:
            from ydb_tpu.utils.metrics import GLOBAL
            seg_rows = bucket_capacity(max(int(gb.out_bound), 1),
                                       minimum=self.mesh_min_segment_rows)
            GLOBAL.inc("bounds/seg_bounded_shuffles")
        key = (merge_prog.fingerprint(),
               tuple((c.name, c.dtype.kind.value, c.dtype.nullable)
                     for c in in_schema.columns), ndev, seg_rows,
               groupby_tuning())
        dag = self._dist_aggs.get(key)
        if dag is None:
            dag = DistributedAgg(merge_prog, merge_prog, in_schema,
                                 self.mesh, seg_rows=seg_rows,
                                 table=plan.pipeline.scan.table)
            self._dist_aggs[key] = dag
        merged = dag.run_device_blocks(per_dev, params,
                                       await_device=self._await_device)
        rest = list(plan.final_program.commands[1:])
        plan2 = dataclasses.replace(
            plan, final_program=ir.Program(rest) if rest else None)
        return self._finalize_read(plan2, merged, params)

    def _finalize_read(self, plan: QueryPlan, union: HostBlock,
                       params: dict) -> HostBlock:
        """Single-device tail of a mesh lane: the rest of the final
        program, sort and limit over the merged rows, and the result's
        read-back under the `readout-transfer` span the fused lane has."""
        fut = self._finalize(plan, [to_device(union)], params, defer=True)
        with self._span("readout-transfer"):
            return fut.result()

    # -- pipelines ---------------------------------------------------------

    def _run_pipeline(self, pipe: Pipeline, params: dict,
                      snapshot: Snapshot, builds=None) -> list:
        """Partial-result DeviceBlocks (≥1: an empty scan still runs the
        programs once so global aggregates emit their row). `builds`:
        BuildTables already prepared by a declined fused attempt."""
        if builds is None:
            builds = self._prepare_builds(pipe, params, snapshot)
        out = []
        for d in self._scan_device_blocks(pipe, snapshot):
            out.extend(self._run_block_multi(pipe, d, builds, params))
        if not out:
            out = self._run_block_multi(
                pipe, to_device(self._empty_scan_block(pipe)), builds,
                params)
        return out

    def _run_block(self, pipe: Pipeline, d: DeviceBlock, builds: list,
                   params: dict) -> DeviceBlock:
        """Single-stream block runner (mesh path — partitioned builds are
        not routed here)."""
        out = self._run_block_multi(pipe, d, builds, params)
        assert len(out) == 1, "partitioned join on the mesh path"
        return out[0]

    def _run_block_multi(self, pipe: Pipeline, d: DeviceBlock, builds: list,
                         params: dict, until: Optional[int] = None) -> list:
        """Run one scan block through the pipeline. A GraceJoin-partitioned
        build forks the stream: probe rows route to their key's partition
        (device-side splitmix64 matches the host partitioner) and each
        partition continues through the remaining steps independently —
        their partials merge like any other blocks.

        `until`: stop BEFORE step index `until` and skip the partial (the
        shuffle-join stage-A prefix)."""
        if pipe.pre_program is not None:
            d = run_on_device(pipe.pre_program, d, params)
        stop = len(pipe.steps) if until is None else until

        def run_steps(d: DeviceBlock, si: int, bi: int) -> list:
            while si < stop:
                kind, step = pipe.steps[si]
                if kind != "join":
                    d = run_on_device(step, d, params)
                    si += 1
                    continue
                table = builds[bi]
                if isinstance(table, J.PartitionedBuild):
                    out = []
                    for p, bt in enumerate(table.tables):
                        dp = self._partition_block(d, step.probe_key, p,
                                                   table.n_partitions)
                        out.extend(self._probe_one(dp, bt, step, pipe,
                                                   run_steps, si, bi))
                    return out
                return self._probe_one(d, table, step, pipe, run_steps,
                                       si, bi)
            if until is None and pipe.partial is not None:
                d = run_on_device(pipe.partial, d, params)
            return [d]

        return run_steps(d, 0, 0)

    def _probe_one(self, d: DeviceBlock, table, step, pipe, run_steps,
                   si: int, bi: int) -> list:
        if not table.unique and step.kind in ("inner", "left"):
            # duplicate build keys → expanding probe; output compact
            d = J.probe_expand(d, table, step.probe_key, step.kind)
            return run_steps(d, si + 1, bi + 1)
        d, sel = J.probe(d, table, step.probe_key, step.kind,
                         sel=None, mark_col=step.mark_col or None,
                         not_in=step.not_in)
        if step.kind != "mark":
            d = compress_block(d, sel)
        return run_steps(d, si + 1, bi + 1)

    @staticmethod
    def _partition_block(d: DeviceBlock, key: str, p: int,
                         nparts: int) -> DeviceBlock:
        """Rows whose key hashes to partition p, compacted."""
        import jax.numpy as jnp

        from ydb_tpu.utils.hashing import splitmix64
        enc = d.arrays[key].astype(jnp.int64)
        part = splitmix64(jnp, enc) % jnp.uint64(nparts)
        return compress_block(d, part == jnp.uint64(p))

    def _prepare_builds(self, pipe: Pipeline, params: dict,
                        snapshot: Snapshot,
                        until: Optional[int] = None,
                        prebuilt: Optional[dict] = None) -> list:
        """Prepare every join build of a pipeline in order, threading the
        probe side's string dictionaries so cross-dictionary string keys
        remap to probe codes (each table/temp owns its own dictionary —
        raw code equality across two of them is meaningless).

        `until`: only the joins among steps[:until] (shuffle-join prefix).
        `prebuilt`: {step index: HostBlock} already-materialized build
        sides (a declined shuffle-join attempt hands its block over)."""
        probe_dicts = dict(self.catalog.table(pipe.scan.table).dictionaries)
        # scan columns are renamed storage→internal in the env
        for (storage, internal) in pipe.scan.columns:
            if storage in probe_dicts:
                probe_dicts[internal] = probe_dicts[storage]
        # FD-verification blocks are only ever read when the consuming
        # pipeline ends in a multi-key group-by (the carry rewrite's
        # measured lane) — don't pin host copies for any other shape
        keep_fd = (pipe.partial is not None and pipe.partial.commands
                   and isinstance(pipe.partial.commands[-1], ir.GroupBy)
                   and len(pipe.partial.commands[-1].keys) >= 2)
        builds = []
        for si, (kind, step) in enumerate(pipe.steps):
            if kind != "join" or (until is not None and si >= until):
                continue
            bt = self._prepare_join(step, params, snapshot,
                                    probe_dict=probe_dicts.get(
                                        step.probe_key),
                                    prebuilt_block=(prebuilt or {}).get(si),
                                    keep_fd=keep_fd)
            builds.append(bt)
            # payload columns join the probe namespace for later steps
            probe_dicts.update(getattr(bt, "dictionaries", None) or {})
        return builds

    def _prepare_join(self, step: JoinStep, params: dict,
                      snapshot: Snapshot, probe_dict=None,
                      prebuilt_block: Optional[HostBlock] = None,
                      keep_fd: bool = False) -> J.BuildTable:
        from ydb_tpu.query.build_cache import build_plan_fingerprint
        cache_key = None
        if prebuilt_block is None:
            single_dev = self.mesh is None or self.mesh.devices.size <= 1
            # knobs that steer the PartitionedBuild-vs-BuildTable choice
            # are part of the key (tests flip grace_budget_bytes); keep_fd
            # rides it so a group-by consumer never cache-hits a lean
            # entry whose FD block was skipped for a join-only shape
            cache_key = build_plan_fingerprint(
                step, params, snapshot, self.catalog,
                extra=(single_dev, self.grace_budget_bytes, keep_fd))
            if cache_key is not None:
                hit = self.build_cache.lookup(cache_key, probe_dict)
                if hit is not None:
                    return hit
        bt = self._prepare_join_uncached(step, params, snapshot,
                                         probe_dict, prebuilt_block,
                                         keep_fd=keep_fd)
        if cache_key is not None:
            self.build_cache.insert(cache_key, bt, probe_dict)
        return bt

    def _prepare_join_uncached(self, step: JoinStep, params: dict,
                               snapshot: Snapshot, probe_dict=None,
                               prebuilt_block: Optional[HostBlock] = None,
                               keep_fd: bool = False) -> J.BuildTable:
        if prebuilt_block is not None:
            built = prebuilt_block
        elif isinstance(step.build, QueryPlan):
            built = self.execute(step.build, snapshot)
        else:
            # route the build PIPELINE through the fused machinery too:
            # its scan gets the single-dispatch path (and the superblock
            # cache) instead of a dispatch per portion — q2/q9-class
            # queries spend most of their time in builds. Empty output =
            # keep every column (composite-key builds carry internal
            # hash columns a projection would drop).
            bplan = QueryPlan(pipeline=step.build, params=dict(params))
            fused = self._try_execute_fused(bplan, params, snapshot) \
                if self.enable_fused else None
            if isinstance(fused, tuple):
                built = fused[1]
            elif isinstance(fused, HostBlock):
                built = fused
            else:
                built = HostBlock.concat(
                    [to_host(d) for d in
                     self._run_pipeline(step.build, params, snapshot,
                                        builds=fused)])
        kcd = built.columns.get(step.build_key)
        if kcd is not None and kcd.dictionary is not None \
                and probe_dict is not None \
                and kcd.dictionary is not probe_dict:
            built = _remap_build_codes(built, step.build_key, probe_dict)
        if step.build_hash_keys:
            built = _add_hash_column(built, step.build_hash_keys,
                                     step.build_key)
        anti_has_null = False
        if step.anti_null_check:
            cd = built.columns[step.anti_null_col or step.build_key]
            if cd.valid is not None and not cd.valid.all():
                if step.kind == "left_anti":
                    # x NOT IN (set with NULL) is never TRUE → the anti
                    # probe selects nothing (SQL three-valued logic)
                    anti_has_null = True
                else:
                    # composite correlated NOT IN: a NULL poisons only its
                    # per-correlation-key set — needs per-key tracking
                    raise NotImplementedError(
                        "correlated NOT IN over a subquery producing NULLs "
                        "is not supported yet")
        # GraceJoin spill: a build side above the device budget hash-
        # partitions into host DRAM (single-device path only — the mesh
        # path replicates builds per device and would need partition
        # placement instead)
        single_dev = self.mesh is None or self.mesh.devices.size <= 1
        if single_dev and not step.not_in and built.length:
            cols = list(dict.fromkeys([step.build_key] + list(step.payload)))
            row_bytes = sum(built.columns[n].data.itemsize for n in cols)
            if built.length * row_bytes > self.grace_budget_bytes:
                return J.build_partitioned(built, step.build_key,
                                           list(step.payload),
                                           self.grace_budget_bytes)
        bt = J.build(built, step.build_key, list(step.payload),
                     keep_fd=keep_fd,
                     existence=step.kind in J.EXISTENCE_KINDS)
        bt.anti_has_null = anti_has_null
        return bt

    def _scan_device_blocks(self, pipe: Pipeline, snapshot: Snapshot,
                            devices=None):
        """Per-portion device blocks via the HBM column cache; committed but
        unindexed inserts upload uncached (they are transient — indexation
        turns them into portions).

        With `devices`, sources are placed round-robin across the mesh and
        (device_index, block) pairs are yielded instead (partition
        parallelism — the DataShard/ColumnShard shard-spread analog)."""
        table = self.catalog.table(pipe.scan.table)
        storage_names = [s for (s, _i) in pipe.scan.columns]
        rename = {s: i for (s, i) in pipe.scan.columns}
        i = 0
        for shard in table.shards:
            portions, insert_entries = shard.scan_sources(
                snapshot, pipe.scan.prune or None)
            for p in portions:
                if p.deletes and p.delete_sig(snapshot):
                    # MVCC delete marks: scan the filtered view uncached
                    # (the view is snapshot-specific; the mark set is in
                    # the superblock cache key on the fused path)
                    hb = _rename_block(
                        p.visible_block(snapshot).select(storage_names),
                        rename)
                    if devices is None:
                        yield to_device(hb)
                    else:
                        di = i % len(devices)
                        i += 1
                        yield di, to_device(hb, device=devices[di])
                    continue
                if devices is None:
                    yield self.device_cache.device_block(p, storage_names,
                                                         rename)
                else:
                    di = i % len(devices)
                    i += 1
                    yield di, self.device_cache.device_block(
                        p, storage_names, rename, device=devices[di])
            for e in insert_entries:
                hb = _rename_block(e.block.select(storage_names), rename)
                if devices is None:
                    yield to_device(hb)
                else:
                    di = i % len(devices)
                    i += 1
                    yield di, to_device(hb, device=devices[di])

    def _empty_scan_block(self, pipe: Pipeline) -> HostBlock:
        """Zero-row block with the scan's schema and dictionaries."""
        table = self.catalog.table(pipe.scan.table)
        cols, schema_cols = {}, []
        for (storage, internal) in pipe.scan.columns:
            c = table.schema.col(storage)
            cols[internal] = ColumnData(
                np.zeros(0, dtype=c.dtype.np), None,
                table.dictionaries.get(storage))
            schema_cols.append(Column(internal, c.dtype))
        return HostBlock(Schema(schema_cols), cols, 0)

    # -- fused finalize ----------------------------------------------------

    def _finalize(self, plan: QueryPlan, dblocks: list, params: dict,
                  defer: bool = False) -> "HostBlock | DeviceResultFuture":
        """Concat partials + final program + sort + limit in ONE device
        call, then one batched transfer (`defer=True`: the transfer is
        wrapped in a `DeviceResultFuture` and runs at `result()` time —
        the pipeline readout phase). Partial-agg states too large to
        merge in one device concat (high-cardinality group-bys on the
        portioned path) route to the host-DRAM partitioned merge instead
        of compiling an HBM-sized program."""
        in_schema = dblocks[0].schema

        fp = plan.final_program
        merge_gb = fp.commands[0] if fp is not None and fp.commands \
            and isinstance(fp.commands[0], ir.GroupBy) else None
        if merge_gb is not None and merge_gb.keys and len(dblocks) > 1:
            prow = sum(np.dtype(c.dtype.np).itemsize + 1
                       for c in in_schema.columns)
            total = sum(d.capacity for d in dblocks) * prow
            if total > self.merge_budget_bytes:
                from ydb_tpu.ops import spill as SP
                from ydb_tpu.utils.metrics import GLOBAL
                P = 1
                while total / P > self.merge_budget_bytes and P < 256:
                    P *= 2
                dicts = {}
                for d in dblocks:
                    dicts.update(d.dictionaries)
                store = SP.PartitionStore(in_schema, list(merge_gb.keys),
                                          P, dicts)
                for d in dblocks:
                    store.feed(d)
                GLOBAL.inc("executor/spilled_rows", store.spilled_rows)
                GLOBAL.inc("executor/spilled_bytes", store.spilled_bytes)
                merged = self._merge_spilled(plan, store, params)
                return DeviceResultFuture.completed(merged) if defer \
                    else merged
        sort_params, sort_spec, rank_assigns = self._sort_setup(
            plan, in_schema, dblocks)
        all_params = {**params, **sort_params}

        blocks_sig = tuple(
            (tuple(sorted(d.arrays)), tuple(sorted(d.valids)), d.capacity)
            for d in dblocks)
        key = (plan.final_program.fingerprint() if plan.final_program else "",
               ir.Program(rank_assigns).fingerprint() if rank_assigns else "",
               sort_spec, plan.limit, plan.offset, blocks_sig,
               tuple((c.name, c.dtype.kind.value, c.dtype.nullable)
                     for c in in_schema.columns),
               tuple(sorted(all_params)),
               tuple(n for (n, _lbl) in plan.output), groupby_tuning())
        entry = self._finalize_cache.get(key)
        if entry is None:
            entry = self._build_finalize(plan, in_schema, blocks_sig,
                                         sort_spec, rank_assigns)
            self._finalize_cache[key] = entry
        fn, out_schema = entry

        blocks_in = tuple((d.arrays, d.valids, d.length) for d in dblocks)
        dev_params = {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
                      for k, v in all_params.items()}
        out_d, out_v, length = fn(blocks_in, dev_params)

        dicts = {}
        for d in dblocks:
            dicts.update(d.dictionaries)
        dicts.update(plan.result_dicts)
        dicts = {n: dc for n, dc in dicts.items() if out_schema.has(n)}
        out_cap = (next(iter(out_d.values())).shape[0] if out_d else 0)
        dblock = DeviceBlock(out_schema, out_d, out_v, length, out_cap, dicts)
        lo = plan.offset or 0
        limit = plan.limit
        fut = to_host_async(dblock).map(
            lambda block: _apply_offset(block, lo, limit))
        return fut if defer else fut.result()

    def _sort_setup(self, plan: QueryPlan, in_schema: Schema, dblocks: list):
        """Rank-LUT params for string sort keys (lexicographic order over
        dictionary codes) + the static sort spec."""
        from ydb_tpu.core import dtypes as dt
        sort_params = {}
        rank_assigns = []
        spec = []
        schema = in_schema
        if plan.final_program is not None:
            schema = ir.infer_schema(plan.final_program, in_schema)
        dicts = {}
        for d in dblocks:
            dicts.update(d.dictionaries)
        dicts.update(plan.result_dicts)
        for j, sk in enumerate(plan.sort):
            dtype = schema.dtype(sk.name)
            dic = dicts.get(sk.name)
            if dtype.is_string and dic is not None:
                ranks = dic.sort_ranks()
                pname = f"__rank{j}"
                sort_params[pname] = ranks
                rank_col = f"__sortrank{j}"
                rank_assigns.append(ir.Assign(rank_col, ir.call(
                    "take_lut", ir.Col(sk.name),
                    ir.Param(pname, dt.DType(dt.Kind.INT32, False),
                             is_array=True))))
                spec.append((rank_col, sk.ascending, sk.nulls_first))
            else:
                spec.append((sk.name, sk.ascending, sk.nulls_first))
        return sort_params, tuple(spec), rank_assigns

    def _build_finalize(self, plan: QueryPlan, in_schema: Schema,
                        blocks_sig: tuple, sort_spec: tuple,
                        rank_assigns: list):
        final_prog = plan.final_program
        in_cols = list(in_schema.columns)
        names = [c.name for c in in_cols]
        out_schema = ir.infer_schema(final_prog, in_schema) \
            if final_prog is not None else in_schema
        limit = plan.limit
        lim2 = None if limit is None else limit + (plan.offset or 0)
        keep = [n for (n, _lbl) in plan.output]
        keep = list(dict.fromkeys(keep))

        @jax.jit
        def fn(blocks, params):
            datas, valid_arrays, masks = {n: [] for n in names}, \
                {n: [] for n in names}, []
            total = 0
            for (arrays, valids, length), (_an, _vn, cap) in zip(blocks,
                                                                 blocks_sig):
                iota = jnp.arange(cap, dtype=jnp.int32)
                masks.append(iota < length)
                total += cap
                for n in names:
                    datas[n].append(arrays[n])
                    v = valids.get(n)
                    valid_arrays[n].append(
                        v if v is not None else jnp.ones((cap,), jnp.bool_))
            env = {n: (jnp.concatenate(datas[n]),
                       jnp.concatenate(valid_arrays[n])) for n in names}
            mask = jnp.concatenate(masks)
            env, length = compress(env, jnp.int32(total), mask, total)
            cap = total
            if final_prog is not None:
                env, length, sel, _schema = _trace_program(
                    final_prog, in_cols, cap, env, length, params)
                if env:
                    cap = next(iter(env.values()))[0].shape[0]
                if sel is not None:
                    env, length = compress(env, length, sel, cap)
            for a in rank_assigns:
                from ydb_tpu.ops.xla_exec import _eval
                env[a.name] = _eval(a.expr, env, params, cap)
            if sort_spec:
                arrays = {n: d for n, (d, _v) in env.items()}
                valids = {n: v for n, (d, v) in env.items() if v is not None}
                arrays2, valids2, length = sort_env(
                    arrays, valids, length, None, sort_spec,
                    tuple(arrays.keys()))
                env = {n: (arrays2[n], valids2.get(n)) for n in arrays2}
            if lim2 is not None:
                length = jnp.minimum(length, jnp.int32(lim2))
                out_cap = min(bucket_capacity(lim2, minimum=128), cap)
                env = {n: (d[:out_cap], v[:out_cap] if v is not None else None)
                       for n, (d, v) in env.items()}
            out_names = [n for n in keep if n in env] or list(env.keys())
            out_d = {n: env[n][0] for n in out_names}
            out_v = {n: env[n][1] for n in out_names
                     if env[n][1] is not None}
            return out_d, out_v, length

        out_cols = [c for c in out_schema.columns if c.name in keep] \
            or list(out_schema.columns)
        return fn, Schema(out_cols)


    # -- output ------------------------------------------------------------

    def _project_output(self, block: HostBlock, output: list) -> HostBlock:
        from ydb_tpu.ops.device import DeviceStageBlock
        if isinstance(block, DeviceStageBlock) and not block.materialized:
            # stage-spine path: rename device-side, references only —
            # touching `block.columns` here would force the readback the
            # capture exists to avoid
            return block.project(output)
        cols = {}
        schema_cols = []
        used = set()
        for (internal, label) in output:
            lbl = label
            k = 2
            while lbl in used:
                lbl = f"{label}_{k}"
                k += 1
            used.add(lbl)
            cd = block.columns[internal]
            cols[lbl] = ColumnData(cd.data, cd.valid, cd.dictionary)
            schema_cols.append(Column(lbl, block.schema.dtype(internal)))
        return HostBlock(Schema(schema_cols), cols, block.length)


def _param_values_equal(a, b) -> bool:
    """Batch-invariance test for one runtime param across two members
    (arrays compare by dtype/shape/contents; scalars by type + value)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
                and a.dtype == b.dtype and a.shape == b.shape
                and np.array_equal(a, b))
    return type(a) is type(b) and bool(a == b)


def _apply_offset(block: HostBlock, lo: int, limit) -> HostBlock:
    """Shared OFFSET/LIMIT tail slice of every deferred-readout path
    (fused fetch + finalize) — one definition so the two lanes can't
    silently diverge."""
    if lo:
        hi = lo + limit if limit is not None else block.length
        block = block.slice(lo, min(hi, block.length))
    return block


def _remap_build_codes(built: HostBlock, key: str, probe_dict) -> HostBlock:
    """Translate a build block's dictionary-encoded key codes into the
    PROBE side's dictionary (host-side O(distinct) LUT; values absent
    from the probe dictionary → -2, the never-match code; negative codes
    — the -1 NULL slot — pass through untouched)."""
    kcd = built.columns[key]
    src = kcd.dictionary.values_array()
    lut = np.full(max(len(src), 1), -2, dtype=np.int32)
    for i, v in enumerate(src):
        lut[i] = probe_dict.encode_existing(v)
    codes = kcd.data
    remapped = np.where(codes >= 0, lut[np.clip(codes, 0, None)],
                        codes).astype(codes.dtype)
    return HostBlock(
        built.schema,
        {**built.columns, key: ColumnData(remapped, kcd.valid, probe_dict)},
        built.length)


def _add_hash_column(block: HostBlock, key_cols: list, out: str) -> HostBlock:
    """Host-side mirror of the device hash-key expression
    (`hash_combine(hash64(c0), hash64(c1), ...)`) — bit-identical by
    construction (`ydb_tpu/utils/hashing.py`). Idempotent: a block that
    already carries `out` (a declined shuffle attempt's prebuilt handoff)
    passes through, instead of appending a duplicate schema column."""
    from ydb_tpu.core.dtypes import DType, Kind
    from ydb_tpu.utils.hashing import hash_combine, splitmix64

    if out in block.columns:
        return block
    h = None
    valid = None
    for name in key_cols:
        cd = block.columns[name]
        x = splitmix64(np, cd.data.astype(np.int64))
        h = x if h is None else hash_combine(np, h, x)
        if cd.valid is not None:
            valid = cd.valid if valid is None else (valid & cd.valid)
    cols = dict(block.columns)
    cols[out] = ColumnData(h, valid, None)
    schema = block.schema.extend([Column(out, DType(Kind.UINT64,
                                                    valid is not None))])
    return HostBlock(schema, cols, block.length)


def _rename_block(block: HostBlock, rename: dict) -> HostBlock:
    cols = {}
    schema_cols = []
    for c in block.schema:
        new = rename.get(c.name, c.name)
        cols[new] = block.columns[c.name]
        schema_cols.append(Column(new, c.dtype))
    return HostBlock(Schema(schema_cols), cols, block.length)
