"""Multi-query batched dispatch lane (continuous batching for SQL).

The north-star traffic shape is many concurrent clients asking ONE
shape with their own literals. PR 1's pipeline overlaps their readouts,
and parameter lifting (`query/paramlift.py`) already collapses their
compiles to one executable per plan SHAPE — but each client still pays
its own device dispatch, its own pass over the scan and its own
device→host readout. The inference-serving answer is to batch:
same-shape arrivals inside a small time window coalesce into ONE
stacked execution (`Executor.execute_fused_batched` — a vmap over the
members' lifted literals, DrJAX-style mapped composition, arxiv
2403.07128), each client's result resolving to its slice. What a
dispatch costs on the chip is PERF.md's to say (round 36: sixteen Q6 of
a 64 Mi-slot scan in one program).

`YDB_TPU_BATCH_WINDOW` (milliseconds; 0 = off, the default) is the A/B
switch: off is byte-identical to the per-query pipeline path. A group
seals EARLY when it reaches `YDB_TPU_BATCH_MAX` members (default 64),
so a thundering herd pays no window latency; sparse traffic pays at
most one window per query, and a leader still alone after
`QueryEngine.batch_alone_probe_ms` (2 ms) runs at once on the per-query
program.

Grouping is correctness-first. Two statements coalesce only when:

  * their `lift_sig`s match — same prune-stripped plan shape, so one
    compiled program serves both (the batched execution runs UN-pruned:
    pruning's outcome is literal-dependent and cannot partition a
    shared scan; the filter programs still apply every predicate);
  * every table either statement scans presents the IDENTICAL visible
    source set (src ids) at both snapshots — the superblock cache's
    data-identity discipline, so executing at the leader's snapshot is
    exact for every member (explicit-tx snapshots with older pins
    simply land in their own groups);
  * their build-affecting lifted literals agree — join builds execute
    once per batch, with the leader's values.

Admission rule: a batch is admitted by what its stacked program HOLDS
(`Executor.batched_working_set`, one function for the gate here and for
the reservation). The shared inputs — superblock columns, build tables
— enter the vmapped program once (`in_axes=None`), so the working set
is NOT `B` scans: for a program that has been compiled it is the
compiler's own `memory_analysis()` (arguments + temporaries + outputs;
sixteen Q6 over 64 Mi slots: 4.4 GB, where `B` x the scan estimate read
25.6); before its first compile, a bound from the plan (shared inputs +
member slots x scan slots x the widths the body computes), which is
loose, so `Executor.warm_batched` builds the `Bb` = 2 .. max programs
before a shape's first statement joins a group. A shape whose working
set passes `fused_scan_budget_bytes` at `max_batch` members stays on
the per-query path (`batch/declined/working-set`); un-limited
un-aggregated outputs keep the tighter merge-budget bound, since `B`
full result buffers also cross to the host.

Members do NOT take individual admission reservations or
pipeline-window slots. The leader takes ONE window slot and ONE byte
reservation of that working set, spanning dispatch and readout — N
nominal slots for one physical execution could deadlock the window
under storm load — and two sealed batches hold theirs side by side
while the budget has room: the second dispatches behind the first's
program, and the device does not wait for the host to answer sixteen
clients.

Spans: `batch-wait` (a member's wait from joining its group to its
slice; `phases["batch_wait_ms"]`, the span's own time: the leader's
dispatch, device wait and readout are phases of their own under it;
attributes `b`, `bb`, `leader`, `sealed_by` full | window | alone),
`admission-wait` on the leader, `device-dispatch-batched` (`b`,
`reserved_mb`, `temp_mb`). Counters: batch/batches,
batch/coalesced_queries, batch/max_size, batch/singles,
batch/fallbacks, batch/declined and batch/declined/<reason> (no-lift,
subplans, mesh, working-set, merge-budget, row-store),
batch/trace_errors, batch/member_slots, batch/pad_slots,
batch/reserved_bytes, batch/ahead_compiles, plus paramlift's
batch/lift_hits / batch/lift_misses; EXPLAIN ANALYZE carries a
`batching` block per statement (QueryStats.batching).
"""

from __future__ import annotations

import threading
from contextlib import ExitStack
from typing import Optional

from ydb_tpu.ops import ir
from ydb_tpu.query.executor import unpruned
from ydb_tpu.query.plan import QueryPlan


class _Group:
    __slots__ = ("members", "sealed", "full", "done", "results", "exc",
                 "batched", "info")

    def __init__(self):
        self.members: list = []       # [(plan, params, snap, est)]
        self.sealed = False
        self.full = threading.Event()
        self.done = threading.Event()
        self.results: Optional[list] = None
        self.exc: Optional[BaseException] = None
        self.batched = False
        # what the leader learned, for every member's `batch-wait` span
        # and `batching` block: sealed_by, bb, reserved_bytes
        self.info: dict = {}


def _has_groupby(plan: QueryPlan) -> bool:
    pipe = plan.pipeline
    progs = [pipe.partial, plan.final_program]
    return any(p is not None and any(isinstance(c, ir.GroupBy)
                                     for c in p.commands) for p in progs)


def _plan_tables(plan: QueryPlan, out: Optional[set] = None) -> set:
    """Every table any pipeline of the plan scans (builds included)."""
    if out is None:
        out = set()

    def walk_pipe(pipe):
        out.add(pipe.scan.table)
        for kind, step in pipe.steps:
            if kind != "join":
                continue
            b = step.build
            if isinstance(b, QueryPlan):
                _plan_tables(b, out)
            else:
                walk_pipe(b)

    walk_pipe(plan.pipeline)
    return out


class BatchLane:
    def __init__(self, engine, window_s: float, max_batch: int = 64):
        self.engine = engine
        self.window_s = window_s
        self.max_batch = max(1, int(max_batch))
        self._mu = threading.Lock()
        self._groups: dict = {}              # guarded-by: _mu
        # (table, uid, data_version, snap.plan_step) -> src-id sig memo:
        # between commits the coordinator publishes no new plan step, so
        # a storm's members all hit one entry; ANY commit advances the
        # step and naturally invalidates (compaction/indexation run at
        # commit points). Bounded: cleared when it outgrows the window.
        self._sig_memo: dict = {}            # guarded-by: _mu

    # -- eligibility / grouping --------------------------------------------

    def _group_key(self, plan: QueryPlan, snap, est: int,
                   declined: Optional[dict] = None):
        """The group this statement may join, or None for one that stays
        on the per-query path; `declined` then takes the `reason`
        (`batch/declined/<reason>`)."""
        from ydb_tpu.query.paramlift import build_lift_values

        def no(reason: str):
            if declined is not None:
                declined["reason"] = reason

        if getattr(plan, "lift_sig", None) is None:
            return no("no-lift")
        if plan.init_subplans:
            # precompute stages run their own sub-SELECTs; keep them on
            # the per-query path
            return no("subplans")
        ex = self.engine.executor
        if not ex.enable_fused:
            return no("fused-off")
        if ex.mesh is not None and ex.mesh.devices.size > 1:
            return no("mesh")
        try:
            data_sig = tuple(self._table_sig(t, snap)
                             for t in sorted(_plan_tables(plan)))
        except (AttributeError, KeyError):
            return no("row-store")     # row-store scan / dropped table:
            #                            no src ids
        # un-limited un-aggregated outputs: B full result buffers cross
        # to the host, whatever the program holds on the device
        if plan.limit is None and not _has_groupby(plan) \
                and est * self.max_batch > ex.merge_budget_bytes:
            return no("merge-budget")
        # the stacked programs exist before the first group forms; the
        # gate below then reads the compiler's figure, not the bound
        ex.warm_batched(plan, snap, self.max_batch,
                        self.engine.admission.timeout_s)
        # working-set gate: what a stacked dispatch of `max_batch`
        # members holds (the shared scan ONCE, plus the program's
        # temporaries and outputs) against what one fused program may
        # hold. A LIMIT or GROUP BY bounds only the result: the body's
        # cap-sized intermediates are the compiler's to count.
        if self._working_set(plan, snap, self.max_batch, est)[0] \
                > ex.fused_scan_budget_bytes:
            return no("working-set")
        return (plan.lift_sig, data_sig, build_lift_values(plan))

    def _working_set(self, plan: QueryPlan, snap, n: int, est: int):
        """(bytes, how) a stacked dispatch of `n` members holds: the ONE
        mechanism of the gate and of the reservation
        (`Executor.batched_working_set`: the compiler's figure, else the
        plan's bound). A plan the bound cannot walk (a derived build
        column it cannot type) is charged, like one whose body sorts, the
        old `n` x the per-member estimate."""
        from ydb_tpu.query.admission import batch_reservation_bytes
        try:
            return self.engine.executor.batched_working_set(
                plan, snap, n, est)
        except (AttributeError, KeyError, TypeError, ValueError):
            return batch_reservation_bytes(est, n), "members"

    def _table_sig(self, name: str, snap) -> tuple:
        from ydb_tpu.storage.device_cache import enumerate_scan_sources
        t = self.engine.catalog.table(name)
        memo_key = (name, t.uid, t.data_version, snap.plan_step)
        with self._mu:
            sig = self._sig_memo.get(memo_key)
        if sig is None:
            # enumerate outside the lock (it walks portions); publish
            # under it — storm threads raced clear()+setitem unguarded
            # here before the locks pass caught it
            _sources, ids = enumerate_scan_sources(t, snap, None)
            sig = (t.uid, t.data_version, tuple(ids))
            with self._mu:
                if len(self._sig_memo) > 256:
                    self._sig_memo.clear()
                self._sig_memo[memo_key] = sig
        return sig

    # -- entry -------------------------------------------------------------

    def try_run(self, plan: QueryPlan, snap, est: int, stats=None):
        """Coalesce this SELECT into a same-shape batch and return its
        HostBlock, or None when the statement isn't lane-eligible (the
        caller runs the normal per-query pipeline)."""
        from ydb_tpu.utils.metrics import GLOBAL

        declined: dict = {}
        key = self._group_key(plan, snap, est, declined)
        if key is None:
            GLOBAL.inc("batch/declined")
            # lint: allow-counters(batch/declined/* registered)
            GLOBAL.inc(f"batch/declined/{declined['reason']}")
            return None
        with self._mu:
            g = self._groups.get(key)
            leader = g is None or g.sealed or len(g.members) >= self.max_batch
            if leader:
                g = _Group()
                self._groups[key] = g
            idx = len(g.members)
            g.members.append((plan, dict(plan.params), snap, est))
            if len(g.members) >= self.max_batch:
                g.full.set()             # herd: seal without window latency
        # a member's wait from joining its group to its slice: a phase of
        # its own (`batch_wait_ms`), the span's OWN time — the leader's
        # admission wait, dispatch, device wait and readout nest under it
        # and stay the phases they are
        with self.engine.tracer.span("batch-wait", leader=leader) as sp:
            self._wait(g, key, leader)
            sp.attrs.update(b=len(g.members), **{
                k: g.info[k] for k in ("bb", "sealed_by") if k in g.info})
        if g.exc is not None:
            raise g.exc
        if stats is not None:
            stats.batching = {"coalesced": len(g.results),
                              "leader": leader,
                              "batched": g.batched, **g.info}
        if g.batched:
            self.engine.executor.last_path = "fused-batched"
        return g.results[idx]

    def _wait(self, g: _Group, key, leader: bool) -> None:
        """Until the group's results (or its exception) stand: the leader
        waits for the seal and executes, a follower waits for the leader."""
        from ydb_tpu.query.admission import AdmissionTimeout
        from ydb_tpu.utils.metrics import GLOBAL
        if leader:
            # the WHOLE leader section runs under one finally: a
            # BaseException during the window wait or the seal (not just
            # inside _execute) must still seal the group and release the
            # followers — an unsealed leaderless group would keep
            # collecting arrivals that block until their deadline
            try:
                # continuous-batching probe: a leader that is still
                # ALONE after a short grace (`batch_alone_probe_ms` of
                # the engine, 2 ms) executes immediately — sparse
                # traffic must not pay the window as latency. Only
                # evidence of concurrency (a follower already queued)
                # buys the full window; a herd seals even earlier via
                # the full event.
                probe = min(self.engine.batch_alone_probe_ms / 1000.0,
                            self.window_s)
                sealed_by = "full"
                if not g.full.wait(probe):
                    with self._mu:
                        alone = len(g.members) <= 1
                    sealed_by = "alone"
                    if not alone:
                        sealed_by = "full" if g.full.wait(
                            max(self.window_s - probe, 0.0)) else "window"
                with self._mu:
                    g.sealed = True
                    if self._groups.get(key) is g:
                        del self._groups[key]
                    members = list(g.members)
                g.info["sealed_by"] = sealed_by
                g.results, g.batched = self._execute(members, g.info)
            except Exception as e:       # noqa: BLE001 — fanned out below
                g.exc = e
            finally:
                with self._mu:
                    g.sealed = True
                    if self._groups.get(key) is g:
                        del self._groups[key]
                if g.results is None and g.exc is None:
                    # a BaseException (KeyboardInterrupt) tore the leader
                    # out mid-batch: followers must not hang on it
                    g.exc = RuntimeError("batch leader aborted")
                g.done.set()
        ok = g.done.wait(self.engine.admission.timeout_s
                         + self.window_s + 60.0)
        if not ok:
            GLOBAL.inc("batch/window_timeouts")
            raise AdmissionTimeout(
                "batched dispatch did not complete inside the admission "
                "deadline (leader stalled)")

    # -- leader ------------------------------------------------------------

    def _execute(self, members: list, info: dict):
        """Run one sealed batch under ONE window slot + ONE admission
        reservation; returns ([HostBlock] in member order, batched?).
        `info` takes what every member's span and `batching` block say:
        `bb`, `reserved_bytes`."""
        from ydb_tpu.utils.metrics import GLOBAL

        eng = self.engine
        B = len(members)
        leader_plan, _p, snap, _e = members[0]
        est = max(m[3] for m in members)
        if B > 1:
            # what the stacked program holds, the shared scan once: the
            # figure the gate admitted the shape by
            est, info["admitted_by"] = self._working_set(
                leader_plan, snap, B, est)
        with ExitStack() as held:
            # the per-query path's own wait and span
            # (`phases["admission_ms"]`, the leader's alone)
            eng._admission_wait(held, est)
            GLOBAL.inc("batch/reservations")
            GLOBAL.inc("batch/reserved_bytes", est)
            info["reserved_bytes"] = est
            if B == 1:
                # nothing coalesced: the per-query executable (with
                # pruning) already exists — don't compile a
                # batch-of-1 variant for sparse traffic
                GLOBAL.inc("batch/singles")
                return [eng.executor.execute(leader_plan, snap)], False
            blocks = eng.executor.execute_fused_batched(
                unpruned(leader_plan), [(m[0], m[1]) for m in members],
                snap, info=info)
            if blocks is None:
                # shape declined at execution depth (expanding probe,
                # tiled-class scan, vmap trace failure): serve every
                # member individually under the held reservation
                GLOBAL.inc("batch/fallbacks")
                return [eng.executor.execute(m[0], m[2])
                        for m in members], False
            GLOBAL.inc("batch/batches")
            GLOBAL.inc("batch/coalesced_queries", B)
            GLOBAL.set_max("batch/max_size", B)
            return blocks, True
