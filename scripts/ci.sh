#!/usr/bin/env bash
# CI gate: tier-1 tests (the ROADMAP.md verify command, verbatim) plus
# the concurrent-dispatch smoke — a regression in the query pipeline
# (no overlap, or concurrent slower than serial) fails the build loudly
# instead of silently re-serializing every client behind the dispatch
# cliff.
#
# Usage: scripts/ci.sh            (from anywhere inside the repo)
#   CI_CONCURRENCY=8              threads for the pipeline smoke
#   BENCH_MIN_SPEEDUP=0.9         concurrent-vs-serial floor (default is
#                                 noise-tolerant; the deterministic gate
#                                 is overlap_hits > 0 — raise the floor
#                                 on quiet dedicated hardware)
#   CI_SKIP_SMOKE=1               tier-1 + gather gate only (1-core runners)

set -u
cd "$(dirname "$0")/.."

echo "== graftlint invariant gate (AST passes vs baseline) =="
# fast static leg, runs FIRST (no JAX, <2 s): host-sync escapes in the
# device-resident modules, cache keys missing YDB_TPU_* levers, guarded
# state mutated outside its lock, unregistered counters, RPC surface
# drift — any finding not in ydb_tpu/analysis/baseline.json fails, and
# so does a baseline recording debt the tree no longer has
python scripts/lint_gate.py
lrc=$?
if [ "$lrc" -ne 0 ]; then
    echo "graftlint gate FAILED (rc=$lrc)" >&2
    exit "$lrc"
fi

echo "== tier-1 tests (ROADMAP.md verify) =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
    echo "tier-1 FAILED (rc=$rc)" >&2
    exit "$rc"
fi

if [ "${CI_SKIP_SMOKE:-0}" = "1" ]; then
    echo "== pipeline smoke skipped (CI_SKIP_SMOKE=1) =="
    exit 0
fi

echo "== concurrent-dispatch smoke (bench.py --concurrency) =="
JAX_PLATFORMS=cpu python bench.py --concurrency "${CI_CONCURRENCY:-8}"
src=$?
if [ "$src" -ne 0 ]; then
    echo "pipeline concurrency smoke FAILED (rc=$src)" >&2
    exit "$src"
fi

echo "== batched-dispatch storm gate (lift: 1 compile; lane: >=5x dispatch amortization, byte-equal) =="
# deterministic gates: the 64-literal storm compiles ONE fused program
# (parameter lifting), the batched lane coalesces >=5 queries per
# stacked device execution, and results are byte-equal with the lane
# off. Wall-clock floor defaults noise-tolerant (CI_STORM_MIN_SPEEDUP,
# like BENCH_MIN_SPEEDUP above) — raise it on quiet on-chip hardware.
JAX_PLATFORMS=cpu python scripts/batch_gate.py
brc=$?
if [ "$brc" -ne 0 ]; then
    echo "batched-dispatch storm gate FAILED (rc=$brc)" >&2
    exit "$brc"
fi

echo "== cross-worker trace gate (one assembled tree; retries visible) =="
# deterministic profile-subsystem gate: a 2-worker DQ join yields exactly
# ONE assembled trace with task spans from both workers and nonzero
# channel bytes, and a retried stage shows both task attempts in the tree
JAX_PLATFORMS=cpu python scripts/trace_gate.py
trc=$?
if [ "$trc" -ne 0 ]; then
    echo "trace gate FAILED (rc=$trc)" >&2
    exit "$trc"
fi

echo "== critical-path gate (connected >=90% coverage, Perfetto export, clock rebase) =="
# the critical-path/timeline floor: a 2-worker DQ join must extract a
# CONNECTED critical path covering >=90% of the graph wall with every
# segment class-labeled, distributed EXPLAIN ANALYZE must print the
# per-class percentages, the Chrome trace-event export must validate
# structurally (matched flows, monotone non-negative rebased
# timestamps, >=1 channel flow arrow) and serve identically over
# GET /trace/<id>, and a +5s worker clock skew must rebase away
JAX_PLATFORMS=cpu python scripts/critpath_gate.py
cprc=$?
if [ "$cprc" -ne 0 ]; then
    echo "critical-path gate FAILED (rc=$cprc)" >&2
    exit "$cprc"
fi

echo "== DQ ICI-plane gate (4-device mesh: plane selection, byte-equal, bytes moved) =="
# the pluggable channel-plane floor: on a virtual 4-device mesh a
# sharded×sharded join must lower its shuffle edges to plane=ici,
# stay byte-equal to the forced host plane (YDB_TPU_DQ_PLANE=host),
# move its bytes from dq/channel_bytes to dq/ici_bytes, and the
# quantization lever must save bytes within the declared tolerance
JAX_PLATFORMS=cpu python scripts/ici_gate.py
irc=$?
if [ "$irc" -ne 0 ]; then
    echo "ICI-plane gate FAILED (rc=$irc)" >&2
    exit "$irc"
fi

echo "== device-resident spine gate (planned redistribution: zero in-plan host sync, wire <= 1.3x live) =="
# the stage-spine floor: a multi-stage join runs with
# hostsync/to_pandas_in_plan == 0 (stage results ride the device link),
# the planned exchange keeps ICI wire bytes <= 1.3x live (the legacy 2x
# path measured ~3.25x), results stay byte-equal vs the forced host
# plane, and YDB_TPU_DQ_PLANNED=0 restores the legacy path byte-equal
JAX_PLATFORMS=cpu python scripts/spine_gate.py
sprc=$?
if [ "$sprc" -ne 0 ]; then
    echo "device-resident spine gate FAILED (rc=$sprc)" >&2
    exit "$sprc"
fi

echo "== resource-ledger memory gate (padding ratio, peak HBM, flight recorder, /metrics) =="
# the bytes floor: the bench-shaped DQ join must report a padding ratio
# from counters alone, a fused SELECT must measure nonzero mem/peak_bytes
# with its .sys/query_memory row, the flight recorder must count exactly
# one boundary transfer per fused SELECT (and pin to_pandas-inside-plan
# nonzero on the DQ join), /metrics must parse as valid OpenMetrics, and
# YDB_TPU_MEMLEDGER=0 must be byte-equal with every ledger counter silent
JAX_PLATFORMS=cpu python scripts/memory_gate.py
mrc=$?
if [ "$mrc" -ne 0 ]; then
    echo "memory gate FAILED (rc=$mrc)" >&2
    exit "$mrc"
fi

echo "== compiled-program observatory gate (roofline rows, EXPLAIN block, lever-off byte-equal) =="
# the program-roofline floor: the fused bench join must land a
# .sys/compiled_programs row with NONZERO compiler-sourced flops+bytes
# (or an explicit cost='unavailable' stamp — never silent zeros), a
# measured utilization % + bound-class, EXPLAIN ANALYZE must print the
# `-- programs:` block, inventory hit counts must match the ProgramCache
# counters, and YDB_TPU_PROGSTATS=0 must be byte-equal with prog/* frozen
JAX_PLATFORMS=cpu python scripts/prog_gate.py
prc=$?
if [ "$prc" -ne 0 ]; then
    echo "compiled-program observatory gate FAILED (rc=$prc)" >&2
    exit "$prc"
fi

echo "== zero-compile serving gate (store warm, kill -9, restart with compile_ms=0, lever-off no files) =="
# the persistent-store floor: a warm run serializes every fused shape
# and dies by SIGKILL (no clean shutdown); the restart against the same
# store dir dispatches every shape from disk (prog/store_hits == warmed
# shapes, prog/compile_ms EXACTLY 0, every inventory row source='store',
# digests byte-equal); YDB_TPU_PROGSTORE=0 runs byte-equal touching no
# store files and no store counters
JAX_PLATFORMS=cpu python scripts/progstore_gate.py
psrc=$?
if [ "$psrc" -ne 0 ]; then
    echo "zero-compile serving gate FAILED (rc=$psrc)" >&2
    exit "$psrc"
fi

echo "== late-materialization gate (row-id deferral, bound-sized compact, bytes_accessed down, lever byte-equal) =="
# the late-mat floor: the bench join must defer its emit-only payloads
# (counter + EXPLAIN `latemat:` lines) on the FUSED path, plan a
# bound-sized ir.Compact (< scan capacity / 2, zero overflow reruns)
# whose live/padded account beats the capacity-sized counterfactual
# >=2x, move fewer cost-model bytes than the lever-off program, and
# YDB_TPU_LATE_MAT=0 must replan + recompile byte-equal
JAX_PLATFORMS=cpu python scripts/latemat_gate.py
lmrc=$?
if [ "$lmrc" -ne 0 ]; then
    echo "late-materialization gate FAILED (rc=$lmrc)" >&2
    exit "$lmrc"
fi

echo "== bench trajectory regression gate (history vs last-known-good, q7/q9 watched, host-lane ceiling) =="
# the newest BENCH_HISTORY.jsonl entry must not regress any suite's
# geomean >25% vs .bench_last_good.json (offending queries named); a
# missing ledger fails — the trajectory is a committed artifact.
# Per-query pins bite on their own: BENCH_GATE_WATCH walls (default
# q7,q9) and the crit/host_lane_ms ceiling (default 120 ms — q12's
# folded portioned residue must not regrow)
python scripts/bench_history.py --gate
hrc=$?
if [ "$hrc" -ne 0 ]; then
    echo "bench trajectory gate FAILED (rc=$hrc)" >&2
    exit "$hrc"
fi

echo "== DQ two-worker smoke (scan→join→agg over hash-shuffle edges) =="
# two real OS worker processes; gates on result correctness AND the
# dq/* counters being non-zero on router + workers (a refactor that
# routes around the task runner fails loudly)
JAX_PLATFORMS=cpu python scripts/dq_smoke.py
drc=$?
if [ "$drc" -ne 0 ]; then
    echo "DQ smoke FAILED (rc=$drc)" >&2
    exit "$drc"
fi

echo "== materialized-views gate (differential fold, kill -9 mirror restart, zero fold recompiles, DROP frees) =="
# the continuous-query floor: a group-by view (NULLable string key,
# count/sum/min/max/avg) under seeded randomized insert/update/delete
# must read equal to a full recompute at the same watermark after every
# batch (incl. min/max-under-delete), survive kill -9 via the host
# mirror with ZERO counted rebuilds, resume folding with
# prog/compile_ms EXACTLY 0 (fold programs deserialize from the
# progstore), and DROP MATERIALIZED VIEW must unsubscribe the consumer
# and free state (view/registered back to 0, mirror + auto topic gone)
JAX_PLATFORMS=cpu python scripts/views_gate.py
vrc=$?
if [ "$vrc" -ne 0 ]; then
    echo "materialized-views gate FAILED (rc=$vrc)" >&2
    exit "$vrc"
fi

echo "== Hive chaos gate (3 workers, kill -9 mid-query, re-placement) =="
# the elastic-cluster floor: kill -9 one of three durable+mirrored
# workers while a query stream runs — every query must COMPLETE after
# Hive lease-expiry + shard re-placement (standby image replayed onto a
# survivor), hive/worker_dead and dq/retry_rerouted must be nonzero,
# and .sys/cluster_nodes must converge to 2 alive / 1 dead
JAX_PLATFORMS=cpu python scripts/chaos_gate.py
crc=$?
if [ "$crc" -ne 0 ]; then
    echo "Hive chaos gate FAILED (rc=$crc)" >&2
    exit "$crc"
fi

if [ "${CI_FULLSUITE:-0}" = "1" ]; then
    echo "== full-suite single-process gate (segfault pin, nightly) =="
    # review weakness #3 regression pin: the WHOLE suite (slow soaks
    # included) in ONE pytest process, green and segfault-free. Minutes
    # long — nightly only (CI_FULLSUITE=1).
    JAX_PLATFORMS=cpu python scripts/fullsuite_gate.py
    frc=$?
    if [ "$frc" -ne 0 ]; then
        echo "full-suite gate FAILED (rc=$frc)" >&2
        exit "$frc"
    fi
fi

echo "== CI green =="
