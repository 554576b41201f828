"""Benchmark: TPC-H on the device — Q1 headline + full 22-query suites,
plus TPC-DS and ClickBench legs.

Runs the full SQL path (parse → plan → pushdown → fused/tiled device
programs) over generated TPC-H data — the measured analog of the
reference's `ydb workload tpch run` (no published numbers exist in-repo;
see BASELINE.md). Suites at each scale factor in BENCH_SUITE_SFS
(default "1,10"): best-of-N per query, geomean reported; at SF ≤ 1 every
query is oracle-gated, above that a fast subset gates. All 22 TPC-H
queries run the fused path in the main pass (the historic q8/q10/q18
fallback class is retired by the bounds lattice); the
BENCH_FALLBACK_QUERIES escape hatch can portioned-rescue a NEW wedge
class, stamped `fallback: true`. The ClickBench leg
(BENCH_CLICKBENCH_ROWS, default 1M rows; 0 disables) runs all 43
queries over the generated hits table under the same watchdog /
blacklist / last_known_good machinery.

HANG-PROOF ORCHESTRATION: this platform's remote compile service can
wedge indefinitely on a cold shape. The parent process NEVER touches the
device; each suite runs in a child process that appends one JSON line
per finished query to a progress file. If the child makes no progress
for BENCH_QUERY_TIMEOUT seconds it is killed, the query it was stuck on
is blacklisted, and the child respawns to continue with the remaining
queries (completed results are kept). The persistent XLA compile cache
(`.jax_cache`) makes respawns cheap for everything already compiled.

Prints ONE JSON line to stdout:
  {"metric": "tpch_q1_rows_per_sec", "value": N, "unit": "rows/s",
   "vs_baseline": ratio, "suites": {"sf1": {...}, "sf10": {...}}}
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

SUITE_SFS = [float(s) for s in
             os.environ.get("BENCH_SUITE_SFS", "1,10").split(",") if s]
# TPC-DS leg (round-5 review: report a TPC-DS geomean): a representative
# query subset at this SF runs as the FINAL suite with its own budget
# share; "" disables
TPCDS_SF = os.environ.get("BENCH_TPCDS_SF", "1")
# bench subset: distinct machinery (star joins, windows+lag, CASE
# buckets, order-set semi-joins, channel unions, ranked CTEs), kept
# small so compile count stays inside the budget
TPCDS_BENCH = [q for q in os.environ.get(
    "BENCH_TPCDS_QUERIES",
    "ds3,ds7,ds27,ds42,ds43,ds52,ds55,ds62,ds67,ds70,ds89,ds94,ds96,"
    "ds97,ds98").split(",") if q]
# the whole bench MUST finish (and print its final JSON) inside the
# driver's kill window with margin — r4 budgeted 2400s+grace against a
# shorter driver window, got rc=124 and recorded NOTHING. The emergency
# deadline emits whatever completed.
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1450"))
EMERGENCY_S = float(os.environ.get("BENCH_EMERGENCY_S", "1620"))
QUERY_TIMEOUT = float(os.environ.get("BENCH_QUERY_TIMEOUT", "420"))
SUITE_REPEATS = int(os.environ.get("BENCH_SUITE_REPEATS", "2"))
# start-of-run platform-health probe: a tiny NOVEL-shape jit must finish
# inside this window or the platform is declared wedged (a stuck remote
# compile burns every suite's budget and reports 0/22 with no
# explanation — the bare 0/22 of the 2026-07-31 driver record)
PROBE_TIMEOUT_S = float(os.environ.get("BENCH_PROBE_TIMEOUT", "120"))
GATE_BIG = ("q1", "q6", "q12", "q14")
# capped-portioned fallback ESCAPE HATCH (default: none). The historic
# q8/q10/q18 class — fused compiles that wedged/crashed the remote
# service — is retired: the bounds lattice (`query/bounds.py`, PR 15)
# carries proven cardinality through those plans (carry-key sort
# reduction, eager-aggregated LEFT JOIN builds), so they run the fused
# path and time honestly in the main pass. The env lever remains for
# triaging a NEW wedge class without losing coverage.
FALLBACK_QUERIES = [q for q in os.environ.get(
    "BENCH_FALLBACK_QUERIES", "").split(",") if q]
# ClickBench leg: the 43-query suite (tests/clickbench_util.py) over a
# generated hits table at this row count — the UDF/LUT string engine's
# on-chip numbers. Pandas-oracle-gated up to CLICKBENCH_ORACLE_ROWS;
# 0 / "" disables the leg.
CLICKBENCH_ROWS = int(os.environ.get("BENCH_CLICKBENCH_ROWS",
                                     "1000000") or 0)
CLICKBENCH_ORACLE_ROWS = int(os.environ.get("BENCH_CLICKBENCH_ORACLE_ROWS",
                                            "5000000"))
CLICKBENCH_TOTAL = 43

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def geomean(xs):
    xs = [x for x in xs if x and x > 0]
    return math.exp(sum(math.log(x) for x in xs) / len(xs)) if xs else 0.0


# ---------------------------------------------------------------------------
# child: runs ONE suite, appending a JSON line per query to the progress
# file; the parent watches mtime and kills on stall
# ---------------------------------------------------------------------------


def child_main(sf: float, progress_path: str, skip: list,
               budget_s: float, workload: str = "tpch",
               fallback: list = ()) -> None:
    import shutil

    from ydb_tpu.query import QueryEngine
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if workload == "tpcds":
        from tests.tpcds_util import QUERIES as ALL_Q, oracle
        from tests.tpch_util import assert_frames_match
        QUERIES = {k: ALL_Q[k] for k in TPCDS_BENCH if k in ALL_Q}
        fact_table, loader = "store_sales", "tpcds"
    elif workload == "clickbench":
        from tests.clickbench_util import QUERIES, oracle
        from tests.tpch_util import assert_frames_match
        fact_table, loader = "hits", "clickbench"
    else:
        from tests.tpch_util import QUERIES, assert_frames_match, oracle
        fact_table, loader = "lineitem", "tpch"

    def emit(rec: dict) -> None:
        with open(progress_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    t0 = time.perf_counter()
    # durable store per (workload, sf): the FIRST child generates + loads
    # + persists; a respawn after a wedge boots from disk (WAL/manifest
    # replay) instead of paying generation + dictionary encode again
    # (~4 min at SF10 — in r4 that alone could eat a respawn's budget)
    store = f"/tmp/bench_store_{loader}_sf{sf:g}" if loader != "tpch" \
        else f"/tmp/bench_store_sf{sf:g}"
    marker = os.path.join(store, ".loaded")
    data = None                       # raw tables — lazily regenerated
    #                                   for oracles on store boots
    if os.path.exists(marker):
        try:
            eng = QueryEngine(block_rows=1 << 20, data_dir=store)
            eng.catalog.table(fact_table)
        except Exception:             # noqa: BLE001 — torn store: reload
            shutil.rmtree(store, ignore_errors=True)
            eng = None
    else:
        shutil.rmtree(store, ignore_errors=True)
        eng = None
    if eng is None:
        eng = QueryEngine(block_rows=1 << 20, data_dir=store)
        if loader == "tpcds":
            from ydb_tpu.bench.tpcds_gen import load_tpcds
            data = load_tpcds(eng.catalog, sf=sf)
        elif loader == "clickbench":
            from ydb_tpu.bench.clickbench_gen import load_hits
            data = load_hits(eng.catalog, n_rows=int(sf))
        else:
            from ydb_tpu.bench.tpch_gen import load_tpch
            data = load_tpch(eng.catalog, sf=sf)
        with open(marker, "w") as f:
            f.write("ok")
    n_rows = eng.catalog.table(fact_table).num_rows
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.prewarm()
    emit({"kind": "meta", "lineitem_rows": int(n_rows),
          "load_s": round(load_s, 1),
          "prewarm_s": round(time.perf_counter() - t0, 1)})

    def oracle_data():
        nonlocal data
        if data is None:
            if loader == "tpcds":
                from ydb_tpu.bench.tpcds_gen import gen_tpcds
                data = gen_tpcds(sf)
            elif loader == "clickbench":
                from ydb_tpu.bench.clickbench_gen import gen_hits
                data = gen_hits(int(sf))   # deterministic: same seed
            else:
                from ydb_tpu.bench.tpch_gen import TpchData
                data = TpchData(sf)  # deterministic: same seed
        return data

    deadline = _T0 + budget_s        # the parent passes REMAINING budget

    def gated(name: str) -> bool:
        if workload == "clickbench":
            return int(sf) <= CLICKBENCH_ORACLE_ROWS
        return sf <= 1 or name in GATE_BIG

    done_ok: set = set()             # timed THIS run (fused or fallback)
    oracle_failed: set = set()       # ran but WRONG — never fallback-rescue

    def run_one(name: str, repeats: int, extra: dict) -> None:
        sql = QUERIES[name]
        try:
            t0 = time.perf_counter()
            got = eng.query(sql)                 # compile + first run
            times = [time.perf_counter() - t0]
            # first-run phase breakdown carries the compile cost;
            # steady-state phases come from the last repeat below
            ph_first = dict(getattr(eng.last_stats, "phases", {}) or {})
            for _ in range(repeats):
                t0 = time.perf_counter()
                got = eng.query(sql)
                times.append(time.perf_counter() - t0)
            best = min(times)
            phases = dict(getattr(eng.last_stats, "phases", {}) or {})
            # repeats=0 (the capped fallback legs): the only run taken
            # IS the first run, so its phases carry compile time
            first_only = repeats == 0
            rec = {"kind": "result", "query": name,
                   "ms": round(best * 1000, 1),
                   "path": eng.executor.last_path, **extra}
            if phases:
                # per-phase attribution (compile/upload/dispatch/device/
                # readout) so a regressed round is blamed on a PHASE,
                # not a bare wall number
                rec["phases"] = {k: round(v, 1)
                                 for k, v in phases.items()}
                if first_only:
                    # these are FIRST-run (compile-bearing) numbers —
                    # tag them so the steady-state aggregate excludes
                    # them instead of misattributing compile to a phase
                    rec["phases_include_compile"] = True
            if ph_first.get("compile_ms"):
                rec["compile_ms_first"] = round(
                    ph_first["compile_ms"], 1)
            # resource-ledger stamps (utils/memledger.py): the bytes
            # companion of the phase attribution — per-query peak HBM,
            # padding efficiency, and host-transfer traffic
            mem = dict(getattr(eng.last_stats, "memory", {}) or {})
            if mem.get("peak_bytes") or mem.get("transfers"):
                rec["peak_device_bytes"] = int(mem.get("peak_bytes", 0))
                if mem.get("pad_efficiency") is not None:
                    rec["pad_efficiency"] = mem["pad_efficiency"]
                rec["host_transfer_bytes"] = int(
                    mem.get("transfer_bytes", 0))
                if mem.get("est_error_pct") is not None:
                    rec["admission_est_error_pct"] = mem["est_error_pct"]
            # critical-path stamp (utils/critpath.py): the blocking-
            # chain class shares of the steady-state run — the raw rows
            # of the artifact's ranked `speed_gap` section
            cp = dict(getattr(eng.last_stats, "critical_path", {}) or {})
            if cp.get("classes"):
                rec["critical_path"] = {
                    "classes": cp["classes"], "pct": cp.get("pct", {}),
                    "wall_ms": cp.get("wall_ms", 0.0),
                    "coverage": cp.get("coverage", 0.0),
                    "non_device_ms": cp.get("non_device_ms", 0.0),
                    "dominant_span": cp.get("dominant_span", ""),
                    "dominant_class": cp.get("dominant_class", ""),
                }
            # compiled-program roofline stamp (utils/progstats.py): the
            # dominant program's utilization + bound-class verdict, so
            # device-dominated queries get a diagnosis instead of
            # silence in the speed-gap ledger
            pg = dict(getattr(eng.last_stats, "programs", {}) or {})
            if pg.get("programs"):
                dom = pg["programs"][0]
                rec["programs"] = {
                    "n": pg.get("n", 0),
                    "device_ms": pg.get("device_ms", 0.0),
                    "utilization_pct": dom.get("utilization_pct"),
                    "bound_class": dom.get("bound_class", ""),
                    "flops": dom.get("flops"),
                    "bytes_accessed": dom.get("bytes_accessed"),
                }
            # per-query Perfetto timeline (`bench.py --trace-dir DIR`):
            # one Chrome trace-event file per profiled query
            tdir = os.environ.get("BENCH_TRACE_DIR")
            if tdir and getattr(eng, "profiles", None):
                try:
                    from ydb_tpu.utils import chrometrace
                    os.makedirs(tdir, exist_ok=True)
                    with open(os.path.join(
                            tdir, f"{name}.trace.json"), "w") as tf:
                        json.dump(chrometrace.render(eng.profiles[-1]),
                                  tf)
                    rec["trace_file"] = f"{name}.trace.json"
                except Exception as te:      # noqa: BLE001 — export
                    rec["trace_error"] = f"{type(te).__name__}: {te}"
            if gated(name):
                d = oracle_data()    # lazy gen OUTSIDE the timed window
                t0 = time.perf_counter()
                want = oracle(name, d)
                cpu_t = time.perf_counter() - t0
                want.columns = list(got.columns)
                assert_frames_match(got, want, ordered=True,
                                    rtol=1e-6 if sf > 1 else 1e-9)
                rec["oracle"] = "ok"
                rec["vs_pandas"] = round(cpu_t / best, 1)
            done_ok.add(name)
            emit(rec)
        except Exception as e:                   # noqa: BLE001
            if isinstance(e, AssertionError):
                oracle_failed.add(name)
            emit({"kind": "result", "query": name, "ms": None,
                  **extra,
                  "error": f"{type(e).__name__}: {str(e)[:160]}"})

    for name in QUERIES:
        if name in skip:
            continue
        if time.perf_counter() > deadline:
            emit({"kind": "skip", "query": name, "reason": "budget"})
            continue
        emit({"kind": "start", "query": name})
        run_one(name, SUITE_REPEATS, {})

    # capped portioned fallback: queries the fused path cannot compile on
    # this platform (the parent lists candidates — blacklisted/untimed
    # only, `.bench_hung.json`-respecting via the `+fallback` key) get
    # ONE timed run with whole-query fusion off, stamped `fallback: true`
    # — 22/22 coverage with the cheat visible in the artifact
    for name in fallback:
        if name not in QUERIES or name in done_ok:
            continue
        if name in oracle_failed:
            # the fused leg RAN and produced wrong rows: that is a
            # correctness bug to report, not a coverage hole to paper
            # over with a passing portioned number
            continue                 # fused already timed it this run
        if time.perf_counter() > deadline:
            emit({"kind": "skip", "query": name, "reason": "budget"})
            continue
        emit({"kind": "start", "query": f"{name}+fallback"})
        eng.executor.enable_fused = False
        try:
            run_one(name, 0, {"fallback": True})
        finally:
            eng.executor.enable_fused = True
    emit({"kind": "done"})


# ---------------------------------------------------------------------------
# parent: orchestration only (no jax import — the device belongs to the
# child; a chip belongs to one process at a time)
# ---------------------------------------------------------------------------


_HUNG_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".bench_hung.json")
_LAST_GOOD_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               ".bench_last_good.json")


def _load_last_good() -> dict:
    try:
        with open(_LAST_GOOD_PATH) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def _save_last_good(suites: dict) -> None:
    """Persist the most recent GOOD per-query numbers per suite — a
    later wedged run still reports them under `last_known_good`. Good =
    successfully timed + oracle-clean (failed/hung queries never land
    here) AND not geomean-regressed beyond the gate threshold: a run
    >25% slower must NOT overwrite the comparison base, or the
    trajectory gate (`scripts/bench_history.py --gate`) would always
    compare the newest ledger entry against itself and never fire."""
    good = _load_last_good()
    threshold = float(os.environ.get("BENCH_GATE_REGRESSION", "1.25"))
    for key, out in suites.items():
        if not out.get("per_query_ms"):
            continue
        prev = good.get(key, {})
        prev_geo = float(prev.get("geomean_ms") or 0)
        new_geo = float(out.get("geomean_ms") or 0)
        if prev_geo and new_geo > threshold * prev_geo:
            log(f"last-good NOT updated for {key}: geomean "
                f"{new_geo:.1f}ms > {threshold}x previous "
                f"{prev_geo:.1f}ms — the gate will flag this run")
            continue
        merged = dict(prev.get("per_query_ms", {}))
        merged.update(out["per_query_ms"])
        good[key] = {
            "per_query_ms": merged,
            "geomean_ms": out.get("geomean_ms"),
            "coverage": out.get("coverage"),
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
    try:
        with open(_LAST_GOOD_PATH, "w") as f:
            json.dump(good, f)
    except OSError:
        pass


def probe_main() -> None:
    """Child (`bench.py --probe`): jit ONE tiny program with a novel
    shape — prime-offset dims keyed on the pid, so the persistent compile
    cache cannot satisfy it — and print a marker. A healthy platform
    finishes in seconds; a wedged compile service hangs here instead of
    eating a whole suite's watchdog budget."""
    import jax
    import jax.numpy as jnp
    n = 1009 + (os.getpid() % 97) * 2
    x = jnp.arange(n, dtype=jnp.float32)
    y = jax.jit(lambda a: (a * 3.0 + 1.0).sum())(x)
    got = float(y)
    want = float(3.0 * (n - 1) * n / 2 + n)
    assert abs(got - want) < 1e-3 * max(1.0, want), (got, want)
    print("probe-ok", n, flush=True)


def platform_probe() -> bool:
    """Run the probe child under its watchdog. True = healthy."""
    cmd = [sys.executable, os.path.abspath(__file__), "--probe"]
    try:
        p = subprocess.run(cmd, timeout=PROBE_TIMEOUT_S,
                           capture_output=True)
    except subprocess.TimeoutExpired:
        log(f"platform probe HUNG past {PROBE_TIMEOUT_S:.0f}s — wedged")
        return False
    if p.returncode != 0:
        log(f"platform probe FAILED rc={p.returncode}: "
            f"{p.stderr.decode(errors='replace')[-300:]}")
        return False
    return b"probe-ok" in p.stdout


def _load_hung() -> dict:
    try:
        with open(_HUNG_PATH) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def _save_hung(d: dict) -> None:
    try:
        with open(_HUNG_PATH, "w") as f:
            json.dump(d, f)
    except OSError:
        pass


def run_suite(sf: float, suite_deadline: float,
              workload: str = "tpch") -> dict:
    """Run one suite; `suite_deadline` is an absolute perf_counter value
    this suite must not outlive (the per-suite budget split keeps SF10
    from starving behind SF1 — r4 recorded no SF10 at all)."""
    progress = f"/tmp/bench_suite_{workload}_sf{sf:g}_{os.getpid()}.jsonl"
    if os.path.exists(progress):
        os.unlink(progress)
    # queries whose COMPILE hung a previous run (a stuck remote compile
    # burns a full watchdog window): pre-skip, they re-enter the pool
    # only when the hung file is deleted
    hung_key = f"sf{sf:g}" if workload == "tpch" \
        else f"clickbench-r{int(sf)}" if workload == "clickbench" \
        else f"{workload}-sf{sf:g}"
    known_hung = _load_hung().get(hung_key, [])
    skip: list = list(known_hung)
    if known_hung:
        log(f"sf={sf:g}: pre-skipping previously hung: {known_hung}")
    results: dict = {}
    meta: dict = {}
    skipped_budget: list = []
    hung: list = list(known_hung)

    while True:
        if time.perf_counter() > suite_deadline:
            break
        remaining = max(suite_deadline - time.perf_counter(), 60)
        # portioned-fallback candidates: FALLBACK_QUERIES not yet TIMED
        # (an errored fused attempt leaves ms=None in results — still a
        # candidate after a respawn), excluding oracle MISMATCHES (wrong
        # rows is a bug to report, not a hole to rescue) and fallback
        # attempts already blacklisted (`+fallback` in .bench_hung.json)
        fb = [q for q in FALLBACK_QUERIES
              if workload == "tpch" and not results.get(q, {}).get("ms")
              and "AssertionError" not in (results.get(q, {}).get("error")
                                           or "")
              and f"{q}+fallback" not in skip]
        # completed queries are skipped too: a respawn must CONTINUE, not
        # redo minutes of timed runs + oracles per already-done query
        cmd = [sys.executable, os.path.abspath(__file__), "--suite-child",
               str(sf), progress, ",".join(skip + sorted(results)),
               str(remaining), workload, ",".join(fb)]
        child = subprocess.Popen(cmd)
        pos = 0
        current = None
        last_progress = time.monotonic()
        done = False
        while child.poll() is None:
            time.sleep(2)
            try:
                with open(progress) as f:
                    f.seek(pos)
                    new = f.read()
                    # consume only whole lines: a partially flushed
                    # record must not crash the parser
                    cut = new.rfind("\n") + 1
                    new = new[:cut]
                    pos += len(new)
            except FileNotFoundError:
                new = ""
            for line in new.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                last_progress = time.monotonic()
                if rec["kind"] == "meta":
                    meta = rec
                elif rec["kind"] == "start":
                    current = rec["query"]
                elif rec["kind"] == "result":
                    results[rec["query"]] = rec
                    current = None
                    log(f"sf={sf:g} {rec['query']}: "
                        + (f"{rec['ms']}ms [{rec.get('path', '')}]"
                           + (" FALLBACK" if rec.get("fallback") else "")
                           + (f" oracle ok, {rec['vs_pandas']}x"
                              if "vs_pandas" in rec else "")
                           if rec["ms"] is not None
                           else f"FAILED {rec.get('error', '')}"))
                elif rec["kind"] == "skip":
                    skipped_budget.append(rec["query"])
                elif rec["kind"] == "done":
                    done = True
            # the suite deadline is a REAL ceiling: a running child is
            # killed once it (+ a short grace for the in-flight query)
            # is gone
            if time.perf_counter() > suite_deadline + 60:
                log(f"sf={sf:g}: suite deadline exceeded — killing child")
                child.kill()
                child.wait()
                done = True
                break
            # stall watchdog: the load+prewarm phase gets one timeout
            # window too (current is None then — generous stall window)
            window = QUERY_TIMEOUT if current else max(QUERY_TIMEOUT, 900)
            if time.monotonic() - last_progress > window:
                log(f"sf={sf:g}: no progress for {window:.0f}s"
                    + (f" (stuck on {current})" if current else "")
                    + " — killing child")
                child.kill()
                child.wait()
                if current is not None:
                    hung.append(current)
                    skip.append(current)
                    d = _load_hung()
                    d.setdefault(hung_key, [])
                    if current not in d[hung_key]:
                        d[hung_key].append(current)
                        _save_hung(d)
                    current = None
                else:
                    done = True      # stuck outside a query: give up
                break
        else:
            # child exited by itself; read any tail lines (mirror the
            # polling loop's record handling — 'start' must update
            # `current` and 'result' must clear it, or crash handling
            # would blame the wrong query)
            try:
                with open(progress) as f:
                    f.seek(pos)
                    for line in f.read().splitlines():
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if rec["kind"] == "result":
                            results[rec["query"]] = rec
                            current = None
                        elif rec["kind"] == "start":
                            current = rec["query"]
                        elif rec["kind"] == "meta":
                            meta = rec
                        elif rec["kind"] == "skip":
                            skipped_budget.append(rec["query"])
                        elif rec["kind"] == "done":
                            done = True
            except FileNotFoundError:
                pass
            if not done and child.returncode != 0:
                # crashed mid-query: blacklist the in-flight one
                if current is not None:
                    hung.append(current)
                    skip.append(current)
                else:
                    done = True
        if done:
            break

    ok = {q: r["ms"] for q, r in results.items() if r.get("ms")}
    ratios = {q: r["vs_pandas"] for q, r in results.items()
              if "vs_pandas" in r}
    total = (22 if workload == "tpch"
             else CLICKBENCH_TOTAL if workload == "clickbench"
             else len(TPCDS_BENCH))
    # a query later rescued by the portioned fallback leaves the
    # not-timed (penalized) set — coverage counts its honest number.
    # Watchdog entries for a hung FALLBACK attempt carry the 'qN+fallback'
    # pseudo-name (the .bench_hung.json key); fold them back to the base
    # query so qN isn't penalized twice and no name outside the suite's
    # query universe leaks into the artifact's hung/not_timed lists
    hung = sorted({q.split("+", 1)[0] for q in hung})
    not_timed = sorted((set(hung)
                        | {q for q, r in results.items() if not r.get("ms")}
                        | set(skipped_budget)) - set(ok))
    # honest aggregate (round-4 review): hung/failed/skipped queries count at
    # the watchdog-timeout penalty, so the blacklist cannot silently
    # flatter the geomean; `geomean_ms` over completed is still reported
    # next to explicit completed/total
    penalized = list(ok.values()) + [QUERY_TIMEOUT * 1000.0] * len(not_timed)
    return {
        "sf": sf,
        "lineitem_rows": meta.get("lineitem_rows"),
        "load_s": meta.get("load_s"),
        "completed": len(ok),
        "total": total,
        "coverage": f"{len(ok)}/{total}",
        "failed": sorted(q for q, r in results.items() if not r.get("ms")),
        "hung": hung,
        "skipped_for_budget": sorted(set(skipped_budget) - set(ok)),
        "not_timed": not_timed,
        "geomean_ms": round(geomean(list(ok.values())), 1),
        "geomean_penalized_ms": round(geomean(penalized), 1),
        "penalty_ms": QUERY_TIMEOUT * 1000.0,
        "fallbacks": sorted(q for q, r in results.items()
                            if r.get("fallback")),
        "per_query_ms": ok,
        "paths": {q: r.get("path", "") for q, r in results.items()},
        "oracle_checked": sorted(ratios),
        "vs_pandas": ratios,
        "vs_pandas_geomean": round(geomean(list(ratios.values())), 1)
        if ratios else None,
        # device-timeline attribution (the round-10 profiling floor):
        # steady-state per-phase ms per query + per-phase geomean, so a
        # regressed round is blamed on compile/upload/dispatch/device/
        # readout instead of a bare wall number
        "per_query_phases": {q: r["phases"] for q, r in results.items()
                             if r.get("phases")},
        # steady-state aggregate only: rows tagged phases_include_compile
        # (repeats=0 fallback legs) would fold compile into a phase
        "phase_geomean_ms": _phase_geomean(
            [r["phases"] for r in results.values()
             if r.get("phases") and not r.get("phases_include_compile")]),
        "compile_ms_first": {q: r["compile_ms_first"]
                             for q, r in results.items()
                             if r.get("compile_ms_first")},
        # the resource-ledger round-13 floor: measured peak HBM, padding
        # efficiency, host-transfer bytes and admission-estimate error
        # per query — the byte gauges ROADMAP items 1 and 2 gate on
        "per_query_memory": {
            q: {k: r[k] for k in ("peak_device_bytes", "pad_efficiency",
                                  "host_transfer_bytes",
                                  "admission_est_error_pct") if k in r}
            for q, r in results.items()
            if r.get("peak_device_bytes") is not None},
        # the SPEED-GAP LEDGER (round-14): every query ranked by the
        # critical-path milliseconds NOT spent executing on device,
        # dominant span named — the machine-generated worklist for
        # ROADMAP items 1–2 (where the 10× actually lives). Round-15:
        # rows carry the dominant program's roofline utilization +
        # bound-class, so device-dominated queries get a verdict too
        "speed_gap": _speed_gap(results),
        # the program-roofline floor (utils/progstats.py): per-query
        # dominant-program verdicts + the suite utilization geomean
        "per_query_programs": {q: r["programs"]
                               for q, r in results.items()
                               if r.get("programs")},
        "utilization_geomean": (lambda us: round(geomean(us), 2)
                                if us else None)(
            [r["programs"]["utilization_pct"]
             for r in results.values()
             if r.get("programs")
             and r["programs"].get("utilization_pct")]),
    }


def _speed_gap(results: dict) -> list:
    """Rank queries by non-device critical-path ms (descending), each
    with its dominant blocking span and per-class share of wall — plus
    the dominant compiled program's roofline utilization + bound-class
    (utils/progstats.py), so a device-dominated query carries a verdict
    (2% of peak, memory_bound) instead of falling off the worklist."""
    rows = []
    for q, r in results.items():
        cp = r.get("critical_path")
        if not cp:
            continue
        pg = r.get("programs") or {}
        rows.append({
            "query": q,
            "non_device_ms": round(cp.get("non_device_ms", 0.0), 1),
            "wall_ms": round(cp.get("wall_ms", 0.0), 1),
            "dominant_span": cp.get("dominant_span", ""),
            "dominant_class": cp.get("dominant_class", ""),
            "class_pct": {k: v for k, v in (cp.get("pct") or {}).items()},
            "utilization_pct": pg.get("utilization_pct"),
            "bound_class": pg.get("bound_class", ""),
        })
    return sorted(rows, key=lambda r: -r["non_device_ms"])


def _phase_geomean(phase_dicts: list) -> dict:
    """Per-phase geomean across the suite's queries (zeros skipped: a
    phase a query never entered must not zero the aggregate)."""
    out = {}
    for key in ("compile_ms", "build_ms", "upload_ms", "dispatch_ms",
                "device_ms", "readout_ms"):
        vals = [d[key] for d in phase_dicts if d.get(key)]
        if vals:
            out[key] = round(geomean(vals), 2)
    return out


_WEDGED = {"v": False}


def _append_history(suites: dict) -> None:
    """Append one bench-trajectory ledger line (BENCH_HISTORY.jsonl —
    git sha, per-suite geomeans/walls/coverage, storm + multichip
    summaries, utilization geomean) via scripts/bench_history.py; never
    allowed to fail the run."""
    if not suites:
        return
    try:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "bench_history.py")
        spec = importlib.util.spec_from_file_location("bench_history",
                                                      path)
        bh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bh)
        bh.append_run(suites)
        log(f"bench history: appended to {bh.HISTORY_PATH}")
    except Exception as e:               # noqa: BLE001 — ledger only
        log(f"bench history append failed: {type(e).__name__}: {e}")


def _emit(suites: dict) -> None:
    """The artifact ALWAYS parses: real numbers when the platform
    cooperated, else `platform_wedged: true` plus the `last_known_good`
    per-query numbers — never again a bare 0/22 with no explanation."""
    sf1 = suites.get("sf1", {})
    q1_ms = sf1.get("per_query_ms", {}).get("q1")
    rows = sf1.get("lineitem_rows") or 0
    value = rows / (q1_ms / 1000) if q1_ms else 0.0
    ratio = sf1.get("vs_pandas", {}).get("q1", 0.0)
    print(json.dumps({
        "metric": "tpch_q1_rows_per_sec",
        "value": round(value, 1),
        "unit": "rows/s",
        "vs_baseline": ratio,
        "platform_wedged": _WEDGED["v"],
        "last_known_good": _load_last_good(),
        "suites": suites,
    }), flush=True)


def concurrency_main(n: int, rows: int = 150_000) -> int:
    """Pipelined-dispatch smoke (`bench.py --concurrency N`): N warm
    single-shot SELECTs through ONE engine, serial then concurrent.
    With the dispatch/readout pipeline (`engine._dispatch_and_drain`)
    the concurrent wall clock must beat the serial sum and the
    `pipeline/overlap_hits` counter must show genuine overlap — a
    regression in either fails loudly (scripts/ci.sh gates on the exit
    code). Runs fine under JAX_PLATFORMS=cpu; on the real chip the same
    harness shows the 35 ms → ~10 ms overlapped-dispatch pipelining."""
    import threading

    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 17)
    eng.execute("create table ct (id Int64 not null, k Int64 not null, "
                "v Double not null, primary key (id)) "
                "with (store = column)")
    import numpy as np
    import pandas as pd
    ids = np.arange(rows, dtype=np.int64)
    df = pd.DataFrame({"id": ids, "k": ids % 31, "v": ids * 0.25})
    t = eng.catalog.table("ct")
    t.bulk_upsert(df, eng._next_version())
    t.indexate()
    sql = "select k, sum(v) as s, count(*) as c from ct group by k"
    want = eng.query(sql)                  # compile + plan-cache warm-up
    assert len(want) == 31

    t0 = time.perf_counter()
    for _ in range(n):
        eng.query(sql)
    serial_s = time.perf_counter() - t0

    errs: list = []
    barrier = threading.Barrier(n)

    def one():
        try:
            barrier.wait()
            got = eng.query(sql)
            assert len(got) == 31
        except Exception as e:             # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=one) for _ in range(n)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    concurrent_s = time.perf_counter() - t0

    c = eng.counters()
    speedup = serial_s / concurrent_s if concurrent_s else 0.0
    out = {
        "metric": "concurrent_select_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "concurrency": n,
        "rows": rows,
        "serial_s": round(serial_s, 3),
        "concurrent_s": round(concurrent_s, 3),
        "overlap_hits": c.get("pipeline/overlap_hits", 0),
        "dispatched": c.get("pipeline/dispatched", 0),
        "readout_ms_total": round(c.get("pipeline/readout_ms", 0.0), 1),
        "pipeline_window": c.get("pipeline/window"),
        "errors": [f"{type(e).__name__}: {e}" for e in errs],
    }
    print(json.dumps(out), flush=True)
    # overlap_hits > 0 is the deterministic regression gate (a
    # re-serialized dispatch path never overlaps); the wall-clock floor
    # defaults BELOW 1.0 because a loaded small runner can measure
    # ~parity with no regression — raise BENCH_MIN_SPEEDUP on quiet
    # dedicated hardware for a sharper gate
    min_speedup = float(os.environ.get("BENCH_MIN_SPEEDUP", "0.9"))
    ok = (not errs and out["overlap_hits"] > 0
          and speedup > min_speedup)
    if not ok:
        log(f"concurrency smoke FAILED: speedup {speedup:.2f}x "
            f"(need > {min_speedup}), overlap_hits {out['overlap_hits']}, "
            f"errors {out['errors']}")
    return 0 if ok else 1


def storm_main(n: int, rows: int = 8192) -> int:
    """Point-lookup storm (`bench.py --storm N`): N literal-varying
    point lookups — N DISTINCT SQL texts, `... where id = X limit 1` —
    through one engine per lane, measured steady-state (texts warmed so
    the plan cache serves the measured rounds — the millions-of-clients
    traffic shape):

      * lane OFF (`YDB_TPU_BATCH_WINDOW=0`): the PR-1 pipelined
        baseline — per-query dispatch + readout, overlapped;
      * lane ON: same storm coalesced into stacked executions.

    Emits ONE JSON line: compile counts (the param-lifting pin: the
    whole literal-varying storm costs exactly 1 fused executable on the
    baseline engine), batch/* counters, best-of-round wall clocks, the
    wall speedup, the DISPATCH AMORTIZATION (mean queries per stacked
    device execution — the deterministic form of the throughput win:
    every per-query dispatch+readout is a fixed round trip (its cost is
    not measured on the current chip), so wall throughput should track
    this ratio there, while a 2-core CPU runner's wall clock is floored
    by thread/GIL overhead either way), and a byte-equality verdict between the lanes.
    `scripts/batch_gate.py` asserts on these fields. rc 0 = storm ran,
    results byte-equal, 1 compile, real coalescing; the thresholds are
    the gate's job."""
    import threading

    import numpy as np
    import pandas as pd

    window_ms = os.environ.get("BENCH_BATCH_WINDOW_MS", "500")
    rounds = max(1, int(os.environ.get("BENCH_STORM_ROUNDS", "3")))
    n_batch = min(n, int(os.environ.get("YDB_TPU_BATCH_MAX", "64") or 64))

    def mk_engine(window: str):
        os.environ["YDB_TPU_BATCH_WINDOW"] = window
        os.environ["YDB_TPU_BATCH_MAX"] = str(n_batch)
        from ydb_tpu.query import QueryEngine
        eng = QueryEngine(block_rows=1 << 17)
        eng.execute("create table st (id Int64 not null, k Int64 not null,"
                    " v Double not null, primary key (id)) "
                    "with (store = column)")
        ids = np.arange(rows, dtype=np.int64)
        df = pd.DataFrame({"id": ids, "k": ids % 97, "v": ids * 0.25})
        t = eng.catalog.table("st")
        t.bulk_upsert(df, eng._next_version())
        t.indexate()
        eng.prewarm()
        return eng

    texts = [f"select k, v from st where id = {(37 + i * 101) % rows} "
             "limit 1" for i in range(n)]

    def warm(eng):
        for q in texts:
            eng.query(q)

    def run_threaded(eng):
        errs: list = []
        results: dict = {}
        barrier = threading.Barrier(n)

        def one(i, sql):
            try:
                barrier.wait()
                results[i] = eng.query(sql)
            except Exception as e:         # noqa: BLE001
                errs.append(f"{type(e).__name__}: {e}")
        threads = [threading.Thread(target=one, args=(i, q))
                   for i, q in enumerate(texts)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return time.perf_counter() - t0, results, errs

    # lane OFF: the pipelined per-query baseline (best of N rounds — a
    # 64-thread storm on a small shared runner is scheduling-noisy)
    base = mk_engine("0")
    fused0 = len(base.executor._fused_cache)
    warm(base)
    storm_compiles = len(base.executor._fused_cache) - fused0
    base_s, base_res, base_errs = run_threaded(base)
    for _ in range(rounds - 1):
        s2, r2, e2 = run_threaded(base)
        if not e2 and s2 < base_s:
            base_s, base_res = s2, r2
        base_errs += e2

    # lane ON: batched dispatch (same best-of-N; the first round also
    # warms the stacked-bucket executable)
    eng = mk_engine(window_ms)
    warm(eng)                              # plan cache + per-query program
    _w_s, _w_res, w_errs = run_threaded(eng)   # warms the batched bucket
    batch_s, batch_res, batch_errs = run_threaded(eng)
    for _ in range(rounds - 1):
        s2, r2, e2 = run_threaded(eng)
        if not e2 and s2 < batch_s:
            batch_s, batch_res = s2, r2
        batch_errs += e2
    c = eng.counters()

    equal = not base_errs and not batch_errs and not w_errs
    for i in range(n):
        if not equal:
            break
        a, b = base_res.get(i), batch_res.get(i)
        if a is None or b is None or list(a.columns) != list(b.columns) \
                or not all(np.array_equal(a[col].to_numpy(),
                                          b[col].to_numpy())
                           for col in a.columns):
            equal = False
    speedup = base_s / batch_s if batch_s else 0.0
    batches = c.get("batch/batches", 0)
    coalesced = c.get("batch/coalesced_queries", 0)
    # queries per stacked device execution: the per-query
    # dispatch+readout round trips the lane eliminated
    amortization = (coalesced / batches) if batches else 0.0
    out = {
        "metric": "storm_batched_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "storm_n": n,
        "rows": rows,
        "window_ms": float(window_ms),
        "rounds": rounds,
        "storm_compiles": storm_compiles,
        "baseline_s": round(base_s, 4),
        "batched_s": round(batch_s, 4),
        "qps_baseline": round(n / base_s, 1) if base_s else 0.0,
        "qps_batched": round(n / batch_s, 1) if batch_s else 0.0,
        "dispatch_amortization": round(amortization, 1),
        "byte_equal": equal,
        "batches": batches,
        "coalesced_queries": coalesced,
        "batch_max_size": c.get("batch/max_size", 0),
        "batch_fallbacks": c.get("batch/fallbacks", 0),
        "batch_trace_errors": c.get("batch/trace_errors", 0),
        "lift_hits": c.get("batch/lift_hits", 0),
        "errors": (base_errs + w_errs + batch_errs)[:5],
    }
    print(json.dumps(out), flush=True)
    ok = equal and storm_compiles == 1 and coalesced >= 2
    if not ok:
        log(f"storm FAILED: byte_equal={equal} "
            f"compiles={storm_compiles} coalesced={coalesced} "
            f"errors={out['errors']}")
    return 0 if ok else 1


def _cold_start_child(phase: str, n: int, rows: int) -> int:
    """One cold-start phase in a FRESH process (restarts are process
    deaths, not in-process cache clears): build the storm table, run the
    N-literal point-lookup storm, print per-query latency percentiles +
    store counters as one JSON line. `phase` only controls whether the
    first pass is warmed untimed (`warm`) or timed from the very first
    dispatch (`cold_store` / `cold_none`)."""
    import hashlib

    import jax
    # the parent pins JAX_PLATFORMS=cpu for deterministic, comparable
    # phases, but the env var alone loses to a TPU plugin — force it
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import pandas as pd

    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils.metrics import GLOBAL

    eng = QueryEngine(block_rows=1 << 17)
    eng.execute("create table st (id Int64 not null, k Int64 not null,"
                " v Double not null, primary key (id)) "
                "with (store = column)")
    ids = np.arange(rows, dtype=np.int64)
    df = pd.DataFrame({"id": ids, "k": ids % 97, "v": ids * 0.25})
    t = eng.catalog.table("st")
    t.bulk_upsert(df, eng._next_version())
    t.indexate()
    eng.prewarm()
    texts = [f"select k, v from st where id = {(37 + i * 101) % rows} "
             "limit 1" for i in range(n)]

    if phase == "warm":
        for q in texts:                     # untimed: compile + store write
            eng.query(q)
    # 3 timed passes -> 3N samples: the single first-dispatch
    # deserialize (or compile) is 1/3N < 1% of the storm, so p99
    # measures the restart's serving tail, not the one-off load — while
    # max_ms/first_query_ms keep the one-off visible
    lat: list = []
    results: list = []
    first_ms = None
    for p in range(3):
        for q in texts:
            t0 = time.perf_counter()
            r = eng.query(q)
            ms = (time.perf_counter() - t0) * 1e3
            lat.append(ms)
            if first_ms is None:
                first_ms = ms
            if p == 0:
                results.append(r)
    dig = hashlib.blake2s(
        "".join(r.to_csv(index=False) for r in results).encode(),
        digest_size=16).hexdigest()
    arr = np.asarray(lat)
    out = {
        "phase": phase,
        "digest": dig,
        "p50_ms": round(float(np.percentile(arr, 50)), 2),
        "p99_ms": round(float(np.percentile(arr, 99)), 2),
        "max_ms": round(float(arr.max()), 2),
        "first_query_ms": round(first_ms, 2),
        "samples": len(lat),
        "compile_ms": GLOBAL.get("prog/compile_ms"),
        "store_writes": GLOBAL.get("prog/store_writes"),
        "store_hits": GLOBAL.get("prog/store_hits"),
        "store_misses": GLOBAL.get("prog/store_misses"),
    }
    print(json.dumps(out), flush=True)
    return 0


def cold_start_main(n: int = 48, rows: int = 8192) -> int:
    """Cold-start serving leg (`bench.py --cold-start [N]`): the
    zero-compile restart claim as a driver-visible number. Three FRESH
    processes run the same N-literal point-lookup storm (one lifted
    fused shape — the millions-of-clients traffic shape):

      * warm: compiles, serializes every shape into a shared
        `YDB_TPU_PROGSTORE` dir, then measures steady-state per-query
        latencies — the serving baseline;
      * cold_store: a restart against that store dir, timed FROM THE
        FIRST DISPATCH — `prog/compile_ms` must stay exactly 0 (every
        shape deserializes) and the storm p99 must land within
        BENCH_COLD_START_MAX_RATIO (default 2x) of warm p99;
      * cold_none: the same restart with `YDB_TPU_PROGSTORE=0` — the
        true-cold contrast, whose first query eats the full XLA compile.

    Emits ONE JSON line (warm/cold-restart/true-cold p99s, the ratios,
    first-query walls, byte-equality, the zero-compile verdict) and
    stamps it into COLDSTART_r16.json; rides BENCH_HISTORY.jsonl via
    scripts/bench_history.py. rc 0 = byte-equal, zero-compile restart,
    ratio under the ceiling."""
    phase = os.environ.get("BENCH_COLD_CHILD")
    if phase:
        return _cold_start_child(phase, n, rows)

    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="bench_cold_")
    store_dir = os.path.join(tmp, "pstore")
    base = dict(os.environ)
    base["JAX_PLATFORMS"] = "cpu"
    # deterministic latencies + counters: per-query dispatch (no batch
    # window), no background compile-ahead lane, and no jax-level
    # persistent cache (a cache-loaded executable does not survive
    # serialize→deserialize, so nothing would land in the store)
    base["YDB_TPU_BATCH_WINDOW"] = "0"
    base["YDB_TPU_COMPILE_AHEAD"] = "0"
    for k in ("JAX_COMPILATION_CACHE_DIR", "YDB_TPU_PROGSTATS",
              "YDB_TPU_PROGSTORE_DEVICE"):
        base.pop(k, None)
    me = os.path.abspath(__file__)

    def run_phase(ph: str, store: str):
        env = {**base, "BENCH_COLD_CHILD": ph, "YDB_TPU_PROGSTORE": store}
        p = subprocess.run([sys.executable, me, "--cold-start", str(n)],
                           env=env, capture_output=True, timeout=900)
        for ln in reversed(p.stdout.decode(errors="replace").splitlines()):
            ln = ln.strip()
            if ln.startswith("{"):
                return json.loads(ln)
        sys.stderr.write(p.stderr.decode(errors="replace")[-2000:])
        return None

    try:
        warm = run_phase("warm", store_dir)
        cold = run_phase("cold_store", store_dir)
        none = run_phase("cold_none", "0")
        max_ratio = float(os.environ.get("BENCH_COLD_START_MAX_RATIO",
                                         "2.0"))
        out = {"metric": "cold_start_p99", "unit": "ms", "storm_n": n,
               "rows": rows, "max_ratio": max_ratio,
               "warm": warm, "cold_store": cold, "cold_none": none}
        ok = bool(warm and cold and none)
        if ok:
            wp = warm["p99_ms"] or 0.0
            ratio = (cold["p99_ms"] / wp) if wp else 0.0
            out.update({
                "warm_p99_ms": warm["p99_ms"],
                "cold_restart_p99_ms": cold["p99_ms"],
                "true_cold_p99_ms": none["p99_ms"],
                "cold_over_warm_p99": round(ratio, 2),
                "true_cold_over_warm_p99":
                    round(none["p99_ms"] / wp, 2) if wp else 0.0,
                "first_query_ms": {"warm": warm["first_query_ms"],
                                   "cold_store": cold["first_query_ms"],
                                   "cold_none": none["first_query_ms"]},
                "byte_equal":
                    warm["digest"] == cold["digest"] == none["digest"],
                # the restart never compiled: every shape deserialized
                "zero_compile_restart": bool(cold["compile_ms"] == 0
                                             and cold["store_hits"] >= 1
                                             and cold["store_writes"] == 0),
            })
            ok = (out["byte_equal"] and out["zero_compile_restart"]
                  and warm["store_writes"] >= 1
                  and none["store_writes"] == 0
                  and ratio <= max_ratio)
        out["ok"] = bool(ok)
        print(json.dumps(out), flush=True)
        artifact = os.path.join(os.path.dirname(me), "COLDSTART_r16.json")
        with open(artifact, "w") as f:
            json.dump(out, f, indent=2)
        if warm and cold and none:
            log(f"cold-start: restart p99 {out['cold_restart_p99_ms']}ms "
                f"vs warm {out['warm_p99_ms']}ms "
                f"({out['cold_over_warm_p99']}x, ceiling {max_ratio}x), "
                f"true-cold first query {none['first_query_ms']}ms, "
                f"zero_compile={out['zero_compile_restart']} "
                f"-> {artifact}")
        return 0 if ok else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def views_main(max_scale: int = 100, reads: int = 20) -> int:
    """Materialized-view serving leg (`bench.py --views`): one group-by
    view over a row table under sustained ingest.

    Two claims, measured in-process with the fold lever at its most
    aggressive (YDB_TPU_VIEW_FOLD_BATCH=1 — every commit folds on the
    write path, the HTAP posture):

      * read latency vs write scale: median/p99 view-read latency with
        1x / 10x / 100x write traffic interleaved between reads must
        stay flat — the 100x median within BENCH_VIEWS_MAX_RATIO
        (default 1.5x) of the idle read (reads are O(state), never
        O(backlog): the write path already folded the deltas);
      * fold O(delta): mean per-fold wall for a FIXED 64-row delta as
        the source table grows 16x must stay flat (folds touch the
        delta capacity bucket, not the table).

    Emits ONE JSON line and a VIEWS_r19.json artifact; rides
    BENCH_HISTORY.jsonl via scripts/bench_history.py. rc 0 = latency
    ratio under the ceiling, fold flat, differential check green."""
    os.environ["YDB_TPU_VIEW_FOLD_BATCH"] = "1"
    import numpy as np

    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils.metrics import GLOBAL

    sel = ("select g, count(*) as n, sum(v) as s, min(v) as mn, "
           "max(v) as mx, avg(v) as av from t group by g")
    eng = QueryEngine(block_rows=1 << 13)
    eng.execute("create table t (id Int64 not null, g Int64 not null, "
                "v Double not null, primary key (id)) with (store = row)")
    eng.execute(f"create materialized view mv as {sel}")
    nxt = [0]

    def ingest(rows_n: int) -> None:
        # one commit per statement: every commit is a write-path fold
        while rows_n > 0:
            k = min(rows_n, 64)
            vals = ", ".join(
                f"({i}, {i % 7}, {(i % 1000) * 0.5})"
                for i in range(nxt[0], nxt[0] + k))
            eng.execute(f"insert into t (id, g, v) values {vals}")
            nxt[0] += k
            rows_n -= k

    def read_ms() -> float:
        t0 = time.perf_counter()
        eng.query("select * from mv")
        return (time.perf_counter() - t0) * 1e3

    ingest(512)                                     # seed + warm shapes
    read_ms()
    # idle baseline = serving cost with ZERO backlog (cache-busted:
    # merge + finalize, the apples-to-apples contrast for reads under
    # write traffic); the cached quiet-view read is reported alongside
    mv = eng.views.get("mv")
    idle_cached = [read_ms() for _ in range(reads)]
    idle = []
    for _ in range(reads):
        mv._serve = None
        idle.append(read_ms())

    scales = {}
    for scale in (1, 10, max_scale):
        lat = []
        for _ in range(reads):
            ingest(scale)                           # write traffic
            lat.append(read_ms())
        scales[str(scale)] = {
            "writes_per_read": scale,
            "median_ms": round(float(np.median(lat)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
        }

    # fold O(delta): fixed 64-row delta, table grows 16x
    fold_curve = []
    for target in (2_048, 8_192, 32_768):
        ingest(target - nxt[0])
        eng.query("select * from mv")               # settle the backlog
        ms0 = GLOBAL.get("view/fold_ms")
        f0 = eng.views.get("mv").folds
        ingest(64)
        eng.query("select * from mv")
        f1 = eng.views.get("mv").folds
        fold_curve.append({
            "table_rows": nxt[0] - 64,
            "delta_rows": 64,
            "fold_ms": round((GLOBAL.get("view/fold_ms") - ms0)
                             / max(f1 - f0, 1), 3),
        })

    # differential floor: the served state still equals a recompute
    def _df_eq(a, b):
        a = a.sort_values("g").reset_index(drop=True)
        b = b.sort_values("g").reset_index(drop=True)
        return all(np.allclose(a[c].astype(float), b[c].astype(float),
                               rtol=1e-9) for c in a.columns)

    diff_ok = _df_eq(eng.query("select * from mv"), eng.query(sel))

    max_ratio = float(os.environ.get("BENCH_VIEWS_MAX_RATIO", "1.5"))
    idle_med = float(np.median(idle))
    hot = scales[str(max_scale)]["median_ms"]
    ratio = hot / idle_med if idle_med else 0.0
    folds = [c["fold_ms"] for c in fold_curve]
    fold_flat = (max(folds) / max(min(folds), 1e-3)) if folds else 0.0
    out = {
        "metric": "view_read_latency_vs_write_scale",
        "unit": "ms",
        "idle_median_ms": round(idle_med, 3),
        "idle_p99_ms": round(float(np.percentile(idle, 99)), 3),
        "idle_cached_median_ms":
            round(float(np.median(idle_cached)), 3),
        "scales": scales,
        "read_over_idle_at_max": round(ratio, 3),
        "max_ratio": max_ratio,
        "fold_curve": fold_curve,
        "fold_flat_ratio": round(fold_flat, 3),
        "table_rows": nxt[0],
        "folds": eng.views.get("mv").folds,
        "rebuilds": eng.views.get("mv").rebuilds,
        "diff_ok": bool(diff_ok),
    }
    # fold-flat ceiling is generous (4x over a 16x table growth): the
    # claim is O(delta) not O(table) — a linear-in-table fold shows ~16x
    out["ok"] = bool(diff_ok and ratio <= max_ratio and fold_flat <= 4.0
                     and out["rebuilds"] == 0)
    print(json.dumps(out), flush=True)
    me = os.path.abspath(__file__)
    artifact = os.path.join(os.path.dirname(me), "VIEWS_r19.json")
    with open(artifact, "w") as f:
        json.dump(out, f, indent=2)
    log(f"views: read {hot}ms @ {max_scale}x writes vs idle "
        f"{out['idle_median_ms']}ms ({out['read_over_idle_at_max']}x, "
        f"ceiling {max_ratio}x), fold flat {out['fold_flat_ratio']}x "
        f"over 16x table growth, diff_ok={diff_ok} -> {artifact}")
    return 0 if out["ok"] else 1


def multichip_main(n: int, rows: int) -> int:
    """Multi-chip shuffle leg (`bench.py --multichip [N]`): an N-worker,
    N-device sharded×sharded join driven through BOTH channel planes —
    host gRPC frames (`YDB_TPU_DQ_PLANE=host`) and the device-resident
    ICI collective — with per-edge plane, `dq/ici_bytes` vs
    `dq/channel_bytes`, wall clocks and the quantization saving stamped
    into MULTICHIP_r06.json, so the host-vs-ICI claim is driver-visible
    per run, not anecdotal. Self-provisions a virtual N-device CPU mesh
    in a subprocess when the ambient platform is smaller (the
    `__graft_entry__.dryrun_multichip` stance); on a real multi-chip
    host the same leg measures genuine ICI. rc 0 = planes selected,
    byte-equal, bytes moved; the ≥3× wall target is asserted only where
    the interconnect is real (BENCH_MULTICHIP_MIN_SPEEDUP)."""
    if os.environ.get("BENCH_MULTICHIP_CHILD") != "1":
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ydb_tpu.utils.vmesh import virtual_mesh_env
        env = virtual_mesh_env(n)
        env["BENCH_MULTICHIP_CHILD"] = "1"
        r = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--multichip", str(n)], env=env,
                           timeout=1800)
        return r.returncode

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import pandas as pd

    from ydb_tpu.cluster import ShardedCluster
    from ydb_tpu.dq.runner import LocalWorker
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.utils.metrics import GLOBAL

    nkeys = 997
    engines = []
    for wid in range(n):
        e = QueryEngine(block_rows=1 << 16)
        e.execute("create table t (id Int64 not null, k Int64 not null, "
                  "v Double not null, primary key (id)) "
                  "with (store = column)")
        ids = np.arange(wid, rows, n, dtype=np.int64)
        df = pd.DataFrame({"id": ids, "k": ids % nkeys, "v": ids * 0.5})
        t = e.catalog.table("t")
        t.bulk_upsert(df, e._next_version())
        t.indexate()
        e.execute("create table u (uid Int64 not null, x Double not null, "
                  "primary key (uid))")
        uids = np.arange(wid, nkeys, n, dtype=np.int64)
        du = pd.DataFrame({"uid": uids, "x": 10.0 + uids * 0.25})
        u = e.catalog.table("u")
        u.bulk_upsert(du, e._next_version())
        u.indexate()
        engines.append(e)
    c = ShardedCluster([LocalWorker(e, name=f"mc{i}")
                        for i, e in enumerate(engines)],
                       merge_engine=engines[0])
    c.key_columns["t"] = ["id"]
    c.key_columns["u"] = ["uid"]
    sql = ("select k, count(*) as cnt, sum(v) as s, sum(x) as sx "
           "from t, u where k = uid group by k order by k")

    def run_plane(plane: str, quant: str = "0"):
        os.environ["YDB_TPU_DQ_PLANE"] = plane
        os.environ["YDB_TPU_DQ_QUANT"] = quant
        c.query(sql)                       # warm: compile + dictionaries
        counters0 = {k: GLOBAL.get(k) for k in
                     ("dq/channel_bytes", "dq/ici_bytes", "dq/frames",
                      "dq/ici_frames", "dq/quant_bytes_saved",
                      "pad/live_bytes", "pad/padded_bytes",
                      "pad/waste_bytes")}
        best, res = float("inf"), None
        for _ in range(3):
            t0 = time.perf_counter()
            out = c.query(sql)
            dt = time.perf_counter() - t0
            if dt < best:
                best, res = dt, out
        delta = {k: GLOBAL.get(k) - v for k, v in counters0.items()}
        return best, res, delta

    host_s, host_res, host_d = run_plane("host")
    ici_s, ici_res, ici_d = run_plane("auto")
    quant_s, _quant_res, quant_d = run_plane("auto", quant="1")
    os.environ["YDB_TPU_DQ_QUANT"] = "0"

    edges = [{"channel": ch.id, "kind": ch.kind, "plane": ch.plane,
              "key": ch.key, "quant_cols": list(ch.quant_cols)}
             for ch in c.plan(sql).channels.values()]
    byte_equal = list(host_res.columns) == list(ici_res.columns) \
        and len(host_res) == len(ici_res) \
        and all(np.array_equal(host_res[col].to_numpy(),
                               ici_res[col].to_numpy())
                for col in host_res.columns)
    shuffle_ici = [e for e in edges if e["kind"] == "hash_shuffle"
                   and e["plane"] == "ici"]
    speedup = host_s / ici_s if ici_s else 0.0
    out = {
        "metric": "multichip_ici_shuffle",
        "value": round(speedup, 2),
        "unit": "x",
        "n_devices": n,
        "rows": rows,
        "platform": jax.default_backend(),
        "virtual_mesh": jax.default_backend() == "cpu",
        "edges": edges,
        "host_plane": {"wall_s": round(host_s, 4),
                       "channel_bytes": int(host_d["dq/channel_bytes"]),
                       "ici_bytes": int(host_d["dq/ici_bytes"])},
        "ici_plane": {"wall_s": round(ici_s, 4),
                      "channel_bytes": int(ici_d["dq/channel_bytes"]),
                      "ici_bytes": int(ici_d["dq/ici_bytes"]),
                      "ici_frames": int(ici_d["dq/ici_frames"])},
        "quant": {"wall_s": round(quant_s, 4),
                  "quant_bytes_saved":
                      int(quant_d["dq/quant_bytes_saved"])},
        # padding-waste account measured FROM COUNTERS during the ICI
        # runs (utils/memledger.py): the ~3.5× capacity-padding tax of
        # MULTICHIP_r06, now a live gauge instead of an estimate —
        # ROADMAP item 1's "wire bytes ≤1.3× live bytes" gate reads
        # exactly this ratio
        "padding": {
            "live_bytes": int(ici_d["pad/live_bytes"]),
            "padded_bytes": int(ici_d["pad/padded_bytes"]),
            "waste_bytes": int(ici_d["pad/waste_bytes"]),
            "padded_over_live": round(
                ici_d["pad/padded_bytes"]
                / max(ici_d["pad/live_bytes"], 1), 2),
        },
        # the WIRE-only view of the same tax (ICI segment frames alone,
        # from the state='channel' rows in `.sys/dq_stage_stats` — the
        # planned exchange's per-edge segments, NOT the per-task
        # aggregate mirror of the same bytes): the r06 figure was ~3.5×
        # live; the count-sized segments must hold this ≤1.3×
        "wire_padding": (lambda rows: {
            "live_bytes": int(sum(r["pad_live_bytes"] for r in rows)),
            "padded_bytes": int(sum(r["pad_padded_bytes"]
                                    for r in rows)),
            "padded_over_live": round(
                sum(r["pad_padded_bytes"] for r in rows)
                / max(sum(r["pad_live_bytes"] for r in rows), 1), 2),
            "channels": sorted({r.get("channel", "") for r in rows}),
        })([r for r in engines[0].dq_stage_stats
            if r.get("state") == "channel"
            and r.get("pad_padded_bytes", 0) > 0]),
        "speedup_vs_host": round(speedup, 2),
        "byte_equal": byte_equal,
        "ici_fallbacks": GLOBAL.get("dq/ici_fallbacks"),
    }
    # the ≥3× wall claim belongs to real interconnect; a virtual CPU
    # mesh emulates collectives through one memcpy domain, so there the
    # gate is plane selection + byte-equality + bytes moved (set
    # BENCH_MULTICHIP_MIN_SPEEDUP on multi-chip hardware)
    min_speedup = float(os.environ.get("BENCH_MULTICHIP_MIN_SPEEDUP",
                                       "0"))
    ok = (byte_equal and len(shuffle_ici) == 2
          and ici_d["dq/ici_bytes"] > 0
          and ici_d["dq/channel_bytes"] == 0
          and host_d["dq/channel_bytes"] > 0
          and quant_d["dq/quant_bytes_saved"] > 0
          and ici_d["pad/padded_bytes"] > 0
          and speedup >= min_speedup)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    artifact = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MULTICHIP_r06.json")
    with open(artifact, "w") as f:
        json.dump(out, f, indent=2)
    log(f"multichip: {speedup:.2f}x vs host plane, "
        f"ici_bytes {out['ici_plane']['ici_bytes']}, "
        f"quant saved {out['quant']['quant_bytes_saved']} "
        f"-> {artifact}")
    # ride the trajectory ledger directly (the artifact is fresh in this
    # process, so entry_from_suites stamps the multichip summary — the
    # gate watches wire padded_over_live against its ceiling from here)
    try:
        import importlib.util
        bhp = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "scripts", "bench_history.py")
        spec = importlib.util.spec_from_file_location("bench_history",
                                                      bhp)
        bh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bh)
        bh.append_run({}, source="bench.py --multichip")
        log(f"bench history: appended to {bh.HISTORY_PATH}")
    except Exception as e:               # noqa: BLE001 — ledger only
        log(f"bench history append failed: {type(e).__name__}: {e}")
    return 0 if ok else 1


def main() -> None:
    import threading
    suites: dict = {}

    def emergency():
        # whatever happens — a wedged child, a wedged poll loop — the
        # driver gets its final JSON line and the process exits. The
        # deadline sits UNDER the driver's kill window (r4's sat above
        # it: rc=124, parsed null, nothing recorded).
        time.sleep(EMERGENCY_S)
        log(f"EMERGENCY deadline ({EMERGENCY_S:.0f}s) — emitting partial "
            "results and exiting")
        _append_history(suites)
        _emit(suites)
        os._exit(0)

    threading.Thread(target=emergency, daemon=True).start()
    if not platform_probe():
        # wedged platform: stamp it and report the last good numbers —
        # running the suites would only burn the budget on watchdog kills
        _WEDGED["v"] = True
        _emit(suites)
        return
    # point-lookup storm leg (batched dispatch lane vs the pipelined
    # baseline): its own child + watchdog like every other leg — a
    # wedged storm costs one QUERY_TIMEOUT window, not the suites'
    storm_n = int(os.environ.get("BENCH_STORM", "64") or 0)
    if storm_n:
        cmd = [sys.executable, os.path.abspath(__file__), "--storm",
               str(storm_n)]
        try:
            p = subprocess.run(cmd, timeout=QUERY_TIMEOUT,
                               capture_output=True)
            line = p.stdout.decode(errors="replace").strip() \
                .splitlines()[-1] if p.stdout.strip() else "{}"
            suites["storm"] = json.loads(line)
            suites["storm"]["rc"] = p.returncode
            log(f"storm: {suites['storm'].get('value')}x batched speedup, "
                f"{suites['storm'].get('storm_compiles')} compile(s), "
                f"byte_equal={suites['storm'].get('byte_equal')}")
        except (subprocess.TimeoutExpired, json.JSONDecodeError,
                IndexError) as e:
            suites["storm"] = {"error": f"{type(e).__name__}"}
            log(f"storm leg failed: {type(e).__name__}")
        _emit(suites)
    # cold-start serving leg (restart against the persistent program
    # store vs warm steady-state vs true cold): same child + watchdog
    # shape — three fresh processes inside, one JSON line out
    cold_n = int(os.environ.get("BENCH_COLD_START", "48") or 0)
    if cold_n:
        cmd = [sys.executable, os.path.abspath(__file__), "--cold-start",
               str(cold_n)]
        try:
            p = subprocess.run(cmd, timeout=QUERY_TIMEOUT,
                               capture_output=True)
            line = p.stdout.decode(errors="replace").strip() \
                .splitlines()[-1] if p.stdout.strip() else "{}"
            suites["cold_start"] = json.loads(line)
            suites["cold_start"]["rc"] = p.returncode
            log(f"cold-start: restart p99 "
                f"{suites['cold_start'].get('cold_restart_p99_ms')}ms vs "
                f"warm {suites['cold_start'].get('warm_p99_ms')}ms "
                f"({suites['cold_start'].get('cold_over_warm_p99')}x), "
                f"zero_compile="
                f"{suites['cold_start'].get('zero_compile_restart')}")
        except (subprocess.TimeoutExpired, json.JSONDecodeError,
                IndexError) as e:
            suites["cold_start"] = {"error": f"{type(e).__name__}"}
            log(f"cold-start leg failed: {type(e).__name__}")
        _emit(suites)
    # materialized-view serving leg (read latency vs write scale + fold
    # O(delta) evidence): same child + watchdog shape as the other legs
    views_n = int(os.environ.get("BENCH_VIEWS", "100") or 0)
    if views_n:
        cmd = [sys.executable, os.path.abspath(__file__), "--views",
               str(views_n)]
        try:
            p = subprocess.run(cmd, timeout=QUERY_TIMEOUT,
                               capture_output=True)
            line = p.stdout.decode(errors="replace").strip() \
                .splitlines()[-1] if p.stdout.strip() else "{}"
            suites["views"] = json.loads(line)
            suites["views"]["rc"] = p.returncode
            log(f"views: {suites['views'].get('read_over_idle_at_max')}x "
                f"read-over-idle @ {views_n}x writes, fold flat "
                f"{suites['views'].get('fold_flat_ratio')}x, "
                f"diff_ok={suites['views'].get('diff_ok')}")
        except (subprocess.TimeoutExpired, json.JSONDecodeError,
                IndexError) as e:
            suites["views"] = {"error": f"{type(e).__name__}"}
            log(f"views leg failed: {type(e).__name__}")
        _emit(suites)
    plan = [("tpch", sf) for sf in SUITE_SFS]
    if TPCDS_SF:
        plan.append(("tpcds", float(TPCDS_SF)))
    if CLICKBENCH_ROWS:
        plan.append(("clickbench", float(CLICKBENCH_ROWS)))
    for i, (workload, sf) in enumerate(plan):
        elapsed = time.perf_counter() - _T0
        if elapsed > BUDGET_S - 120:
            log(f"budget exhausted before {workload} sf={sf:g} suite")
            continue
        # per-suite budget split: remaining budget divided over remaining
        # suites, so a slow first suite cannot starve the later ones
        share = (BUDGET_S - elapsed) / (len(plan) - i)
        out = run_suite(sf, time.perf_counter() + share, workload)
        key = f"sf{sf:g}" if workload == "tpch" \
            else f"clickbench_{int(sf)}" if workload == "clickbench" \
            else f"{workload}_sf{sf:g}"
        suites[key] = out
        log(f"suite {key}: {out['coverage']} ok, "
            f"geomean {out['geomean_ms']}ms "
            f"(penalized {out['geomean_penalized_ms']}ms)"
            + (f", {out['vs_pandas_geomean']}x pandas geomean"
               if out["vs_pandas_geomean"] else ""))
        # incremental emission: every completed suite immediately lands a
        # full cumulative JSON line — if anything later wedges or the
        # driver kills us, the LAST printed line already carries it
        _save_last_good({key: out})
        _emit(suites)
    # one trajectory-ledger line per finished run (partial runs included
    # — the ledger is the history, regressions and all; last-known-good
    # stays the separate green-only gate input)
    _append_history(suites)
    if not suites:
        _emit(suites)


if __name__ == "__main__":
    # --trace-dir DIR (composable with every mode): write one Chrome
    # trace-event JSON per profiled query into DIR — rides the
    # environment into suite children
    if "--trace-dir" in sys.argv:
        _i = sys.argv.index("--trace-dir")
        if _i + 1 >= len(sys.argv):
            print("--trace-dir needs a directory", file=sys.stderr)
            sys.exit(2)
        os.environ["BENCH_TRACE_DIR"] = sys.argv[_i + 1]
        del sys.argv[_i:_i + 2]
    if len(sys.argv) > 1 and sys.argv[1] == "--probe":
        probe_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--concurrency":
        sys.exit(concurrency_main(
            int(sys.argv[2]) if len(sys.argv) > 2 else 8,
            rows=int(os.environ.get("BENCH_CONCURRENCY_ROWS", "150000"))))
    elif len(sys.argv) > 1 and sys.argv[1] == "--storm":
        sys.exit(storm_main(
            int(sys.argv[2]) if len(sys.argv) > 2 else 64,
            rows=int(os.environ.get("BENCH_STORM_ROWS", "8192"))))
    elif len(sys.argv) > 1 and sys.argv[1] == "--cold-start":
        sys.exit(cold_start_main(
            int(sys.argv[2]) if len(sys.argv) > 2 else 48,
            rows=int(os.environ.get("BENCH_COLD_START_ROWS", "8192"))))
    elif len(sys.argv) > 1 and sys.argv[1] == "--views":
        sys.exit(views_main(
            int(sys.argv[2]) if len(sys.argv) > 2 else 100))
    elif len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        sys.exit(multichip_main(
            int(sys.argv[2]) if len(sys.argv) > 2 else 4,
            rows=int(os.environ.get("BENCH_MULTICHIP_ROWS", "40000"))))
    elif len(sys.argv) > 1 and sys.argv[1] == "--suite-child":
        sf = float(sys.argv[2])
        skip = [s for s in sys.argv[4].split(",") if s] \
            if len(sys.argv) > 4 else []
        budget = float(sys.argv[5]) if len(sys.argv) > 5 else BUDGET_S
        workload = sys.argv[6] if len(sys.argv) > 6 else "tpch"
        fallback = [s for s in sys.argv[7].split(",") if s] \
            if len(sys.argv) > 7 else []
        child_main(sf, sys.argv[3], skip, budget, workload, fallback)
    else:
        main()
