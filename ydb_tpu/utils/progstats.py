"""Compiled-program observatory — the XLA cost model joined to measured
device time, per compiled executable.

PR 12's speed-gap ledger ranks every query by *non-device* blocking
milliseconds; this module answers the question that ledger leaves open
for the device time that remains: does this fused program achieve 2% or
80% of what the chip can do? XLA already computes the needed oracle at
compile time — `Compiled.cost_analysis()` (flops, transcendentals,
bytes accessed) and `Compiled.memory_analysis()` (argument/output/temp/
generated-code bytes, the compiler-reported HBM complement of PR 11's
shape-arithmetic ledger). Capturing both at the cache-fill sites
(`ops/xla_exec.ProgramCache`, the fused/batched dispatch lanes in
`query/executor.py`) and joining them to PR 7's measured device-execute
spans turns every compiled program into a roofline data point:

  achieved GFLOP/s   flops / measured device ms
  achieved GB/s      bytes accessed / measured device ms
  intensity          flops / bytes accessed
  utilization %      roofline-bound time / measured time (how close the
                     measured execution came to the peak-table ceiling)
  bound class        memory_bound | compute_bound | launch_bound
                     (sub-µs roofline work: dispatch overhead dominates)

The peak table comes from `YDB_TPU_PEAK_GFLOPS` / `YDB_TPU_PEAK_GBPS`
(always win), else a per-device-kind reference table for known TPUs,
else a one-shot micro-probe on CPU-class backends — the source is
stamped so a verdict can be audited.

Capture rides the compile itself: at a fresh cache fill the jitted
callable is AOT-compiled (`fn.lower(*args).compile()` — ONE trace + ONE
compile, the same work the lazy first call would have done) and the
returned `ProgramHandle` dispatches through the AOT executable, falling
back to the plain jit path on aval/device drift (a mesh path running
the cached program on another device pays exactly the per-device
compile jit itself would have paid). Cost analysis is BACKEND-DEPENDENT:
CPU may return sparse or absent keys — consumers degrade to explicit
`unavailable` rows, never fabricated zeros.

Surfaces: the `.sys/compiled_programs` inventory sysview (hit/miss/
eviction counts, compile_ms, cost+memory analysis, cumulative device
ms, utilization, bound class — evicted entries persist in the ring
marked `evicted`), the EXPLAIN ANALYZE `-- programs:` block +
`QueryStats.programs`, per-query `utilization`/`bound_class` in the
bench `speed_gap` section, and `prog/*` counters + the utilization
histogram on /counters and /metrics.

`YDB_TPU_PROGSTATS=0` disables everything byte-equal: fills return the
bare jitted callable (the legacy lazy-jit first call), every record is
a no-op, `prog/*` counters freeze and the sysview reports zero rows.
Attribution is thread-local like the tracer and the mem ledger: the
engine opens one statement accumulator per OUTERMOST statement; nested
statements contribute to the enclosing one.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import OrderedDict

from ydb_tpu.utils.metrics import GLOBAL, GLOBAL_HIST

_MU = threading.RLock()
_INVENTORY: OrderedDict = OrderedDict()   # guarded-by: _MU — key_id -> entry
_PEAKS: dict = {}                         # guarded-by: _MU — probe/table cache
# guarded-by: _MU — key_id -> live handle (gone with its cache entry)
_HANDLES: weakref.WeakValueDictionary = weakref.WeakValueDictionary()
_TLS = threading.local()

# roofline work below this is dispatch/launch overhead territory — the
# program cannot meaningfully bound on compute or bandwidth
LAUNCH_BOUND_US = 1.0

BOUND_CLASSES = ("memory_bound", "compute_bound", "launch_bound",
                 "unavailable")

# reference ceilings per device kind (peak GFLOP/s in bf16, peak HBM
# GB/s), per chip, from the vendor's published system tables (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM; the
# v6e, v5p, v4, v3 and v2 pages for the other rows). `chip_smoke.py`
# prints the `device_kind` the chip reports, so a row can be checked
# against it. The env levers override for calibrated hardware. Longest
# prefix wins; a non-CPU device that is NOT in the table gets no peak
# at all (`unavailable`), never an invented one.
_DEVICE_PEAKS = (
    ("TPU v6", 918_000.0, 1_640.0),
    ("TPU v5p", 459_000.0, 2_765.0),
    ("TPU v5 lite", 197_000.0, 819.0),
    ("TPU v5e", 197_000.0, 819.0),
    ("TPU v5", 459_000.0, 2_765.0),
    ("TPU v4", 275_000.0, 1_228.0),
    ("TPU v3", 123_000.0, 900.0),
    ("TPU v2", 46_000.0, 700.0),
)


def enabled() -> bool:
    """`YDB_TPU_PROGSTATS` lever: 0 = no AOT capture, no records, no
    rows — results byte-equal, `prog/*` counters frozen."""
    return os.environ.get("YDB_TPU_PROGSTATS", "1").strip() != "0"


def ring_len() -> int:
    return max(16, int(os.environ.get("YDB_TPU_PROGSTATS_RING", "256")))


# --------------------------------------------------------------------------
# hardware peak table
# --------------------------------------------------------------------------


def _probe_cpu() -> tuple:
    """One-shot micro-probe for the CPU backend, which has no table
    entry (the CPU runner): a small timed matmul for GFLOP/s, a streaming add for
    GB/s. Runs once per process, at the first utilization computation —
    compile-time-adjacent, never in a per-row hot loop."""
    import jax
    import jax.numpy as jnp
    n, reps = 384, 4
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    mm(a).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        r = mm(a)
    r.block_until_ready()
    gflops = reps * 2.0 * n ** 3 / (time.perf_counter() - t0) / 1e9
    m = jnp.ones((1 << 22,), jnp.float32)          # 16 MB
    st = jax.jit(lambda x: x + 1.0)
    st(m).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        r = st(m)
    r.block_until_ready()
    gbps = reps * 2.0 * m.nbytes / (time.perf_counter() - t0) / 1e9
    return max(gflops, 0.1), max(gbps, 0.1)


def peaks() -> dict:
    """{gflops, gbps, source} — env levers win (re-read every call, so
    tests can flip them), else the device-kind table, else — on the CPU
    only — the one-shot probe (cached). A device that is neither in the
    table nor a CPU has no known peak: gflops/gbps are None and every
    roofline against them is `unavailable`."""
    env_gf = float(os.environ.get("YDB_TPU_PEAK_GFLOPS", "0") or 0)
    env_gb = float(os.environ.get("YDB_TPU_PEAK_GBPS", "0") or 0)
    if env_gf > 0 and env_gb > 0:
        return {"gflops": env_gf, "gbps": env_gb, "source": "env"}
    with _MU:
        cached = dict(_PEAKS)
    if not cached:
        import jax
        dev = jax.local_devices()[0]
        kind = str(getattr(dev, "device_kind", ""))
        hit = next(((gf, gb) for (p, gf, gb) in _DEVICE_PEAKS
                    if kind.startswith(p)), None)
        if hit is not None:
            cached = {"gflops": hit[0], "gbps": hit[1], "source": "table"}
        elif dev.platform == "cpu":
            gf, gb = _probe_cpu()
            cached = {"gflops": gf, "gbps": gb, "source": "probe"}
        else:
            cached = {"gflops": None, "gbps": None,
                      "source": f"unknown device kind {kind!r}"}
        with _MU:
            _PEAKS.update(cached)
    out = dict(cached)
    if env_gf > 0:
        out["gflops"], out["source"] = env_gf, "env+" + out["source"]
    if env_gb > 0:
        out["gbps"], out["source"] = env_gb, "env+" + cached["source"]
    return out


# --------------------------------------------------------------------------
# roofline math
# --------------------------------------------------------------------------


def roofline(flops, bytes_accessed, device_ms=None, pk=None) -> dict:
    """Classify one (flops, bytes, measured-ms) triple against the peak
    table. Absent/zero cost → the explicit `unavailable` verdict (a
    backend that withholds analysis must not read as a 0-flop program).
    `device_ms` None/0 → static classification only (no utilization)."""
    pk = pk or peaks()
    f = max(float(flops or 0), 0.0)
    b = max(float(bytes_accessed or 0), 0.0)
    if (f <= 0 and b <= 0) or not pk["gflops"] or not pk["gbps"]:
        return {"bound_class": "unavailable", "roofline_ms": None,
                "intensity": None, "utilization_pct": None,
                "achieved_gflops": None, "achieved_gbps": None}
    t_comp_ms = f / (pk["gflops"] * 1e6)
    t_mem_ms = b / (pk["gbps"] * 1e6)
    roof_ms = max(t_comp_ms, t_mem_ms)
    if roof_ms * 1000.0 < LAUNCH_BOUND_US:
        bound = "launch_bound"
    elif t_mem_ms >= t_comp_ms:
        bound = "memory_bound"
    else:
        bound = "compute_bound"
    out = {"bound_class": bound, "roofline_ms": round(roof_ms, 6),
           "intensity": round(f / b, 3) if b > 0 else None,
           "utilization_pct": None, "achieved_gflops": None,
           "achieved_gbps": None}
    if device_ms and device_ms >= roof_ms:
        # a measured delta BELOW the roofline floor is not a
        # measurement: the block_until_ready probe ran after the
        # program already finished (warm sub-ms programs drain their
        # future late), so the delta bounds nothing — a ">100%
        # utilization" would be fabricated. Stay unmeasured; the
        # static bound_class above still stands.
        out["achieved_gflops"] = round(f / (device_ms * 1e6), 3)
        out["achieved_gbps"] = round(b / (device_ms * 1e6), 3)
        out["utilization_pct"] = round(100.0 * roof_ms / device_ms, 2)
    return out


# --------------------------------------------------------------------------
# compile-time capture
# --------------------------------------------------------------------------


def key_id(kind: str, key) -> str:
    """Stable short inventory id for a cache key (the raw keys are big
    tuples of fingerprints/signatures — repr-hash them once)."""
    import hashlib
    h = hashlib.blake2s(repr(key).encode(), digest_size=6).hexdigest()
    return f"{kind}:{h}"


def _module_name(jit_fn) -> str:
    """The name XLA gives the program's module (`jit_<__name__>`): what
    a device trace's `XLA Modules` line calls it. The fused builders name
    theirs from the plan (`ops/fused.program_name`)."""
    name = getattr(jit_fn, "__name__", "") or ""
    return f"jit_{name}" if name else ""


def _cost_dict(compiled):
    """Normalized cost analysis, or None when the backend withholds it
    (raises, empty, or all-zero — zeros would fabricate a free
    program)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:                  # noqa: BLE001 — backend-dependent
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or not ca:
        return None
    out = {
        "flops": float(ca.get("flops", 0) or 0),
        "transcendentals": float(ca.get("transcendentals", 0) or 0),
        "bytes_accessed": float(ca.get("bytes accessed", 0) or 0),
        "output_bytes": float(ca.get("bytes accessedout{}", 0) or 0),
    }
    if out["flops"] <= 0 and out["bytes_accessed"] <= 0:
        return None
    return out


def _memory_dict(compiled):
    """Compiler-reported executable memory stats, or None."""
    try:
        ms = compiled.memory_analysis()
        out = {
            "arg_bytes": int(getattr(ms, "argument_size_in_bytes", 0)),
            "out_bytes": int(getattr(ms, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ms, "temp_size_in_bytes", 0)),
            "code_bytes":
                int(getattr(ms, "generated_code_size_in_bytes", 0)),
        }
    except Exception:                  # noqa: BLE001 — backend-dependent
        return None
    if not any(out.values()):
        return None
    return out


_HLO_TEXT_CAP = 8 << 20               # skip op-counting monster modules


def _hlo_op_count(compiled) -> int:
    """HLO instruction count of the optimized module (0 when the text
    form is unavailable or too large to bother)."""
    try:
        txt = compiled.as_text()
        if not txt or len(txt) > _HLO_TEXT_CAP:
            return 0
        return sum(1 for ln in txt.splitlines() if " = " in ln)
    except Exception:                  # noqa: BLE001 — backend-dependent
        return 0


class LazyJit:
    """The jit-path stand-in for a store-loaded handle: the store hit
    skipped tracing entirely, so there is no jitted callable to fall
    back to until drift actually happens. `rebuild()` then produces it
    once (paying exactly the trace+compile the legacy path would have
    paid) and is memoized."""

    __slots__ = ("_rebuild", "_fn", "_mu")

    def __init__(self, rebuild):
        self._rebuild = rebuild
        self._fn = None
        self._mu = threading.Lock()

    def __call__(self, *args):
        with self._mu:
            if self._fn is None:
                self._fn = self._rebuild()
            fn = self._fn
        return fn(*args)

    def clear_cache(self) -> None:
        with self._mu:
            fn, self._fn = self._fn, None
        cc = getattr(fn, "clear_cache", None)
        if callable(cc):
            cc()


class ProgramHandle:
    """A cache entry wrapping the AOT-compiled executable. Calls
    dispatch through the `Compiled`; aval/device drift (a mesh path
    running this program for another placement) falls back to the plain
    jit path — which compiles per placement exactly as it would have
    without AOT. `clear_cache` drops the executable AND clears the jit
    cache, so the exec-cache release-on-evict lifecycle holds."""

    __slots__ = ("key_id", "compile_ms", "_jit", "_compiled",
                 "__weakref__")

    def __init__(self, kid: str, jit_fn, compiled, compile_ms: float):
        self.key_id = kid
        self.compile_ms = compile_ms
        self._jit = jit_fn
        self._compiled = compiled
        with _MU:
            _HANDLES[kid] = self

    def __call__(self, *args):
        c = self._compiled
        if c is not None:
            try:
                return c(*args)
            except (TypeError, ValueError):
                GLOBAL.inc("prog/aot_fallbacks")
        return self._jit(*args)

    def clear_cache(self) -> None:
        self._compiled = None
        cc = getattr(self._jit, "clear_cache", None)
        if callable(cc):
            cc()


def _analysis_triple(compiled, extra=None):
    """(cost, memory, hlo_ops) for an executable — preferring the
    values the SAVING process persisted (a deserialized executable may
    withhold analysis the original compile reported)."""
    extra = extra or {}
    cost = extra.get("cost") or _cost_dict(compiled)
    mem = extra.get("memory") or _memory_dict(compiled)
    hlo = int(extra.get("hlo_ops") or 0) or _hlo_op_count(compiled)
    return cost, mem, hlo


def capture(kind: str, key, jit_fn, args, consult_store: bool = True,
            store_extra=None, source: str = "fresh"):
    """AOT-compile `jit_fn(*args)` at a fresh cache fill, recording the
    executable's cost/memory analysis into the inventory. Returns a
    `ProgramHandle` to cache in place of `jit_fn` — or `jit_fn`
    unchanged when disabled or when lower/compile raises (trace errors
    then surface at the normal jit call site, byte-identical to the
    legacy lazy path).

    With the program store enabled, the store is consulted FIRST: a hit
    deserializes the persisted executable and registers with
    `compile_ms = 0` and `source = "store"` (no trace, no compile). A
    fresh compile is serialized back into the store. `consult_store =
    False` skips the lookup for call sites that already consulted the
    store themselves (the fused lane, which needs the stored extra
    payload before it can even build `jit_fn`)."""
    if not enabled():
        return jit_fn
    kid = key_id(kind, key)
    pstore = _store() if consult_store else None
    if pstore is not None:
        rec = pstore.load(kind, key)
        if rec is not None:
            compiled = rec["compiled"]
            cost, mem, hlo = _analysis_triple(compiled, rec["extra"])
            _register(kid, kind, 0.0, cost, mem, hlo, source="store",
                      name=_module_name(jit_fn))
            return ProgramHandle(kid, jit_fn, compiled, 0.0)
    t0 = time.perf_counter()
    try:
        compiled = jit_fn.lower(*args).compile()
    except Exception:                  # noqa: BLE001 — the jit call site
        GLOBAL.inc("prog/aot_errors")  # re-raises the real error
        return jit_fn
    ms = (time.perf_counter() - t0) * 1000.0
    cost, mem, hlo = (_cost_dict(compiled), _memory_dict(compiled),
                      _hlo_op_count(compiled))
    name = _module_name(jit_fn)
    _register(kid, kind, ms, cost, mem, hlo, source=source, name=name)
    pstore = _store()
    if pstore is not None:
        extra = dict(store_extra or {})
        extra.update({"cost": cost, "memory": mem, "hlo_ops": hlo,
                      "name": name})
        pstore.save(kind, key, compiled, extra=extra)
    return ProgramHandle(kid, jit_fn, compiled, round(ms, 3))


def store_load(kind: str, key, rebuild):
    """Fused-lane store lookup: deserialize the persisted executable
    for (kind, key) WITHOUT building or tracing anything. Returns
    `(handle, extra)` — `extra` carrying whatever the saving process
    persisted alongside (the fused lane needs `layout_box`/`out_schema`
    that only trace time would otherwise produce) — or None on any
    miss. `rebuild` lazily reconstructs the jitted callable for the
    drift-fallback path (memoized, never called on the hit path)."""
    if not enabled():
        return None
    pstore = _store()
    if pstore is None:
        return None
    rec = pstore.load(kind, key)
    if rec is None:
        return None
    kid = key_id(kind, key)
    compiled = rec["compiled"]
    cost, mem, hlo = _analysis_triple(compiled, rec["extra"])
    _register(kid, kind, 0.0, cost, mem, hlo, source="store",
              name=(rec["extra"] or {}).get("name", ""))
    return ProgramHandle(kid, LazyJit(rebuild), compiled, 0.0), rec["extra"]


def store_save(kind: str, key, handle, extra=None) -> None:
    """Persist an already-captured handle's executable (the fused lane
    saves AFTER first successful dispatch, when `layout_box` is
    populated — a trace-time artifact the store hit must replay)."""
    pstore = _store()
    if pstore is None or not isinstance(handle, ProgramHandle):
        return
    compiled = handle._compiled
    if compiled is None:
        return
    ent = inventory_entry(handle.key_id) or {}
    full = {"cost": ent.get("cost"), "memory": ent.get("memory"),
            "hlo_ops": ent.get("hlo_ops", 0), "name": ent.get("name", "")}
    full.update(extra or {})
    pstore.save(kind, key, compiled, extra=full)


def _store():
    """The active program store, or None (lever off / open failure)."""
    try:
        from ydb_tpu.progstore import store as _ps
        return _ps.get_store()
    except Exception:                  # noqa: BLE001 — store is optional
        return None


def _register(kid: str, kind: str, compile_ms, cost, mem,
              hlo_ops: int, source: str = "fresh", name: str = "") -> None:
    GLOBAL.inc("prog/registered")
    if compile_ms:
        GLOBAL.inc("prog/compile_ms", compile_ms)
    if cost is None:
        GLOBAL.inc("prog/cost_unavailable")
    with _MU:
        ent = _INVENTORY.get(kid)
        if ent is None:
            ent = _INVENTORY[kid] = {
                "key": kid, "kind": kind, "name": "", "state": "live",
                "hits": 0, "misses": 0, "evictions": 0, "compiles": 0,
                "compile_ms": 0.0, "cost": None, "memory": None,
                "hlo_ops": 0, "execs": 0, "device_ms": 0.0,
                "device_ms_max": 0.0, "source": source,
            }
        was_evicted = ent["state"] == "evicted"
        ent["state"] = "live"
        ent["misses"] += 1             # every register IS a cache miss
        ent["compiles"] += 1
        ent["compile_ms"] += float(compile_ms or 0.0)
        ent["cost"] = cost
        ent["memory"] = mem
        ent["hlo_ops"] = int(hlo_ops)
        ent["source"] = source
        ent["name"] = name or ent["name"]
        _INVENTORY.move_to_end(kid)
        while len(_INVENTORY) > ring_len():
            _INVENTORY.popitem(last=False)
    if was_evicted:
        # the PR-4 companion invariant: a re-compile of an evicted key
        # is a MISS that re-records compile cost, never a silent hit
        GLOBAL.inc("prog/recompiled")


def record_hit(kid) -> None:
    """One cache hit for an inventoried program (the handle's `key_id`;
    None — a pre-lever or lever-off entry — is a no-op)."""
    if kid is None or not enabled():
        return
    with _MU:
        ent = _INVENTORY.get(kid)
        if ent is not None:
            ent["hits"] += 1


def mark_evicted(kind: str, key) -> None:
    """Exec-cache LRU eviction surfaced: the inventory entry persists in
    the ring marked `evicted` (the executable itself was released by
    `ops/exec_cache.release_executable`)."""
    if not enabled():
        return
    with _MU:
        ent = _INVENTORY.get(key_id(kind, key))
        if ent is None:
            return
        ent["state"] = "evicted"
        ent["evictions"] += 1
    GLOBAL.inc("prog/evicted")


def record_exec(kid, device_ms: float, fresh: bool = False) -> None:
    """Join one measured device run (`Executor._await_device`: the
    block_until_ready wait of a fused/batched dispatch LESS the part
    spent behind another statement's program) to its program:
    cumulative device ms, the roofline utilization histogram, and the
    statement accumulator feeding `QueryStats.programs`."""
    if kid is None or not enabled():
        return
    device_ms = max(float(device_ms), 0.0)
    with _MU:
        ent = _INVENTORY.get(kid)
        if ent is None:
            return
        ent["execs"] += 1
        ent["device_ms"] += device_ms
        # the max delta is the best estimate of the program's full
        # device wall (a late-drained future measures only the tail)
        ent["device_ms_max"] = max(ent["device_ms_max"], device_ms)
        cost = dict(ent["cost"]) if ent["cost"] else None
        kind = ent["kind"]
        name = ent.get("name", "")
        source = ent.get("source", "fresh")
    GLOBAL.inc("prog/executions")
    GLOBAL.inc("prog/device_ms", device_ms)
    rf = roofline(cost.get("flops") if cost else None,
                  cost.get("bytes_accessed") if cost else None,
                  device_ms)
    if rf["utilization_pct"] is not None:
        GLOBAL_HIST.observe("prog/utilization_pct", rf["utilization_pct"])
    st = current()
    if st is not None:
        st.add({"key": kid, "kind": kind, "name": name, "source": source,
                "device_ms": round(device_ms, 3), "fresh": bool(fresh),
                "flops": cost.get("flops") if cost else None,
                "bytes_accessed":
                    cost.get("bytes_accessed") if cost else None,
                **rf})


# --------------------------------------------------------------------------
# per-statement attribution (the memledger thread-local discipline)
# --------------------------------------------------------------------------


class StatementPrograms:
    """One statement's program executions (thread-safe: the batched lane
    may record from the leader thread for members)."""

    __slots__ = ("events", "_mu")

    def __init__(self):
        self.events: list = []
        self._mu = threading.Lock()

    def add(self, ev: dict) -> None:
        with self._mu:
            self.events.append(ev)

    def summary(self) -> dict:
        """The `QueryStats.programs` payload: per-program rows (merged
        across repeat executions within the statement, sorted by device
        ms) plus a dominant-program rollup. Empty dict when the
        statement ran no instrumented program."""
        with self._mu:
            events = [dict(e) for e in self.events]
        if not events:
            return {}
        merged: OrderedDict = OrderedDict()
        for e in events:
            m = merged.get(e["key"])
            if m is None:
                merged[e["key"]] = m = dict(e)
                m["_best_ms"] = e["device_ms"]
            else:
                m["device_ms"] = round(m["device_ms"] + e["device_ms"], 3)
                m["fresh"] = m["fresh"] or e["fresh"]
                # keep the roofline verdict of the slower (fuller)
                # measurement — the honest utilization estimate
                if e["device_ms"] > m.get("_best_ms", 0.0):
                    for k in ("utilization_pct", "achieved_gflops",
                              "achieved_gbps", "bound_class"):
                        m[k] = e[k]
                    m["_best_ms"] = e["device_ms"]
        progs = sorted(merged.values(), key=lambda p: -p["device_ms"])
        for p in progs:
            p.pop("_best_ms", None)
        dom = progs[0]
        return {"n": len(progs),
                "device_ms": round(sum(p["device_ms"] for p in progs), 3),
                "utilization_pct": dom.get("utilization_pct"),
                "bound_class": dom.get("bound_class", ""),
                "programs": progs}


def current():
    return getattr(_TLS, "programs", None)


def open_statement():
    """Open the accumulator for an OUTERMOST statement on this thread;
    None when disabled or nested (nested statements contribute to the
    enclosing accumulator — the memledger rule)."""
    if not enabled() or getattr(_TLS, "programs", None) is not None:
        return None
    st = StatementPrograms()
    _TLS.programs = st
    return st


def close_statement(st) -> None:
    if getattr(_TLS, "programs", None) is st:
        _TLS.programs = None


# --------------------------------------------------------------------------
# inventory export (the `.sys/compiled_programs` payload)
# --------------------------------------------------------------------------


def inventory_rows() -> list:
    """One row per inventoried program, oldest first — live and evicted
    alike. Empty under YDB_TPU_PROGSTATS=0 (the lever freezes the view,
    not just the capture)."""
    if not enabled():
        return []
    with _MU:
        entries = [dict(e) for e in _INVENTORY.values()]
    pk = peaks() if entries else None
    rows = []
    for e in entries:
        cost = e["cost"] or {}
        mem = e["memory"] or {}
        rf = roofline(cost.get("flops"), cost.get("bytes_accessed"),
                      e["device_ms_max"] or None, pk=pk)
        rows.append({
            "program": e["key"], "name": e.get("name", ""),
            "kind": e["kind"], "state": e["state"],
            "source": e.get("source", "fresh"),
            "hits": e["hits"], "misses": e["misses"],
            "evictions": e["evictions"], "compiles": e["compiles"],
            "compile_ms": round(e["compile_ms"], 3),
            "cost": "ok" if e["cost"] else "unavailable",
            "flops": cost.get("flops", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
            "bytes_accessed": cost.get("bytes_accessed", 0.0),
            "output_bytes": cost.get("output_bytes", 0.0),
            "hlo_ops": e["hlo_ops"],
            "arg_bytes": mem.get("arg_bytes", 0),
            "out_bytes": mem.get("out_bytes", 0),
            "temp_bytes": mem.get("temp_bytes", 0),
            "code_bytes": mem.get("code_bytes", 0),
            "execs": e["execs"],
            "device_ms": round(e["device_ms"], 3),
            "device_ms_max": round(e["device_ms_max"], 3),
            "achieved_gflops": rf["achieved_gflops"] or 0.0,
            "achieved_gbps": rf["achieved_gbps"] or 0.0,
            "intensity": rf["intensity"] or 0.0,
            "utilization_pct": rf["utilization_pct"] or 0.0,
            "bound_class": rf["bound_class"],
        })
    return rows


def inventory_entry(kid: str):
    """Test/tooling hook: the raw inventory entry for a key id."""
    with _MU:
        e = _INVENTORY.get(kid)
        return dict(e) if e is not None else None


def hlo_text(kid: str) -> str:
    """Tooling hook: the optimized HLO text of a program that is still
    live — each instruction with its `op_name`, the `jax.named_scope`
    path of the IR command it came from (what maps a device trace's
    `fusion.N` to a line of the plan). '' once the entry was evicted."""
    with _MU:
        handle = _HANDLES.get(kid)
    compiled = getattr(handle, "_compiled", None)
    return compiled.as_text() if compiled is not None else ""


def reset_for_tests() -> None:
    """Clear the process-global inventory (test isolation only —
    counters are NOT reset)."""
    with _MU:
        _INVENTORY.clear()
