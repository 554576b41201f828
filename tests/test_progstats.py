"""Compiled-program observatory (`ydb_tpu/utils/progstats.py`): roofline
classification math, the AOT capture + inventory lifecycle (eviction
survival, miss-not-hit recompiles), cost-analysis-absent backend
degradation, the `.sys/compiled_programs` sysview, EXPLAIN ANALYZE's
`-- programs:` block, and the PROGSTATS=0 lever being byte-equal with
`prog/*` frozen.
"""

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.utils import progstats
from ydb_tpu.utils.metrics import GLOBAL


def _mk_engine(rows: int = 400):
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table pt (id Int64 not null, k Int64 not null, "
                "v Double not null, primary key (id)) "
                "with (store = column)")
    ids = np.arange(rows, dtype=np.int64)
    df = pd.DataFrame({"id": ids, "k": ids % 7, "v": ids * 0.5})
    t = eng.catalog.table("pt")
    t.bulk_upsert(df, eng._next_version())
    t.indexate()
    return eng


# -- roofline classification on hand-built (flops, bytes, ms) triples ------


def test_roofline_bound_classes(monkeypatch):
    monkeypatch.setenv("YDB_TPU_PEAK_GFLOPS", "100")
    monkeypatch.setenv("YDB_TPU_PEAK_GBPS", "10")
    pk = progstats.peaks()
    assert pk["gflops"] == 100 and pk["gbps"] == 10
    assert pk["source"] == "env"
    # 1e9 flops @ 100 GFLOP/s = 10ms compute; 1e6 B @ 10 GB/s = 0.1ms
    r = progstats.roofline(1e9, 1e6, device_ms=20.0, pk=pk)
    assert r["bound_class"] == "compute_bound"
    assert r["utilization_pct"] == pytest.approx(50.0, abs=0.1)
    assert r["achieved_gflops"] == pytest.approx(50.0, rel=0.01)
    assert r["intensity"] == pytest.approx(1000.0)
    # bandwidth-dominated triple
    r = progstats.roofline(1e5, 1e9, device_ms=200.0, pk=pk)
    assert r["bound_class"] == "memory_bound"
    # 1e9 B @ 10 GB/s = 100ms roofline; measured 200ms → 50%
    assert r["utilization_pct"] == pytest.approx(50.0, abs=0.1)
    # sub-µs roofline work: launch/dispatch overhead territory
    r = progstats.roofline(100.0, 100.0, device_ms=1.0, pk=pk)
    assert r["bound_class"] == "launch_bound"
    # a delta below the roofline floor is NOT a measurement (the probe
    # ran after a warm program already finished): utilization stays
    # unmeasured rather than reporting an impossible >100%
    r = progstats.roofline(1e9, 1e6, device_ms=1.0, pk=pk)   # roof 10ms
    assert r["utilization_pct"] is None
    assert r["achieved_gflops"] is None
    assert r["bound_class"] == "compute_bound"   # static class stands
    # absent cost — explicit unavailable, never a fabricated zero verdict
    r = progstats.roofline(None, None, device_ms=5.0, pk=pk)
    assert r["bound_class"] == "unavailable"
    assert r["utilization_pct"] is None
    r = progstats.roofline(0, 0, device_ms=5.0, pk=pk)
    assert r["bound_class"] == "unavailable"


def test_roofline_static_classification_without_measurement(monkeypatch):
    monkeypatch.setenv("YDB_TPU_PEAK_GFLOPS", "100")
    monkeypatch.setenv("YDB_TPU_PEAK_GBPS", "10")
    r = progstats.roofline(1e9, 1e6, device_ms=None)
    assert r["bound_class"] == "compute_bound"
    assert r["utilization_pct"] is None and r["achieved_gflops"] is None


# -- AOT capture + handle lifecycle ----------------------------------------


def test_capture_handle_and_fallback(monkeypatch):
    import jax
    import jax.numpy as jnp

    progstats.reset_for_tests()
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.arange(8, dtype=jnp.float32)
    h = progstats.capture("program", ("tkey", 8), f, (x,))
    assert isinstance(h, progstats.ProgramHandle)
    assert float(h(x)) == float(f(x))
    ent = progstats.inventory_entry(h.key_id)
    assert ent is not None and ent["state"] == "live"
    assert ent["compiles"] == 1 and ent["misses"] == 1
    assert ent["compile_ms"] > 0
    # CPU XLA reports cost for this shape — and if it ever stops, the
    # entry must say so explicitly rather than hold zeros
    if ent["cost"] is not None:
        assert ent["cost"]["flops"] > 0 or ent["cost"]["bytes_accessed"] > 0
    # aval drift (different shape) falls back to the jit path — correct
    # result, counted
    fb0 = GLOBAL.get("prog/aot_fallbacks")
    y = jnp.arange(16, dtype=jnp.float32)
    assert float(h(y)) == float(f(y))
    assert GLOBAL.get("prog/aot_fallbacks") == fb0 + 1


def test_capture_disabled_returns_jit_fn(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("YDB_TPU_PROGSTATS", "0")
    f = jax.jit(lambda x: x + 1)
    out = progstats.capture("program", ("off",), f,
                            (jnp.arange(4),))
    assert out is f


def test_inventory_survives_eviction_and_recompile_is_miss():
    """The exec-cache eviction accounting satellite: eviction marks the
    inventory entry `evicted` (it persists in the ring), emits
    prog/evicted, and a re-compile of the evicted key counts a MISS
    that re-records compile_ms — never a hit."""
    import jax
    import jax.numpy as jnp

    from ydb_tpu.ops.exec_cache import ExecCache, _Budget

    progstats.reset_for_tests()
    b = _Budget(1)
    c = ExecCache("program", b)
    c.on_evict = lambda key: progstats.mark_evicted("program", key)
    x = jnp.arange(4, dtype=jnp.float32)
    f1 = jax.jit(lambda v: v * 2.0)
    f2 = jax.jit(lambda v: v * 3.0)
    h1 = progstats.capture("program", ("k1",), f1, (x,))
    c[("k1",)] = h1
    ev0 = GLOBAL.get("prog/evicted")
    rc0 = GLOBAL.get("prog/recompiled")
    h2 = progstats.capture("program", ("k2",), f2, (x,))
    c[("k2",)] = h2                    # budget 1 → evicts k1
    ent = progstats.inventory_entry(h1.key_id)
    assert ent["state"] == "evicted" and ent["evictions"] == 1
    assert GLOBAL.get("prog/evicted") == ev0 + 1
    # the evicted key's entry PERSISTS in the inventory ring
    assert any(r["program"] == h1.key_id and r["state"] == "evicted"
               for r in progstats.inventory_rows())
    # cache-level re-lookup is a miss…
    m0 = c.misses
    assert c.get(("k1",)) is None
    assert c.misses == m0 + 1
    # …and the re-compile re-registers: miss count + fresh compile_ms
    ms_before = ent["compile_ms"]
    h1b = progstats.capture("program", ("k1",), f1, (x,))
    assert h1b.key_id == h1.key_id
    ent2 = progstats.inventory_entry(h1.key_id)
    assert ent2["state"] == "live"
    assert ent2["misses"] == 2 and ent2["compiles"] == 2
    assert ent2["compile_ms"] > ms_before
    assert ent2["evictions"] == 1      # history kept
    assert GLOBAL.get("prog/recompiled") == rc0 + 1


def test_statement_attribution_summary():
    progstats.reset_for_tests()
    st = progstats.open_statement()
    assert st is not None
    try:
        # nested open on the same thread yields None (enclosing wins)
        assert progstats.open_statement() is None
        import jax
        import jax.numpy as jnp
        x = jnp.arange(8, dtype=jnp.float32)
        h = progstats.capture("fused", ("stmt",), jax.jit(lambda v: v * 2),
                              (x,))
        h(x)
        progstats.record_exec(h.key_id, 5.0, fresh=True)
        progstats.record_exec(h.key_id, 3.0, fresh=False)
        s = st.summary()
        assert s["n"] == 1
        assert s["device_ms"] == pytest.approx(8.0)
        assert s["programs"][0]["fresh"] is True
        assert s["programs"][0]["key"] == h.key_id
        assert s["bound_class"] in progstats.BOUND_CLASSES
        assert "_best_ms" not in s["programs"][0]
    finally:
        progstats.close_statement(st)
    assert progstats.current() is None


def test_statement_summary_keeps_fuller_measurement():
    """A warm re-exec that drains an already-finished future (tiny
    delta, unmeasurable utilization) must NOT overwrite the fresh
    exec's measured verdict — the slower (fuller) measurement wins."""
    st = progstats.StatementPrograms()
    st.add({"key": "fused:x", "kind": "fused", "device_ms": 100.0,
            "fresh": True, "flops": 1e9, "bytes_accessed": 1e6,
            "bound_class": "compute_bound", "roofline_ms": 40.0,
            "intensity": 1000.0, "utilization_pct": 40.0,
            "achieved_gflops": 10.0, "achieved_gbps": 0.01})
    st.add({"key": "fused:x", "kind": "fused", "device_ms": 0.01,
            "fresh": False, "flops": 1e9, "bytes_accessed": 1e6,
            "bound_class": "compute_bound", "roofline_ms": 40.0,
            "intensity": 1000.0, "utilization_pct": None,
            "achieved_gflops": None, "achieved_gbps": None})
    s = st.summary()
    assert s["utilization_pct"] == 40.0
    assert s["programs"][0]["utilization_pct"] == 40.0
    assert s["programs"][0]["device_ms"] == pytest.approx(100.01)



# -- engine end-to-end ------------------------------------------------------


def test_engine_fused_program_inventory_and_explain():
    progstats.reset_for_tests()
    eng = _mk_engine()
    eng.query("select k, sum(v) as s from pt group by k order by k")
    eng.query("select k, sum(v) as s from pt group by k order by k")
    stats = eng.last_stats
    assert stats.programs, "fused statement must attribute its program"
    assert stats.programs["n"] >= 1
    dom = stats.programs["programs"][0]
    assert dom["kind"] == "fused" and dom["device_ms"] >= 0
    assert dom["bound_class"] in progstats.BOUND_CLASSES
    # sysview row shape via plain SELECT (the scan-path composition)
    inv = eng.query("select program, kind, state, hits, misses, cost, "
                    "flops, bytes_accessed, utilization_pct, bound_class "
                    "from `.sys/compiled_programs` where kind = 'fused'")
    assert len(inv) >= 1
    row = inv.iloc[0]
    assert row["state"] == "live" and int(row["hits"]) >= 1
    if row["cost"] == "ok":
        assert float(row["flops"]) > 0 or float(row["bytes_accessed"]) > 0
        assert row["bound_class"] in ("memory_bound", "compute_bound",
                                      "launch_bound")
    else:
        assert row["cost"] == "unavailable"
        assert row["bound_class"] == "unavailable"
    # EXPLAIN ANALYZE renders the programs block
    plan = eng.query("explain analyze select k, sum(v) as s from pt "
                     "group by k order by k")
    text = "\n".join(str(x) for x in plan["plan"])
    assert "-- programs:" in text


def test_progstats_lever_off_byte_equal_and_frozen(monkeypatch):
    eng = _mk_engine()
    sql = "select k, count(*) as n, sum(v) as s from pt group by k order by k"
    on = eng.query(sql)
    keys = ("prog/registered", "prog/executions", "prog/device_ms",
            "prog/compile_ms", "prog/evicted", "prog/recompiled",
            "prog/cost_unavailable", "prog/aot_errors",
            "prog/aot_fallbacks")
    monkeypatch.setenv("YDB_TPU_PROGSTATS", "0")
    before = {k: GLOBAL.get(k) for k in keys}
    off = eng.query(sql)
    assert all(GLOBAL.get(k) == v for k, v in before.items()), \
        "prog/* counters must freeze under the lever"
    assert list(on.columns) == list(off.columns)
    assert all(np.array_equal(on[c].to_numpy(), off[c].to_numpy())
               for c in on.columns)
    assert not (eng.last_stats.programs or {})
    # the sysview reports zero rows under the lever
    inv = eng.query("select program from `.sys/compiled_programs`")
    assert len(inv) == 0


def test_cost_analysis_absent_backend(monkeypatch):
    """A backend that raises from (or returns nothing for)
    cost_analysis must degrade to explicit `unavailable` rows — and
    EXPLAIN ANALYZE must still render."""
    from jax._src import stages

    progstats.reset_for_tests()
    monkeypatch.setattr(
        stages.Compiled, "cost_analysis",
        lambda self: (_ for _ in ()).throw(
            NotImplementedError("no cost analysis on this backend")),
        raising=True)
    cu0 = GLOBAL.get("prog/cost_unavailable")
    eng = _mk_engine(rows=300)          # fresh shape → fresh capture
    eng.query("select k, sum(v) as s, count(*) as n from pt "
              "group by k order by k")
    assert GLOBAL.get("prog/cost_unavailable") > cu0
    inv = eng.query("select cost, flops, bytes_accessed, bound_class, "
                    "utilization_pct from `.sys/compiled_programs` "
                    "where kind = 'fused' and cost = 'unavailable'")
    assert len(inv) >= 1
    row = inv.iloc[0]
    assert float(row["flops"]) == 0.0
    assert row["bound_class"] == "unavailable"
    plan = eng.query("explain analyze select k, sum(v) as s, "
                     "count(*) as n from pt group by k order by k")
    text = "\n".join(str(x) for x in plan["plan"])
    assert "-- programs:" in text and "unavailable" in text


def test_cost_analysis_empty_dict_is_unavailable(monkeypatch):
    from jax._src import stages

    progstats.reset_for_tests()
    monkeypatch.setattr(stages.Compiled, "cost_analysis",
                        lambda self: {}, raising=True)
    eng = _mk_engine(rows=200)
    eng.query("select k, min(v) as m from pt group by k order by k")
    inv = eng.query("select cost from `.sys/compiled_programs` "
                    "where kind = 'fused'")
    assert len(inv) >= 1
    assert set(inv["cost"]) == {"unavailable"}


# -- graftlint hygiene ------------------------------------------------------


def test_host_sync_pass_treats_progstats_as_analysis_side():
    import os

    from ydb_tpu.analysis.core import Project
    from ydb_tpu.analysis.passes.host_sync import (
        ANALYSIS_SIDE, HostSyncPass,
    )
    assert "ydb_tpu/utils/progstats.py" in ANALYSIS_SIDE
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    project = Project.from_dir(repo)
    findings = HostSyncPass().check(project)
    assert not [f for f in findings if f.path in ANALYSIS_SIDE]


def test_registry_covers_prog_families():
    from ydb_tpu.utils.metrics import COUNTER_REGISTRY
    for name in ("prog/registered", "prog/compile_ms", "prog/executions",
                 "prog/device_ms", "prog/evicted", "prog/recompiled",
                 "prog/cost_unavailable", "prog/aot_errors",
                 "prog/aot_fallbacks", "prog/utilization_pct"):
        assert name in COUNTER_REGISTRY
    assert COUNTER_REGISTRY["prog/utilization_pct"].startswith("[hist]")


# -- peak table: published figures, and none invented ----------------------


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_v5e_row_is_the_published_figure(monkeypatch, kind):
    import jax
    monkeypatch.setattr(progstats, "_PEAKS", {})
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDevice("tpu", kind)])
    pk = progstats.peaks()
    assert pk == {"gflops": 197_000.0, "gbps": 819.0, "source": "table"}


def test_peaks_unknown_device_invents_nothing(monkeypatch):
    """A non-CPU device that is not in the table is neither probed as if
    it were a CPU nor given a made-up peak: every roofline against it is
    the explicit `unavailable` class."""
    import jax
    monkeypatch.setattr(progstats, "_PEAKS", {})
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDevice("tpu", "TPU v99 mystery")])
    monkeypatch.setattr(progstats, "_probe_cpu",
                        lambda: pytest.fail("probed a non-CPU device"))
    pk = progstats.peaks()
    assert pk["gflops"] is None and pk["gbps"] is None
    assert "TPU v99 mystery" in pk["source"]
    r = progstats.roofline(1e9, 1e9, device_ms=5.0, pk=pk)
    assert r["bound_class"] == "unavailable"
    assert r["utilization_pct"] is None and r["roofline_ms"] is None
