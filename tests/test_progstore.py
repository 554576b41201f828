"""Persistent compiled-program store (`ydb_tpu/progstore/`): canonical
key encoding, the shape-bucket ladder, single-flight compile dedup, the
zero-compile restart path (store write → fresh process → deserialize
with `compile_ms ~= 0`), the corruption/device-mismatch failure ladder,
bucket migration recompiling exactly once per boundary, the
`YDB_TPU_PROGSTORE=0` byte-equal lever, and the `.sys/progstore` + ProgStoreStats observability surfaces.
"""

import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from ydb_tpu.progstore import buckets, compile_ahead, store
from ydb_tpu.utils import progstats
from ydb_tpu.utils.metrics import GLOBAL

SQL = "select k, count(*) as n, sum(v) as s from pt group by k order by k"


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


@pytest.fixture
def fresh_compiles():
    """Force genuinely fresh compiles for store-write assertions: an
    executable that XLA loaded from its own persistent compilation
    cache (conftest's JAX_COMPILATION_CACHE_DIR) serializes to a payload with
    dangling symbol references, which the save-path round-trip
    validation rejects — correctly, but then nothing lands on disk."""
    import jax
    from jax._src import compilation_cache as _cc

    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    # the dir and the per-process used-bit are memoized at first cache
    # use (jax 0.4.x `_cache_initialized`/`_cache_checked`): once any
    # earlier test compiled through the cache, flipping the config
    # alone is a no-op and the "fresh" compile still loads the broken-
    # to-serialize cached executable — reset so the new dir is seen
    _cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    _cc.reset_cache()


def _restart_sim():
    """What a process restart resets: the progstats inventory and the
    cached store instances. Engine/data are rebuilt by the caller."""
    progstats.reset_for_tests()
    store.reset_for_tests()


def _frames_equal(a, b) -> bool:
    return list(a.columns) == list(b.columns) and all(
        np.array_equal(a[c].to_numpy(), b[c].to_numpy())
        for c in a.columns)


# -- canonical key encoding -------------------------------------------------


def test_canon_bytes_is_order_independent_for_unordered_collections():
    assert store.canon_bytes(frozenset({"a", "b", "c"})) == \
        store.canon_bytes(frozenset({"c", "a", "b"}))
    assert store.canon_bytes({"x": 1, "y": 2}) == \
        store.canon_bytes({"y": 2, "x": 1})
    # ordered containers keep their order
    assert store.canon_bytes((1, 2)) != store.canon_bytes((2, 1))
    # type confusion must not alias ("1" vs 1, bytes vs str)
    assert store.canon_bytes("1") != store.canon_bytes(1)
    assert store.canon_bytes(b"ab") != store.canon_bytes("ab")
    assert store.canon_bytes(True) != store.canon_bytes(1)
    # numpy scalars/dtypes normalize to stable primitives
    assert store.canon_bytes(np.int64(7)) == store.canon_bytes(7)
    assert store.canon_bytes(np.dtype(np.int32)) == \
        store.canon_bytes(np.dtype("int32"))


def test_key_digest_separates_kinds():
    key = ("sig", frozenset({"a"}), 4, 1024)
    assert store.key_digest("fused", key) != store.key_digest("batched", key)
    assert store.key_digest("fused", key) == store.key_digest("fused", key)


# -- bucket ladder ----------------------------------------------------------


def test_bucket_ladder_shape():
    assert buckets.ladder(32) == (1, 2, 3, 4, 6, 8, 12, 16, 24, 32)
    # O(log n): 64 possible source counts visit at most 12 shapes
    assert len({buckets.bucket_sources(k) for k in range(1, 65)}) <= 12


def test_bucket_sources_quantizes_up():
    assert buckets.bucket_sources(1) == 1
    assert buckets.bucket_sources(4) == 4
    assert buckets.bucket_sources(5) == 6
    assert buckets.bucket_sources(6) == 6
    assert buckets.bucket_sources(7) == 8
    assert buckets.bucket_sources(13) == 16
    # above the ceiling: pass-through, never pad a giant scan
    assert buckets.bucket_sources(buckets.CEILING) == buckets.CEILING
    assert buckets.bucket_sources(buckets.CEILING + 1) == \
        buckets.CEILING + 1


# -- single-flight dedup ----------------------------------------------------


def test_single_flight_storm_compiles_once():
    sf = compile_ahead.SingleFlight()
    calls, results = [], []
    release = threading.Event()

    def thunk():
        calls.append(1)
        release.wait(10)
        return "compiled"

    def runner():
        results.append(sf.run("k", thunk))

    leader = threading.Thread(target=runner)
    leader.start()
    deadline = time.monotonic() + 10
    while sf.inflight() == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    d0 = GLOBAL.get("prog/compile_ahead_dedup")
    followers = [threading.Thread(target=runner) for _ in range(5)]
    for th in followers:
        th.start()
    # followers count dedup BEFORE blocking on the leader's future —
    # once all five counted, releasing the leader cannot race a late
    # arrival into a second compile
    while GLOBAL.get("prog/compile_ahead_dedup") < d0 + 5 and \
            time.monotonic() < deadline:
        time.sleep(0.001)
    release.set()
    leader.join(10)
    for th in followers:
        th.join(10)
    assert len(calls) == 1, "a 6-caller storm must compile exactly once"
    assert results == ["compiled"] * 6
    assert GLOBAL.get("prog/compile_ahead_dedup") == d0 + 5
    assert sf.inflight() == 0


def test_single_flight_leader_exception_propagates_then_retries():
    sf = compile_ahead.SingleFlight()
    with pytest.raises(RuntimeError, match="trace failed"):
        sf.run("k", lambda: (_ for _ in ()).throw(
            RuntimeError("trace failed")))
    # the slot cleared: the NEXT request retries fresh, not a poisoned
    # cached future
    assert sf.inflight() == 0
    assert sf.run("k", lambda: 42) == 42


def test_compile_ahead_launch_counts_and_swallows_errors():
    sf = compile_ahead.SingleFlight()
    l0 = GLOBAL.get("prog/compile_ahead_launches")
    e0 = GLOBAL.get("prog/compile_ahead_errors")
    done = threading.Event()

    def boom():
        try:
            raise ValueError("background trace error")
        finally:
            done.set()

    assert sf.launch("bg", boom) is True
    assert done.wait(10)
    deadline = time.monotonic() + 10
    while GLOBAL.get("prog/compile_ahead_errors") == e0 and \
            time.monotonic() < deadline:
        time.sleep(0.001)
    assert GLOBAL.get("prog/compile_ahead_launches") == l0 + 1
    assert GLOBAL.get("prog/compile_ahead_errors") == e0 + 1


def test_compile_ahead_lever_off_never_launches(monkeypatch):
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    sf = compile_ahead.SingleFlight()
    l0 = GLOBAL.get("prog/compile_ahead_launches")
    assert sf.launch("k", lambda: 1) is False
    assert GLOBAL.get("prog/compile_ahead_launches") == l0


# -- the zero-compile restart path ------------------------------------------


def test_store_roundtrip_restart_is_zero_compile(monkeypatch, tmp_path, fresh_compiles):
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    w0 = GLOBAL.get("prog/store_writes")
    eng1 = _mk_engine()
    r1 = eng1.query(SQL)
    assert GLOBAL.get("prog/store_writes") > w0, \
        "a fresh compile must serialize its executable"
    assert os.path.exists(tmp_path / "pstore" / "manifest.jsonl")
    assert any(n.endswith(".bin")
               for n in os.listdir(tmp_path / "pstore" / "objects"))

    # "restart": fresh engine + reset inventory/stores, same store dir,
    # identical data — every program deserializes, nothing compiles
    _restart_sim()
    eng2 = _mk_engine()
    h0 = GLOBAL.get("prog/store_hits")
    cm0 = GLOBAL.get("prog/compile_ms")
    w1 = GLOBAL.get("prog/store_writes")
    r2 = eng2.query(SQL)
    assert GLOBAL.get("prog/store_hits") > h0
    assert GLOBAL.get("prog/compile_ms") == cm0, \
        "the restart run must not compile anything"
    assert GLOBAL.get("prog/store_writes") == w1
    assert _frames_equal(r1, r2)
    # the inventory attributes the hit to the store
    inv = eng2.query("select kind, source, compile_ms from "
                     "`.sys/compiled_programs` where kind = 'fused'")
    assert len(inv) >= 1
    assert set(inv["source"]) == {"store"}
    assert all(float(ms) == 0.0 for ms in inv["compile_ms"])
    # EXPLAIN ANALYZE tags the provenance
    plan = eng2.query(f"explain analyze {SQL}")
    text = "\n".join(str(x) for x in plan["plan"])
    assert "[store]" in text


def test_store_corruption_is_evicted_and_self_heals(monkeypatch, tmp_path, fresh_compiles):
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    eng1 = _mk_engine()
    r1 = eng1.query(SQL)
    objdir = tmp_path / "pstore" / "objects"
    victims = [n for n in os.listdir(objdir) if n.endswith(".bin")]
    assert victims
    for n in victims:                   # satellite: garbage bytes in place
        with open(objdir / n, "wb") as f:
            f.write(b"\x00garbage not an executable\xff" * 17)

    _restart_sim()
    eng2 = _mk_engine()
    c0 = GLOBAL.get("prog/store_corrupt")
    r2 = eng2.query(SQL)
    assert GLOBAL.get("prog/store_corrupt") > c0, \
        "checksum mismatch must be detected and counted"
    assert _frames_equal(r1, r2), \
        "a corrupt entry is a cold miss, never a wrong program"
    # the corrupt objects were DELETED and the key re-written fresh —
    # a third restart hits the healed store
    _restart_sim()
    eng3 = _mk_engine()
    h0 = GLOBAL.get("prog/store_hits")
    c1 = GLOBAL.get("prog/store_corrupt")
    r3 = eng3.query(SQL)
    assert GLOBAL.get("prog/store_hits") > h0
    assert GLOBAL.get("prog/store_corrupt") == c1
    assert _frames_equal(r1, r3)


def test_store_version_skew_reads_as_corrupt(monkeypatch, tmp_path, fresh_compiles):
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    eng1 = _mk_engine()
    r1 = eng1.query(SQL)
    # simulate a store written by an older format revision
    monkeypatch.setattr(store, "FORMAT_VERSION", store.FORMAT_VERSION + 1)
    _restart_sim()
    eng2 = _mk_engine()
    c0 = GLOBAL.get("prog/store_corrupt")
    r2 = eng2.query(SQL)
    assert GLOBAL.get("prog/store_corrupt") > c0
    assert _frames_equal(r1, r2)


def test_store_refuses_foreign_device_fingerprint(monkeypatch, tmp_path, fresh_compiles):
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    eng1 = _mk_engine()
    r1 = eng1.query(SQL)
    entries_before = store.stats()["entries"]

    # a data dir copied onto a different backend: the spoofed
    # fingerprint makes every stored entry foreign
    monkeypatch.setenv("YDB_TPU_PROGSTORE_DEVICE", "tpu:TPU v4:8")
    _restart_sim()
    eng2 = _mk_engine()
    ref0 = GLOBAL.get("prog/store_refused")
    cor0 = GLOBAL.get("prog/store_corrupt")
    cm0 = GLOBAL.get("prog/compile_ms")
    r2 = eng2.query(SQL)
    assert GLOBAL.get("prog/store_refused") > ref0, \
        "a foreign-device executable must be refused, not dispatched"
    assert GLOBAL.get("prog/store_corrupt") == cor0, \
        "refusal is not corruption — the entry stays valid for ITS device"
    assert GLOBAL.get("prog/compile_ms") > cm0, "fresh compile instead"
    assert _frames_equal(r1, r2)
    assert store.stats()["entries"] >= entries_before


def test_store_lever_off_writes_nothing_and_is_byte_equal(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    _restart_sim()
    on = _mk_engine().query(SQL)

    for lever in ("0", ""):
        monkeypatch.setenv("YDB_TPU_PROGSTORE", lever)
        _restart_sim()
        probe = tmp_path / f"probe{lever or 'empty'}"
        w0 = GLOBAL.get("prog/store_writes")
        m0 = GLOBAL.get("prog/store_misses")
        off = _mk_engine().query(SQL)
        assert _frames_equal(on, off)
        assert GLOBAL.get("prog/store_writes") == w0
        assert GLOBAL.get("prog/store_misses") == m0
        assert not probe.exists(), "the lever must leave zero files"
        assert store.get_store() is None
        assert store.stats()["root"] == ""


# -- shape-bucketed polymorphism on a growing table -------------------------


def _grow_chunk(eng, i: int, n: int = 256):
    t = eng.catalog.table("pt")
    ids = np.arange(i * n, (i + 1) * n, dtype=np.int64)
    df = pd.DataFrame({"id": ids, "k": ids % 7, "v": ids * 0.5})
    t.bulk_upsert(df, eng._next_version())
    t.indexate()


def _mk_growing_engine(chunks: int):
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 12)
    eng.execute("create table pt (id Int64 not null, k Int64 not null, "
                "v Double not null, primary key (id)) "
                "with (store = column)")
    for i in range(chunks):
        _grow_chunk(eng, i)
    return eng


def _fused_programs() -> int:
    return len([r for r in progstats.inventory_rows()
                if r["kind"] == "fused"])


def test_bucket_migration_recompiles_exactly_once(monkeypatch):
    """Growing 4 → 5 sources crosses the 4→6 bucket boundary: ONE
    recompile. Growing 5 → 6 stays inside bucket 6: ZERO recompiles —
    the padded program serves the larger table as-is."""
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    monkeypatch.setenv("YDB_TPU_PROGSTORE", "0")
    progstats.reset_for_tests()
    eng = _mk_growing_engine(4)
    eng.query(SQL)
    assert _fused_programs() == 1
    _grow_chunk(eng, 4)
    r5 = eng.query(SQL)
    assert _fused_programs() == 2, "crossing a boundary recompiles once"
    _grow_chunk(eng, 5)
    r6 = eng.query(SQL)
    assert _fused_programs() == 2, \
        "growth inside a bucket reuses the padded program"

    # the zero-length pad sources change no answer (v = id * 0.5 and
    # its sums are exact in a double)
    for got, chunks in ((r5, 5), (r6, 6)):
        ids = np.arange(chunks * 256, dtype=np.int64)
        want = (pd.DataFrame({"k": ids % 7, "v": ids * 0.5}).groupby("k")
                .agg(n=("v", "size"), s=("v", "sum")).reset_index())
        assert _frames_equal(got, want)


# -- observability surfaces -------------------------------------------------


def test_progstore_sysview_and_rpc(monkeypatch, tmp_path, fresh_compiles):
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    eng = _mk_engine()
    eng.query(SQL)
    row = eng.query("select root, entries, objects, object_bytes, "
                    "hits, writes, env, device, admission_active "
                    "from `.sys/progstore`")
    assert len(row) == 1
    assert row.iloc[0]["root"] == str(tmp_path / "pstore")
    assert int(row.iloc[0]["entries"]) >= 1
    assert int(row.iloc[0]["objects"]) >= 1
    assert int(row.iloc[0]["object_bytes"]) > 0
    assert "jax=" in row.iloc[0]["env"]

    from ydb_tpu.server.service import QueryServicer
    snap = QueryServicer(eng).prog_store_stats({}, None)
    assert "store" in snap
    assert snap["store"]["entries"] >= 1
    assert snap["store"]["admission"]["active"] == 0
    assert snap["store"]["admission"]["free_bytes"] > 0


def test_compile_ahead_lane_end_to_end(monkeypatch, tmp_path):
    """The engine hook launches a background fill between planning and
    admission; the synchronous dispatch either finds the program ready
    or dedups onto the in-flight compile — and the result is correct
    either way."""
    monkeypatch.setenv("YDB_TPU_PROGSTORE", str(tmp_path / "pstore"))
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "1")
    _restart_sim()
    l0 = GLOBAL.get("prog/compile_ahead_launches")
    eng = _mk_engine()
    on = eng.query(SQL)
    assert GLOBAL.get("prog/compile_ahead_launches") > l0
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    monkeypatch.setenv("YDB_TPU_PROGSTORE", "0")
    _restart_sim()
    off = _mk_engine().query(SQL)
    assert _frames_equal(on, off)


def test_compile_ahead_hands_build_trace_to_consuming_statement(
        monkeypatch, tmp_path):
    """The warm lane builds (traces) the fused program on a background
    worker thread, but the trace-time groupby/bounds gauges are
    thread-local — the statement that consumes the warmed entry must
    fold the parked build delta into ITS window, or EXPLAIN ANALYZE /
    `last_stats` (and the bounds CI gate) see an empty trace for every
    warmed shape."""
    monkeypatch.setenv("YDB_TPU_PROGSTORE", "0")
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")
    _restart_sim()
    eng = _mk_engine()
    eng.query(SQL)
    want = dict(eng.last_stats.groupby or {})
    assert want, "lane-off fresh compile must trace groupby gauges"

    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "1")
    _restart_sim()
    eng2 = _mk_engine()
    eng2.query(SQL)
    got = dict(eng2.last_stats.groupby or {})
    # whichever thread won the single-flight race (warm leader or the
    # dispatch itself), the statement's window reports the same build
    assert sorted(got) == sorted(want)


def test_registry_covers_store_and_compile_ahead_counters():
    from ydb_tpu.utils.metrics import COUNTER_REGISTRY
    for name in ("prog/store_hits", "prog/store_misses",
                 "prog/store_writes", "prog/store_corrupt",
                 "prog/store_refused", "prog/store_errors",
                 "prog/compile_ahead_launches",
                 "prog/compile_ahead_dedup", "prog/compile_ahead_hits",
                 "prog/compile_ahead_errors"):
        assert name in COUNTER_REGISTRY
