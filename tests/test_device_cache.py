"""`DeviceColumnCache` counted where the work happens (PR 33): the
upload and eviction counters `devcache/*` in `utils.metrics.GLOBAL`
beside the cache's own `hits` / `misses` / `bytes` (served as
`device_cache/*`: one name a fact), and what the `superblock-upload`
and `admission-wait` spans say of a statement.

CPU, toy sizes: counts and paths, never a speed. `GLOBAL` is the
process's, so every case reads a delta round its own steps.
"""

import threading
import time

import numpy as np
import pytest

from ydb_tpu.progstore import compile_ahead
from ydb_tpu.query import QueryEngine
from ydb_tpu.storage.device_cache import DeviceColumnCache
from ydb_tpu.utils.metrics import COUNTER_REGISTRY, GLOBAL, Timer

COUNTERS = ("devcache/uploads", "devcache/upload_bytes", "devcache/upload_ms",
            "devcache/evictions", "devcache/evicted_bytes")
# counted by the cache itself and served per engine: not counted twice
SERVED = {"device_cache/hits": "hits", "device_cache/misses": "misses",
          "device_cache/bytes": "bytes"}
SCAN = "select sum(a) as s, sum(b) as t from t where k >= 0"
COL = np.arange(256, dtype=np.int64)               # 2 048 bytes


def engine(rows: int = 4096) -> QueryEngine:
    eng = QueryEngine()
    eng.execute("create table t (k Int64 not null, a Int64 not null, "
                "b Double not null, primary key (k)) with (store = column)")
    eng.execute("insert into t (k, a, b) values "
                + ", ".join(f"({i}, {i % 7}, {i}.5)" for i in range(rows)))
    return eng


class Delta:
    """What the counters moved by since it was made."""

    def __init__(self):
        self.c0 = GLOBAL.snapshot()

    def __getitem__(self, leaf: str) -> float:
        name = f"devcache/{leaf}"
        return GLOBAL.get(name) - self.c0.get(name, 0)


def span(eng, name: str):
    found = [s for s in eng.last_trace if s.name == name]
    assert len(found) == 1, [s.name for s in eng.last_trace]
    return found[0]


@pytest.mark.parametrize("name", COUNTERS)
def test_every_counter_has_its_line_and_moves(name):
    assert name in COUNTER_REGISTRY
    cache = DeviceColumnCache(budget_bytes=3000)
    before = GLOBAL.get(name)
    cache._insert(("x", 1), COL, None, COL.nbytes, Timer())    # an upload
    cache._insert(("x", 2), COL, None, COL.nbytes, Timer())    # drops 1
    assert GLOBAL.get(name) > before


@pytest.mark.parametrize("served", sorted(SERVED))
def test_one_name_a_fact(served):
    """Hits, misses and residency keep the name they had; the process's
    registry holds no second count of them."""
    assert served in COUNTER_REGISTRY
    eng = engine(64)
    eng.query(SCAN)
    eng.query(SCAN)
    cache = eng.executor.device_cache
    assert eng.counters()[served] == getattr(cache, SERVED[served]) > 0
    twin = "devcache/" + SERVED[served]
    assert twin not in COUNTER_REGISTRY and twin not in GLOBAL.snapshot()
    assert "devcache/resident_bytes" not in GLOBAL.snapshot()


def test_a_miss_then_a_hit():
    cache = DeviceColumnCache()
    d = Delta()
    assert cache._lookup(("x", 1)) is None
    assert (cache.hits, cache.misses) == (0, 1)
    cache._insert(("x", 1), COL, None, COL.nbytes, Timer())
    assert cache.bytes == 2048
    assert cache._lookup(("x", 1))[0] is COL
    assert (cache.hits, cache.misses, d["uploads"]) == (1, 1, 1)
    assert d["evictions"] == 0 and cache.bytes == 2048


@pytest.mark.parametrize("uploaded", [True, False])
def test_only_a_host_built_entry_counts_as_an_upload(uploaded):
    cache = DeviceColumnCache()
    d = Delta()
    built = Timer() if uploaded else None       # None: stacked on device
    cache._insert(("x", 1), COL, None, COL.nbytes, built)
    assert d["uploads"] == int(uploaded)
    assert d["upload_bytes"] == (2048 if uploaded else 0)
    assert (d["upload_ms"] > 0) is uploaded
    assert cache.bytes == 2048


@pytest.fixture
def built_once(monkeypatch):
    """The compile-ahead thunk of a new shape stacks the statement's
    columns on its own thread too (the case after the next): off, so that
    each entry is built once and the byte counts are exact."""
    monkeypatch.setenv("YDB_TPU_COMPILE_AHEAD", "0")


def test_a_cold_scan_uploads_the_superblocks_bytes_and_a_warm_one_none(
        built_once):
    eng = engine()
    cache = eng.executor.device_cache
    d = Delta()
    eng.query(SCAN)
    up = span(eng, "superblock-upload")
    # three columns and the vector of source lengths, each asked for once
    assert (cache.misses, d["uploads"], cache.hits) == (4, 4, 0)
    assert d["upload_bytes"] == up.attrs["bytes"] == cache.bytes > 0
    assert d["upload_ms"] > 0
    assert eng.counters()["device_cache/bytes"] == cache.bytes
    assert up.attrs["hit"] is False and up.attrs["columns"] == 3
    assert up.attrs["sources"] >= 1

    d, resident = Delta(), cache.bytes
    eng.query(SCAN)
    assert (cache.hits, cache.misses, d["uploads"]) == (4, 4, 0)
    assert d["upload_bytes"] == 0 and cache.bytes == resident
    assert span(eng, "superblock-upload").attrs == {
        "columns": 3, "sources": up.attrs["sources"], "bytes": 0,
        "hit": True}


def test_a_statement_and_its_compile_ahead_thunk_may_both_upload_a_column():
    """Both miss, both stack and upload; the later insert is dropped. The
    counters say what crossed the link, `cache.bytes` what stayed."""
    eng = engine()
    cache = eng.executor.device_cache
    d = Delta()
    eng.query(SCAN)
    compile_ahead.reset_for_tests()         # the thunk has run to its end
    assert cache.misses == d["uploads"] >= len(cache._entries) == 4
    assert d["upload_bytes"] >= cache.bytes
    assert d["evictions"] == 0


@pytest.mark.parametrize("how", ["insert", "reserve", "foreign"])
def test_an_eviction_under_a_small_budget_is_counted(how):
    cache = DeviceColumnCache(budget_bytes=3000)
    d = Delta()
    cache._insert(("x", 1), COL, None, COL.nbytes, Timer())
    assert d["evictions"] == 0 and cache.bytes == 2048
    if how == "insert":                 # a second column pushes the first
        cache._insert(("x", 2), COL, None, COL.nbytes, Timer())
        assert list(cache._entries) == [("x", 2)]
    elif how == "reserve":              # room for an untracked allocation
        cache.reserve(2000)
    else:                               # another cache's bytes, one budget
        cache.acquire_foreign(2000)
    assert (d["evictions"], d["evicted_bytes"]) == (1, 2048)
    resident = 2048 if how == "insert" else 0
    assert cache.bytes == resident
    assert cache.bytes == d["upload_bytes"] - d["evicted_bytes"]


def test_an_engine_whose_budget_is_full_counts_what_the_next_column_drops(
        built_once):
    eng = engine()
    cache = eng.executor.device_cache
    eng.query("select sum(a) as s from t where k >= 0")
    cache.budget = before = cache.bytes
    d = Delta()
    eng.query("select sum(b) as s from t where k >= 0")
    assert d["evictions"] >= 1
    assert d["evicted_bytes"] == before + d["upload_bytes"] - cache.bytes
    assert cache.bytes <= cache.budget


def test_both_spans_attributes_reach_explain_analyze():
    eng = engine()
    eng.query(SCAN)
    text = "\n".join(eng.query("explain analyze " + SCAN)["plan"])
    up = next(ln for ln in text.splitlines() if "superblock-upload" in ln)
    for attr in ("columns=3", "sources=", "bytes=0", "hit=True"):
        assert attr in up, up
    adm = next(ln for ln in text.splitlines() if "admission-wait" in ln)
    for attr in ("admitted_mb=", "in_flight_mb=0", "waited=False"):
        assert attr in adm, adm


def test_a_statement_that_queued_behind_a_full_budget_says_it_waited():
    eng = engine()
    eng.query(SCAN)                                     # planned, compiled
    seen = []

    def one():
        eng.query(SCAN)
        seen.append((dict(eng.last_stats.phases),
                     dict(span(eng, "admission-wait").attrs)))

    t = threading.Thread(target=one)
    with eng.admission.admit(eng.admission.budget):     # the budget is taken
        t.start()
        time.sleep(0.05)                                # it queues behind us
    t.join(timeout=30)
    assert not t.is_alive() and len(seen) == 1
    phases, attrs = seen[0]
    assert attrs["waited"] is True
    assert attrs["in_flight_mb"] == eng.admission.budget >> 20
    assert phases["admission_ms"] >= 40
    assert eng.admission.in_flight == 0 and eng.admission.active == 0
    eng.query(SCAN)                                     # alone again
    assert span(eng, "admission-wait").attrs["waited"] is False
