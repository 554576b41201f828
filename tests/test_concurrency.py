"""Concurrent query execution: lock-free readers over MVCC snapshots.

round-3 review item 2: the r3 engine held ONE lock around every statement
from every front. Now SELECTs run concurrently (the session-actor model —
`kqp_session_actor.cpp:128` runs thousands of sessions; here a thread per
session), writers serialize on the engine write lock, and memory
admission (`query/admission.py`, the `kqp_rm_service.h:68` analog) queues
queries when the device is oversubscribed.
"""

import threading
import time

import numpy as np
import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.query.admission import AdmissionTimeout, MemoryAdmission
from ydb_tpu.query.engine import QueryError


def _mk_engine(rows: int = 60_000) -> QueryEngine:
    e = QueryEngine(block_rows=1 << 12)
    e.execute("create table t (id Int64 not null, k Int64 not null, "
              "v Double not null, primary key (id)) with (store = column)")
    for lo in range(0, rows, 20_000):
        n = min(20_000, rows - lo)
        vals = ",".join(f"({i},{i % 13},{i * 0.25})"
                        for i in range(lo, lo + n))
        e.execute(f"insert into t (id, k, v) values {vals}")
    return e


def test_concurrent_selects_in_flight():
    """>1 reader genuinely in flight at once (the old design serialized
    every statement on one lock)."""
    eng = _mk_engine()
    eng.query("select k, sum(v) as s from t group by k")  # compile warm-up

    active = [0]
    max_active = [0]
    mu = threading.Lock()
    # the engine drives the pipelined seam now: SELECT dispatches go
    # through execute_async (readout resolves the returned future)
    orig = eng.executor.execute_async

    def instrumented(plan, snapshot):
        with mu:
            active[0] += 1
            max_active[0] = max(max_active[0], active[0])
        try:
            # hold the overlap window open long enough for peers to enter
            time.sleep(0.05)
            return orig(plan, snapshot)
        finally:
            with mu:
                active[0] -= 1

    eng.executor.execute_async = instrumented
    errs = []
    want_sum = sum(i * 0.25 for i in range(60_000))

    def reader():
        try:
            for _ in range(3):
                df = eng.query("select k, sum(v) as s from t group by k")
                assert len(df) == 13
                np.testing.assert_allclose(df.s.sum(), want_sum, rtol=1e-9)
        except Exception as e:               # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=reader) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert max_active[0] >= 2, \
        f"readers serialized: max in flight {max_active[0]}"


def test_readers_never_see_partial_commits():
    """Writers serialize; readers at MVCC snapshots see whole committed
    batches only (linearizable counts: multiples of the batch size,
    non-decreasing per reader)."""
    eng = QueryEngine(block_rows=1 << 10)
    eng.execute("create table w (id Int64 not null, primary key (id)) "
                "with (store = column)")
    BATCH, BATCHES = 500, 10
    eng.query("select count(*) as c from w")     # warm the plan/compile
    stop = threading.Event()
    errs = []

    def writer():
        try:
            for b in range(BATCHES):
                vals = ",".join(f"({i})" for i in
                                range(b * BATCH, (b + 1) * BATCH))
                eng.execute(f"insert into w (id) values {vals}")
        except Exception as e:               # noqa: BLE001
            errs.append(e)
        finally:
            stop.set()

    def reader():
        last = 0
        try:
            while not stop.is_set():
                c = int(eng.query("select count(*) as c from w").c[0])
                assert c % BATCH == 0, f"partial batch visible: {c}"
                assert c >= last, f"count went backwards: {last} -> {c}"
                last = c
        except Exception as e:               # noqa: BLE001
            errs.append(e)

    rs = [threading.Thread(target=reader) for _ in range(3)]
    wt = threading.Thread(target=writer)
    for t in rs:
        t.start()
    wt.start()
    wt.join()
    for t in rs:
        t.join()
    assert not errs, errs
    assert int(eng.query("select count(*) as c from w").c[0]) \
        == BATCH * BATCHES


def test_optimistic_lock_under_real_threads():
    """Two racing read-modify-write transactions: exactly the committed
    increments land (no lost updates — optimistic locks abort the loser)."""
    eng = QueryEngine(block_rows=1 << 10)
    eng.execute("create table acct (id Int64 not null, bal Int64 not null, "
                "primary key (id)) with (store = row)")
    eng.execute("insert into acct (id, bal) values (1, 0)")
    committed = []
    mu = threading.Lock()

    def actor(n):
        done = 0
        attempts = 0
        # retry on abort: under heavy CPU contention pure optimism can
        # livelock every actor — the invariant under test is NO LOST
        # UPDATES, not wait-freedom
        while done < 4 and attempts < 60:
            attempts += 1
            s = eng.session()
            try:
                s.execute("begin")
                bal = int(s.query("select bal from acct where id = 1"
                                  ).bal[0])
                s.execute(f"update acct set bal = {bal + 1} where id = 1")
                s.execute("commit")
                with mu:
                    committed.append(n)
                done += 1
            except QueryError:
                try:
                    s.execute("rollback")
                except QueryError:
                    pass
            time.sleep(0.001)

    ts = [threading.Thread(target=actor, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    final = int(eng.query("select bal from acct where id = 1").bal[0])
    assert final == len(committed), (final, len(committed))
    assert final >= 1


def test_memory_admission_queue_and_timeout():
    adm = MemoryAdmission(1000, timeout_s=0.2)
    with adm.admit(800):
        # fits alongside
        with adm.admit(100):
            assert adm.in_flight == 900
        # does not fit → queues → times out
        t0 = time.monotonic()
        with pytest.raises(AdmissionTimeout):
            with adm.admit(300):
                pass
        assert time.monotonic() - t0 >= 0.15
    # oversize estimates clamp to the whole budget (run solo, no deadlock)
    with adm.admit(10**12):
        assert adm.in_flight == 1000


def test_admission_wires_into_selects():
    eng = _mk_engine(5_000)
    eng.query("select count(*) as c from t")
    from ydb_tpu.utils.metrics import GLOBAL
    # shrink the budget so the next query must wait on a fake occupant
    eng.admission = MemoryAdmission(100, timeout_s=0.1)
    with eng.admission.admit(100):
        with pytest.raises(QueryError, match="admission"):
            eng.query("select count(*) as c from t")
    assert GLOBAL.snapshot().get("admission/timeouts", 0) >= 1
    # and with room, queries flow
    df = eng.query("select count(*) as c from t")
    assert df.c[0] == 5_000


def test_concurrent_grpc_sessions():
    """Mixed read/write load through the gRPC front's thread pool."""
    pytest.importorskip("grpc")
    from ydb_tpu.server import Client, serve
    eng = QueryEngine(block_rows=1 << 10)
    eng.execute("create table g (id Int64 not null, v Int64 not null, "
                "primary key (id)) with (store = column)")
    eng.execute("insert into g (id, v) values (0, 0)")
    server, port = serve(eng, port=0)
    errs = []

    def client_thread(n):
        try:
            c = Client(f"127.0.0.1:{port}", session_id=f"s{n}")
            base = (n + 1) * 1000
            for i in range(5):
                c.execute(f"insert into g (id, v) values ({base + i}, {n})")
                rows = c.execute("select count(*) as c from g")["rows"]
                assert rows[0][0] >= 1 + i + 1 - 1
            c.close()
        except Exception as e:               # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=client_thread, args=(i,))
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    server.stop(0)
    assert not errs, errs
    assert int(eng.query("select count(*) as c from g").c[0]) == 1 + 4 * 5
