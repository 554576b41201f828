"""Distributed two-phase commit with crash injection.

round-4 review #4 Done criterion: kill -9 a worker between prepare and
commit — recovery must leave both workers consistent either way. Two
durable worker PROCESSES, a router with a durable decision log, fault
points armed via YDB_TPU_TEST_FAULTS (the nemesis shape of the
reference's deterministic test runtime, `test_runtime.h` event
interception — here as os._exit at protocol points)."""

import os
import subprocess
import sys
import time

import pytest

pytest.importorskip("grpc")

from ydb_tpu.cluster import ShardedCluster  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Workers:
    def __init__(self, root):
        self.root = root
        self.procs = {}
        self.ports = {}

    def spawn(self, wid: int, port: int = 0):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                   YDB_TPU_TEST_FAULTS="1")
        env.pop("XLA_FLAGS", None)
        pf = self.root / f"port{wid}"
        if pf.exists():
            pf.unlink()
        p = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "dtx_worker.py"),
             str(self.root / f"w{wid}"), str(pf)]
            + ([str(port)] if port else []),
            env=env, cwd=REPO)
        deadline = time.time() + 120
        while not pf.exists() or not pf.read_text().strip():
            if p.poll() is not None:
                raise RuntimeError(f"worker {wid} died: {p.returncode}")
            if time.time() > deadline:
                raise RuntimeError("worker startup timed out")
            time.sleep(0.3)
        self.procs[wid] = p
        self.ports[wid] = int(pf.read_text())
        return self.ports[wid]

    def wait_dead(self, wid: int, timeout=30):
        self.procs[wid].wait(timeout=timeout)

    def stop(self):
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.fixture()
def cluster(tmp_path):
    ws = _Workers(tmp_path)
    for wid in range(2):
        ws.spawn(wid)
    # decision log mirrored to a standby sink (review weakness #11): a lost
    # router disk must not strand prepared workers in-doubt
    c = ShardedCluster([f"127.0.0.1:{ws.ports[i]}" for i in range(2)],
                       dtx_log=str(tmp_path / "router_dtx.jsonl"),
                       dtx_replica=str(tmp_path / "standby"))
    c._ws = ws
    c._standby = tmp_path / "standby"
    yield c
    ws.stop()


def _counts(c):
    return [int(w.execute("select count(*) as n from kv")["rows"][0][0])
            for w in c.workers]


def test_2pc_commit_and_crash_recovery(cluster):
    c = cluster
    ws = c._ws
    c.execute("create table kv (id Int64 not null, v Int64 not null, "
              "primary key (id)) with (store = row)")

    # 1. plain 2PC spanning both workers
    rows = ", ".join(f"({i}, {i})" for i in range(20))
    r = c.execute(f"upsert into kv (id, v) values {rows}")
    assert r["ok"] and not r.get("healed_later")
    n0 = _counts(c)
    assert sum(n0) == 20 and all(n > 0 for n in n0)

    # 2. kill -9 worker 1 BEFORE it applies the commit decision
    victim = c.workers[1].endpoint
    c.dtx_test_crash = {victim: "before_apply"}
    rows = ", ".join(f"({i}, {i})" for i in range(20, 40))
    r = c.execute(f"upsert into kv (id, v) values {rows}")
    assert r["healed_later"]
    ws.wait_dead(1)
    # restart on the SAME port (clients keep their endpoints), re-deliver
    ws.spawn(1, port=ws.ports[1])
    c.dtx_test_crash = {}
    healed = c.resolve_in_doubt()
    assert healed["resolved"] >= 1
    n1 = _counts(c)
    assert sum(n1) == 40, n1            # no lost committed writes

    # 3. kill -9 worker 1 AFTER the local apply, before the done mark:
    #    resolve re-executes; UPSERT idempotence must not duplicate
    c.dtx_test_crash = {victim: "after_apply"}
    rows = ", ".join(f"({i}, {i})" for i in range(40, 60))
    r = c.execute(f"upsert into kv (id, v) values {rows}")
    assert r["healed_later"]
    ws.wait_dead(1)
    ws.spawn(1, port=ws.ports[1])
    c.dtx_test_crash = {}
    c.resolve_in_doubt()
    n2 = _counts(c)
    assert sum(n2) == 60, n2            # exactly once despite the replay

    # 4. prepare-time crash → presumed abort: no partial writes anywhere
    c.dtx_test_crash = {victim: "after_prepare"}
    # arm the PREPARE crash: tx_prepare honors the same request hook
    orig = type(c.workers[0]).tx_prepare
    def prep(self, gtx, sqls, **extra):
        if self.endpoint == victim:
            extra["crash_point"] = "after_prepare"
        return orig(self, gtx, sqls, **extra)
    type(c.workers[0]).tx_prepare = prep
    try:
        rows = ", ".join(f"({i}, {i})" for i in range(60, 80))
        try:
            c.execute(f"upsert into kv (id, v) values {rows}")
            raised = False
        except Exception:                # noqa: BLE001 — expected abort
            raised = True
        assert raised
    finally:
        type(c.workers[0]).tx_prepare = orig
        c.dtx_test_crash = {}
    ws.wait_dead(1)
    ws.spawn(1, port=ws.ports[1])
    c.resolve_in_doubt()                 # unknown gtx → presumed abort
    n3 = _counts(c)
    assert sum(n3) == 60, n3            # the aborted tx left nothing


def test_standby_decision_log_recovers_lost_router_disk(cluster, tmp_path):
    """review weakness #11: the decision log mirrors synchronously to the
    standby sink, so losing the router's disk mid-commit no longer
    strands prepared workers — a NEW router booted from the standby copy
    re-delivers the logged decision."""
    import json

    c = cluster
    ws = c._ws
    c.execute("create table kv (id Int64 not null, v Int64 not null, "
              "primary key (id)) with (store = row)")
    rows = ", ".join(f"({i}, {i})" for i in range(20))
    assert c.execute(f"upsert into kv (id, v) values {rows}")["ok"]

    # wedge worker 1 in-doubt: killed before applying the commit decision
    victim = c.workers[1].endpoint
    c.dtx_test_crash = {victim: "before_apply"}
    rows = ", ".join(f"({i}, {i})" for i in range(20, 40))
    assert c.execute(f"upsert into kv (id, v) values {rows}")["healed_later"]
    ws.wait_dead(1)
    ws.spawn(1, port=ws.ports[1])

    # the standby mirror carries the commit decision the primary logged
    mirror = c._standby / "router_dtx.jsonl"
    assert mirror.exists()
    recs = [json.loads(ln) for ln in mirror.read_text().splitlines()]
    assert any(r.get("decision") == "commit" for r in recs)

    # lost router disk: the primary log is GONE; a fresh router boots
    # with the standby copy as its decision log and heals the worker
    (tmp_path / "router_dtx.jsonl").unlink()
    c2 = ShardedCluster([w.endpoint for w in c.workers],
                        dtx_log=str(mirror))
    healed = c2.resolve_in_doubt()
    assert healed["resolved"] >= 1 and not healed["unreachable"]
    n = _counts(c2)
    assert sum(n) == 40, n              # the in-doubt commit landed
