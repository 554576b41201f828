"""Replication v1: synchronous store mirroring + standby failover.

round-4 review #7 Done criterion: kill the primary, boot from the standby,
recover to the last committed step — tests pin that no committed write
is lost, across row and column stores, compaction rewrites, delete
marks, and DDL."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from ydb_tpu.cluster.replica import DirSink, GrpcSink, StandbyServer
from ydb_tpu.query import QueryEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dir_mirror_failover(tmp_path):
    """Same-host mirror: every committed write present after promoting
    the mirror directory."""
    prim = str(tmp_path / "primary")
    stby = str(tmp_path / "standby")
    eng = QueryEngine(block_rows=1 << 10, data_dir=prim,
                      replica=DirSink(stby))
    eng.execute("create table t (id Int64 not null, tag Utf8, v Double, "
                "primary key (id))")
    eng.execute("create table r (id Int64 not null, v Int64 not null, "
                "primary key (id)) with (store = row)")
    for lo in range(0, 300, 100):
        rows = ", ".join(f"({i}, 'g{i % 7}', {i * 0.5})"
                         for i in range(lo, lo + 100))
        eng.execute(f"insert into t (id, tag, v) values {rows}")
    eng.execute("insert into r (id, v) values " +
                ", ".join(f"({i}, {i})" for i in range(50)))
    eng.execute("delete from t where id >= 290")
    eng.execute("update r set v = v + 1000 where id < 10")
    want_t = eng.query("select count(*) as n, sum(v) as s from t")
    want_r = eng.query("select sum(v) as s from r")
    # primary "dies" here (no clean shutdown) — promote the standby
    del eng
    e2 = QueryEngine(block_rows=1 << 10, data_dir=stby)
    got_t = e2.query("select count(*) as n, sum(v) as s from t")
    got_r = e2.query("select sum(v) as s from r")
    assert int(got_t.n[0]) == int(want_t.n[0]) == 290
    assert np.isclose(got_t.s[0], want_t.s[0])
    assert int(got_r.s[0]) == int(want_r.s[0])
    # the promoted engine is fully writable
    e2.execute("insert into t (id, tag, v) values (1000, 'x', 1.0)")
    assert int(e2.query("select count(*) as n from t").n[0]) == 291


def test_grpc_standby_failover(tmp_path):
    """Cross-process standby over the Replica gRPC front, with a
    mid-stream SIGKILL of the primary process."""
    stby_root = str(tmp_path / "standby")
    standby = StandbyServer(stby_root, port=0)
    prim_root = str(tmp_path / "primary")

    # the primary runs in a SUBPROCESS so we can kill -9 it mid-write;
    # it prints a line per committed batch
    code = f"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
from ydb_tpu.query import QueryEngine
eng = QueryEngine(block_rows=1 << 10, data_dir={prim_root!r},
                  replica="127.0.0.1:{standby.port}")
eng.execute("create table t (id Int64 not null, v Double, primary key (id))")
for b in range(1000):
    rows = ", ".join(f"({{i}}, {{i}}.5)" for i in range(b * 10, b * 10 + 10))
    eng.execute(f"insert into t (id, v) values {{rows}}")
    print(f"committed {{b}}", flush=True)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    p = subprocess.Popen([sys.executable, "-c", code], env=env, cwd=REPO,
                         stdout=subprocess.PIPE, text=True)
    committed = -1
    deadline = time.time() + 180
    try:
        while committed < 12:
            line = p.stdout.readline()
            if not line:
                raise RuntimeError("primary exited early")
            if line.startswith("committed"):
                committed = int(line.split()[1])
            if time.time() > deadline:
                raise RuntimeError("primary too slow")
        p.send_signal(signal.SIGKILL)      # die mid-stream, no shutdown
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
    standby.stop()

    # promote: every batch the primary ACKNOWLEDGED (printed) must be
    # present — synchronous shipping means ack ⇒ on the standby
    e2 = QueryEngine(block_rows=1 << 10, data_dir=stby_root)
    n = int(e2.query("select count(*) as n from t").n[0])
    assert n >= (committed + 1) * 10, (n, committed)
    # and the standby is consistent (contiguous prefix of batches + at
    # most one trailing partial batch's rows, never torn inside a batch)
    ids = e2.query("select id from t order by id").id.to_numpy()
    assert list(ids[:n]) == list(range(len(ids)))


def test_replica_survives_compaction_and_ddl(tmp_path):
    """Compaction rewrites/unlinks and DDL drops ship too — the standby
    tracks the whole lifecycle, not just appends."""
    prim = str(tmp_path / "p2")
    stby = str(tmp_path / "s2")
    eng = QueryEngine(block_rows=1 << 10, data_dir=prim,
                      replica=DirSink(stby))
    eng.execute("create table c (id Int64 not null, primary key (id)) "
                "with (partitions = 1)")
    for i in range(20):   # many small portions → auto-compaction folds
        eng.execute(f"insert into c (id) values ({i})")
    eng.execute("create table dropme (id Int64 not null, primary key (id))")
    eng.execute("drop table dropme")
    del eng
    e2 = QueryEngine(block_rows=1 << 10, data_dir=stby)
    assert int(e2.query("select count(*) as n from c").n[0]) == 20
    assert not e2.catalog.has("dropme")


def test_replica_bootstrap_pre_existing_store(tmp_path):
    """A standby attached to a store that ALREADY holds data gets a full
    initial sync — manifests must never reference blobs the standby
    never received."""
    prim = str(tmp_path / "p3")
    eng = QueryEngine(block_rows=1 << 10, data_dir=prim)
    eng.execute("create table t (id Int64 not null, primary key (id))")
    eng.execute("insert into t (id) values " +
                ", ".join(f"({i})" for i in range(30)))
    del eng
    stby = str(tmp_path / "s3")
    eng = QueryEngine(block_rows=1 << 10, data_dir=prim,
                      replica=DirSink(stby))   # attach late → full sync
    eng.execute("insert into t (id) values (100)")
    del eng
    e2 = QueryEngine(block_rows=1 << 10, data_dir=stby)
    assert int(e2.query("select count(*) as n from t").n[0]) == 31
