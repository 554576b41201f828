"""Network query service + CLI: the public API surface end-to-end.

The analog of the reference's gRPC functional tests (`ydb/tests/functional/
api`): real SQL over a real gRPC channel against an in-process server,
including per-connection transaction sessions.
"""

import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.server import Client, serve


@pytest.fixture(scope="module")
def endpoint():
    eng = QueryEngine(block_rows=1 << 13)
    server, port = serve(eng, port=0)
    yield f"127.0.0.1:{port}"
    server.stop(grace=None)


def test_ping_and_ddl_dml_roundtrip(endpoint):
    c = Client(endpoint)
    assert c.ping()
    c.execute("create table t (id Int64 not null, tag Utf8, primary key (id))")
    c.execute("insert into t (id, tag) values (1, 'a'), (2, 'b'), (3, null)")
    df = c.query("select id, tag from t order by id")
    assert list(df.id) == [1, 2, 3]
    import pandas as pd
    assert list(df.tag[:2]) == ["a", "b"] and pd.isna(df.tag[2])
    resp = c.execute("select count(*) as n from t")
    assert resp["rows"] == [[3]]
    assert resp["stats"]["rows_out"] == 1
    assert resp["stats"]["path"] in ("fused", "portioned")


def test_error_propagation(endpoint):
    c = Client(endpoint)
    with pytest.raises(RuntimeError, match="unknown table"):
        c.query("select * from missing_table")


def test_session_scoped_transactions(endpoint):
    c1 = Client(endpoint, session_id="s1")
    c2 = Client(endpoint, session_id="s2")
    c1.execute("""create table acct (id Int64 not null, bal Int64 not null,
                  primary key (id)) with (store = row)""")
    c1.execute("insert into acct (id, bal) values (1, 100), (2, 100)")
    c1.execute("begin")
    c1.execute("update acct set bal = bal - 25 where id = 1")
    # other session can't see the staged write
    assert list(c2.query("select bal from acct order by id").bal) == [100, 100]
    # the owning session can
    assert list(c1.query("select bal from acct order by id").bal) == [75, 100]
    c1.execute("commit")
    assert list(c2.query("select bal from acct order by id").bal) == [75, 100]


def test_counters_endpoint(endpoint):
    c = Client(endpoint)
    c.query("select 1 + 1 as two") if False else None
    counters = c.counters()
    assert counters["engine/statements"] >= 1


def test_cli_embedded_sql(capsys):
    from ydb_tpu.cli import main
    rc = main(["workload", "tpch", "run", "--queries", "q6", "--repeat", "1",
               "--sf", "0.002"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "q6" in out and "geomean" in out


def test_cli_workload_run_fails_when_a_query_fails(capsys, monkeypatch):
    """A query that fails is printed AND makes the run exit non-zero —
    a benchmark runner must not report a broken suite as a success."""
    from ydb_tpu import cli
    monkeypatch.setattr(
        cli, "_workload_queries",
        lambda _w, _n: {"q6": "select count(*) as n from lineitem",
                        "bad": "select * from no_such_table"})
    rc = cli.main(["workload", "tpch", "run", "--repeat", "1",
                   "--sf", "0.002"])
    cap = capsys.readouterr()
    assert rc == 1
    assert "bad: FAILED" in cap.out and "q6" in cap.out
    assert "1 of 2 queries FAILED: bad" in cap.err
