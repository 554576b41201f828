"""Config system (YAML + selector overrides + feature flags) and the
health endpoint.

Reference: `ydb/library/yaml_config` (selector/override resolution),
`ydb/core/base/feature_flags.h` (gates on real paths), and
`ydb/core/health_check/health_check.cpp` (aggregated health API).
"""

import pytest

from ydb_tpu.query import QueryEngine
from ydb_tpu.utils.config import Config


def test_config_defaults_and_flags():
    c = Config()
    assert c.block_rows == 1 << 20
    assert c.flag("enable_fused") and c.flag("enable_plan_cache")
    with pytest.raises(KeyError):
        c.flag("enable_warp_drive")


def test_config_selector_overrides():
    doc = {
        "block_rows": 4096,
        "feature_flags": {"enable_fused": True},
        "overrides": [
            {"selector": {"env": "test"},
             "config": {"block_rows": 1024,
                        "feature_flags": {"enable_fused": False}}},
            {"selector": {"env": "prod"},
             "config": {"block_rows": 1 << 21}},
        ],
    }
    base = Config.from_dict(doc)
    assert base.block_rows == 4096 and base.flag("enable_fused")
    test = Config.from_dict(doc, labels={"env": "test"})
    assert test.block_rows == 1024 and not test.flag("enable_fused")
    prod = Config.from_dict(doc, labels={"env": "prod"})
    assert prod.block_rows == 1 << 21 and prod.flag("enable_fused")


def test_config_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown config"):
        Config.from_dict({"block_rowz": 1})
    with pytest.raises(ValueError, match="unknown feature flags"):
        Config.from_dict({"feature_flags": {"nope": True}})


def test_config_yaml_roundtrip(tmp_path):
    p = tmp_path / "conf.yaml"
    p.write_text("block_rows: 2048\n"
                 "feature_flags:\n  enable_plan_cache: false\n")
    c = Config.load(str(p))
    assert c.block_rows == 2048 and not c.flag("enable_plan_cache")


def test_flags_gate_real_paths():
    c = Config.from_dict({
        "block_rows": 1024,
        "feature_flags": {"enable_fused": False,
                          "enable_plan_cache": False}})
    eng = QueryEngine(config=c)
    assert eng.executor.block_rows == 1024
    eng.execute("create table t (id Int64 not null, v Double, "
                "primary key (id))")
    eng.execute("insert into t (id, v) values (1, 1.0), (2, 2.0)")
    df = eng.query("select sum(v) as s from t")
    assert float(df.s[0]) == 3.0
    assert eng.executor.last_path == "portioned"   # fused disabled
    eng.query("select sum(v) as s from t")
    assert eng.plan_cache_hits == 0                # cache disabled


def test_health_endpoint():
    from ydb_tpu.server import Client, serve
    eng = QueryEngine(block_rows=1 << 10)
    eng.execute("create table t (id Int64 not null, primary key (id))")
    eng.create_topic("tp")
    server, port = serve(eng, port=0)
    try:
        c = Client(f"127.0.0.1:{port}")
        h = c.health()
        assert h["status"] == "GOOD"
        assert h["tables"] == 1 and h["topics"] == 1
        assert h["durable"] is False
        assert h["platform"] in ("cpu", "tpu")
        assert h["uptime_s"] >= 0
    finally:
        server.stop(0)
