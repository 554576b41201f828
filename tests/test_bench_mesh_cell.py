"""The four-chip cell `tpch-sf1-mesh4.shuffle` rehearsed whole (PR 28):
`benchmark/run.py`'s own `run_cell` over four virtual CPU devices at sf
0.01, untraced, traced, and with an answer altered where it is produced.

One child process makes the three runs (the platform requirement is the
child's to relax, never an option of the benchmark), so the parent's eight
virtual devices and compiled programs stay out of it. A CPU run shows
paths, counts and verdicts, never a speed.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
CELL = "tpch-sf1-mesh4.shuffle"
LANE = "distributed-shuffle-join"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())

DRIVER = """
import json, sys
sys.path.insert(0, {bench!r})
import devices
devices.REQUIRED_PLATFORM = "cpu"          # the test's, never an option
import proxy, run
paths = []
real_match = proxy.match
def match(samples, calls):
    real_match(samples, calls)
    paths.append(sorted({{s.call.path if s.call else "?" for s in samples}}))
proxy.match = match
small = {{"sf": 0.01}}
out = {{"untraced": run.run_cell({cell!r}, 2**31 + 28, 1.0, False,
                                overrides=small),
       "traced": run.run_cell({cell!r}, 28, 1.0, True, overrides=small)}}

# a float cell a millionth off in every answer, where it is produced
# (the means of benchmark/tests/test_bench_rehearsal.py)
from ydb_tpu.query.engine import QueryEngine
real = QueryEngine.execute
def nudged(self, sql, *a, **kw):
    block = real(self, sql, *a, **kw)
    if "count(*) as n from" in sql:
        return block
    for c in block.schema.columns:
        data = block.columns[c.name].data
        if data.dtype.kind == "f" and len(data):
            data = data.copy()
            data[0] *= 1 + 1e-6
            block.columns[c.name].data = data
            break
    return block
QueryEngine.execute = nudged
out["fault"] = run.run_cell({cell!r}, 7, 1.0, False, overrides=small)
out["paths"] = paths
for r in (out["untraced"], out["traced"], out["fault"]):
    r.pop("breakdown", None)
print(json.dumps(out))
"""


def reported(section: str) -> set:
    return {m["name"] for m in BENCHMARK[section]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    driver = tmp_path_factory.mktemp("mesh-cell") / "driver.py"
    driver.write_text(DRIVER.format(bench=str(ROOT / "benchmark"),
                                    cell=CELL))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_is_a_four_chip_cell_of_its_own_configuration():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    cfg = next(c for c in BENCHMARK["configs"]
               if c["name"] == cell["config"])
    assert cell["chips"] == 4 and cell["traffic"] == "shuffle"
    assert len(cfg["source"]) <= 200 and len(cell["why"]) <= 200
    conf = json.loads((ROOT / cfg["file"]).read_text())
    assert conf["chips"] == conf["shards"] == 4 and conf["sf"] == 1.0
    # the second is the program's default, stated: the attribute arrives
    # with the counted segments, so `run.build_engine` stops a program
    # without them (it cannot serve the cell inside a run's limit) at once
    from ydb_tpu.query import QueryEngine
    assert conf["engine_attrs"] == {
        "executor.dist_broadcast_budget_bytes": 1,
        "executor.mesh_min_segment_rows":
            QueryEngine().executor.mesh_min_segment_rows}
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    one_chip = json.loads(
        (ROOT / "benchmark/configs/tpch-sf1.json").read_text())
    assert {k: v for k, v in conf["guarantees"].items()
            if k != "placement"} == one_chip["guarantees"]
    mix = json.loads(
        (ROOT / "benchmark/workloads" / f"{CELL}.json").read_text())
    assert mix["queries"] == ["q3"] and mix["expected_path"] == LANE


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_rehearsal_is_correct_on_the_shuffle_lane(runs, run):
    r = runs[run]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert r["device"]["count"] == 4
    assert r["compared"]["max_rel_err"]["value"] <= 1e-9
    assert all(p == [LANE] for p in runs["paths"]) and len(runs["paths"]) == 3


def test_untraced_run_reports_the_end_to_end_metrics(runs):
    assert set(runs["untraced"]["metrics"]) == reported("end_to_end") == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in runs["untraced"]["metrics"].values())


def test_traced_run_reports_the_mesh_layer(runs):
    got = runs["traced"]["metrics"]
    # a CPU trace has no device plane: the device's two readers find
    # nothing to read and are left out
    assert set(got) == reported("per_layer") - {"programs_roofline",
                                                "device_idle_pct"}
    for name in ("exchange_ms", "mesh_build_ms", "mesh_stage_ms",
                 "program_ms", "device_queue_ms", "readout_ms"):
        assert got[name]["value"] >= 0
    assert 0 < got["exchange_pad_pct"]["value"] < 100
    assert got["compiles_in_window"]["value"] == 0
    p50 = runs["untraced"]["metrics"]["latency_p50_ms"]["value"]
    assert 0 <= got["unspanned_ms"]["value"] < 0.1 * p50


def test_an_altered_answer_is_not_correct(runs):
    r = runs["fault"]
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    c = r["compared"]["max_rel_err"]
    assert c["value"] > c["limit"]
