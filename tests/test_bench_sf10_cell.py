"""The one-chip cell `tpch-sf10.scan` rehearsed whole (PR 33):
`benchmark/run.py`'s own `run_cell` at sf 0.01, untraced, traced, and with
an answer altered where it is produced.

One child process makes the three runs (the platform requirement is the
child's to relax, never an option of the benchmark). A CPU run shows
paths, counts and verdicts, never a speed.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
CELL = "tpch-sf10.scan"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW_METRICS = ("admission_wait_ms", "devcache_upload_mb_in_window")

DRIVER = """
import json, sys
sys.path.insert(0, {bench!r})
import devices
devices.REQUIRED_PLATFORM = "cpu"          # the test's, never an option
import proxy, run
seen = []
real_match = proxy.match
def match(samples, calls):
    real_match(samples, calls)
    # the paths of the window's engine calls themselves: `proxy.match` can
    # leave a sample without its call where two streams send one text at
    # once (PERF.md section 7), and every statement has to be held
    seen.append({{"paths": sorted({{c.path for c in calls}}),
                 "calls": len(calls), "samples": len(samples),
                 "streams": sorted({{s.stream for s in samples}}),
                 "queries": sorted({{s.item.query for s in samples}}),
                 "items": len({{s.item for s in samples}})}})
proxy.match = match
small = {{"sf": 0.01}}
out = {{"untraced": run.run_cell({cell!r}, 2**31 + 33, 1.0, False,
                                overrides=small),
       "traced": run.run_cell({cell!r}, 33, 1.0, True, overrides=small)}}

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
out["seen"] = seen
for r in (out["untraced"], out["traced"], out["fault"]):
    r.pop("breakdown", None)
print(json.dumps(out))
"""


def reported(section: str) -> set:
    return {m["name"] for m in BENCHMARK[section]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    driver = tmp_path_factory.mktemp("sf10-cell") / "driver.py"
    driver.write_text(DRIVER.format(bench=str(ROOT / "benchmark"),
                                    cell=CELL))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_is_the_sf10_database_on_one_chip_at_two_streams():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    cfg = next(c for c in BENCHMARK["configs"]
               if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "scan"
    assert len(cfg["source"]) <= 200 and len(cell["why"]) <= 200
    conf = json.loads((ROOT / cfg["file"]).read_text())
    assert conf["name"] == "tpch-sf10" and conf["source"] == cfg["source"]
    assert conf["sf"] == 10.0 and conf["chips"] == conf["shards"] == 1
    assert conf["portion_rows"] == 1048576 and conf["store"] == "column"
    # nothing of the deployment rests on an engine attribute or a lever
    assert conf["engine_attrs"] == {} and conf["env"] == {}
    sf1 = json.loads((ROOT / "benchmark/configs/tpch-sf1.json").read_text())
    assert conf["tables"] == sf1["tables"] and len(conf["tables"]) == 8
    assert conf["guarantees"] == sf1["guarantees"]
    assert list(conf["reduced"]) == cfg["reduced"] == [
        "query_set", "streams", "refresh_functions", "power_test"]
    assert {"generator", "size"} <= set(conf["assumed"])
    # the traffic the issue names, to the letter: the builder does not
    # change it to make a counter move
    mix = json.loads(
        (ROOT / "benchmark/workloads" / f"{CELL}.json").read_text())
    assert mix == {"config": "tpch-sf10", "front": "pgwire",
                   "loop": "closed", "streams": 2, "family": "tpch",
                   "queries": ["q1", "q6"], "param_sets": 2,
                   "expected_path": "fused"}


def test_the_cell_reports_what_the_sf1_scan_cell_reports_but_its_tail():
    def of(cell: str, section: str) -> set:
        return {m["name"] for m in BENCHMARK[section]
                if cell in m.get("workloads", [cell])}
    assert of(CELL, "per_layer") == of("tpch-sf1.scan", "per_layer") \
        | set(NEW_METRICS)
    assert of(CELL, "end_to_end") == of("tpch-sf1.scan", "end_to_end") \
        - {"latency_p90_ms"}
    for m in BENCHMARK["per_layer"]:
        if m["name"] in NEW_METRICS:
            # the cell they came with; later cells join the list
            # (`tpch-sf10-batched.scan-burst`, PR 36)
            assert m["workloads"][0] == CELL


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_rehearsal_is_correct(runs, run):
    r = runs[run]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 4
    assert r["device"]["count"] == 1
    assert r["compared"]["max_rel_err"]["value"] <= 1e-9
    assert r["compared"]["unanswered"]["value"] == 0
    assert r["compared"]["wrong_answers"]["value"] == 0


def test_two_streams_send_both_queries_and_every_statement_is_fused(runs):
    assert len(runs["seen"]) == 3
    for window in runs["seen"]:
        assert window["paths"] == ["fused"]
        assert window["calls"] == window["samples"] >= 4
        assert window["streams"] == [0, 1]
        assert window["queries"] == ["q1", "q6"]
        assert window["items"] == 4           # 2 literal sets a query


def test_untraced_run_reports_the_cells_end_to_end_metrics(runs):
    assert set(runs["untraced"]["metrics"]) == reported("end_to_end") == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in runs["untraced"]["metrics"].values())


def test_traced_run_reports_the_cells_layers_and_both_new_metrics(runs):
    got = runs["traced"]["metrics"]
    # a CPU trace has no device plane: the device's two readers find
    # nothing to read and are left out
    assert set(got) == reported("per_layer") - {"programs_roofline",
                                                "device_idle_pct"}
    assert got["admission_wait_ms"]["unit"] == "ms"
    assert 0 <= got["admission_wait_ms"]["value"] < 5
    # the resident set holds: nothing is uploaded inside the window
    assert got["devcache_upload_mb_in_window"] == {"value": 0.0,
                                                   "unit": "MiB"}
    for name in ("compiles_in_window", "compact_reruns_in_window"):
        assert got[name]["value"] == 0
    # the span round a lookup that finds every column resident
    assert got["upload_ms"]["value"] < 1.0
    assert got["latemat_direct_pct"]["value"] > 50


def test_an_altered_answer_is_not_correct(runs):
    r = runs["fault"]
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    c = r["compared"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_the_new_readers_leave_out_what_a_program_does_not_count():
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import traffic
        uploaded, wait = (traffic.load_module("metrics", n).read
                          for n in reversed(NEW_METRICS))
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    # the parent's program has no `devcache/*`: left out, never a 0
    assert uploaded({"setup_counters": {"prog/registered": 4},
                     "window_counters": {}}) is None
    warm = {"devcache/upload_bytes": 3 << 30}
    assert uploaded({"setup_counters": warm, "window_counters": {}}) == 0
    assert uploaded({"setup_counters": warm, "window_counters": {
        "devcache/upload_bytes": 3 << 20}}) == 3.0
    assert wait({"samples": []}) is None
