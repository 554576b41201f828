"""The cell `tpch-sf10-batched.scan-burst` rehearsed whole (PR 36):
`benchmark/run.py`'s own `run_cell` at sf 0.01 with the configuration's
lane levers, untraced, traced, and with an answer altered where it is
produced; and sixteen pgwire clients with sixteen distinct literal sets
against the pandas reference and against the lane-off engine.

One child process makes the three runs (the platform requirement is the
child's to relax, never an option of the benchmark). A CPU run shows
paths, counts and verdicts, never a speed.
"""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
CELL = "tpch-sf10-batched.scan-burst"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW_METRICS = ("batch_occupancy", "batch_wait_ms", "batch_pad_pct",
               "dispatch_roofline")
SF10_ONLY = ("programs_roofline", "compact_reruns_in_window",
             "latemat_direct_pct")

DRIVER = """
import json, sys
sys.path.insert(0, {bench!r})
import devices
devices.REQUIRED_PLATFORM = "cpu"          # the test's, never an option
import proxy, run
seen = []
real_match, real_delta = proxy.match, run.counters_delta
def match(samples, calls):
    real_match(samples, calls)
    seen.append({{"paths": sorted({{c.path for c in calls}}),
                 "calls": len(calls), "samples": len(samples),
                 "streams": len({{s.stream for s in samples}}),
                 "queries": sorted({{s.item.query for s in samples}}),
                 "items": len({{s.item for s in samples}})}})
proxy.match = match
windows = []
def delta(before, after):
    d = real_delta(before, after)
    windows.append({{k: v for k, v in d.items()
                    if k.startswith(("batch/", "prog/registered"))}})
    return d
run.counters_delta = delta
small = {{"sf": 0.01}}
out = {{"untraced": run.run_cell({cell!r}, 2**31 + 36, 1.5, False,
                                overrides=small),
       "traced": run.run_cell({cell!r}, 36, 1.5, True, overrides=small)}}

# a float cell a millionth off in every answer, where it is produced
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
out["windows"] = windows[1::2]            # set-up, window; set-up, window
for r in (out["untraced"], out["traced"], out["fault"]):
    r.pop("breakdown", None)
print(json.dumps(out))
"""


def reported(section: str) -> set:
    return {m["name"] for m in BENCHMARK[section]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    driver = tmp_path_factory.mktemp("batched-cell") / "driver.py"
    driver.write_text(DRIVER.format(bench=str(ROOT / "benchmark"),
                                    cell=CELL))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    for k in ("YDB_TPU_BATCH_WINDOW", "YDB_TPU_BATCH_MAX"):
        env.pop(k, None)                   # the configuration's, not ours
    p = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_is_the_sf10_database_with_the_lane_on_at_32_streams():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    cfg = next(c for c in BENCHMARK["configs"]
               if c["name"] == cell["config"])
    assert BENCHMARK["workloads"][-1] is cell      # added at the end
    assert BENCHMARK["configs"][-1] is cfg
    assert cell["chips"] == 1 and cell["traffic"] == "scan-burst"
    assert len(cfg["source"]) <= 200 and len(cell["why"]) <= 200
    assert len(cfg["why"]) <= 200
    conf = json.loads((ROOT / cfg["file"]).read_text())
    sf10 = json.loads((ROOT / "benchmark/configs/tpch-sf10.json").read_text())
    assert conf["name"] == "tpch-sf10-batched"
    assert conf["source"] == cfg["source"] != sf10["source"]
    for k in ("loader", "sf", "chips", "shards", "portion_rows", "store",
              "tables", "guarantees"):
        assert conf[k] == sf10[k], k
    # the program's default, stated: the attribute arrives with the lane
    # that admits SF10's stacked dispatch, so `run.build_engine` stops a
    # program whose lane declines every statement at once
    from ydb_tpu.query import QueryEngine
    assert conf["engine_attrs"] == {
        "batch_alone_probe_ms": QueryEngine().batch_alone_probe_ms}
    assert "alone_probe" in conf["assumed"]
    assert conf["sf"] == 10.0 and conf["portion_rows"] == 1048576
    # the lane's two documented levers, and nothing else
    assert conf["env"] == {"YDB_TPU_BATCH_WINDOW": 50,
                           "YDB_TPU_BATCH_MAX": 16}
    assert list(conf["reduced"]) == cfg["reduced"] == [
        "query_set", "refresh_functions", "power_test"]
    assert {"generator", "batching", "size"} <= set(conf["assumed"])
    # the traffic the issue names, to the letter
    mix = json.loads(
        (ROOT / "benchmark/workloads" / f"{CELL}.json").read_text())
    assert mix == {"config": "tpch-sf10-batched", "front": "pgwire",
                   "loop": "closed", "streams": 32, "family": "tpch",
                   "queries": ["q6"], "param_sets": 16,
                   "expected_path": "fused"}


@pytest.mark.parametrize("has_it", [True, False])
def test_build_engine_stops_a_program_without_the_lanes_attribute(
        monkeypatch, has_it):
    """The parent's engine (no `batch_alone_probe_ms`: its lane declines
    every SF10 statement) fails in set-up, before anything is loaded."""
    import ydb_tpu.query as query
    conf = json.loads(
        (ROOT / "benchmark/configs/tpch-sf10-batched.json").read_text())

    class Parent(query.QueryEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            del self.batch_alone_probe_ms

    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import run
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    if has_it:
        eng = run.build_engine(conf)
        assert eng.batch_alone_probe_ms == 2.0
        return
    monkeypatch.setattr(query, "QueryEngine", Parent)
    with pytest.raises(run.SetupFailure, match="batch_alone_probe_ms"):
        run.build_engine(conf)


def test_the_cell_reports_the_sf10_scan_cells_metrics_and_its_four():
    def of(cell: str, section: str) -> set:
        return {m["name"] for m in BENCHMARK[section]
                if cell in m.get("workloads", [cell])}
    assert of(CELL, "per_layer") == (of("tpch-sf10.scan", "per_layer")
                                     - set(SF10_ONLY)) | set(NEW_METRICS)
    assert of(CELL, "end_to_end") == of("tpch-sf10.scan", "end_to_end") \
        == {"queries_per_s", "latency_p50_ms", "setup_s"}
    new = [m for m in BENCHMARK["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in BENCHMARK["per_layer"][-4:]] \
        == list(NEW_METRICS)
    for m in new:
        assert m["workloads"][0] == CELL     # later cells may join
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {m["name"]: (m["layer"], m["moves"], m["source"])
            for m in new} == {
        "batch_occupancy": ("engine", "queries_per_s", "program_counter"),
        "batch_wait_ms": ("engine", "latency_p50_ms", "program_span"),
        "batch_pad_pct": ("engine", "queries_per_s", "program_counter"),
        "dispatch_roofline": ("device programs", "queries_per_s",
                              "device_trace")}


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_rehearsal_is_correct(runs, run):
    r = runs[run]
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 64
    assert r["compared"]["max_rel_err"]["value"] <= 1e-9
    assert r["compared"]["unanswered"]["value"] == 0
    assert r["compared"]["wrong_answers"]["value"] == 0


def test_the_windows_statements_ride_stacked_dispatches(runs):
    assert len(runs["seen"]) == 3 == len(runs["windows"])
    for seen, w in zip(runs["seen"], runs["windows"]):
        # `fused`: a leader still alone after the lane's 2 ms probe runs
        # the per-query program (a straggler on this CPU's one GIL, the
        # window's last statements); every other one rides a batch
        assert "fused-batched" in seen["paths"]
        assert set(seen["paths"]) <= {"fused", "fused-batched"}
        assert seen["calls"] == seen["samples"] >= 64
        assert seen["streams"] == 32 and seen["queries"] == ["q6"]
        assert seen["items"] == 16            # sixteen literal sets
        assert w["batch/batches"] > 0
        assert w["batch/coalesced_queries"] + w.get("batch/singles", 0) \
            == seen["samples"]
        assert w["batch/coalesced_queries"] >= 0.9 * seen["samples"]
        for bad in ("batch/declined", "batch/fallbacks",
                    "batch/trace_errors", "batch/ahead_compiles",
                    "prog/registered"):
            assert bad not in w, (bad, w)
        assert not [k for k in w if k.startswith("batch/declined/")]


def test_untraced_run_reports_the_cells_end_to_end_metrics(runs):
    assert set(runs["untraced"]["metrics"]) == reported("end_to_end") == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in runs["untraced"]["metrics"].values())


def test_traced_run_reports_the_cells_layers_and_its_four_metrics(runs):
    got = runs["traced"]["metrics"]
    # a CPU trace has no device plane: the device's readers find nothing
    # to read and are left out
    assert set(got) == reported("per_layer") - {"device_idle_pct",
                                                "dispatch_roofline"}
    assert got["batch_occupancy"]["unit"] == "stmts/dispatch"
    assert 12 <= got["batch_occupancy"]["value"] <= 16
    assert 0 <= got["batch_pad_pct"]["value"] < 25
    assert got["batch_wait_ms"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["devcache_upload_mb_in_window"] == {"value": 0.0,
                                                   "unit": "MiB"}
    # the leader's phases: one reservation a dispatch, never queued here
    assert 0 <= got["admission_wait_ms"]["value"] < 5


def test_an_altered_answer_is_not_correct(runs):
    r = runs["fault"]
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    c = r["compared"]["max_rel_err"]
    assert c["value"] > c["limit"]


def _reader(name):
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import traffic
        return traffic.load_module("metrics", name).read
    finally:
        sys.path.remove(str(ROOT / "benchmark"))


def test_the_readers_leave_out_what_a_program_does_not_count():
    class S:
        error = None

        def __init__(self, phases):
            self.call = type("C", (), {"phases": phases})()
    occupancy, wait, pad, roof = (_reader(n) for n in NEW_METRICS)
    # the parent at SF10: its lane declines every statement
    none = {"window_counters": {"batch/declined": 900}, "samples": [],
            "trace": {"busy_s": 50.0}, "least_bytes": 1, "hbm_bytes_per_s": 1}
    assert occupancy(none) is pad(none) is wait(none) is roof(none) is None
    w = {"batch/batches": 10, "batch/coalesced_queries": 155,
         "batch/member_slots": 160, "batch/pad_slots": 5}
    samples = [S({"batch_wait_ms": 10.0}), S({"batch_wait_ms": 30.0}),
               S({"batch_wait_ms": 20.0}), S({})]
    ctx = {"window_counters": w, "samples": samples}
    assert occupancy(ctx) == 15.5 and pad(ctx) == 3.125
    assert wait(ctx) == 20.0
    # once a DISPATCH: 160 statements of 1 GB each, 155 of them in ten
    # batches and five alone = 15 dispatches of 1 GB over 100 GB/s in 1 s
    done = [S({}) for _ in range(160)]
    ctx = {"window_counters": w, "samples": done, "trace": {"busy_s": 1.0},
           "least_bytes": 160e9, "hbm_bytes_per_s": 100e9}
    assert roof(ctx) == pytest.approx(15.0)
    assert roof(dict(ctx, trace=None)) is None


def test_the_float32_control_fails_the_same_comparison():
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import control
        r = control.control_reading(CELL, 2**31 + 36, sf=0.01)
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    assert len(r["gaps"]) == 16 and r["wrong_answers"] == 0
    assert r["correct"] is False
    assert r["max_rel_err"] > r["limit"] == 1e-9


# -- sixteen clients, sixteen literal sets, through pgwire --------------------


@pytest.fixture(scope="module")
def herd():
    """(items, reference frames by item, lane-on rows, lane-off rows):
    sixteen pgwire clients each sending one of sixteen distinct (year,
    discount, quantity) sets at once, against a seeded sf 0.01 database,
    with the lane on and with it off."""
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import traffic
        from pgclient import PgClient
        from refutil import Frames
        loader = traffic.load_module("loaders", "tpch")
        mix = {"family": "tpch", "queries": ["q6"], "param_sets": 16}
        mods, items = traffic.build_items(mix, 2**31 + 36)
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    from ydb_tpu.query import QueryEngine
    from ydb_tpu.server.pgwire import serve_pg
    from ydb_tpu.utils.metrics import GLOBAL

    def serve(lane: bool):
        old = {k: os.environ.get(k) for k in
               ("YDB_TPU_BATCH_WINDOW", "YDB_TPU_BATCH_MAX")}
        os.environ["YDB_TPU_BATCH_WINDOW"] = "500" if lane else "0"
        os.environ["YDB_TPU_BATCH_MAX"] = "16"
        try:
            eng = QueryEngine()
        finally:
            for k, v in old.items():
                os.environ.pop(k, None) if v is None else \
                    os.environ.__setitem__(k, v)
        data = loader.load(eng, {"sf": 0.01, "portion_rows": 1 << 20},
                           2**31 + 36)
        srv = serve_pg(eng, port=0)
        clients = [PgClient(srv.port, timeout=120.0) for _ in items]
        clients[0].query(items[0].sql)         # one connection's warm-up
        b0 = GLOBAL.get("batch/batches")
        rows, errs = {}, []
        barrier = threading.Barrier(len(items))

        def one(i):
            try:
                barrier.wait()
                rows[i] = clients[i].query(items[i].sql)
            except Exception as e:             # noqa: BLE001
                errs.append(repr(e))
        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(items))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for c in clients:
            c.close()
        srv.stop()
        assert not errs, errs[:3]
        return data, rows, GLOBAL.get("batch/batches") - b0

    data, on, batches = serve(True)
    _d, off, none = serve(False)
    assert batches >= 1 and none == 0
    frames = Frames(data.tables)
    want = [mods["q6"].reference(frames, dict(it.params)) for it in items]
    return items, want, on, off


def test_sixteen_clients_agree_with_the_reference_cell_by_cell(herd):
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        import compare
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    items, want, on, _off = herd
    assert len({it.params for it in items}) == 16
    gaps = []
    for i, w in enumerate(want):
        cols, rows, _tag = on[i]
        ok, gap = compare.answer_gap(cols, rows, w)
        assert ok, (items[i].params, rows)
        gaps.append(gap)
    assert max(gaps) < 1e-9
    assert len({on[i][1][0][0] for i in range(16)}) > 8   # answers differ


def test_sixteen_clients_answer_byte_for_byte_as_with_the_lane_off(herd):
    _items, _want, on, off = herd
    for i in range(16):
        assert on[i][0] == off[i][0]
        assert on[i][1] == off[i][1], i
