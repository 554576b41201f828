"""The whole of a run on the CPU at sf 0.01: both cells, traced and not;
and the timed path broken underneath, which has to read `correct` false."""

import json
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, SMALL

import run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def reported(section: str, cell: str) -> set:
    return {m["name"] for m in run.metrics_of(BENCHMARK, section, cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_the_end_to_end_metrics(on_cpu, cell):
    r = run.run_cell(cell, seed=2**31 + 11, seconds=1.5, trace=False,
                     overrides=SMALL)
    assert list(r)[:5] == KEYS and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 4
    assert set(r["metrics"]) == reported("end_to_end", cell)
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["compared"]["max_rel_err"]["value"] <= \
        r["compared"]["max_rel_err"]["limit"]
    json.dumps(r)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_what_a_cpu_trace_can(on_cpu, cell):
    r = run.run_cell(cell, seed=5, seconds=1.5, trace=True, overrides=SMALL)
    assert r["correct"] is True
    # a CPU trace has no device plane: the device's readers find nothing to
    # read and are left out, never reported as 0
    want = reported("per_layer", cell) - {"programs_roofline",
                                          "device_idle_pct"}
    assert set(r["metrics"]) == want
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" not in r["device"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_answers(monkeypatch, how):
    from ydb_tpu.query.engine import QueryEngine
    real = QueryEngine.execute
    state = {"n": 0}

    def broken(self, sql, *a, **kw):
        block = real(self, sql, *a, **kw)
        state["n"] += 1
        return how(block, sql, state["n"])
    monkeypatch.setattr(QueryEngine, "execute", broken)


def _nudge_a_float(block, sql, n):
    """One float cell a millionth off, in one answer of three."""
    if "count(*) as n from" in sql or n % 3:
        return block
    for c in block.schema.columns:
        data = block.columns[c.name].data
        if data.dtype.kind == "f" and len(data):
            data = data.copy()
            data[0] *= 1 + 1e-6
            block.columns[c.name].data = data
            break
    return block


def _drop_a_row(block, sql, n):
    if "count(*) as n from" in sql or n % 3 or block.length < 2:
        return block
    for c in block.schema.columns:
        col = block.columns[c.name]
        col.data = col.data[:-1]
        if col.valid is not None:
            col.valid = col.valid[:-1]
    block.length -= 1
    return block


def _refuse(block, sql, n):
    if "count(*) as n from" in sql or n < 30 or n % 3:
        return block
    raise RuntimeError("planted fault")


@pytest.mark.parametrize("fault,number", [
    (_nudge_a_float, "max_rel_err"), (_drop_a_row, "wrong_answers"),
    (_refuse, "unanswered")])
@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        on_cpu, monkeypatch, cell, fault, number):
    _alter_answers(monkeypatch, fault)
    r = run.run_cell(cell, seed=7, seconds=1.5, trace=False, overrides=SMALL)
    assert r["correct"] is False and r["failed"] > 0
    c = r["compared"][number]
    assert c["value"] > c["limit"]


def test_a_run_without_a_tpu_exits_nonzero_before_loading():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"})
    assert p.returncode != 0
    assert "load sf=" not in p.stdout and '"correct"' not in p.stdout
    assert "need 'tpu'" in p.stderr
