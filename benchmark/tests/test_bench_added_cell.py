"""A configuration and a cell are added as NEW files plus `BENCHMARK.json`
entries, with no edit to a file that is there: a `chips: 4` configuration
over four virtual CPU devices reaches the `distributed` path."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

DRIVER = """
import json, sys
sys.path.insert(0, {bench!r})
import devices
devices.REQUIRED_PLATFORM = "cpu"          # the test's, never an option
import run
print(json.dumps(run.run_cell("tpch-tiny-mesh4.q1", 11, 1.0, False)))
"""


def test_a_four_chip_cell_is_only_new_files(tmp_path):
    tree = tmp_path / "checkout"
    shutil.copytree(BENCH, tree / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(ROOT / "ydb_tpu", tree / "ydb_tpu")
    before = {p: p.read_bytes() for p in (tree / "benchmark").rglob("*")
              if p.is_file()}

    cfg = json.loads((BENCH / "configs" / "tpch-sf1.json").read_text())
    cfg.update(name="tpch-tiny-mesh4", sf=0.01, chips=4, shards=4,
               portion_rows=1 << 21)
    (tree / "benchmark/configs/tpch-tiny-mesh4.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "workloads" / "tpch-sf1.scan.json").read_text())
    mix.update(config="tpch-tiny-mesh4", queries=["q1"], param_sets=2,
               expected_path="distributed")
    (tree / "benchmark/workloads/tpch-tiny-mesh4.q1.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tpch-tiny-mesh4", "source": "test",
                             "file": "benchmark/configs/tpch-tiny-mesh4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tpch-tiny-mesh4.q1",
                               "config": "tpch-tiny-mesh4", "traffic": "q1",
                               "chips": 4, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "queries_per_s":
            m["workloads"] = ["tpch-tiny-mesh4.q1"]
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER.format(bench=str(tree / "benchmark")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       text=True, cwd=tree, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["device"]["count"] == 4
    assert "queries_per_s" in r["metrics"]
    assert all(p.read_bytes() == b for p, b in before.items())
