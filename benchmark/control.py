#!/usr/bin/env python3
"""The control of "How `correct` is decided": the plain reference put in
the program's place, computed in float32 where the configuration states
float64, judged by the same comparison. It has to come out NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

Needs no chip and touches no JAX: it generates the cell's data from each
seed at the configuration's own scale, draws the cell's parameter sets,
answers each in float32 and prints the widest gap from the float64
reference, the number `compare.LIMITS["max_rel_err"]` has to lie under.
Not part of a benchmark run; `tests/test_control.py` keeps it at a small
scale.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np                       # noqa: E402

import compare                           # noqa: E402
import traffic                           # noqa: E402
from refutil import Frames               # noqa: E402


def as_wire(frame):
    """A reference frame as the front would have sent it: text cells."""
    cols = list(frame.columns)
    rows = []
    for rec in frame.itertuples(index=False):
        rows.append([None if v is None else
                     repr(float(v)) if isinstance(v, (float, np.floating))
                     else str(v) for v in rec])
    return cols, rows


def control_reading(workload: str, seed: int, sf: float | None = None) -> dict:
    """Per item the float32 reference's gap from the float64 one."""
    from tpch_gen import TpchData
    mix = traffic.read_json(HERE / "workloads" / f"{workload}.json")
    cfg = traffic.read_json(HERE / "configs" / f"{mix['config']}.json")
    data = TpchData(float(cfg["sf"] if sf is None else sf), seed)
    mods, items = traffic.build_items(mix, seed)
    f64, f32 = Frames(data.tables), Frames(data.tables, np.float32)
    gaps, wrong = {}, 0
    for it in items:
        want = mods[it.query].reference(f64, dict(it.params))
        cols, rows = as_wire(mods[it.query].reference(f32, dict(it.params)))
        ok, gap = compare.answer_gap(cols, rows, want)
        wrong += not ok
        gaps[f"{it.query}[{it.set_no}]"] = gap if ok else None
    finite = [g for g in gaps.values() if g is not None]
    widest = max(finite, default=0.0)
    correct = wrong == 0 and widest <= compare.LIMITS["max_rel_err"]
    return {"workload": workload, "seed": seed, "wrong_answers": wrong,
            "max_rel_err": widest, "narrowest_item_gap": min(finite, default=None),
            "limit": compare.LIMITS["max_rel_err"], "correct": correct,
            "gaps": gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor (default: the configuration's)")
    args = ap.parse_args(argv)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        r = control_reading(args.workload, seed, args.sf)
        print(json.dumps(r), flush=True)
        bad += r["correct"]
    return 1 if bad else 0       # a control that passes is the failure


if __name__ == "__main__":
    sys.exit(main())
