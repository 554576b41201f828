"""The comparison that decides `correct`: every answer the window received
against the plain reference of its (query, parameter set).

Three numbers, each with a limit of its own (`LIMITS`; PERF.md section 2
gives the readings each was set from):

  unanswered     queries that errored or never answered            limit 0
  wrong_answers  answers whose shape, column names or any exact
                 cell (integer, string, date, NULL) differ          limit 0
  max_rel_err    the widest gap of a float cell from the
                 reference's, as a share of the reference's value   limit below

Cells arrive as text: the pgwire front prints a float64 with `repr`, so
`float()` gives back every bit, and the gap measured is the engine's, not
the wire's.
"""

from __future__ import annotations

import math

import numpy as np

# readings (PERF.md section 2): the program's widest gap over its seeds is
# the lower, the float32 control's narrowest the upper
LIMITS = {"unanswered": 0, "wrong_answers": 0, "max_rel_err": 1e-9}


def answer_gap(cols, rows, want) -> tuple[bool, float]:
    """(exact parts agree, widest relative gap of the float cells)."""
    if list(cols) != list(want.columns) or len(rows) != len(want):
        return False, math.inf
    worst = 0.0
    for i, name in enumerate(want.columns):
        w = want[name].to_numpy()
        cells = [r[i] for r in rows]
        if np.issubdtype(w.dtype, np.floating):
            if any(c is None for c in cells):
                return False, math.inf
            g = np.array([float(c) for c in cells], dtype=np.float64)
            w = w.astype(np.float64)
            if not np.all(np.isfinite(g)):
                return False, math.inf
            if len(g):
                scale = np.maximum(np.abs(w), np.finfo(np.float64).tiny)
                worst = max(worst, float(np.max(np.abs(g - w) / scale)))
        elif np.issubdtype(w.dtype, np.integer):
            try:
                if [int(c) for c in cells] != [int(x) for x in w]:
                    return False, worst
            except (TypeError, ValueError):
                return False, worst
        elif cells != [None if x is None else str(x) for x in w]:
            return False, worst
    return True, worst


def judge(samples, reference_of) -> dict:
    """samples: the window's records (`.item`, `.answer`, `.error`);
    reference_of(item) -> the reference's DataFrame. Returns the numbers
    compared, each beside its limit, and `failed`/`correct`."""
    unanswered = wrong = 0
    widest = 0.0
    for s in samples:
        s.failed = True
        if s.error is not None or s.answer is None:
            unanswered += 1
            continue
        cols, rows = s.answer
        ok, gap = answer_gap(cols, rows, reference_of(s.item))
        if not ok:
            wrong += 1
            continue
        widest = max(widest, gap)
        s.failed = gap > LIMITS["max_rel_err"]
    values = {"unanswered": unanswered, "wrong_answers": wrong,
              "max_rel_err": widest}
    compared = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    failed = sum(1 for s in samples if s.failed)
    correct = bool(samples) and all(values[k] <= LIMITS[k] for k in LIMITS)
    return {"compared": compared, "failed": failed, "correct": correct}
