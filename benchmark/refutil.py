"""What the plain references share: frames of the generated tables, dates.

A reference is pandas over the numpy tables of `tpch_gen.TpchData`. It
imports nothing of the program and takes nothing the program has made.
`Frames(tables, float_dtype=np.float32)` is the control of "How `correct`
is decided": the same reference with every float64 column held, and so
every product and sum computed, in the precision below the configuration's.
"""

from __future__ import annotations

import datetime

import numpy as np
import pandas as pd


class Frames:
    """DataFrames of just the columns a query names, built once each."""

    def __init__(self, tables: dict, float_dtype=np.float64):
        self._tables = tables
        self._float = float_dtype
        self._memo: dict = {}

    def __call__(self, table: str, columns) -> pd.DataFrame:
        key = (table, tuple(columns))
        if key not in self._memo:
            cols = {}
            for c in columns:
                a = self._tables[table][c]
                if a.dtype == np.float64 and self._float is not np.float64:
                    a = a.astype(self._float)
                cols[c] = a
            self._memo[key] = pd.DataFrame(cols)
        return self._memo[key]


_EPOCH = datetime.date(1970, 1, 1)


def iso(days) -> np.ndarray:
    """Days since 1970 -> 'YYYY-MM-DD', as the fronts print a date."""
    return np.array([(_EPOCH + datetime.timedelta(days=int(d))).isoformat()
                     for d in days], dtype=object)


def day(iso_date: str) -> int:
    return (datetime.date.fromisoformat(iso_date) - _EPOCH).days
