"""Loader `tpch`: TPC-H from `--seed` into the program's catalog.

The way `chip_smoke.load` and `ydb_tpu.bench.tpch_gen.load_tpch` do it
(copied, PR 25): one bulk write per table through the catalog's public
calls, committed at one version, then indexed. The data is the
benchmark's own (`tpch_gen.TpchData`); only the table types are the
program's.
"""

from __future__ import annotations

import numpy as np

from tpch_gen import TPCH_COLUMNS, TpchData

SCHEMA = TPCH_COLUMNS        # plain data: what `least_bytes.py` reads


def load(eng, cfg: dict, seed: int):
    """Generate at `cfg["sf"]` from `seed`, load; returns the TpchData."""
    from ydb_tpu.core import dtypes as dt
    from ydb_tpu.core.block import HostBlock
    from ydb_tpu.core.schema import Column, Schema
    from ydb_tpu.storage.mvcc import WriteVersion

    kinds = {"int64": dt.Kind.INT64, "int32": dt.Kind.INT32,
             "float64": dt.Kind.FLOAT64, "date32": dt.Kind.DATE32,
             "string": dt.Kind.STRING}
    data = TpchData(float(cfg["sf"]), seed)
    shards = int(cfg.get("shards", 1))
    for tname, (cols, keys) in TPCH_COLUMNS.items():
        schema = Schema([Column(n, dt.DType(kinds[k], nullable=False))
                         for n, k in cols])
        small = tname in ("nation", "region")
        table = eng.catalog.create_table(
            tname, schema, keys, shards=1 if small else shards,
            portion_rows=int(cfg["portion_rows"]))
        arrays = data.tables[tname]
        enc = {}
        for c in schema:
            a = arrays[c.name]
            if c.dtype.is_string:
                enc[c.name] = table.dictionaries[c.name].encode_bulk(
                    np.asarray(a, dtype=object))
            else:
                enc[c.name] = np.asarray(a, dtype=c.dtype.np)
        block = HostBlock.from_arrays(schema, enc,
                                      dictionaries=dict(table.dictionaries))
        table.commit(table.write(block), WriteVersion(1, 1))
        table.indexate()
    return data


def row_counts(data) -> dict[str, int]:
    """Rows generated per table: what the acknowledged load must read back."""
    return {t: len(next(iter(cols.values()))) for t, cols in data.tables.items()}
