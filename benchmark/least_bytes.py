"""The least bytes any implementation must read to answer a query: for
every base-table column the query names, rows x stored width, once. A
function of the schema and the generated row counts, the same whatever
implements the query (no compiler's `cost_analysis`), which is what makes
`programs_roofline` comparable across PRs."""

from __future__ import annotations

# bytes a value takes in the column store: a string is a dictionary code,
# a date a day number
STORED_WIDTH = {"int64": 8, "int32": 4, "float64": 8, "date32": 4,
                "string": 4}


def query_bytes(tables_used: dict, schema: dict, row_counts: dict) -> int:
    """tables_used: {table: [column, ...]} as a query file's `TABLES`;
    schema: {table: ([(column, kind), ...], keys)}; row_counts: rows."""
    total = 0
    for table, cols in tables_used.items():
        kinds = dict(schema[table][0])
        total += row_counts[table] * sum(STORED_WIDTH[kinds[c]] for c in cols)
    return total
