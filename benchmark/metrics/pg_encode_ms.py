"""Layer fronts: what the pgwire front spends on an answer with rows (row
description + data rows + flush), counted where it does the work:
`front/pg/encode_ms` delta / `front/pg/statements` delta over the window.
A program that does not count them is left out."""


def read(ctx):
    c = ctx["window_counters"]
    if not c.get("front/pg/statements"):
        return None
    return c.get("front/pg/encode_ms", 0.0) / c["front/pg/statements"]
