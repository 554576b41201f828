"""Layer device programs: of the deferred scan columns the window's fused
statements materialized, the share read in place (first referenced while
the row positions were still the iota) and not gathered through moved row
positions: 100 x `latemat/direct_cols` delta / (`latemat/direct_cols` +
`latemat/gathered_cols` deltas). The executor counts both per fused or
batched dispatch from what the trace recorded. A program without the counters is left
out."""


def read(ctx):
    c = ctx["window_counters"]
    direct = c.get("latemat/direct_cols", 0)
    total = direct + c.get("latemat/gathered_cols", 0)
    return 100.0 * direct / total if total else None
