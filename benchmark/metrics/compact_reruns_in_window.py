"""Layer executor: statements the executor ran twice inside the window
because an `ir.Compact` overflowed its capacity
(`latemat/compact_overflow_reruns` delta); 0 where the counter did not
move or is absent."""


def read(ctx):
    return ctx["window_counters"].get("latemat/compact_overflow_reruns", 0)
