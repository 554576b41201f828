"""Layer device programs: device run per program execution inside the
window: `prog/device_ms` delta / `prog/executions` delta. Only where the
program counts the run WITHOUT the wait behind another statement's
program, which it says by giving its statements a `queue_ms` phase; a
program whose `prog/device_ms` holds the wait too is left out."""


def read(ctx):
    c = ctx["window_counters"]
    split = any(s.call is not None and "queue_ms" in s.call.phases
                for s in ctx["samples"])
    if not split or not c.get("prog/executions"):
        return None
    return c.get("prog/device_ms", 0.0) / c["prog/executions"]
