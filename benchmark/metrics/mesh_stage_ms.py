"""Layer mesh: `QueryStats.phases["stage_ms"]` of each statement: the
host's own time dispatching the per-device prefix programs of a mesh lane
(scan blocks, pushdown, earlier joins); median. A program whose lanes have
no such span is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["stage_ms"] for s in ctx["samples"]
         if s.call is not None and "stage_ms" in s.call.phases]
    return statistics.median(d) if d else None
