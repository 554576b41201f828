"""Layer engine: `QueryStats.phases["admission_ms"]` of each statement,
the `admission-wait` span's own time: the wait for a pipeline-window slot
and for the memory admission's byte budget (`query/engine.py`); median.
A statement the program's tracer did not sample has no such phase; where
no statement has it the metric is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["admission_ms"] for s in ctx["samples"]
         if s.call is not None and "admission_ms" in s.call.phases]
    return statistics.median(d) if d else None
