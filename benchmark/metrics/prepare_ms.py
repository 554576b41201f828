"""Layer executor: `QueryStats.phases["prepare_ms"]` of each statement,
the `fused-prepare` span's own time: the host's preparation of a fused or
stacked program between the join builds and the dispatch (plan walk, scan
sources, sort and limit setup, the program key and the Compact's sizing,
the cache lookup; the superblock's and the literals' uploads are phases of
their own) in `query/executor.py`; median. A statement without the phase
(unsampled, a mesh lane, a batch follower) is not counted; where none has
it, as in a program that predates the span, the metric is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["prepare_ms"] for s in ctx["samples"]
         if s.call is not None and "prepare_ms" in s.call.phases]
    return statistics.median(d) if d else None
