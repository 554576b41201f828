"""Layer mesh: `QueryStats.phases["mesh_build_ms"]` of each statement: the
shuffle join's build side materialized on the host, hash-partitioned with
numpy and landed as one build table a chip; median. A program whose lane
has no such span is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["mesh_build_ms"] for s in ctx["samples"]
         if s.call is not None and "mesh_build_ms" in s.call.phases]
    return statistics.median(d) if d else None
