"""Layer storage: `QueryStats.phases` upload_ms + build_ms of each
statement (superblock uploads and join builds); median. A statement that
was not sampled by the program's tracer has no phases and is left out."""
import statistics


def read(ctx):
    d = [s.call.phases.get("upload_ms", 0.0) + s.call.phases.get("build_ms", 0.0)
         for s in ctx["samples"] if s.call is not None and s.call.phases]
    return statistics.median(d) if d else None
