"""Layer engine: `QueryStats.parse_ms + plan_ms` of each statement; median."""
import statistics


def read(ctx):
    d = [s.call.parse_ms + s.call.plan_ms
         for s in ctx["samples"] if s.call is not None]
    return statistics.median(d) if d else None
