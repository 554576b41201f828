"""Layer engine: the wall of the engine call (the proxy's clock) less
parse, plan and the sum of `QueryStats.phases`: the time no span of the
program covers; median. Only where the phases are disjoint and cover the
admission wait (`admission_ms`, `queue_ms` beside `device_ms`); a program
whose phases lack them is left out."""
import statistics


def read(ctx):
    d = [(s.call.t1 - s.call.t0) * 1e3 - s.call.parse_ms - s.call.plan_ms
         - sum(s.call.phases.values())
         for s in ctx["samples"] if s.call is not None
         and "admission_ms" in s.call.phases and "queue_ms" in s.call.phases]
    return statistics.median(d) if d else None
