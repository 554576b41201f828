"""Layer device: `QueryStats.phases["queue_ms"]` of each statement, the
part of its wait for the device spent behind another statement's program
(`Executor._await_device` splits the wait where it ends); median. A
program that does not split the wait has no such phase: left out."""
import statistics


def read(ctx):
    d = [s.call.phases["queue_ms"] for s in ctx["samples"]
         if s.call is not None and "queue_ms" in s.call.phases]
    return statistics.median(d) if d else None
