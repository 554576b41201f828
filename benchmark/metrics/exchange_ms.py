"""Layer mesh: `QueryStats.phases` exchange_ms + merge_ms of each statement:
the host's own time in the two steps of a mesh lane that hold a
collective (the probe rows' all_to_all, the partials' merge exchange and
its single-device tail); the devices' run is `device_ms`; median. A
program whose mesh lanes have no such spans is left out."""
import statistics


def read(ctx):
    d = [s.call.phases.get("exchange_ms", 0.0) + s.call.phases["merge_ms"]
         for s in ctx["samples"] if s.call is not None
         and "merge_ms" in s.call.phases]
    return statistics.median(d) if d else None
