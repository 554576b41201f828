"""Layer executor: `QueryStats.phases["readout_ms"]` of each statement
(the result's device-to-host transfer and host unpack); median. A
statement the program's tracer did not sample has no phases: left out."""
import statistics


def read(ctx):
    d = [s.call.phases["readout_ms"] for s in ctx["samples"]
         if s.call is not None and "readout_ms" in s.call.phases]
    return statistics.median(d) if d else None
