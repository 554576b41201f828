"""Layer fronts: client latency less the wall of the engine call it
caused (the proxy's clock on the server's thread); median."""
import statistics


def read(ctx):
    d = [s.latency_ms - (s.call.t1 - s.call.t0) * 1e3
         for s in ctx["samples"] if s.call is not None]
    return statistics.median(d) if d else None
