"""90th percentile of the client-side latency of all statements of the
window (nearest rank), for cells whose window holds a hundred or so."""
import math


def read(ctx):
    lat = sorted(s.latency_ms for s in ctx["samples"] if s.error is None)
    return lat[max(0, math.ceil(0.9 * len(lat)) - 1)] if lat else None
