"""Median client-side latency of all statements of the window."""
import statistics


def read(ctx):
    lat = [s.latency_ms for s in ctx["samples"] if s.error is None]
    return statistics.median(lat) if lat else None
