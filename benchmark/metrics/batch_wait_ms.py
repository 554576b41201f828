"""Layer engine: `QueryStats.phases["batch_wait_ms"]` of each statement,
the `batch-wait` span's own time: a member's wait from joining its group
to its slice (the window, the other members, on a follower the leader's
whole execution; the leader's dispatch, device wait and readout are
phases of their own); median. A program without the span is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["batch_wait_ms"] for s in ctx["samples"]
         if s.call is not None and "batch_wait_ms" in s.call.phases]
    return statistics.median(d) if d else None
