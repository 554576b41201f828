"""Layer executor: `QueryStats.phases["param_upload_ms"]` of each
statement, the `param-upload` span's own time: the device puts made
before every fused or stacked dispatch, the statement's array literals and
each join build's scalar inputs (`Executor._upload_params`); median. A
statement without the phase (unsampled, a mesh lane, a batch follower) is
not counted; where none has it, as in a program that predates the span,
the metric is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["param_upload_ms"] for s in ctx["samples"]
         if s.call is not None and "param_upload_ms" in s.call.phases]
    return statistics.median(d) if d else None
