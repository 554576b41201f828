"""Layer engine: `QueryStats.phases["bookkeeping_ms"]` of each statement:
the statement's own analysis and records (phases, ledger and program
roll-ups, critical path, histograms, profile, audit), from where its
`total_ms` stops to the end of `QueryEngine.execute`; timed in the program
and written into the phases before the call returns (no span: the trace
has closed; the annotation `bookkeeping` on the profiler's clock); median.
Where no statement has it, as in a program that predates the phase, the
metric is left out."""
import statistics


def read(ctx):
    d = [s.call.phases["bookkeeping_ms"] for s in ctx["samples"]
         if s.call is not None and "bookkeeping_ms" in s.call.phases]
    return statistics.median(d) if d else None
