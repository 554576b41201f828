"""Layer compile caches: `prog/compile_ms` delta over set-up: cache loads
on a warm run, compiles on a checkout's first."""


def read(ctx):
    return ctx["setup_counters"].get("prog/compile_ms", 0.0) / 1e3
