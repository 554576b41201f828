"""Layer executor: programs the engine registered inside the window
(`prog/registered` delta); 0 expected. XLA's own count of compiles and
cache loads in the window is printed beside it on an earlier line."""


def read(ctx):
    return ctx["window_counters"].get("prog/registered", 0)
