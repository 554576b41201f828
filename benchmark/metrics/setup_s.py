"""Process start to the window's start: imports, load, warm-up and, in a
run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
