"""All statements completed in the window over the window's whole length
(its start to the last completion)."""


def read(ctx):
    done = [s for s in ctx["samples"] if s.error is None]
    return len(done) / ctx["window_s"] if done else None
