"""Layer device programs: the least bytes any implementation must read for
the statements completed in the traced window (`least_bytes.py`) over the
chip's published HBM rate, as a share of the device's busy seconds there.
Bound by memory bandwidth: these are scans, joins and group-bys."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    least_s = ctx["least_bytes"] / ctx["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
