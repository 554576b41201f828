"""Layer device programs: the stacked program's share of its roofline.
The least bytes any implementation must read for the columns the query
names (`least_bytes.py`), ONCE A DISPATCH (a stacked dispatch reads the
shared scan once for all its members; a statement outside a batch is a
dispatch of its own), over the chip's published HBM rate, as a share of
the device's busy seconds in the traced window. `programs_roofline`
counts the same bytes once a STATEMENT, sixteen times what a full batch
must read. Left out where the window holds no batch (`batch/batches`)."""


def read(ctx):
    tr = ctx.get("trace")
    c = ctx["window_counters"]
    done = [s for s in ctx["samples"] if s.error is None]
    if not tr or not tr["busy_s"] or not c.get("batch/batches") or not done:
        return None
    dispatches = c["batch/batches"] + max(
        0, len(done) - c.get("batch/coalesced_queries", 0))
    least_s = ctx["least_bytes"] / len(done) * dispatches \
        / ctx["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
