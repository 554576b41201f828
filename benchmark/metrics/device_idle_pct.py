"""Layer device: 1 - union of the device's operation intervals over the
traced window. Nothing to read without a device plane in the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
