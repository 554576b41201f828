"""Layer storage: MiB the device column cache stacked on the host and
uploaded inside the window (`devcache/upload_bytes` delta / 2^20): 0
while the resident set holds, the scanned columns again after an
eviction. The warm passes of set-up always upload, so a program that
counts its cache shows the counter there; one that does not is left out,
never given a 0 it did not count."""


def read(ctx):
    if "devcache/upload_bytes" not in ctx["setup_counters"]:
        return None
    return ctx["window_counters"].get("devcache/upload_bytes", 0) / 2**20
