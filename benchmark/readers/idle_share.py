"""1 - union of the device's leaf operations over the traced window."""


def read(ctx):
    t = ctx["trace"] or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
