"""idle_share.sweep: share of the traced span in which no operation ran on
the device (device trace)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
