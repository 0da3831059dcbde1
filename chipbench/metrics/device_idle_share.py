"""device: 1 - (union of device operation intervals / traced window)."""
UNIT = "%"


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
