"""runners: programs lowered inside the measured window (a persistent
cache hit counts too: it still traces and lowers on the host). Should
read 0."""
UNIT = "count"


def read(ctx):
    return float(ctx.lowered)
