"""Share of the traced window in which no operation ran on the device,
averaged over the chips."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx.trace, ctx.lo, ctx.hi)
