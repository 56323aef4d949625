"""Collective time on each chip during which no other operation runs,
over the chip's busy time, averaged over the chips. None where no
collective ran."""
from bench import trace


def read(ctx):
    return trace.exposed_collective_pct(ctx.trace, ctx.lo, ctx.hi)
