"""Collective bytes per device of the compiled train step, one step per
epoch (bench/counts.py). None where the cell compiles no halo step."""
from bench import counts


def read(ctx):
    if not ctx.hlo_text:
        return None
    return counts.collective_bytes(ctx.hlo_text)["total"]
