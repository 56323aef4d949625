"""Device-idle milliseconds inside the training entry's table assembly, per
timed call: the embedding pass's dispatch (``train.embed``), the fetch of
the per-partition table (``train.fetch``) and its pooling into the global
table (``train.pool``), less the program build nested in them (counted by
``build_gap_ms_per_call``), averaged over the chips. None where the
program writes no such span."""
from bench import spans

TABLE = ("train.embed", "train.fetch", "train.pool")
BUILD = ("train.lower", "train.compile")


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, ctx.lo, ctx.hi, TABLE, BUILD)
