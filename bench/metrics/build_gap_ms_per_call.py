"""Device-idle milliseconds inside the training entry's program build, per
timed call: its ``train.lower`` spans (tracing and lowering) and
``train.compile`` spans (compiling, or loading from the persistent compile
cache), of the train step and of the embedding pass, averaged over the
chips. None where the program writes no such span."""
from bench import spans

BUILD = ("train.lower", "train.compile")


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, ctx.lo, ctx.hi, BUILD)
