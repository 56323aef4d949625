"""Device-idle milliseconds inside the training entry's host gather of the
partition tensors (``train.gather``) and their upload with the parameters
and optimizer state (``train.upload``), per timed call, averaged over the
chips. None where the program writes no such span."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, ctx.lo, ctx.hi,
                                  ("train.gather", "train.upload"))
