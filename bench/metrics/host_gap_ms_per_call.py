"""Device-idle milliseconds inside each timed call (the benchmark's
``bench.call`` span): the training entry's host work that the device waits
for, per call, averaged over the chips."""
from bench import trace


def read(ctx):
    return trace.span_idle_ms(ctx.trace, "bench.call")
