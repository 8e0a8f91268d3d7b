"""Device: milliseconds per merged update the host spends blocked in the
program's ``fl.sync`` reads (a device value turned into a host number,
such as the top-k encode's kept count), waiting on the work queued
before it."""
from bench import tracing


def read(ctx):
    return tracing.span_ms(ctx, ("fl.sync",), "updates")
