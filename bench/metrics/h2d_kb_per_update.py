"""Local training dispatch: kilobytes (1,000 bytes) of host arrays the
program copies to the device per merged update, from its ``h2d_bytes``
counter over the window (training shards, merge weights, row indices,
server-optimizer scalars)."""
from bench import tracing


def read(ctx):
    n = tracing.counted(ctx, "h2d_bytes")
    return None if n is None else n / 1e3
