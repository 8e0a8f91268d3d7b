"""Local training dispatch: kilobytes (1,000 bytes) of host arrays the
program copies to the device per merged update, from its ``h2d_bytes``
counter over the window (training shards, merge weights, row indices,
server-optimizer scalars)."""
from bench import spans


def read(ctx):
    n = spans.counted(ctx, "h2d_bytes")
    return None if n is None else n / 1e3
