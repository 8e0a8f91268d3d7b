"""Device: blocking device-to-host reads per merged update, from the
program's ``host_syncs`` counter over the window (each ``fl.sync`` read
and each evaluation)."""
from bench import spans


def read(ctx):
    return spans.counted(ctx, "host_syncs")
