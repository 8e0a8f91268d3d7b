"""Device: blocking device-to-host reads per merged update, from the
program's ``host_syncs`` counter over the window (each ``fl.sync`` read
and each evaluation)."""
from bench import tracing


def read(ctx):
    return tracing.counted(ctx, "host_syncs")
