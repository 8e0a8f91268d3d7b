"""Device: the share of the traced window in which no operation ran."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
