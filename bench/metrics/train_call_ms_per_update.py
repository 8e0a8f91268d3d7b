"""Local training dispatch: host milliseconds inside the wrapped
``train_fn`` (``core/worker.py`` -> ``setup.train_fn``) per merged
update."""


def read(ctx):
    if not ctx.win["updates"]:
        return None
    return 1e3 * ctx.rec.train_s / ctx.win["updates"]
