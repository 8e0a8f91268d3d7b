"""Federation loop: host milliseconds per merged update spent outside the
wrapped ``train_fn`` and ``eval_fn`` (event loop, server, selection,
estimator, population, transport host side, flat-buffer dispatch)."""


def read(ctx):
    w = ctx.win
    if not w["updates"]:
        return None
    rest = w["window_s"] - ctx.rec.train_s - ctx.rec.eval_s
    return 1e3 * rest / w["updates"]
