"""Evaluation: host milliseconds inside the wrapped ``eval_fn``
(``cnn_accuracy`` and the ``float`` that waits for it) per evaluated
version.  The wait includes whatever device work was queued before it."""


def read(ctx):
    if not ctx.rec.evals:
        return None
    return 1e3 * ctx.rec.eval_s / ctx.rec.evals
