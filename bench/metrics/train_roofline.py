"""Local-training program (``cnn_sgd_train``): its least time on the
chip, from the operations and bytes of every training the traced window
ran, as a share of its device time in the trace."""
from bench import costs

PROGRAMS = ("jit_cnn_sgd_train",)


def read(ctx):
    dev = ctx.trace.time_of(PROGRAMS)
    if dev <= 0 or not ctx.rec.trains:
        return None
    fam, cfg = ctx.cell.family, ctx.cell.config
    least = sum(costs.least_seconds(fam.train_flops(cfg, n, e),
                                    fam.train_bytes(cfg, n, e), ctx.peaks)
                for n, e in ctx.rec.trains)
    return 100.0 * least / dev
