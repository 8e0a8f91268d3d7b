"""The whole step's share of the chip's bf16 peak: local-training
operations of the traced window (forward and backward as the model
needs them, from the shapes) over the window's length times the peak."""


def read(ctx):
    if not ctx.rec.trains:
        return None
    fam, cfg = ctx.cell.family, ctx.cell.config
    ops = sum(fam.train_flops(cfg, n, e) for n, e in ctx.rec.trains)
    return 100.0 * ops / (ctx.win["window_s"] * ctx.peaks["bf16_flops"])
