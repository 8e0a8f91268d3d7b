"""Local-training program (the family's ``TRAIN_PROGRAMS``, such as the
language model's ``lm_sgd_train``): its device microseconds in the traced
window per token those trainings processed, from the program's
``train_tokens`` counter over the window.  None where the program keeps
no such counter."""


def read(ctx):
    tokens = (ctx.win.get("counters") or {}).get("train_tokens")
    dev = ctx.trace.time_of(ctx.cell.family.TRAIN_PROGRAMS)
    if not tokens or dev <= 0:
        return None
    return 1e6 * dev / tokens
