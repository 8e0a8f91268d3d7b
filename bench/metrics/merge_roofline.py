"""Merge and server optimizer (``core/flatbuf.py`` ->
``kernels/fedavg_agg.py``): the least time of every merge in the traced
window, from the model's size and each version's update rows, as a share
of those programs' device time."""
from bench import costs

# flatbuf's merge programs (alpha = 1 and alpha < 1) and server_opt's step
PROGRAMS = ("jit__weighted_sum", "jit__fused_mix", "jit_step")


def read(ctx):
    dev = ctx.trace.time_of(PROGRAMS)
    if dev <= 0:
        return None
    run = ctx.cell.traffic["run"]
    mixes = (run.get("mode") == "async"
             and not run.get("async_latest_table", True)
             and float(run.get("async_alpha", 1.0)) < 1.0)
    n = ctx.n_params
    least = 0.0
    for u in ctx.win["updates_each"]:
        if u:
            least += costs.least_seconds(*costs.merge(n, u, mixes),
                                         ctx.peaks)
            if run.get("server_opt") is not None:
                least += costs.least_seconds(*costs.adam_step(n), ctx.peaks)
    return 100.0 * least / dev
