"""Transport codec (``core/transport.py`` -> ``kernels/topk_quant.py``):
the least time of every codec program the traced window ran, from the
model's size, as a share of those programs' device time.  An encode runs
the top-k threshold, the kept count, the int8 scale, the Pallas encode
and the sender's dequantised reconstruction; a decode runs the Pallas
dequantise-and-add."""
from bench import costs

# each jitted program of the codec, and its (operations, bytes) per run
PROGRAMS = {"jit__topk_thresh_exact": costs.codec_threshold,
            "jit__kept_count": costs.codec_threshold,
            "jit__int8_scale": costs.codec_threshold,
            "jit__encode_impl": costs.codec_encode,
            "jit__dequant": costs.codec_dequant,
            "jit__decode_impl": costs.codec_decode}


def read(ctx):
    dev = ctx.trace.time_of(tuple(PROGRAMS))
    if dev <= 0:
        return None
    n = ctx.n_params
    least = sum(ctx.trace.calls_of((name,))
                * costs.least_seconds(*count(n), ctx.peaks)
                for name, count in PROGRAMS.items())
    return 100.0 * least / dev
