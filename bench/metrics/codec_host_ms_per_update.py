"""Transport codec, host side: milliseconds per merged update of the self
time of the program's ``fl.encode_down``, ``fl.decode_down``,
``fl.encode_up`` and ``fl.decode_up`` spans (``bench/tracing.py``); the
waits of their ``fl.sync`` reads are not in it."""
from bench import tracing

SPANS = ("fl.encode_down", "fl.decode_down", "fl.encode_up", "fl.decode_up")


def read(ctx):
    return tracing.span_ms(ctx, SPANS, "updates")
