"""Merge and server optimizer, host side: milliseconds per global version
of the self time of the program's ``fl.merge`` span (aggregator weights,
the fused merge and server-optimizer dispatch, the warehouse put)."""
from bench import tracing


def read(ctx):
    return tracing.span_ms(ctx, ("fl.merge",), "versions")
