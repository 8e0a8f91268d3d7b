"""Federation loop: milliseconds per merged update of the self time of
the program's ``fl.dispatch``, ``fl.select`` and ``fl.respond`` spans
(round opening, cohort draw and selection, response bookkeeping), with
the codec, merge, training and evaluation spans inside them left out."""
from bench import tracing

SPANS = ("fl.dispatch", "fl.select", "fl.respond")


def read(ctx):
    return tracing.span_ms(ctx, SPANS, "updates")
