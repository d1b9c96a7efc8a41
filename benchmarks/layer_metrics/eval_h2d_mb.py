"""MB an evaluation re-sends from the host: the ``h2d_bytes`` attr of the
``fedml.eval`` spans of the traced part (summed ``nbytes`` of the
operands that are host numpy arrays at the call). A count, not a timing:
it repeats exactly."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "eval_h2d_mb")
