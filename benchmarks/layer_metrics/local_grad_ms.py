"""Round-program busy time a traced round under ``fedml.local.grad`` (the
``value_and_grad`` call: forward and backward), mean over chips."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "local_grad_ms")
