"""Device ms a traced round under ``fedml.model.head``: the final norm
and the output head, forward and backward (the loss's own arithmetic is
the task's and lies under ``fedml.local.grad``)."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.head")
