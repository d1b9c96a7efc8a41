"""Device ms a traced round in the grouped expert products
(``fedml.model.moe.experts``)."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.moe.experts")
