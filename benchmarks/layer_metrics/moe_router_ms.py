"""Device ms a traced round under ``fedml.model.moe.router``: the
logits, top-k and weights of a router that reads its layer's attention
input and so stands before attention, forward, recomputation and
backward together. Where the router reads the rows it routes there is
no such scope (that work lies under ``fedml.model.moe.route``) and
nothing to read."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.moe.router")
