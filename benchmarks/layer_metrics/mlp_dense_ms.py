"""Device ms a traced round under ``fedml.model.mlp``: the dense
layers' gated feed-forward (norm and three products) and the sparse
layers' shared expert, forward, recomputation and backward together."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.mlp")
