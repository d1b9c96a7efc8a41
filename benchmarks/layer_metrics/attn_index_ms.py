"""Device ms a traced round under ``fedml.model.attn.index``: the index
projections, their rotary and the index scores of every sparse-attention
layer, forward and recomputation (the index has no backward pass), mean
over chips."""

from lib import decoder_kernels, sparse_attention


def read(ctx):
    return decoder_kernels.scope_ms(ctx, sparse_attention.INDEX)
