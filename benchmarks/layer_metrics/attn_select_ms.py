"""Device ms a traced round under ``fedml.model.attn.select``: the exact
top-k of every query's index scores (and the count of keys kept),
forward and recomputation, mean over chips."""

from lib import decoder_kernels, sparse_attention


def read(ctx):
    return decoder_kernels.scope_ms(ctx, sparse_attention.SELECT)
