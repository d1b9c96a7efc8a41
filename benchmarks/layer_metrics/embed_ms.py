"""Device ms a traced round under ``fedml.model.embed``: the decoder
stack's embedding lookup and its gradient — the gather forward and, in
the backward pass, the sort of a step's token ids, the products that
sum the cotangent rows of each distinct id and the gather that writes
every table row once (``fedml_tpu/ops/embedding.py``; before PR 43 one
scatter-add of a row a token, which the same scope holds). A program
without the scope gives nothing to read."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.embed")
