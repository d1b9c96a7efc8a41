"""Device ms a traced round under ``fedml.model.attn`` (norm,
projections, rotary, gate, output projection) and the blockwise kernel
inside it (``fedml.model.attn.kernel``), forward, recomputation and
backward together, mean over chips."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(
        ctx, "fedml.model.attn", "fedml.model.attn.kernel")
