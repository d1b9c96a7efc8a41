"""Device ms a traced round under ``fedml.model.attn.latent``: a
latent-attention layer's two down-projections, their norms and its two
up-projections (queries through ``q_lora_rank``, keys and values
through ``kv_lora_rank``), forward, recomputation and backward
together. An innermost scope: this time is NOT in the cell's
``attn_ms``, which reads ``fedml.model.attn`` (norm, rotary, output
projection) and the kernel. Nothing to read on a program without the
scope."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(ctx, "fedml.model.attn.latent")
