"""What the blockwise attention kernel has to do in the latent-attention
layers of a stack that holds a SHARE of their heads
(``query_heads_held``), for ``latent_share_roofline_pct``:
``lib/latent_attention.py``'s arithmetic — keys of ``qk_nope_head_dim +
qk_rope_head_dim`` beside values of ``v_head_dim``, the key blocks the
causal mask leaves, one forward and one backward call a layer and
optimizer step — over the heads HELD, where that file counts every
published head and would read double on a half share."""

from __future__ import annotations

from lib import decoder_kernels as K
from lib import latent_attention as LA


def latent_share_work(extra: dict, seq: int, batch: int, block: int):
    """-> (operations, bytes) of one optimizer step's attention kernel
    calls over the latent heads held, or None where the stack has no
    latent-attention layer."""
    held = extra.get("query_heads_held")
    if held:
        extra = {**extra,
                 "heads_per_layer": [held[1]] * len(extra["layer_types"])}
    return LA.latent_attention_work(extra, seq, batch, block)


def roofline_pct(ctx):
    block, sizes = K.attention_block(), K.decoder_sizes(ctx)
    if block is None or sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq = sizes
    work = latent_share_work(
        extra, seq, int(ctx["cell"]["config"]["batch_size"]), block)
    if work is None:
        return None
    steps = ctx["client_steps"]
    return K.roofline_pct(ctx, "fedml.model.attn.kernel",
                          (steps * work[0], steps * work[1]))
