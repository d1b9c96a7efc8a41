"""The blockwise attention kernel's share of its roofline in a stack of
latent-attention layers: the least time the chip could take for the
kernel calls of the traced rounds
(``lib/latent_attention.latent_attention_work``: every head, keys of
the published ``qk_nope_head_dim + qk_rope_head_dim`` beside values of
``v_head_dim``, the key blocks the causal mask leaves, one forward and
one backward call a layer and step) over the device time under
``fedml.model.attn.kernel``."""

from lib import decoder_kernels as K
from lib import latent_attention


def read(ctx):
    block, sizes = K.attention_block(), K.decoder_sizes(ctx)
    if block is None or sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq = sizes
    batch = int(ctx["cell"]["config"]["batch_size"])
    work = latent_attention.latent_attention_work(extra, seq, batch, block)
    if work is None:
        return None
    steps = ctx["client_steps"]
    return K.roofline_pct(ctx, "fedml.model.attn.kernel",
                          (steps * work[0], steps * work[1]))
