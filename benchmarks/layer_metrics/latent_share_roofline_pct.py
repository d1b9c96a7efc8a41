"""The blockwise attention kernel's share of its roofline in
latent-attention layers that hold a share of their heads: the least
time the chip could take for the kernel calls of the traced rounds
(``lib/latent_share.latent_share_work``: the heads HELD, keys of the
published ``qk_nope_head_dim + qk_rope_head_dim`` beside values of
``v_head_dim``) over the device time under ``fedml.model.attn.kernel``."""

from lib import latent_share


def read(ctx):
    return latent_share.roofline_pct(ctx)
