"""The selected-keys attention kernel's share of its roofline: the least
time the chip could take for the (query, key) pairs the traced rounds'
OWN ``attn_keys_selected`` says were read
(``lib/sparse_attention.attention_work``) over the device time under
``fedml.model.attn.kernel``."""

from lib import sparse_attention as S


def read(ctx):
    return S.roofline_pct(
        ctx, S.KERNEL, S.attention_work, "attn_keys_selected")
