"""The index scores' share of their roofline: the least time the chip
could take to score the causal (query, key) pairs the traced rounds' OWN
``attn_keys_causal`` counts (``lib/sparse_attention.index_work``) over
the device time under ``fedml.model.attn.index``."""

from lib import sparse_attention as S


def read(ctx):
    return S.roofline_pct(ctx, S.INDEX, S.index_work, "attn_keys_causal")
