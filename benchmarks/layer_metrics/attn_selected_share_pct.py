"""Of the keys dense causal attention would have read in the traced
rounds' training steps, the share the learned selection kept: 100 x
``attn_keys_selected`` / ``attn_keys_causal`` (100 where ``topk`` is the
sequence length or more)."""

from lib import sparse_attention


def read(ctx):
    return sparse_attention.selected_share_pct(ctx)
