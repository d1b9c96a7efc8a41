"""What the blockwise attention kernel has to do in a latent-attention
layer, counted from the ``latent_attention`` record's sizes and from
the calls the program makes — for ``latent_attn_roofline_pct``.

Keys and queries are ``qk_nope_head_dim + qk_rope_head_dim`` wide (the
PUBLISHED size: a launch that pads them is charged for the padding as
share lost) and values ``v_head_dim``, so a visited (query block, key
block) pair of edge ``b`` costs ``2 b^2`` operations a unit of width:
forward the scores (key width) and the mix (value width); backward the
scores again, dK and dQ's partial (key width, three) and dV and dP
(value width, two). Every head has keys of its own, so all
``heads_per_layer`` heads are counted, over the key blocks the causal
mask leaves (``decoder_kernels.blocks_visited``), ONE forward and ONE
backward call a layer and optimizer step (the layer keeps its kernel's
output for the backward pass). ``lib/attention_share.py`` counts one
``head_dim`` for keys and values alike and is not pointed at such a
stack.
"""

from __future__ import annotations

from lib import decoder_kernels as K

LATENT = "latent_attention"


def pair_widths(record: dict) -> tuple[int, int]:
    """(forward, backward) operations of one visited pair of blocks, in
    units of ``2 b^2``."""
    key = record["qk_nope_head_dim"] + record["qk_rope_head_dim"]
    value = record["v_head_dim"]
    return key + value, 3 * key + 2 * value


def latent_attention_work(extra: dict, seq: int, batch: int, block: int):
    """-> (operations, bytes) of one optimizer step's attention kernel
    calls in the latent-attention layers, or None where the stack has
    none. Bytes: q, k, v read and the output written by the forward
    call; those and dO read and dq, dk, dv written by the backward
    call, each at its own width."""
    record = extra.get(LATENT)
    layers = [l for l, kind in enumerate(extra["layer_types"])
              if kind == LATENT]
    if not record or not layers:
        return None
    key = record["qk_nope_head_dim"] + record["qk_rope_head_dim"]
    value = record["v_head_dim"]
    block = min(block, seq)
    pairs = K.blocks_visited(seq, block, None)
    heads = batch * sum(extra["heads_per_layer"][l] for l in layers)
    flops = heads * pairs * 2.0 * block * block * sum(pair_widths(record))
    # forward q k v o; backward those, dO, dq, dk, dv: six of each width
    nbytes = float(K.BF16 * heads * seq * 6 * (key + value))
    return flops, nbytes
