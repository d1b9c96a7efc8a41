"""What the blockwise attention kernel has to do in a configuration
that holds a SHARE of its heads, counted from shapes and from the calls
the program makes — for ``attn_share_roofline_pct``.

``lib/decoder_kernels.attention_work`` counts the published heads
(``heads_per_layer``, ``num_key_value_heads``) and two forward calls a
step, which is what its cells ran when it was written; it is left as it
stands. Here the heads are the ones HELD (``query_heads_held`` over
``key_value_heads_held``; the whole where a configuration names no
share), the key blocks are those a layer visits under its mask (a block
the causal or window mask empties is never loaded:
``decoder_kernels.blocks_visited``), and the calls are the program's
since a layer keeps its kernel's output for the backward pass: ONE
forward (scores and mix: 2 block products a visited pair) and ONE
backward that walks the pairs once (the scores again, then dV, dP, dK
and dQ's partial: 5) a layer and optimizer step. A stack with learned
sparse attention has another kernel and another reader; it is given
nothing here.
"""

from __future__ import annotations

from lib import decoder_kernels as K

FULL, SLIDING, SELECTED = (
    "full_attention", "sliding_attention", "sparse_attention")
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def heads_held(extra: dict, layer: int) -> tuple[int, int]:
    """(query heads, key-value heads) layer ``layer`` holds here."""
    query = extra.get("query_heads_held")
    key_value = extra.get("key_value_heads_held")
    return (query[1] if query else extra["heads_per_layer"][layer],
            key_value[1] if key_value else extra["num_key_value_heads"])


def attention_share_work(extra: dict, seq: int, batch: int, block: int):
    """-> (operations, bytes) of one optimizer step's attention kernel
    calls over the heads held, or None where a layer's attention is not
    this kernel's. Bytes: q, k, v read and the output written by the
    forward call; those and dO read and dq, dk, dv written by the
    backward call; keys and values once a query-head group."""
    d = extra["head_dim"]
    block = min(block, seq)
    flops = nbytes = 0.0
    for layer, kind in enumerate(extra["layer_types"]):
        if kind == SELECTED:  # another kernel, another reader
            return None
        if kind not in (FULL, SLIDING):
            continue
        heads, kv = heads_held(extra, layer)
        window = extra.get("sliding_window") if kind == SLIDING else None
        pairs = K.blocks_visited(seq, block, window)
        product = 2.0 * block * block * d
        flops += batch * heads * pairs * product * (
            FORWARD_PRODUCTS + BACKWARD_PRODUCTS)
        q_rows, kv_rows = batch * heads * seq * d, batch * kv * seq * d
        forward = 2 * q_rows + 2 * kv_rows
        backward = 4 * q_rows + 4 * kv_rows
        nbytes += K.BF16 * (forward + backward)
    return (flops, nbytes) if flops else None
