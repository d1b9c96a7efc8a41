"""What learned sparse attention has to do, counted from shapes and
from the round program's own counters — for the per-layer metrics of a
``decoder`` configuration with ``sparse_attention`` layers
(``attn_index_ms``, ``attn_select_ms``, ``attn_selected_share_pct``,
``sparse_attn_roofline_pct``).

The index, the selection and the attention over the selected keys are
timed by their scopes (``fedml.model.attn.index``, ``.select``,
``.kernel``). The attention kernel's work is counted by the keys the
traced rounds REALLY selected (the program's ``attn_keys_selected``:
one count a forward call of the training step) and the calls really
made — a layer is recomputed in the backward pass, so every step runs
the forward kernel twice and the backward kernels once — never by the
expectation and never by the dense causal triangle the blockwise kernel
walks, so the share cannot count more than the mechanism needs.

A program without the scopes or the counters (the parent of the PR that
added them) gives nothing to read: every function returns None.
"""

from __future__ import annotations

from lib import decoder_kernels as K

INDEX, SELECT, KERNEL = (
    "fedml.model.attn.index", "fedml.model.attn.select",
    "fedml.model.attn.kernel")
SELECTED = "sparse_attention"
COUNTERS = ("attn_keys_selected", "attn_keys_causal")


def sparse_sizes(ctx):
    """-> (``model.extra``, sequence length, sparse-attention layers),
    or None where the cell's model has no such layer."""
    sizes = K.decoder_sizes(ctx)
    if sizes is None or SELECTED not in sizes[0].get("layer_types", ()):
        return None
    extra, seq = sizes
    return extra, seq, sum(k == SELECTED for k in extra["layer_types"])


def keys(ctx):
    """``{attn_keys_selected, attn_keys_causal}`` summed over the traced
    rounds, or None where a traced round reported neither."""
    return K.round_counters(ctx, *COUNTERS)


def selected_share_pct(ctx):
    """Of the keys dense causal attention would read, the share the
    selection kept: 100 x selected / causal."""
    c = keys(ctx)
    if not c or not c["attn_keys_causal"]:
        return None
    return 100.0 * c["attn_keys_selected"] / c["attn_keys_causal"]


def expected_keys(seq: int, topk: int) -> tuple[int, int]:
    """(selected, causal) keys of one sequence in one layer:
    ``sum min(t + 1, topk)`` and ``sum t + 1``."""
    full = min(topk, seq)
    return (full * (full + 1) // 2 + (seq - full) * full,
            seq * (seq + 1) // 2)


def attention_work(extra: dict, seq: int, keys_selected: float,
                   layer_calls: float):
    """-> (operations, bytes) of the attention kernel calls behind
    ``keys_selected`` (query, key) pairs, counted over ``layer_calls``
    forward calls of the training step (:func:`layer_calls`): a pair
    costs every query head one score product and one mix product of
    ``head_dim`` forward, run twice, and five such products backward
    (the scores again, dV, dP, dQ, dK); bytes: q, k, v read and the
    output written a forward call, those and dO read and dq, dk, dv
    written a backward call, keys and values once a query-head GROUP,
    and the selection's ``T x T`` bytes once a kernel call (what the
    mechanism needs; the kernel in use reads them once a query
    head)."""
    d, kv = extra["head_dim"], extra["num_key_value_heads"]
    heads = next(h for h, kind in zip(extra["heads_per_layer"],
                                      extra["layer_types"])
                 if kind == SELECTED)
    flops = keys_selected * heads * 2.0 * d * (2 * 2 + 5)
    q_rows, kv_rows = heads * seq * d, kv * seq * d
    forward = 2 * q_rows + 2 * kv_rows
    backward = 4 * q_rows + 4 * kv_rows
    mask = seq * seq  # one byte a pair
    nbytes = layer_calls * (
        K.BF16 * (2 * forward + backward) + 4 * mask)
    return flops, nbytes


def index_work(extra: dict, seq: int, keys_causal: float,
               layer_calls: float):
    """-> (operations, bytes) of what runs under the index's scope for
    ``keys_causal`` (query, key) pairs with ``s <= t`` over
    ``layer_calls``: every index head's product of ``index_head_dim`` a
    pair and the three index projections of every token, forward only,
    run twice a step (the recomputation); bytes: the normed input read,
    the projections written, the float32 scores written."""
    sa = extra["sparse_attention"]
    j, e = sa["index_heads"], sa["index_head_dim"]
    width = j * e + e + j  # what the three index projections put out
    flops = 2 * (keys_causal * j * 2.0 * e
                 + layer_calls * seq * 2.0 * extra["hidden_size"] * width)
    nbytes = 2 * layer_calls * (
        K.BF16 * seq * (extra["hidden_size"] + width) + 4 * seq * seq)
    return flops, nbytes


def layer_calls(ctx, layers: int) -> float:
    """Forward calls of the training step the traced rounds made: one a
    sparse-attention layer a client step a sequence of the batch."""
    return (ctx["client_steps"] * layers
            * int(ctx["cell"]["config"]["batch_size"]))


def roofline_pct(ctx, scope: str, work, counter: str):
    """100 x the least seconds for ``work(extra, seq, the traced rounds'
    own counter, their layer calls)`` over the device seconds under
    ``scope``; None without the counters, the scope or such a model."""
    c, sizes = keys(ctx), sparse_sizes(ctx)
    if not c or sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq, layers = sizes
    return K.roofline_pct(ctx, scope, work(
        extra, seq, c[counter], layer_calls(ctx, layers)))
