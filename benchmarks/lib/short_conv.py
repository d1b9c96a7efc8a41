"""What the gated short convolution's memory-bound part and the
blockwise attention kernel at one small head size have to do, counted
from shapes — for ``conv_mix_roofline_pct`` and
``attn64_kernel_roofline_pct`` of a ``decoder`` configuration with
``short_conv`` layers.

**The mix** (scope ``fedml.model.conv.mix``): ``y = C * taps(B * u)``
over ``[batch, seq, hidden]`` with ``K`` taps a channel. ONE fused pass
forward reads ``B``, ``C``, ``u`` and writes ``y``: four arrays at the
compute dtype. Charged: that ONE forward pass a layer and optimizer
step, and nothing of the backward pass. ISSUE 46 asked for the backward
pass too (reads of the three and the cotangent, writes of three
gradients: seven arrays more); the program compiled for a v5e books the
backward gates inside the products' fusions (the two weight-gradient
products and the cotangents' products read them as producers), whose
time lies under ``fedml.model.conv`` by their root, so charging them
against the time under ``.mix`` would count work that this time leaves
out and could read over 100 %. What stays under ``.mix`` in that
program is ``B * u`` forward (the taps and ``C * z`` ride in
``out_proj``'s fusion) and the same again in the layer's recomputation:
the second pass is NOT charged (as ``useful_mxu_pct`` does not count
recomputation) and shows as share lost, with whatever passes the
compiler makes beyond one. Operations: ``B * u``, ``K`` multiplies and
``K - 1`` adds, ``C * z``; bytes bound it.

**The attention kernel** (scope ``fedml.model.attn.kernel``) in the
``full_attention`` layers of such a stack: ``heads_per_layer[l]`` query
heads over ``num_key_value_heads`` key-value heads of ``head_dim`` (the
PUBLISHED size: a launch that pads it is charged for the padding as
share lost), over the key blocks the causal mask leaves
(``decoder_kernels.blocks_visited``). Charged: the passes the program
makes, ONE forward call (the layer keeps its kernel's output and row
log-sum-exp, so the recomputation does not call it) and ONE backward
walk (the dk/dv kernel forms dQ's partials too): a visited pair of
blocks of edge ``b`` costs ``2 b^2 head_dim`` times 2 forward (scores,
mix) and 5 backward (scores again, dV, dP, dK, dQ's partial). Not the
two forward calls ``decoder_kernels.attention_work`` charges — PR 41
found the siblings' expert reader charging a forward too many
(``PERF.md`` section 7) — and that function counts every layer of the
stack as an attention layer, which here five of six are not. Bytes: q
and the output forward, those, dO and dq backward (six arrays of the
query heads' rows); k, v forward and k, v, dk, dv backward (six of the
key-value heads' rows, read once a GROUP).
"""

from __future__ import annotations

from lib import decoder_kernels as K

SHORT_CONV, FULL = "short_conv", "full_attention"
MIX_SCOPE = "fedml.model.conv.mix"
MIXER_SCOPES = ("fedml.model.conv", MIX_SCOPE)
KERNEL_SCOPE = "fedml.model.attn.kernel"


def _layers(extra: dict, kind: str) -> list[int]:
    return [l for l, k in enumerate(extra["layer_types"]) if k == kind]


def mix_work(extra: dict, seq: int, batch: int):
    """-> (operations, bytes) of one optimizer step's gates and taps in
    the short-convolution layers, ONE forward pass a layer, or None
    where the stack has none."""
    record, layers = extra.get(SHORT_CONV), _layers(extra, SHORT_CONV)
    if not record or not layers:
        return None
    elements = float(len(layers) * batch * seq * extra["hidden_size"])
    forward = 2 + 2 * record["kernel"] - 1
    return forward * elements, K.BF16 * 4 * elements


def attention_work(extra: dict, seq: int, batch: int, block: int):
    """-> (operations, bytes) of one optimizer step's attention kernel
    calls in the ``full_attention`` layers of a stack with
    short-convolution layers, or None where it is no such stack."""
    layers = _layers(extra, FULL)
    if not _layers(extra, SHORT_CONV) or not layers:
        return None
    d, kv = extra["head_dim"], extra["num_key_value_heads"]
    block = min(block, seq)
    pairs = K.blocks_visited(seq, block, None)
    heads = batch * sum(extra["heads_per_layer"][l] for l in layers)
    flops = heads * pairs * 2.0 * block * block * d * (2 + 5)
    rows = 6 * (heads + batch * len(layers) * kv) * seq * d
    return flops, float(K.BF16 * rows)


def step_work(ctx, work):
    """``work(extra, seq, batch, ...)`` of the traced rounds' optimizer
    steps, or None where there is nothing to count."""
    sizes = K.decoder_sizes(ctx)
    if sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq = sizes
    one = work(extra, seq, int(ctx["cell"]["config"]["batch_size"]))
    if one is None:
        return None
    return ctx["client_steps"] * one[0], ctx["client_steps"] * one[1]
