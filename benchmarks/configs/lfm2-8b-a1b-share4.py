"""Plain float32 reference of ``lfm2-8b-a1b-share4``: one chip's share
of LiquidAI's LFM2-8B-A1B
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json;
``model_type: lfm2_moe``) — layers 0-5 of its 24 (``conv``, ``conv``,
``full_attention``, ``conv``, ``conv``, ``conv``; the two leading dense
feed-forwards and four sparse ones), every sparse layer shared
EXPERT-parallel by the four chips of one host: experts 0-7 of 32 here;
the mixers, the dense feed-forwards, the norms and the routers whole; a
quarter of the vocabulary, in ONE table for embedding and head.
Straight ``jax.numpy``: no kernel, no row buffer, no sort, no cache;
every matrix product at precision "highest" and through the ``quant``
pair (the float8 control). Imports nothing of ``fedml_tpu``. Sizes are
read from the ``.json`` beside this file (``model.extra``), so a test
can shrink both.

One layer (``x`` is ``[T, hidden]``; no bias anywhere unless the
``short_conv`` record asks the convolution for one; RMSNorm eps 1e-5
with a learned scale; ``t`` a position; K taps, 3 as published):

    h  = RMSNorm_operator(x)
    a convolution layer:
        [B | C | u] = h W_in         three chunks of ``hidden``, this order
        z_t = sum_{i < K} k_i * (B * u)_{t - (K - 1) + i}
                                     depthwise, causal, zeros before the
                                     sequence, NO activation
        x'  = x + (C * z) W_out
    an attention layer (H query heads over G key-value heads of D):
        q, k, v = h W_q, h W_k, h W_v
        q, k <- RMSNorm over the D of each head (one scale each), THEN
                rotary over all D, dimension i with i + D / 2
        a_j = softmax(q_j k_{j // (H / G)}^T / sqrt(D) + causal)
              v_{j // (H / G)}
        x'  = x + concat_j(a_j) W_o
    g  = RMSNorm_ffn(x')
    a dense layer:   y = x' + (silu(g W1) * (g W3)) W2
    a sparse layer:  s   = sigmoid(g W_r)          over ALL experts
                     E   = the k experts of largest s_e + b_e
                     w_e = scale s_e / (sum_{e' in E} s_e' + 1e-6)
                     y   = x' + sum_{e in E and HELD} w_e
                              (silu(g W1_e) * (g W3_e)) W2_e

and after the last layer RMSNorm and ``logits = x Emb^T`` over the SAME
table the tokens were looked up in, so that table's gradient is the sum
of two roads (``HEAD`` names it). ``b`` (the expert bias) enters the
CHOICE alone: the ids come from ``top_k(s + b)``, whose values are
thrown away, so no gradient reaches it; the weights read the unbiased
``s`` over all ``k`` chosen, held or not, with the family's 1e-6 in the
denominator. The convolution is written as K shifted products; the held
experts run as a dense loop, each on every token, weighted by a mask;
scores a block of queries at a time, so that ``[H, T, T]`` never
exists.

What the absent chips' experts would add to a layer's output is left
out. What the config is silent on — the tying, the 1e-6, the chunk
order, no activation on the convolution, the norms' place — is listed
under ``assumed`` in the ``.json``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    _MODEL = json.load(_f)["model"]
C = _MODEL["extra"]
VOCAB, SEQ = int(_MODEL["num_classes"]), int(_MODEL["input_shape"][0])
HIDDEN, EPS = C["hidden_size"], C["rms_norm_eps"]
MIXERS, FEED_FORWARDS = C["layer_types"], C["mlp_layer_types"]
LAYERS = len(MIXERS)
TAPS, CONV_BIAS = C["short_conv"]["kernel"], C["short_conv"]["bias"]
HEADS, KV, HEAD_DIM = (max(C["heads_per_layer"]), C["num_key_value_heads"],
                       C["head_dim"])
THETA = float(C["rope"]["full_attention"]["rope_theta"])
# the expert layers' share
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
WIDTH, DENSE = C["moe_intermediate_size"], C["intermediate_size"]
RENORM_EPS = C["router_renorm_epsilon"]
BIAS_STD = 0.01  # the law of a seed's expert bias
QUERY_BLOCK = 512  # queries scored at a time

TASK = "nwp"
HEAD = ("embed",)  # the tied table: the lookup's gradient + the head's


def _product(spec, a, b, quant):
    """One matrix product at precision "highest"; under the control both
    inputs and the cotangent are rounded (``quant``)."""
    if quant is not None:
        a, b = quant[0](a), quant[0](b)
    y = jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return y if quant is None else quant[1](y)


def _rms_norm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _later(x, steps):
    """``x`` ``[B, T, C]`` moved ``steps`` positions later, zeros in
    front."""
    if not steps:
        return x
    return jnp.concatenate(
        [jnp.zeros_like(x[:, :steps]), x[:, :x.shape[1] - steps]], 1)


def _convolution(x, p, quant):
    """``x + out((C * taps(B * u)))``, the gated short convolution."""
    h = _rms_norm(x, p["conv_norm"]["scale"])
    gates = _product("btc,cd->btd", h, p["in_proj"]["kernel"], quant)
    b_gate, c_gate, u = (gates[..., :HIDDEN], gates[..., HIDDEN:2 * HIDDEN],
                         gates[..., 2 * HIDDEN:])
    bu = b_gate * u
    z = sum(p["conv_kernel"][i] * _later(bu, TAPS - 1 - i)
            for i in range(TAPS))
    if CONV_BIAS:
        z = z + p["conv_bias"]
    return x + _product("btc,cd->btd", c_gate * z, p["out_proj"]["kernel"],
                        quant)


def _rotate(x):
    """``x`` ``[B, T, heads, n]``: every dimension turned by its
    position, dimension i paired with i + n / 2 (angles in float64 on
    the host)."""
    t, n = x.shape[1], x.shape[-1]
    inv = THETA ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], -1)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    turned = jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)
    return x * cos + turned * sin


def _query_blocks(t):
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens do not split into blocks of {block}")
    return t // block, block


def _attention(x, p, quant):
    """``x + attention(RMSNorm_operator(x))``, grouped-query."""
    b, t, _ = x.shape
    group = HEADS // KV
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    q = _rotate(_rms_norm(proj("q_proj").reshape(b, t, HEADS, HEAD_DIM),
                          p["q_norm"]["scale"]))
    q = q.reshape(b, t, KV, group, HEAD_DIM)
    k = _rotate(_rms_norm(proj("k_proj").reshape(b, t, KV, HEAD_DIM),
                          p["k_norm"]["scale"]))
    v = proj("v_proj").reshape(b, t, KV, HEAD_DIM)
    blocks, block = _query_blocks(t)

    @jax.checkpoint
    def one_block(args):  # a block of queries against every key
        first, q_blk = args  # [], [B, block, KV, group, D]
        s = _product("bqgnd,bkgd->bgnqk", q_blk, k, quant) / HEAD_DIM ** 0.5
        rows = first + jnp.arange(block)[:, None]
        seen = jnp.arange(t)[None, :] <= rows
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("bgnqk,bkgd->bqgnd", a, v, quant)

    q = jnp.moveaxis(q.reshape(b, blocks, block, *q.shape[2:]), 1, 0)
    a = lax.map(one_block, (jnp.arange(blocks) * block, q))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS * HEAD_DIM)
    return x + _product("btc,cd->btd", a, p["o_proj"]["kernel"], quant)


def _gated(g, w1, w3, w2, quant):
    up = jax.nn.silu(_product("nc,cf->nf", g, w1, quant)) * _product(
        "nc,cf->nf", g, w3, quant)
    return _product("nf,fc->nc", up, w2, quant)


def _feed_forward(x, p, kind, quant):
    b, t, d = x.shape
    g = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)
    if kind == "dense":
        y = _gated(g, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"], quant)
        return x + y.reshape(b, t, d)
    score = jax.nn.sigmoid(_product("nc,ce->ne", g, p["router"], quant))
    # the bias enters the choice alone: top_k's values are not read
    _, top_e = lax.top_k(score + p["router_bias"], TOP_K)
    top_s = jnp.take_along_axis(score, top_e, -1)
    weight = C["routed_scaling_factor"] * top_s / (
        jnp.sum(top_s, -1, keepdims=True) + RENORM_EPS)

    @jax.checkpoint
    def one_expert(y, expert):  # every held expert on every token
        e, w1, w3, w2 = expert
        share = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
        return y + share[:, None] * _gated(g, w1, w3, w2, quant), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), (
        FIRST + jnp.arange(HELD), p["experts_w1"], p["experts_w3"],
        p["experts_w2"]))
    return x + y.reshape(b, t, d)


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    table = p["embed"]["embedding"]
    h = table[x]
    for l in range(LAYERS):
        mixer = _convolution if MIXERS[l] == "short_conv" else _attention
        layer = jax.checkpoint(
            lambda h, pl, mixer=mixer, kind=FEED_FORWARDS[l]: _feed_forward(
                mixer(h, pl, quant), pl, kind, quant))
        h = layer(h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,vc->btv", h, table, quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables (no ``lm_head``): matrices — the convolution's taps
    (fan-in K) and the tied table (as the head's matrix, fan-in
    ``hidden``) among them — normal with variance 1 / fan-in, norm
    scales 1 + 0.1 normal, the convolution's bias (where the record has
    one) 0.1 normal, the expert bias ``BIAS_STD`` normal."""
    keys = iter(jax.random.split(key, 16 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda n=HIDDEN: {"scale": 1.0 + normal(n, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN,
                                            std=HIDDEN ** -0.5)},
              "final_norm": norm()}
    for l in range(LAYERS):
        layer = {"mlp_norm": norm()}
        if MIXERS[l] == "short_conv":
            layer.update(conv_norm=norm(), in_proj=dense(HIDDEN, 3 * HIDDEN),
                         conv_kernel=normal(TAPS, HIDDEN, std=TAPS ** -0.5),
                         out_proj=dense(HIDDEN, HIDDEN))
            if CONV_BIAS:
                layer.update(conv_bias=normal(HIDDEN, std=0.1))
        else:
            layer.update(
                attn_norm=norm(), q_proj=dense(HIDDEN, HEADS * HEAD_DIM),
                k_proj=dense(HIDDEN, KV * HEAD_DIM),
                v_proj=dense(HIDDEN, KV * HEAD_DIM),
                q_norm=norm(HEAD_DIM), k_norm=norm(HEAD_DIM),
                o_proj=dense(HEADS * HEAD_DIM, HIDDEN))
        if FEED_FORWARDS[l] == "dense":
            layer.update(gate_proj=dense(HIDDEN, DENSE),
                         up_proj=dense(HIDDEN, DENSE),
                         down_proj=dense(DENSE, HIDDEN))
        else:
            layer.update(
                router=normal(HIDDEN, EXPERTS, std=HIDDEN ** -0.5),
                router_bias=normal(EXPERTS, std=BIAS_STD),
                experts_w1=normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
                experts_w3=normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
                experts_w2=normal(HELD, WIDTH, HIDDEN, std=WIDTH ** -0.5))
        params[f"layer_{l}"] = layer
    return {"params": params}


def token_macs():
    """Multiply-accumulates of one token's forward pass in the share, by
    part: the convolution mixers' two projections (the taps and gates
    are elementwise: no matrix work), the attention's projections, its
    scores and mix over the ``(T + 1) / 2`` keys a causal query reads,
    the routed experts at the held experts' uniform share of the ``k``
    a token, the head over the tied table."""
    conv = sum(kind == "short_conv" for kind in MIXERS)
    dense = sum(kind == "dense" for kind in FEED_FORWARDS)
    sparse = LAYERS - dense
    return {
        "conv": conv * (HIDDEN * 3 * HIDDEN + HIDDEN * HIDDEN),
        "attn_proj": (LAYERS - conv) * 2 * HIDDEN * (HEADS + KV) * HEAD_DIM,
        "attn_scores": (LAYERS - conv) * HEADS * 2 * HEAD_DIM * (
            SEQ + 1) / 2,
        "dense": dense * 3 * HIDDEN * DENSE,
        "router": sparse * HIDDEN * EXPERTS,
        "experts": sparse * TOP_K * HELD / EXPERTS * 3 * HIDDEN * WIDTH,
        "head": float(HIDDEN * VOCAB),
    }


def step_flops(batch):
    """Matrix work the published arithmetic needs for one optimizer step
    of ``batch`` sequences of ``SEQ`` in the share: forward + backward
    (two products backward for one forward); two operations a
    multiply-accumulate; recomputation not counted."""
    return 2.0 * 3.0 * sum(token_macs().values()) * SEQ * batch
