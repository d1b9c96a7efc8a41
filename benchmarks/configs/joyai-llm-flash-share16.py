"""Plain float32 reference of ``joyai-llm-flash-share16``: one chip's
share of JD's JoyAI-LLM-Flash
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/
config.json; ``model_type: joyai_llm_flash``, whose keys are those of
the DeepSeek-V3 family, arXiv:2412.19437) — layers 0-4 of its 40 (the
leading dense layer and four sparse ones), every sparse layer shared
EXPERT-parallel by 16 chips: experts 0-15 of 256 here; attention, the
dense layer, the shared expert and the router whole; an eighth of the
vocabulary. Straight ``jax.numpy``: no kernel, no row buffer, no sort,
no cache; every matrix product at precision "highest" and through the
``quant`` pair (the float8 control). Imports nothing of ``fedml_tpu``.
Sizes are read from the ``.json`` beside this file (``model.extra``),
so a test can shrink both.

One layer (``x`` is ``[T, hidden]``; no bias; RMSNorm eps 1e-6 with a
learned scale; ``t`` a query position, ``s <= t`` a key position; H
heads):

    h    = RMSNorm_in(x)
    c_q  = RMSNorm_q(h W_qa)                      the queries' latent
    q    = c_q W_qb     as H heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva ;  c_kv <- RMSNorm_kv(c_kv)
    [k_nope | v] = c_kv W_kvb    as H heads of nope + v_head_dim
    q_rope, k_r <- rotary over ADJACENT pairs (2i, 2i + 1); k_r is ONE
                   head, the same for all H
    q_j = [q_nope_j | q_rope_j],  k_j = [k_nope_j | k_r]
    a_j = softmax(q_j k_j^T / sqrt(nope + rope) + causal) v_j
    x'   = x + concat_j(a_j) W_o
    g    = RMSNorm_post(x')

    a dense layer:   y = x' + (silu(g W_g) * (g W_u)) W_d
    a sparse layer:  s   = sigmoid(g W_r)          over ALL experts
                     E   = the k experts of largest s_e + b_e
                     w_e = scale s_e / sum_{e' in E} s_e'
                     y   = x' + sum_{e in E and HELD} w_e
                              (silu(g W1_e) * (g W3_e)) W2_e
                            + (silu(g S1) * (g S3)) S2

and after the last layer RMSNorm and ``logits = x W_head`` (untied).
``b`` (the score-correction bias) enters the CHOICE alone: the ids come
from ``top_k(s + b)``, whose values are thrown away, so no gradient
reaches it; the weights read the unbiased ``s``, normalised over all
``k`` chosen, held or not. The held experts run as a dense loop, each
on every token, weighted by a mask; scores a block of queries at a
time, so that ``[H, T, T]`` never exists.

What the absent chips' experts would add to a layer's output is left
out. What the config is silent on is listed under ``assumed`` in the
``.json``.
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
FEED_FORWARDS = C["mlp_layer_types"]
LAYERS = len(FEED_FORWARDS)
HEADS = C["heads_per_layer"][0]
_LA = C["latent_attention"]
Q_RANK, KV_RANK = _LA["q_lora_rank"], _LA["kv_lora_rank"]
NOPE, ROPE, V_DIM = (_LA["qk_nope_head_dim"], _LA["qk_rope_head_dim"],
                     _LA["v_head_dim"])
THETA = float(C["rope"]["latent_attention"]["rope_theta"])
# the expert layers' share
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
WIDTH, SHARED = C["moe_intermediate_size"], (
    C["shared_expert_intermediate_size"])
DENSE = C["intermediate_size"]
BIAS_STD = 0.01  # the law of a seed's score-correction bias
QUERY_BLOCK = 512  # queries scored at a time

TASK = "nwp"
HEAD = ("lm_head",)


def _product(spec, a, b, quant):
    """One matrix product at precision "highest"; under the control both
    inputs and the cotangent are rounded (``quant``)."""
    if quant is not None:
        a, b = quant[0](a), quant[0](b)
    y = jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return y if quant is None else quant[1](y)


def _rms_norm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rotate(x):
    """``x`` ``[B, T, heads, rope]``: dimensions ``2i`` and ``2i + 1``
    turned together by ``t theta^(-2i / rope)`` (angles in float64 on
    the host)."""
    t, n = x.shape[1], x.shape[-1]
    inv = THETA ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _query_blocks(t):
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens do not split into blocks of {block}")
    return t // block, block


def _attention(x, p, quant):
    """``x + attention(RMSNorm_in(x))``, latent."""
    b, t, _ = x.shape
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda y, name: _product("btc,cd->btd", y, p[name]["kernel"], quant)
    c_q = _rms_norm(proj(h, "q_a_proj"), p["q_a_norm"]["scale"])
    q = proj(c_q, "q_b_proj").reshape(b, t, HEADS, NOPE + ROPE)
    kv_a = proj(h, "kv_a_proj")
    c_kv = _rms_norm(kv_a[..., :KV_RANK], p["kv_a_norm"]["scale"])
    k_r = _rotate(kv_a[..., KV_RANK:].reshape(b, t, 1, ROPE))
    kv = proj(c_kv, "kv_b_proj").reshape(b, t, HEADS, NOPE + V_DIM)
    k_nope, v = kv[..., :NOPE], kv[..., NOPE:]
    q = jnp.concatenate([q[..., :NOPE], _rotate(q[..., NOPE:])], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, t, HEADS, ROPE))], -1)
    blocks, block = _query_blocks(t)

    @jax.checkpoint
    def one_block(args):  # a block of queries against every key
        first, q_blk = args  # [], [B, block, H, nope + rope]
        s = _product("bqnd,bknd->bnqk", q_blk, k, quant) / (
            NOPE + ROPE) ** 0.5
        rows = first + jnp.arange(block)[:, None]
        seen = jnp.arange(t)[None, :] <= rows
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("bnqk,bknd->bqnd", a, v, quant)

    q = jnp.moveaxis(q.reshape(b, blocks, block, *q.shape[2:]), 1, 0)
    a = lax.map(one_block, (jnp.arange(blocks) * block, q))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS * V_DIM)
    return x + proj(a, "o_proj")


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
    weight = C["routed_scaling_factor"] * top_s / jnp.sum(
        top_s, -1, keepdims=True)

    @jax.checkpoint
    def one_expert(y, expert):  # every held expert on every token
        e, w1, w3, w2 = expert
        share = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
        return y + share[:, None] * _gated(g, w1, w3, w2, quant), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), (
        FIRST + jnp.arange(HELD), p["experts_w1"], p["experts_w3"],
        p["experts_w2"]))
    if SHARED:
        y = y + _gated(g, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                       quant)
    return x + y.reshape(b, t, d)


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    h = p["embed"]["embedding"][x]
    for l in range(LAYERS):
        layer = jax.checkpoint(
            lambda h, pl, kind=FEED_FORWARDS[l]: _feed_forward(
                _attention(h, pl, quant), pl, kind, quant))
        h = layer(h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices normal with variance 1 / fan-in, norm scales 1 +
    0.1 normal, embedding rows unit normal, the score-correction bias
    ``BIAS_STD`` normal."""
    keys = iter(jax.random.split(key, 32 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda n=HIDDEN: {"scale": 1.0 + normal(n, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    for l in range(LAYERS):
        layer = {
            "attn_norm": norm(), "mlp_norm": norm(),
            "q_a_proj": dense(HIDDEN, Q_RANK), "q_a_norm": norm(Q_RANK),
            "q_b_proj": dense(Q_RANK, HEADS * (NOPE + ROPE)),
            "kv_a_proj": dense(HIDDEN, KV_RANK + ROPE),
            "kv_a_norm": norm(KV_RANK),
            "kv_b_proj": dense(KV_RANK, HEADS * (NOPE + V_DIM)),
            "o_proj": dense(HEADS * V_DIM, HIDDEN)}
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
            if SHARED:
                layer.update(
                    shared_w1=normal(HIDDEN, SHARED, std=HIDDEN ** -0.5),
                    shared_w3=normal(HIDDEN, SHARED, std=HIDDEN ** -0.5),
                    shared_w2=normal(SHARED, HIDDEN, std=SHARED ** -0.5))
        params[f"layer_{l}"] = layer
    return {"params": params}


def token_macs():
    """Multiply-accumulates of one token's forward pass in the share, by
    part: the latent projections, the scores and mix over the ``(T + 1)
    / 2`` keys a causal query reads (keys of ``nope + rope``, values of
    ``v_head_dim``), the routed experts at the held experts' uniform
    share of the ``k`` a token."""
    sparse = sum(kind == "sparse" for kind in FEED_FORWARDS)
    return {
        "attn_latent": LAYERS * (
            HIDDEN * (Q_RANK + KV_RANK + ROPE)
            + Q_RANK * HEADS * (NOPE + ROPE)
            + KV_RANK * HEADS * (NOPE + V_DIM)),
        "attn_out": LAYERS * HEADS * V_DIM * HIDDEN,
        "attn_scores": LAYERS * HEADS * (NOPE + ROPE + V_DIM) * (
            SEQ + 1) / 2,
        "dense": (LAYERS - sparse) * 3 * HIDDEN * DENSE,
        "router": sparse * HIDDEN * EXPERTS,
        "experts": sparse * TOP_K * HELD / EXPERTS * 3 * HIDDEN * WIDTH,
        "shared": sparse * 3 * HIDDEN * SHARED,
        "head": float(HIDDEN * VOCAB),
    }


def step_flops(batch):
    """Matrix work the published arithmetic needs for one optimizer step
    of ``batch`` sequences of ``SEQ`` in the share: forward + backward
    (two products backward for one forward); two operations a
    multiply-accumulate; recomputation not counted."""
    return 2.0 * 3.0 * sum(token_macs().values()) * SEQ * batch
