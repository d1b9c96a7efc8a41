"""Plain float32 reference of ``ling3-flash-share64``: one chip's share
of inclusionAI's Ling-3.0-flash
(https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/
config.json; ``model_type: bailing_hybrid``) — layers 0-5 of its 42
(five gated delta-rule layers, Kimi Delta Attention, arXiv:2510.26692
section 3, then one latent-attention layer: one period of
``layer_group_size`` 6; the two leading dense feed-forwards and four
sparse ones), every layer shared by 64 chips: heads 0-15 of 32 in every
mixer (two tensor-parallel chips), experts 0-7 of 512, an eighth of the
vocabulary; the keys-values' down-projection, the routers and their
biases, the shared expert, the dense feed-forwards and every norm
whole. Straight ``jax.numpy``: no kernel, no chunk, no row buffer, no
cache; every matrix product at precision "highest" and through the
``quant`` pair (the float8 control). Imports nothing of ``fedml_tpu``.
Sizes are read from the ``.json`` beside this file (``model.extra``),
so a test can shrink both.

One layer (``x`` is ``[T, hidden]``; no bias; RMSNorm eps 1e-6 with a
learned scale; ``h = RMSNorm_in(x)``; H heads HELD, of K = V =
``head_dim``; ``t`` a position):

    a delta-rule layer:
      q~, k~, v~ = h W_q, h W_k, h W_v
      q', k', v  = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                   depthwise, causal, ``conv_kernel`` taps a channel,
                   zeros before the sequence, no bias
      q = q' / sqrt(sum q'^2 + 1e-6) / sqrt(K),  k = k' / sqrt(sum k'^2 + 1e-6)
                   a head
      gamma_t = lower * sigmoid(exp(A_log_head) * (h W_f + dt_bias))
                   in R^{H x K}: a log-decay a channel, in (lower, 0)
      beta_t  = sigmoid(h W_b)                          in R^H
      S_t = (I - beta_t k_t k_t^T) Diag(exp(gamma_t)) S_{t-1}
            + beta_t k_t v_t^T                          S_0 = 0, a head
      o_t = S_t^T q_t
      o^_t = RMSNorm_head(o_t) * sigmoid(h W_g)         one scale [V] for
                   all heads; the gate a channel
      x' = x + concat_heads(o^) W_o                     no position term
    the latent layer:
      q = h W_q         as H heads of [q_nope | q_rope]; NO query latent
      [c_kv | k_r] = h W_kva ;  c_kv <- RMSNorm_kv(c_kv)
      [k_nope | v] = c_kv W_kvb    as H heads of nope + v_head_dim
      q_rope, k_r <- rotary over ADJACENT pairs (2i, 2i + 1); k_r is ONE
                     head, the same for all H
      a_j = softmax([q_nope_j | q_rope_j] [k_nope_j | k_r]^T
                    / sqrt(nope + rope) + causal) v_j
      a_j <- a_j * sigmoid(h W_gate)_j       ONE scalar a head and token
      x' = x + concat_j(a_j) W_o
    g = RMSNorm_post(x')
    a dense layer:   y = x' + (silu(g W1) * (g W3)) W2
    a sparse layer:  s = sigmoid(g W_r) over ALL experts;  c = s + b
                     a group (E / n_group consecutive experts) scores
                     the sum of its TWO largest c; the topk_group groups
                     of largest score stay open; E = the k largest c
                     among the experts of open groups
                     w_e = scale s_e / sum_{e' in E} s_e'
                     y = x' + sum_{e in E and HELD} w_e
                              (silu(g W1_e) * (g W3_e)) W2_e
                            + (silu(g S1) * (g S3)) S2

and after the last layer RMSNorm and ``logits = x W_head`` (untied).
The delta rule is the recurrence itself, a token at a time: the state
decays a channel, ``u_t = beta_t (v_t - k_t^T S)`` is what is written
along ``k_t``, ``S += k_t u_t^T``, ``o_t = q_t^T S`` — three products a
token and head — in blocks of :data:`TOKEN_BLOCK` tokens under
``jax.checkpoint``, so that the backward pass holds one state a block.
Both of the router's choices are sets: the group scores and the ranking
come from sorts whose values are thrown away, so no gradient reaches
``b`` or passes through a group's score; the weights read the unbiased
``s``, normalised over all ``k`` chosen, held or not. The held experts
run as a dense loop, each on every token, weighted by a mask; scores a
block of queries at a time, so that ``[H, T, T]`` never exists.

Departures from the published model: the multi-token-prediction module
is left out (its published loss weight is 0), the experts' activation
clamps belong to layers the cut does not keep, and what the absent
chips' heads and experts would add to a layer's output is left out.
What the config is silent on is listed under ``assumed`` in the
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
MIXERS, FEED_FORWARDS = C["layer_types"], C["mlp_layer_types"]
LAYERS = len(MIXERS)
# the heads held, in every mixer
HEADS = (C.get("query_heads_held") or [0, C["heads_per_layer"][0]])[1]
_DA = C["delta_attention"]
HEAD_DIM, TAPS, LOWER = (_DA["head_dim"], _DA["conv_kernel"],
                         float(_DA["gate_lower_bound"]))
_LA = C["latent_attention"]
KV_RANK = _LA["kv_lora_rank"]
NOPE, ROPE, V_DIM = (_LA["qk_nope_head_dim"], _LA["qk_rope_head_dim"],
                     _LA["v_head_dim"])
THETA = float(C["rope"]["latent_attention"]["rope_theta"])
# the expert layers' share
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
N_GROUP, TOPK_GROUP = C["router_groups"]
WIDTH, SHARED = C["moe_intermediate_size"], (
    C["shared_expert_intermediate_size"])
DENSE = C["intermediate_size"]
BIAS_STD = 0.01  # the law of a seed's score-correction bias
SLOPES, STEPS = (0.5, 1.5), (1e-3, 1e-1)  # ... and of its delta-rule gate
QUERY_BLOCK = 512  # queries scored at a time
TOKEN_BLOCK = 64  # tokens of the recurrence a checkpoint

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


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _later(x, steps):
    """``x`` ``[B, T, C]`` moved ``steps`` positions later, zeros in
    front."""
    if not steps:
        return x
    return jnp.concatenate(
        [jnp.zeros_like(x[:, :steps]), x[:, :x.shape[1] - steps]], 1)


def _recurrence(q, k, v, gamma, beta, quant):
    """The delta rule a token at a time: ``q``, ``k``, ``gamma`` ``[B,
    T, H, K]``, ``v`` ``[B, T, H, V]``, ``beta`` ``[B, T, H]`` -> ``o``
    ``[B, T, H, V]``."""
    b, t, heads, dk = k.shape
    block = min(TOKEN_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens do not split into blocks of {block}")

    def token(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None] * state
        write = b_t[..., None] * (v_t - _product(
            "bhk,bhkv->bhv", k_t, state, quant))
        state = state + _product("bhk,bhv->bhkv", k_t, write, quant)
        return state, _product("bhk,bhkv->bhv", q_t, state, quant)

    @jax.checkpoint
    def one_block(state, block_of):
        return lax.scan(token, state, block_of)

    blocks = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        t // block, block, b, *a.shape[2:])
    _, o = lax.scan(one_block, jnp.zeros((b, heads, dk, v.shape[-1])),
                    tuple(map(blocks, (q, k, v, gamma, beta))))
    return jnp.moveaxis(o.reshape(t, b, heads, v.shape[-1]), 0, 1)


def _delta(x, p, quant):
    """``x + delta_rule(RMSNorm_in(x))``."""
    b, t, _ = x.shape
    h = _rms_norm(x, p["delta_norm"]["scale"])
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    heads = lambda y: y.reshape(b, t, HEADS, HEAD_DIM)
    taps = lambda y, kernel: heads(jax.nn.silu(sum(
        kernel[i] * _later(y, TAPS - 1 - i) for i in range(TAPS))))
    mixed = lambda m: taps(proj(m + "_proj"), p[m + "_conv"])
    q, k, v = _unit(mixed("q")) * HEAD_DIM ** -0.5, _unit(mixed("k")), (
        mixed("v"))
    gamma = LOWER * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * heads(
        proj("f_proj") + p["dt_bias"]))
    o = _recurrence(q, k, v, gamma, jax.nn.sigmoid(proj("b_proj")), quant)
    o = _rms_norm(o, p["o_norm"]["scale"]) * heads(
        jax.nn.sigmoid(proj("g_proj")))
    return x + _product("btc,cd->btd", o.reshape(b, t, -1),
                        p["o_proj"]["kernel"], quant)


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
    """``x + attention(RMSNorm_in(x))``, latent keys and values, direct
    queries, a gate a head."""
    b, t, _ = x.shape
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda y, name: _product("btc,cd->btd", y, p[name]["kernel"], quant)
    q = proj(h, "q_proj").reshape(b, t, HEADS, NOPE + ROPE)
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
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS, V_DIM)
    a = a * jax.nn.sigmoid(proj(h, "g_proj"))[..., None]
    return x + proj(a.reshape(b, t, HEADS * V_DIM), "o_proj")


def _gated(g, w1, w3, w2, quant):
    up = jax.nn.silu(_product("nc,cf->nf", g, w1, quant)) * _product(
        "nc,cf->nf", g, w3, quant)
    return _product("nf,fc->nc", up, w2, quant)


def chosen(score, bias):
    """The experts a token chooses, by sorting: ``score`` ``[N, E]``
    (the unbiased probabilities), ``bias`` ``[E]`` -> (ids ``[N, k]``,
    open groups ``[N, n_group]`` bool)."""
    ranked = score + bias
    inside = ranked.reshape(-1, N_GROUP, EXPERTS // N_GROUP)
    group = jnp.sum(jnp.sort(inside, -1)[..., -2:], -1)
    _, best = lax.top_k(group, TOPK_GROUP)
    is_open = jnp.any(best[..., None] == jnp.arange(N_GROUP), -2)
    ranked = jnp.where(
        jnp.repeat(is_open, EXPERTS // N_GROUP, -1), ranked, -jnp.inf)
    return lax.top_k(ranked, TOP_K)[1], is_open


def _feed_forward(x, p, kind, quant):
    b, t, d = x.shape
    g = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)
    if kind == "dense":
        y = _gated(g, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"], quant)
        return x + y.reshape(b, t, d)
    score = jax.nn.sigmoid(_product("nc,ce->ne", g, p["router"], quant))
    # the bias and the groups enter the choice alone: ids, no values
    top_e, _ = chosen(score, p["router_bias"])
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
        mixer = _delta if MIXERS[l] == "delta_attention" else _attention
        layer = jax.checkpoint(
            lambda h, pl, mixer=mixer, kind=FEED_FORWARDS[l]: _feed_forward(
                mixer(h, pl, quant), pl, kind, quant))
        h = layer(h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices — the convolutions' taps (fan-in ``TAPS``) among
    them — normal with variance 1 / fan-in, norm scales 1 + 0.1 normal,
    embedding rows unit normal, the score-correction bias ``BIAS_STD``
    normal; the delta rule's gate by the program's own law: ``A_log``
    the log of a slope uniform in ``SLOPES``, ``dt_bias`` where the gate
    rests at a decay a token drawn log-uniform in ``STEPS``."""
    keys = iter(jax.random.split(key, 32 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    uniform = lambda n, low, high: jax.random.uniform(
        next(keys), (n,), minval=low, maxval=high)
    norm = lambda n=HIDDEN: {"scale": 1.0 + normal(n, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    inner = HEADS * HEAD_DIM
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    for l in range(LAYERS):
        layer = {"mlp_norm": norm()}
        if MIXERS[l] == "delta_attention":
            part = jnp.exp(uniform(inner, *np.log(STEPS))) / -LOWER
            layer.update(
                delta_norm=norm(), o_norm=norm(HEAD_DIM),
                **{m + "_proj": dense(HIDDEN, inner) for m in "qkvfg"},
                **{m + "_conv": normal(TAPS, inner, std=TAPS ** -0.5)
                   for m in "qkv"},
                b_proj=dense(HIDDEN, HEADS), o_proj=dense(inner, HIDDEN),
                A_log=jnp.log(uniform(HEADS, *SLOPES)),
                dt_bias=jnp.log(part) - jnp.log1p(-part))
        else:
            layer.update(
                attn_norm=norm(),
                q_proj=dense(HIDDEN, HEADS * (NOPE + ROPE)),
                kv_a_proj=dense(HIDDEN, KV_RANK + ROPE),
                kv_a_norm=norm(KV_RANK),
                kv_b_proj=dense(KV_RANK, HEADS * (NOPE + V_DIM)),
                g_proj=dense(HIDDEN, HEADS),
                o_proj=dense(HEADS * V_DIM, HIDDEN))
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
    part. The delta rule by the SEQUENTIAL form's three products a token
    and head (read ``k^T S``, write ``k u^T``, read ``q^T S``: ``K x V``
    each), whatever a chunked form adds to them; the latent layer's
    scores and mix over the ``(T + 1) / 2`` keys a causal query reads
    (keys of ``nope + rope``, values of ``v_head_dim``); the routed
    experts at the held experts' uniform share of the ``k`` a token."""
    delta = sum(kind == "delta_attention" for kind in MIXERS)
    latent = LAYERS - delta
    sparse = sum(kind == "sparse" for kind in FEED_FORWARDS)
    inner = HEADS * HEAD_DIM
    return {
        "delta_proj": delta * (6 * HIDDEN * inner + HIDDEN * HEADS),
        "delta_rule": delta * HEADS * 3 * HEAD_DIM * HEAD_DIM,
        "attn_latent": latent * (
            HIDDEN * (HEADS * (NOPE + ROPE) + KV_RANK + ROPE + HEADS)
            + KV_RANK * HEADS * (NOPE + V_DIM)),
        "attn_out": latent * HEADS * V_DIM * HIDDEN,
        "attn_scores": latent * HEADS * (NOPE + ROPE + V_DIM) * (
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
