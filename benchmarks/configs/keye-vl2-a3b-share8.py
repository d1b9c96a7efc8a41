"""Plain float32 reference of ``keye-vl2-a3b-share8``: one chip's share
of the language model of Kwai-Keye's Keye-VL-2.0-30B-A3B
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
config.json) — 5 of its 48 identical layers, 16 of the 128 routed
experts, an eighth of the vocabulary; the vision tower is left out and
the traffic is text. Straight ``jax.numpy``: no kernel, no cache, no
bisection; every matrix product at precision "highest" and through the
``quant`` pair (the float8 control). Imports nothing of ``fedml_tpu``.
Sizes are read from the ``.json`` beside this file (``model.extra``), so
a test can shrink both.

The equations (``x`` is ``[T, hidden]``; no bias; RMSNorm with a learned
scale; pre-norm residual blocks; ``t`` a query position, ``s <= t`` a
key position):

- index: ``h = RMSNorm(x)``; ``qI[t, j] = rot(h[t] WqI)[j]`` in R^E for
  the J index heads, ``kI[s] = rot(h[s] WkI)`` in R^E (ONE head, shared
  by the J), ``w[t] = h[t] Ww`` in R^J; ``I[t, s] = (J E)^-1/2 sum_j
  w[t, j] relu(qI[t, j] . kI[s])``.
- selection: ``S[t]`` = the ``min(t + 1, topk)`` positions ``s <= t`` of
  largest ``I[t, s]``, ties to the lower ``s`` (``lax.top_k`` over the
  row, which ranks equal scores by position); one set a query, shared by
  every head.
- attention: ``q = h Wq`` as ``[T, H, d]``, ``k``, ``v`` as ``[T, Hkv,
  d]``; each head of ``q`` and ``k`` RMS-normed (one learned scale over
  ``d``) and then rotated; query head j reads key-value head ``j // (H /
  Hkv)``; per head ``softmax over s in S[t]`` of ``q[t] . k[s] /
  sqrt(d)``, the mix of ``v[s]``; ``x = x + a Wo``.
- experts: ``E(h) = (silu(h W1) * (h W3)) W2``; ``p = softmax(h Wr)``
  over ALL experts; the ``k`` largest renormalised to sum 1; ``y = sum
  over the chosen and HELD of p_e E_e(h)``: a dense loop over the held
  experts, each on every token, weighted by a mask. No shared expert.
- head: RMSNorm, ``logits = x Whead``.
- gradient: ``S[t]`` is a set, so ``WqI``, ``WkI``, ``Ww`` get exactly
  zero gradient from the next-token loss (the published recipe trains
  them with an alignment loss the config does not carry).

Scores are computed a block of queries at a time, so that ``[H, T, T]``
never exists. ``rot`` is rotary over all dimensions of the vector it
turns, theta from the config, rotate-half pairing; on text the three
position ids of ``mrope_section`` are equal and the sections are plain
rotary. What the config is silent on is listed under ``assumed`` in the
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
HIDDEN, HEAD_DIM, KV = C["hidden_size"], C["head_dim"], C["num_key_value_heads"]
HEADS = C["heads_per_layer"][0]
LAYERS = len(C["layer_types"])
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
WIDTH = C["moe_intermediate_size"]
INDEX_HEADS = C["sparse_attention"]["index_heads"]
INDEX_DIM = C["sparse_attention"]["index_head_dim"]
KEYS_KEPT = C["sparse_attention"]["topk"]
THETA = float(C["rope"]["sparse_attention"]["rope_theta"])
EPS = C["rms_norm_eps"]
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


def _selection(h, p, quant):
    """``[B, T, T]`` bool: the keys each query reads. Nothing here is
    differentiated: the result is a set."""
    b, t, _ = h.shape
    h = lax.stop_gradient(h)
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    qi = _rotate(proj("index_q_proj").reshape(b, t, INDEX_HEADS, INDEX_DIM))
    ki = _rotate(proj("index_k_proj").reshape(b, t, 1, INDEX_DIM))[:, :, 0]
    w = proj("index_w_proj")
    blocks, block = _query_blocks(t)
    kept = min(KEYS_KEPT, t)

    def one_block(args):
        first, q_blk, w_blk = args  # [], [B, block, J, E], [B, block, J]
        s = _product("bqje,bke->bqjk", q_blk, ki, quant)
        score = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(s), w_blk) * (
            INDEX_HEADS * INDEX_DIM) ** -0.5
        score = jnp.where(score == 0, 0.0, score)  # -0.0 ranks as 0.0
        rows = first + jnp.arange(block)[:, None]
        score = jnp.where(jnp.arange(t)[None, :] <= rows, score, -jnp.inf)
        top_v, top_i = lax.top_k(score, kept)  # equal scores: lower s first
        chosen = jnp.zeros((b, block, t), bool)
        return chosen.at[jnp.arange(b)[:, None, None],
                         jnp.arange(block)[None, :, None],
                         top_i].max(top_v > -jnp.inf)

    split = lambda x: jnp.moveaxis(
        x.reshape(b, blocks, block, *x.shape[2:]), 1, 0)
    chosen = lax.map(one_block, (jnp.arange(blocks) * block, split(qi),
                                 split(w)))
    return jnp.moveaxis(chosen, 0, 1).reshape(b, t, t)


def _attention(x, p, quant):
    b, t, _ = x.shape
    group = HEADS // KV
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    q = proj("q_proj").reshape(b, t, KV, group, HEAD_DIM)
    k = proj("k_proj").reshape(b, t, KV, HEAD_DIM)
    v = proj("v_proj").reshape(b, t, KV, HEAD_DIM)
    q = _rotate(_rms_norm(q, p["q_norm"]["scale"]).reshape(
        b, t, HEADS, HEAD_DIM)).reshape(b, t, KV, group, HEAD_DIM)
    k = _rotate(_rms_norm(k, p["k_norm"]["scale"]))
    chosen = _selection(h, p, quant)
    blocks, block = _query_blocks(t)

    @jax.checkpoint
    def one_block(args):  # a block of queries against every key
        q_blk, chosen_blk = args  # [B, block, KV, group, d], [B, block, T]
        s = _product("bqgnd,bkgd->bgnqk", q_blk, k, quant) / HEAD_DIM ** 0.5
        a = jax.nn.softmax(
            jnp.where(chosen_blk[:, None, None], s, -jnp.inf), axis=-1)
        return _product("bgnqk,bkgd->bqgnd", a, v, quant)

    split = lambda x: jnp.moveaxis(
        x.reshape(b, blocks, block, *x.shape[2:]), 1, 0)
    a = lax.map(one_block, (split(q), split(chosen)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS * HEAD_DIM)
    return x + _product("btc,cd->btd", a, p["o_proj"]["kernel"], quant)


def _gated(h, w1, w3, w2, quant):
    up = jax.nn.silu(_product("nc,cf->nf", h, w1, quant)) * _product(
        "nc,cf->nf", h, w3, quant)
    return _product("nf,fc->nc", up, w2, quant)


def _experts(x, p, quant):
    b, t, d = x.shape
    h = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)
    prob = jax.nn.softmax(_product("nc,ce->ne", h, p["router"], quant), -1)
    top_p, top_e = lax.top_k(prob, TOP_K)
    weight = C["routed_scaling_factor"] * top_p / jnp.sum(
        top_p, -1, keepdims=True)

    @jax.checkpoint
    def one_expert(y, expert):  # every held expert on every token
        e, w1, w3, w2 = expert
        share = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
        return y + share[:, None] * _gated(h, w1, w3, w2, quant), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), (
        FIRST + jnp.arange(HELD), p["experts_w1"], p["experts_w3"],
        p["experts_w2"]))
    return x + y.reshape(b, t, d)


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    h = p["embed"]["embedding"][x]
    layer = jax.checkpoint(
        lambda h, pl: _experts(_attention(h, pl, quant), pl, quant))
    for l in range(LAYERS):
        h = layer(h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices normal with variance 1 / fan-in, norm scales 1 +
    0.1 normal, embedding rows unit normal."""
    keys = iter(jax.random.split(key, 32 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda n=HIDDEN: {"scale": 1.0 + normal(n, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    for l in range(LAYERS):
        params[f"layer_{l}"] = {
            "attn_norm": norm(), "mlp_norm": norm(),
            "q_norm": norm(HEAD_DIM), "k_norm": norm(HEAD_DIM),
            "q_proj": dense(HIDDEN, HEADS * HEAD_DIM),
            "k_proj": dense(HIDDEN, KV * HEAD_DIM),
            "v_proj": dense(HIDDEN, KV * HEAD_DIM),
            "o_proj": dense(HEADS * HEAD_DIM, HIDDEN),
            "index_q_proj": dense(HIDDEN, INDEX_HEADS * INDEX_DIM),
            "index_k_proj": dense(HIDDEN, INDEX_DIM),
            "index_w_proj": dense(HIDDEN, INDEX_HEADS),
            "router": normal(HIDDEN, EXPERTS, std=HIDDEN ** -0.5),
            "experts_w1": normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
            "experts_w3": normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
            "experts_w2": normal(HELD, WIDTH, HIDDEN, std=WIDTH ** -0.5),
        }
    return {"params": params}


def keys_attended():
    """-> (keys a query reads, keys its index scores), means over the
    ``SEQ`` positions: ``min(t + 1, topk)`` and ``t + 1``."""
    seen = np.arange(SEQ) + 1
    return float(np.minimum(seen, KEYS_KEPT).mean()), float(seen.mean())


def token_macs():
    """Multiply-accumulates of one token's forward pass, by part; the
    two ``index_*`` parts are never differentiated."""
    read, scored = keys_attended()
    width = HEADS * HEAD_DIM
    return {
        "attn_proj": LAYERS * HIDDEN * (2 * width + 2 * KV * HEAD_DIM),
        "attn_scores": LAYERS * 2 * width * read,
        "index_proj": LAYERS * HIDDEN * (
            INDEX_HEADS * INDEX_DIM + INDEX_DIM + INDEX_HEADS),
        "index_scores": LAYERS * INDEX_HEADS * INDEX_DIM * scored,
        "router": LAYERS * HIDDEN * EXPERTS,
        # the EXPECTED routed work: k experts a token, of which the held
        # share lands here
        "experts": LAYERS * TOP_K * HELD / EXPERTS * 3 * HIDDEN * WIDTH,
        "head": float(HIDDEN * VOCAB),
    }


def step_flops(batch):
    """Matrix work the published arithmetic needs for one optimizer step
    of ``batch`` sequences of ``SEQ``: attention over ``|S[t]|`` keys,
    the index over ``t + 1``; forward + backward (two products backward
    for one forward) but for the index, which is forward only; two
    operations a multiply-accumulate; recomputation not counted."""
    parts = token_macs()
    index = parts["index_proj"] + parts["index_scores"]
    return 2.0 * (3.0 * (sum(parts.values()) - index) + index) * SEQ * batch
