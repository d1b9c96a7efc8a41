"""Plain float32 reference of ``smallthinker-21b-share4``: one chip's
share of PowerInfer's SmallThinker-21BA3B-Instruct
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/
main/config.json, arXiv:2507.20984) — layers 0-3 of its 52 (one period:
a full-attention layer with NO position term, then three causal windows
of 4,096 with rotary), every layer shared by the four chips of one
host: query heads 0-6 of 28 over key-value head 0 of 4, experts 0-15 of
64, a quarter of the vocabulary. Straight ``jax.numpy``: no kernel, no
row buffer, no sort, no cache; every matrix product at precision
"highest" and through the ``quant`` pair (the float8 control). Imports
nothing of ``fedml_tpu``. Sizes are read from the ``.json`` beside this
file (``model.extra``), so a test can shrink both.

One layer (``x`` is ``[T, hidden]``; no bias; RMSNorm eps 1e-6 with a
learned scale; ``t`` a query position, ``s <= t`` a key position):

    h  = RMSNorm_in(x)
    l  = h W_r                  over ALL experts   <- the router reads
                                                      the ATTENTION's input
    E  = the k experts of largest l
    w_e = exp(l_e) / sum_{e' in E} exp(l_e')       (top-k, THEN softmax)
    q, k, v = h W_q, h W_k, h W_v   as heads of d; rotary (rotate-half:
              dimension i with i + d / 2) on q and k in window layers
              only, none in a full layer
    x' = x + [softmax(q k^T / sqrt(d) + mask) v] W_o
              mask: s <= t, and s > t - window in a window layer
    g  = RMSNorm_post(x')
    y  = x' + sum_{e in E and HELD} w_e (relu(g W1_e) * (g W3_e)) W2_e

and after the last layer RMSNorm and ``logits = x W_head`` (untied).
The held experts run as a dense loop, each on every token, weighted by
a mask; scores a block of queries at a time, so that ``[H, T, T]``
never exists. The gradient reaches ``x`` by two roads: through ``w_e``
into ``h``, and through the experts' rows into ``g``.

What the absent chips' heads and experts would add to a layer's output
is left out (the weights ``w_e`` stay normalised over all ``k`` chosen,
held or not). What the config is silent on is listed under ``assumed``
in the ``.json``.
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
KINDS = C["layer_types"]
LAYERS = len(KINDS)
HEAD_DIM, WINDOW = C["head_dim"], C["sliding_window"]
# attention's share: the held query heads read the held key-value heads
HEADS, KV = C["query_heads_held"][1], C["key_value_heads_held"][1]
# the expert layers' share
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
WIDTH = C["moe_intermediate_size"]
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


def _rotate(x, theta):
    """``x`` ``[B, T, heads, d]``: every dimension turned by its
    position, dimension i paired with i + d / 2 (angles in float64 on
    the host)."""
    t, n = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
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


def _attention(x, h, p, kind, quant):
    """``x + attention(h)``, ``h`` the layer's normed input."""
    b, t, _ = x.shape
    group = HEADS // KV
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    q = proj("q_proj").reshape(b, t, HEADS, HEAD_DIM)
    k = proj("k_proj").reshape(b, t, KV, HEAD_DIM)
    v = proj("v_proj").reshape(b, t, KV, HEAD_DIM)
    rope = C["rope"][kind]
    if rope.get("rope_type", "default") != "none":
        theta = float(rope["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    q = q.reshape(b, t, KV, group, HEAD_DIM)
    blocks, block = _query_blocks(t)

    @jax.checkpoint
    def one_block(args):  # a block of queries against every key
        first, q_blk = args  # [], [B, block, KV, group, d]
        s = _product("bqgnd,bkgd->bgnqk", q_blk, k, quant) / HEAD_DIM ** 0.5
        rows = first + jnp.arange(block)[:, None]
        seen = jnp.arange(t)[None, :] <= rows
        if kind == "sliding_attention":
            seen &= jnp.arange(t)[None, :] > rows - WINDOW
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("bgnqk,bkgd->bqgnd", a, v, quant)

    q = jnp.moveaxis(q.reshape(b, blocks, block, *q.shape[2:]), 1, 0)
    a = lax.map(one_block, (jnp.arange(blocks) * block, q))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS * HEAD_DIM)
    return x + _product("btc,cd->btd", a, p["o_proj"]["kernel"], quant)


def _relu_gated(g, w1, w3, w2, quant):
    up = jax.nn.relu(_product("nc,cf->nf", g, w1, quant)) * _product(
        "nc,cf->nf", g, w3, quant)
    return _product("nf,fc->nc", up, w2, quant)


def _layer(x, p, kind, quant):
    b, t, d = x.shape
    h = _rms_norm(x, p["attn_norm"]["scale"])
    # the router stands before attention: it reads the attention's input
    logits = _product("nc,ce->ne", h.reshape(b * t, d), p["router"], quant)
    top_l, top_e = lax.top_k(logits, TOP_K)
    weight = C["routed_scaling_factor"] * jax.nn.softmax(top_l, -1)
    x = _attention(x, h, p, kind, quant)
    g = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)

    @jax.checkpoint
    def one_expert(y, expert):  # every held expert on every token
        e, w1, w3, w2 = expert
        share = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
        return y + share[:, None] * _relu_gated(g, w1, w3, w2, quant), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), (
        FIRST + jnp.arange(HELD), p["experts_w1"], p["experts_w3"],
        p["experts_w2"]))
    return x + y.reshape(b, t, d)


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    h = p["embed"]["embedding"][x]
    for l in range(LAYERS):
        h = jax.checkpoint(
            lambda h, pl, kind=KINDS[l]: _layer(h, pl, kind, quant))(
                h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices normal with variance 1 / fan-in, norm scales 1 +
    0.1 normal, embedding rows unit normal."""
    keys = iter(jax.random.split(key, 16 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda: {"scale": 1.0 + normal(HIDDEN, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    for l in range(LAYERS):
        params[f"layer_{l}"] = {
            "attn_norm": norm(), "mlp_norm": norm(),
            "q_proj": dense(HIDDEN, HEADS * HEAD_DIM),
            "k_proj": dense(HIDDEN, KV * HEAD_DIM),
            "v_proj": dense(HIDDEN, KV * HEAD_DIM),
            "o_proj": dense(HEADS * HEAD_DIM, HIDDEN),
            "router": normal(HIDDEN, EXPERTS, std=HIDDEN ** -0.5),
            "experts_w1": normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
            "experts_w3": normal(HELD, HIDDEN, WIDTH, std=HIDDEN ** -0.5),
            "experts_w2": normal(HELD, WIDTH, HIDDEN, std=WIDTH ** -0.5),
        }
    return {"params": params}


def keys_attended(kind):
    """Keys a query reads, mean over the ``SEQ`` positions: ``(T + 1) /
    2`` under the causal mask, ``min(t + 1, window)`` in a window
    layer."""
    seen = np.arange(SEQ) + 1
    if kind == "sliding_attention":
        seen = np.minimum(seen, WINDOW)
    return float(seen.mean())


def token_macs():
    """Multiply-accumulates of one token's forward pass in the share, by
    part: attention over the keys a query of each layer kind reads, the
    routed experts at the held experts' uniform share of the ``k`` a
    token."""
    width = HEADS * HEAD_DIM
    return {
        "attn_proj": LAYERS * HIDDEN * (2 * width + 2 * KV * HEAD_DIM),
        "attn_scores": sum(2 * width * keys_attended(k) for k in KINDS),
        "router": LAYERS * HIDDEN * EXPERTS,
        "experts": LAYERS * TOP_K * HELD / EXPERTS * 3 * HIDDEN * WIDTH,
        "head": float(HIDDEN * VOCAB),
    }


def step_flops(batch):
    """Matrix work the published arithmetic needs for one optimizer step
    of ``batch`` sequences of ``SEQ`` in the share: forward + backward
    (two products backward for one forward); two operations a
    multiply-accumulate; recomputation not counted."""
    return 2.0 * 3.0 * sum(token_macs().values()) * SEQ * batch
