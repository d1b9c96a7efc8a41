"""Plain float32 reference of ``laguna-xs2-share8``: one chip's share of
poolside's Laguna-XS.2 (https://huggingface.co/poolside/Laguna-XS.2/
blob/main/config.json) — the leading dense layer and one period of the
layer pattern, 32 of the 256 routed experts, an eighth of the
vocabulary. Straight ``jax.numpy``: no kernel, no cache, no sort; every
matrix product at precision "highest" and through the ``quant`` pair
(the float8 control). Imports nothing of ``fedml_tpu``. Sizes are read
from the ``.json`` beside this file (``model.extra``), so a test can
shrink both.

The equations (``x`` is ``[T, hidden]``; no bias; RMSNorm with a learned
scale; pre-norm residual blocks):

- attention, layer l: ``h = RMSNorm(x)``; ``q = h Wq`` as ``[T, H_l,
  d]``, ``k``, ``v`` as ``[T, Hkv, d]``; query head j reads key-value
  head ``j // (H_l / Hkv)``; rotary positions on the first ``r x d``
  dimensions of q and k (sliding layers: plain; full layers: YaRN, cos
  and sin times the attention factor); scores ``q k^T / sqrt(d)``,
  causal, sliding layers also mask ``j <= i - window``; ``a =
  softmax(scores) v``; ``g = sigmoid(h Wg)``, head j of ``a`` times
  ``g[:, j]``; ``x = x + a Wo``.
- feed-forward: ``E(h) = (silu(h W1) * (h W3)) W2``. Dense layer: ``x +
  E(h)``. Sparse layer: ``p = sigmoid(h Wr)`` over ALL experts; ``S(t)``
  the ``k`` largest; ``w_e = p_e / sum_S p``; ``y = scale * sum over S(t)
  and HELD of w_e E_e(h) + E_shared(h)``: a dense loop over the held
  experts, each on every token, weighted by a mask.
- head: RMSNorm, ``logits = x Whead``.

Departures from the published description, each also under ``assumed``
in the ``.json``: (1) the form of ``gating: true`` (the config names the
switch, not the arithmetic): a per-head sigmoid gate of the normed
input on the attention output; (2) router scores are a sigmoid
renormalised over the chosen experts (no scoring key in the config; the
routed scale 2.5 without groups is that convention); (3) rotate-half
pairing of rotary dimensions (the ``transformers`` convention); (4) the
chip's share: experts ``[first, first + count)`` of 256 and 12,544 of
100,352 vocabulary rows, 5 of 40 layers — what the absent experts would
add is left out, the weights ``w_e`` still normalised over all ``k``.
"""

import json
import math
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
LAYERS = len(C["layer_types"])
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
EPS = C["rms_norm_eps"]

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


def _rotary(kind):
    """cos, sin ``[T, rot]`` of one layer kind (float64 arithmetic on the
    host), the attention factor folded in."""
    rope = C["rope"][kind]
    rot = int(HEAD_DIM * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        scale = float(rope["factor"])
        span = float(rope["original_max_position_embeddings"])
        # the dimension at which a wavelength makes `turns` rotations
        # over the original context
        dim_of = lambda turns: rot * math.log(
            span / (turns * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / scale
        factor = float(rope.get("attention_factor", 1.0))
    angles = np.arange(SEQ, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], -1)
    return (jnp.asarray(np.cos(angles) * factor, jnp.float32),
            jnp.asarray(np.sin(angles) * factor, jnp.float32))


def _rotate(x, cos, sin):
    """``x`` ``[B, T, H, d]``: the first ``rot`` dimensions turned,
    dimension i paired with i + rot / 2; the rest passed through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    t = x.shape[1]
    c, s = cos[None, :t, None, :], sin[None, :t, None, :]
    return jnp.concatenate([xr * c + turned * s, rest], -1)


def _attention(x, p, heads, kind, quant):
    b, t, _ = x.shape
    group = heads // KV
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    cos, sin = _rotary(kind)
    q = _rotate(proj("q_proj").reshape(b, t, heads, HEAD_DIM), cos, sin)
    k = _rotate(proj("k_proj").reshape(b, t, KV, HEAD_DIM), cos, sin)
    v = proj("v_proj").reshape(b, t, KV, HEAD_DIM)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        seen &= j > i - C["sliding_window"]

    @jax.checkpoint
    def one_kv_head(qkv):  # in blocks of one key-value head, so it fits
        qh, kh, vh = qkv  # [B, T, group, d], [B, T, d], [B, T, d]
        s = _product("bqnd,bkd->bnqk", qh, kh, quant) / HEAD_DIM ** 0.5
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("bnqk,bkd->bqnd", a, vh, quant)

    qg = q.reshape(b, t, KV, group, HEAD_DIM)
    a = lax.map(one_kv_head, (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))
    a = jnp.moveaxis(a, 0, 2).reshape(b, t, heads, HEAD_DIM)
    if C["gating"]:
        a = a * jax.nn.sigmoid(proj("g_proj"))[..., None]
    return x + _product("btc,cd->btd", a.reshape(b, t, heads * HEAD_DIM),
                        p["o_proj"]["kernel"], quant)


def _gated(h, w1, w3, w2, quant):
    up = jax.nn.silu(_product("nc,cf->nf", h, w1, quant)) * _product(
        "nc,cf->nf", h, w3, quant)
    return _product("nf,fc->nc", up, w2, quant)


def _feed_forward(x, p, kind, quant):
    b, t, d = x.shape
    h = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)
    if kind == "dense":
        y = _gated(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"], quant)
        return x + y.reshape(b, t, d)
    prob = jax.nn.sigmoid(_product("nc,ce->ne", h, p["router"], quant))
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
    if "shared_w1" in p:
        y = y + _gated(h, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                       quant)
    return x + y.reshape(b, t, d)


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    h = p["embed"]["embedding"][x]
    for l in range(LAYERS):
        layer = jax.checkpoint(
            lambda h, pl, l=l: _feed_forward(
                _attention(h, pl, C["heads_per_layer"][l],
                           C["layer_types"][l], quant),
                pl, C["mlp_layer_types"][l], quant))
        h = layer(h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices normal with variance 1 / fan-in, norm scales 1 +
    0.1 normal, embedding rows unit normal."""
    keys = iter(jax.random.split(key, 32 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda: {"scale": 1.0 + normal(HIDDEN, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    f, fs = C["moe_intermediate_size"], C["shared_expert_intermediate_size"]
    for l in range(LAYERS):
        width = C["heads_per_layer"][l] * HEAD_DIM
        layer = {"attn_norm": norm(), "mlp_norm": norm(),
                 "q_proj": dense(HIDDEN, width),
                 "k_proj": dense(HIDDEN, KV * HEAD_DIM),
                 "v_proj": dense(HIDDEN, KV * HEAD_DIM),
                 "o_proj": dense(width, HIDDEN)}
        if C["gating"]:
            layer["g_proj"] = dense(HIDDEN, C["heads_per_layer"][l])
        if C["mlp_layer_types"][l] == "dense":
            wide = C["intermediate_size"]
            layer.update(gate_proj=dense(HIDDEN, wide),
                         up_proj=dense(HIDDEN, wide),
                         down_proj=dense(wide, HIDDEN))
        else:
            layer.update(
                router=normal(HIDDEN, EXPERTS, std=HIDDEN ** -0.5),
                experts_w1=normal(HELD, HIDDEN, f, std=HIDDEN ** -0.5),
                experts_w3=normal(HELD, HIDDEN, f, std=HIDDEN ** -0.5),
                experts_w2=normal(HELD, f, HIDDEN, std=f ** -0.5))
            if fs:
                layer.update(
                    shared_w1=normal(HIDDEN, fs, std=HIDDEN ** -0.5),
                    shared_w3=normal(HIDDEN, fs, std=HIDDEN ** -0.5),
                    shared_w2=normal(fs, HIDDEN, std=fs ** -0.5))
        params[f"layer_{l}"] = layer
    return {"params": params}


def keys_attended(kind):
    """Keys a query reads, mean over the ``SEQ`` positions: ``(T + 1) /
    2`` under the causal mask, ``min(i + 1, window)`` in a sliding
    layer."""
    i = np.arange(SEQ)
    if kind == "sliding_attention":
        return float(np.minimum(i + 1, C["sliding_window"]).mean())
    return float((i + 1).mean())


def token_macs():
    """Multiply-accumulates of one token's forward pass, by part."""
    parts = {"attn_proj": 0.0, "attn_scores": 0.0, "dense": 0.0,
             "router": 0.0, "experts": 0.0, "shared": 0.0}
    for l in range(LAYERS):
        heads = C["heads_per_layer"][l]
        width = heads * HEAD_DIM
        parts["attn_proj"] += HIDDEN * (
            2 * width + 2 * KV * HEAD_DIM + (heads if C["gating"] else 0))
        parts["attn_scores"] += 2 * width * keys_attended(C["layer_types"][l])
        if C["mlp_layer_types"][l] == "dense":
            parts["dense"] += 3 * HIDDEN * C["intermediate_size"]
        else:
            parts["router"] += HIDDEN * EXPERTS
            # the EXPECTED routed work: k experts a token, of which the
            # held share lands here
            parts["experts"] += TOP_K * HELD / EXPERTS * 3 * HIDDEN * (
                C["moe_intermediate_size"])
            parts["shared"] += 3 * HIDDEN * (
                C["shared_expert_intermediate_size"])
    parts["head"] = float(HIDDEN * VOCAB)
    return parts


def step_flops(batch):
    """Matrix work of forward + backward (two products backward for one
    forward) of one optimizer step of ``batch`` sequences of ``SEQ``;
    two operations a multiply-accumulate; recomputation not counted."""
    return 3.0 * 2.0 * sum(token_macs().values()) * SEQ * batch
