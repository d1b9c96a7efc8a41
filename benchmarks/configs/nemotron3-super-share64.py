"""Plain float32 reference of ``nemotron3-super-share64``: one chip's
share of NVIDIA's Nemotron-3-Super-120B-A12B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/
blob/main/config.json, ``model_type: nemotron_h``) — layers 0-10 of its
88 (``MEMEMEM*EME``: 5 Mamba-2, 5 latent expert, 1 attention), every
layer shared by 64 chips: 16 of the 128 Mamba heads with 1 of the 8 B/C
groups, 4 of the 32 query heads over 1 of the 2 key-value heads, 8 of
the 512 routed experts, 672 of the shared expert's 5,376 columns, an
eighth of the vocabulary. Straight ``jax.numpy``: no kernel, no chunks,
no cache; every matrix product at precision "highest" and through the
``quant`` pair (the float8 control). Imports nothing of ``fedml_tpu``.
Sizes are read from the ``.json`` beside this file (``model.extra``), so
a test can shrink both.

Every layer is ``x <- x + mixer(RMSNorm(x))`` (``x`` is ``[T, hidden]``;
eps 1e-5; no bias but the convolution's; a learned scale a norm):

- ``M``, Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC <- silu(causal
  depthwise convolution of kernel 4, with bias)``; ``x`` as heads of P,
  ``B`` and ``C`` as groups of N (a group serves H / G heads); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; per head the
  recurrence, ONE TOKEN AT A TIME (``lax.scan``; cut into segments only
  so that the backward pass keeps a segment's states and not a
  sequence's), ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t``; ``y <- RMSNorm_group(y * silu(z)) * scale``, the
  norm over each group's channels; out ``= y W_out``.
- ``E``, latent sparse layer: ``p = sigmoid(h W_r)`` over ALL experts,
  the ``k`` largest renormalised to sum 1, times the scaling factor;
  ``u = h W_1`` (hidden -> latent); expert ``e``: ``relu(u A_e)^2
  B_e``; ``y = (sum over the chosen and HELD of p_e expert_e(u)) W_2``
  (latent -> hidden) ``+ relu(h S_1)^2 S_2`` (the held columns of the
  shared expert, on the full hidden): a dense loop over the held
  experts, each on every token, weighted by a mask.
- ``*``, attention: ``q = h Wq`` as ``[T, H, d]``, ``k``, ``v`` as ``[T,
  Hkv, d]``; NO rotary and no other position term; per head causal
  ``softmax(q . k / sqrt(d))``, the mix of ``v``; out ``= a Wo``; a
  block of queries at a time, so that ``[H, T, T]`` never exists.
- head: RMSNorm, ``logits = x Whead`` (untied).

What the absent chips' heads, columns and experts would add to a layer's
output is left out. What the config is silent on is listed under
``assumed`` in the ``.json``.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    _MODEL = json.load(_f)["model"]
C = _MODEL["extra"]
VOCAB, SEQ = int(_MODEL["num_classes"]), int(_MODEL["input_shape"][0])
HIDDEN, EPS = C["hidden_size"], C["rms_norm_eps"]
MIXERS, FEED_FORWARDS = C["layer_types"], C["mlp_layer_types"]
LAYERS = len(MIXERS)
# attention's share
HEAD_DIM = C["head_dim"]
HEADS, KV = C["query_heads_held"][1], C["key_value_heads_held"][1]
# the state-space layers' share
S = C["state_space"]
SSM_HEADS, SSM_DIM, STATE = S["heads_held"][1], S["head_dim"], S["state_size"]
GROUPS = SSM_HEADS // (S["num_heads"] // S["n_groups"])
INNER, KERNEL = SSM_HEADS * SSM_DIM, S["conv_kernel"]
CHANNELS = INNER + 2 * GROUPS * STATE  # what the convolution runs over
# the expert layers' share
FIRST, HELD = C["experts_held"]
TOP_K, EXPERTS = C["num_experts_per_tok"], C["num_experts"]
WIDTH, LATENT = C["moe_intermediate_size"], C["moe_latent_size"]
SHARED = C["shared_expert_columns_held"][1]
QUERY_BLOCK = 512  # queries scored at a time
SEGMENT = 128  # tokens whose states a backward pass holds at a time

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


def _blocks(t, block):
    block = min(block, t)
    if t % block:
        raise ValueError(f"{t} tokens do not split into blocks of {block}")
    return t // block, block


def _recurrence(x, dt, a, b, c, quant):
    """``x`` ``[B, T, H, P]``, ``dt`` ``[B, T, H]``, ``a`` ``[H]``, ``b``
    / ``c`` ``[B, T, G, N]`` -> ``S_t C_t`` ``[B, T, H, P]``, the state a
    token at a time. Under the control the recurrence's inputs and its
    cotangent are rounded as a product's are."""
    if quant is not None:
        x, b, c = quant[0](x), quant[0](b), quant[0](c)
    bsz, t = x.shape[:2]
    per = SSM_HEADS // GROUPS

    def token(state, now):
        x_t, dt_t, b_t, c_t = now  # [B, H, P], [B, H], [B, G, N] twice
        b_t, c_t = jnp.repeat(b_t, per, 1), jnp.repeat(c_t, per, 1)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    segments, length = _blocks(t, SEGMENT)
    cut = lambda v: jnp.moveaxis(v, 1, 0).reshape(
        segments, length, *v.shape[:1], *v.shape[2:])
    _, y = lax.scan(
        jax.checkpoint(lambda state, seg: lax.scan(token, state, seg)),
        jnp.zeros((bsz, SSM_HEADS, SSM_DIM, STATE), jnp.float32),
        (cut(x), cut(dt), cut(b), cut(c)))
    y = jnp.moveaxis(y.reshape(t, bsz, SSM_HEADS, SSM_DIM), 0, 1)
    return y if quant is None else quant[1](y)


def _mamba(x, p, quant):
    b, t, _ = x.shape
    h = _rms_norm(x, p["ssm_norm"]["scale"])
    z, xbc, dt = jnp.split(
        _product("btc,cd->btd", h, p["in_proj"]["kernel"], quant),
        [INNER, INNER + CHANNELS], -1)
    padded = jnp.pad(xbc, ((0, 0), (KERNEL - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][i] * padded[:, i:i + t] for i in range(KERNEL)))
    xs, bm, cm = jnp.split(xbc, [INNER, INNER + GROUPS * STATE], -1)
    xs = xs.reshape(b, t, SSM_HEADS, SSM_DIM)
    y = _recurrence(
        xs, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        bm.reshape(b, t, GROUPS, STATE), cm.reshape(b, t, GROUPS, STATE),
        quant) + p["D"][:, None] * xs
    y = y.reshape(b, t, INNER) * jax.nn.silu(z)
    y = y.reshape(b, t, GROUPS, INNER // GROUPS)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + EPS)
    y = y.reshape(b, t, INNER) * p["gate_norm"]
    return x + _product("btc,cd->btd", y, p["out_proj"]["kernel"], quant)


def _attention(x, p, quant):
    b, t, _ = x.shape
    group = HEADS // KV
    h = _rms_norm(x, p["attn_norm"]["scale"])
    proj = lambda name: _product("btc,cd->btd", h, p[name]["kernel"], quant)
    q = proj("q_proj").reshape(b, t, KV, group, HEAD_DIM)
    k = proj("k_proj").reshape(b, t, KV, HEAD_DIM)
    v = proj("v_proj").reshape(b, t, KV, HEAD_DIM)
    blocks, block = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(args):  # a block of queries against every key
        first, q_blk = args  # [], [B, block, KV, group, d]
        s = _product("bqgnd,bkgd->bgnqk", q_blk, k, quant) / HEAD_DIM ** 0.5
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("bgnqk,bkgd->bqgnd", a, v, quant)

    q = jnp.moveaxis(q.reshape(b, blocks, block, *q.shape[2:]), 1, 0)
    a = lax.map(one_block, (jnp.arange(blocks) * block, q))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, HEADS * HEAD_DIM)
    return x + _product("btc,cd->btd", a, p["o_proj"]["kernel"], quant)


def _relu2(h, w1, w2, quant):
    up = jax.nn.relu(_product("nc,cf->nf", h, w1, quant))
    return _product("nf,fc->nc", up * up, w2, quant)


def _experts(x, p, quant):
    b, t, d = x.shape
    h = _rms_norm(x, p["mlp_norm"]["scale"]).reshape(b * t, d)
    prob = jax.nn.sigmoid(_product("nc,ce->ne", h, p["router"], quant))
    top_p, top_e = lax.top_k(prob, TOP_K)
    weight = C["routed_scaling_factor"] * top_p / jnp.sum(
        top_p, -1, keepdims=True)
    u = _product("nc,cl->nl", h, p["latent_in"], quant)

    @jax.checkpoint
    def one_expert(y, expert):  # every held expert on every token
        e, w1, w2 = expert
        share = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
        return y + share[:, None] * _relu2(u, w1, w2, quant), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(u), (
        FIRST + jnp.arange(HELD), p["experts_w1"], p["experts_w2"]))
    y = _product("nl,lc->nc", y, p["latent_out"], quant) + _relu2(
        h, p["shared_w1"], p["shared_w2"], quant)
    return x + y.reshape(b, t, d)


def _layer(l):
    """Layer ``l``'s one mixer."""
    if MIXERS[l] == "state_space":
        return _mamba
    if MIXERS[l] == "full_attention":
        return _attention
    if FEED_FORWARDS[l] == "sparse":
        return _experts
    raise ValueError(f"layer {l}: {MIXERS[l]} + {FEED_FORWARDS[l]}")


def forward(variables, x, train, quant=None):
    """Tokens ``[B, T]`` -> (logits ``[B, T, VOCAB]``, no statistics)."""
    p = variables["params"]
    h = p["embed"]["embedding"][x]
    for l in range(LAYERS):
        mixer = _layer(l)
        h = jax.checkpoint(lambda h, pl, mixer=mixer: mixer(h, pl, quant))(
            h, p[f"layer_{l}"])
    h = _rms_norm(h, p["final_norm"]["scale"])
    return _product("btc,cv->btv", h, p["lm_head"]["kernel"], quant), {}


def init(key):
    """Seeded weights in the layout of the program's ``decoder``
    variables: matrices (the convolution's kernel, the two residual
    output matrices too) normal with variance 1 / fan-in, norm scales 1
    + 0.1 normal, the convolution's bias 0.1 normal, embedding rows unit
    normal; ``dt_bias`` the inverse softplus of a step log-uniform in
    ``[time_step_min, time_step_max]`` floored at ``time_step_floor``,
    ``A_log`` the log of uniform [1, 16], ``D`` ones."""
    keys = iter(jax.random.split(key, 16 * LAYERS + 8))
    normal = lambda *shape, std: std * jax.random.normal(next(keys), shape)
    norm = lambda n=HIDDEN: {"scale": 1.0 + normal(n, std=0.1)}
    dense = lambda a, b: {"kernel": normal(a, b, std=a ** -0.5)}
    params = {"embed": {"embedding": normal(VOCAB, HIDDEN, std=1.0)},
              "final_norm": norm(), "lm_head": dense(HIDDEN, VOCAB)}
    low, high = math.log(S["time_step_min"]), math.log(S["time_step_max"])
    for l in range(LAYERS):
        if _layer(l) is _mamba:
            step = jnp.maximum(jnp.exp(low + (high - low) * jax.random.uniform(
                next(keys), (SSM_HEADS,))), S["time_step_floor"])
            layer = {
                "ssm_norm": norm(),
                "in_proj": dense(HIDDEN, INNER + CHANNELS + SSM_HEADS),
                "conv_kernel": normal(KERNEL, CHANNELS, std=KERNEL ** -0.5),
                "conv_bias": normal(CHANNELS, std=0.1),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (SSM_HEADS,), minval=1.0, maxval=16.0)),
                "D": jnp.ones((SSM_HEADS,)),
                "gate_norm": 1.0 + normal(INNER, std=0.1),
                "out_proj": dense(INNER, HIDDEN),
            }
        elif _layer(l) is _attention:
            layer = {
                "attn_norm": norm(),
                "q_proj": dense(HIDDEN, HEADS * HEAD_DIM),
                "k_proj": dense(HIDDEN, KV * HEAD_DIM),
                "v_proj": dense(HIDDEN, KV * HEAD_DIM),
                "o_proj": dense(HEADS * HEAD_DIM, HIDDEN),
            }
        else:
            layer = {
                "mlp_norm": norm(),
                "router": normal(HIDDEN, EXPERTS, std=HIDDEN ** -0.5),
                "latent_in": normal(HIDDEN, LATENT, std=HIDDEN ** -0.5),
                "latent_out": normal(LATENT, HIDDEN, std=LATENT ** -0.5),
                "experts_w1": normal(HELD, LATENT, WIDTH, std=LATENT ** -0.5),
                "experts_w2": normal(HELD, WIDTH, LATENT, std=WIDTH ** -0.5),
                "shared_w1": normal(HIDDEN, SHARED, std=HIDDEN ** -0.5),
                "shared_w2": normal(SHARED, HIDDEN, std=SHARED ** -0.5),
            }
        params[f"layer_{l}"] = layer
    return {"params": params}


def token_macs():
    """Multiply-accumulates of one token's forward pass in the share, by
    part: the recurrence as the equations state it (a state update and a
    state read of ``P x N`` a head), attention over the ``(SEQ + 1) /
    2`` keys a causal query reads on average, the routed experts at the
    held experts' uniform share of the ``k`` a token."""
    mamba = sum(m == "state_space" for m in MIXERS)
    attention = sum(m == "full_attention" for m in MIXERS)
    sparse = sum(f == "sparse" for f in FEED_FORWARDS)
    width = HEADS * HEAD_DIM
    return {
        # in: z, xBC and dt; out: the heads' channels
        "ssm_proj": mamba * HIDDEN * (
            INNER + CHANNELS + SSM_HEADS + INNER),
        "ssm_scan": mamba * SSM_HEADS * 2 * SSM_DIM * STATE,
        "attn_proj": attention * HIDDEN * (2 * width + 2 * KV * HEAD_DIM),
        "attn_scores": attention * 2 * width * (SEQ + 1) / 2,
        "router": sparse * HIDDEN * EXPERTS,
        "latent": sparse * 2 * HIDDEN * LATENT,
        "experts": sparse * TOP_K * HELD / EXPERTS * 2 * LATENT * WIDTH,
        "shared": sparse * 2 * HIDDEN * SHARED,
        "head": float(HIDDEN * VOCAB),
    }


def step_flops(batch):
    """Matrix work the published arithmetic needs for one optimizer step
    of ``batch`` sequences of ``SEQ`` in the share: forward + backward
    (two products backward for one forward); two operations a
    multiply-accumulate; recomputation not counted."""
    return 2.0 * 3.0 * sum(token_macs().values()) * SEQ * batch
