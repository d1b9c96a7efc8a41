"""A decoder stack set by configuration (``create_model`` name
``decoder``): every size and every per-layer choice comes from
``ModelConfig.extra``, so a published ``config.json`` — cut to a chip's
share — is a configuration and not a new module.

Per layer ``l`` (pre-norm residual blocks, RMSNorm, no bias anywhere):

- attention: ``heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``;
  ``layer_types[l]`` is one of three kinds, all causal:
  ``full_attention``; ``sliding_attention`` (within ``sliding_window``
  keys); ``sparse_attention`` (query ``t`` reads the ``min(t + 1,
  topk)`` keys a learned index ranks highest — ``sparse_attention:
  {index_heads, index_head_dim, topk}``: ``index_heads`` index queries
  and one head weight each a token, ONE index key head, all of the
  normed layer input, rotated like the layer's own queries;
  :func:`fedml_tpu.ops.attention.index_scores`, ``select_top_k``,
  ``selected_attention``; the set is discrete, so the index
  projections get no gradient from the task's loss). Each kind has its
  own ``rope`` record (``rope_theta``, ``partial_rotary_factor``,
  ``rope_type`` ``default`` | ``yarn`` with ``factor``,
  ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
  ``attention_factor``; rotate-half pairing); with ``qk_norm`` every
  query and key head is RMS-normed (one learned scale over
  ``head_dim``) before it is rotated; with ``gating`` a per-head
  sigmoid gate of the normed input scales each head's output before
  the output projection;
- feed-forward: ``mlp_layer_types[l]`` is ``dense`` (a gated
  feed-forward of ``intermediate_size``) or ``sparse``
  (:func:`fedml_tpu.ops.moe.moe_layer`: a router over ``num_experts``,
  ``num_experts_per_tok`` a token, probabilities by ``router_scoring``
  — ``sigmoid``, the default, or ``softmax`` over all experts —
  renormalised over the chosen and times ``routed_scaling_factor``,
  the experts ``experts_held = [first, count]`` of width
  ``moe_intermediate_size`` held here, and a shared expert of
  ``shared_expert_intermediate_size``, none at 0).

Untied embedding and head over ``vocab_size`` rows. Each layer is
recomputed in the backward pass (``nn.remat``) but for what
:data:`fedml_tpu.ops.attention.KEPT` names: beside a layer's input, its
attention kernel's output and row log-sum-exp and, in a
sparse-attention layer, the selection are kept, so the forward
attention kernel, the index and the top-k run once a training step and
the projections, norms, rotary, gate and the whole feed-forward twice.
What the layers count (:func:`counter_names`: the sparse layers'
:data:`fedml_tpu.ops.moe.MOE_COUNTERS` and, in a stack with
sparse-attention layers, :data:`fedml_tpu.ops.attention.
ATTN_COUNTERS`) is summed over layers and sown into the ``counters``
collection, which :meth:`fedml_tpu.models.base.FedModel.
apply_train_counted` hands to the local update.

Projections are built through the ``dense_cls`` hook of
:mod:`fedml_tpu.models.transformer`, so ``peft/`` wraps them as it
wraps ``Block``'s.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.models.transformer import AttnFn, DenseFactory, _dense
from fedml_tpu.ops.attention import (
    ATTN_COUNTERS, KEPT, KEPT_SELECTION, causal_attention, index_scores,
    select_top_k,
)
from fedml_tpu.ops.moe import MOE_COUNTERS, SCORINGS, moe_layer

# any other layer type is full attention
SLIDING, SELECTED = "sliding_attention", "sparse_attention"
DENSE, SPARSE = "dense", "sparse"
SIGMOID = "sigmoid"  # the router's scoring where the configuration names none


def attention_counters(layer_types) -> tuple[str, ...]:
    """What a stack of these attention kinds counts beside its sparse
    layers' :data:`MOE_COUNTERS` (every layer of it, of any kind)."""
    return ATTN_COUNTERS if SELECTED in layer_types else ()


def counter_names(layer_types) -> tuple[str, ...]:
    """What a stack of these attention kinds counts a training step."""
    return MOE_COUNTERS + attention_counters(layer_types)


def rope_inverse_frequencies(rope: dict, head_dim: int):
    """-> (``inv_freq`` float64 ``[rot / 2]``, ``attention_factor``,
    ``rot``): the rotary frequencies of one layer kind over the first
    ``rot = partial_rotary_factor x head_dim`` dimensions. ``yarn``
    blends ``theta^(-2i/rot)`` with the same over ``factor`` by the
    linear ramp between the two correction dimensions (Peng et al.
    2023, as ``transformers`` computes it)."""
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0, rot
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rot * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    return inv, float(rope.get("attention_factor", 1.0)), rot


def rope_tables(rope: dict, head_dim: int, t: int):
    """``cos, sin`` float32 ``[T, rot]`` (the half-tables repeated, for
    rotate-half pairing), the attention factor folded in."""
    inv, factor, _ = rope_inverse_frequencies(rope, head_dim)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    return (np.float32(np.cos(angles) * factor),
            np.float32(np.sin(angles) * factor))


def apply_rope(x, cos, sin):
    """``x`` ``[B, T, H, D]``: rotate the first ``rot`` dimensions
    (rotate-half pairing), pass the rest through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], axis=-1)
    c = jnp.asarray(cos, x.dtype)[None, :, None, :]
    s = jnp.asarray(sin, x.dtype)[None, :, None, :]
    return jnp.concatenate([xr * c + turned * s, rest], axis=-1)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def _counted(routed, attended):
    """One layer's counts in :func:`counter_names`' order."""
    return (jnp.concatenate([routed, jnp.stack(attended)]) if attended
            else routed)


class DecoderLayer(nn.Module):
    cfg: Any  # the frozen configuration (a tuple of items)
    index: int
    attn_fn: AttnFn = causal_attention
    dense_cls: DenseFactory = None

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        l = self.index
        b, t, d = x.shape
        heads, kv, hd = c["heads_per_layer"][l], c["num_key_value_heads"], (
            c["head_dim"])
        kind = c["layer_types"][l]
        dense = lambda f, name: _dense(self.dense_cls, f, False, name)
        with jax.named_scope("fedml.model.attn"):
            h = RMSNorm(c["rms_norm_eps"], name="attn_norm")(x)
            q = dense(heads * hd, "q_proj")(h).reshape(b, t, heads, hd)
            k = dense(kv * hd, "k_proj")(h).reshape(b, t, kv, hd)
            v = dense(kv * hd, "v_proj")(h).reshape(b, t, kv, hd)
            if c["qk_norm"]:
                q = RMSNorm(c["rms_norm_eps"], name="q_norm")(q)
                k = RMSNorm(c["rms_norm_eps"], name="k_norm")(k)
            rope = dict(dict(c["rope"])[kind])
            cos, sin = rope_tables(rope, hd, t)
            # a stack with sparse-attention layers counts in every layer
            how, attended = {}, (jnp.float32(0),) * len(
                attention_counters(c["layer_types"]))
            if kind == SELECTED:
                how["selection"], attended = self.select(h, rope)
            a = self.attn_fn(
                apply_rope(q, cos, sin), apply_rope(k, cos, sin), v,
                causal=True,
                window=c["sliding_window"] if kind == SLIDING else None,
                **how)
            if c["gating"]:
                a = a * jax.nn.sigmoid(dense(heads, "g_proj")(h))[..., None]
            x = x + dense(d, "o_proj")(a.reshape(b, t, heads * hd))
        if c["mlp_layer_types"][l] == DENSE:
            with jax.named_scope("fedml.model.mlp"):
                h = RMSNorm(c["rms_norm_eps"], name="mlp_norm")(x)
                up = jax.nn.silu(
                    dense(c["intermediate_size"], "gate_proj")(h)
                ) * dense(c["intermediate_size"], "up_proj")(h)
                x = x + dense(d, "down_proj")(up)
            return x, _counted(
                jnp.zeros((len(MOE_COUNTERS),), jnp.float32), attended)
        with jax.named_scope("fedml.model.moe"):
            h = RMSNorm(c["rms_norm_eps"], name="mlp_norm")(x)
            first, count = c["experts_held"]
            f, fs = c["moe_intermediate_size"], (
                c["shared_expert_intermediate_size"])
            into = nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                batch_axis=(0,))
            flat = nn.initializers.lecun_normal()
            params = {
                "router": self.param(
                    "router", flat, (d, c["num_experts"])),
                "w1": self.param("experts_w1", into, (count, d, f)),
                "w3": self.param("experts_w3", into, (count, d, f)),
                "w2": self.param("experts_w2", into, (count, f, d)),
            }
            if fs:
                params["shared"] = (
                    self.param("shared_w1", flat, (d, fs)),
                    self.param("shared_w3", flat, (d, fs)),
                    self.param("shared_w2", flat, (fs, d)),
                )
            params = jax.tree.map(lambda p: p.astype(x.dtype), params)
            y, counters = moe_layer(
                params, h.reshape(b * t, d), (first, count),
                c["num_experts_per_tok"], c["routed_scaling_factor"],
                scoring=c["router_scoring"])
            return x + y.reshape(b, t, d), _counted(counters, attended)

    @nn.nowrap
    def select(self, h, rope: dict):
        """The keys each query of a sparse-attention layer reads, from
        the normed layer input ``h`` -> (``[B, T, T]`` bool,
        :data:`ATTN_COUNTERS`' two counts of this call). The index's
        output is a set: no gradient reaches its projections. The set
        is named :data:`KEPT_SELECTION`: a rematerialised layer keeps
        it whole for its backward pass, ``B T^2`` bytes (67 MB a
        sequence of 8,192 tokens, 1.07 GB at 32,768), and neither
        scores nor ranks a second time."""
        sa = dict(dict(self.cfg)["sparse_attention"])
        b, t, _ = h.shape
        j, e = sa["index_heads"], sa["index_head_dim"]
        dense = lambda f, name: _dense(self.dense_cls, f, False, name)
        with jax.named_scope("fedml.model.attn.index"):
            qi = dense(j * e, "index_q_proj")(h).reshape(b, t, j, e)
            ki = dense(e, "index_k_proj")(h).reshape(b, t, 1, e)
            w = dense(j, "index_w_proj")(h)
            cos, sin = rope_tables({**rope, "partial_rotary_factor": 1.0},
                                   e, t)
            scores = index_scores(apply_rope(qi, cos, sin),
                                  apply_rope(ki, cos, sin)[:, :, 0], w)
        with jax.named_scope("fedml.model.attn.select"):
            selection = checkpoint_name(
                select_top_k(scores, sa["topk"]), KEPT_SELECTION)
            selected = jnp.sum(selection, dtype=jnp.float32)
        return selection, (selected, jnp.float32(b * t * (t + 1) // 2))


class DecoderLM(nn.Module):
    """Tokens ``[B, T]`` int32 -> logits ``[B, T, vocab_size]``."""

    cfg: Any  # tuple of (key, value) items: hashable, as flax wants
    vocab_size: int
    attn_fn: AttnFn = causal_attention
    dense_cls: DenseFactory = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = dict(self.cfg)
        with jax.named_scope("fedml.model.embed"):
            x = nn.Embed(self.vocab_size, c["hidden_size"],
                         name="embed")(tokens)
        layer = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
        names = counter_names(c["layer_types"])
        counters = jnp.zeros((len(names),), jnp.float32)
        for l in range(len(c["layer_types"])):
            x, counted = layer(self.cfg, l, self.attn_fn, self.dense_cls,
                               name=f"layer_{l}")(x)
            counters = counters + counted
        for name, value in zip(names, counters):
            self.sow("counters", name, value,
                     reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.zeros((), jnp.float32))
        with jax.named_scope("fedml.model.head"):
            x = RMSNorm(c["rms_norm_eps"], name="final_norm")(x)
            return _dense(self.dense_cls, self.vocab_size, False,
                          "lm_head")(x)


def _freeze(value):
    """Lists and dicts of ``ModelConfig.extra`` as hashable tuples."""
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def decoder_from_extra(extra: dict, num_classes: int) -> DecoderLM:
    """The module ``ModelConfig.extra`` describes (module docstring).
    ``vocab_size`` defaults to ``num_classes``."""
    need = ("hidden_size", "head_dim", "num_key_value_heads",
            "heads_per_layer", "layer_types", "mlp_layer_types", "rope",
            "intermediate_size")
    missing = [k for k in need if k not in extra]
    if missing:
        raise ValueError(f"decoder: model extra lacks {', '.join(missing)}")
    c = {
        "sliding_window": None, "gating": False, "qk_norm": False,
        "rms_norm_eps": 1e-6, "router_scoring": SIGMOID,
        "moe_intermediate_size": 0, "shared_expert_intermediate_size": 0,
        "num_experts": 0, "num_experts_per_tok": 0,
        "routed_scaling_factor": 1.0, "experts_held": (0, 0),
        **{k: v for k, v in extra.items() if k != "vocab_size"},
    }
    n = len(c["layer_types"])
    if not (len(c["heads_per_layer"]) == len(c["mlp_layer_types"]) == n):
        raise ValueError(
            "decoder: heads_per_layer, layer_types and mlp_layer_types "
            "must have one entry a layer")
    first, count = c["experts_held"]
    if SPARSE in c["mlp_layer_types"] and not (
            0 <= first and count >= 1
            and first + count <= c["num_experts"]
            and c["num_experts_per_tok"] >= 1):
        raise ValueError(
            f"decoder: experts_held {c['experts_held']} does not lie in "
            f"the router's {c['num_experts']} experts")
    if c["router_scoring"] not in SCORINGS:
        raise ValueError(
            f"decoder: unknown router_scoring {c['router_scoring']!r}; "
            f"known: {sorted(SCORINGS)}")
    if SELECTED in c["layer_types"]:
        sa = c.get("sparse_attention") or {}
        lacks = [k for k in ("index_heads", "index_head_dim", "topk")
                 if not isinstance(sa.get(k), int)]
        if lacks:
            raise ValueError(
                f"decoder: sparse_attention lacks {', '.join(lacks)}")
        if min(sa["index_heads"], sa["index_head_dim"], sa["topk"]) < 1:
            raise ValueError(
                f"decoder: sparse_attention sizes must be at least 1: {sa}")
    return DecoderLM(_freeze(c), int(extra.get("vocab_size", num_classes)))
