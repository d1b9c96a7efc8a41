"""A decoder stack set by configuration (``create_model`` name
``decoder``): every size and every per-layer choice comes from
``ModelConfig.extra``, so a published ``config.json`` — cut to a chip's
share — is a configuration and not a new module.

Layer ``l`` is pre-norm residual blocks (RMSNorm, no bias but a
convolution's, where its record asks for one): first the sequence mixer
``layer_types[l]``
names, then the feed-forward ``mlp_layer_types[l]`` names. Either may
be ``none`` (not both), so a layer can be a mixer alone, ``x +
mixer(norm(x))``, and a published pattern of single-mixer layers is a
configuration; any other name is refused.

- attention: ``heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``;
  ``layer_types[l]`` is one of four attention kinds, all causal, the
  first three over grouped-query heads of that ONE size:
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
  ``attention_factor``; ``rope_pairing`` ``half``, the default —
  dimension ``i`` of the rotated part turns with ``i + rot / 2`` — or
  ``adjacent``, ``2i`` with ``2i + 1``; ``rope_type`` ``none``:
  no rotary and no other position term); with ``qk_norm`` every
  query and key head is RMS-normed (one learned scale over
  ``head_dim``) before it is rotated; with ``gating`` a per-head
  sigmoid gate of the normed input scales each head's output before
  the output projection;
- ``latent_attention`` (full causal attention whose queries, keys and
  values come through low-rank projections; sizes in the
  ``latent_attention`` record: ``q_lora_rank``, ``kv_lora_rank``,
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``): ``c_q =
  RMSNorm(h W_qa)``, ``q = c_q W_qb`` as ``heads_per_layer[l]`` heads
  of ``[nope | rope]`` — or, with ``q_lora_rank`` null, ``q = h W_q``
  straight from the normed input, with no ``q_a_proj`` / ``q_a_norm``
  leaves; ``[c_kv | k_r] = h W_kva``, ``c_kv <-
  RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` as heads of ``nope +
  v_head_dim``; the rotary (the kind's ``rope`` record, over
  ``qk_rope_head_dim`` dimensions) turns the LAST ``rope`` dimensions
  of every query head and the ONE key head ``k_r``, which then stands
  beside every head's own ``k_nope``: keys of ``nope + rope`` beside
  values of ``v_head_dim``, scores scaled by ``1 / sqrt(nope +
  rope)``, every head with keys of its own (the kernel runs them as
  key-value heads of group 1), so a chip's share is ``query_heads_held``
  alone (``W_kva`` and its norm stay whole); ``gating`` is ONE sigmoid
  scalar a head and token here too; ``head_dim`` is not read. The
  down-projections, their norms and the up-projections run under
  the scope ``fedml.model.attn.latent``, inside ``fedml.model.attn``;
- ``delta_attention`` (a gated delta rule with a decay a channel of the
  key, Kimi Delta Attention; sizes in the ``delta_attention`` record:
  ``head_dim`` = K = V, ``conv_kernel`` taps, ``gate_lower_bound``,
  ``chunk_size``; any other key refused): ``q~, k~, v~ = h W_q, h W_k,
  h W_v``, each through its own causal depthwise convolution
  (:func:`causal_depthwise_conv`, no bias) and SiLU; ``q = q' /
  ||q'||_2 / sqrt(K)``, ``k = k' / ||k'||_2`` a head
  (:func:`l2_normalised`); ``gamma = gate_lower_bound *
  sigmoid(exp(A_log_head) * (h W_f + dt_bias))``, a log-decay a channel
  in ``(gate_lower_bound, 0)``; ``beta = sigmoid(h W_b)`` a head; the
  recurrence ``S_t = (I - beta_t k_t k_t^T) Diag(exp(gamma_t)) S_{t-1}
  + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` in chunks
  (:func:`fedml_tpu.ops.delta.kda_chunked`); ``o <- RMSNorm_head(o) *
  sigmoid(h W_g)`` (one scale of ``head_dim`` for all heads, the gate a
  channel); out ``= o W_o``; no position term. A head has its own
  ``q``, ``k``, ``v``, decay, gate and state: a chip's share is
  ``query_heads_held`` of ``heads_per_layer[l]``. A fresh gate
  (:data:`DELTA_SLOPES`, :data:`DELTA_STEPS`) rests where a chunk keeps
  a good part of a channel and loses a good part. The whole mixer runs
  under the scope ``fedml.model.delta``, the recurrence (from the
  decays' running sums to ``o``) under ``fedml.model.delta.scan`` and
  the convolutions, norms and gates under ``fedml.model.delta.mix``
  inside it;
- ``state_space`` (Mamba-2; sizes in the ``state_space`` record:
  ``num_heads`` heads of ``head_dim``, ``n_groups`` groups of
  ``state_size``, ``conv_kernel``, ``chunk_size``, the ``time_step_*``
  of the weights' law): ``[z | xBC | dt] = h W_in``; ``xBC <-
  silu(causal depthwise convolution, with bias)``, split into ``x``
  (the heads), ``B`` and ``C`` (a group's, shared by its heads); ``dt
  <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the
  recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t`` in chunks (:func:`fedml_tpu.ops.ssm.ssd_chunked`);
  ``y <- RMSNorm(y * silu(z)) * scale`` over each group's channels;
  out ``= y W_out``;
- ``short_conv`` (a gated short convolution; sizes in the
  ``short_conv`` record: ``kernel`` taps, ``bias`` true or false):
  ``[B | C | u] = h W_in``, three chunks of the hidden width in this
  order from ONE projection; ``z = causal depthwise convolution of B *
  u`` (:func:`causal_depthwise_conv`: ``kernel`` taps a channel, zeros
  before the sequence, a bias a channel only where ``bias`` is true, NO
  activation); out ``= (C * z) W_out``. The channels are whole on every
  chip: a stack with such layers takes no head share. The whole mixer
  runs under the scope ``fedml.model.conv`` and the two gates and the
  taps, the part no matrix product does, under ``fedml.model.conv.mix``
  inside it;
- feed-forward: ``mlp_layer_types[l]`` is ``dense`` (a gated
  feed-forward of ``intermediate_size``) or ``sparse``
  (:func:`fedml_tpu.ops.moe.moe_layer`: a router over ``num_experts``,
  ``num_experts_per_tok`` a token, probabilities by ``router_scoring``
  — ``sigmoid``, the default, or ``softmax`` over all experts —
  renormalised over the chosen and times ``routed_scaling_factor``,
  (``router_renorm_epsilon``, 0 where absent, is added to the sum the
  chosen probabilities are divided by), the experts ``experts_held =
  [first, count]`` of width
  ``moe_intermediate_size`` held here, and a shared expert of
  ``shared_expert_intermediate_size``, none at 0). With
  ``router_score_bias`` every sparse layer has a ``router_bias`` leaf
  (``[num_experts]`` float32, drawn :data:`ROUTER_BIAS_STD` normal): a
  score-correction bias that the CHOICE reads — the experts of largest
  probability plus bias — and the weights do not (the chosen experts'
  unbiased probabilities renormalised), so no gradient reaches it and
  a round leaves it as it came. With ``router_groups = [n_group,
  topk_group]`` the choice is limited to a token's ``topk_group`` open
  groups of ``num_experts / n_group`` consecutive experts — a group
  scores the sum of its two largest probabilities plus bias
  (:func:`fedml_tpu.ops.moe.open_groups`); one group, the default, is
  no limit — and the groups' choice, a set too, passes no gradient.
  ``mlp_activation``
  (:data:`fedml_tpu.ops.moe.ACTIVATIONS`, the shared expert's too):
  ``silu_gated`` (the default: three matrices an expert, ``(silu(x W1)
  * (x W3)) W2``, as the dense layer is), ``relu_gated`` (three,
  ``(relu(x W1) * (x W3)) W2``) or ``relu2`` (two, ``relu(x W1)^2
  W2``); the last two in a stack with no dense layer. With
  ``moe_latent_size`` the experts work at that width between two
  projections all of them share; the router and the shared expert read
  the full hidden width. ``router_input`` says what a sparse layer's
  router reads: ``feed_forward_input`` (the default: the layer's own
  ``RMSNorm(x + attention)``, which its experts read) or
  ``attention_input`` (the attention's normed input, so the router
  stands BEFORE attention: the experts are chosen and weighted from
  ``RMSNorm_in(x)`` and fed ``RMSNorm_post(x + attention)``, a
  gradient reaches ``x`` by both, and the router's logits, top-k and
  weights run under the scope ``fedml.model.moe.router``, apart from
  ``fedml.model.moe.route``; every sparse layer of such a stack has an
  attention mixer).

**A chip's share of a layer** (each ``[first, count]`` of the published
count beside it; the whole where absent): ``experts_held`` of
``num_experts`` (the router stays whole); ``state_space.heads_held`` of
its ``num_heads``, whole groups only, with those groups' ``B`` / ``C``,
convolution channels and gated-norm groups (the norm's statistics are a
group's own); ``query_heads_held`` of ``heads_per_layer[l]`` over
``key_value_heads_held`` of ``num_key_value_heads`` (the held query
heads read held key-value heads, evenly) — stated ONCE and read by the
delta-rule and the latent layers alike, whose heads have keys of their
own, so that a stack with either has no ``key_value_heads_held``;
``shared_expert_columns_held`` of ``shared_expert_intermediate_size``.
What the absent heads, columns and experts would add to a layer's
output is left out, and nothing stands in for their exchange.

Embedding and head over ``vocab_size`` rows: two tables (``embed``
and ``lm_head``) or, with ``tie_word_embeddings`` true, ONE — ``logits
= x embedding^T`` in the compute dtype, no ``lm_head`` leaf, and the
table's gradient JAX's sum of its two roads (the lookup's own backward
rule and the head's product); ``peft/`` is refused beside it
(:func:`fedml_tpu.peft.lora.apply_lora`). Each layer is
recomputed in the backward pass (``nn.remat``) but for what
:data:`KEPT` names. Beside a layer's input it keeps its attention
kernel's output and row log-sum-exp; in a sparse-attention layer the
selection; in a state-space layer the states entering each chunk and
the scan's result; in a delta-rule layer the states entering the
chunks (every chunk's on the TPU, where the chunk kernels run; every
other one's in the plain form) and the result
(:data:`fedml_tpu.ops.delta.KEPT`); in a
short-convolution layer
nothing more (the mixer runs twice a step); and of a sparse
feed-forward the routing (the
router's logits, the chosen experts and their weights, the order of
the assignments, where a token's slots find their rows, the rows a
held expert) and what the held experts' backward rule reads (the row
buffer's rows, those rows through each matrix that leads in, and their
result; :data:`fedml_tpu.ops.moe.KEPT`). So these run ONCE a training
step: the forward attention kernel, the index and its top-k; of the
chunked scan the mix, the chunks' own states and the recurrence between
them; the delta rule's recurrence between chunks (on the TPU its whole
forward kernel); the router's product,
the groups' scores, its scoring's ranking (``ops.moe.largest``), the
sorts and the count, the row gather, the grouped products forward and
the combine. And these twice: the norms, the attention's projections,
rotary, gate and output projection, the convolution, the dense
feed-forward, the shared expert and the latent projections; of a
delta-rule layer its seven projections, taps, norms and gates and,
inside a chunk, the two decayed Gram products, the solve and the reads
of the entering state (which the backward pass differentiates; on the
TPU the backward kernel makes them again in fast memory). The
embedding, outside the layers, gathers its rows once forward and forms
its table's gradient once backward by a rule of its own
(:class:`DecoderLM`).
What the layers count (:func:`counter_names`: the sparse layers'
:data:`fedml_tpu.ops.moe.MOE_COUNTERS` — the assignments that landed
on held experts, those made, the fullest held expert's, those of calls
that went through the bounded row buffer, the rows the combine
read back, one a SLOT of a token: a token has ``min(num_experts_per_tok,
experts held)`` slots, its ways where those are the fewer (each finds
its row through the order's ``inverse``, a second sort) and the held
experts where THOSE are (no ``inverse`` is computed: :func:`fedml_tpu.
ops.moe.moe_layer`), the rows a training step's four gathers into
and out of the row buffer move, the held rows whose grouped
products ran in the row-tiled kernels, and the tokens for which a group
that holds a held expert was open — and, in a stack with
sparse-attention layers, :data:`fedml_tpu.ops.attention.
ATTN_COUNTERS`; in one with delta-rule layers, :data:`fedml_tpu.ops.
delta.DELTA_COUNTERS`: a chunk of a head through such a layer, and those
of them the TPU's chunk kernels ran) is summed over layers and sown into
the ``counters`` collection, which :meth:`fedml_tpu.models.base.FedModel.
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
    ATTN_COUNTERS, KEPT as ATTENTION_KEPT, KEPT_SELECTION, causal_attention,
    index_scores, select_top_k,
)
from fedml_tpu.ops.delta import (
    DELTA_COUNTERS, KEPT as DELTA_KEPT, chunk_counts, kda_chunked,
)
from fedml_tpu.ops.embedding import embedding_lookup
from fedml_tpu.ops.moe import (
    ACTIVATIONS, KEPT as MOE_KEPT, MOE_COUNTERS, ONE_GROUP, SCORINGS,
    SILU_GATED, leading, moe_layer,
)
from fedml_tpu.ops.ssm import KEPT as SCAN_KEPT, ssd_chunked

NONE = "none"  # a layer without this half; a rope record's ``rope_type``
FULL, SLIDING, SELECTED, LATENT = (
    "full_attention", "sliding_attention", "sparse_attention",
    "latent_attention")
STATE_SPACE, SHORT_CONV, DELTA = "state_space", "short_conv", "delta_attention"
MIXERS = (FULL, SLIDING, SELECTED, LATENT, STATE_SPACE, SHORT_CONV, DELTA,
          NONE)
DENSE, SPARSE = "dense", "sparse"
FEED_FORWARDS = (DENSE, SPARSE, NONE)
SIGMOID = "sigmoid"  # the router's scoring where the configuration names none
# ``router_input``: what a sparse layer's router reads
FEED_FORWARD_INPUT, ATTENTION_INPUT = "feed_forward_input", "attention_input"
ROUTER_INPUTS = (FEED_FORWARD_INPUT, ATTENTION_INPUT)
#: the law of a fresh ``router_bias``: normal of this deviation, beside
#: probabilities in (0, 1) — it reorders experts whose probabilities
#: lie this close and leaves the rest of a token's choice alone
ROUTER_BIAS_STD = 0.01
ATTENTIONS = (FULL, SLIDING, SELECTED, LATENT)
# a rope record's ``rope_pairing``: which dimensions turn together
HALF, ADJACENT = "half", "adjacent"
PAIRINGS = (HALF, ADJACENT)
# the sizes a ``latent_attention`` record gives (``q_lora_rank`` an
# integer, or null: queries straight from the normed input)
LATENT_SIZES = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")
# what a ``short_conv`` record gives, and each key's type
SHORT_CONV_KEYS = {"kernel": int, "bias": bool}
# what a ``delta_attention`` record gives, and each key's type
DELTA_KEYS = {"head_dim": int, "conv_kernel": int,
              "gate_lower_bound": (int, float), "chunk_size": int}
#: the law of a fresh delta-rule gate: ``A_log`` the log of a slope
#: drawn uniform in this range, ``dt_bias`` the gate's value at rest as
#: a decay a token drawn log-uniform in :data:`DELTA_STEPS` (so that a
#: chunk of 64 tokens keeps between e^-6.4 and e^-0.064 of a channel,
#: the median channel a half: the decay does work at a seed)
DELTA_SLOPES, DELTA_STEPS = (0.5, 1.5), (1e-3, 1e-1)
# the shares of attention heads a chip may hold
HEAD_SHARES = ("query_heads_held", "key_value_heads_held")

#: what a rematerialised layer keeps (``checkpoint_name``s)
KEPT = ATTENTION_KEPT + SCAN_KEPT + DELTA_KEPT + MOE_KEPT


def attention_counters(layer_types) -> tuple[str, ...]:
    """What a stack of these attention kinds counts beside its sparse
    layers' :data:`MOE_COUNTERS` (every layer of it, of any kind): the
    sparse-attention layers' two, then the delta-rule layers' two."""
    return (ATTN_COUNTERS if SELECTED in layer_types else ()) + (
        DELTA_COUNTERS if DELTA in layer_types else ())


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


def rope_pairing(rope: dict) -> str:
    """Which dimensions of a rotated part turn together, by the rope
    record's ``rope_pairing``: ``half`` (the default: ``i`` with ``i +
    rot / 2``, rotate-half) or ``adjacent`` (``2i`` with ``2i + 1``)."""
    pairing = rope.get("rope_pairing", HALF)
    if pairing not in PAIRINGS:
        raise ValueError(
            f"decoder: unknown rope_pairing {pairing!r}; known: "
            f"{', '.join(PAIRINGS)}")
    return pairing


def rope_tables(rope: dict, head_dim: int, t: int):
    """``cos, sin`` float32 ``[T, rot]``, the attention factor folded
    in: pair ``i``'s angle at both of its dimensions (the half-tables
    side by side under ``half`` pairing, each entry twice under
    ``adjacent``)."""
    inv, factor, _ = rope_inverse_frequencies(rope, head_dim)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = (np.repeat(angles, 2, axis=-1) if rope_pairing(rope) == ADJACENT
              else np.concatenate([angles, angles], axis=-1))
    return (np.float32(np.cos(angles) * factor),
            np.float32(np.sin(angles) * factor))


def apply_rope(x, cos, sin, pairing: str = HALF):
    """``x`` ``[B, T, H, D]``: rotate the first ``rot`` dimensions in
    pairs by ``pairing`` (:func:`rope_pairing`; the tables are
    :func:`rope_tables`' for the same), pass the rest through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    if pairing == ADJACENT:
        pairs = xr.reshape(*xr.shape[:-1], half, 2)
        turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(
            xr.shape)
    else:
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


def _steps(key, shape, low: float, high: float):
    """Steps drawn log-uniform in ``[low, high]``."""
    return jnp.exp(jax.random.uniform(key, shape) * (
        math.log(high) - math.log(low)) + math.log(low))


def _inverse_softplus_steps(low: float, high: float, floor: float):
    """``dt_bias``: the inverse softplus of a step drawn log-uniform in
    ``[low, high]`` and floored."""
    def init(key, shape, dtype=jnp.float32):
        step = jnp.maximum(_steps(key, shape, low, high), floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


def _log_uniform(low: float, high: float):
    """``A_log``: the log of a rate drawn uniform in ``[low, high]``."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(
            key, shape, minval=low, maxval=high)).astype(dtype)
    return init


def _inverse_sigmoid_steps(low: float, high: float, bound: float):
    """``dt_bias`` of a delta-rule gate ``bound * sigmoid(.)``: where the
    gate rests at a decay a token drawn log-uniform in ``[low, high]``
    (``bound`` negative, ``high < -bound``)."""
    def init(key, shape, dtype=jnp.float32):
        part = _steps(key, shape, low, high) / -bound
        return (jnp.log(part) - jnp.log1p(-part)).astype(dtype)
    return init


def l2_normalised(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def causal_depthwise_conv(x, kernel, bias=None):
    """``x`` ``[B, T, C]``, ``kernel`` ``[K, C]``: channel ``c`` at ``t``
    is ``sum_i kernel[i, c] x[t - (K - 1) + i, c]``, zeros before the
    sequence, plus ``bias[c]`` where a ``bias`` ``[C]`` is given (a
    state-space layer's; a short-convolution layer's where its record
    asks for one)."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = sum(kernel[i] * padded[:, i:i + t] for i in range(k))
    return taps if bias is None else bias + taps


def _held(c: dict, key: str, whole: int) -> int:
    """How many of ``whole`` this chip holds under share key ``key``."""
    return c[key][1] if c.get(key) else whole


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
        kind = c["layer_types"][l]
        # a stack with sparse-attention or delta-rule layers counts in
        # every layer
        attended = (jnp.float32(0),) * len(
            attention_counters(c["layer_types"]))
        read = None  # what the attention read: its normed input
        if kind == STATE_SPACE:
            x = x + self.state_space(x)
        elif kind == SHORT_CONV:
            x = x + self.short_conv(x)
        elif kind == DELTA:
            mixed, chunks = self.delta_attention(x)
            x, attended = x + mixed, attended[:-len(chunks)] + chunks
        elif kind != NONE:
            x, attended, read = self.attention(x, kind, attended)
        if c["mlp_layer_types"][l] == DENSE:
            with jax.named_scope("fedml.model.mlp"):
                h = RMSNorm(c["rms_norm_eps"], name="mlp_norm")(x)
                up = jax.nn.silu(
                    self.dense(c["intermediate_size"], "gate_proj")(h)
                ) * self.dense(c["intermediate_size"], "up_proj")(h)
                x = x + self.dense(d, "down_proj")(up)
        if c["mlp_layer_types"][l] != SPARSE:
            return x, _counted(
                jnp.zeros((len(MOE_COUNTERS),), jnp.float32), attended)
        with jax.named_scope("fedml.model.moe"):
            h = RMSNorm(c["rms_norm_eps"], name="mlp_norm")(x)
            first, count = c["experts_held"]
            activation = c["mlp_activation"]
            latent = c["moe_latent_size"]
            w, f = latent or d, c["moe_intermediate_size"]
            fs = _held(c, "shared_expert_columns_held",
                       c["shared_expert_intermediate_size"])
            into = nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                batch_axis=(0,))
            flat = nn.initializers.lecun_normal()
            into_middle = leading(activation)
            params = {
                "router": self.param(
                    "router", flat, (d, c["num_experts"])),
                **{m: self.param("experts_" + m, into, (count, w, f))
                   for m in into_middle},
                "w2": self.param("experts_w2", into, (count, f, w)),
            }
            if fs:
                params["shared"] = (
                    *(self.param("shared_" + m, flat, (d, fs))
                      for m in into_middle),
                    self.param("shared_w2", flat, (fs, d)),
                )
            if latent:
                params["latent"] = (
                    self.param("latent_in", flat, (d, latent)),
                    self.param("latent_out", flat, (latent, d)))
            params = jax.tree.map(lambda p: p.astype(x.dtype), params)
            if c["router_score_bias"]:  # float32; the choice alone reads it
                params["router_bias"] = self.param(
                    "router_bias", nn.initializers.normal(ROUTER_BIAS_STD),
                    (c["num_experts"],))
            y, counters = moe_layer(
                params, h.reshape(b * t, d), (first, count),
                c["num_experts_per_tok"], c["routed_scaling_factor"],
                scoring=c["router_scoring"], activation=activation,
                router_input=read.reshape(b * t, d) if (
                    c["router_input"] == ATTENTION_INPUT) else None,
                renorm_epsilon=c["router_renorm_epsilon"],
                groups=tuple(c["router_groups"]))
            return x + y.reshape(b, t, d), _counted(counters, attended)

    @nn.nowrap
    def dense(self, features: int, name: str):
        """A projection of this layer, through the ``dense_cls`` hook."""
        return _dense(self.dense_cls, features, False, name)

    @nn.nowrap
    def attention(self, x, kind: str, attended):
        """``x + attention(norm(x))`` of kind ``kind`` -> (``x``, the
        layer's :data:`ATTN_COUNTERS` counts or ``attended`` as it
        came, ``norm(x)``: what a router before attention reads)."""
        c = dict(self.cfg)
        b, t, d = x.shape
        rope = dict(dict(c["rope"])[kind])
        pairing = rope_pairing(rope)
        turns = rope.get("rope_type") != NONE
        with jax.named_scope("fedml.model.attn"):
            h = RMSNorm(c["rms_norm_eps"], name="attn_norm")(x)
            how = {}
            if kind == LATENT:
                heads = _held(
                    c, "query_heads_held", c["heads_per_layer"][self.index])
                q, k, v, k_rope = self.latent_heads(h, heads)
                if turns:
                    nope = k.shape[-1]
                    cos, sin = rope_tables(rope, k_rope.shape[-1], t)
                    q = jnp.concatenate([q[..., :nope], apply_rope(
                        q[..., nope:], cos, sin, pairing)], -1)
                    k_rope = apply_rope(k_rope, cos, sin, pairing)
                # the ONE rotary key head stands beside every head's own
                k = jnp.concatenate([k, jnp.broadcast_to(
                    k_rope, (b, t, heads, k_rope.shape[-1]))], -1)
            else:
                hd = c["head_dim"]
                heads = _held(
                    c, "query_heads_held", c["heads_per_layer"][self.index])
                kv = _held(c, "key_value_heads_held", c["num_key_value_heads"])
                q = self.dense(heads * hd, "q_proj")(h).reshape(
                    b, t, heads, hd)
                k = self.dense(kv * hd, "k_proj")(h).reshape(b, t, kv, hd)
                v = self.dense(kv * hd, "v_proj")(h).reshape(b, t, kv, hd)
                if c["qk_norm"]:
                    q = RMSNorm(c["rms_norm_eps"], name="q_norm")(q)
                    k = RMSNorm(c["rms_norm_eps"], name="k_norm")(k)
                if kind == SELECTED:
                    how["selection"], keys = self.select(h, rope)
                    attended = keys + attended[len(keys):]
                if turns:
                    cos, sin = rope_tables(rope, hd, t)
                    q = apply_rope(q, cos, sin, pairing)
                    k = apply_rope(k, cos, sin, pairing)
            a = self.attn_fn(
                q, k, v, causal=True,
                window=c["sliding_window"] if kind == SLIDING else None,
                **how)
            if c["gating"]:
                gate = jax.nn.sigmoid(self.dense(heads, "g_proj")(h))
                a = a * gate[..., None]
            a = self.dense(d, "o_proj")(a.reshape(b, t, -1))
            return x + a, attended, h

    @nn.nowrap
    def latent_heads(self, h, heads: int):
        """A latent-attention layer's heads from its normed input ``h``
        (module docstring) -> (``q`` ``[B, T, heads, nope + rope]``, the
        keys' own part ``[B, T, heads, nope]``, ``v`` ``[B, T, heads,
        v_head_dim]``, the one rotary key head ``[B, T, 1, rope]``),
        nothing rotated yet: the two down-projections, their norms and
        the two up-projections (``q_lora_rank`` null: the queries' ONE
        projection), under a scope of their own."""
        c = dict(self.cfg)
        la = dict(c["latent_attention"])
        b, t, _ = h.shape
        nope, rope, dv = (la["qk_nope_head_dim"], la["qk_rope_head_dim"],
                          la["v_head_dim"])
        with jax.named_scope("fedml.model.attn.latent"):
            if la["q_lora_rank"] is None:
                q = self.dense(heads * (nope + rope), "q_proj")(h)
            else:
                cq = RMSNorm(c["rms_norm_eps"], name="q_a_norm")(
                    self.dense(la["q_lora_rank"], "q_a_proj")(h))
                q = self.dense(heads * (nope + rope), "q_b_proj")(cq)
            ckv, k_rope = jnp.split(self.dense(
                la["kv_lora_rank"] + rope, "kv_a_proj")(h),
                [la["kv_lora_rank"]], -1)
            ckv = RMSNorm(c["rms_norm_eps"], name="kv_a_norm")(ckv)
            k, v = jnp.split(self.dense(
                heads * (nope + dv), "kv_b_proj")(ckv).reshape(
                    b, t, heads, nope + dv), [nope], -1)
        return (q.reshape(b, t, heads, nope + rope), k, v,
                k_rope.reshape(b, t, 1, rope))

    @nn.nowrap
    def state_space(self, x):
        """``mixer(norm(x))`` of a Mamba-2 layer (module docstring) over
        the heads and groups held here."""
        c = dict(self.cfg)
        s = dict(c["state_space"])
        b, t, d = x.shape
        p, n = s["head_dim"], s["state_size"]
        heads = _held(s, "heads_held", s["num_heads"])
        groups = heads // (s["num_heads"] // s["n_groups"])
        inner, bc = heads * p, groups * n
        own = lambda name, init, *shape: self.param(name, init, shape)
        with jax.named_scope("fedml.model.ssm"):
            h = RMSNorm(c["rms_norm_eps"], name="ssm_norm")(x)
            z, xbc, dt = jnp.split(
                self.dense(2 * inner + 2 * bc + heads, "in_proj")(h),
                [inner, 2 * inner + 2 * bc], -1)
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc,
                own("conv_kernel", nn.initializers.lecun_normal(),
                    s["conv_kernel"], inner + 2 * bc).astype(x.dtype),
                own("conv_bias", nn.initializers.zeros,
                    inner + 2 * bc).astype(x.dtype)))
            xs, bm, cm = jnp.split(xbc, [inner, inner + bc], -1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + own(
                "dt_bias", _inverse_softplus_steps(
                    s["time_step_min"], s["time_step_max"],
                    s["time_step_floor"]), heads).astype(jnp.float32))
            rate = -jnp.exp(own("A_log", _log_uniform(1.0, 16.0),
                                heads).astype(jnp.float32))
            y = ssd_chunked(
                xs.reshape(b, t, heads, p), dt, rate,
                bm.reshape(b, t, groups, n), cm.reshape(b, t, groups, n),
                own("D", nn.initializers.ones, heads), s["chunk_size"])
            y = y.reshape(b, t, inner).astype(jnp.float32) * jax.nn.silu(
                z.astype(jnp.float32))
            y = y.reshape(b, t, groups, inner // groups)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, -1, keepdims=True) + c["rms_norm_eps"])
            y = y.reshape(b, t, inner) * own(
                "gate_norm", nn.initializers.ones, inner)
            return self.dense(d, "out_proj")(y.astype(x.dtype))

    @nn.nowrap
    def short_conv(self, x):
        """``mixer(norm(x))`` of a gated short-convolution layer (module
        docstring): every channel, whole."""
        c = dict(self.cfg)
        s = dict(c["short_conv"])
        d = x.shape[-1]
        with jax.named_scope("fedml.model.conv"):
            h = RMSNorm(c["rms_norm_eps"], name="conv_norm")(x)
            bm, cm, u = jnp.split(self.dense(3 * d, "in_proj")(h), 3, -1)
            kernel = self.param(
                "conv_kernel", nn.initializers.lecun_normal(),
                (s["kernel"], d)).astype(x.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros, (d,)
                              ).astype(x.dtype) if s["bias"] else None
            with jax.named_scope("fedml.model.conv.mix"):
                y = cm * causal_depthwise_conv(bm * u, kernel, bias)
            return self.dense(d, "out_proj")(y)

    @nn.nowrap
    def delta_attention(self, x):
        """``mixer(norm(x))`` of a gated delta-rule layer (module
        docstring) over the heads held here -> (it,
        :data:`DELTA_COUNTERS`' two counts of this call)."""
        c = dict(self.cfg)
        s = dict(c["delta_attention"])
        b, t, d = x.shape
        hd, bound = s["head_dim"], float(s["gate_lower_bound"])
        heads = _held(c, "query_heads_held", c["heads_per_layer"][self.index])
        inner = heads * hd
        own = lambda name, init, *shape: self.param(name, init, shape)
        by_head = lambda v: v.reshape(b, t, heads, hd)
        with jax.named_scope("fedml.model.delta"):
            h = RMSNorm(c["rms_norm_eps"], name="delta_norm")(x)
            into = [self.dense(inner, m + "_proj")(h) for m in "qkv"]
            decay = self.dense(inner, "f_proj")(h)
            write = self.dense(heads, "b_proj")(h)
            gate = self.dense(inner, "g_proj")(h)
            with jax.named_scope("fedml.model.delta.mix"):
                q, k, v = (by_head(jax.nn.silu(causal_depthwise_conv(
                    y, own(m + "_conv", nn.initializers.lecun_normal(),
                           s["conv_kernel"], inner).astype(x.dtype))))
                           for m, y in zip("qkv", into))
                q = l2_normalised(q) * hd ** -0.5
                k = l2_normalised(k)
                slope = jnp.exp(own("A_log", _log_uniform(*DELTA_SLOPES),
                                    heads).astype(jnp.float32))
                gamma = bound * jax.nn.sigmoid(slope[:, None] * by_head(
                    decay.astype(jnp.float32) + own(
                        "dt_bias",
                        _inverse_sigmoid_steps(*DELTA_STEPS, bound),
                        inner).astype(jnp.float32)))
                beta = jax.nn.sigmoid(write.astype(jnp.float32))
            o = kda_chunked(q, k, v, gamma, beta, s["chunk_size"])
            counts = chunk_counts(q, k, v, s["chunk_size"])
            with jax.named_scope("fedml.model.delta.mix"):
                o = RMSNorm(c["rms_norm_eps"], name="o_norm")(o)
                o = o * by_head(jax.nn.sigmoid(gate))
            return self.dense(d, "o_proj")(o.reshape(b, t, inner)), counts

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
        with jax.named_scope("fedml.model.attn.index"):
            qi = self.dense(j * e, "index_q_proj")(h).reshape(b, t, j, e)
            ki = self.dense(e, "index_k_proj")(h).reshape(b, t, 1, e)
            w = self.dense(j, "index_w_proj")(h)
            cos, sin = rope_tables({**rope, "partial_rotary_factor": 1.0},
                                   e, t)
            pairing = rope_pairing(rope)
            scores = index_scores(
                apply_rope(qi, cos, sin, pairing),
                apply_rope(ki, cos, sin, pairing)[:, :, 0], w)
        with jax.named_scope("fedml.model.attn.select"):
            selection = checkpoint_name(
                select_top_k(scores, sa["topk"]), KEPT_SELECTION)
            selected = jnp.sum(selection, dtype=jnp.float32)
        return selection, (selected, jnp.float32(b * t * (t + 1) // 2))


class Embedding(nn.Embed):
    """``flax.linen.Embed`` — its parameter ``embedding``, its
    initialiser, its row gather — under
    :func:`fedml_tpu.ops.embedding.embedding_lookup`'s backward rule."""

    def __call__(self, inputs):
        (embedding,) = self.promote_dtype(
            self.embedding, dtype=self.dtype, inexact=False)
        return embedding_lookup(embedding, inputs)


class DecoderLM(nn.Module):
    """Tokens ``[B, T]`` int32 -> logits ``[B, T, vocab_size]``.

    With ``tie_word_embeddings`` the head is ``x embedding^T`` over the
    table the tokens were looked up in (``flax.linen.Embed.attend``: the
    table in the step's compute dtype), under ``fedml.model.head``, and
    the table's gradient is JAX's sum of that product's and the rule's
    below.

    Under the scope ``fedml.model.embed`` run, forward, the gather of a
    row of ``embed/embedding`` a token (``jnp.take``, what
    ``flax.linen.Embed`` makes, to the bit) and, backward, that table's
    gradient by :func:`fedml_tpu.ops.embedding.embedding_lookup`'s own
    rule: the step's ``B T`` ids sorted, the cotangent rows of each
    DISTINCT id summed in float32 by one-hot products over blocks of
    the sorted tokens, and every table row written once by a gather.
    It is NOT the scatter-add of a row a token that ``jnp.take``
    transposes to: text repeats its ids, the TPU compiler's loop for a
    scatter whose indices repeat walks the table at 0.4 us a row where
    the table is 2,560 wide (15 ms a step into 37,984 rows, for 84 MB
    of traffic), and the rule costs a tenth of that there and less
    than the scatter at every decoder cell's table and bfloat16 rows
    (``PERF.md`` section 6, PR 43)."""

    cfg: Any  # tuple of (key, value) items: hashable, as flax wants
    vocab_size: int
    attn_fn: AttnFn = causal_attention
    dense_cls: DenseFactory = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = dict(self.cfg)
        embed = Embedding(self.vocab_size, c["hidden_size"], name="embed")
        with jax.named_scope("fedml.model.embed"):
            x = embed(tokens)
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
            if c["tie_word_embeddings"]:  # logits = x embedding^T
                return embed.attend(x)
            return _dense(self.dense_cls, self.vocab_size, False,
                          "lm_head")(x)


def _freeze(value):
    """Lists and dicts of ``ModelConfig.extra`` as hashable tuples."""
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def _share_in(held, whole: int) -> bool:
    """Is ``held`` a ``[first, count]`` inside ``whole``?"""
    return (isinstance(held, (list, tuple)) and len(held) == 2
            and 0 <= held[0] and held[1] >= 1 and held[0] + held[1] <= whole)


def _check_state_space(s: dict) -> None:
    sizes = ("num_heads", "head_dim", "n_groups", "state_size",
             "conv_kernel", "chunk_size")
    lacks = [k for k in sizes if not isinstance(s.get(k), int)] + [
        k for k in ("time_step_min", "time_step_max", "time_step_floor")
        if k not in s]
    if lacks:
        raise ValueError(f"decoder: state_space lacks {', '.join(lacks)}")
    if min(s[k] for k in sizes) < 1 or s["num_heads"] % s["n_groups"]:
        raise ValueError(
            "decoder: state_space sizes must be at least 1 and n_groups "
            f"divide num_heads: {s}")
    per = s["num_heads"] // s["n_groups"]
    held = s.get("heads_held") or [0, s["num_heads"]]
    if not _share_in(held, s["num_heads"]) or held[0] % per or held[1] % per:
        raise ValueError(
            f"decoder: state_space heads_held {held} is not whole groups "
            f"of {per} of the {s['num_heads']} heads")


def _check_attention_share(c: dict, attention_heads: list) -> None:
    """``query_heads_held`` over ``key_value_heads_held``: every held
    query head reads a held key-value head, each of those as many."""
    kv = c["num_key_value_heads"]
    kf, kc = c.get("key_value_heads_held") or [0, kv]
    if not _share_in([kf, kc], kv):
        raise ValueError(
            f"decoder: key_value_heads_held {[kf, kc]} does not lie in "
            f"the {kv} key-value heads")
    for heads in attention_heads:
        qf, qc = c.get("query_heads_held") or [0, heads]
        per = heads // kv  # query heads a key-value head, as published
        one = kc == 1 and kf * per <= qf and qf + qc <= (kf + 1) * per
        if not (_share_in([qf, qc], heads) and heads % kv == 0
                and (one or (qf, qc) == (kf * per, kc * per))):
            raise ValueError(
                f"decoder: query_heads_held {[qf, qc]} of {heads} heads do "
                f"not read key_value_heads_held {[kf, kc]} of {kv} evenly")


def _check_heads_with_keys_of_their_own(c: dict, kind: str) -> None:
    """A latent or a delta-rule head has keys and values of its own: a
    chip's share of such layers is ``query_heads_held`` alone."""
    if c.get("key_value_heads_held"):
        raise ValueError(
            f"decoder: a {kind} head has keys of its own, so a stack with "
            "such layers states its share as query_heads_held alone; it "
            "has no key_value_heads_held")
    held = c.get("query_heads_held")
    for l, mixer in enumerate(c["layer_types"]):
        if held and mixer == kind and not _share_in(
                held, c["heads_per_layer"][l]):
            raise ValueError(
                f"decoder: query_heads_held {list(held)} does not lie in "
                f"layer {l}'s {c['heads_per_layer'][l]} heads")


def _check_latent_attention(c: dict) -> None:
    la = c.get("latent_attention") or {}
    lacks = [k for k in LATENT_SIZES if not isinstance(la.get(k), int)
             and not (k == "q_lora_rank" and k in la and la[k] is None)]
    if lacks:
        raise ValueError(
            f"decoder: latent_attention lacks {', '.join(lacks)}")
    unknown = sorted(set(la) - set(LATENT_SIZES))
    if unknown or min(la[k] for k in LATENT_SIZES if la[k] is not None
                      ) < 1 or la["qk_rope_head_dim"] % 2:
        raise ValueError(
            "decoder: latent_attention sizes must be at least 1, the "
            f"rotary part even, and no other key given: {la}")
    if c["qk_norm"]:
        raise ValueError(
            "decoder: a stack with latent_attention layers norms its "
            "latents alone; it has no qk_norm")
    _check_heads_with_keys_of_their_own(c, LATENT)


def _check_delta_attention(c: dict) -> None:
    s = c.get("delta_attention") or {}
    lacks = [k for k, kind in DELTA_KEYS.items()
             if not isinstance(s.get(k), kind) or isinstance(s.get(k), bool)]
    if lacks:
        raise ValueError(f"decoder: delta_attention lacks {', '.join(lacks)}")
    unknown = sorted(set(s) - set(DELTA_KEYS))
    if unknown or s["gate_lower_bound"] >= -DELTA_STEPS[1] or min(
            s["head_dim"], s["conv_kernel"], s["chunk_size"]) < 1:
        raise ValueError(
            "decoder: delta_attention sizes must be at least 1, the gate's "
            f"lower bound under {-DELTA_STEPS[1]}, and no other key given "
            f"than {', '.join(DELTA_KEYS)}: {s}")
    _check_heads_with_keys_of_their_own(c, DELTA)


def _check_router_groups(c: dict) -> None:
    groups = c["router_groups"]
    pair = (isinstance(groups, (list, tuple)) and len(groups) == 2
            and all(isinstance(g, int) for g in groups)
            and 1 <= groups[1] <= groups[0])
    if pair and tuple(groups) == ONE_GROUP:
        return
    experts, ways = c["num_experts"], c["num_experts_per_tok"]
    if not pair or experts % groups[0] or experts // groups[0] < 2 or (
            ways > groups[1] * (experts // groups[0])):
        raise ValueError(
            f"decoder: router_groups {groups} is not [n_group, topk_group] "
            f"with whole groups of at least 2 of the {experts} experts and "
            f"room in the open ones for {ways} a token")


def _check_short_conv(c: dict) -> None:
    s = c.get("short_conv") or {}
    lacks = [k for k, kind in SHORT_CONV_KEYS.items()
             if not isinstance(s.get(k), kind)]
    if lacks:
        raise ValueError(f"decoder: short_conv lacks {', '.join(lacks)}")
    unknown = sorted(set(s) - set(SHORT_CONV_KEYS))
    if unknown or s["kernel"] < 1:
        raise ValueError(
            "decoder: short_conv has at least one tap and no other key "
            f"than {', '.join(SHORT_CONV_KEYS)}: {s}")
    shares = [k for k in HEAD_SHARES if c.get(k)]
    if shares:
        raise ValueError(
            "decoder: a stack with short_conv layers holds every channel, "
            f"so its attention holds every head; it has no "
            f"{', '.join(shares)}")


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
        "router_input": FEED_FORWARD_INPUT, "router_score_bias": False,
        "router_renorm_epsilon": 0.0, "tie_word_embeddings": False,
        "mlp_activation": SILU_GATED, "moe_latent_size": 0,
        "moe_intermediate_size": 0, "shared_expert_intermediate_size": 0,
        "num_experts": 0, "num_experts_per_tok": 0,
        "routed_scaling_factor": 1.0, "experts_held": (0, 0),
        "router_groups": ONE_GROUP,
        **{k: v for k, v in extra.items() if k != "vocab_size"},
    }
    n = len(c["layer_types"])
    if not (len(c["heads_per_layer"]) == len(c["mlp_layer_types"]) == n):
        raise ValueError(
            "decoder: heads_per_layer, layer_types and mlp_layer_types "
            "must have one entry a layer")
    for l, (mixer, forward) in enumerate(
            zip(c["layer_types"], c["mlp_layer_types"])):
        if mixer not in MIXERS or forward not in FEED_FORWARDS:
            raise ValueError(
                f"decoder: layer {l} is {mixer!r} + {forward!r}; known "
                f"layer_types: {', '.join(MIXERS)}; mlp_layer_types: "
                f"{', '.join(FEED_FORWARDS)}")
        if mixer == forward == NONE:
            raise ValueError(f"decoder: layer {l} is nothing at all")
    if c["mlp_activation"] not in ACTIVATIONS or (
            c["mlp_activation"] != SILU_GATED
            and DENSE in c["mlp_layer_types"]):
        others = sorted(set(ACTIVATIONS) - {SILU_GATED})
        raise ValueError(
            f"decoder: mlp_activation {c['mlp_activation']!r} is not "
            f"{SILU_GATED!r} or, in a stack with no dense layer, one of "
            f"{', '.join(map(repr, others))}")
    if c["router_input"] not in ROUTER_INPUTS:
        raise ValueError(
            f"decoder: unknown router_input {c['router_input']!r}; known: "
            f"{', '.join(ROUTER_INPUTS)}")
    before = [l for l, (mixer, forward) in enumerate(
        zip(c["layer_types"], c["mlp_layer_types"]))
        if forward == SPARSE and mixer not in ATTENTIONS]
    if c["router_input"] == ATTENTION_INPUT and before:
        raise ValueError(
            f"decoder: router_input {ATTENTION_INPUT!r} needs an attention "
            f"mixer in every sparse layer; layer {before[0]} has "
            f"{c['layer_types'][before[0]]!r}")
    first, count = c["experts_held"]
    if SPARSE in c["mlp_layer_types"] and not (
            0 <= first and count >= 1
            and first + count <= c["num_experts"]
            and c["num_experts_per_tok"] >= 1):
        raise ValueError(
            f"decoder: experts_held {c['experts_held']} does not lie in "
            f"the router's {c['num_experts']} experts")
    columns = c.get("shared_expert_columns_held")
    if columns and not _share_in(
            columns, c["shared_expert_intermediate_size"]):
        raise ValueError(
            f"decoder: shared_expert_columns_held {columns} does not lie "
            f"in the {c['shared_expert_intermediate_size']} columns")
    _check_router_groups(c)
    if c["router_scoring"] not in SCORINGS:
        raise ValueError(
            f"decoder: unknown router_scoring {c['router_scoring']!r}; "
            f"known: {sorted(SCORINGS)}")
    if not c["router_renorm_epsilon"] >= 0:
        raise ValueError(
            "decoder: router_renorm_epsilon is added to a sum of "
            f"probabilities and cannot be {c['router_renorm_epsilon']}")
    if c["router_score_bias"] and SPARSE not in c["mlp_layer_types"]:
        raise ValueError(
            "decoder: router_score_bias needs a sparse layer: the bias is "
            "its router's")
    for rope in dict(c["rope"]).values():
        rope_pairing(dict(rope))
    _check_attention_share(c, [
        h for h, kind in zip(c["heads_per_layer"], c["layer_types"])
        if kind in ATTENTIONS and kind != LATENT])
    if LATENT in c["layer_types"]:
        _check_latent_attention(c)
    if STATE_SPACE in c["layer_types"]:
        _check_state_space(c.get("state_space") or {})
    if SHORT_CONV in c["layer_types"]:
        _check_short_conv(c)
    if DELTA in c["layer_types"]:
        _check_delta_attention(c)
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
