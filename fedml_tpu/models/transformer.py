"""Transformer LM with pluggable attention (full / flash / ring).

Capability the TPU build adds beyond the reference (whose NLP zoo is
2-layer LSTMs, ``fedml_api/model/nlp/rnn.py:4-70``): a causal transformer
whose attention implementation is injected, so the SAME module runs

- single-chip with the pallas flash kernel
  (:func:`fedml_tpu.ops.flash_attention.flash_attention`),
- sequence-parallel with ring attention under ``shard_map``
  (:func:`fedml_tpu.ops.ring_attention.ring_attention`) — embeddings, MLP,
  and layernorm are position-wise, so sharding the T axis only touches the
  attention collective; position ids are passed in so shards embed their
  GLOBAL positions.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.ops.ring_attention import full_attention

AttnFn = Callable[..., jax.Array]  # (q, k, v, causal=...) -> out

#: dense factory: (features, use_bias, name) -> nn.Module. None = stock
#: nn.Dense. The PEFT subsystem (fedml_tpu.peft.lora.dense_factory)
#: substitutes LoRA-wrapped projections for targeted names without
#: touching this module's structure or the attn_fn contract.
DenseFactory = Any


def _dense(factory: DenseFactory, features: int, use_bias: bool,
           name: str) -> nn.Module:
    if factory is None:
        return nn.Dense(features, use_bias=use_bias, name=name)
    return factory(features, use_bias, name)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attn_fn: AttnFn = full_attention
    dense_cls: DenseFactory = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, t, c = x.shape
        h = nn.LayerNorm()(x)
        # separate q/k/v projections (explicitly named): under tensor
        # parallelism each is column-sharded on its own output dim, so
        # shards align with head boundaries (a fused 3c projection sharded
        # contiguously would cut across q/k/v and force extra resharding)
        q = _dense(self.dense_cls, c, False, "q_proj")(h)
        k = _dense(self.dense_cls, c, False, "k_proj")(h)
        v = _dense(self.dense_cls, c, False, "v_proj")(h)
        hd = c // self.num_heads

        def heads(z):
            return z.reshape(b, t, self.num_heads, hd)

        a = self.attn_fn(heads(q), heads(k), heads(v), causal=True)
        a = a.reshape(b, t, c)
        x = x + _dense(self.dense_cls, c, False, "attn_out")(a)
        h = nn.LayerNorm()(x)
        h = _dense(self.dense_cls, self.mlp_ratio * c, True, "mlp_up")(h)
        h = nn.gelu(h)
        x = x + _dense(self.dense_cls, c, True, "mlp_down")(h)
        return x


class TransformerLM(nn.Module):
    vocab_size: int
    num_layers: int = 2
    num_heads: int = 4
    embed_dim: int = 128
    max_len: int = 2048
    attn_fn: AttnFn = full_attention
    dense_cls: DenseFactory = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None):
        """``tokens`` [B, T] int32; ``positions`` [B, T] global positions
        (defaults to 0..T-1 — pass explicitly under sequence parallelism,
        where a shard holds tokens t0..t0+T_local)."""
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        x = nn.Embed(self.vocab_size, self.embed_dim)(tokens)
        x = x + nn.Embed(self.max_len, self.embed_dim, name="pos_emb")(
            positions
        )
        for _ in range(self.num_layers):
            x = Block(
                self.num_heads, attn_fn=self.attn_fn,
                dense_cls=self.dense_cls,
            )(x, train=train)
        x = nn.LayerNorm()(x)
        # named so the PEFT partition (fedml_tpu.peft.partition) can
        # select the head subtree as densely-trainable by path
        return nn.Dense(self.vocab_size, use_bias=False, name="lm_head")(x)


def make_sequence_parallel_lm_step(
    model: TransformerLM, mesh, axis_name: str = "sp"
):
    """Compile a sequence-parallel causal-LM loss/grad step.

    The whole forward+backward runs inside one ``shard_map`` over the
    sequence axis: each device holds [B, T/p] tokens; the only cross-shard
    communication is ring attention's K/V rotation (plus the psum of the
    scalar loss and of parameter grads, which are replicated).
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from fedml_tpu.ops.ring_attention import ring_attention

    sp_model = model.clone(
        attn_fn=functools.partial(ring_attention, axis_name=axis_name)
    )
    p = mesh.shape[axis_name]

    def local_step(params, tokens, targets):
        # tokens/targets: LOCAL [B, T/p] shards
        idx = jax.lax.axis_index(axis_name)
        b, t_local = tokens.shape
        positions = jnp.broadcast_to(
            idx * t_local + jnp.arange(t_local)[None], (b, t_local)
        )

        def loss_fn(params):
            logits = sp_model.apply(params, tokens, positions=positions)
            import optax

            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            )
            return jax.lax.pmean(jnp.mean(ce), axis_name)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, axis_name)
        return loss, grads

    tok_spec = P(None, axis_name)
    return shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), tok_spec, tok_spec),
        out_specs=(P(), P()),
    )


def tp_param_specs(params, tp_axis: str = "tp"):
    """Megatron-style tensor-parallel PartitionSpecs for TransformerLM
    params: per block, the qkv projection and MLP up-projection are
    COLUMN-parallel (output dim sharded over ``tp_axis``) and the attention
    output / MLP down-projection are ROW-parallel (input dim sharded), so
    each block needs exactly one all-reduce per sublayer — GSPMD inserts
    it from these annotations. Embeddings, layernorms, and the LM head are
    replicated."""
    from jax.sharding import PartitionSpec as P

    COLUMN = ("q_proj", "k_proj", "v_proj", "mlp_up")
    ROW = ("attn_out", "mlp_down")

    def spec_for(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        module = keys[-2] if len(keys) >= 2 else ""
        if keys[-1] == "kernel":
            if module in COLUMN:
                return P(None, tp_axis)
            if module in ROW:
                return P(tp_axis, None)
        if keys[-1] == "bias" and module in COLUMN:
            return P(tp_axis)  # bias follows its column shard
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def make_tp_dp_lm_step(
    model: TransformerLM,
    mesh,
    tp_axis: str = "tp",
    dp_axis: str = "data",
    lr: float = 0.1,
):
    """Compile a tensor-parallel x data-parallel causal-LM SGD step via
    GSPMD sharding annotations (jit + NamedSharding — XLA inserts the
    per-sublayer all-reduces and the data-parallel gradient reduction).
    Heads must divide the tp axis size. Returns
    ``step(params, tokens, targets) -> (params, loss)`` with params
    sharded per :func:`tp_param_specs` and the batch over ``dp_axis``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert model.num_heads % mesh.shape[tp_axis] == 0, (
        model.num_heads, mesh.shape[tp_axis]
    )

    def loss_fn(params, tokens, targets):
        import optax

        logits = model.apply(params, tokens)
        return jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        )

    def step(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, loss

    def param_shardings(params):
        specs = tp_param_specs(params, tp_axis)
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P),
        )

    def shard_params(params):
        return jax.device_put(params, param_shardings(params))

    def compile_step(params):
        pshard = param_shardings(params)
        dshard = NamedSharding(mesh, P(dp_axis, None))
        return jax.jit(
            step,
            in_shardings=(pshard, dshard, dshard),
            out_shardings=(pshard, NamedSharding(mesh, P())),
        )

    return compile_step, shard_params
