"""Ring attention / flash attention / sequence-parallel transformer tests
on the 8-device virtual CPU mesh (slow), and the launch shape of the
decoder stack's blockwise kernel (``ops/attention.py:_block_sizes``),
with the kernel's gradients under it, by mask kind, in the Pallas
interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.ops.ring_attention import full_attention, ring_attention
from fedml_tpu.models.transformer import (
    TransformerLM,
    make_sequence_parallel_lm_step,
)


def _mesh(n=4, name="sp"):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def _qkv(b=2, t=32, h=2, d=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v = _qkv()
    expect = full_attention(q, k, v, causal=causal)
    mesh = _mesh(4)
    spec = P(None, "sp", None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    got = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), atol=2e-5, rtol=2e-5
    )


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_full(causal):
    q, k, v = _qkv(t=64)
    expect = full_attention(q, k, v, causal=causal)
    got = flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), atol=2e-5, rtol=2e-5
    )


@pytest.mark.slow
def test_transformer_lm_forward():
    model = TransformerLM(vocab_size=50, num_layers=2, num_heads=2,
                          embed_dim=32)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 50)


@pytest.mark.slow
def test_sequence_parallel_lm_matches_single_device():
    """SP loss and grads == single-device loss and grads."""
    vocab, b, t = 37, 2, 32
    model = TransformerLM(vocab_size=vocab, num_layers=2, num_heads=2,
                          embed_dim=32, max_len=t)
    rng = jax.random.key(0)
    tokens = jax.random.randint(rng, (b, t), 0, vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.key(1), tokens)

    # single-device reference
    import optax

    def ref_loss(params):
        logits = model.apply(params, tokens)
        return jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        )

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)

    mesh = _mesh(4)
    step = make_sequence_parallel_lm_step(model, mesh, "sp")
    loss_sp, grads_sp = step(params, tokens, targets)

    np.testing.assert_allclose(
        float(loss_sp), float(loss_ref), atol=1e-5, rtol=1e-5
    )
    for a, b_ in zip(jax.tree.leaves(grads_ref), jax.tree.leaves(grads_sp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
        )


@pytest.mark.slow
def test_tp_dp_step_matches_unsharded():
    """Megatron-style TP x DP GSPMD step == the unsharded SGD step (one
    all-reduce per sublayer inserted by XLA from the column/row specs)."""
    import optax

    from fedml_tpu.models.transformer import (
        TransformerLM,
        make_tp_dp_lm_step,
    )

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("tp", "data"))
    lm = TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                       embed_dim=32, max_len=64)
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1)
    params = lm.init(jax.random.key(1), tokens)
    compile_step, shard_params = make_tp_dp_lm_step(lm, mesh, lr=0.1)
    sp, loss = compile_step(params)(shard_params(params), tokens, targets)

    def ref_step(params):
        def lf(p):
            lg = lm.apply(p, tokens)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(lg, targets)
            )
        l, g = jax.value_and_grad(lf)(params)
        return jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g), l

    rp, rl = jax.jit(ref_step)(params)
    np.testing.assert_allclose(float(loss), float(rl), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(rp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# the blockwise kernel's launch shape (ops/attention.py:_block_sizes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t, block, wide", [
    pytest.param(8192, 512, 1024, id="keye_and_nemotron_8192"),
    pytest.param(2048, 512, 1024, id="laguna_2048"),
    pytest.param(1536, 512, 512, id="not_whole_double_blocks"),
    pytest.param(512, 512, 512, id="one_block"),
    pytest.param(256, 256, 256, id="shorter_than_a_block"),
])
def test_block_sizes_name_one_backward_kernel(t, block, wide):
    """Every use of the kernel gets one launch shape by its length: the
    forward's three edges as they were, and a backward pass that is ONE
    kernel — no dq edge (the library refuses them beside the flag), key
    blocks of two compute blocks where ``t`` is whole double blocks."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )
    from fedml_tpu.ops import attention as A

    got = A._block_sizes(t)
    assert got == sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=wide, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    assert got.block_q_dq is None and got.block_kv_dq is None
    assert got.has_backward_blocks and t % wide == 0


@pytest.mark.parametrize("t", [128, 512], ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("kind", ["selection", "causal", "window"])
def test_kernel_gradients_under_the_block_sizes_of_each_use(
        kind, t, monkeypatch):
    """dq, dk and dv of the blockwise kernel (Pallas interpreter, blocks
    of 128) under :func:`_block_sizes`' launch shape, for each mask
    kind, against the masked product's: at one block (the backward
    pass's key block IS its compute block) and at four (a key block
    holds two compute blocks, whose dQ terms add up in the kernel's
    float32 scratch, and dQ is the sum of two partials)."""
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "BLOCK", 128)
    A._splash_kernel.cache_clear()
    heads, kv, d = 4, 2, 128
    ks = jax.random.split(jax.random.key(t), 5)
    q = jax.random.normal(ks[0], (1, t, heads, d))
    k = jax.random.normal(ks[1], (1, t, kv, d))
    v = jax.random.normal(ks[2], (1, t, kv, d))
    g = jax.random.normal(ks[3], (1, t, heads, d))
    window = 72 if kind == "window" else None
    selection = (A.select_top_k(jax.random.normal(ks[4], (1, t, t)), 40)
                 if kind == "selection" else None)
    sizes = A._block_sizes(t)
    assert sizes.use_fused_bwd_kernel
    assert sizes.block_kv_dkv == min(256, t)
    assert sizes.block_kv_dkv_compute == 128
    if selection is not None:
        kernel = lambda q, k, v: A.selected_splash(
            q, k, v, selection, interpret=True)
    else:
        kernel = lambda q, k, v: A.splash_attention(
            q, k, v, window=window, interpret=True)
    masked = lambda q, k, v: A.masked_attention(
        q, k, v, window, selection=selection)
    want, vjp = jax.vjp(masked, q, k, v)
    got, vjp_got = jax.vjp(kernel, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for a, b in zip(vjp_got(g), vjp(g)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    A._splash_kernel.cache_clear()  # kernels built at this BLOCK
