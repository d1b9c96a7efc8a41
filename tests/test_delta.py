"""The gated delta rule with a decay a channel
(``fedml_tpu/ops/delta.py``): the chunked form against the sequential
one that defines it — values and the gradients of all five inputs — at
chunks of 16 and of 64 (four sub-blocks about an origin each), with a
whole chunk at the steepest decay the decoder's gate gives, with the
write strength at 0 and at 1, in float32 and with bfloat16 products;
the recurrence between chunks against JAX's own derivative of its scan;
what a rematerialised layer keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import delta as DL

INPUTS = ("q", "k", "v", "gamma", "beta")


def _inputs(seed=0, b=2, t=128, h=2, dk=8, dv=8, lower=-5.0):
    """Unit keys, queries scaled as the decoder scales them, decays in
    ``(lower, 0)`` a channel, write strengths in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            lower * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, dk))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


def _both(args, chunk):
    """-> ((o, gradients) chunked, (o, gradients) sequential) under one
    random cotangent."""
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    run = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weigh), argnums=tuple(range(5)))(*args)
    chunked = run(lambda *a: DL.kda_chunked(*a, chunk))
    return chunked, run(DL.kda_sequential)


def _assert_close(got, want, rtol):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=rtol * scale)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("dv", [8, 16])
def test_chunked_equals_sequential_values_and_gradients(chunk, dv):
    """Keys of 8 beside values of 8 or 16, 128 tokens: 8 chunks of 16
    or 2 of 64."""
    args = _inputs(dv=dv)
    (o, grads), (o_ref, grads_ref) = _both(args, chunk)
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    for name, g, g_ref in zip(INPUTS, grads, grads_ref):
        assert float(jnp.max(jnp.abs(g_ref))) > 0, name
        _assert_close(g, g_ref, 2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_whole_chunk_at_the_steepest_decay_stays_finite_and_right(chunk):
    """``gamma = -5`` on every channel and token: inside a sub-block of
    16 the two factors reach ``e^40`` and ``e^-40`` about its middle
    row, across sub-blocks and chunks everything underflows to the zero
    it is; nothing overflows and no cotangent is flushed, forward or
    backward."""
    q, k, v, gamma, beta = _inputs(1)
    args = (q, k, v, jnp.full_like(gamma, -5.0), beta)
    (o, grads), (o_ref, grads_ref) = _both(args, chunk)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, o_ref, atol=5e-5)
    for g, g_ref in zip(grads, grads_ref):
        assert bool(jnp.all(jnp.isfinite(g)))
        _assert_close(g, g_ref, 2e-4)
    # and nearly no decay at all: the state lives through every chunk
    args = (q, k, v, jnp.full_like(gamma, -1e-4), beta)
    (o, _), (o_ref, _) = _both(args, chunk)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)


@pytest.mark.parametrize("strength", [0.0, 1.0])
def test_write_strength_at_its_ends(strength):
    """``beta = 0`` writes nothing (``o`` is zero and ``A`` vanishes);
    ``beta = 1`` replaces what the state held along ``k``."""
    q, k, v, gamma, beta = _inputs(2)
    args = (q, k, v, gamma, jnp.full_like(beta, strength))
    (o, grads), (o_ref, grads_ref) = _both(args, 64)
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    if not strength:
        assert float(jnp.max(jnp.abs(o))) == 0.0
    for g, g_ref in zip(grads, grads_ref):
        _assert_close(g, g_ref, 2e-5)


def test_the_state_crosses_chunks(monkeypatch):
    """With the entering states zeroed the output changes after the
    first chunk and not inside it: the recurrence between chunks
    carries something."""
    args = _inputs(3, t=64)
    want = DL.kda_chunked(*args, 16)
    monkeypatch.setattr(
        DL, "entering_states",
        lambda decay, kt, w, u: jnp.zeros(
            (*decay.shape, u.shape[-1]), jnp.float32))
    alone = DL.kda_chunked(*args, 16)
    np.testing.assert_allclose(alone[:, :16], want[:, :16], atol=1e-6)
    assert float(jnp.max(jnp.abs(alone[:, 16:] - want[:, 16:]))) > 1e-2


def test_the_recurrence_between_chunks_has_its_scans_own_derivative():
    """``entering_states``'s rule — the transposed recurrence, from the
    kept states alone, those entering every other of FIVE chunks —
    against ``jax.vjp`` of the scan it replaces; the states the forward
    pass makes one step on from the kept ones are the scan's."""
    ks = jax.random.split(jax.random.key(4), 5)
    b, nc, h, q, dk, dv = 2, 5, 3, 4, 6, 7
    args = (jax.random.uniform(ks[0], (b, nc, h, dk), minval=0.2),
            jax.random.normal(ks[1], (b, nc, h, q, dk)) * 0.5,
            jax.random.normal(ks[2], (b, nc, h, q, dk)) * 0.5,
            jax.random.normal(ks[3], (b, nc, h, q, dv)))
    g = jax.random.normal(ks[4], (b, nc, h, dk, dv))
    out, rule = jax.vjp(DL.entering_states, *args)
    ref, plain = jax.vjp(DL._recur, *args)
    np.testing.assert_array_equal(out[:, ::2], ref[:, ::2])
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)
    assert float(jnp.max(jnp.abs(out[:, 0]))) == 0.0  # S_0 = 0
    for got, want in zip(rule(g), plain(g)):
        _assert_close(got, want, 1e-5)


def test_bfloat16_products_keep_float32_decays_and_states():
    """Values in bfloat16 (the step's compute dtype): the result is
    bfloat16, close to the float32 recurrence, and the states entering
    the chunks are float32."""
    q, k, v, gamma, beta = _inputs(5)
    o = DL.kda_chunked(q, k, v.astype(jnp.bfloat16), gamma, beta, 64)
    assert o.dtype == jnp.bfloat16
    want = DL.kda_sequential(q, k, v, gamma, beta)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) < 0.05
    states = jax.eval_shape(
        DL.entering_states,
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (2, 2, 2, 8), (2, 2, 2, 64, 8), (2, 2, 2, 64, 8),
            (2, 2, 2, 64, 8))))
    assert states.shape == (2, 2, 2, 8, 8) and states.dtype == jnp.float32


def test_a_rematerialised_call_runs_the_recurrence_once():
    """Under ``save_only_these_names(*KEPT)`` the gradient's program
    holds ONE forward loop over the chunks and one reversed: the kept
    states feed the rule, and the forward recurrence is not run again."""
    args = _inputs(6)
    kept = jax.checkpoint(
        lambda *a: DL.kda_chunked(*a, 16),
        policy=jax.checkpoint_policies.save_only_these_names(*DL.KEPT))
    plain = jax.checkpoint(lambda *a: DL.kda_chunked(*a, 16))
    loops = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text().count(" while(")
    assert loops(kept) == 2
    assert loops(plain) == 3  # nothing kept: forward, again, reversed
    g_kept = jax.grad(lambda *a: jnp.sum(kept(*a) ** 2), argnums=3)(*args)
    g_plain = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=3)(*args)
    np.testing.assert_allclose(g_kept, g_plain, atol=1e-6)


@pytest.mark.parametrize("t, chunk, message", [
    (100, 64, "not whole chunks of 64"),
    (96, 24, "sub-blocks of 16"),
])
def test_a_sequence_that_does_not_split_is_refused(t, chunk, message):
    with pytest.raises(ValueError, match=message):
        DL.kda_chunked(*_inputs(t=t), chunk)


def test_a_sequence_shorter_than_a_chunk_is_one_chunk():
    args = _inputs(7, t=8)
    np.testing.assert_allclose(
        DL.kda_chunked(*args, 64), DL.kda_sequential(*args), atol=1e-6)
