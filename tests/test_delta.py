"""The gated delta rule with a decay a channel
(``fedml_tpu/ops/delta.py``): the chunked form against the sequential
one that defines it — values and the gradients of all five inputs — at
chunks of 16 and of 64 (four sub-blocks about an origin each), with a
whole chunk at the steepest decay the decoder's gate gives, with the
write strength at 0 and at 1, in float32 and with bfloat16 products;
the recurrence between chunks against JAX's own derivative of its scan;
what a rematerialised layer keeps. And the TPU's chunk kernels
(``fedml_tpu/ops/delta_chunk.py``) in the Pallas interpreter against the
same definition: values and all five gradients over dtype, chunk count
and heads, the steepest decay, the write strength's ends, the explicit
inverse against a triangular solve, the kept states against the plain
form's, the shape rule and the two counters."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import attention, delta as DL, delta_chunk as DC

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


# ---------------------------------------------------------------------------
# the chunk kernels, in the Pallas interpreter
# ---------------------------------------------------------------------------


def _kernels(args, weigh, heads=1):
    """-> (o, the states entering the chunks, the five cotangents under
    ``weigh``) of the two kernels, interpreted, at chunks of 64."""
    o, entering = DC.chunks_forward(
        *args, chunk=64, heads=heads, interpret=True)
    return o, entering, DC.chunks_backward(
        *args, entering, weigh.astype(o.dtype), chunk=64, heads=heads,
        interpret=True)


def _defined(args, weigh):
    """-> (o, gradients) of the sequential rule on the same operands."""
    wide = tuple(a.astype(jnp.float32) for a in args)
    weigh = weigh.astype(args[2].dtype).astype(jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(DL.kda_sequential(*a) * weigh),
                     argnums=tuple(range(5)))(*wide)
    return DL.kda_sequential(*wide), grads


def _operands(seed, dtype=jnp.float32, keys=jnp.float32, **sizes):
    """Values of ``dtype`` (the products'), queries and keys float32 as
    the decoder's normalisation leaves them (or of ``keys``)."""
    q, k, v, gamma, beta = _inputs(seed, **{"b": 1, **sizes})
    return (q.astype(keys), k.astype(keys), v.astype(dtype), gamma, beta)


@pytest.mark.parametrize("h", [1, 3])
@pytest.mark.parametrize("chunks", [1, 2, 3, 8])
@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 2e-5), (jnp.bfloat16, 5e-2)], ids=["float32", "bfloat16"])
def test_the_kernels_equal_the_sequential_rule(dtype, tol, chunks, h):
    """``delta_chunk_fwd`` and ``delta_chunk_bwd`` against the
    definition on the operands as the kernels get them: values and the
    five gradients; a head a grid step or all three; queries and keys
    float32 or of the values' dtype."""
    args = _operands(chunks, dtype, keys=jnp.float32 if chunks % 2 else dtype,
                     t=64 * chunks, h=h)
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    o, entering, grads = _kernels(args, weigh, heads=h if chunks % 2 else 1)
    o_ref, grads_ref = _defined(args, weigh)
    assert o.dtype == dtype and entering.dtype == jnp.float32
    assert entering.shape == (1, chunks, h, 8, 8)  # [.., V, K]
    _assert_close(o.astype(jnp.float32), o_ref, tol)
    for name, g, a, g_ref in zip(INPUTS, grads, args, grads_ref):
        assert g.dtype == a.dtype and g.shape == a.shape, name
        assert float(jnp.max(jnp.abs(g_ref))) > 0, name
        _assert_close(g.astype(jnp.float32), g_ref, tol)


def test_the_kernels_at_the_steepest_decay_stay_finite_and_right():
    """As the plain form's test of the same name: ``gamma = -5`` on
    every channel and token of two chunks."""
    q, k, v, gamma, beta = _operands(1, t=128, h=2)
    args = (q, k, v, jnp.full_like(gamma, -5.0), beta)
    weigh = jax.random.normal(jax.random.key(9), v.shape)
    o, _, grads = _kernels(args, weigh, heads=2)
    o_ref, grads_ref = _defined(args, weigh)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, o_ref, atol=5e-5)
    for g, g_ref in zip(grads, grads_ref):
        assert bool(jnp.all(jnp.isfinite(g)))
        _assert_close(g, g_ref, 2e-4)


@pytest.mark.parametrize("strength", [0.0, 1.0])
def test_the_kernels_at_the_write_strengths_ends(strength):
    q, k, v, gamma, beta = _operands(2, t=128, h=2)
    args = (q, k, v, gamma, jnp.full_like(beta, strength))
    weigh = jax.random.normal(jax.random.key(9), v.shape)
    o, entering, grads = _kernels(args, weigh)
    o_ref, grads_ref = _defined(args, weigh)
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    if not strength:
        assert float(jnp.max(jnp.abs(o))) == 0.0
        assert float(jnp.max(jnp.abs(entering))) == 0.0
    for g, g_ref in zip(grads, grads_ref):
        _assert_close(g, g_ref, 2e-5)


def test_the_kernels_inverse_is_the_triangular_solves():
    """``(I + A)^-1`` by substitution in the 16 x 16 diagonal blocks and
    block products below them, against ``solve_triangular`` on a random
    unit-lower-triangular 64 x 64; and its rule ``-M^T dM M^T`` against
    JAX's derivative of the solve."""
    a = jnp.tril(jax.random.normal(jax.random.key(0), (64, 64)) * 0.3, -1)
    solve = lambda a: jax.scipy.linalg.solve_triangular(
        a, jnp.eye(64), lower=True, unit_diagonal=True)
    want, back = jax.vjp(solve, a)
    got, rule = jax.vjp(DC.unit_lower_inverse, a)
    _assert_close(got, want, 1e-5)
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0
    np.testing.assert_array_equal(jnp.diagonal(got), 1.0)
    g = jax.random.normal(jax.random.key(1), (64, 64))
    _assert_close(jnp.tril(rule(g)[0], -1), jnp.tril(back(g)[0], -1), 1e-5)


def test_the_kernels_kept_states_are_the_plain_forms(monkeypatch):
    """The states entering the chunks, float32 whatever the operands
    are: every chunk's from the kernel, equal to what the plain form's
    recurrence between chunks makes of the same operands."""
    plain = []
    recur = DL.entering_states
    monkeypatch.setattr(DL, "entering_states", lambda *a: plain.append(
        recur(*a)) or plain[-1])
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        args = _operands(3, dtype, t=320, h=2)
        DL.kda_chunked(*args, 64)
        _, entering = DC.chunks_forward(*args, chunk=64, interpret=True)
        assert entering.dtype == plain[-1].dtype == jnp.float32
        assert float(jnp.max(jnp.abs(entering[:, 0]))) == 0.0  # S_0 = 0
        _assert_close(jnp.swapaxes(entering, -1, -2), plain[-1], tol)


def _sized(t=128, h=2, dk=128, dv=128, dtype=jnp.bfloat16,
           keys=jnp.float32, k_dtype=None):
    sds = jax.ShapeDtypeStruct
    return (sds((1, t, h, dk), keys), sds((1, t, h, dk), k_dtype or keys),
            sds((1, t, h, dv), dtype))


@pytest.mark.parametrize("why, sizes, chunk, heads", [
    ("published", {"t": 8192, "h": 16}, 64, 4),
    ("float32", {"dtype": jnp.float32}, 64, 2),
    ("keys_as_values", {"keys": jnp.bfloat16}, 64, 2),
    ("odd_heads", {"h": 3}, 64, 1),
    ("another_chunk", {}, 32, None),
    ("narrow_keys", {"dk": 64}, 64, None),
    ("narrow_values", {"dv": 64}, 64, None),
    ("shorter_than_a_chunk", {"t": 32}, 64, None),
    ("not_whole_chunks", {"t": 96}, 64, None),
    ("keys_wider_than_values", {"keys": jnp.bfloat16,
                                "dtype": jnp.float32}, 64, None),
    ("queries_unlike_keys", {"k_dtype": jnp.bfloat16}, 64, None),
    ("float16", {"dtype": jnp.float16}, 64, None),
])
def test_the_shape_rule(why, sizes, chunk, heads, monkeypatch):
    """Which calls the kernels take, and in how many heads a grid step;
    off the TPU none."""
    assert DL.kernel_heads(*_sized(**sizes), chunk) is None
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert DL.kernel_heads(*_sized(**sizes), chunk) == heads


def _interpreted(monkeypatch):
    """The shape rule as on the chip, its kernels in the interpreter."""
    monkeypatch.setattr(DL, "kernel_heads", DC.heads_a_step)
    for name in ("chunks_forward", "chunks_backward"):
        monkeypatch.setattr(DC, name, functools.partial(
            getattr(DC, name), interpret=True))


def test_a_call_the_kernels_take_counts_its_chunks_fused(monkeypatch):
    """``kda_chunked`` through the kernels (keys and values of 128) is
    the plain form's result and gradients, and ``chunk_counts`` says
    which of the two ran: off the chip and at a shape the rule leaves to
    the plain form, ``delta_chunks_fused`` is 0."""
    assert DL.DELTA_COUNTERS == ("delta_chunks", "delta_chunks_fused")
    args = _operands(4, t=128, h=2, dk=128, dv=128)
    run = lambda: jax.value_and_grad(
        lambda *a: jnp.sum(DL.kda_chunked(*a, 64) ** 2),
        argnums=tuple(range(5)))(*args)
    counts = lambda a, chunk=64: tuple(
        map(float, DL.chunk_counts(*a[:3], chunk)))
    plain = run()
    assert counts(args) == (4.0, 0.0)
    _interpreted(monkeypatch)
    fused = run()
    assert counts(args) == (4.0, 4.0)
    assert counts(_operands(4, t=128, h=2)) == (4.0, 0.0)  # keys of 8
    assert counts(args, chunk=16) == (16.0, 0.0)
    np.testing.assert_allclose(fused[0], plain[0], rtol=1e-5)
    for g, g_ref in zip(fused[1], plain[1]):
        _assert_close(g, g_ref, 1e-4)
    # and the narrow call went the plain way, kernels patched or not
    narrow = _operands(4, t=128, h=2)
    np.testing.assert_allclose(
        DL.kda_chunked(*narrow, 64), DL.kda_sequential(*narrow), atol=1e-5)


def test_a_mapped_call_is_each_call_alone(monkeypatch):
    """``jax.vmap`` over clients (a block wider than one) through the
    kernels: values and gradients of each mapped call to the bit those
    of the call alone — the state in scratch is zeroed at every
    sequence's first chunk, whatever axis the map adds to the grid."""
    _interpreted(monkeypatch)
    alone = [_operands(s, t=128, h=2, dk=128, dv=128) for s in (0, 1)]
    run = jax.value_and_grad(
        lambda *a: jnp.sum(DL.kda_chunked(*a, 64) ** 2),
        argnums=tuple(range(5)))
    mapped = jax.vmap(run)(*(jnp.stack(a) for a in zip(*alone)))
    for i, args in enumerate(alone):
        jax.tree.map(np.testing.assert_array_equal, run(*args),
                     jax.tree.map(lambda a: a[i], mapped))


def test_a_rematerialised_call_runs_each_kernel_once(monkeypatch):
    """Under ``save_only_these_names(*KEPT)`` the gradient's program
    holds the forward kernel ONCE and the backward kernel once (in the
    interpreter a kernel is one loop over its grid): the kept states and
    ``o`` are all the forward kernel makes, so the backward pass does
    not run it again; with nothing kept it does."""
    _interpreted(monkeypatch)
    args = _operands(6, t=128, h=2, dk=128, dv=128)
    kept = jax.checkpoint(
        lambda *a: DL.kda_chunked(*a, 64),
        policy=jax.checkpoint_policies.save_only_these_names(*DL.KEPT))
    plain = jax.checkpoint(lambda *a: DL.kda_chunked(*a, 64))
    loops = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text().count(" while(")
    assert loops(kept) == 2
    assert loops(plain) == 3
