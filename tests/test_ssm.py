"""The Mamba-2 recurrence (``fedml_tpu/ops/ssm.py``): the chunked form
the decoder stack's ``state_space`` layers run against the sequential
recurrence it stands for, values and gradients, and what a
rematerialised layer keeps of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import ssm

B, T, H, P, G, N = 2, 64, 4, 8, 2, 16  # four heads over two groups


def _inputs(seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (B, T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    b = jax.random.normal(k[3], (B, T, G, N)).astype(dtype)
    c = jax.random.normal(k[4], (B, T, G, N)).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(k[5], (H,))
    weigh = jax.random.normal(k[6], (B, T, H, P))
    return (x, dt, a, b, c, d), weigh


@pytest.mark.parametrize("chunk", [16, 32, 64, 128],
                         ids=["4_chunks", "2_chunks", "1_chunk", "short"])
def test_chunked_scan_equals_the_sequential_one(chunk):
    """Values and the gradient of every input (``x``, ``dt``, ``A``,
    ``B``, ``C``, ``D``), a sequence of several chunks, of one, and one
    shorter than a chunk; four heads over two groups."""
    args, weigh = _inputs()
    want = ssm.ssd_sequential(*args)
    got = ssm.ssd_chunked(*args, chunk)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weigh)
    g_want = jax.grad(loss(ssm.ssd_sequential), argnums=range(6))(*args)
    g_got = jax.grad(loss(lambda *a: ssm.ssd_chunked(*a, chunk)),
                     argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, err_msg=name)


def test_bfloat16_inputs_keep_decays_and_states_in_float32():
    """The step's compute dtype reaches the products' inputs only: the
    result of bfloat16 ``x``, ``B``, ``C`` lies within bfloat16 rounding
    of the float32 one, and comes back in bfloat16."""
    args, _ = _inputs(dtype=jnp.bfloat16)
    got = ssm.ssd_chunked(*args, 16)
    assert got.dtype == jnp.bfloat16
    full = [a.astype(jnp.float32) for a in args]
    want = ssm.ssd_sequential(*full)
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(
        want)
    assert float(err) < 0.02


def test_a_sequence_of_broken_chunks_is_refused():
    args, _ = _inputs()
    with pytest.raises(ValueError, match="whole chunks of 48"):
        ssm.ssd_chunked(*args, 48)


def _count(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and all it calls."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == name
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    found += _count(inner, name)
    return found


def test_a_rematerialised_scan_keeps_its_states_and_its_result():
    """Under ``save_only_these_names(*KEPT)`` the backward pass reads
    the kept states and the kept ``y``: the recurrence between chunks (a
    ``scan``) is not run a second time and of the four products only
    ``C B^T`` and the read of the entering states are (4 forward + 2 + 2
    x 4 backward = 14), where plain ``jax.checkpoint`` runs the
    recurrence and all four again — and the gradient is the same (to
    rounding: the compiler fuses the two programs differently)."""
    args, weigh = _inputs()
    # (what reads ``y`` — the gate — needs it again in the backward pass)
    fn = lambda *a: jnp.sum(jnp.tanh(ssm.ssd_chunked(*a, 16)) * weigh)
    kept = jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*ssm.KEPT))
    plain = jax.checkpoint(fn)
    grads = lambda f: jax.grad(f, argnums=range(6))
    jaxprs = {name: jax.make_jaxpr(grads(f))(*args).jaxpr
              for name, f in (("kept", kept), ("plain", plain))}
    # forward scan + its transpose; plain remat adds the recomputed one
    assert _count(jaxprs["kept"], "scan") == 2
    assert _count(jaxprs["plain"], "scan") == 3
    assert _count(jaxprs["kept"], "dot_general") == 14
    assert _count(jaxprs["plain"], "dot_general") == 16
    for a, b in zip(jax.jit(grads(kept))(*args), jax.jit(grads(plain))(*args)):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(b))))
