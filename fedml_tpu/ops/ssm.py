"""The Mamba-2 state-space recurrence (Dao & Gu 2024, "SSD"), in the
chunked form a chip runs and in the sequential form it stands for: what
the configured decoder stack's ``state_space`` layers
(:mod:`fedml_tpu.models.decoder`) call.

Per head (``x_t`` in R^P, a state ``S_t`` in R^{P x N}, a step ``dt_t >
0``, one decay rate ``A < 0`` a head; ``B_t``, ``C_t`` in R^N shared by
the heads of a group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

:func:`ssd_sequential` is that, a ``lax.scan`` a token.
:func:`ssd_chunked` computes the same ``y`` in chunks of ``Q`` tokens,
as matrix products: with ``cs`` the running sum of ``dt A`` inside a
chunk,

- within a chunk, ``y_t += sum_{s <= t} exp(cs_t - cs_s) dt_s (C_t .
  B_s) x_s``: the masked ``C B^T`` product of a group, weighted a head,
  times the chunk's ``x``;
- a chunk's own state, ``sum_s exp(cs_Q - cs_s) dt_s x_s B_s^T``;
- between chunks the state recurrence ``S_in[c + 1] = exp(cs_Q[c])
  S_in[c] + own[c]``, the ``T / Q`` sequential steps that are left;
- ``y_t += exp(cs_t) C_t . S_in[c]``, what the earlier chunks left.

Decays, their running sums and the states are float32 whatever the
products' inputs are (the step's compute dtype, accumulated in float32).
The backward pass is JAX's own of these products, and the reversed
recurrence for the one between chunks (:func:`entering_states`). **What
a rematerialised layer keeps:** the states entering every chunk carry
the ``checkpoint_name`` :data:`KEPT_STATES` (``[B, T / Q, H, P, N]``
float32: 33.5 MB for 16 heads of 64 x 128 over 8,192 tokens) and the
result ``y`` :data:`KEPT_OUTPUT` (16.8 MB in bfloat16 there), so under
``save_only_these_names(*KEPT)`` the mix within a chunk, a chunk's own
state and the recurrence between chunks run once a training step; the
``C B^T`` product, the decay masks and the read of the entering state
(whose result the decay's own gradient needs), which the backward pass
reads, are made again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

SCAN = "fedml.model.ssm.scan"

#: ``checkpoint_name``s (module docstring): the states entering each
#: chunk, and the scan's result
KEPT_STATES, KEPT_OUTPUT = "fedml_ssm_states", "fedml_ssm_output"
KEPT = (KEPT_STATES, KEPT_OUTPUT)


def _by_head(grouped, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: head ``h`` reads group ``h //
    (H / G)``."""
    return jnp.repeat(grouped, heads // grouped.shape[-2], axis=-2)


def ssd_sequential(x, dt, a, b, c, d):
    """The recurrence itself. ``x`` ``[B, T, H, P]``, ``dt`` ``[B, T,
    H]`` (positive), ``a`` ``[H]`` (negative), ``b`` / ``c`` ``[B, T, G,
    N]`` with ``H`` a multiple of ``G``, ``d`` ``[H]`` -> ``y`` ``[B, T,
    H, P]`` in ``x``'s dtype; the state is float32."""
    bsz, _, h, p = x.shape
    f32 = lambda t: t.astype(jnp.float32)
    bh, ch = _by_head(f32(b), h), _by_head(f32(c), h)

    def step(state, now):
        x_t, dt_t, b_t, c_t = now  # [B, H, P], [B, H], [B, H, N] x 2
        state = (jnp.exp(dt_t * f32(a))[..., None, None] * state
                 + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t))
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
        (time_first(f32(x)), time_first(f32(dt)), time_first(bh),
         time_first(ch)))
    y = jnp.moveaxis(y, 0, 1) + f32(d)[:, None] * f32(x)
    return y.astype(x.dtype)


def _recur(carry, own):
    """``S_in[0] = 0``, ``S_in[c + 1] = carry[c] S_in[c] + own[c]`` along
    axis 1 -> the state ENTERING each chunk (``own``'s shape)."""
    def step(state, chunk_of):
        carried, made = chunk_of
        return carried[..., None, None] * state + made, state

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(own[:, 0]),
        (jnp.moveaxis(carry, 1, 0), jnp.moveaxis(own, 1, 0)))
    return jnp.moveaxis(entering, 0, 1)


@jax.custom_vjp
def entering_states(carry, own):
    """The recurrence between chunks: ``carry`` ``[B, nc, G, per]`` (a
    chunk's whole decay), ``own`` ``[B, nc, G, per, P, N]`` (its own
    state, from nothing) -> the state entering each chunk, named
    :data:`KEPT_STATES`. Its backward pass is the same recurrence run
    from the last chunk to the first, and reads nothing of the forward
    one but its result: what a rematerialised layer keeps is all it
    needs."""
    return _recur(carry, own)


def _entering_fwd(carry, own):
    entering = checkpoint_name(_recur(carry, own), KEPT_STATES)
    return entering, (carry, entering)


def _entering_bwd(res, g):
    carry, entering = res

    def step(later, chunk_of):  # ``later``: the cotangent of S_in[c + 1]
        carried, g_c, s_c = chunk_of
        return (g_c + carried[..., None, None] * later,
                (jnp.sum(later * s_c, (-2, -1)), later))

    time_first = lambda v: jnp.moveaxis(v, 1, 0)
    _, (d_carry, d_own) = jax.lax.scan(
        step, jnp.zeros_like(g[:, 0]),
        (time_first(carry), time_first(g), time_first(entering)),
        reverse=True)
    return jnp.moveaxis(d_carry, 0, 1), jnp.moveaxis(d_own, 0, 1)


entering_states.defvjp(_entering_fwd, _entering_bwd)


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """:func:`ssd_sequential`'s ``y`` by chunks of ``chunk`` tokens
    (module docstring). A sequence shorter than a chunk is one chunk; a
    longer one is whole chunks or refused."""
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    q = min(chunk, t)
    if t % q:
        raise ValueError(
            f"ssd_chunked: {t} tokens are not whole chunks of {q}; pad the "
            "sequence")
    nc, per = t // q, h // g
    f32 = jnp.float32
    with jax.named_scope(SCAN):
        # [B, nc, G, per, Q, ...]: a group's heads side by side
        xc = x.reshape(bsz, nc, q, g, per, p).transpose(0, 1, 3, 4, 2, 5)
        by_head = lambda v: v.astype(f32).reshape(
            bsz, nc, q, g, per).transpose(0, 1, 3, 4, 2)
        dtc = by_head(dt)
        cs = jnp.cumsum(dtc * a.astype(f32).reshape(g, per, 1), -1)
        bc = b.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4)
        cc = c.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4)
        # within a chunk: the masked C B^T of a group, weighted a head
        cb = jnp.einsum("bcgtn,bcgsn->bcgts", cc, bc,
                        preferred_element_type=f32)
        lower = jnp.tril(jnp.ones((q, q), bool))
        decay = jnp.exp(jnp.where(
            lower, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        mix = cb[:, :, :, None] * decay * dtc[..., None, :]
        y = jnp.einsum("bcgrts,bcgrsp->bcgrtp", mix.astype(x.dtype), xc,
                       preferred_element_type=f32)
        # a chunk's own state, from nothing
        to_end = jnp.exp(cs[..., -1:] - cs) * dtc
        own = jnp.einsum(
            "bcgrsp,bcgsn->bcgrpn",
            (to_end[..., None] * xc.astype(f32)).astype(x.dtype), bc,
            preferred_element_type=f32)
        # between chunks: what is left of the sequential recurrence
        entering = entering_states(jnp.exp(cs[..., -1]), own)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bcgtn,bcgrpn->bcgrtp", cc, entering.astype(x.dtype),
            preferred_element_type=f32)
        y = y + d.astype(f32).reshape(g, per, 1, 1) * xc.astype(f32)
        return checkpoint_name(y.transpose(0, 1, 4, 2, 3, 5).reshape(
            bsz, t, h, p).astype(x.dtype), KEPT_OUTPUT)
