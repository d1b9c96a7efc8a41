"""The gated delta rule with a decay a CHANNEL of the key (Kimi Delta
Attention, arXiv:2510.26692 section 3), in the sequential form that
defines it and in the chunked form a chip runs: what the configured
decoder stack's ``delta_attention`` layers
(:mod:`fedml_tpu.models.decoder`) call.

Per head (``q_t``, ``k_t`` in R^K, ``v_t`` in R^V, a state ``S_t`` in
R^{K x V}, ``S_0 = 0``; ``gamma_t`` in R^K a log-decay a channel, at most
0; ``beta_t`` in [0, 1] a head)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(gamma_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The transition is NOT diagonal (``ops/ssm.py``'s is): the state first
decays a channel, then forgets what it held along ``k_t`` and writes
``v_t`` there. :func:`kda_sequential` is that, a ``lax.scan`` a token
(``u_t = beta_t (v_t - (Diag(exp(gamma_t)) S_{t-1})^T k_t)``, ``S_t =
Diag(exp(gamma_t)) S_{t-1} + k_t u_t^T``). :func:`kda_chunked` computes
the same ``o`` in chunks of ``Q`` tokens, as matrix products (the WY /
UT transform): with ``G_i`` the running sum of ``gamma`` inside a chunk
and ``S`` the state entering it,

- ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``i > j``, and
  ``P_ij`` the same of ``q_i`` (no ``beta``) for ``i >= j``;
- ``[W | U] = (I + A)^-1 Diag(beta) [k * exp(G) | v]``: one
  unit-lower-triangular solve a chunk and head, in float32;
- ``U~ = U - W S`` (the ``u_t`` above, all of a chunk's at once), ``o =
  (q * exp(G)) S + P U~``;
- between chunks ``S' = Diag(exp(G_Q)) S + (k * exp(G_Q - G))^T U~``,
  the ``T / Q`` sequential steps that are left
  (:func:`entering_states`).

``exp(G_i - G_j)`` is a product of two factors only about an origin
between ``j`` and ``i``: ``exp(-G_j)`` alone reaches ``e^{5 n}`` after
``n`` tokens at the steepest decay the decoder's gate gives (-5), past
float32 after 18. So ``A`` and ``P`` are formed a SUB-BLOCK of
:data:`SUB` rows at a time, each about the running sum at its own
MIDDLE row: rows ``exp(G_i - origin)`` against columns ``exp(origin -
G_j)``, both within ``e^{+-5 x 8} = e^{+-40}`` inside the block (the
columns at most 1 before it), so that neither they nor their
cotangents leave float32 (about the block's first row they would reach
``e^{+-80}``: finite, but a cotangent times ``e^-80`` is flushed to
zero). Everything else decays FROM the chunk's start or TO its end and
is at most 1.

Decays, their running sums, the solve and the states are float32
whatever the products' inputs are (``v``'s dtype, the step's compute
dtype, accumulated in float32; the products whose operand is the float32
state or the solve's, at precision "highest").

**On the TPU** (:func:`kernel_heads`; ``fedml_tpu/ops/delta_chunk.py``)
a chunk's whole work runs inside ONE Pallas kernel a pass, a few heads a
grid step, the chunks the grid's last, sequential axis:

- ``delta_chunk_fwd`` holds in fast memory the chunk's five inputs (read
  once from HBM: ``q``, ``k`` as the layer's normalisation leaves them,
  float32; ``v`` in the step's dtype; ``gamma``, ``beta`` float32), every
  intermediate of the list above, and the float32 state, which stays in
  scratch from one chunk to the next (TRANSPOSED, ``[V, K]``: a
  channel's decay then runs along its rows and every product with it
  sums over both operands' last axis). It writes ``o`` and the state
  ENTERING the chunk, every chunk's: no ``[B, T / Q, H, Q, ...]`` array
  exists. ``rest = U - W S`` is made once, from the float32 state.
- the solve is an explicit inverse in float32, without the compiler's
  ``triangular_solve`` (Mosaic has none): ``I + A``'s four 16 x 16
  diagonal blocks by row substitution (15 steps, all blocks and heads of
  the step at once), the blocks below them by block products — ``[[L1,
  0], [B, L2]]^-1 = [[L1^-1, 0], [-L2^-1 B L1^-1, L2^-1]]``, once at
  16 and once at 32 — so that ``[W | U] = M Diag(beta) [k e^G | v]`` is
  one product, and the backward pass's ``dA = -M^T dM M^T`` two.
- ``delta_chunk_bwd`` walks the chunks from the last to the first with
  the state's cotangent in scratch: a step reads the five inputs, the
  chunk's entering state and ``o``'s cotangent, makes ``A``, ``P``,
  ``M``, ``W``, ``U`` again in fast memory (``jax.vjp`` of the chunk's
  forward inside the kernel body) and writes the five cotangents.
- what stays float32: decays, running sums (a product with a triangle
  of ones at "highest": exact products, float32 sums), the inverse,
  ``W``, ``U``, ``rest``, the state and its cotangent, and every product
  with the state or the inverse but ``(q e^G) S``, at "highest". In
  ``v``'s dtype with float32 sums, as in the plain form: the two Gram
  products, ``(q e^G) S`` and ``P rest``.
- which calls take the plain form below: off the TPU all; a chunk other
  than 64; keys or values not whole lanes of 128; a sequence that is not
  whole chunks (one shorter than a chunk among them); values neither
  bfloat16 nor float32, or queries and keys neither float32 nor the
  values' dtype. Decided on what the call's operands show, and counted:
  :data:`DELTA_COUNTERS`.

**What a rematerialised layer keeps** (:data:`KEPT`): the result ``o``
(:data:`KEPT_OUTPUT`, 33.5 MB in bfloat16 for 16 heads of 128 x 128 over
8,192 tokens) and states entering chunks (:data:`KEPT_STATES`, float32).
On the TPU every chunk's (134 MB there): with ``o`` that is ALL the
forward kernel writes, so under ``save_only_these_names(*KEPT)`` a
training step runs ``delta_chunk_fwd`` once and ``delta_chunk_bwd`` once
a layer, and the backward kernel starts each chunk from its own state
(with every other one kept it would first step the even chunk's state
on, one more forward a pair of chunks for 67 MB a layer; the 2.4 GB of
whole-sequence scratch a layer's plain form held is gone either way). In
the
plain form the states entering every OTHER chunk (67 MB; the others are
one step on from them, all at once: :func:`_all_from_every_other`): the
recurrence between chunks (:func:`entering_states`, its backward pass
the reversed recurrence) runs once a training step; ``A``, ``P``, the
solve and the reads of the entering state, which JAX's own backward pass
of those products differentiates, are made again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.ops import attention, delta_chunk

SCAN = "fedml.model.delta.scan"

#: what a ``delta_attention`` layer counts a step: a chunk of a head
#: through the layer, and those of them the TPU kernels ran
DELTA_COUNTERS = ("delta_chunks", "delta_chunks_fused")

#: ``checkpoint_name``s (module docstring): the states entering each
#: chunk, and the recurrence's result
KEPT_STATES, KEPT_OUTPUT = "fedml_delta_states", "fedml_delta_output"
KEPT = (KEPT_STATES, KEPT_OUTPUT)

#: rows of ``A`` and ``P`` formed about one origin (module docstring)
SUB = delta_chunk.SUB

_HIGHEST = jax.lax.Precision.HIGHEST


def kda_sequential(q, k, v, gamma, beta):
    """The recurrence itself. ``q``, ``k`` ``[B, T, H, K]``, ``v`` ``[B,
    T, H, V]``, ``gamma`` ``[B, T, H, K]`` (at most 0), ``beta`` ``[B,
    T, H]`` -> ``o`` ``[B, T, H, V]`` in ``v``'s dtype; the state is
    float32."""
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now  # [B, H, K] x 2, [B, H, V], ...
        state = jnp.exp(g_t)[..., None] * state
        u_t = b_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, state, precision=_HIGHEST))
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.einsum(
            "bhk,bhkv->bhv", q_t, state, precision=_HIGHEST)

    bsz, _, h, dk = k.shape
    _, o = jax.lax.scan(
        step, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
        (f32(q), f32(k), f32(v), f32(gamma), f32(beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _chunk(state, decay, kt, w, u):
    """One chunk's step of the recurrence between chunks, over any
    leading axes: ``Diag(decay) S + kt^T (u - w S)``."""
    rest = u - jnp.einsum("...ck,...kv->...cv", w, state, precision=_HIGHEST)
    return decay[..., None] * state + jnp.einsum(
        "...ck,...cv->...kv", kt, rest, precision=_HIGHEST)


def _recur(decay, kt, w, u):
    """``S_in[0] = 0``, ``S_in[c + 1] = Diag(decay[c]) S_in[c] + kt[c]^T
    (u[c] - w[c] S_in[c])`` along axis 1 -> the state ENTERING each
    chunk, ``[B, nc, H, K, V]``."""
    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, entering = jax.lax.scan(
        lambda state, chunk_of: (_chunk(state, *chunk_of), state),
        jnp.zeros((*decay.shape[:1], *decay.shape[2:], u.shape[-1]),
                  jnp.float32),
        (first(decay), first(kt), first(w), first(u)))
    return jnp.moveaxis(entering, 0, 1)


def _all_from_every_other(even, decay, kt, w, u):
    """The states entering EVERY chunk from those entering chunks 0, 2,
    4, ...: an odd chunk's is one step on from the even chunk's before
    it, all of them at once."""
    pairs = decay.shape[1] // 2
    before = lambda t: t[:, 0:2 * pairs:2]
    odd = _chunk(even[:, :pairs], *map(before, (decay, kt, w, u)))
    both = jnp.stack([even[:, :pairs], odd], 2).reshape(
        even.shape[0], 2 * pairs, *even.shape[2:])
    return jnp.concatenate([both, even[:, pairs:]], 1)


@jax.custom_vjp
def entering_states(decay, kt, w, u):
    """The recurrence between chunks, float32: ``decay`` ``[B, nc, H,
    K]`` (a chunk's whole decay a channel), ``kt`` ``[B, nc, H, Q, K]``
    (its keys decayed to its end), ``w`` (as ``kt``) and ``u`` ``[B,
    nc, H, Q, V]`` (the solve's two results) -> the state entering each
    chunk. Those entering every OTHER chunk are named
    :data:`KEPT_STATES`, and the rest are one step on from them
    (:func:`_all_from_every_other`). Its backward pass is the transposed
    recurrence run from the last chunk to the first, and reads nothing
    of the forward one but those: what a rematerialised layer keeps is
    all it needs."""
    return _recur(decay, kt, w, u)


def _entering_fwd(decay, kt, w, u):
    even = checkpoint_name(_recur(decay, kt, w, u)[:, ::2], KEPT_STATES)
    return _all_from_every_other(even, decay, kt, w, u), (
        decay, kt, w, u, even)


def _entering_bwd(res, g):
    decay, kt, w, u, even = res
    entering = _all_from_every_other(even, decay, kt, w, u)

    def step(later, chunk_of):  # ``later``: the cotangent of S_in[c + 1]
        d, kt_c, w_c, g_c = chunk_of
        d_rest = jnp.einsum(
            "bhck,bhkv->bhcv", kt_c, later, precision=_HIGHEST)
        return (g_c + d[..., None] * later - jnp.einsum(
            "bhck,bhcv->bhkv", w_c, d_rest, precision=_HIGHEST), later)

    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, later = jax.lax.scan(
        step, jnp.zeros_like(g[:, 0]),
        (first(decay), first(kt), first(w), first(g)), reverse=True)
    later = jnp.moveaxis(later, 0, 1)
    # every chunk's own terms at once, from the cotangent that left it
    rest = u - jnp.einsum(
        "bnhck,bnhkv->bnhcv", w, entering, precision=_HIGHEST)
    d_rest = jnp.einsum("bnhck,bnhkv->bnhcv", kt, later, precision=_HIGHEST)
    d_kt = jnp.einsum("bnhcv,bnhkv->bnhck", rest, later, precision=_HIGHEST)
    d_w = -jnp.einsum(
        "bnhcv,bnhkv->bnhck", d_rest, entering, precision=_HIGHEST)
    return jnp.sum(later * entering, -1), d_kt, d_w, d_rest


entering_states.defvjp(_entering_fwd, _entering_bwd)


def kernel_heads(q, k, v, chunk: int) -> int | None:
    """How this call runs HERE: the heads a grid step of the TPU's chunk
    kernels (:func:`fedml_tpu.ops.delta_chunk.heads_a_step`, the shape
    rule), or None for the plain form — off the TPU always."""
    if not attention._on_tpu():
        return None
    return delta_chunk.heads_a_step(q, k, v, chunk)


def chunk_counts(q, k, v, chunk: int):
    """:data:`DELTA_COUNTERS`' two counts of one :func:`kda_chunked`
    call: its chunks times its heads, and those the kernels run."""
    bsz, t, h, _ = k.shape
    chunks = bsz * h * -(-t // chunk)
    fused = kernel_heads(q, k, v, chunk) is not None
    return jnp.float32(chunks), jnp.float32(chunks * fused)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_kernels(q, k, v, gamma, beta, chunk, heads):
    return _kernels_fwd(q, k, v, gamma, beta, chunk, heads)[0]


def _kernels_fwd(q, k, v, gamma, beta, chunk, heads):
    o, entering = delta_chunk.chunks_forward(
        q, k, v, gamma, beta, chunk=chunk, heads=heads)
    return o, (q, k, v, gamma, beta, checkpoint_name(entering, KEPT_STATES))


def _kernels_bwd(chunk, heads, kept, g):
    # (the rule's ops carry no scope of the call's: booked here)
    with jax.named_scope(SCAN):
        return delta_chunk.chunks_backward(
            *kept, g, chunk=chunk, heads=heads)


_kda_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kda_chunked(q, k, v, gamma, beta, chunk: int):
    """:func:`kda_sequential`'s ``o`` by chunks of ``chunk`` tokens
    (module docstring). A sequence shorter than a chunk is one chunk; a
    longer one is whole chunks, and a chunk whole sub-blocks of
    :data:`SUB` (or one shorter than that), or refused. Through the TPU
    kernels where :func:`kernel_heads` sends the call."""
    heads = kernel_heads(q, k, v, chunk)
    if heads is None:
        return _kda_plain(q, k, v, gamma, beta, chunk)
    with jax.named_scope(SCAN):
        return checkpoint_name(_kda_kernels(
            q, k, v, gamma, beta, chunk, heads), KEPT_OUTPUT)


def _kda_plain(q, k, v, gamma, beta, chunk: int):
    """:func:`kda_chunked` in plain JAX: whole-sequence arrays a chunk
    side by side, the compiler's solve, :func:`entering_states`."""
    bsz, t, h, dk = k.shape
    c = min(chunk, t)
    sub = min(SUB, c)
    if t % c or c % sub:
        raise ValueError(
            f"kda_chunked: {t} tokens are not whole chunks of {c} in "
            f"sub-blocks of {sub}; pad the sequence")
    nc, ns = t // c, c // sub
    f32, dt = jnp.float32, v.dtype
    with jax.named_scope(SCAN):
        # [B, nc, H, Q, ...]: a head's chunk side by side
        chunks = lambda a: jnp.moveaxis(
            a.reshape(bsz, nc, c, *a.shape[2:]), 3, 2)
        qc, kc = chunks(q).astype(f32), chunks(k).astype(f32)
        vc, bc = chunks(v), chunks(beta).astype(f32)
        g = jnp.cumsum(chunks(gamma).astype(f32), 3)
        # A and P, a sub-block of rows about the running sum at its middle
        blocks = lambda a: a.reshape(bsz, nc, h, ns, sub, dk)
        origin = blocks(g)[..., (sub - 1) // 2, :]
        left = jnp.exp(blocks(g) - origin[..., None, :])
        seen = jnp.arange(c) < (jnp.arange(ns)[:, None] + 1) * sub
        right = (kc[..., None, :, :] * jnp.exp(jnp.where(
            seen[..., None], origin[..., None, :] - g[..., None, :, :],
            -jnp.inf))).astype(dt)
        about = lambda rows: jnp.einsum(
            "bnhsik,bnhsjk->bnhsij", (blocks(rows) * left).astype(dt), right,
            preferred_element_type=f32).reshape(bsz, nc, h, c, c)
        a = jnp.tril(about(kc), -1) * bc[..., None]
        p = jnp.tril(about(qc))
        # [W | U] = (I + A)^-1 Diag(beta) [k exp(G) | v]
        from_start = jnp.exp(g)
        w, u = jnp.split(jax.scipy.linalg.solve_triangular(
            a, bc[..., None] * jnp.concatenate(
                [kc * from_start, vc.astype(f32)], -1),
            lower=True, unit_diagonal=True), [dk], -1)
        # between chunks: what is left of the sequential recurrence
        entering = entering_states(
            from_start[..., -1, :], kc * jnp.exp(g[..., -1:, :] - g), w, u)
        held = entering.astype(dt)
        product = lambda spec, x, y: jnp.einsum(
            spec, x.astype(dt), y, preferred_element_type=f32)
        rest = u - product("bnhck,bnhkv->bnhcv", w, held)
        o = product("bnhck,bnhkv->bnhcv", qc * from_start, held) + product(
            "bnhij,bnhjv->bnhiv", p, rest.astype(dt))
        return checkpoint_name(jnp.moveaxis(o, 2, 3).reshape(
            bsz, t, h, v.shape[-1]).astype(dt), KEPT_OUTPUT)
