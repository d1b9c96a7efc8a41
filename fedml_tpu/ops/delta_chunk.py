"""One chunk of the gated delta rule as a function of values a kernel
holds in fast memory, and the two TPU kernels that walk a sequence's
chunks with it: what :func:`fedml_tpu.ops.delta.kda_chunked` runs on
the chip (its module docstring has the mathematics and the names).

:func:`chunk_step` is a chunk, whole, for the few heads of a grid step
side by side (a leading axis: independent heads fill the waits of each
other's products): from the chunk's inputs and the float32 state ``S``
entering it to ``o`` and the state leaving it — ``A`` and ``P`` a
sub-block of :data:`SUB` rows about its own middle row, the inverse ``M
= (I + A)^-1`` (:func:`unit_lower_inverse`), ``[W | U] = M Diag(beta)
[k e^G | v]``, ``rest = U - W S`` ONCE from the float32 state, ``o = (q
e^G) S + P rest``, ``S' = Diag(e^{G_Q}) S + (k e^{G_Q - G})^T rest``.
The state is held TRANSPOSED (``[V, K]``): a channel's decay then runs
along its rows, and every product with it sums over both operands' last
axis, so nothing passes through the transpose unit. No ``[B, T / Q, H,
Q, ...]`` array exists anywhere.

* :func:`chunks_forward` (the kernel ``delta_chunk_fwd``): grid ``(B, H
  / heads, T / Q)``, the last axis sequential with the state ``[heads,
  V, K]`` float32 in scratch, zeroed at chunk 0. A step reads the
  chunk's ``q``, ``k``, ``v``, ``gamma`` and ``beta`` — the five arrays,
  once — and writes ``o`` and the state ENTERING the chunk (every
  chunk's: ``[B, T / Q, H, V, K]`` float32, what the backward pass
  starts each chunk from).
* :func:`chunks_backward` (``delta_chunk_bwd``): the chunks from the
  last to the first with the state's cotangent in scratch. A step reads
  the five inputs, the entering state and ``o``'s cotangent, takes
  ``jax.vjp`` of :func:`chunk_step` on them — so the chunk's ``A``,
  ``P``, ``M``, ``W``, ``U`` are made again in fast memory and never
  written — and writes the five cotangents. The inverse has a rule of
  its own there (``dA = -M^T dM M^T``), so the substitution is not
  transposed step by step, and so has every product (:func:`_dot`: a
  cotangent is a product written in its operand's own layout).

float32: decays, running sums (:func:`running_sums`), the inverse, ``[W
| U]``, ``rest`` and the state, and the products whose operand is the
state or the inverse (but ``(q e^G) S``) at precision "highest". In
``v``'s dtype with float32 accumulation: the Gram products, ``(q e^G)
S`` and ``P rest`` (for float32 values at the default precision, a
bfloat16 pass on the TPU in a kernel as in the compiler's own products).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.grouped import LANES

#: rows of ``A`` and ``P`` formed about one origin, and the edge of the
#: diagonal blocks the inverse starts from
SUB = 16
VMEM_LIMIT = 64 * 2 ** 20

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _contract(x, y, cx: int, cy: int, dt):
    if dt is not None:
        x, y = x.astype(dt), y.astype(dt)
    return jax.lax.dot_general(
        x, y, (((x.ndim + cx,), (y.ndim + cy,)),
               (tuple(range(x.ndim - 2)),) * 2),
        precision=_HIGHEST if dt is None else None,
        preferred_element_type=_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dot(x, y, cx: int = -1, cy: int = -2, dt=None):
    """float32 ``x`` by ``y`` summed over axis ``cx`` of ``x`` and ``cy``
    of ``y`` (each -1 or -2: their last two axes; any axes before them
    are batch axes of both), accumulated in float32: at precision
    "highest", or with ``dt`` both operands rounded to it for the
    product. Its two cotangents are the same kind of product of the
    other operand and the result's cotangent, written in the operand's
    own layout (JAX's rule forms the right operand's transposed and
    turns it: a pass through the transpose unit a product) and left in
    float32 (the rounding to ``dt`` is the product's, inside: a
    cotangent is rounded as an operand of the next product and not a
    second time as a result, which is what the compiler makes of the
    plain form's casts)."""
    return _contract(x, y, cx, cy, dt)


def _dot_fwd(x, y, cx, cy, dt):
    return _contract(x, y, cx, cy, dt), (x, y)


def _dot_bwd(cx, cy, dt, kept, g):
    x, y = kept
    dx = (_dot(g, y, -1, -3 - cy, dt) if cx == -1
          else _dot(y, g, -3 - cy, -1, dt))
    dy = (_dot(x, g, -3 - cx, -2, dt) if cy == -2
          else _dot(g, x, -2, -3 - cx, dt))
    return dx, dy


_dot.defvjp(_dot_fwd, _dot_bwd)
_full = _dot  # (reads better where the product is a float32 one)


def _grid(shape):
    """Row and column numbers of the last two axes of ``shape``."""
    shape = tuple(shape)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2),
            jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1))


def _inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` ``[..., Q, Q]``
    float32, ``Q`` whole blocks of :data:`SUB` (a power of two of
    them), explicit and in float32 without a solve: the diagonal blocks
    by row substitution (``SUB - 1`` steps, every block and leading
    index at once: after step ``j`` row ``j`` of a block's inverse is
    final and has been taken, ``a[i, j]`` times, off every row below
    it), then the blocks below them two at a time: ``[[L1, 0], [B,
    L2]]^-1 = [[L1^-1, 0], [-L2^-1 B L1^-1, L2^-1]]``, once for blocks
    of 16 and once for blocks of 32 in a chunk of 64."""
    size = a.shape[-1]
    blocks = []
    for at in range(0, size, SUB):
        # (the numbers made at a block's size: Mosaic fails a slice of
        # an iota)
        r, c = _grid((*a.shape[:-2], SUB, size))
        n = jnp.where(c >= at, a[..., at:at + SUB, :], 0.0)  # its own
        y = jnp.where(c == r + at, 1.0, 0.0).astype(_F32)
        for j in range(SUB - 1):
            y = y - n[..., at + j:at + j + 1] * y[..., j:j + 1, :]
        blocks.append(y)
    m = jnp.concatenate(blocks, -2) if len(blocks) > 1 else blocks[0]
    row, col = _grid(a.shape)
    edge = SUB
    while edge < size:
        # the blocks of ``edge`` just left of the diagonal, in pairs
        below = jnp.where(
            (row // (2 * edge) == col // (2 * edge))
            & (row // edge > col // edge), a, 0.0)
        m = m - _full(_full(m, below), m)
        edge *= 2
    return m


@jax.custom_vjp
def unit_lower_inverse(a):
    """:func:`_inverse` with the rule ``dA = -M^T dM M^T``."""
    return _inverse(a)


def _inverse_fwd(a):
    m = _inverse(a)
    return m, m


def _inverse_bwd(m, g):
    return (-_full(_full(m, g, -2, -2), m, -1, -1),)  # -(M^T dM) M^T


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


#: the chunk the kernels were measured at (the published one)
CHUNK = 64
#: heads a grid step, the most that divides the layer's (``PERF.md``
#: section 6, PR 50: the kernels alone at 16 heads of 128 x 128 read,
#: forward / backward, 4.95 / 8.07 ms a call at 1 head a step, 3.47 /
#: 5.85 at 2, 3.06 / 5.55 at 4 and 2.97 / 5.44 at 8: independent heads
#: fill the waits of each other's products)
HEADS_A_STEP = (4, 2, 1)


def heads_a_step(q, k, v, chunk: int) -> int | None:
    """The shape rule: how many heads a grid step the kernels take a
    call of ``q``, ``k`` ``[B, T, H, K]`` and ``v`` ``[B, T, H, V]`` in,
    or None where the plain form keeps it: a chunk other than
    :data:`CHUNK`; keys or values not whole lanes of 128; a sequence
    that is not whole chunks (one shorter than a chunk among them);
    values that are neither bfloat16 nor float32 (their dtype is the
    products'), or queries and keys that are neither float32 (as the
    decoder's normalisation leaves them) nor of the values' dtype."""
    _, t, h, dk = k.shape
    if (chunk != CHUNK or t % chunk or dk % LANES or v.shape[-1] % LANES
            or v.dtype not in (jnp.bfloat16, jnp.float32)
            or q.dtype != k.dtype or q.dtype not in (_F32, v.dtype)):
        return None
    return next(n for n in HEADS_A_STEP if h % n == 0)


def running_sums(gamma):
    """The decays' running sums down a chunk's rows, ``[Q, N]`` float32:
    a product with a triangle of ones at precision "highest" (exact
    products, float32 sums), every head of a grid step at once."""
    row, col = _grid((gamma.shape[0],) * 2)
    return _full(jnp.where(row >= col, 1.0, 0.0).astype(_F32), gamma)


def chunk_step(q, k, v, g, beta, state):
    """A grid step's heads through one chunk: ``q``, ``k`` ``[h, Q, K]``
    and ``v`` ``[h, Q, V]`` (``v``'s dtype is the products'), ``g`` ``[h, Q, K]``
    (the decays' running sums) and ``beta`` ``[h, Q, 1]`` float32,
    ``state`` ``[h, V, K]`` float32: the state entering the chunk,
    TRANSPOSED (so that a channel's decay runs along its rows and every
    product with it sums over both operands' last axis: nothing is
    turned) -> (``o`` ``[h, Q, V]`` float32, the state leaving)."""
    size, dt = q.shape[-2], v.dtype
    row, col = _grid((*q.shape[:-2], size, size))
    tokens = _grid((*q.shape[:-2], size, 1))[0]
    qf, kf = q.astype(_F32), k.astype(_F32)
    a_rows, p_rows = [], []
    for at in range(0, size, SUB):
        middle = at + (SUB - 1) // 2
        origin = g[..., middle:middle + 1, :]
        left = jnp.exp(g[..., at:at + SUB, :] - origin)
        right = kf * jnp.exp(jnp.where(
            tokens < at + SUB, origin - g, -jnp.inf))
        both = jnp.concatenate([kf[..., at:at + SUB, :] * left,
                                qf[..., at:at + SUB, :] * left], -2)
        about = _dot(both, right, -1, -1, dt)  # [h, 2 SUB, Q]
        a_rows.append(about[..., :SUB, :])
        p_rows.append(about[..., SUB:, :])
    stack = lambda parts: (
        jnp.concatenate(parts, -2) if len(parts) > 1 else parts[0])
    a = jnp.where(row > col, stack(a_rows), 0.0) * beta
    p = jnp.where(row >= col, stack(p_rows), 0.0)
    # [W | U] = (I + A)^-1 Diag(beta) [k exp(G) | v]
    from_start = jnp.exp(g)
    w_u = _full(unit_lower_inverse(a), beta * jnp.concatenate(
        [kf * from_start, v.astype(_F32)], -1))
    w, u = w_u[..., :k.shape[-1]], w_u[..., k.shape[-1]:]
    rest = u - _full(w, state, -1, -1)
    o = _dot(qf * from_start, state, -1, -1, dt) + _dot(p, rest, -1, -2, dt)
    whole = g[..., size - 1:size, :]  # every channel's whole decay
    to_end = kf * jnp.exp(whole - g)
    return o, jnp.exp(whole) * state + _full(rest, to_end, -2, -2)


def _by_head(ref, heads: int):
    """A block ``[Q, heads x N]`` -> ``[heads, Q, N]``."""
    width = ref.shape[-1] // heads
    value = ref[...]
    return jnp.stack([value[:, h * width:(h + 1) * width]
                      for h in range(heads)])


def _side_by_side(a):
    """``[heads, Q, N]`` -> ``[Q, heads x N]``."""
    return jnp.concatenate(list(a), -1) if a.shape[0] > 1 else a[0]


def _forward_kernel(heads, q_ref, k_ref, v_ref, gamma_ref, beta_ref,
                    o_ref, entering_ref, state_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    entering_ref[...] = state
    by_head = functools.partial(_by_head, heads=heads)
    o, state_ref[...] = chunk_step(
        by_head(q_ref), by_head(k_ref), by_head(v_ref),
        by_head(running_sums(gamma_ref[...])), by_head(beta_ref), state)
    o_ref[...] = _side_by_side(o).astype(o_ref.dtype)


def _by_heads(beta, heads: int):
    """``[B, T, H]`` -> ``[B, H / heads, T, heads]``: a grid step's
    heads side by side, a token a row."""
    b, t, h = beta.shape
    return jnp.moveaxis(beta.reshape(b, t, h // heads, heads), 2, 1)


def _flat(a):
    return a.reshape(*a.shape[:2], -1)


def _rows(chunk: int, width: int, reverse: int | None = None):
    """The block of a chunk's tokens and a grid step's heads of a ``[B,
    T, H x width]`` array; with ``reverse`` (the chunk count) the chunks
    from the last to the first."""
    at = (lambda c: c) if reverse is None else (lambda c: reverse - 1 - c)
    return pl.BlockSpec((None, chunk, width), lambda b, g, c: (b, at(c), g))


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "interpret"))
def chunks_forward(q, k, v, gamma, beta, *, chunk: int, heads: int = 1,
                   interpret: bool = False):
    """``q``, ``k`` ``[B, T, H, K]`` (float32 or ``v``'s dtype), ``v``
    ``[B, T, H, V]`` (its dtype is the products'), ``gamma`` ``[B, T, H,
    K]`` and ``beta`` ``[B, T, H]`` float32 -> (``o`` ``[B, T, H, V]`` in
    ``v``'s dtype, the states entering the ``T / chunk`` chunks,
    transposed: ``[B, T / chunk, H, V, K]`` float32), ``heads`` heads a
    grid step. ``T`` whole chunks, ``H`` whole steps, ``K`` and ``V``
    whole lanes."""
    bsz, t, h, dk = k.shape
    dv, nc = v.shape[-1], t // chunk
    keys, values = _rows(chunk, heads * dk), _rows(chunk, heads * dv)
    o, entering = pl.pallas_call(
        functools.partial(_forward_kernel, heads),
        out_shape=(jax.ShapeDtypeStruct((bsz, t, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, h, dv, dk), _F32)),
        grid=(bsz, h // heads, nc),
        in_specs=[keys, keys, values, keys, pl.BlockSpec(
            (None, None, chunk, heads), lambda b, g, c: (b, g, c, 0))],
        out_specs=(values, pl.BlockSpec(
            (None, None, heads, dv, dk), lambda b, g, c: (b, c, g, 0, 0))),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="delta_chunk_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(gamma.astype(_F32)),
      _by_heads(beta.astype(_F32), heads))
    return o.reshape(v.shape), entering


def _backward_kernel(heads, q_ref, k_ref, v_ref, gamma_ref, beta_ref,
                     entering_ref, do_ref, dq_ref, dk_ref, dv_ref,
                     dgamma_ref, dbeta_ref, later_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        later_ref[...] = jnp.zeros_like(later_ref)

    by_head = functools.partial(_by_head, heads=heads)
    sums, back_to_gamma = jax.vjp(running_sums, gamma_ref[...])
    _, back = jax.vjp(
        chunk_step, by_head(q_ref), by_head(k_ref), by_head(v_ref),
        by_head(sums), by_head(beta_ref), entering_ref[...])
    dq, dk, dv, dg, dbeta, later_ref[...] = back(
        (by_head(do_ref).astype(_F32), later_ref[...]))
    dq_ref[...] = _side_by_side(dq)
    dk_ref[...] = _side_by_side(dk)
    dv_ref[...] = _side_by_side(dv)
    dgamma_ref[...], = back_to_gamma(_side_by_side(dg))
    dbeta_ref[...] = _side_by_side(dbeta)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "interpret"))
def chunks_backward(q, k, v, gamma, beta, entering, do, *, chunk: int,
                    heads: int = 1, interpret: bool = False):
    """The cotangents of :func:`chunks_forward`'s five inputs under
    ``do``, ``o``'s (``[B, T, H, V]``), from the states ``entering`` its
    chunks; each in its input's shape and dtype."""
    bsz, t, h, dk = k.shape
    dv, nc = v.shape[-1], t // chunk
    keys, values = _rows(chunk, heads * dk, nc), _rows(chunk, heads * dv, nc)
    writes = pl.BlockSpec(
        (None, None, chunk, heads), lambda b, g, c: (b, g, nc - 1 - c, 0))
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(
        a.shape, dtype or a.dtype)
    by_heads = _by_heads(beta.astype(_F32), heads)
    dq, dk_, dv_, dgamma, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, heads),
        out_shape=(like(_flat(q)), like(_flat(k)), like(_flat(v)),
                   like(_flat(gamma), _F32), like(by_heads)),
        grid=(bsz, h // heads, nc),
        in_specs=[keys, keys, values, keys, writes, pl.BlockSpec(
            (None, None, heads, dv, dk),
            lambda b, g, c: (b, nc - 1 - c, g, 0, 0)), values],
        out_specs=(keys, keys, values, keys, writes),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="delta_chunk_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(gamma.astype(_F32)), by_heads,
      entering, _flat(do))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dgamma.reshape(gamma.shape).astype(gamma.dtype),
            jnp.moveaxis(dbeta, 1, 2).reshape(beta.shape).astype(beta.dtype))
