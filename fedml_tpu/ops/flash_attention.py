"""Pallas flash attention: the single-chip hot-path attention kernel.

Blockwise attention with online softmax, tiled for VMEM: the grid walks
(batch*heads, Q blocks); each program streams K/V blocks of the full
sequence through VMEM scratch, keeping the running (max, sum, output)
statistics in registers/VMEM — HBM traffic is O(T) per Q block instead of
materializing the [T, T] score matrix.

The kernel is compiled for the TPU; off the TPU a caller asks for the
pallas interpreter itself (``interpret=True``, as the CPU tests do) or
uses :func:`fedml_tpu.ops.ring_attention.full_attention`.

This kernel has no backward pass, no window and no grouped-query heads,
and no model calls it: ``chip_smoke.py`` keeps it as the bring-up check
that a Pallas kernel of this repo compiles and runs on the chip. The
attention the decoder stack trains through is
:func:`fedml_tpu.ops.attention.causal_attention` (JAX's bundled splash
attention on the TPU). What the chip read for that one (PR 27, TPU v5
lite, 2 sequences of 2,048, 8 key-value heads of 128, bfloat16, forward
+ backward, wall clock over 10 calls): 64 query heads with a 512-window
5.48 ms in blocks of 512 (7.69 at 256, 7.42 at 1,024) against 22.6 ms
for the masked product XLA fuses (:func:`fedml_tpu.ops.attention.
masked_attention`); 48 heads causal 5.57 ms (5.37 at 1,024) against
17.5 ms. An earlier note here (2026-07) had XLA's fused attention 6-11x
faster than THIS kernel at T 2048-8192; it was not re-measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                  causal: bool, q_block: int):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)  # [Bq, D]
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    t_total = k_ref.shape[0]
    n_kb = t_total // block_k

    m0 = jnp.full((q.shape[0],), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)

    q_pos = qi * q_block + jax.lax.iota(jnp.int32, q.shape[0])

    def body(kb, carry):
        o_acc, m_acc, l_acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Bq, Bk]
        if causal:
            k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, -jnp.inf)
        m_b = jnp.max(s, axis=-1)
        p = jnp.where(
            jnp.isfinite(m_b)[:, None], jnp.exp(s - m_b[:, None]), 0.0
        )
        l_b = jnp.sum(p, axis=-1)
        o_b = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        new_m = jnp.maximum(m_acc, m_b)
        alpha = jnp.where(
            jnp.isfinite(m_acc), jnp.exp(m_acc - new_m), 0.0
        )
        beta = jnp.where(jnp.isfinite(m_b), jnp.exp(m_b - new_m), 0.0)
        return (
            o_acc * alpha[:, None] + o_b * beta[:, None],
            new_m,
            l_acc * alpha + l_b * beta,
        )

    if causal:
        # skip K blocks strictly after this Q block
        n_run = jnp.minimum(
            (qi + 1) * q_block // block_k + 1, n_kb
        )
    else:
        n_run = n_kb
    o, m, l = jax.lax.fori_loop(0, n_run, body, (o0, m0, l0))
    o_ref[...] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """[B, T, H, D] attention via the pallas kernel, compiled for the
    TPU. ``interpret=True`` runs the kernel in the pallas interpreter
    (the CPU tests pass it themselves)."""
    b, t, h, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)

    # fold batch and heads into the grid's first axis; kernel sees [T, D]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, q_block=block_q
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
