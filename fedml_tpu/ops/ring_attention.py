"""Ring attention: exact long-context attention over a sequence-parallel
mesh axis.

The reference has no attention models at all (SURVEY.md §5.7) — this is a
capability the TPU build adds as first-class: sequences are sharded over a
mesh axis; each device keeps its Q shard resident while K/V shards rotate
around the ring via ``ppermute`` (ICI neighbor exchange), accumulating with
an online-softmax (flash-attention style, Liu et al. "Ring Attention with
Blockwise Transformers"). Communication overlaps compute: each of the
``p`` steps moves one K/V block while the MXU contracts the previous one.

Usage (inside ``shard_map`` over the sequence axis)::

    out = ring_attention(q, k, v, axis_name="sp", causal=True)

``q, k, v``: [B, T_local, H, D] shards; returns [B, T_local, H, D].
Numerics: accumulation in float32 regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, bias):
    """One (Q-block, K-block) attention contribution.

    Returns (o_unnorm [B,Tq,H,D] f32, row_max [B,H,Tq] f32,
    row_sum [B,H,Tq] f32) for online-softmax merging.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    # a fully-masked row has m = -inf; exp(-inf - -inf) would be NaN, and
    # a NaN in the UNSELECTED where-branch still poisons gradients, so
    # sanitize m before subtracting (double-where trick)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(
        jnp.isfinite(m)[..., None], jnp.exp(s - m_safe[..., None]), 0.0
    )
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o, m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Exact attention with sequence shards rotating K/V around the ring.

    Must run inside ``shard_map``/``pjit`` with ``axis_name`` a mesh axis
    of size p; T_global = p * T_local.
    """
    p = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape

    q32 = q.astype(jnp.float32)
    perm = [(i, (i + 1) % p) for i in range(p)]

    # positions for causal masking
    q_pos = my * t_local + jnp.arange(t_local)  # [Tq]

    def body(step, carry):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # origin of the K/V block currently held: it has been forwarded
        # `step` times along the +1 ring, so it started at (my - step) % p
        origin = (my - step) % p
        if causal:
            k_pos = origin * t_local + jnp.arange(t_local)  # [Tk]
            mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
            bias = jnp.where(mask, 0.0, -jnp.inf)[None, None]
        else:
            bias = None
        o_b, m_b, l_b = _block_attn(q32, k_cur, v_cur, bias)

        # online-softmax merge (flash-attention rescaling). All operands
        # are sanitized BEFORE subtraction: -inf - -inf = NaN inside an
        # unselected where-branch would still poison the backward pass.
        new_m = jnp.maximum(m_acc, m_b)
        new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        fin_acc = jnp.isfinite(m_acc)
        fin_b = jnp.isfinite(m_b)
        alpha = jnp.where(
            fin_acc,
            jnp.exp(jnp.where(fin_acc, m_acc, 0.0) - new_m_safe),
            0.0,
        )
        beta = jnp.where(
            fin_b, jnp.exp(jnp.where(fin_b, m_b, 0.0) - new_m_safe), 0.0
        )
        l_new = l_acc * alpha + l_b * beta
        o_new = (
            o_acc * alpha.transpose(0, 2, 1)[..., None]
            + o_b * beta.transpose(0, 2, 1)[..., None]
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o_new, new_m, l_new, k_nxt, v_nxt

    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    m0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    # newer shard_map tracks varying-manual-axes: literal-initialized
    # carries must be marked as varying over the ring axis or the loop
    # carry types mismatch. jax.lax.pcast(to='varying') is the current
    # spelling; fall back to the deprecated pvary on older jax.
    pcast = getattr(jax.lax, "pcast", None)
    if pcast is not None:
        mark = lambda a: pcast(a, (axis_name,), to="varying")
    else:
        pvary = getattr(jax.lax, "pvary", None)
        mark = (lambda a: pvary(a, (axis_name,))) if pvary else (lambda a: a)
    o0, m0, l0 = (mark(a) for a in (o0, m0, l0))
    o, m, l, _, _ = jax.lax.fori_loop(
        0, p, body, (o0, m0, l0, k.astype(jnp.float32), v.astype(jnp.float32))
    )
    # rows with no visible keys (can't happen for causal with step 0
    # including self, but guard anyway)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def full_attention(q, k, v, causal: bool = False) -> jax.Array:
    """Single-device reference: plain softmax attention (the oracle for
    ring/flash tests)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v
    ).astype(q.dtype)


def make_sequence_parallel_attention(mesh, axis_name: str, causal: bool):
    """Wrap :func:`ring_attention` in a ``shard_map`` over ``axis_name``:
    takes/returns GLOBAL [B, T, H, D] arrays sharded on T."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(
        ring_attention, axis_name=axis_name, causal=causal
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
