"""Causal attention with an optional sliding window and grouped-query
heads: the attention the configured decoder stack
(:mod:`fedml_tpu.models.decoder`) calls.

``q`` is ``[B, T, H, D]``; ``k`` and ``v`` are ``[B, T, Hkv, D]`` with
``H`` a multiple of ``Hkv``: query head ``j`` reads key-value head
``j // (H / Hkv)``. Query ``i`` sees keys ``j <= i`` and, with
``window=w``, only ``j > i - w`` (``w`` keys, itself included).

On the TPU this is JAX's bundled splash attention (a Pallas kernel:
blockwise, online softmax, forward and backward, no ``[T, T]`` score
tensor in HBM) built over a causal or local mask, so a sliding layer
never visits the key blocks outside its window. One kernel a
key-value head serves that head's ``H / Hkv`` query heads (the kernel's
multi-query form), mapped over the batch and the key-value heads. Off
the TPU the same function is the masked product written out
(:func:`masked_attention`): what the CPU tests run and what the kernel
is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query / key block edge of the kernel (tokens); a sequence shorter
#: than a block is one block
BLOCK = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_mask(t: int, window: int | None) -> np.ndarray:
    """``[T, T]`` bool: may query ``i`` (row) read key ``j`` (column)."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    return seen


def masked_attention(q, k, v, window: int | None = None) -> jax.Array:
    """The arithmetic itself: scores ``q k^T / sqrt(D)``, the mask, a
    float32 softmax, the mix. Holds ``[B, H, T, T]`` scores."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("bqgnd,bkgd->bgnqk", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = jnp.where(attention_mask(t, window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bgnqk,bkgd->bqgnd", p.astype(v.dtype), v)
    return a.reshape(b, t, h, d).astype(q.dtype)


@functools.lru_cache(maxsize=16)
def _splash_kernel(t: int, group: int, window: int | None,
                   interpret: bool = False):
    """One multi-query splash kernel: ``group`` query heads over one
    key-value head at sequence length ``t``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    one = (sm.CausalMask((t, t)) if window is None
           else sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    blk = min(BLOCK, t)
    sizes = sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk,
    )
    # the kernel object keeps its mask tables as arrays: made concrete
    # here, so that one traced program's tracers never reach the next
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * group), block_sizes=sizes,
            interpret=interpret)


def splash_attention(q, k, v, window: int | None = None,
                     interpret: bool = False) -> jax.Array:
    """The TPU kernel behind the same contract as
    :func:`masked_attention` (``interpret``: the Pallas interpreter, for
    the CPU tests)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    kernel = _splash_kernel(t, h // hkv, window, interpret)
    # the kernel does not scale its scores
    qg = (q * (d ** -0.5)).astype(q.dtype).reshape(b, t, hkv, h // hkv, d)
    qg = qg.transpose(0, 2, 3, 1, 4)  # [B, Hkv, group, T, D]
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    a = jax.vmap(jax.vmap(kernel))(qg, kg, vg)  # [B, Hkv, group, T, D]
    return a.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)


def causal_attention(q, k, v, causal: bool = True,
                     window: int | None = None) -> jax.Array:
    """``[B, T, H, D]`` causal (optionally windowed) grouped-query
    attention behind the ``AttnFn`` contract of
    :mod:`fedml_tpu.models.transformer`; the kernel on the TPU, the
    masked product elsewhere."""
    if not causal:
        raise ValueError("causal_attention is causal; use full_attention")
    with jax.named_scope("fedml.model.attn.kernel"):
        if _on_tpu():
            return splash_attention(q, k, v, window)
        return masked_attention(q, k, v, window)
