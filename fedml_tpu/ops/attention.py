"""Causal attention with an optional sliding window, or over a set of
keys chosen a query by a learned index, and grouped-query heads: the
attention the configured decoder stack
(:mod:`fedml_tpu.models.decoder`) calls.

``q`` is ``[B, T, H, D]``; ``k`` is ``[B, T, Hkv, D]`` and ``v``
``[B, T, Hkv, Dv]`` with ``H`` a multiple of ``Hkv``: query head ``j``
reads key-value head ``j // (H / Hkv)``. Query ``i`` sees keys ``j <=
i`` and, with ``window=w``, only ``j > i - w`` (``w`` keys, itself
included). The values' head size ``Dv`` need not be the keys' ``D`` (a
latent-attention layer has keys of 192 beside values of 128): scores
are scaled by ``1 / sqrt(D)``, the keys' size, and the output is ``[B,
T, H, Dv]``. The kernel takes ``D = 192`` as it is, one and a half
lane tiles: on a v5e one call of 8,192 tokens x 32 heads, forward +
backward under the layer's checkpoint policy in bfloat16, reads 29.24
ms (9.10 forward) against 29.97 (9.55) with queries and keys
zero-padded to 256, which gives the same output and gradients to the
bit (PERF.md section 6, PR 42) — so nothing is padded. Heads of 64,
half a lane tile (32 query heads over 8 key-value heads, the same
tokens), run as they are too: 17.43 ms forward + backward (6.08
forward) against 17.45 (5.79) with queries, keys and values zero-padded
to 128 and the output cut back, and 17.46 (5.76) with queries and keys
alone padded, output and every gradient the same to the bit; scores are
scaled by ``1 / 8`` either way. The padded forward call is 0.3 ms
shorter and the padded backward as much longer, so a training step
gains nothing and the pads and the cut would be three more passes: ONE
launch shape, the head size as it comes (PERF.md section 6, PR 46).

On the TPU this is JAX's bundled splash attention (a Pallas kernel:
blockwise, online softmax, forward and backward, no ``[T, T]`` score
tensor in HBM) built over a causal or local mask, so a sliding layer
never visits the key blocks outside its window. One kernel a
key-value head serves that head's ``H / Hkv`` query heads (the kernel's
multi-query form), mapped over the batch and the key-value heads. Off
the TPU the same function is the masked product written out
(:func:`masked_attention`): what the CPU tests run and what the kernel
is tested against.

Learned sparse attention (a mask that is DATA) is three functions. With
``t`` a query position and ``s <= t`` a key position:

- :func:`index_scores`: ``I[t, s] = (J E)^-1/2 sum_j w[t, j]
  relu(qI[t, j] . kI[s])`` from ``J`` index heads of ``E`` dimensions
  over ONE index key head; float32, a block at a time (on the TPU a
  Pallas kernel over the causal tiles), so that ``[J, T, T]`` never
  exists;
- :func:`select_top_k`: ``S[t]``, the ``min(t + 1, k)`` positions ``s <=
  t`` of largest ``I[t, s]``, ties to the lower ``s`` — exact (the
  ``k``-th largest score of a row is found bit by bit on the scores'
  order-preserving integer image, no sort and no approximation; on the
  TPU a Pallas kernel that keeps a block of rows in fast memory through
  its passes) and one set a query, shared by every head;
- :func:`selected_attention`: softmax attention over ``S[t]`` alone. On
  the TPU the same splash kernel over the selection as a DYNAMIC mask
  (every block's mask bits are an operand; a block in which no query
  keeps a key is never visited), forward and backward; elsewhere the
  masked product. The selection is discrete: no gradient reaches the
  index through it.

**What a rematerialised layer keeps for its backward pass.** Three
values carry a ``jax.ad_checkpoint.checkpoint_name`` of :data:`KEPT`,
and a ``jax.checkpoint`` (``nn.remat``) whose policy is
``save_only_these_names(*KEPT)`` —
:class:`fedml_tpu.models.decoder.DecoderLM`'s — keeps them where it
would run their producers a second time: the attention's output and,
from the blockwise kernel, its row log-sum-exp (all the kernel's
backward pass needs of its forward: ``[B, T, H, D]`` and ``[B, H, T]``
float32) under :data:`KEPT_OUTPUT`, and the selection, WHOLE, under
:data:`KEPT_SELECTION` (named by the layer that hands it to
:func:`selected_attention`: ``[B, T, T]`` bool, a byte a pair, so its
bytes grow with ``T`` squared — 67 MB a layer at 8,192 tokens, 1.07 GB
at 32,768). So the forward kernel, the index and the top-k run once a
training step. The names do nothing anywhere else: without a gradient,
or under no such policy.

**The kernel's backward pass walks its score blocks once**
(:func:`_block_sizes`). The library's backward pass is two kernels that
each walk every visited (query block, key block) pair — a dq kernel
(scores, mask, exponentials, dP, then dQ: three block products) and a
dk/dv kernel (the same again, then dV and dK: four) — or, as here, ONE
(``use_fused_bwd_kernel``): the dk/dv kernel also forms ``dS K`` and
writes it as one partial of dQ a key block (``dq_unreduced``, ``[T /
block_kv_dkv, heads, T, D]`` in the compute dtype), five products a
pair and one softmax recomputation. One attention call alone on a v5e
(forward + backward, bfloat16, the cells' shapes; PERF.md section 6,
PR 38) reads 31.30 -> 23.97 ms over a selection of 8,192 tokens x 32
heads, 6.65 -> 5.52 causal and 6.58 -> 6.22 under a window of 512 at 2
x 2,048 x 48 / 64 heads, 2.34 -> 1.84 causal at 8,192 x 4: the kernel
is 7-21 % slower for the fifth product (68 % under a window, whose
grid no longer shrinks to the pairs the mask leaves: every pair is
stepped over, its partial written as zeros) and the dq pass, 75-88 %
of it, goes — every use wins, so there is one launch shape and no
choice. What the partials cost: ``T / block_kv_dkv`` times dQ's own
bytes, written by the kernel and read back by the sum — at key blocks
of 1,024 tokens 8 x 67 MB = 537 MB a layer call at 8,192 tokens x 32
heads (1.07 GB at blocks of 512; blocks of 2,048 do not fit the
dynamic mask's fast memory), transient, and growing with ``T``
squared: 8.6 GB at 32,768 tokens, where this form cannot stand.
Inside a key block dQ's terms add up across the compute blocks in a
float32 scratch; the partials are rounded to the compute dtype once
each and their sum (the library's ``dq_unreduced.sum(axis=0)``)
accumulates in float32 — ``jnp.sum`` widens a bfloat16 operand for the
reduction — and is rounded once: the parent's arithmetic in another
order of summation (dk and dv to the bit; dq 1.3e-4 to 3.1e-4 from the
parent's, as far from float32 as the parent's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

#: query / key block edge of the kernel (tokens); a sequence shorter
#: than a block is one block
BLOCK = 512

#: ``checkpoint_name``s (module docstring): the attention's output (with
#: the kernel's log-sum-exp), and a sparse-attention layer's selection
KEPT_OUTPUT, KEPT_SELECTION = "fedml_attn_output", "fedml_attn_selection"
KEPT = (KEPT_OUTPUT, KEPT_SELECTION)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_mask(t: int, window: int | None) -> np.ndarray:
    """``[T, T]`` bool: may query ``i`` (row) read key ``j`` (column)."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    return seen


def masked_attention(q, k, v, window: int | None = None,
                     selection=None) -> jax.Array:
    """The arithmetic itself: scores ``q k^T / sqrt(D)``, the mask (the
    causal or window one, or ``selection`` ``[B, T, T]`` bool: the keys
    each query reads), a float32 softmax, the mix ``[B, T, H, Dv]``.
    Holds ``[B, H, T, T]`` scores. The output is named
    :data:`KEPT_OUTPUT`, as the kernel's is."""
    b, t, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    qg = q.reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("bqgnd,bkgd->bgnqk", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    seen = (attention_mask(t, window) if selection is None
            else selection[:, None, None])
    s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bgnqk,bkgd->bqgnd", p.astype(v.dtype), v)
    return checkpoint_name(
        a.reshape(b, t, h, dv).astype(q.dtype), KEPT_OUTPUT)


def _block_sizes(t: int):
    """The kernel's launch shape at ``t`` tokens, one for every use
    (module docstring): the forward pass in blocks of :data:`BLOCK`;
    the backward pass ONE kernel (``use_fused_bwd_kernel``; the library
    refuses dq edges beside it) over query blocks of :data:`BLOCK` and
    key blocks of two — ``t / (2 BLOCK)`` partials of dQ — in compute
    blocks of :data:`BLOCK`; one block of each where ``t`` is not whole
    double blocks."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    blk = min(BLOCK, t)
    return sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk if t % (2 * blk) else 2 * blk,
        block_kv_dkv_compute=blk, use_fused_bwd_kernel=True,
    )


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
    sizes = _block_sizes(t)
    # the kernel object keeps its mask tables as arrays: made concrete
    # here, so that one traced program's tracers never reach the next
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * group), block_sizes=sizes,
            residual_checkpoint_name=KEPT_OUTPUT, interpret=interpret)


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
    a = jax.vmap(jax.vmap(kernel))(qg, kg, vg)  # [B, Hkv, group, T, Dv]
    return a.transpose(0, 3, 1, 2, 4).reshape(b, t, h, v.shape[-1])


def causal_attention(q, k, v, causal: bool = True,
                     window: int | None = None, selection=None) -> jax.Array:
    """``[B, T, H, Dv]`` causal grouped-query attention — within
    ``window`` keys, or over ``selection`` (``[B, T, T]`` bool from
    :func:`select_top_k`: :func:`selected_attention`) — behind the
    ``AttnFn`` contract of :mod:`fedml_tpu.models.transformer`; the
    kernel on the TPU, the masked product elsewhere."""
    if not causal:
        raise ValueError("causal_attention is causal; use full_attention")
    if selection is not None:
        return selected_attention(q, k, v, selection)
    with jax.named_scope("fedml.model.attn.kernel"):
        if _on_tpu():
            return splash_attention(q, k, v, window)
        return masked_attention(q, k, v, window)


# ---------------------------------------------------------------------------
# learned sparse attention: index, selection, attention over the selection
# ---------------------------------------------------------------------------

#: what a stack with sparse-attention layers counts, in this order: keys
#: selected (sum over layers and queries of ``|S[t]|``) and keys a dense
#: causal layer would read (sum of ``t + 1``)
ATTN_COUNTERS = ("attn_keys_selected", "attn_keys_causal")

_INT_MIN = np.iinfo(np.int32).min


def index_scores(qi, ki, w) -> jax.Array:
    """``qi`` ``[B, T, J, E]`` index queries, ``ki`` ``[B, T, E]`` the
    one index key head, ``w`` ``[B, T, J]`` head weights -> ``I`` ``[B,
    T, T]`` float32 (module docstring). Products take their inputs as
    they come (the step's compute dtype) and accumulate in float32. On
    the TPU a kernel that scores the blocks a causal query can read and
    leaves ``I[t, s]`` for ``s`` in a later block than ``t`` UNWRITTEN
    (:func:`select_top_k` never reads ``s > t``); elsewhere every pair,
    a block of :data:`BLOCK` queries at a time. The scores exist to be
    ranked: they are cut off from every gradient."""
    qi, ki, w = jax.lax.stop_gradient((qi, ki, w))
    if _whole_blocks(qi.shape[1], BLOCK, "index_scores") and _on_tpu():
        return index_scores_kernel(qi, ki, w)
    return index_scores_blocks(qi, ki, w)


def _whole_blocks(t: int, edge: int, what: str) -> bool:
    """Whether ``t`` tokens are whole blocks of ``edge``, which is what
    ``what``'s kernel tiles. A sequence of less than one block is small
    enough for the written-out form; a longer one that is not whole
    blocks is refused, since the written-out form would be several
    times slower on the chip without a word."""
    if t > edge and t % edge:
        raise ValueError(
            f"{what}: {t} tokens are not whole blocks of {edge}; pad the "
            "sequence")
    return t % edge == 0


def index_scores_blocks(qi, ki, w) -> jax.Array:
    """:func:`index_scores` written out."""
    b, t, j, e = qi.shape
    blk = min(BLOCK, t)

    def block(args):
        q_blk, w_blk = args  # [B, blk, J, E], [B, blk, J]
        s = jnp.einsum("bqje,bke->bqjk", q_blk, ki,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(s),
                          w_blk.astype(jnp.float32))

    split = lambda x: jnp.moveaxis(
        x.reshape(b, t // blk, blk, *x.shape[2:]), 1, 0)
    scores = jax.lax.map(block, (split(qi), split(w)))  # [T/blk, B, blk, T]
    return jnp.moveaxis(scores, 0, 1).reshape(b, t, t) * (j * e) ** -0.5


def index_scores_kernel(qi, ki, w, interpret: bool = False) -> jax.Array:
    """:func:`index_scores`' TPU kernel: one ``[block, block]`` tile of
    scores a grid step — ``J`` products ``[block, E] x [E, block]``,
    each through a relu and its head's weight into a float32
    accumulator — over the tiles at or under the diagonal; a tile above
    it is neither computed nor written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, j, e = qi.shape
    blk = min(BLOCK, t)
    n = t // blk

    def kernel(q_ref, k_ref, w_ref, o_ref):
        @pl.when(pl.program_id(2) <= pl.program_id(1))
        def _():
            keys, weights = k_ref[...], w_ref[...]
            acc = jnp.zeros((blk, blk), jnp.float32)
            for head in range(j):
                dots = jax.lax.dot_general(
                    q_ref[head], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc += weights[:, head:head + 1] * jnp.maximum(dots, 0.0)
            o_ref[...] = acc

    under = lambda qb, kb: jnp.minimum(qb, kb)  # a tile above: the diagonal's
    return pl.pallas_call(
        kernel,
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((None, j, blk, e), lambda i, qb, kb: (i, 0, qb, 0)),
            pl.BlockSpec((None, blk, e),
                         lambda i, qb, kb: (i, under(qb, kb), 0)),
            pl.BlockSpec((None, blk, j), lambda i, qb, kb: (i, qb, 0)),
        ],
        out_specs=pl.BlockSpec((None, blk, blk),
                               lambda i, qb, kb: (i, qb, under(qb, kb))),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # float32 inputs (the evaluator's) take 19.4 MB of it
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="sparse_index_scores",
    )(qi.transpose(0, 2, 1, 3), ki,
      w.astype(jnp.float32) * (j * e) ** -0.5)


def _ordered(scores):
    """float32 -> int32 with the same order (``-0.0`` as ``0.0``)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    bits = jnp.where(scores == 0, 0, bits)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


#: rows of scores one step of the selection kernel ranks
SELECT_ROWS = 128


def select_top_k(scores, k: int) -> jax.Array:
    """``scores`` ``[B, T, T]`` -> ``[B, T, T]`` bool: for query ``t``
    the ``min(t + 1, k)`` keys ``s <= t`` of largest score, ties to the
    lower ``s``; ``scores[t, s]`` for ``s > t`` is never read. Exact: a
    row's ``k``-th largest score is built bit by bit on the scores'
    order-preserving integer image (32 counting passes over the row),
    then the position of the last of the equal scores it may keep the
    same way. On the TPU a kernel that holds :data:`SELECT_ROWS` rows in
    fast memory through all their passes; elsewhere the same passes over
    the whole array.

    The sparse layers' router (``ops/moe.py:largest``) breaks its ties
    the same way, to the lower index, and shares none of these passes:
    it brings 6 to 22 of 64 to 512 values OUT, in order, which ``k``
    rounds of taking the largest do in ``2 k`` reductions; counting
    takes 41 here to mark a set in place, whatever ``k`` (on the chip
    the counting form ranked 22 of 512 in 0.81 ms, the rounds in 0.30:
    ``PERF.md`` section 6, PR 44)."""
    if k < 1:
        raise ValueError(f"select_top_k: k = {k}")
    b, t, _ = scores.shape
    if k >= t:  # every causal key is kept: nothing to rank
        return jnp.broadcast_to(attention_mask(t, None), (b, t, t))
    if _on_tpu() and _whole_blocks(t, SELECT_ROWS, "select_top_k"):
        return select_top_k_kernel(scores, k)
    return select_top_k_passes(scores, k)


def _kept(key, rows, cols, k: int):
    """The keys kept of ``key`` (``[..., R, T]`` rows of
    order-preserving int32 scores, ``_INT_MIN`` where ``s > t``);
    ``rows`` ``[R, 1]`` and ``cols`` their positions."""
    count = lambda m: jnp.sum(m.astype(jnp.int32), -1, keepdims=True)
    keep = jnp.minimum(rows + 1, k)
    zero = jnp.zeros(key.shape[:-1] + (1,), jnp.int32)

    def raise_bit(i, kth):  # bits 30 .. 0, after the sign
        above = kth | jnp.left_shift(np.int32(1), 30 - i)
        return jnp.where(count(key >= above) >= keep, above, kth)

    kth = jax.lax.fori_loop(0, 31, raise_bit, jnp.where(
        count(key >= zero) >= keep, zero, _INT_MIN))
    over, level = key > kth, key == kth
    owed = keep - count(over)  # of the keys level with the k-th
    bits = max(1, int(key.shape[-1] - 1).bit_length())

    def raise_position(i, last):
        later = last | jnp.left_shift(np.int32(1), bits - 1 - i)
        return jnp.where(count(level & (cols < later)) < owed, later, last)

    last = jax.lax.fori_loop(0, bits, raise_position, zero)
    return over | (level & (cols <= last))


def select_top_k_passes(scores, k: int) -> jax.Array:
    """:func:`select_top_k` written out."""
    b, t, _ = scores.shape
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    key = jnp.where(cols <= rows, _ordered(scores), _INT_MIN)
    return _kept(key, rows, cols, k)


def select_top_k_kernel(scores, k: int, interpret: bool = False) -> jax.Array:
    """:func:`select_top_k`'s TPU kernel: :data:`SELECT_ROWS` rows of
    scores a grid step, ranked where they lie; a block of rows that all
    keep every causal key (``t < k``) is not ranked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = scores.shape
    r = SELECT_ROWS

    def kernel(s_ref, o_ref):
        first = pl.program_id(1) * r
        rows = first + jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (r, t), 1)
        causal = cols <= rows

        @pl.when(first + r <= k)
        def _():
            o_ref[...] = causal.astype(jnp.int8)

        @pl.when(first + r > k)
        def _():
            key = jnp.where(causal, _ordered(s_ref[...]), _INT_MIN)
            o_ref[...] = _kept(key, rows, cols, k).astype(jnp.int8)

    rows_of = pl.BlockSpec((None, r, t), lambda i, block: (i, block, 0))
    return pl.pallas_call(
        kernel, grid=(b, t // r), in_specs=[rows_of], out_specs=rows_of,
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=100 * 2 ** 20),
        interpret=interpret, name="sparse_select_top_k",
    )(scores.astype(jnp.float32)) != 0


def selected_splash(q, k, v, selection, interpret: bool = False) -> jax.Array:
    """:func:`selected_attention`'s TPU kernel: splash attention with
    ``selection`` as a dynamic mask, one kernel a query head (the mask's
    blocks are read a head; keys and values too), a sequence at a
    time."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    b, t, h, d = q.shape
    hkv = k.shape[2]
    qg = (q * (d ** -0.5)).astype(q.dtype).reshape(b, t, hkv, h // hkv, 1, d)
    qg = qg.transpose(0, 2, 3, 4, 1, 5)  # [B, Hkv, group, 1, T, D]
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    out = []
    for i in range(b):
        kernel = sk.make_splash_mqa_single_device(
            selection[i][None], block_sizes=_block_sizes(t),
            residual_checkpoint_name=KEPT_OUTPUT, interpret=interpret)
        heads = jax.vmap(jax.vmap(kernel, in_axes=(0, None, None)))
        out.append(heads(qg[i], kg[i], vg[i]))  # [Hkv, group, 1, T, D]
    a = jnp.stack(out)[:, :, :, 0]
    return a.transpose(0, 3, 1, 2, 4).reshape(b, t, h, v.shape[-1])


def selected_attention(q, k, v, selection) -> jax.Array:
    """``[B, T, H, D]`` grouped-query attention in which query ``t``
    reads the keys ``selection[b, t]`` marks (``[B, T, T]`` bool, a
    subset of ``s <= t`` that is never empty; :func:`select_top_k` keeps
    ``t`` itself only if it ranks) and no other: per head,
    softmax over the marked keys of ``q . k / sqrt(D)``, then the mix of
    their values. Where every causal key is marked this is dense causal
    attention, to the bit of the written-out form. No gradient flows to
    ``selection``."""
    selection = jax.lax.stop_gradient(selection)
    with jax.named_scope("fedml.model.attn.kernel"):
        if _on_tpu():
            return selected_splash(q, k, v, selection)
        return masked_attention(q, k, v, selection=selection)
