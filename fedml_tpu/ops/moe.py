"""Mixture-of-Experts FFN with expert parallelism (the "ep" mesh axis).

Beyond-reference capability (the reference has no MoE; SURVEY.md §2.7
lists EP as absent). TPU-native design: experts live as a stacked
parameter pytree ``[E, ...]`` sharded over the ``ep`` axis; tokens are
routed top-1 and exchanged with ``jax.lax.all_to_all`` — the canonical
expert-parallel pattern (tokens sorted into per-destination-shard
capacity-padded buckets, one all_to_all out, expert compute, one
all_to_all back, unsort).

Static shapes throughout: each (source shard -> destination shard) lane
carries a fixed ``capacity`` of token slots; overflow tokens are dropped
(standard MoE capacity semantics) and masked slots contribute zero.

The second half of the module is ONE chip's share of a dropless top-k
sparse layer (:func:`moe_layer`): a router over all experts, the
assignments to the experts held here ordered by expert into a bounded
row buffer, and grouped matrix products over those rows
(:func:`grouped_product`). The products are the layer's largest cost
and run, on the TPU in bfloat16, in row-tiled Pallas kernels of the
repo's own (:mod:`fedml_tpu.ops.grouped`, 1.4 to 2.9 times the speed of
the compiler's ``jax.lax.ragged_dot`` rewrite at the benchmark's
shapes); the shape rule that sends a call there
(:func:`fedml_tpu.ops.grouped.tiles`) reads nothing but the operands'
shape and dtype, and everything else — the CPU, float32, a mapped call
— keeps ``ragged_dot``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.ops import attention, grouped
from fedml_tpu.ops.mapped import once_a_client


def init_moe_params(key, num_experts: int, d_model: int, d_hidden: int):
    """Router + stacked expert FFNs ([E, ...] leaves)."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(d_model)
    s2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * s1,
        "w_in": jax.random.normal(k2, (num_experts, d_model, d_hidden)) * s1,
        "w_out": jax.random.normal(k3, (num_experts, d_hidden, d_model)) * s2,
    }


def _expert_ffn(w_in, w_out, x):
    return jax.nn.gelu(x @ w_in) @ w_out


def moe_ffn_reference(params, x):
    """Single-device top-1 MoE (the oracle): every token goes to its
    argmax expert, scaled by the softmax gate weight."""
    logits = x @ params["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(logits, axis=-1)  # [n]
    gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]
    outs = jax.vmap(
        lambda wi, wo: _expert_ffn(wi, wo, x)
    )(params["w_in"], params["w_out"])  # [E, n, d]
    sel = outs[expert, jnp.arange(x.shape[0])]
    return sel * gate[:, None]


def make_expert_parallel_moe(mesh, axis_name: str = "ep",
                             capacity_factor: float = 2.0):
    """Build ``moe(params, x) -> y`` running under ``shard_map``:
    ``params['w_in']/['w_out']`` sharded over experts on ``axis_name``,
    tokens sharded over the same axis, routed cross-shard via all_to_all.

    Call with GLOBAL arrays; returns the sharded computation wrapped and
    ready (in/out specs applied)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis_name]

    def local_moe(router, w_in, w_out, x):
        # x: [n_local, d]; w_in/w_out: [E/p, ...] local experts
        n_local, d = x.shape
        e_local = w_in.shape[0]
        num_experts = e_local * p
        shard = jax.lax.axis_index(axis_name)
        capacity = int(capacity_factor * n_local / p) or 1

        logits = x @ router
        gates = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(logits, axis=-1)  # global expert id [n_local]
        gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]
        dest = expert // e_local  # destination shard per token

        # slot each token into its destination bucket (capacity-limited):
        # position = rank of the token among same-destination tokens
        order = jnp.argsort(dest)  # stable: groups by destination
        ranks = jnp.zeros((n_local,), jnp.int32)
        # rank within destination group = index - first index of the group
        sorted_dest = dest[order]
        first_idx = jnp.searchsorted(sorted_dest, jnp.arange(p))
        pos_sorted = jnp.arange(n_local) - first_idx[sorted_dest]
        ranks = ranks.at[order].set(pos_sorted.astype(jnp.int32))
        keep = ranks < capacity

        # scatter tokens into [p, capacity, d] send buffer (+gates, +ids)
        buf_x = jnp.zeros((p, capacity, d), x.dtype)
        buf_e = jnp.full((p, capacity), -1, jnp.int32)  # -1 = empty slot
        slot_dest = jnp.where(keep, dest, p - 1)
        slot_rank = jnp.where(keep, ranks, capacity - 1)
        # masked scatter: dropped tokens write zeros/-1 via the mask trick
        buf_x = buf_x.at[slot_dest, slot_rank].add(
            jnp.where(keep[:, None], x, 0.0)
        )
        buf_e = buf_e.at[slot_dest, slot_rank].max(
            jnp.where(keep, expert, -1)
        )

        # exchange: [p, capacity, d] -> tokens FROM every shard
        recv_x = jax.lax.all_to_all(
            buf_x, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # [p, capacity, d]
        recv_e = jax.lax.all_to_all(
            buf_e, axis_name, split_axis=0, concat_axis=0, tiled=False
        )

        # local expert compute on received tokens
        flat_x = recv_x.reshape(p * capacity, d)
        flat_e = recv_e.reshape(p * capacity)
        local_e = flat_e - shard * e_local  # local expert index
        valid = flat_e >= 0
        local_e = jnp.clip(local_e, 0, e_local - 1)
        outs = jax.vmap(
            lambda wi, wo: _expert_ffn(wi, wo, flat_x)
        )(w_in, w_out)  # [E/p, p*capacity, d]
        y = outs[local_e, jnp.arange(p * capacity)]
        y = jnp.where(valid[:, None], y, 0.0)

        # return trip + unscatter
        back = jax.lax.all_to_all(
            y.reshape(p, capacity, d), axis_name,
            split_axis=0, concat_axis=0, tiled=False,
        )  # [p, capacity, d] keyed by original (dest, rank)
        gathered = back[slot_dest, slot_rank]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        return gathered * gate[:, None]

    spec_x = P(axis_name)
    spec_e = P(axis_name)
    return shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(P(), spec_e, spec_e, spec_x),
        out_specs=spec_x,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# the chip's share of a sparse-expert layer (top-k, dropless)
# ---------------------------------------------------------------------------

#: what :func:`moe_layer` counts, in this order (its docstring says what
#: each is; ``moe_rows_combined``, the rows the combine read back into
#: the tokens, is ``N`` times the SLOTS a token has: ``min(top_k,
#: count)``; ``moe_rows_gathered``, the rows a training step's four
#: gathers move, over ``4 x moe_rows_held`` is the rows moved a held
#: row; ``moe_rows_tiled``, the held rows of a call whose grouped
#: products ran in the row-tiled kernels, 0 of one the shape rule left
#: to ``ragged_dot``: :func:`product_tiles`; ``moe_tokens_group_open``,
#: the tokens whose open groups of experts include a held expert's —
#: every token where the router has one group: :func:`open_groups`)
MOE_COUNTERS = ("moe_rows_held", "moe_rows_routed", "moe_rows_max_expert",
                "moe_rows_compact", "moe_rows_combined", "moe_rows_gathered",
                "moe_rows_tiled", "moe_tokens_group_open")

#: the row buffer holds this many times the held experts' uniform share
#: of the assignments, and never under this part of all of them,
#: rounded up to the grouped product's row tile
ROW_SHARE, ROW_FLOOR, ROW_TILE = 2, 1 / 8, 128
#: rows of a memory tile: an array's second-to-last axis comes in these
SUBLANES = 8

ROUTE, EXPERTS = "fedml.model.moe.route", "fedml.model.moe.experts"
LATENT = "fedml.model.moe.latent"
#: a router that reads another tensor than the rows it routes (the
#: attention's input: it runs before attention) is timed apart: logits,
#: top-k and weights, not the ordering, which stays under :data:`ROUTE`
ROUTER = "fedml.model.moe.router"

#: ``checkpoint_name``s of what the sparse layer's backward rules read,
#: for a rematerialised layer to keep (``models/decoder.py:KEPT``), so
#: that its second run computes none of it again: the routing (the
#: router's logits in float32, the chosen probabilities and
#: :class:`Routing`: a few MB a layer) and the held rows (``(rows, into,
#: out)`` of the row buffer, which buffer it was, and the combine's
#: result, which a latent layer's projection back reads). ALL of
#: ``(rows, into, out)``, for every model: with them kept the four
#: decoder cells hold 13.9 to 15.1 GB at their peak on a v5e (13.1 to
#: 14.1 without; 0.2 to 1.3 GB a cell, ``PERF.md`` section 6, PR 41) of
#: the 15.4 a cell may. Their SHAPE is the row buffer's
#: (:func:`row_buffer`, or all ``N x top_k`` on the worst-case side), and
#: that is what they cost in memory and in the ONE gather that fills
#: ``rows`` (10 ns a row of 2,560 on a v5e: cheaper whole than walked
#: over the held rows' tiles); past the ``n_held`` held rows nothing
#: else touches them: the grouped products visit the row tiles present,
#: no pass masks them, no zero row is appended to them, and the combine
#: reads no place there (:func:`_read_weighed`, :func:`_read_back`).
#: Were they to stop fitting, ``rows`` go first
#: (gathered again from the kept ``order``: 14.57 for 15.07 GB and
#: 19 ms more of a 686 ms round in the tightest cell), then ``out`` (one
#: product and the activation again); the routing and ``into`` are what
#: remove the sorts and two of the three forward products.
KEPT_ROUTING, KEPT_ROWS = "fedml_moe_routing", "fedml_moe_rows"
KEPT = (KEPT_ROUTING, KEPT_ROWS)

SILU_GATED, RELU_GATED, RELU2 = "silu_gated", "relu_gated", "relu2"
#: a feed-forward's activation by name -> (matrices that lead in, what
#: lies between them and the last matrix)
ACTIVATIONS = {
    SILU_GATED: (2, lambda a, b: jax.nn.silu(a) * b),
    RELU_GATED: (2, lambda a, b: jax.nn.relu(a) * b),
    RELU2: (1, lambda a: jnp.square(jax.nn.relu(a))),
}


def leading(activation: str) -> tuple[str, ...]:
    """The names of the matrices that lead into ``activation``: ``w1``
    and, for a gated one, ``w3`` (``w2`` leads out)."""
    return ("w1", "w3")[:ACTIVATIONS[activation][0]]


def _middle(activation: str, *into):
    """What lies between a feed-forward's matrices: ``silu(a) * b``,
    ``relu(a) * b`` (both gated: two matrices lead in) or ``relu(a)^2``
    (one)."""
    return ACTIVATIONS[activation][1](*into)


def ffn(activation: str, x, *w):
    """The feed-forward every expert and the shared expert is:
    ``(act(x W1) * (x W3)) W2`` with ``act`` ``silu`` (``silu_gated``,
    as the dense layer is) or ``relu`` (``relu_gated``), three matrices;
    or ``relu(x W1)^2 W2`` (``relu2``), two."""
    return _middle(activation, *(x @ m for m in w[:-1])) @ w[-1]


def _mapped(fn, alone):
    """``fn`` with a ``vmap`` rule that first gives every operand the
    mapped axis: ``ragged_dot`` maps only when all three of its operands
    are mapped along axis 0, and under the cohort's ``vmap`` the weights
    of a client's first step are not (they are the global ones).
    ``alone``: what a call that is NOT mapped runs in ``fn``'s stead
    (the same product; ``fn`` stays the form that is mapped)."""
    wrapped = jax.custom_batching.custom_vmap(alone)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if mapped else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, mapped in zip(args, in_batched)]
        out = jax.vmap(fn)(*args)
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


def product_tiles(m: int, k: int, n: int, groups: int, dtype):
    """How a grouped product of ``[m, k]`` rows by ``groups`` matrices
    ``[k, n]`` of ``dtype`` runs HERE: the row tiles of the TPU's tiled
    kernels (:func:`fedml_tpu.ops.grouped.tiles`, the shape rule), or
    None for ``jax.lax.ragged_dot`` — off the TPU always."""
    if not attention._on_tpu():
        return None
    return grouped.tiles(m, k, n, groups, dtype)


def _tiles_of(x, w):
    """:func:`product_tiles` of rows ``x`` by matrices ``w`` (None where
    their dtypes differ)."""
    return product_tiles(*x.shape, w.shape[2], w.shape[0],
                         x.dtype if x.dtype == w.dtype else None)


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


def _ragged_transposed(x, w, sizes, g):
    # the product is linear in the rows and in the matrices: each
    # transpose alone, so that no forward product is traced beside them
    return (*jax.linear_transpose(lambda x: _ragged(x, w, sizes), x)(g),
            *jax.linear_transpose(lambda w: _ragged(x, w, sizes), w)(g))


def _product(x, w, sizes):
    tiles = _tiles_of(x, w)
    if tiles is None:
        return _ragged(x, w, sizes)
    return grouped.rows_product(x, w, sizes, tm=tiles.tm, sub=tiles.sub,
                                tn=w.shape[2])


def _product_transposed(x, w, sizes, g):
    tiles = _tiles_of(x, w)
    if tiles is None or g.dtype != x.dtype:
        return _ragged_transposed(x, w, sizes, g)
    return (grouped.rows_product(g, w, sizes, tm=tiles.tm, sub=tiles.sub,
                                 tn=w.shape[1], transposed=True),
            grouped.matrices_product(x, g, sizes, tm=tiles.matrices,
                                     tk=w.shape[1], tn=w.shape[2]))


_grouped = _mapped(_ragged, _product)
_grouped_transposed = _mapped(_ragged_transposed, _product_transposed)


@jax.custom_batching.custom_vmap
def _rows_tiled(n_held, tiled):
    """``n_held`` where ``tiled``, else 0 — and 0 under ``vmap``, which
    keeps the ``ragged_dot`` form (:func:`_mapped`): the held rows whose
    products ran in the tiled kernels."""
    return jnp.where(tiled, n_held, 0)


@_rows_tiled.def_vmap
def _rows_tiled_mapped(axis_size, in_batched, n_held, tiled):
    return jnp.zeros((axis_size,), n_held.dtype), True


@jax.custom_vjp
def grouped_product(x, w, sizes):
    """Rows ``[M, K]`` sorted by group, one ``[K, N]`` matrix a group
    (``w`` ``[G, K, N]``), ``sizes`` ``[G]`` rows a group -> ``[M, N]``;
    rows past ``sum(sizes)`` are not to be read, of the result and of
    the rows' cotangent alike (the TPU kernels leave them unwritten:
    the caller reads neither end there, :func:`_read_back`).

    What runs where (:func:`product_tiles`, decided while tracing from
    ``M``, ``K``, ``N``, ``G``, the dtype, the backend and whether the
    call is mapped — nothing else):

    * on the TPU, bfloat16 operands, a call of its own: the row-tiled
      kernels of :mod:`fedml_tpu.ops.grouped` — :func:`~fedml_tpu.ops.
      grouped.rows_product` forward and for the rows' cotangent,
      :func:`~fedml_tpu.ops.grouped.matrices_product` for the matrices'
      — in the tiles :func:`~fedml_tpu.ops.grouped.tiles` gives the
      shape: a group's whole matrix in fast memory, tiles of 512 rows
      worked in parts of 128, the work following the rows present. On a
      v5e a product of LFM2's 8,260 held rows by 8 matrices of 2,048 x
      1,792 takes 0.42 ms (146 TFLOP/s) against ``ragged_dot``'s 0.92
      (66), and every shape of the benchmark's six cells, from 128 rows
      an expert to 1,024, reads 1.4 to 2.9 times faster in each of the
      three kinds (that function's table; ``PERF.md`` section 6,
      PR 48). bfloat16 products summed in float32 over the whole
      contraction and rounded once: ``ragged_dot``'s sums in another
      order;
    * everywhere else ``jax.lax.ragged_dot`` (on the TPU the compiler's
      own rewrite, ``ragged-dot-none`` in a trace) and its two
      ``linear_transpose``s: off the TPU; float32 operands (the
      evaluator's stack, whose arithmetic is the configuration's stated
      one); fewer rows a group than was measured; widths off the lanes;
      and under ``vmap`` (:func:`_mapped`: the mapped form is the one
      ``ragged_dot`` has).

    A kernel's result lies where the kernel wrote it, not in the chip's
    fast memory: the gathers that read one say so to :func:`_gathered`.
    """
    return _grouped(x, w, sizes)


grouped_product.defvjp(
    lambda x, w, sizes: (_grouped(x, w, sizes), (x, w, sizes)),
    lambda res, g: (*_grouped_transposed(*res, g), None))


#: how a router turns its logits into the probabilities it ranks
SCORINGS = {"sigmoid": jax.nn.sigmoid,
            "softmax": lambda scores: jax.nn.softmax(scores, -1)}


def _weights(top_p, scale: float, epsilon: float = 0.0):
    """The chosen probabilities renormalised over themselves, times
    ``scale``; a family that guards the division adds its ``epsilon``
    to the sum (0: the bare sum, and the program of before)."""
    scaled = scale * top_p
    total = jnp.sum(top_p, -1, keepdims=True)
    return scaled / (total + epsilon if epsilon else total)


#: tokens a step of the ranking kernel holds in fast memory (22 of 512
#: read 0.30 ms a call on the chip at 128 tokens and 0.34 to 0.36 at 256
#: to 1,024; the cells' other shapes read alike at every size:
#: ``PERF.md`` section 6, PR 44)
RANK_TOKENS = 128


def _largest(b, p, k: int):
    """``b`` ``[E, R]``, finite: of each COLUMN its ``k`` largest values
    in ``jax.lax.top_k``'s order — descending, equal values by the lower
    row — by taking the largest that is left ``k`` times: ``(rows [k, R]
    int32, values [k, R])``, the values ``p``'s at those rows where
    ``p`` (as ``b``) is given, else ``b``'s own. Every pass runs down
    the experts with the tokens side by side, so that what a pass finds
    of a token is one number a lane: ``2 k`` reductions a token (``3 k``
    with ``p``)."""
    e, r = b.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (e, r), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, r), 0)

    def take(j, carry):
        b, ids, values = carry
        top = jnp.max(b, 0, keepdims=True)
        at = jnp.min(jnp.where(b == top, rows, e), 0, keepdims=True)
        hit = rows == at
        if p is not None:
            top = jnp.sum(jnp.where(hit, p, 0), 0, keepdims=True)
        return (jnp.where(hit, -jnp.inf, b), jnp.where(slot == j, at, ids),
                jnp.where(slot == j, top, values))

    return jax.lax.fori_loop(0, k, take, (
        b, jnp.zeros((k, r), jnp.int32), jnp.zeros((k, r), b.dtype)))[1:]


def largest_kernel(b, p, k: int, tokens: int = RANK_TOKENS,
                   interpret: bool = False):
    """:func:`_largest` of ``b`` ``[E, N]`` (and ``p``, or None) as the
    TPU's kernel: ``tokens`` of them a grid step, held in fast memory
    through all ``k`` passes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, n = b.shape
    operands = (b,) if p is None else (b, p)

    def kernel(*refs):
        *ins, ids_ref, values_ref = refs
        own = ins[1][...] if len(ins) == 2 else None
        ids_ref[...], values_ref[...] = _largest(ins[0][...], own, k)

    columns = lambda rows: pl.BlockSpec((rows, tokens), lambda i: (0, i))
    return pl.pallas_call(
        kernel, grid=(n // tokens,),
        in_specs=[columns(e)] * len(operands), out_specs=[columns(k)] * 2,
        out_shape=[jax.ShapeDtypeStruct((k, n), jnp.int32),
                   jax.ShapeDtypeStruct((k, n), b.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="moe_rank_top_k",
    )(*operands)


#: a router's groups where the configuration names none: all experts
#: in one, none closed (``(n_group, topk_group)``)
ONE_GROUP = (1, 1)


def open_groups(ranked, groups: tuple[int, int]):
    """``ranked`` ``[E, N]`` (what the choice reads of each expert, a
    token a column), ``groups = (n_group, topk_group)`` -> ``[n_group,
    N]`` bool: the ``topk_group`` groups of ``E / n_group`` consecutive
    experts that a token may choose from, those of largest score, a
    group's score the sum of its TWO largest entries; at a tie the
    lower group, as ``jax.lax.top_k`` orders. Maxima and a count of the
    groups that rank before each: no sort."""
    n_group, topk_group = groups
    e, n = ranked.shape
    inside = ranked.reshape(n_group, e // n_group, n)
    place = jax.lax.broadcasted_iota(jnp.int32, inside.shape, 1)
    first = jnp.max(inside, 1, keepdims=True)
    at = jnp.min(jnp.where(inside == first, place, e), 1, keepdims=True)
    score = first[:, 0] + jnp.max(
        jnp.where(place == at, -jnp.inf, inside), 1)
    ahead = (score[:, None] > score[None]) | (
        (score[:, None] == score[None])
        & (jnp.arange(n_group)[:, None, None]
           < jnp.arange(n_group)[None, :, None]))
    return jnp.sum(ahead, 0) < topk_group


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def largest(ranked, own, k: int, kernel: bool,
            groups: tuple[int, int] = ONE_GROUP):
    """``ranked`` ``[N, E]`` -> ``(own [N, k], ids [N, k])`` of each
    token's ``k`` largest, as ``jax.lax.top_k(ranked, k)`` orders and
    chooses them (:func:`_largest`), the values read from ``own`` (as
    ``ranked``) where that is not None; through the TPU's ``kernel`` or
    by the same passes over the whole array. With more than one group
    the experts of a token's closed groups (:func:`open_groups`) are
    out of its ranking, marked before either road is taken. One program
    a shape, whichever layers call it."""
    columns = ranked.T
    if groups[0] > 1:
        columns = jnp.where(jnp.repeat(
            open_groups(columns, groups), columns.shape[0] // groups[0], 0),
            columns, -jnp.inf)
    top_e, top_p = (largest_kernel if kernel else _largest)(
        columns, None if own is None else own.T, k)
    return top_p.T, top_e.T


def _ranked(scores, top_k: int, scoring: str, choice_bias=None,
            groups: tuple[int, int] = ONE_GROUP):
    """The ``top_k`` probabilities a token that rank highest and their
    experts, as ``jax.lax.top_k`` returns them TO THE BIT — descending,
    equal probabilities by the lower expert id — without its sort of
    ``[N, E]`` (:func:`largest`; on the TPU its kernel, over whole
    blocks of :data:`RANK_TOKENS` tokens). With ``choice_bias`` ``[E]``
    the RANKING reads probability plus bias and the probabilities
    returned are the chosen experts' own, without it.

    ``ops/attention.py:select_top_k`` keeps its own selection, by
    counting passes: it marks 2,048 of 8,192 keys in place and orders
    nothing, where this brings 6 to 22 of 64 to 512 out in order.
    ``groups``: :func:`largest`'s; the groups are scored by what the
    ranking reads, the bias included."""
    p = SCORINGS[scoring](scores)
    kernel = attention._on_tpu() and p.shape[0] % RANK_TOKENS == 0
    if choice_bias is None:
        return largest(p, None, top_k, kernel, groups)
    return largest(p + choice_bias, p, top_k, kernel, groups)


def _chosen_to_experts(top_e, d_top_p, experts: int):
    """``take_along_axis(p, top_e, -1)`` transposed: ``d_p[n, e] = sum_k
    (top_e[n, k] == e) d_top_p[n, k]``. A token's ids are distinct, so
    at most one term a place is not zero and the sum is, to the bit,
    what a scatter-add of the ``N x k`` updates into zeros leaves."""
    hit = top_e[..., None] == jnp.arange(experts, dtype=top_e.dtype)
    return jnp.sum(jnp.where(hit, d_top_p[..., None], 0), -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 5, 6, 7))
def route_top_k(scores, top_k: int, scale: float, scoring: str = "sigmoid",
                choice_bias=None, scope: str = ROUTE, epsilon: float = 0.0,
                groups: tuple[int, int] = ONE_GROUP):
    """Router logits ``[N, E]`` (float32) over ALL experts -> the
    ``top_k`` a token of largest probability — ``scoring`` ``sigmoid``
    (each expert's own) or ``softmax`` (over all ``E``, in float32) —
    their weights renormalised over those ``top_k`` and times
    ``scale``: ``(expert ids [N, k], weights [N, k])``, in
    ``jax.lax.top_k``'s order and with its choice at a tie (the lower
    expert id), bit for bit (:func:`_ranked`). With
    ``choice_bias`` ``[E]`` float32 (a score-correction bias) the
    ``top_k`` are those of largest probability PLUS bias; the weights
    stay the chosen experts' unbiased probabilities renormalised, so
    the bias moves the set and nothing else, and gets no gradient.
    ``epsilon`` (static) is added to the sum the weights are divided by
    (:func:`_weights`), forward and in the rule alike. ``groups =
    (n_group, topk_group)`` (static) limits the choice to the experts of
    a token's ``topk_group`` open groups of ``n_group``
    (:func:`open_groups`, scored by probability plus bias); one group,
    the default, is no limit and the program of before. Both choices
    are sets: neither the group scores nor the bias gets a gradient.

    The choice is a set, so the weights' cotangent reaches the
    probabilities as ``lax.top_k``'s own rule sends it, to the ids the
    forward pass chose and to the same bits, by a compare and a sum in
    place of its scatter (:func:`_chosen_to_experts`), under ``scope``;
    the rule reads the logits, the
    chosen probabilities and the ids alone, named
    :data:`KEPT_ROUTING`, so a rematerialised layer ranks once (the
    scoring, elementwise, is run again for its own rule)."""
    top_p, top_e = _ranked(scores, top_k, scoring, choice_bias, groups)
    return top_e, _weights(top_p, scale, epsilon)


def _route_top_k_fwd(scores, top_k, scale, scoring, choice_bias, scope,
                     epsilon=0.0, groups=ONE_GROUP):
    scores, (top_p, top_e) = checkpoint_name(
        (scores, _ranked(scores, top_k, scoring, choice_bias, groups)),
        KEPT_ROUTING)
    return (top_e, _weights(top_p, scale, epsilon)), (
        scores, top_p, top_e, choice_bias)


def _route_top_k_bwd(top_k, scale, scoring, scope, epsilon, groups, res,
                     cotangents):
    scores, top_p, top_e, choice_bias = res
    with jax.named_scope(scope):
        d_top_p, = jax.vjp(
            lambda top_p: _weights(top_p, scale, epsilon), top_p)[1](
                cotangents[1])
        _, scored = jax.vjp(SCORINGS[scoring], scores)
        d_p = _chosen_to_experts(top_e, d_top_p, scores.shape[-1])
        return (*scored(d_p), jax.tree.map(jnp.zeros_like, choice_bias))


route_top_k.defvjp(_route_top_k_fwd, _route_top_k_bwd)


def row_buffer(n: int, top_k: int, count: int, experts: int) -> int:
    """Rows of the bounded buffer for ``n`` tokens routed ``top_k`` ways
    over ``experts`` of which ``count`` are held: :data:`ROW_SHARE`
    times what uniform routing sends here or, where that is less,
    :data:`ROW_FLOOR` of all ``n * top_k`` assignments (the fewer of
    the experts a chip holds, the wider its load swings about the
    uniform share: one expert that a frequent token favours is a large
    part of 8 of 512 and a small part of 32 of 256), in whole row tiles,
    and never more than the worst case ``n * top_k``."""
    share = max(-(-ROW_SHARE * n * top_k * count // experts),
                int(ROW_FLOOR * n * top_k))
    return min(n * top_k, -(-share // ROW_TILE) * ROW_TILE)


def _one_path_a_batch(fits_fn, other_fn):
    """``(fits, *arrays) -> lax.cond(fits, fits_fn, other_fn, *arrays)``
    that stays ONE branch under ``vmap``: a mapped predicate turns
    ``cond`` into a select that runs both sides, so the batch takes
    ``fits_fn`` when EVERY instance fits and ``other_fn`` otherwise."""
    @jax.custom_batching.custom_vmap
    def branch(fits, *arrays):
        return jax.lax.cond(fits, fits_fn, other_fn, *arrays)

    @branch.def_vmap
    def rule(axis_size, in_batched, fits, *arrays):
        axes = jax.tree.map(lambda mapped: 0 if mapped else None,
                            tuple(in_batched[1:]))
        out = _one_path_a_batch(
            jax.vmap(fits_fn, axes, axis_size=axis_size),
            jax.vmap(other_fn, axes, axis_size=axis_size),
        )(jnp.all(fits), *arrays)
        return out, jax.tree.map(lambda _: True, out)

    return branch


def _slot_rows(local, count: int, nk: int):
    """A token's slots where fewer experts are held than it has ways:
    one slot a held expert. ``local`` ``[N, k]``: each way's expert id
    less the first held one. -> ``((pos [N, count], hit [N, k, count]),
    sizes [count])``: ``hit[n, w, j]`` says that way ``w`` of token
    ``n`` names held expert ``j`` — at most one way does, a token's
    ``top_k`` ids being distinct — and ``pos[n, j]`` is the row of the
    order that assignment stands on, or ``nk`` (past every buffer) where
    no way names ``j``. The order is a STABLE sort by expert, so an
    expert's rows stand in token order: the expert's first row plus the
    earlier tokens that chose it — a running count over ``[N, count]``
    in place of a second sort of the ``N x top_k`` ids, and its last row
    is ``sizes``, the rows a held expert, in place of a count over all
    of them."""
    hit = local[:, :, None] == jnp.arange(count)
    chose = jnp.any(hit, 1)
    running = jnp.cumsum(chose, 0, dtype=jnp.int32)
    sizes = running[-1]
    starts = jnp.cumsum(sizes, 0) - sizes
    return (jnp.where(chose, starts + running - chose, nk), hit), sizes


@once_a_client
def _all_rows(x, index):
    """``x[index]``, unbatched under ``vmap`` (:func:`once_a_client`):
    the gather into the row buffer, all its rows. (A walk over the held
    rows' share of them alone, :data:`ROW_TILE`s at a time into zeros,
    is slower than this one gather of twice as many: ``PERF.md`` section
    6, PR 47.)"""
    return x.at[index].get(mode="promise_in_bounds")


#: a gather whose operand is no larger than this reads it from the
#: chip's fast memory: on a v5e 49,152 rows of 2,560 come out of 8,192 or
#: 16,384 such rows (40 and 80 MiB) in 0.42 ms, 8.5 ns a row, and out of
#: 24,576 (120 MiB) in 2.07 ms, 42 ns a row (``PERF.md`` section 6, PR 47)
RESIDENT_BYTES = 80 * 2 ** 20


#: the leading part of a KERNEL's result that a gather reads it out of:
#: the compiler holds an operand of its own making in fast memory by
#: itself, a Pallas kernel's result lies where the kernel wrote it, and
#: a gather of 32,768 to 65,536 rows out of the 64 MiB one of the tiled
#: products wrote took 1.0 to 2.0 ms more a layer step than out of
#: ``ragged_dot``'s (LFM2's ``fedml.model.moe.route`` 38.9 -> 55.7 ms a
#: round, Keye's 83.9 -> 124.3; with the slice 40.8 and 85.4: ``PERF.md``
#: section 6, PR 48). A slice of
#: the leading rows is a copy the compiler makes in fast memory, as it
#: is for a buffer over :data:`RESIDENT_BYTES`; three quarters of a
#: buffer of twice the uniform share is one and a half times that share
KERNEL_RESULT_PART = 3 / 4


def _resident_rows(x, kernel_made: bool = False) -> int:
    """The leading rows of ``x`` ``[r, ...]`` that make a gather's
    operand of at most :data:`RESIDENT_BYTES`, in whole row tiles: all
    ``r`` where ``x`` is that small — but of a result that is
    ``kernel_made`` at most :data:`KERNEL_RESULT_PART`, so that what is
    gathered from is a copy of the compiler's own."""
    row = x.dtype.itemsize * (x.size // x.shape[0])
    rows = RESIDENT_BYTES // row // ROW_TILE * ROW_TILE
    if kernel_made:
        rows = min(rows, int(KERNEL_RESULT_PART * x.shape[0])
                   // ROW_TILE * ROW_TILE)
    return min(rows, x.shape[0]) or x.shape[0]


def _gathered(x, index, n_held, then=lambda rows: rows,
              kernel_made: bool = False):
    """``then(x[index])``, where ``index`` names one of the first
    ``n_held`` rows, and row 0 read where it does not, for ``then`` or
    the caller to make a zero of. Read out of the leading
    :func:`_resident_rows` of ``x`` alone when the held rows are fewer
    than those, so that a buffer too large for the chip's fast memory
    (or one that is ``kernel_made``, and so not in it) is read out of
    it all the same: what is gathered from follows
    ``n_held``, not the buffer. Else, and where ``x`` is small enough
    whole, out of all of it. (``then`` runs inside the ``cond`` that
    picks: a select and a sum applied after it the compiler parts, the
    select into the branches as a pass of its own.)"""
    at = jnp.where(index < n_held, index, 0)
    read = lambda rows: lambda: then(
        x[:rows].at[at].get(mode="promise_in_bounds"))
    part = _resident_rows(x, kernel_made)
    if part == x.shape[0]:
        return read(part)()
    return jax.lax.cond(n_held < part, read(part), read(x.shape[0]))


def _zero_past_held(rows, index, n_held):
    """``rows`` ``[*index.shape, ...]`` with zeros where ``index`` lies
    past the held rows."""
    held = (index < n_held).reshape(
        index.shape + (1,) * (rows.ndim - index.ndim))
    return jnp.where(held, rows, 0)


@once_a_client
def _read_back(x, index, n_held):
    """``x`` ``[r, ...]``, of which the first ``n_held`` rows are held
    experts' and the rest anything at all (the chip leaves them
    unwritten), as ``index`` reads it: row ``index`` where that is one
    of the first ``n_held`` of the order, else zero. ``index``:
    ``[N, slots]`` or ``[slots, N]``, the place in the order of each of
    a token's slots (:func:`_slot_places`). A place past the held rows
    reads row 0 (:func:`_gathered`) and a select puts the zero in its
    stead: no row is appended to the buffer and no mask pass runs over
    it. Unbatched under ``vmap`` (:func:`once_a_client`)."""
    return _gathered(x, index, n_held,
                     lambda rows: _zero_past_held(rows, index, n_held))


def _read_summed(axis: int, kernel_made: bool, x, index, n_held):
    """:func:`_read_back` summed over ``axis``, a token's slots: the
    select is part of the sum's own pass."""
    return _gathered(
        x, index, n_held,
        lambda rows: _zero_past_held(rows, index, n_held).sum(axis),
        kernel_made)


def _weighed_rows(kernel_made: bool, x, index, n_held):
    return jax.lax.cond(
        n_held > 0,
        lambda: _gathered(x, index, n_held, kernel_made=kernel_made),
        lambda: jnp.zeros(index.shape + x.shape[1:], x.dtype))


def _read_weighed(x, index, n_held, kernel_made: bool = False):
    """:func:`_read_back` for a sum that WEIGHS its rows by
    :func:`_held_weights`: a place past the held rows reads row 0 — a
    held expert's, so finite — and its weight is the zero. No select
    over the ``N x slots`` rows read: the weighted sum is a product the
    compiler moves out of the branch the rows are read in, and a select
    would stay behind as a pass of its own over all of them (nor a row
    of the buffer zeroed for the purpose: reading one row of a grouped
    product's result to write it back cost a pass over the buffer on the
    chip). Where NO row is held row 0 is anything at all, and what is
    read is zeros. ``kernel_made``: ``x`` is a Pallas kernel's result
    (:func:`_gathered`). Unbatched under ``vmap``
    (:func:`once_a_client`)."""
    return once_a_client(functools.partial(_weighed_rows, kernel_made))(
        x, index, n_held)


def _slot_places(back, k: int):
    """Where a token's slots find their rows, ``[N, slots]``."""
    return back[0] if isinstance(back, tuple) else back.reshape(-1, k)


def _by_slot(back, k: int):
    """Where a token's slots find their rows and how they lie:
    ``[N, slots]``, ``"nk"``, where the slots are whole tiles of
    :data:`SUBLANES` rows, so that splitting the gathered rows token by
    token is free; else each token's first, second, ... slot side by
    side, ``[slots, N]``, ``"kn"`` (cut token by token such rows are all
    copied into padded tiles; cut slot by slot none is). A token's
    slots are its ``k`` ways where ``k <= count`` (``back``:
    ``inverse``, flat) and the ``count`` held experts where there are
    fewer of them (``back``: :func:`_slot_rows`' pair), so a token reads
    ``min(k, count)`` rows."""
    places = _slot_places(back, k)
    if places.shape[1] % SUBLANES == 0:
        return places, "nk"
    return places.T, "kn"


def _slot_weights(top_w, back):
    """``top_w`` ``[N, k]`` as a token's slots weigh their rows, ``[N,
    slots]``: a held expert's slot has the weight of the way that names
    it, 0 where none does."""
    if isinstance(back, tuple):
        return jnp.sum(jnp.where(back[1], top_w[:, :, None], 0), 1)
    return top_w


def _held_weights(top_w, back, n_held):
    """:func:`_slot_weights`, and 0 for a slot whose place lies past
    the held rows: what :func:`_read_weighed`'s rows are weighed by."""
    held = _slot_places(back, top_w.shape[1]) < n_held
    return jnp.where(held, _slot_weights(top_w, back), 0)


def _way_cotangents(d_weight, back, n_held, n: int, k: int):
    """:func:`_slot_weights` transposed over the rows: ``d_weight``
    ``[r]``, a row's cotangent of its weight -> ``[N, k]``, each way's
    (0 for a way whose expert is not held)."""
    if isinstance(back, tuple):
        by_slot = _read_back(d_weight, back[0], n_held)
        return jnp.sum(jnp.where(back[1], by_slot[:, None], 0), 2)
    return _read_back(d_weight, back, n_held).reshape(n, k)


def _held_rows_forward(r, activation, slot_w, h, w, top_w, order, back,
                       sizes, n_held):
    """The held experts' sum over a buffer of ``r`` rows (at least
    ``n_held``): row ``i`` is token ``order[i] // k``'s. ``w``: the
    experts' matrices, ``(w1, w3, w2)`` or ``(w1, w2)``, as
    ``activation`` has them (:func:`ffn`); ``slot_w``: the weights of
    a token's slots (:func:`_held_weights`), or None where ``y`` is not
    asked for.
    -> ``(y [N, D], (rows, into, out))``, what the backward pass reads
    again (``into``: the rows through each matrix that leads in), all
    of ``r`` rows. Rows from ``n_held`` on are no held expert's: the
    grouped products leave them unwritten and the combine reads a held
    row at weight zero in their stead (:func:`_read_weighed`), so no
    pass masks them."""
    k = top_w.shape[1]
    with jax.named_scope(ROUTE):
        rows = _all_rows(h, order[:r] // k)
    with jax.named_scope(EXPERTS):
        into = tuple(grouped_product(rows, m, sizes) for m in w[:-1])
        out = grouped_product(_middle(activation, *into), w[-1], sizes)
    if slot_w is None:
        return None, (rows, into, out)
    with jax.named_scope(ROUTE):
        places, axes = _by_slot(back, k)
        y = jnp.einsum(axes + "d,nk->nd",
                       _read_weighed(out, places, n_held,
                                     _tiles_of(rows, w[0]) is not None),
                       slot_w.astype(out.dtype))
    return y, (rows, into, out)


def _held_rows_backward(r, activation, kept, w, top_w, order, back, sizes,
                        n_held, g):
    """:func:`_held_rows_forward`'s cotangents of ``h``, ``w`` and
    ``top_w`` for ``g`` ``[N, D]``, from what it kept. The rows'
    cotangent is unwritten from ``n_held`` on as well, and what it holds
    there (as ``d_weight`` does: ``out`` times rows not gathered)
    reaches nothing: the gathers back read a zero for every place past
    the held rows."""
    rows, into, out = kept
    n, k = top_w.shape
    with jax.named_scope(ROUTE):
        g_rows = _all_rows(g, order[:r] // k)
        weight = _all_rows(top_w.reshape(-1), order[:r]).astype(out.dtype)
        d_out = g_rows * weight[:, None]
        d_weight = jnp.einsum("rd,rd->r", out, g_rows,
                              preferred_element_type=jnp.float32)
        d_top_w = _way_cotangents(d_weight, back, n_held, n, k).astype(
            top_w.dtype)
    with jax.named_scope(EXPERTS):
        up, middle = jax.vjp(
            functools.partial(_middle, activation), *into)
        d_up, d_out_w = _grouped_transposed(up, w[-1], sizes, d_out)
        d_rows, d_w = zip(*(
            _grouped_transposed(rows, m, sizes, d_into)
            for m, d_into in zip(w[:-1], middle(d_up))))
    with jax.named_scope(ROUTE):
        places, axes = _by_slot(back, k)
        # (one leading matrix: its rows' cotangent is a kernel's result
        # as it is; of two the compiler's sum is what is read)
        d_h = once_a_client(functools.partial(
            _read_summed, axes.index("k"),
            len(d_rows) == 1 and _tiles_of(rows, w[0]) is not None))(
                sum(d_rows[1:], d_rows[0]), places, n_held)
    return d_h, (*d_w, d_out_w), d_top_w


def _held_experts_run(c, activation, keep, *args):
    """-> ``(y, taken, kept)``: over ``c`` rows where the held
    assignments fit them (``taken`` = ``N x top_k``, ``kept`` = what
    the backward pass reads again if ``keep``), else over all ``N x
    top_k`` (``taken`` 0, ``kept`` zeros of the ``c``-row shapes: the
    backward pass runs that side's forward pass again)."""
    top_w, back, n_held = args[2], args[4], args[-1]
    nk = top_w.size
    # (outside the branch: made inside it, the weighted sum that reads
    # them stays there with all a token's rows, 170 MB more of scratch
    # in a round of four layers)
    with jax.named_scope(ROUTE):
        slot_w = _held_weights(top_w, back, n_held)

    def over(r):
        def run(slot_w, *args):
            y, kept = _held_rows_forward(r, activation, slot_w, *args)
            if not keep:
                kept = ()
            elif r != c:
                kept = jax.tree.map(
                    lambda x: jnp.zeros((c,) + x.shape[1:], x.dtype), kept)
            return y, jnp.float32(nk if r < nk else 0), kept
        return run

    if c == nk:  # no smaller buffer: one path, decided while tracing
        return over(nk)(slot_w, *args)
    return _one_path_a_batch(over(c), over(nk))(n_held <= c, slot_w, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(c, activation, h, w, top_w, order, back, sizes, n_held):
    """What the held experts (matrices ``w`` around ``activation``,
    :func:`ffn`'s) add to the tokens: ``(y [N, D], N x top_k if the
    rows went through the bounded buffer of ``c`` rows, else 0)``.

    The branch sits in the rules, not under them: differentiated by
    JAX, a ``cond`` returns the residuals of BOTH sides, zero-filled
    for the side not taken — the worst-case-sized arrays the bounded
    buffer exists to avoid."""
    return _held_experts_run(
        c, activation, False, h, w, top_w, order, back, sizes, n_held)[:2]


def _held_experts_fwd(c, activation, *args):
    # named after the branch: one name for whichever side ran (``y``:
    # a latent layer's projection back reads it for its own gradient)
    y, taken, kept = checkpoint_name(
        _held_experts_run(c, activation, True, *args), KEPT_ROWS)
    return (y, taken), (kept, taken, args)


def _held_experts_bwd(c, activation, res, cotangents):
    kept, taken, args = res
    nk = args[2].size  # top_w

    def bounded(kept, g, h, *rest):
        return _held_rows_backward(c, activation, kept, *rest, g)

    def worst(kept, g, h, *rest):
        _, kept = _held_rows_forward(nk, activation, None, h, *rest)
        return _held_rows_backward(nk, activation, kept, *rest, g)

    if c == nk:
        grads = bounded(kept, cotangents[0], *args)
    else:
        # (the barrier keeps the compiler from moving what reads the
        # gradients — a cast to float32 twice their size — into the
        # branches, whose results then wait for the update at that size)
        grads = jax.lax.optimization_barrier(_one_path_a_batch(
            bounded, worst)(taken > 0, kept, cotangents[0], *args))
    return (*grads, None, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class Routing(NamedTuple):
    """What a router decided for ``N`` tokens (:func:`route`):
    ``top_e``, ``top_w`` ``[N, k]`` (:func:`route_top_k`'s); ``order``
    ``[N k]``, the assignments sorted by held expert, absent experts'
    last; ``back``, where a token's slots find their rows in that order
    (:func:`_by_slot`); ``sizes`` ``[count]`` rows a held expert and
    ``n_held`` their sum; ``opened``, the tokens for which a group that
    holds a held expert was open (all ``N`` where the router has one
    group)."""
    top_e: jax.Array
    top_w: jax.Array
    order: jax.Array
    back: Any
    sizes: jax.Array
    n_held: jax.Array
    opened: jax.Array


def route(router, x, held: tuple[int, int], top_k: int, scale: float,
          scoring: str = "sigmoid", scope: str = ROUTE,
          choice_bias=None, renorm_epsilon: float = 0.0,
          groups: tuple[int, int] = ONE_GROUP) -> Routing:
    """From what the router reads to the routing: ``router`` ``[D, E]``
    over ALL ``E`` experts, ``x`` ``[N, D]`` — the rows the experts
    will be given, or any other tensor of the same tokens (a router
    that reads its layer's attention input) — ``held = (first, count)``
    the experts held here; ``choice_bias`` ``[E]`` float32 or None,
    ``renorm_epsilon`` and ``groups``, :func:`route_top_k`'s. Logits
    (float32), the groups a token may choose from, top-k
    and weights run under ``scope``; ordering the assignments by held expert under
    :data:`ROUTE`. A token's slots are its ways where ``top_k <= count``
    (``back``: the order's own ``argsort``) and the held experts where
    those are the fewer (``back``: :func:`_slot_rows`' pair; neither
    the inverse nor a count over the ``N x top_k`` ids is computed)."""
    first, count = held
    n = x.shape[0]
    with jax.named_scope(scope):
        scores = jnp.dot(x, router, preferred_element_type=jnp.float32)
        top_e, top_w = route_top_k(scores, top_k, scale, scoring,
                                   choice_bias, scope, renorm_epsilon, groups)
        opened = jnp.int32(n)
        if groups[0] > 1:  # counted from the choice's own group scores
            ranked = SCORINGS[scoring](jax.lax.stop_gradient(scores))
            if choice_bias is not None:
                ranked = ranked + choice_bias
            size = router.shape[1] // groups[0]
            opened = jnp.sum(jnp.any(open_groups(ranked.T, groups)[
                first // size:(first + count - 1) // size + 1], 0))
    with jax.named_scope(ROUTE):
        local = top_e.reshape(-1) - first
        # an assignment to an absent expert sorts past every held group
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)
        if top_k <= count:  # a token's slots are its ways
            back = jnp.argsort(order)
            sizes = jnp.bincount(group, length=count + 1)[:count].astype(
                jnp.int32)
        else:  # ... or the held experts, where those are the fewer
            back, sizes = _slot_rows(
                local.reshape(n, top_k), count, n * top_k)
        n_held = jnp.sum(sizes)
    return Routing(top_e, *checkpoint_name(
        (top_w, order, back, sizes, n_held, opened), KEPT_ROUTING))


def moe_layer(params, h, held: tuple[int, int], top_k: int, scale: float,
              scoring: str = "sigmoid", activation: str = SILU_GATED,
              router_input=None, renorm_epsilon: float = 0.0,
              groups: tuple[int, int] = ONE_GROUP):
    """This chip's part of one sparse-expert layer, dropless.

    ``params``: ``router`` ``[D, E]`` over ALL ``E`` experts;
    ``router_bias`` ``[E]`` float32 or no such key (a score-correction
    bias: :func:`route_top_k`'s ``choice_bias``, which moves the set of
    experts chosen and not their weights, and gets no gradient); the
    matrices of the experts held here — ids ``[first, first + count)``,
    ``held = (first, count)`` — as ``activation`` has them
    (:data:`ACTIVATIONS`, :func:`ffn`): gated (``silu_gated``,
    ``relu_gated``), ``w1`` / ``w3`` ``[count, W, F]`` and ``w2``
    ``[count, F, W]``, or the two of ``relu(x W1)^2 W2`` (``relu2``: no
    ``w3``); ``shared`` (the shared expert's matrices, as many, on the
    full ``D``) or no such key; and ``latent`` (``[D, L]``, ``[L, D]``)
    or no such key. ``h``: ``[N, D]`` tokens, the rows the experts
    read. ``router_input``: ``[N, D]``, what the router reads of the
    same tokens where that is not ``h`` (a layer whose router reads its
    attention's input): the choice and the weights then come from it
    and the rows from ``h``, so a gradient reaches the layer's input by
    both (through the weights into ``router_input``, through the rows
    into ``h``), and the router's logits, top-k and weights are timed
    under :data:`ROUTER`, not :data:`ROUTE`. ``renorm_epsilon``
    (static): what the family adds to the sum of the chosen
    probabilities before it divides by it (:func:`_weights`; 0 where it
    adds nothing). ``groups = (n_group, topk_group)`` (static): the
    router's experts in ``n_group`` groups of which a token may choose
    from its ``topk_group`` best (:func:`route_top_k`,
    :func:`open_groups`; one group: no limit). The experts' width ``W``
    is ``D``, or ``L`` where the layer is latent: its experts then read
    ``h`` through the first latent projection and their weighted sum
    goes back through the second (no activation on either; both shared
    by all experts), while the router and the shared expert read the
    full width.

    Every token is routed over all ``E`` experts (:func:`route`,
    :func:`route_top_k`, ``scoring`` its kind of probability); the
    assignments whose
    expert is held are ordered by expert and go through grouped matrix
    products (:func:`grouped_product`: on the TPU kernels whose work
    follows the rows present, never ``E x N`` — the row-tiled ones of
    :mod:`fedml_tpu.ops.grouped` where the shape rule sends the call,
    else the compiler's ``ragged_dot``), are weighted —
    weights normalised over all ``top_k``, held or not — and summed back
    into their tokens; the shared expert is added. That sum, and its
    transpose in the backward rule, reads a row a SLOT of a token, of
    which it has ``min(top_k, count)``, decided while tracing: its ways
    where ``top_k <= count`` (each finds its row through ``inverse``,
    the order's own ``argsort``), the held experts where ``count <
    top_k`` (a token names an expert at most once; its row is found by
    a running count, :func:`_slot_rows`, whose last row is the rows a
    held expert, so neither ``inverse`` nor a count over the ``N x
    top_k`` ids is computed; a slot no way names reads a zero). The
    row buffer has the SHAPE of
    what the held experts can be expected to receive
    (:func:`row_buffer`: twice their uniform share of the ``N x top_k``
    assignments, an eighth of them at least); a call that receives more
    goes through a buffer of
    all ``N x top_k`` rows instead, so no assignment is ever dropped,
    and a mapped batch goes one way together. Past the one gather that
    fills it (:func:`_all_rows`) the rows TOUCHED follow the held count,
    not the buffer: the products visit the row tiles present, and the
    gather back reads a zero for a place past the held rows with no
    zero row appended to the buffer and no mask pass over it
    (:func:`_read_weighed` forward, :func:`_read_back` in the rule); the
    gathers run unbatched under ``vmap``
    (:func:`fedml_tpu.ops.mapped.once_a_client`). What the absent
    experts would add is left out: on one chip there is no exchange and
    nothing stands in for one.

    -> ``(y [N, D], counters float32 [8])`` in :data:`MOE_COUNTERS`'
    order: assignments that landed on held experts, assignments made
    (``N x top_k``), rows of the fullest held expert, assignments made
    in a call that went through the bounded buffer, rows the combine
    read (``N`` times a token's slots), and the rows of the tokens'
    width that the four gathers of a training step move: twice the
    buffer of the side taken (forward the tokens' rows, in the rule
    their cotangents') and twice what the combine reads (forward, and
    its transpose in the rule), and the held rows of a call whose
    products ran in the row-tiled kernels (:func:`product_tiles`; 0
    else, and under ``vmap``), and the tokens for which a group that
    holds a held expert was open (``N`` under one group)."""
    count = held[1]
    n, _ = h.shape
    apart = router_input is not None  # a router with an input of its own
    routing = route(params["router"], router_input if apart else h, held,
                    top_k, scale, scoring, scope=ROUTER if apart else ROUTE,
                    choice_bias=params.get("router_bias"),
                    renorm_epsilon=renorm_epsilon, groups=groups)
    inside = h
    if "latent" in params:
        with jax.named_scope(LATENT):
            inside = h @ params["latent"][0]
    buffer = row_buffer(n, top_k, count, params["router"].shape[1])
    y, bounded = _held_experts(
        buffer, activation, inside,
        tuple(params[m] for m in (*leading(activation), "w2")),
        routing.top_w, routing.order, routing.back, routing.sizes,
        routing.n_held)
    if "latent" in params:
        with jax.named_scope(LATENT):
            y = y @ params["latent"][1]
    if "shared" in params:
        with jax.named_scope("fedml.model.mlp"):
            y = y + ffn(activation, h, *params["shared"])
    combined = n * min(top_k, count)
    buffered = jnp.where(bounded > 0, buffer, n * top_k)  # the side taken
    tiled = [_tiles_of(jax.ShapeDtypeStruct((r, inside.shape[1]),
                                            inside.dtype), params["w1"])
             is not None for r in (buffer, n * top_k)]
    counters = jnp.stack([
        routing.n_held.astype(jnp.float32), jnp.float32(n * top_k),
        jnp.max(routing.sizes).astype(jnp.float32), bounded,
        jnp.float32(combined),
        2 * buffered.astype(jnp.float32) + 2 * combined,
        _rows_tiled(routing.n_held, jnp.where(bounded > 0, *tiled)).astype(
            jnp.float32),
        routing.opened.astype(jnp.float32)])
    return y.astype(h.dtype), counters
