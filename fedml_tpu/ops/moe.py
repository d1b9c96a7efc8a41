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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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

#: what :func:`moe_layer` counts, in this order
MOE_COUNTERS = ("moe_rows_held", "moe_rows_routed", "moe_rows_max_expert")


def gated_ffn(x, w1, w3, w2):
    """``(silu(x W1) * (x W3)) W2``, the gated feed-forward every expert
    (and the dense layer, and the shared expert) is."""
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(h, order, inverse, n_held, k):
    """Row ``r`` of the result is token ``order[r] // k``'s row of ``h``.
    Both directions are gathers: the cotangent is un-sorted through
    ``inverse`` and a token's ``k`` copies summed. Only the first
    ``n_held`` rows are anyone's input, so only their cotangent counts:
    the grouped products leave the rest of theirs unwritten."""
    return h[order // k]


def _dispatch_fwd(h, order, inverse, n_held, k):
    return h[order // k], (inverse, n_held, h.shape[0])


def _dispatch_bwd(k, res, g):
    inverse, n_held, n = res
    g = jnp.where((jnp.arange(g.shape[0]) < n_held)[:, None], g, 0)
    return g[inverse].reshape(n, k, -1).sum(1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """``y[inverse]`` with the gather ``g[order]`` as its transpose (a
    permutation's, where autodiff would scatter)."""
    return y[inverse]


_unsort.defvjp(lambda y, order, inverse: (y[inverse], order),
               lambda order, g: (g[order], None, None))


def _mapped(fn):
    """``fn`` with a ``vmap`` rule that first gives every operand the
    mapped axis: ``ragged_dot`` maps only when all three of its operands
    are mapped along axis 0, and under the cohort's ``vmap`` the weights
    of a client's first step are not (they are the global ones)."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if mapped else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, mapped in zip(args, in_batched)]
        out = jax.vmap(fn)(*args)
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


_ragged = _mapped(lambda x, w, sizes: jax.lax.ragged_dot(x, w, sizes))
_ragged_transposed = _mapped(lambda x, w, sizes, g: jax.vjp(
    lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)[1](g))


@jax.custom_vjp
def grouped_product(x, w, sizes):
    """Rows ``[M, K]`` sorted by group, one ``[K, N]`` matrix a group
    (``w`` ``[G, K, N]``), ``sizes`` ``[G]`` rows a group -> ``[M, N]``;
    rows past ``sum(sizes)`` are not to be read, of the result and of
    the rows' cotangent alike (the TPU kernel leaves them unwritten:
    the caller masks both ends). ``jax.lax.ragged_dot``
    (on the TPU a Mosaic kernel that visits the row tiles present) and
    its own two transposes, made to run under ``vmap``."""
    return _ragged(x, w, sizes)


grouped_product.defvjp(
    lambda x, w, sizes: (_ragged(x, w, sizes), (x, w, sizes)),
    lambda res, g: (*_ragged_transposed(*res, g), None))


def route_top_k(scores, top_k: int, scale: float):
    """Sigmoid scores ``[N, E]`` (float32) -> the ``top_k`` largest a
    token, their weights renormalised over those ``top_k`` and times
    ``scale``: ``(expert ids [N, k], weights [N, k])``."""
    p = jax.nn.sigmoid(scores)
    top_p, top_e = jax.lax.top_k(p, top_k)
    return top_e, scale * top_p / jnp.sum(top_p, -1, keepdims=True)


def moe_layer(params, h, held: tuple[int, int], top_k: int, scale: float):
    """This chip's part of one sparse-expert layer, dropless.

    ``params``: ``router`` ``[D, E]`` over ALL ``E`` experts, ``w1`` /
    ``w3`` ``[count, D, F]`` and ``w2`` ``[count, F, D]`` of the experts
    held here — ids ``[first, first + count)``, ``held = (first,
    count)`` — and ``shared`` (``w1, w3, w2`` of the shared expert) or
    no such key. ``h``: ``[N, D]`` tokens.

    Every token is routed over all ``E`` experts; the assignments whose
    expert is held are ordered by expert and go through grouped matrix
    products (:func:`grouped_product`: on the TPU a kernel whose work
    follows the rows present, never ``E x N``), are weighted —
    weights normalised over all ``top_k``, held or not — and summed back
    into their tokens; the shared expert is added. The row buffer holds
    the worst case (``N x top_k`` rows), so no assignment is ever
    dropped. What the absent experts would add is left out: on one chip
    there is no exchange and nothing stands in for one.

    -> ``(y [N, D], counters float32 [3])`` in :data:`MOE_COUNTERS`'
    order: assignments that landed on held experts, assignments made
    (``N x top_k``), rows of the fullest held expert."""
    first, count = held
    n, _ = h.shape
    with jax.named_scope("fedml.model.moe.route"):
        scores = jnp.dot(h, params["router"],
                         preferred_element_type=jnp.float32)
        top_e, top_w = route_top_k(scores, top_k, scale)
        local = top_e.reshape(-1) - first
        # an assignment to an absent expert sorts past every held group
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)
        inverse = jnp.argsort(order)
        sizes = jnp.bincount(group, length=count + 1)[:count].astype(
            jnp.int32)
        n_held = jnp.sum(sizes)
        rows = _dispatch(h, order, inverse, n_held, top_k)
    with jax.named_scope("fedml.model.moe.experts"):
        up = jax.nn.silu(grouped_product(rows, params["w1"], sizes)) * (
            grouped_product(rows, params["w3"], sizes))
        out = grouped_product(up, params["w2"], sizes)
    with jax.named_scope("fedml.model.moe.route"):
        # rows past the held groups belong to absent experts
        out = jnp.where((jnp.arange(n * top_k) < n_held)[:, None], out, 0)
        out = _unsort(out, order, inverse).reshape(n, top_k, -1)
        y = jnp.einsum("nkd,nk->nd", out, top_w.astype(out.dtype))
    if "shared" in params:
        with jax.named_scope("fedml.model.mlp"):
            y = y + gated_ffn(h, *params["shared"])
    counters = jnp.stack([
        n_held, jnp.asarray(n * top_k, jnp.int32), jnp.max(sizes),
    ]).astype(jnp.float32)
    return y.astype(h.dtype), counters
