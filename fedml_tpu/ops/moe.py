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
