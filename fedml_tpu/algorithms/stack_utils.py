"""Shared helpers for algorithms keeping per-client model stacks.

A "stack" is a pytree whose leaves carry a leading ``[num_clients]`` axis —
the TPU-native representation of the reference's per-client stateful
trainers (``standalone/utils/BaseClient.py:13``). Cohort selection is a
gather, writing results back is a scatter, and per-client evaluation walks
the leading axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

Pytree = Any


def stack_gather(stack: Pytree, cohort: jax.Array) -> Pytree:
    return jax.tree.map(lambda s: s[cohort], stack)


def stack_scatter(stack: Pytree, cohort: jax.Array, new: Pytree) -> Pytree:
    return jax.tree.map(lambda s, n: s.at[cohort].set(n), stack, new)


def vmap_init(init_fn: Callable, root_key: jax.Array, n: int) -> Pytree:
    """Independent per-client inits (the reference deep-copies a prototype;
    independent seeds match heterogeneous stateful clients better)."""
    keys = jax.vmap(lambda i: jax.random.fold_in(root_key, i))(jnp.arange(n))
    return jax.vmap(init_fn)(keys)


def evaluate_stack(
    evaluator: Callable, stack: Pytree, test_x, test_y, n: int
) -> dict:
    """Mean per-client metrics on the global test set (reference
    ``_local_test_on_all_clients``,
    ``HeterogeneousModelBaseTrainerAPI.py:82-164``)."""
    accs, losses = [], []
    for i in range(n):
        v = jax.tree.map(lambda s: s[i], stack)
        m = evaluator(v, test_x, test_y)
        accs.append(float(m["acc"]))
        losses.append(float(m["loss"]))
    return {
        "test_acc": sum(accs) / n,
        "test_loss": sum(losses) / n,
        "per_client_acc": accs,
    }


def resolve_cohort_groups(
    requested: int, cohort: int, auto_group_size: int = 5
) -> int:
    """Number of size-sorted sub-groups a cohort runs in.
    ``requested`` is capped at cohort // 2 (a group needs >= 2 clients)
    and rounded DOWN to the nearest divisor of the cohort (static shapes
    need equal groups); 0 = auto -> groups of ``auto_group_size``
    clients. The fused classification cohort measures best at ~5-client
    groups (its fat model's cost scales linearly down to C=5); the
    vmapped GAN path measures best at 2-client groups (FedGDKD 0.93 ->
    1.19 r/s, FedDTG round 1.9x vs static — v5e, idle-machine A/B)."""
    if cohort <= 2:
        return 1
    want = (
        requested if requested > 0
        else max(1, round(cohort / auto_group_size))
    )
    want = max(1, min(want, cohort // 2))
    while cohort % want:
        want -= 1
    return want


def size_grouped_lanes(vcall, lane_args: tuple, mask_rows, requested: int,
                       auto_group_size: int = 2, traced_once: bool = False):
    """Run a per-client update in size-sorted sub-groups.

    Callers: the fused cohort update of both runtimes
    (``fedavg.grouped_cohort_call`` — ``FedAvgSim._locals``, and
    ``ShardedFedAvg``'s shard body with ``traced_once``) and the vmapped
    GAN rounds (``sgan``, ``gan_family``).

    ``requested`` is the raw ``TrainConfig.cohort_groups`` value; the
    actual group count is resolved HERE against the true lane count
    (``mask_rows.shape[0]``), so the split always divides the lanes —
    resolving against a config-side client count that disagrees with
    the data's natural client count cannot drop or duplicate lanes.

    Sorting clients by n_k means each sub-group's step-loop cost is set
    by ITS largest member, not the cohort's (the fused cohort update and
    vmap's batched while both run a call to the max over its lanes).
    Scheduling only: each lane's trajectory depends on (globals, its
    rows, its key) alone.

    ``traced_once``: the sorted lanes are reshaped to ``[groups,
    lanes / groups, ...]`` and the groups run under ``lax.map``, so
    ``vcall`` is traced and lowered once however many groups there are;
    each group's inner loop keeps its own dynamic trip count. Otherwise
    a Python loop traces ``vcall`` once a group (ResNet-56, two groups:
    27 s of tracing against 16 s) and the round runs 1 % shorter (367
    against 370 ms, the same mix; PERF.md section 6, PR 25) — the
    single-device callers keep it until a benchmark cell weighs their
    set-up against their rounds.

    ``lane_args`` are pytrees with leading lane axis; every output of
    ``vcall`` must be lane-stacked. Results come back in input order.
    """
    c = mask_rows.shape[0]
    groups = resolve_cohort_groups(requested, c, auto_group_size)
    if groups == 1:
        return vcall(*lane_args)
    assert c % groups == 0, (c, groups)
    sub = c // groups
    order = jnp.argsort(-jnp.sum(mask_rows, axis=1))
    if traced_once:
        grouped = jax.tree.map(
            lambda a: a[order].reshape((groups, sub) + a.shape[1:]),
            lane_args,
        )
        outs = jax.lax.map(lambda args: vcall(*args), grouped)
        inv = jnp.argsort(order)
        return jax.tree.map(
            lambda a: a.reshape((c,) + a.shape[2:])[inv], outs
        )
    inv = jnp.argsort(order)
    sorted_args = jax.tree.map(lambda a: a[order], lane_args)
    outs = []
    for g in range(groups):
        outs.append(vcall(*jax.tree.map(
            lambda a: a[g * sub:(g + 1) * sub], sorted_args
        )))
    cat = jax.tree.map(lambda *ls: jnp.concatenate(ls, 0), *outs)
    return jax.tree.map(lambda a: a[inv], cat)


def lockstep_slot_steps(mask_rows, groups: int, batch_size: int,
                        epochs: int = 1):
    """Slot-steps a lockstep cohort update executes on ``mask_rows``
    (``[lanes, max_n]``) run as ``groups`` size-sorted groups: every
    group steps to the count of its largest member,
    ``ceil(max n_k / batch)`` an epoch, at its full width — the trip
    counts ``build_cohort_local_update`` takes on the groups
    :func:`size_grouped_lanes` forms. Over the live client steps
    (``sum ceil(n_k / batch)``) it is the schedule's occupancy."""
    width = mask_rows.shape[0] // groups
    by_size = jnp.sort(jnp.sum(mask_rows, axis=1))[::-1]
    largest = by_size.reshape(groups, width)[:, 0]
    return epochs * width * jnp.sum(jnp.ceil(largest / batch_size))
