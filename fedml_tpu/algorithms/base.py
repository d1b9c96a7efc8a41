"""Task definitions and the compiled client local-update.

The reference's per-task trainers
(``fedml_api/standalone/fedavg/my_model_trainer_classification.py``,
``..._nwp.py``, ``..._tag_prediction.py``) become pure loss/metric functions
here, and ``MyModelTrainer.train`` (epochs x minibatch SGD) becomes a jitted
``lax.scan`` over steps that is *vmapped across the cohort* — one XLA
program trains every sampled client in parallel on the MXU.

Padding discipline: every client's index row is padded to ``max_n``; a
padded batch contributes zero gradient AND zero optimizer-state update
(updates are gated on the batch containing at least one real sample), so a
small client's trajectory exactly matches serial training on its real data.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from fedml_tpu.config import TrainConfig
from fedml_tpu.core import tree as T
from fedml_tpu.models.base import FedModel

Pytree = Any


# ---------------------------------------------------------------------------
# Tasks (loss + metrics)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Task:
    """Sufficient-statistics metrics for one task type.

    ``metric_sums(logits, y, w)`` returns additive SUMS:
    ``loss_sum`` (weighted loss numerator), ``w_sum`` (loss denominator),
    ``correct`` / ``count`` (accuracy numerator / denominator — for the tag
    task these are micro-precision TP / predicted-positives). Reduce sums
    across batches/clients/shards first, then call :func:`finalize_sums`.
    """

    name: str
    metric_sums: Callable[[jax.Array, jax.Array, jax.Array], dict]


def zero_sums() -> dict:
    return {
        "loss_sum": jnp.asarray(0.0),
        "correct": jnp.asarray(0.0),
        "count": jnp.asarray(0.0),
        "w_sum": jnp.asarray(0.0),
    }


def finalize_sums(sums: dict) -> dict:
    """Turn reduced metric sums into {loss, acc}. Clamps are applied ONCE
    here, after the final reduction, so per-batch zero-prediction batches
    don't distort micro-precision."""
    return {
        "loss": sums["loss_sum"] / jnp.maximum(sums["w_sum"], 1.0),
        "acc": sums["correct"] / jnp.maximum(sums["count"], 1.0),
    }


def _classification_task() -> Task:
    def sums(logits, y, w):
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        return {
            "loss_sum": jnp.sum(ce * w),
            "correct": jnp.sum(correct * w),
            "count": jnp.sum(w),
            "w_sum": jnp.sum(w),
        }

    return Task("classification", sums)


def _nwp_task() -> Task:
    """Next-word/char prediction: logits [B,T,V], y [B,T]; token-level
    accuracy (reference ``my_model_trainer_nwp.py``)."""

    def sums(logits, y, w):
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        tokens = jnp.sum(w) * y.shape[1]
        return {
            "loss_sum": jnp.sum(ce * w[:, None]),
            "correct": jnp.sum(correct * w[:, None]),
            "count": tokens,
            "w_sum": tokens,
        }

    return Task("nwp", sums)


def _tag_task() -> Task:
    """Multi-label tag prediction with sigmoid BCE; accuracy = micro
    precision at threshold 0.5 (reference multilabel path,
    ``fedml_core/trainer/model_trainer.py:57-112``)."""

    def sums(logits, y, w):
        bce = optax.sigmoid_binary_cross_entropy(logits, y).mean(-1)
        pred = (jax.nn.sigmoid(logits) > 0.5).astype(jnp.float32)
        tp = jnp.sum(pred * y * w[:, None])
        predicted = jnp.sum(pred * w[:, None])
        return {
            "loss_sum": jnp.sum(bce * w),
            "correct": tp,  # micro-precision numerator
            "count": predicted,  # micro-precision denominator (raw sum)
            "w_sum": jnp.sum(w),
        }

    return Task("tag_prediction", sums)


def _segmentation_task() -> Task:
    """Per-pixel CE for semantic segmentation: logits [B,H,W,K], y [B,H,W];
    accuracy = pixel accuracy (reference fedseg ``MyModelTrainer`` CE loss +
    ``Evaluator.Pixel_Accuracy``, ``fedseg/utils.py:251``). mIoU/FWIoU come
    from :class:`fedml_tpu.metrics.segmentation.SegEvaluator`."""

    def sums(logits, y, w):
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        ce = ce.mean(axis=(1, 2))  # per-image mean over pixels
        correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        pixels = y.shape[1] * y.shape[2]
        return {
            "loss_sum": jnp.sum(ce * w),
            "correct": jnp.sum(correct.mean(axis=(1, 2)) * w * pixels),
            "count": jnp.sum(w) * pixels,
            "w_sum": jnp.sum(w),
        }

    return Task("segmentation", sums)


def make_task(name: str) -> Task:
    return {
        "classification": _classification_task,
        "nwp": _nwp_task,
        "tag_prediction": _tag_task,
        "segmentation": _segmentation_task,
    }[name]()


# ---------------------------------------------------------------------------
# Client optimizer
# ---------------------------------------------------------------------------


def make_client_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Reference client optimizers: SGD(momentum, wd) or Adam(wd, amsgrad)
    (``my_model_trainer_classification.py`` train())."""
    chain = []
    if cfg.clip_norm > 0:
        chain.append(optax.clip_by_global_norm(cfg.clip_norm))
    if cfg.optimizer == "sgd":
        if cfg.weight_decay > 0:
            chain.append(optax.add_decayed_weights(cfg.weight_decay))
        chain.append(
            optax.sgd(cfg.lr, momentum=cfg.momentum if cfg.momentum else None)
        )
    elif cfg.optimizer == "adam":
        chain.append(optax.adamw(cfg.lr, weight_decay=cfg.weight_decay))
    else:
        raise ValueError(f"unknown client optimizer: {cfg.optimizer}")
    return optax.chain(*chain)


# ---------------------------------------------------------------------------
# Mixed-precision casting policy (shared by both local-update builders)
# ---------------------------------------------------------------------------


def _tree_to_dtype(t: Pytree, dtype) -> Pytree:
    """Cast float leaves to the compute dtype (mixed precision: master
    params and optimizer state stay f32, the network runs in bf16 — grads
    flow back through the cast as f32)."""
    cast = lambda a: (
        a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a
    )
    return jax.tree.map(cast, t)


def _static_vars_to_dtype(static_vars: dict, dtype) -> dict:
    """batch_stats stay f32: the BN running-statistic EMA has relative
    updates below bf16 resolution (momentum 0.99 -> 1% steps), so
    quantizing the accumulator would freeze it. Flax computes the EMA in
    the stats' own dtype — keeping the stored stats f32 keeps the
    accumulation exact while activations run bf16."""
    return {
        k: (v if k == "batch_stats" else _tree_to_dtype(v, dtype))
        for k, v in static_vars.items()
    }


def _tree_floats_back(t: Pytree, compute_dtype) -> Pytree:
    cast = lambda a: (
        a.astype(jnp.float32) if a.dtype == compute_dtype else a
    )
    return jax.tree.map(cast, t)


# ---------------------------------------------------------------------------
# Local update (the client hot loop, compiled)
# ---------------------------------------------------------------------------


def _padded_perm(ekey: jax.Array, mask_row: jax.Array, max_n: int):
    """One epoch's batch order for one client: shuffle, then stable-sort
    so real samples occupy the first ceil(n_k/B) batches (shuffled among
    themselves) and trailing batches are fully padding. A small client
    thus takes exactly its serial-equivalent number of optimizer steps
    instead of scattering 1-2 real samples into many full-lr steps — and
    FedNova's tau = ceil(n_k/B)*epochs stays exact. SHARED by the vmapped
    and cohort-fused local updates: their trajectory equality depends on
    this ordering being identical."""
    perm = jax.random.permutation(ekey, max_n)
    order = jnp.argsort(1.0 - mask_row[perm], stable=True)
    return perm[order]


def build_local_update(
    model: FedModel,
    task: Task,
    cfg: TrainConfig,
    batch_size: int,
    max_n: int,
    data_axis: str | None = None,
    data_axis_size: int = 1,
    partition=None,
):
    """Build ``local_update(global_vars, idx_row, mask_row, x, y, rng)``.

    Replaces ``MyModelTrainer.train`` (reference
    ``standalone/fedavg/my_model_trainer_classification.py``): runs
    ``cfg.epochs`` passes of minibatch SGD over the client's (padded) data,
    returns ``(new_vars, n_k, train_metric_sums)``.

    ``batch_size`` and ``max_n`` are static; ``max_n`` must be a multiple of
    ``batch_size`` (the padder guarantees it). The whole function is pure and
    vmappable over the leading axis of (idx_row, mask_row, rng).

    If ``data_axis`` is set, the function must run inside a ``shard_map``
    over a mesh axis of that name: each shard consumes a disjoint
    ``batch_size // data_axis_size`` slice of every batch and gradients are
    ``psum``-ed — the TPU analog of the reference's intra-silo DDP
    (``fedavg_cross_silo/DistWorker.py:52-54``, NCCL allreduce per batch).

    ``partition`` (a :class:`fedml_tpu.peft.partition.ParamPartition`)
    restricts training to the TRAINABLE params subtree: gradients,
    optimizer state, the scan carry, and the RETURNED ``new_vars["params"]``
    all live at O(trainable) — the frozen base is closed over as a
    constant (it reaches the forward via a structural merge that costs
    nothing at runtime), takes no optimizer step, and never appears in
    the client's update. With ``partition=None`` (the default) every
    code path below is byte-identical to its pre-PEFT self.
    """
    assert max_n % batch_size == 0, (max_n, batch_size)
    assert batch_size % data_axis_size == 0, (batch_size, data_axis_size)
    steps_per_epoch = max_n // batch_size
    shard_bs = batch_size // data_axis_size
    opt = make_client_optimizer(cfg)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    mixed = compute_dtype != jnp.float32

    _to_compute = lambda t: _tree_to_dtype(t, compute_dtype)
    _to_compute_vars = lambda sv: _static_vars_to_dtype(sv, compute_dtype)
    _to_f32 = lambda t: _tree_floats_back(t, compute_dtype)

    def loss_fn(params, static_vars, x_b, y_b, w_b, rng, global_params,
                frozen_params=None):
        """Weighted-SUM loss normalized by the psum-ed weight total, so that
        psum of per-shard grads equals the exact full-batch gradient even
        with masked (padded) samples. Under a partition ``params`` is the
        trainable subtree only; the frozen base merges in structurally
        (grads flow to the trainable leaves alone)."""
        if frozen_params is not None:
            params = partition.merge(params, frozen_params)
        if mixed:
            variables = {
                **_to_compute_vars(static_vars),
                "params": _to_compute(params),
            }
            x_b = _to_compute(x_b)
        else:
            variables = {**static_vars, "params": params}
        logits, new_vars, counted = model.apply_train_counted(
            variables, x_b, rng
        )
        if mixed:
            logits = logits.astype(jnp.float32)
            new_vars = _to_f32(new_vars)
        # what the model counted this step (nothing, for most models)
        # rides the metric sums
        sums = {**task.metric_sums(logits, y_b, w_b), **counted}
        w_total = sums["w_sum"]
        if data_axis is not None:
            w_total = jax.lax.psum(w_total, data_axis)
        loss = sums["loss_sum"] / jnp.maximum(w_total, 1.0)
        if cfg.prox_mu > 0:  # FedProx proximal term (fedprox trainer)
            diff = T.tree_sub(params, global_params)
            loss = loss + 0.5 * cfg.prox_mu * T.tree_dot(diff, diff) / (
                data_axis_size
            )
        return loss, (new_vars, sums)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def local_update(global_vars, idx_row, mask_row, x, y, rng):
        global_params = global_vars["params"]
        if partition is not None:
            # frozen base: a per-round constant captured here, NOT part
            # of the scan carry or the optimizer state — under
            # vmap(local_update, in_axes=(None, ...)) it stays unbatched,
            # so no [C, model] copy of the base ever materializes
            frozen_params = partition.frozen(global_params)
            start_params = partition.trainable(global_params)
        else:
            frozen_params = None
            start_params = global_params
        start_vars = {
            **{k: v for k, v in global_vars.items() if k != "params"},
            "params": start_params,
        }

        def epoch_body(carry, ekey):
            variables, opt_state, msums = carry
            perm = _padded_perm(ekey, mask_row, max_n)

            def step_body(carry2, step):
                variables, opt_state, msums = carry2
                offset = step * batch_size
                if data_axis is not None:
                    offset = offset + jax.lax.axis_index(data_axis) * shard_bs
                with jax.named_scope("fedml.local.gather"):
                    take = jax.lax.dynamic_slice_in_dim(
                        perm, offset, shard_bs
                    )
                    b_idx = idx_row[take]
                    w_b = mask_row[take]
                    x_b = jnp.take(x, b_idx, axis=0)
                    y_b = jnp.take(y, b_idx, axis=0)
                skey = jax.random.fold_in(ekey, step)
                params = variables["params"]
                static_vars = {
                    k: v for k, v in variables.items() if k != "params"
                }
                with jax.named_scope("fedml.local.grad"):
                    (_, (new_vars, sums)), grads = grad_fn(
                        params, static_vars, x_b, y_b, w_b, skey,
                        global_params, frozen_params,
                    )
                if data_axis is not None:
                    grads = jax.lax.psum(grads, data_axis)
                    sums = jax.tree.map(
                        lambda s: jax.lax.psum(s, data_axis), sums
                    )
                    # keep batch_stats consistent across the data axis
                    # (sync-BN-lite; reference uses SynchronizedBatchNorm
                    # for fedseg, batchnorm_utils.py:240). For EXACT
                    # synchronized moments use a model built with
                    # ModelConfig(extra=(("norm", "syncbn:<data_axis>"),))
                    # — models.vision.SyncBatchNorm psums the batch
                    # statistics inside the forward; this pmean is then a
                    # no-op on its already-identical stats.
                    new_vars = {
                        k: (
                            jax.lax.pmean(v, data_axis)
                            if k == "batch_stats"
                            else v
                        )
                        for k, v in new_vars.items()
                    }
                with jax.named_scope("fedml.local.update"):
                    updates, new_opt_state = opt.update(
                        grads, opt_state, params
                    )
                    new_params = optax.apply_updates(params, updates)
                    # gate: a fully-padded batch must be a strict no-op.
                    # Uses the data-axis-psum'd weight total (sums were
                    # psum'd above) so every data shard takes the SAME
                    # branch — a shard whose slice happens to be all
                    # padding must still apply the collective update or
                    # shards silently diverge.
                    valid = sums["w_sum"] > 0
                    sel = lambda n, o: jax.tree.map(
                        lambda a, b: jnp.where(valid, a, b), n, o
                    )
                    new_variables = {**new_vars, "params": new_params}
                    out_vars = sel(new_variables, variables)
                    out_opt = sel(new_opt_state, opt_state)
                if model.counters:
                    # a fully-padded step counts nothing
                    sums = {
                        k: jnp.where(valid, v, 0.0)
                        if k in model.counters else v
                        for k, v in sums.items()
                    }
                msums = {k: msums[k] + sums[k] for k in msums}
                return (out_vars, out_opt, msums), None

            (variables, opt_state, msums), _ = jax.lax.scan(
                step_body,
                (variables, opt_state, msums),
                jnp.arange(steps_per_epoch),
                unroll=min(cfg.scan_unroll, steps_per_epoch),
            )
            return (variables, opt_state, msums), None

        opt_state = opt.init(start_params)
        msums0 = {
            **zero_sums(), **{c: jnp.asarray(0.0) for c in model.counters}
        }
        ekeys = jax.vmap(lambda e: jax.random.fold_in(rng, e))(
            jnp.arange(cfg.epochs)
        )
        # A length-1 scan still emits a while loop with loop-carry layout
        # copies; inline tiny epoch counts instead. Bounded at 2 so the
        # program size cannot blow up as epochs x scan_unroll.
        if cfg.epochs <= 2:
            carry = (start_vars, opt_state, msums0)
            for e in range(cfg.epochs):
                carry, _ = epoch_body(carry, ekeys[e])
            variables, _, msums = carry
        else:
            (variables, _, msums), _ = jax.lax.scan(
                epoch_body, (start_vars, opt_state, msums0), ekeys
            )
        n_k = jnp.sum(mask_row)
        return variables, n_k, msums

    return local_update


def cohort_update_supported(model: FedModel, cfg: TrainConfig) -> bool:
    """Whether the cohort-grouped local update can replace
    ``vmap(local_update)`` exactly. Requires architecture support (see
    :meth:`FedModel.supports_cohort`) and a client optimizer whose state
    leaves all carry the per-client leading axis (sgd/momentum; adam's
    scalar step count cannot be gated per client in stacked form).
    Gradient clipping is excluded: ``optax.clip_by_global_norm`` over the
    stacked tree would compute one cohort-joint norm, not per-client
    norms."""
    return (
        model.supports_cohort()
        and cfg.optimizer == "sgd"
        and cfg.clip_norm == 0
    )


def build_cohort_local_update(
    model: FedModel,
    task: Task,
    cfg: TrainConfig,
    batch_size: int,
    max_n: int,
    cohort: int,
):
    """Cohort-major local update: the whole sampled cohort trains inside
    ONE network application per step (:mod:`fedml_tpu.models.cohort`),
    instead of ``vmap`` of the per-client update.

    Same contract as ``vmap(build_local_update(...), in_axes=(None, 0, 0,
    None, None, 0))`` — takes (global_vars, idx_rows [C, max_n], mask_rows,
    x, y, rngs [C]), returns (stacked_vars, n_k [C], metric sums with [C]
    leaves) — and the same numerics: per-client batch order, gradients,
    masking, and BN statistics agree to f32 round-off (the grouped network
    is the per-client network re-laid-out; reductions reassociate, so
    equality is not bitwise — see tests/test_cohort_conv.py's chaos
    calibration). It exists purely because XLA lowers
    one wide grouped conv far better than a batched-kernel conv on TPU
    (measured ~3x on the ResNet-56 round; see
    :mod:`fedml_tpu.ops.cohort_conv` for numbers).

    Per-client losses are summed, so ``d(total)/d(params_c)`` is exactly
    client c's gradient. A fully-padded batch contributes zero gradient
    AND is where-gated per client (params, optimizer state, and
    batch_stats all carry the leading [C] axis outside the network), so
    padded steps remain strict no-ops, matching the vmapped path.

    Callers: ``FedAvgSim`` (``_locals``) and ``ShardedFedAvg`` (each
    shard's slice of the cohort), both through
    ``fedavg.grouped_cohort_call``: ``cohort`` is the width of ONE
    size-sorted group (``stack_utils.resolve_cohort_groups``), and the
    call runs once a group, each to its own step count.
    """
    assert max_n % batch_size == 0, (max_n, batch_size)
    steps_per_epoch = max_n // batch_size
    C = cohort
    opt = make_client_optimizer(cfg)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    mixed = compute_dtype != jnp.float32

    _to_compute = lambda t: _tree_to_dtype(t, compute_dtype)
    _to_f32 = lambda t: _tree_floats_back(t, compute_dtype)

    def loss_fn(stacked_params, static_stacked, x_cb, y_cb, w_cb, rng,
                global_params):
        if mixed:
            variables = {
                **_static_vars_to_dtype(static_stacked, compute_dtype),
                "params": _to_compute(stacked_params),
            }
            x_cb = _to_compute(x_cb)
        else:
            variables = {**static_stacked, "params": stacked_params}
        logits, new_vars = model.apply_cohort_train(variables, x_cb, rng)
        if mixed:
            logits = logits.astype(jnp.float32)
            new_vars = _to_f32(new_vars)
        sums = jax.vmap(task.metric_sums)(logits, y_cb, w_cb)  # [C] leaves
        loss = jnp.sum(
            sums["loss_sum"] / jnp.maximum(sums["w_sum"], 1.0)
        )
        if cfg.prox_mu > 0:
            diff = jax.tree.map(
                lambda p, g: p - g[None], stacked_params, global_params
            )
            loss = loss + 0.5 * cfg.prox_mu * T.tree_dot(diff, diff)
        return loss, (new_vars, sums)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def cohort_update(global_vars, idx_rows, mask_rows, x, y, rngs):
        global_params = global_vars["params"]
        stacked0 = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (C,) + v.shape), global_vars
        )

        def epoch_body(carry, ekeys):
            variables, opt_state, msums = carry

            perms = jax.vmap(lambda k, m: _padded_perm(k, m, max_n))(
                ekeys, mask_rows
            )  # [C, max_n]

            def step_body(carry2, step):
                variables, opt_state, msums = carry2
                with jax.named_scope("fedml.local.gather"):
                    take = jax.lax.dynamic_slice_in_dim(
                        perms, step * batch_size, batch_size, axis=1
                    )
                    b_idx = jnp.take_along_axis(idx_rows, take, axis=1)
                    w_b = jnp.take_along_axis(mask_rows, take, axis=1)
                    x_b = jnp.take(x, b_idx, axis=0)
                    y_b = jnp.take(y, b_idx, axis=0)
                # ONE key for the whole cohort, derived from client 0's
                # epoch key — safe only because cohort eligibility
                # (FedModel.supports_cohort) excludes stochastic layers:
                # apply_cohort_train never consumes this rng. A future
                # cohort-eligible model that does would need per-client
                # keys (vmap fold_in over ekeys) threaded into the fat
                # module instead.
                skey = jax.random.fold_in(ekeys[0], step)
                params = variables["params"]
                static_vars = {
                    k: v for k, v in variables.items() if k != "params"
                }
                with jax.named_scope("fedml.local.grad"):
                    (_, (new_vars, sums)), grads = grad_fn(
                        params, static_vars, x_b, y_b, w_b, skey,
                        global_params,
                    )
                with jax.named_scope("fedml.local.update"):
                    updates, new_opt_state = opt.update(
                        grads, opt_state, params
                    )
                    new_params = optax.apply_updates(params, updates)
                    valid = sums["w_sum"] > 0  # [C]
                    sel = lambda n, o: jax.tree.map(
                        lambda a, b: jnp.where(
                            valid.reshape((C,) + (1,) * (a.ndim - 1)),
                            a, b,
                        ),
                        n,
                        o,
                    )
                    new_variables = {**new_vars, "params": new_params}
                    out_vars = sel(new_variables, variables)
                    out_opt = sel(new_opt_state, opt_state)
                msums = {k: msums[k] + sums[k] for k in msums}
                return (out_vars, out_opt, msums), None

            # Dynamic trip count: padded trailing steps are exact no-ops
            # (zero grads + where-gating), so running only
            # ceil(max cohort n_k / B) steps is bitwise-identical and
            # skips the padding waste entirely — the worst client in the
            # POPULATION no longer taxes every round, only the worst in
            # the sampled cohort. With hetero-LDA partitions this is the
            # single largest round-time lever (population max can be many
            # times the cohort max at 1000-client scale).
            def fori_body(step, carry2):
                carry2, _ = step_body(carry2, step)
                return carry2

            variables, opt_state, msums = jax.lax.fori_loop(
                0, cohort_steps, fori_body, (variables, opt_state, msums)
            )
            return (variables, opt_state, msums), None

        cohort_steps = jnp.minimum(
            jnp.ceil(jnp.max(jnp.sum(mask_rows, axis=1)) / batch_size)
            .astype(jnp.int32),
            steps_per_epoch,
        )
        opt_state = jax.vmap(opt.init)(stacked0["params"])
        msums0 = jax.tree.map(
            lambda s: jnp.zeros((C,), s.dtype), zero_sums()
        )
        # per-client epoch keys, identical to the vmapped path's
        # fold_in(rng_c, e) derivation so trajectories match exactly
        ekeys = jax.vmap(
            lambda r: jax.vmap(
                lambda e: jax.random.fold_in(r, e)
            )(jnp.arange(cfg.epochs))
        )(rngs)  # [C, epochs]
        if cfg.epochs <= 2:
            carry = (stacked0, opt_state, msums0)
            for e in range(cfg.epochs):
                carry, _ = epoch_body(carry, ekeys[:, e])
            variables, _, msums = carry
        else:
            (variables, _, msums), _ = jax.lax.scan(
                epoch_body,
                (stacked0, opt_state, msums0),
                jnp.moveaxis(ekeys, 1, 0),
            )
        n_k = jnp.sum(mask_rows, axis=1)
        return variables, n_k, msums

    return cohort_update


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------


#: the most float32 logits one evaluation batch may hold
EVAL_LOGIT_BYTES = 256 * 2 ** 20


def build_evaluator(model: FedModel, task: Task, eval_batch: int = 256,
                    mesh=None):
    """Jitted global-test evaluation: pad to a multiple of the batch —
    ``eval_batch`` samples, or as many fewer as keep a batch's float32
    logits under :data:`EVAL_LOGIT_BYTES` (a language model's are
    ``T x vocab`` a sample: 103 MB at 2,048 x 12,544) — scan batches,
    reduce metric sums (reference ``_local_test_on_all_clients`` /
    ``test_on_server_for_all_clients``,
    ``FedAVGAggregator.py:110-164``).

    With a ``mesh`` the evaluator is ``evaluate(variables, x, y, w)``
    over rows split across EVERY device of the mesh (leading axis
    ``P(mesh.axis_names)``, ``w`` 1 for a real row and 0 for the rows
    that pad the set to a multiple of the device count): each device
    runs the same body over its own rows under ``shard_map`` with the
    variables replicated, and the metric sums are ``psum``med. Without
    one it is ``evaluate(variables, x, y)`` on whatever holds ``x``."""

    def shard_sums(variables, x, y, w=None):
        one = jax.eval_shape(model.apply_eval, variables, x[:1])
        batch = max(1, min(
            eval_batch, EVAL_LOGIT_BYTES // (4 * math.prod(one.shape))))
        n = x.shape[0]
        pad = (-n) % batch
        xp = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        yp = jnp.concatenate([y, jnp.zeros((pad,) + y.shape[1:], y.dtype)])
        w = jnp.concatenate(
            [jnp.ones((n,)) if w is None else w, jnp.zeros((pad,))])
        nb = (n + pad) // batch

        def body(sums, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(
                a, i * batch, batch
            )
            logits = model.apply_eval(variables, sl(xp))
            s = task.metric_sums(logits, sl(yp), sl(w))
            return {k: sums[k] + s[k] for k in sums}, None

        sums, _ = jax.lax.scan(body, zero_sums(), jnp.arange(nb))
        return sums

    if mesh is None:
        def evaluate(variables, x, y):
            sums = shard_sums(variables, x, y)
            return {**finalize_sums(sums), "count": sums["count"]}

        return jax.jit(evaluate)

    axes = mesh.axis_names
    rows = P(axes)

    def evaluate(variables, x, y, w):
        sums = shard_map(
            lambda *operands: jax.lax.psum(shard_sums(*operands), axes),
            mesh=mesh, in_specs=(P(), rows, rows, rows), out_specs=P(),
            check_vma=False,
        )(variables, x, y, w)
        return {**finalize_sums(sums), "count": sums["count"]}

    return jax.jit(evaluate)
