"""FedAvg family as one compiled round program.

TPU-native redesign of the reference's standalone simulator
(``fedml_api/standalone/fedavg/fedavg_api.py:40-115``) and the FedOpt /
FedProx / FedNova / robust-aggregation variants — each reference variant is a
configuration of the same compiled round:

- client sampling          (``FedAVGAggregator.client_sampling``)
- vmapped local SGD        (``FedAVGTrainer.train`` x cohort, in parallel)
- weighted pytree mean     (``FedAVGAggregator.aggregate``)
- server optimizer step    (``fedopt/FedOptAggregator`` pseudo-gradient)
- robust preprocessing     (``fedml_core/robustness/robust_aggregation.py``)
- FedNova tau-normalization(``standalone/fednova/fednova.py:97``)

One ``jax.jit`` round; all state device-resident; the python loop only
sequences rounds and reads metrics.

The server aggregation is written once, parameterized by a :class:`Reducer`
— plain in-device reduction for the single-chip simulator, ``psum`` /
``all_gather`` over the ``clients`` mesh axis for the sharded runtime
(:mod:`fedml_tpu.parallel.client_parallel`) — so the two paths cannot drift.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fedml_tpu.config import ExperimentConfig, FedConfig, TrainConfig
from fedml_tpu.core import adversary as A
from fedml_tpu.core.anatomy import ANATOMY
from fedml_tpu.core.tracing import log_span, span
from fedml_tpu.core import bulk as BK
from fedml_tpu.core import compress as C
from fedml_tpu.core import elastic as E
from fedml_tpu.core import memscope as M
from fedml_tpu.core import random as R
from fedml_tpu.core import robust, telemetry, tree as T
from fedml_tpu.core import statebank as SB
from fedml_tpu.core import streamdef as SD
from fedml_tpu import peft as PF
from fedml_tpu.peft import personal as PP
from fedml_tpu.data.federated import FederatedArrays, FederatedData, arrays_and_batch
from fedml_tpu.algorithms.base import (
    build_cohort_local_update,
    build_evaluator,
    build_local_update,
    cohort_update_supported,
    finalize_sums,
    make_task,
)
from fedml_tpu.models.base import FedModel

Pytree = Any


def consume_round_counters(train_metrics: dict) -> dict:
    """Pop device-computed counter values out of a round's metric dict
    and feed them to the process metrics registry (the round loops —
    :meth:`FedAvgSim.run` and the harness — call this where they
    already force the metrics to host, so the bench's sync-free
    ``run_round`` loop pays nothing)."""
    rej = train_metrics.pop("nonfinite_rejected", None)
    if rej is not None:
        r = float(rej)
        if r:
            telemetry.METRICS.inc("robust.nonfinite_rejected", r)
            telemetry.RECORDER.record("nonfinite_rejected", count=r,
                                      path="sim")
    res = train_metrics.pop("compress_residual_norm", None)
    if res is not None:
        # the error-feedback carry (docs/OBSERVABILITY.md): bounded ==
        # compression error is telescoping carry, not accumulating bias
        telemetry.METRICS.gauge("compress.residual_norm", float(res))
    # round-boundary device-memory sample (core/memscope.py): every
    # sim round loop funnels through here exactly once per round with
    # the metrics already forced to host — the natural boundary for
    # the live mem.* gauges. One attribute check when telemetry is off.
    M.MONITOR.sample()
    return train_metrics


def round_counters(model: FedModel, msums: dict) -> dict:
    """What the model counted in one round (``FedModel.counters``, summed
    by the local update over a client's steps) as round metrics: every
    counter's total over the cohort and, for ``client_counters``, the
    sampled clients' own values as ``<name>_by_client``. ``msums``
    leaves are ``[C]``; a ``<name>_by_client`` leaf already there (the
    bulk round scatters it block by block) is passed on."""
    out = {name: jnp.sum(msums[name]) for name in model.counters}
    for name in model.client_counters:
        out[name + "_by_client"] = msums.get(
            name + "_by_client", msums[name]
        )
    return out


class ServerState(NamedTuple):
    variables: Pytree  # full model variables (params [+ batch_stats])
    opt_state: Any  # server optimizer state
    momentum: Pytree  # global momentum buffer (FedNova gmf)
    round: jax.Array  # int32


class Reducer(NamedTuple):
    """How to reduce per-client quantities over the (possibly sharded)
    cohort. ``wmean(stacked, w)``: weighted mean over ALL clients;
    ``sum_scalar``: global scalar sum; ``gather``: full stacked tree (for
    coordinate-wise defenses); ``axis``: the mesh axis the cohort is
    sharded over (None on a local reduce) — defense rules with a
    blockwise-shardable term (the Krum gram) key their sharded fast
    path off it."""

    wmean: Callable[[Pytree, jax.Array], Pytree]
    sum_scalar: Callable[[jax.Array], jax.Array]
    gather: Callable[[Pytree], Pytree]
    axis: str | None = None


def local_reducer() -> Reducer:
    return Reducer(
        wmean=T.tree_weighted_mean,
        sum_scalar=lambda s: s,
        gather=lambda t: t,
    )


def psum_reducer(axis: str) -> Reducer:
    def wmean(stacked, w):
        n_total = jax.lax.psum(jnp.sum(w), axis)
        local = T.tree_weighted_sum(stacked, w)
        return jax.tree.map(lambda v: jax.lax.psum(v, axis) / n_total, local)

    return Reducer(
        wmean=wmean,
        sum_scalar=lambda s: jax.lax.psum(s, axis),
        gather=lambda t: jax.tree.map(
            lambda v: jax.lax.all_gather(v, axis, tiled=True), t
        ),
        axis=axis,
    )


def make_server_optimizer(name: str, lr: float, momentum: float):
    """Server optimizers (reference ``fedopt/optrepo.py:7`` reflection over
    torch optimizers; ``sgd`` with lr=1 and no momentum == plain FedAvg)."""
    if name == "sgd":
        return optax.sgd(lr, momentum=momentum if momentum else None)
    if name == "adam":
        return optax.adam(lr)
    if name == "adagrad":
        return optax.adagrad(lr)
    if name == "yogi":
        return optax.yogi(lr)
    raise ValueError(f"unknown server optimizer: {name}")


def server_update(
    fed: FedConfig,
    train: TrainConfig,
    steps_per_epoch: int,
    batch_size: int,
    state: ServerState,
    stacked_vars: Pytree,
    n_k: jax.Array,
    rkey: jax.Array,
    red: Reducer,
    valid: jax.Array | None = None,
) -> ServerState:
    """One server step from stacked client results. Shared between the
    single-device and mesh-sharded rounds (reference equivalents:
    ``FedAVGAggregator.aggregate``, ``FedOptAggregator``,
    ``fednova.py`` tau-normalized averaging, ``RobustAggregator``).

    ``valid`` (``[C]`` bool, possibly traced) marks the live rows of a
    bucket-padded elastic cohort (:mod:`fedml_tpu.core.elastic`):
    padded rows carry the global variables (delta exactly zero) and
    weight 0, and every defense rule masks them out — the aggregate
    depends only on the live rows (content-blind, pinned bitwise in
    ``tests/test_elastic.py``) while the compiled program's shapes —
    and therefore the XLA cache — depend only on the bucket."""
    global_params = state.variables["params"]
    deltas = jax.tree.map(
        lambda s, g: s - g[None], stacked_vars["params"], global_params
    )

    # the full defense stack (core/robust.py): clip each delta, reduce
    # under the configured rule (mean/median/trimmed_mean/krum/
    # multikrum/fltrust), then noise the aggregate. The default
    # pipeline (mean, clip 0, noise 0) is byte-identical to the plain
    # weighted mean.
    pipe = robust.DefensePipeline.from_fed(fed)
    deltas = pipe.preprocess(deltas)

    robust.check_fednova_compat(fed.algorithm, pipe.method)
    if fed.algorithm == "fednova":
        # tau_k = true local steps (real-first batch ordering makes this
        # exact); d_k = delta_k / tau_k; delta = tau_eff * sum p_k d_k.
        # Padded rows are weight-0 everywhere n_k appears, so they
        # vanish from n_total, tau_eff, and the weighted mean exactly.
        tau = (
            jnp.ceil(n_k / batch_size).clip(1, steps_per_epoch)
            * train.epochs
        )
        n_total = red.sum_scalar(jnp.sum(n_k))
        tau_eff = red.sum_scalar(jnp.sum(n_k * tau)) / n_total
        d = jax.tree.map(
            lambda v: v / tau.reshape((-1,) + (1,) * (v.ndim - 1)), deltas
        )
        agg_delta = T.tree_scale(red.wmean(d, n_k), tau_eff)
    else:
        agg_delta = pipe.reduce(deltas, n_k, red, valid)

    agg_delta = pipe.postprocess(agg_delta, jax.random.fold_in(rkey, 1))
    new_params, new_opt_state, new_momentum = _server_delta_step(
        fed, state, agg_delta
    )

    # non-param collections (batch_stats): plain weighted mean, like the
    # reference's full-state_dict averaging (FedAVGAggregator.py:73-81)
    other = {
        k: red.wmean(v, n_k)
        for k, v in stacked_vars.items()
        if k != "params"
    }
    return ServerState(
        variables={**other, "params": new_params},
        opt_state=new_opt_state,
        momentum=new_momentum,
        round=state.round + 1,
    )


def _server_delta_step(fed: FedConfig, state: ServerState,
                       agg_delta: Pytree):
    """The post-reduce server tail — global momentum buffer (FedNova
    gmf) + server optimizer step — shared verbatim by the stacked
    (:func:`server_update`) and streaming
    (:func:`server_update_from_partials`) aggregation paths, so the two
    cannot drift past the reduce itself. Returns ``(new_params,
    new_opt_state, new_momentum)``."""
    global_params = state.variables["params"]
    if fed.gmf > 0:
        new_momentum = T.tree_add(
            T.tree_scale(state.momentum, fed.gmf), agg_delta
        )
        agg_delta = new_momentum
    else:
        new_momentum = state.momentum

    opt = make_server_optimizer(
        fed.server_optimizer, fed.server_lr, fed.server_momentum
    )
    pseudo_grad = T.tree_scale(agg_delta, -1.0)
    updates, new_opt_state = opt.update(
        pseudo_grad, state.opt_state, global_params
    )
    new_params = optax.apply_updates(global_params, updates)
    return new_params, new_opt_state, new_momentum


def fold_block_partials(
    fed: FedConfig,
    train: TrainConfig,
    steps_per_epoch: int,
    batch_size: int,
    state: ServerState,
    stacked_vars: Pytree,
    n_k: jax.Array,
    msums: dict,
    rejected: jax.Array,
) -> BK.RoundPartials:
    """Reduce ONE block of (injected/healed/screened) stacked local
    results to its O(model) :class:`~fedml_tpu.core.bulk.RoundPartials`
    — the streaming half of :func:`server_update`. Mirrors the stacked
    reduce head exactly: delta against the global params, defense
    preprocess (per-row clip), FedNova's per-row tau normalization.
    Weighted sums ride ``T.tree_weighted_sum`` (the same f32
    accumulator ``tree_weighted_mean`` uses), so bulk-vs-stacked parity
    is the reduce-reassociation ulp band and nothing more (pinned in
    ``tests/test_bulk.py``)."""
    pipe = robust.DefensePipeline.from_fed(fed)
    global_params = state.variables["params"]
    deltas = jax.tree.map(
        lambda s, g: s - g[None], stacked_vars["params"], global_params
    )
    deltas = pipe.preprocess(deltas)
    nf = n_k.astype(jnp.float32)
    if fed.algorithm == "fednova":
        tau = (
            jnp.ceil(n_k / batch_size).clip(1, steps_per_epoch)
            * train.epochs
        )
        deltas = jax.tree.map(
            lambda v: v / tau.reshape((-1,) + (1,) * (v.ndim - 1)),
            deltas,
        )
        tau_wsum = jnp.sum(nf * tau)
    else:
        tau_wsum = jnp.zeros((), jnp.float32)

    return BK.RoundPartials(
        delta_wsum=T.tree_weighted_sum(deltas, nf),
        other_wsum={
            k: T.tree_weighted_sum(v, nf)
            for k, v in stacked_vars.items()
            if k != "params"
        },
        n_sum=jnp.sum(nf),
        tau_wsum=tau_wsum,
        msums=jax.tree.map(jnp.sum, msums),
        rejected=rejected,
    )


def server_update_from_partials(
    fed: FedConfig,
    state: ServerState,
    partials: BK.RoundPartials,
    rkey: jax.Array,
    agg_delta: Pytree | None = None,
) -> ServerState:
    """One server step from GLOBALLY-reduced streaming partials — the
    bulk twin of :func:`server_update`, sharing its exact tail
    (:func:`_server_delta_step`). ``partials`` must already be summed
    over every block (and every shard: the mesh runtime psums the
    O(model) partials before calling this, replacing the stacked
    wmean/gather collectives). The ``mean``/FedNova reduce rules fold
    their aggregate out of ``partials`` directly; a streamed defense
    (:mod:`fedml_tpu.core.streamdef`) passes the sketch-decided
    ``agg_delta`` override instead — the non-param collections still
    reduce as weighted means of the partials, exactly what the stacked
    reducer does under any defense rule. The assert is the
    traced-program backstop for a reduce rule that is neither."""
    pipe = robust.DefensePipeline.from_fed(fed)
    assert (pipe.method in BK.BULK_REDUCE_RULES
            or agg_delta is not None), pipe.method
    global_params = state.variables["params"]
    # the same max(Σw, 1e-12) guard tree_weighted_mean applies, so the
    # degenerate all-zero-weight round degrades identically
    denom = jnp.maximum(partials.n_sum, 1e-12)
    if agg_delta is None:
        agg_delta = jax.tree.map(
            lambda s, g: (s / denom).astype(g.dtype),
            partials.delta_wsum, global_params,
        )
        if fed.algorithm == "fednova":
            # tau_eff = Σ n·tau / Σ n, exactly the stacked formula with
            # both sums pre-reduced
            agg_delta = T.tree_scale(
                agg_delta, partials.tau_wsum / partials.n_sum
            )
    else:
        agg_delta = jax.tree.map(
            lambda d, g: d.astype(g.dtype), agg_delta, global_params
        )
    agg_delta = pipe.postprocess(agg_delta, jax.random.fold_in(rkey, 1))
    new_params, new_opt_state, new_momentum = _server_delta_step(
        fed, state, agg_delta
    )
    other = {
        k: jax.tree.map(
            lambda s, g: (s / denom).astype(g.dtype),
            v, state.variables[k],
        )
        for k, v in partials.other_wsum.items()
    }
    return ServerState(
        variables={**other, "params": new_params},
        opt_state=new_opt_state,
        momentum=new_momentum,
        round=state.round + 1,
    )


# canonical implementations live in stack_utils (shared with the GAN
# family's vmapped path); re-exported here for the established import
# path
from fedml_tpu.algorithms.stack_utils import (  # noqa: E402
    resolve_cohort_groups as _resolve_cohort_groups,
    size_grouped_lanes as _size_grouped_lanes,
)


def grouped_cohort_call(
    cohort_update, groups: int, variables, idx_rows, mask_rows, x, y, ckeys,
    traced_once: bool = False,
):
    """Run the fused cohort update in ``groups`` size-sorted sub-groups
    (``FedAvgSim._locals`` over the sampled cohort;
    ``ShardedFedAvg._sharded_round`` over a shard's slice of it, with
    the groups under ``lax.map``: ``traced_once``, see
    ``stack_utils.size_grouped_lanes``).

    Clients are sorted by sample count (descending) so each sub-group's
    dynamic trip count is set by ITS largest member, not the cohort's;
    results are unsorted back so callers see cohort order. Each client's
    trajectory depends only on (globals, its rows, its key) — sorting and
    grouping change scheduling, not numerics (same equality class as the
    fused-vs-vmapped comparison, tests/test_cohort_conv.py). ``groups``
    was resolved at build time against the SAME cohort size the fused
    update was compiled for, so the helper's re-resolution is a no-op
    here (a lane-count mismatch would fail loudly on the update's
    static shapes regardless)."""
    if groups == 1:
        return cohort_update(variables, idx_rows, mask_rows, x, y, ckeys)
    return _size_grouped_lanes(
        lambda i, m, k: cohort_update(variables, i, m, x, y, k),
        (idx_rows, mask_rows, ckeys), mask_rows, groups,
        traced_once=traced_once,
    )


class FedAvgSim:
    """Compiled federated simulation on one chip (see
    :mod:`fedml_tpu.parallel` for the mesh-sharded version)."""

    def __init__(
        self,
        model: FedModel,
        data: FederatedData,
        cfg: ExperimentConfig,
        sampler=None,
    ):
        # cohort sampler: (key, num_clients, clients_per_round) -> ids.
        # Default = global uniform without replacement; the sharded runtime's
        # equality tests pass R.sample_clients_stratified to mirror its
        # per-shard sampling on one device.
        self.sampler = sampler or R.sample_clients
        self.cfg = cfg
        # surfaced at construction instead of the first traced round
        robust.check_fednova_compat(cfg.fed.algorithm,
                                    cfg.fed.robust_method)
        # -- parameter-efficient fine-tuning (fedml_tpu.peft, docs/
        # PERFORMANCE.md "Parameter-efficient federated fine-tuning"):
        # with cfg.fed.peft='lora' the model's targeted projections are
        # wrapped with zero-init low-rank branches and the rounds below
        # train/aggregate ONLY the adapter + head subtree — the frozen
        # base never grows an optimizer state, a delta, or a wire
        # payload. Off by default: build_peft returns the model
        # untouched and every path stays byte-identical.
        model, self._peft = PF.build_peft(model, cfg)
        self.model = model
        # personalization bank: a client-id-keyed ClientStateBank
        # (core/statebank.py), created lazily on the first round;
        # `_adapter_bank` exposes its raw rows for callers
        self._bank_adapter = None
        self.task = make_task(data.task)
        self._prepare_data(data, cfg)
        # token-model sanity: an embed table smaller than the data's
        # id space makes XLA CLAMP every out-of-range lookup — the
        # run trains and reports metrics on silently corrupted
        # gathers. Surface it here, where both sides are known.
        vocab = getattr(self.model.module, "vocab_size", None)
        if (self.task.name == "nwp" and vocab is not None
                and vocab < self.arrays.num_classes):
            raise ValueError(
                f"model vocab_size {vocab} < the dataset's token-id "
                f"space {self.arrays.num_classes}: out-of-range "
                "embedding lookups clamp silently. Set --num_classes "
                "(or model extra vocab_size) to the dataset's vocab "
                f"({self.arrays.num_classes})."
            )
        max_n = self.arrays.max_client_samples
        self.steps_per_epoch = max_n // self.batch_size
        self.local_update = build_local_update(
            model, self.task, cfg.train, self.batch_size, max_n,
            partition=self._peft.part if self._peft else None,
        )
        # cohort-grouped fast path: run the whole cohort as ONE widened
        # network instead of vmapping per-client nets (same numerics,
        # ~3x on conv models — see fedml_tpu.models.cohort). Explicitly
        # disabled with TrainConfig(cohort_fused=False).
        cohort = min(cfg.fed.clients_per_round, cfg.data.num_clients)
        # -- elastic shape bucketing (core/elastic.py, docs/
        # FAULT_TOLERANCE.md "Elastic membership"): the round program is
        # compiled for the power-of-two BUCKET above the cohort, with
        # the live count a traced operand — set_cohort_size() then
        # changes the cohort within the bucket without a recompile.
        # Padded slots run masked local updates (weight 0, params
        # healed to the global model) that provably cannot perturb any
        # aggregation rule. Off by default: the static path stays
        # byte-identical to its pre-elastic self.
        self._elastic = bool(cfg.fed.elastic_buckets)
        if self._elastic and sampler is not None:
            # the bucketed round draws a full-bucket permutation whose
            # live PREFIX is the cohort (_sample_bucket) — a
            # (key, n, k) sampler cannot express that contract, and
            # silently ignoring it would report uniform-sampling
            # results under the user's sampler's name
            raise ValueError(
                "elastic_buckets=True is incompatible with a custom "
                "cohort sampler: the compiled bucketed round draws its "
                "own full-bucket permutation (core/elastic.py). "
                "Disable elastic buckets or drop the sampler."
            )
        self._bucket = (
            min(E.bucket_for(cohort), cfg.data.num_clients)
            if self._elastic else cohort
        )
        self._n_active = cohort
        # -- bulk-client streaming (core/bulk.py, docs/PERFORMANCE.md
        # "Bulk-client execution"): with cfg.fed.client_block_size = B
        # the round streams the cohort through the device in blocks of
        # B vmapped local updates, each folded into an O(model)
        # partial-sum scan carry — peak memory O(B + model), not O(C).
        # Selection defenses stream as two-pass sketches
        # (core/streamdef.py); compression and personalization keep
        # their per-client state in client-id-keyed ClientStateBanks
        # (core/statebank.py) riding the scan carry. Off by default:
        # the stacked round stays byte-identical.
        self._bulk = BK.BulkSpec.from_fed(cfg.fed)
        self._stream_defense = (
            cfg.fed.robust_method
            if (self._bulk.enabled()
                and cfg.fed.robust_method in SD.STREAM_METHODS)
            else None
        )
        if self._bulk.enabled():
            BK.check_bulk_compat(cfg.fed, cfg.adversary)
            self._block_size = self._bulk.block_size
            # elastic buckets apply to the BLOCK COUNT: the compiled
            # scan length is the power-of-two bucket of ceil(C/B)
            # blocks, so cohort churn within it is a cache hit
            self._n_blocks = BK.plan_blocks(
                cohort, self._block_size, self._elastic
            )
            self._slots = self._n_blocks * self._block_size
            # the live cohort can grow into the headroom blocks, but
            # never past the population (sampling is w/o replacement)
            self._max_live = min(self._slots, cfg.data.num_clients)
        self._cohort_groups = _resolve_cohort_groups(
            cfg.train.cohort_groups, cohort
        )
        self._cohort_update = (
            build_cohort_local_update(
                model, self.task, cfg.train, self.batch_size, max_n,
                cohort // self._cohort_groups,
            )
            if cfg.train.cohort_fused
            and cohort_update_supported(model, cfg.train)
            # the cohort-grouped network bakes the cohort size into its
            # widened layer shapes — bucketing covers the vmapped path
            and not self._elastic
            # the bulk engine streams the VMAPPED update per block (the
            # widened cohort network would bake C back into one program)
            and not self._bulk.enabled()
            # the partitioned local update is the vmapped builder's
            # (no cohort-eligible architecture is LoRA-injectable
            # today; stated rather than assumed)
            and self._peft is None
            else None
        )
        self.evaluator = build_evaluator(model, self.task)
        self.root_key = jax.random.key(cfg.seed)
        # -- wire compression (core/compress.py, docs/PERFORMANCE.md
        # "Wire compression"): with cfg.fed.compress the round applies
        # the exact compress->decompress arithmetic the deploy wire
        # sees — per-slot, inside the compiled round, with the
        # error-feedback residual carried across rounds as a donated
        # [bucket, ...] operand. Off by default: the dense round is
        # byte-identical (no extra operand, no residual allocation).
        self._cspec = C.CompressionSpec.from_fed(cfg.fed, seed=cfg.seed)
        self._ef_residual = None  # lazy zero carry, [bucket, ...]
        # bulk mode keeps the EF carry in a client-id-keyed
        # ClientStateBank instead of the slot-keyed [bucket, ...] carry
        # (the residual follows the CLIENT across rounds; core/
        # statebank.py) — also created lazily, checkpointed alongside
        # the adapter bank (bank_state/restore_banks)
        self._ef_bank = None
        if self._peft is not None and self._peft.personalized:
            # the private adapter bank rides as a donated operand
            # (arg 4 of _round) exactly like the EF residual would —
            # compress+personalize is rejected, so the two never
            # coexist
            donate = (0, 4)
        elif self._cspec.enabled():
            donate = (0, 3)
        else:
            donate = (0,)
        # the round program is an instrumented AOT site
        # (core/memscope.py): compiles are explicit .lower().compile()
        # calls — byte-identical lowering to a first jit call — so
        # every compile is timed (mem.compile_s.sim_round), its
        # memory_analysis recorded (mem.program.*), and the donated
        # state/residual audited is_deleted after the first execution.
        # ProgramSite exposes _cache_size, so the elastic paths'
        # mirror_jit_cache accounting is unchanged. Bulk rounds get
        # their own program family (sim_bulk.<blocks>.<B>) so the
        # mem.program.* accounting and the donation audit name the
        # block program distinctly from the stacked one.
        family = "sim_bulk" if self._bulk.enabled() else "sim_round"
        self._round_fn = M.ProgramSite(self._round, family=family,
                                       donate_argnums=donate)
        # -- fused multi-round execution (core/fuse.py, docs/
        # PERFORMANCE.md "Round fusion"): with fuse_rounds K > 1 ONE
        # compiled program runs K complete rounds as a lax.scan over
        # the round body — ServerState (and the error-feedback
        # residual) ride as donated scan carries, per-round train
        # metrics stack into [K, ...] outputs the driver consumes once
        # per block. Cohort sampling folds in the CARRIED round
        # counter, so the sampled cohorts are bitwise-identical to the
        # unfused loop's. K = 1 (the default) never builds the block
        # program: the per-round path stays byte-identical.
        fuse = cfg.fed.fuse_rounds
        self._fuse = 1 if fuse is None else int(fuse)
        if self._fuse < 1:
            raise ValueError(
                f"fuse_rounds must be >= 1, got {cfg.fed.fuse_rounds}"
            )
        # the sharded runtime rebinds this to its shard_map'd round so
        # the SAME fused-block scan wraps either body
        self._round_impl = self._round
        self._block_fn = (
            M.ProgramSite(
                self._fused_block,
                family=(
                    "sim_bulk_block" if self._bulk.enabled()
                    else "sim_block"
                ),
                static_argnums=(5,), donate_argnums=donate,
            )
            if self._fuse > 1 else None
        )
        # process-global headroom threshold for the memory monitor
        # (--mem_headroom_warn; docs/OBSERVABILITY.md "Memory &
        # compilation")
        M.MONITOR.headroom_warn = float(
            getattr(cfg.fed, "mem_headroom_warn", 0.9) or 0.9
        )

    def _prepare_data(self, data: FederatedData, cfg: ExperimentConfig):
        """Resolve device data + batch size: the whole population and
        the test set on the default device, where the round and
        ``evaluate_global`` read them. The mesh-sharded subclass
        overrides this: its global arrays stay host-side, its training
        data lives in per-shard banks and its test set split over the
        mesh."""
        self.arrays, self.batch_size = arrays_and_batch(data, cfg.data)

    # -- initialization ----------------------------------------------------
    def init(self) -> ServerState:
        variables = self.model.init(
            jax.random.fold_in(self.root_key, 0x7FFFFFFF)
        )
        opt = make_server_optimizer(
            self.cfg.fed.server_optimizer,
            self.cfg.fed.server_lr,
            self.cfg.fed.server_momentum,
        )
        # PEFT: server optimizer state + momentum live at the
        # AGGREGATED subtree's shape only (adapters + head, or the
        # shared head under personalization) — the frozen base never
        # grows server-side state
        opt_params = (
            variables["params"] if self._peft is None
            else self._peft.agg_part.trainable(variables["params"])
        )
        if self._peft is not None:
            self._note_peft(variables)
        return ServerState(
            variables=variables,
            opt_state=opt.init(opt_params),
            # the FedNova buffer exists only where gmf asks for one: at
            # gmf 0 it would be a model-sized tree nothing reads
            momentum=(
                T.tree_zeros_like(opt_params) if self.cfg.fed.gmf > 0
                else ()
            ),
            round=jnp.asarray(0, jnp.int32),
        )

    def _note_peft(self, variables) -> None:
        """Host-side PEFT accounting at init (docs/OBSERVABILITY.md
        ``peft.*`` vocabulary) — one attribute check when telemetry
        is off."""
        m = telemetry.METRICS
        if not m.enabled:
            return
        params = variables["params"]
        trainable, frozen = self._peft.counts(params)
        m.gauge("peft.trainable_params", float(trainable))
        m.gauge("peft.frozen_params", float(frozen))
        m.gauge(
            "peft.adapter_wire_mb",
            self._peft.adapter_wire_bytes(params) / 1e6,
        )
        m.gauge(
            "peft.wire_ratio",
            PF.compound_wire_ratio(self._peft, self._cspec, params),
        )

    # -- elastic cohort control (core/elastic.py) --------------------------
    def set_cohort_size(self, n: int) -> None:
        """Change the live cohort size for subsequent rounds WITHOUT a
        recompile, as long as ``n`` fits the compiled bucket — the
        simulator face of elastic membership (a churn schedule walks
        this up and down; docs/FAULT_TOLERANCE.md "Elastic
        membership")."""
        if not self._elastic:
            raise ValueError(
                "set_cohort_size requires FedConfig(elastic_buckets="
                "True) — the static round program bakes the cohort "
                "size into its shapes"
            )
        if self._bulk.enabled():
            # bulk mode buckets the BLOCK COUNT: any cohort within the
            # compiled block grid reuses the one scan program
            if not (1 <= n <= self._max_live):
                raise ValueError(
                    f"cohort size {n} does not fit the compiled "
                    f"{self._n_blocks}x{self._block_size} block grid "
                    f"(live cohort must stay in [1, {self._max_live}]; "
                    "grow needs a new simulator)"
                )
            self._n_active = n
            return
        if not (1 <= n <= self._bucket):
            raise ValueError(
                f"cohort size {n} does not fit the compiled bucket "
                f"{self._bucket} (grow needs a new simulator; within "
                f"[1, {self._bucket}] changes are free)"
            )
        self._n_active = n

    def _sample_bucket(self, key, num_clients: int) -> jax.Array:
        """Sample BUCKET client ids; the live prefix of the draw is the
        round's cohort (the active mask hides the rest)."""
        if self._bucket >= num_clients:
            # a permutation, not arange: the active mask keeps the live
            # PREFIX of this draw, so a fixed order would pin the same
            # first-n_active clients into every round once the bucket
            # covers the whole population
            return jax.random.permutation(key, num_clients).astype(
                jnp.int32
            )
        return jax.random.choice(
            key, num_clients, shape=(self._bucket,), replace=False
        ).astype(jnp.int32)

    def _sample_slot_ids(self, key, num_clients: int) -> jax.Array:
        """Elastic-bulk sampling: ``[slots]`` client ids whose live
        PREFIX is the round's cohort (the bulk twin of
        :meth:`_sample_bucket` — a permutation when the grid covers the
        population, so the live prefix never pins the same clients).
        Slots beyond the population are dead by construction
        (``_max_live``) and carry the out-of-range SENTINEL id
        (``num_clients``) so they can never alias a real client's bank
        row (core/statebank.py sentinel padding)."""
        draw = min(self._slots, num_clients)
        if draw >= num_clients:
            ids = jax.random.permutation(key, num_clients).astype(
                jnp.int32
            )
        else:
            ids = jax.random.choice(
                key, num_clients, shape=(draw,), replace=False
            ).astype(jnp.int32)
        return SB.pad_ids(ids, self._slots, num_clients)

    # -- one round ---------------------------------------------------------
    def _locals(self, state: ServerState, arrays: FederatedArrays,
                n_active=None):
        """Sampling + local updates, the pre-aggregation prefix of the
        round: returns (stacked_vars, n_k, metric sums, round key,
        cohort). Shared with aggregation rules that live outside the
        compiled round (e.g. TurboAggregate secure aggregation,
        :class:`fedml_tpu.algorithms.mpc.SecureFedAvgSim`) so alternate
        servers cannot drift from the canonical sampling/local math.
        The sampled cohort rides the return value so consumers (the
        adversary injection gate) never re-derive the draw."""
        cfg = self.cfg.fed
        with jax.named_scope("fedml.sample"):
            rkey = R.round_key(self.root_key, state.round)
            if n_active is not None:
                cohort = self._sample_bucket(
                    jax.random.fold_in(rkey, 0), arrays.num_clients
                )
            else:
                cohort = self.sampler(
                    jax.random.fold_in(rkey, 0),
                    arrays.num_clients,
                    cfg.clients_per_round,
                )
            ckeys = jax.vmap(lambda c: R.client_key(rkey, c))(cohort)
        with jax.named_scope("fedml.local"):
            idx_rows = arrays.idx[cohort]
            mask_rows = arrays.mask[cohort]
            if self._cohort_update is not None:
                stacked_vars, n_k, msums = grouped_cohort_call(
                    self._cohort_update,
                    self._cohort_groups,
                    state.variables,
                    idx_rows,
                    mask_rows,
                    arrays.x,
                    arrays.y,
                    ckeys,
                )
            else:
                stacked_vars, n_k, msums = jax.vmap(
                    self.local_update,
                    in_axes=(None, 0, 0, None, None, 0),
                )(state.variables, idx_rows, mask_rows, arrays.x,
                  arrays.y, ckeys)
        return stacked_vars, n_k, msums, rkey, cohort

    def _inject_adversaries(self, state, arrays, stacked_vars, cohort):
        """Seeded Byzantine injection (core/adversary.py): adversarial
        cohort slots get their params replaced by ``global + attacked
        delta``; honest slots keep their EXACT local-update output (the
        select happens at the variables level, so no honest value is
        rewritten through a subtract/add round trip). ``cohort`` is the
        draw `_locals` actually used — never re-derived."""
        adv = self.cfg.adversary
        mask = A.cohort_mask(adv, cohort, arrays.num_clients)
        gp = state.variables["params"]
        deltas = jax.tree.map(
            lambda s, g: s - g[None], stacked_vars["params"], gp
        )
        # cohort keys the gauss draw per (round, client id) — chunking-
        # independent, so the bulk engine's per-block injection is
        # bitwise-equal to the stacked round at matched seeds
        attacked = A.corrupt_stacked_deltas(
            adv, deltas, state.round, cohort
        )
        params = jax.tree.map(
            lambda s, g, a: jnp.where(
                mask.reshape((-1,) + (1,) * (s.ndim - 1)),
                (g[None] + a).astype(s.dtype),
                s,
            ),
            stacked_vars["params"], gp, attacked,
        )
        return {**stacked_vars, "params": params}

    def _screen_nonfinite(self, state, stacked_vars, n_k):
        """NaN/Inf screening on the simulator path — the same contract
        as the deploy-path message handler (``_result_is_finite``): a
        poisoned result must never enter the aggregate. Static shapes
        cannot drop a row, so a screened client is replaced by the
        global model (delta exactly 0 — a neutral no-op vote for the
        coordinate defenses) with zero aggregation weight. All-finite
        cohorts pass through byte-identically (``where(True, x, _) is
        x`` value-wise)."""
        ok = robust.finite_client_mask(stacked_vars, n_k)

        def heal(s, g):
            m = ok.reshape((-1,) + (1,) * (s.ndim - 1))
            return jnp.where(m, s, g[None].astype(s.dtype))

        cleaned = jax.tree.map(heal, stacked_vars, state.variables)
        n_k = jnp.where(ok, n_k, jnp.zeros_like(n_k))
        rejected = (ok.shape[0] - jnp.sum(ok)).astype(jnp.float32)
        return cleaned, n_k, rejected

    def _wire_roundtrip(self, state, stacked_vars, residual, rkey,
                        live):
        """The in-round wire model (core/compress.py): delta each
        slot's variables against the global model, fold in the
        error-feedback carry, compress->decompress with the SAME
        arithmetic the deploy wire applies, and rebuild the variables
        from the decompressed delta. Padded slots of an elastic bucket
        get their carry zeroed (a slot that just left the live prefix
        must not smuggle its stale residual into a healed row's
        content)."""
        gp = state.variables
        deltas = jax.tree.map(
            lambda s, g: s - g[None], stacked_vars, gp
        )
        deq, new_residual = C.roundtrip_stacked(
            self._cspec, deltas, residual, rkey
        )
        stacked_vars = jax.tree.map(
            lambda g, d: (g[None] + d).astype(d.dtype), gp, deq
        )
        if live is not None:
            new_residual = jax.tree.map(
                lambda r: jnp.where(
                    live.reshape((-1,) + (1,) * (r.ndim - 1)),
                    r, jnp.zeros((), r.dtype),
                ),
                new_residual,
            )
        return stacked_vars, new_residual

    def _bulk_round(self, state: ServerState, arrays: FederatedArrays,
                    n_active=None, ef_bank=None, adapter_bank=None):
        """The block-streamed round body (core/bulk.py,
        docs/PERFORMANCE.md "Bulk-client execution"): sample the
        cohort, chunk it into ``block_size`` slots, run each block
        through the SAME vmapped local update / adversary injection /
        wire roundtrip / padding-heal / non-finite screen the stacked
        round applies, and fold each block's
        :func:`fold_block_partials` into the O(model) scan carry. Peak
        memory is O(block + model + sketch) — no ``[C, ...]`` stacked
        operand ever materializes. The final server step is
        :func:`server_update_from_partials`, which shares
        :func:`server_update`'s exact post-reduce tail.

        ``ef_bank`` (the compression error-feedback
        :class:`~fedml_tpu.core.statebank.ClientStateBank`) and
        ``adapter_bank`` (the PEFT personalization bank) ride the scan
        carry and come back updated; compress+personalize stays
        rejected, so at most one is non-None. A streamed defense
        (:mod:`fedml_tpu.core.streamdef`) turns the body into TWO
        passes over the same blocks: pass 1 folds partials + the
        defense sketch (EF rows read-only), the selection/quantile
        decision is made from the sketch, pass 2 folds the decided
        aggregate (and performs the authoritative EF write — both
        passes recompute the identical deterministic local updates, so
        the roundtrip inputs match bitwise)."""
        cfg = self.cfg.fed
        rkey = R.round_key(self.root_key, state.round)
        skey = jax.random.fold_in(rkey, 0)
        # PEFT view: partials, healing, and the server step fold only
        # the aggregated subtree (local updates keep the FULL state —
        # the frozen base is needed for the forward pass)
        view = (
            state if self._peft is None
            else self._peft.view_state(state)
        )
        with jax.named_scope("fedml.sample"):
            if n_active is not None:
                # elastic: full-grid draw, live prefix = the traced
                # cohort
                ids = self._sample_slot_ids(skey, arrays.num_clients)
                live = E.active_mask(self._slots, n_active)
            else:
                # static: the SAME draw the stacked round makes
                # (parity), tail slots padded with the out-of-range
                # sentinel id (a pad slot must never alias a real
                # client's bank row)
                cohort = self.sampler(
                    skey, arrays.num_clients, cfg.clients_per_round
                )
                pad = self._slots - cohort.shape[0]
                ids = SB.pad_ids(
                    cohort, self._slots, arrays.num_clients
                )
                live = (
                    E.active_mask(self._slots, cohort.shape[0])
                    if pad else None
                )
        if adapter_bank is not None:
            return self._bulk_personal(
                state, view, arrays, ids, live, rkey, adapter_bank
            )

        def local_block(block_ids, block_live, bank, write_bank=True):
            """The stacked round's pre-aggregation prefix, one block at
            a time: vmapped local updates, adversary injection, wire
            roundtrip against the gathered EF rows, pad heal,
            non-finite screen. Returns ``(stacked_vars, n_k, msums,
            rejected, new_bank)`` — ``new_bank`` None unless ``bank``
            rode in and ``write_bank`` held."""
            with jax.named_scope("fedml.sample"):
                ckeys = jax.vmap(
                    lambda c: R.client_key(rkey, c)
                )(block_ids)
            with jax.named_scope("fedml.local"):
                idx_rows = arrays.idx[block_ids]
                mask_rows = arrays.mask[block_ids]
                if self._block_size == 1:
                    # a block of ONE client is that client's update, not
                    # a map of width 1 over it: kernels keep their own
                    # lowering and nothing is batched
                    stacked_vars, n_k, msums = jax.tree.map(
                        lambda a: a[None],
                        self.local_update(
                            state.variables, idx_rows[0], mask_rows[0],
                            arrays.x, arrays.y, ckeys[0],
                        ),
                    )
                else:
                    stacked_vars, n_k, msums = jax.vmap(
                        self.local_update,
                        in_axes=(None, 0, 0, None, None, 0),
                    )(state.variables, idx_rows, mask_rows, arrays.x,
                      arrays.y, ckeys)
            with jax.named_scope("fedml.defense_agg"):
                return heal_block(
                    block_ids, block_live, bank, write_bank,
                    stacked_vars, n_k, msums,
                )

        def heal_block(block_ids, block_live, bank, write_bank,
                       stacked_vars, n_k, msums):
            """`local_block`'s post-training half: everything between
            a block's local updates and its fold."""
            if self.cfg.adversary.enabled():
                stacked_vars = self._inject_adversaries(
                    view, arrays, stacked_vars, block_ids
                )
            rows = new_rows = None
            if bank is not None:
                # the in-round wire model against the CLIENT-keyed EF
                # carry (compress.roundtrip_rows): gather this block's
                # rows, roundtrip, scatter back below once the screen
                # has decided which rows survive
                gp = view.variables
                rows = bank.gather(block_ids)
                deltas = jax.tree.map(
                    lambda s, g: s - g[None], stacked_vars, gp
                )
                deq, new_rows = C.roundtrip_rows(
                    self._cspec, deltas, rows, rkey, block_ids
                )
                stacked_vars = jax.tree.map(
                    lambda g, d: (g[None] + d).astype(d.dtype), gp, deq
                )
            if block_live is not None:
                # padded slots (partial final block / elastic headroom)
                # healed exactly like a bucketed stacked round's
                stacked_vars, n_k, msums = E.mask_padded(
                    stacked_vars, n_k, msums, view.variables,
                    block_live,
                )
            ok = robust.finite_client_mask(stacked_vars, n_k)
            stacked_vars, n_k, rejected = self._screen_nonfinite(
                view, stacked_vars, n_k
            )
            new_bank = None
            if bank is not None and write_bank:
                # a poisoned (or non-live) slot keeps its pre-round EF
                # row — the carry follows the CLIENT, not the slot;
                # sentinel pad ids are dropped by the scatter
                keep = ok if block_live is None else ok & block_live
                new_bank = bank.put(
                    block_ids, new_rows, keep=keep, gathered=rows
                )
            return stacked_vars, n_k, msums, rejected, new_bank

        def partials_of(sv, n_k, msums, rejected):
            with jax.named_scope("fedml.defense_agg"):
                return fold_block_partials(
                    cfg, self.cfg.train, self.steps_per_epoch,
                    self.batch_size, view, sv, n_k, msums, rejected,
                )

        by_client = self.model.client_counters
        if self._stream_defense is None:
            if ef_bank is None:
                def fold_block(block_ids, block_live, block_pos=None):
                    sv, n_k, msums, rej, _ = local_block(
                        block_ids, block_live, None
                    )
                    p = partials_of(sv, n_k, msums, rej)
                    if block_pos is None:
                        return p
                    # a sampled client's own count, at its slot: the
                    # blocks' vectors add up to the cohort's
                    return p._replace(msums={**p.msums, **{
                        name + "_by_client": jnp.zeros(
                            ids.shape, jnp.float32
                        ).at[block_pos].set(msums[name])
                        for name in by_client
                    }})

                partials = BK.stream_blocks(
                    fold_block, ids, live, self._block_size,
                    positions=bool(by_client),
                )
                new_ef = None
            else:
                def fold_block(block_ids, block_live, bank):
                    sv, n_k, msums, rej, bank = local_block(
                        block_ids, block_live, bank
                    )
                    return partials_of(sv, n_k, msums, rej), bank

                partials, new_ef = BK.stream_blocks(
                    fold_block, ids, live, self._block_size,
                    banks=ef_bank,
                )
            agg_delta = None
        else:
            partials, agg_delta, new_ef = self._defended_fold(
                view, ids, live, rkey, ef_bank, local_block,
                partials_of,
            )

        with jax.named_scope("fedml.server_update"):
            new_state = server_update_from_partials(
                cfg, view, partials, rkey, agg_delta=agg_delta
            )
            if self._peft is not None:
                new_state = self._peft.merge_state(new_state, state)
            fin = finalize_sums(partials.msums)
        train_metrics = {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            "nonfinite_rejected": partials.rejected,
        }
        if self.model.counters:
            train_metrics.update(
                round_counters(self.model, partials.msums)
            )
        if new_ef is not None:
            return new_state, train_metrics, new_ef
        return new_state, train_metrics

    def _defended_fold(self, view, ids, live, rkey, ef_bank,
                       local_block, partials_of):
        """The two-pass streamed-defense body (core/streamdef.py):
        pass 1 folds ``(RoundPartials, sketch)`` with the EF rows read
        from the UNCHANGED operand bank (no write — the authoritative
        roundtrip happens in pass 2, recomputing identical inputs), the
        defense decision is made from the sketch in-program, pass 2
        folds the decided aggregate (per-coordinate histogram for the
        quantile rules; selection-weighted delta sum for the projection
        rules) and writes the EF bank. Returns ``(partials, agg_delta,
        new_ef_bank)``."""
        cfg = self.cfg.fed
        pipe = robust.DefensePipeline.from_fed(cfg)
        method = pipe.method
        quantile = method in SD.QUANTILE_METHODS
        gp = view.variables["params"]

        def block_deltas(sv):
            # the defenses see the same per-row preprocessed (clipped)
            # deltas the stacked reducer sees
            return pipe.preprocess(jax.tree.map(
                lambda s, g: s - g[None], sv["params"], gp
            ))

        def live_votes(block_live, n_k):
            # quantile rules vote over LIVE rows — a screened client
            # votes its healed zero delta, matching the stacked
            # reducer's valid=live membership
            if block_live is None:
                return jnp.ones(n_k.shape, jnp.float32)
            return block_live.astype(jnp.float32)

        def fold_pass1(block_ids, block_live, block_pos):
            sv, n_k, msums, rej, _ = local_block(
                block_ids, block_live, ef_bank, write_bank=False
            )
            p = partials_of(sv, n_k, msums, rej)
            deltas = block_deltas(sv)
            lv = live_votes(block_live, n_k)
            if quantile:
                sk = SD.fold_moments(SD.flatten_rows(deltas), lv)
            else:
                sk = SD.fold_proj(
                    deltas, n_k.astype(jnp.float32), lv, block_pos,
                    self._slots, rkey,
                )
            return p, sk

        partials, sketch = BK.stream_blocks(
            fold_pass1, ids, live, self._block_size, positions=True
        )

        if quantile:
            lo, width = SD.hist_edges(sketch)

            def block_hist(sv, n_k, block_live):
                return SD.fold_hist(
                    SD.flatten_rows(block_deltas(sv)),
                    live_votes(block_live, n_k), lo, width,
                )

            if ef_bank is None:
                def fold_pass2(block_ids, block_live, block_pos):
                    sv, n_k, *_unused = local_block(
                        block_ids, block_live, None
                    )
                    return block_hist(sv, n_k, block_live)

                hist = BK.stream_blocks(
                    fold_pass2, ids, live, self._block_size,
                    positions=True,
                )
                new_ef = None
            else:
                def fold_pass2(block_ids, block_live, block_pos, bank):
                    sv, n_k, _m, _r, bank = local_block(
                        block_ids, block_live, bank
                    )
                    return block_hist(sv, n_k, block_live), bank

                hist, new_ef = BK.stream_blocks(
                    fold_pass2, ids, live, self._block_size,
                    banks=ef_bank, positions=True,
                )
            if method == "median":
                est = SD.median_from_hist(
                    hist, lo, width, sketch.count
                )
            else:
                est = SD.trimmed_mean_from_hist(
                    hist, lo, width, sketch.count,
                    SD.trim_table(pipe.trim_frac, self._slots),
                )
            return partials, T.tree_unvectorize(est, gp), new_ef

        w, den = SD.selection_weights(
            method, sketch, pipe.num_adversaries, pipe.multikrum_m
        )

        def block_wsum(sv, block_pos):
            return T.tree_weighted_sum(block_deltas(sv), w[block_pos])

        if ef_bank is None:
            def fold_pass2(block_ids, block_live, block_pos):
                sv, *_unused = local_block(block_ids, block_live, None)
                return block_wsum(sv, block_pos)

            wsum = BK.stream_blocks(
                fold_pass2, ids, live, self._block_size, positions=True
            )
            new_ef = None
        else:
            def fold_pass2(block_ids, block_live, block_pos, bank):
                sv, _n, _m, _r, bank = local_block(
                    block_ids, block_live, bank
                )
                return block_wsum(sv, block_pos), bank

            wsum, new_ef = BK.stream_blocks(
                fold_pass2, ids, live, self._block_size,
                banks=ef_bank, positions=True,
            )
        return partials, T.tree_scale(wsum, 1.0 / den), new_ef

    def _bulk_personal(self, state, view, arrays, ids, live, rkey,
                       bank):
        """Personalized PEFT at bulk scale (fedml_tpu.peft.personal ×
        core/bulk.py): each block gathers its clients' private adapter
        rows from the :class:`~fedml_tpu.core.statebank.
        ClientStateBank`, trains with them merged into the shared
        model, folds the SHARED half into :class:`~fedml_tpu.core.bulk.
        RoundPartials`, and scatters the trained rows back through the
        scan carry. The no-leak contract is structural exactly as in
        :meth:`_personal_round` — the aggregate simply does not contain
        the private paths — and the non-finite screen covers BOTH
        halves: a poisoned client contributes nothing to the shared
        aggregate AND keeps its pre-round bank row."""
        cfg = self.cfg.fed
        plan = self._peft
        base_frozen = plan.private.frozen(state.variables["params"])

        def fold_block(block_ids, block_live, bk):
            priv = bk.gather(block_ids)
            ckeys = jax.vmap(lambda c: R.client_key(rkey, c))(block_ids)

            def one(priv_row, idx_row, mask_row, key):
                params_c = plan.private.merge(priv_row, base_frozen)
                vars_c = {**state.variables, "params": params_c}
                out_vars, n_k, msums = self.local_update(
                    vars_c, idx_row, mask_row, arrays.x, arrays.y, key
                )
                trained = out_vars["params"]
                shared = {
                    **{k: v for k, v in out_vars.items()
                       if k != "params"},
                    "params": plan.private.frozen(trained),
                }
                return (shared, plan.private.trainable(trained), n_k,
                        msums)

            shared, new_priv, n_k, msums = jax.vmap(one)(
                priv, arrays.idx[block_ids], arrays.mask[block_ids],
                ckeys,
            )
            if block_live is not None:
                shared, n_k, msums = E.mask_padded(
                    shared, n_k, msums, view.variables, block_live
                )
            # the screen covers BOTH halves; a non-live slot is already
            # healed and zero-weight, so only live non-finite rows
            # count as rejections (and only live finite rows write
            # their bank row)
            ok = robust.finite_client_mask(
                {"shared": shared, "private": new_priv}, n_k
            )
            lv = (
                jnp.ones(ok.shape, bool) if block_live is None
                else block_live
            )
            ok = ok | ~lv

            def heal(s, g):
                m = ok.reshape((-1,) + (1,) * (s.ndim - 1))
                return jnp.where(m, s, g[None].astype(s.dtype))

            shared = jax.tree.map(heal, shared, view.variables)
            n_k = jnp.where(ok, n_k, jnp.zeros_like(n_k))
            rejected = (ok.shape[0] - jnp.sum(ok)).astype(jnp.float32)
            bk = bk.put(block_ids, new_priv, keep=ok & lv,
                        gathered=priv)
            p = fold_block_partials(
                cfg, self.cfg.train, self.steps_per_epoch,
                self.batch_size, view, shared, n_k, msums, rejected,
            )
            return p, bk

        partials, bank = BK.stream_blocks(
            fold_block, ids, live, self._block_size, banks=bank
        )
        new_view = server_update_from_partials(
            cfg, view, partials, rkey
        )
        new_state = plan.merge_state(new_view, state)
        fin = finalize_sums(partials.msums)
        train_metrics = {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            "nonfinite_rejected": partials.rejected,
        }
        return new_state, train_metrics, bank

    def _personal_round(self, state: ServerState,
                        arrays: FederatedArrays, bank, n_active=None):
        """Personalized PEFT round (fedml_tpu.peft.personal,
        docs/PERFORMANCE.md "Parameter-efficient federated
        fine-tuning"): each sampled client trains with ITS OWN private
        adapter row merged into the shared model; only the shared
        (head) subtree is aggregated, and the trained adapter rows are
        scattered back into the bank. The no-leak contract is
        structural: the aggregated view simply does not contain the
        private paths, and the bank scatter writes each row from its
        own client's update only. ``bank`` is the adapter
        :class:`~fedml_tpu.core.statebank.ClientStateBank`; with
        ``n_active`` (elastic buckets) the draw is the full-bucket
        permutation and non-live slots are healed to zero weight AND
        keep their pre-round bank rows. Returns ``(state, metrics,
        bank)``."""
        cfg = self.cfg.fed
        plan = self._peft
        rkey = R.round_key(self.root_key, state.round)
        if n_active is not None:
            cohort = self._sample_bucket(
                jax.random.fold_in(rkey, 0), arrays.num_clients
            )
            live = E.active_mask(self._bucket, n_active)
        else:
            cohort = self.sampler(
                jax.random.fold_in(rkey, 0),
                arrays.num_clients,
                cfg.clients_per_round,
            )
            live = None
        ckeys = jax.vmap(lambda c: R.client_key(rkey, c))(cohort)
        priv_rows = bank.gather(cohort)
        base_frozen = plan.private.frozen(state.variables["params"])

        def one(priv, idx_row, mask_row, key):
            params_c = plan.private.merge(priv, base_frozen)
            vars_c = {**state.variables, "params": params_c}
            out_vars, n_k, msums = self.local_update(
                vars_c, idx_row, mask_row, arrays.x, arrays.y, key
            )
            trained = out_vars["params"]  # adapters + head, pruned
            shared = {
                **{k: v for k, v in out_vars.items() if k != "params"},
                "params": plan.private.frozen(trained),
            }
            return shared, plan.private.trainable(trained), n_k, msums

        stacked_shared, new_priv, n_k, msums = jax.vmap(one)(
            priv_rows, arrays.idx[cohort], arrays.mask[cohort], ckeys
        )

        view = plan.view_state(state)
        if live is not None:
            # elastic: non-live slots healed to the global shared view
            # with zero weight before the screen, like the dense path
            stacked_shared, n_k, msums = E.mask_padded(
                stacked_shared, n_k, msums, view.variables, live
            )
        # the non-finite screen covers BOTH halves of a client's
        # update: a poisoned client contributes nothing to the shared
        # aggregate AND keeps its pre-round bank row (the private twin
        # of the dense path's heal-to-global). Non-live slots are
        # already healed/zero-weight — they are not rejections, and
        # they keep their pre-round rows too.
        ok = robust.finite_client_mask(
            {"shared": stacked_shared, "private": new_priv}, n_k
        )
        lv = jnp.ones(ok.shape, bool) if live is None else live
        ok = ok | ~lv

        def heal(s, g):
            m = ok.reshape((-1,) + (1,) * (s.ndim - 1))
            return jnp.where(m, s, g)

        stacked_shared = jax.tree.map(
            lambda s, g: heal(s, g[None].astype(s.dtype)),
            stacked_shared, view.variables,
        )
        n_k = jnp.where(ok, n_k, jnp.zeros_like(n_k))
        rejected = (ok.shape[0] - jnp.sum(ok)).astype(jnp.float32)

        new_view = server_update(
            cfg, self.cfg.train, self.steps_per_epoch,
            self.batch_size, view, stacked_shared, n_k, rkey,
            local_reducer(), valid=live,
        )
        new_state = plan.merge_state(new_view, state)
        new_bank = bank.put(cohort, new_priv, keep=ok & lv,
                            gathered=priv_rows)
        fin = finalize_sums(jax.tree.map(jnp.sum, msums))
        train_metrics = {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            "nonfinite_rejected": rejected,
        }
        return new_state, train_metrics, new_bank

    def _round(self, state: ServerState, arrays: FederatedArrays,
               n_active=None, residual=None, bank=None):
        if self._bulk.enabled():
            # in bulk mode the residual slot carries the EF
            # ClientStateBank and the bank slot the adapter bank —
            # never both (compress+personalize stays rejected); the
            # python-level dispatch keeps the stacked trace below
            # byte-identical when bulk is off
            return self._bulk_round(
                state, arrays, n_active, ef_bank=residual,
                adapter_bank=bank,
            )
        if bank is not None:
            # personalized PEFT: private adapter bank in, bank out
            # (fedml_tpu.peft.personal; compress+personalize is
            # rejected at construction, so residual is None)
            return self._personal_round(state, arrays, bank, n_active)
        cfg = self.cfg.fed
        stacked_vars, n_k, msums, rkey, cohort = self._locals(
            state, arrays, n_active
        )
        # PEFT: the aggregation half of the round sees the pruned VIEW
        # of the state — deltas, healing, the wire model, and the
        # server step are all O(aggregated subtree); the frozen base is
        # re-merged bitwise at the end (fedml_tpu.peft.partition).
        # Without peft the view IS the state: zero added work.
        view = (
            state if self._peft is None
            else self._peft.view_state(state)
        )

        with jax.named_scope("fedml.defense_agg"):
            if self.cfg.adversary.enabled():
                stacked_vars = self._inject_adversaries(
                    view, arrays, stacked_vars, cohort
                )
            live = (
                E.active_mask(self._bucket, n_active)
                if n_active is not None else None
            )
            new_residual = None
            if residual is not None:
                # wire order mirrors the deploy path: the client
                # compresses its (possibly adversarial) delta, THEN the
                # server pads / screens what it decompressed
                stacked_vars, new_residual = self._wire_roundtrip(
                    view, stacked_vars, residual, rkey, live
                )
            if live is not None:
                # elastic bucketing: the padded slots beyond the live
                # cohort are healed to the global model (delta exactly
                # 0) with zero weight BEFORE screening, so downstream
                # they are indistinguishable from absent — and they
                # must not pollute the round's train metrics either
                stacked_vars, n_k, msums = E.mask_padded(
                    stacked_vars, n_k, msums, view.variables, live
                )
            stacked_vars, n_k, rejected = self._screen_nonfinite(
                view, stacked_vars, n_k
            )

        with jax.named_scope("fedml.server_update"):
            new_state = server_update(
                cfg,
                self.cfg.train,
                self.steps_per_epoch,
                self.batch_size,
                view,
                stacked_vars,
                n_k,
                rkey,
                local_reducer(),
                valid=live,
            )
            if self._peft is not None:
                new_state = self._peft.merge_state(new_state, state)
            reduced = jax.tree.map(jnp.sum, msums)
            fin = finalize_sums(reduced)
        train_metrics = {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            # LAST so rate_bench's first-value sync stays train_loss;
            # consumed host-side by consume_round_counters (the
            # robust.nonfinite_rejected counter)
            "nonfinite_rejected": rejected,
        }
        if self.model.counters:
            train_metrics.update(round_counters(self.model, msums))
        if new_residual is not None:
            train_metrics["compress_residual_norm"] = T.tree_l2_norm(
                new_residual
            )
            return new_state, train_metrics, new_residual
        return new_state, train_metrics

    def _fused_block(self, state: ServerState, operand, n_active=None,
                     residual=None, bank=None, length: int = 1):
        """``length`` complete rounds as ONE program: a ``lax.scan``
        over the round body with (state[, EF residual / adapter bank])
        as the carry. Each iteration derives its round key from the
        CARRIED ``state.round`` (``_locals`` folds it in), so sampling,
        adversary injection, and the compression quantizer draws are
        bitwise-identical to ``length`` separate ``_round`` calls —
        only XLA's cross-iteration fusion may reassociate float sums
        (the PR-5/PR-7 band, pinned in tests/test_fuse.py). The
        elastic live count is a scan-invariant traced operand: churn
        mid-block is impossible by construction — ``set_cohort_size``
        lands at the next block boundary. Metric leaves stack to
        ``[length, ...]``."""
        if residual is not None:
            def body(carry, _):
                s, res = carry
                s, m, res = self._round_impl(s, operand, n_active, res)
                return (s, res), m

            (state, residual), ms = jax.lax.scan(
                body, (state, residual), None, length=length
            )
            return state, ms, residual
        if bank is not None:
            def body(carry, _):
                s, bk = carry
                s, m, bk = self._round_impl(
                    s, operand, n_active, None, bk
                )
                return (s, bk), m

            (state, bank), ms = jax.lax.scan(
                body, (state, bank), None, length=length
            )
            return state, ms, bank

        def body(carry, _):
            s, m = self._round_impl(carry, operand, n_active)
            return s, m

        state, ms = jax.lax.scan(body, state, None, length=length)
        return state, ms

    def _round_operand(self):
        """Device operand the round body trains from (the sharded
        runtime overrides this with its per-shard banks)."""
        return self.arrays

    def run_block(self, state: ServerState, length: int):
        """Run ``length`` complete rounds as one compiled block
        (:meth:`_fused_block`); returns ``(state, metrics)`` with every
        metric leaf stacked ``[length, ...]``. Requires
        ``FedConfig(fuse_rounds > 1)`` — the block program is built at
        construction. Distinct ``length`` values are distinct compiles
        (``core.fuse.plan_blocks`` keeps the set tiny: the configured K
        plus the remainders eval/checkpoint boundaries force)."""
        if self._block_fn is None:
            raise ValueError(
                "run_block requires FedConfig(fuse_rounds > 1) — the "
                "fused block program is built at construction"
            )
        bulk = self._bulk.enabled()
        compressed = self._cspec.enabled()
        personalized = (
            self._peft is not None and self._peft.personalized
        )
        if personalized:
            self._ensure_adapter_bank(state)
        if compressed:
            if bulk:
                self._ensure_ef_bank(state)
            elif self._ef_residual is None:
                self._ef_residual = C.zero_residual(
                    self._wire_template(state.variables), self._bucket
                )
                telemetry.METRICS.gauge(
                    "compress.ratio",
                    C.wire_ratio(self._cspec,
                                 self._wire_template(state.variables)),
                )
        operand = self._round_operand()
        n = (
            jnp.asarray(self._n_active, jnp.int32)
            if self._elastic else None
        )
        if bulk:
            # nested scans: the outer fused-round scan wraps the inner
            # block scan (the bulk round IS _round_impl's body here);
            # the fused block counts its K rounds so bulk.rounds stays
            # per-round like every fused metric
            self._note_bulk_dispatch(rounds=length)
            if self._stream_defense is not None:
                self._note_stream_defense(state)
            key = self._program_key() + (length,)
        else:
            key = (self._bucket, length)
        res = None
        if compressed:
            res = self._ef_bank if bulk else self._ef_residual

        def call():
            return self._block_fn(
                key, state, operand, n, res,
                self._bank_adapter if personalized else None, length,
            )

        out = (
            E.mirror_jit_cache(self._block_fn, call)
            if self._elastic else call()
        )
        if compressed:
            state, m, new_res = out
            if bulk:
                self._ef_bank = new_res
                SB.note_round_io(
                    length * self._n_blocks
                    * (2 if self._stream_defense else 1),
                    length * self._n_blocks,
                )
            else:
                self._ef_residual = new_res
            return state, m
        if personalized:
            state, m, self._bank_adapter = out
            SB.note_round_io(
                length * (self._n_blocks if bulk else 1),
                length * (self._n_blocks if bulk else 1),
            )
            return state, m
        return out

    def _program_key(self) -> tuple:
        """Executable identity of the bulk round program: the compiled
        block grid. (Only meaningful with the bulk engine on; the
        stacked paths key by bucket as they always have.)"""
        return (self._n_blocks, self._block_size)

    def _note_bulk_dispatch(self, rounds: int = 1) -> None:
        BK.note_round(
            self._block_size, self._n_blocks,
            self._slots - self._n_active, rounds=rounds,
        )

    def _note_stream_defense(self, state: ServerState) -> None:
        """``defense.sketch_*`` gauges at bulk dispatch
        (docs/OBSERVABILITY.md) — one attribute check when off."""
        if not telemetry.METRICS.enabled:
            return
        variables = (
            state.variables if self._peft is None
            else self._peft.view_state(state).variables
        )
        flat_dim = sum(
            int(v.size) for v in jax.tree.leaves(variables["params"])
        )
        SD.note_defense(self._stream_defense, flat_dim, self._slots)

    # -- client-state banks (core/statebank.py) ----------------------------
    @property
    def _adapter_bank(self):
        """Raw ``[num_clients, ...]`` adapter rows (None before the
        first personalized round) — the established surface
        :func:`fedml_tpu.peft.personal.personal_variables` and the
        personalization tests consume; internally the rows live in a
        :class:`~fedml_tpu.core.statebank.ClientStateBank`."""
        b = self._bank_adapter
        return None if b is None else b.rows

    @_adapter_bank.setter
    def _adapter_bank(self, rows):
        self._bank_adapter = (
            None if rows is None
            else SB.ClientStateBank("adapter", rows)
        )

    def _ensure_adapter_bank(self, state: ServerState) -> None:
        """Create the personalization bank LAZILY on the first round
        (from the CURRENT state's init-valued adapters) so that the
        repo's re-call-init()-for-a-snapshot idiom can never reset a
        trained bank mid-run; its lifetime is the simulator's."""
        if self._bank_adapter is not None:
            return
        rows = PP.init_bank(
            self._peft, state.variables["params"],
            self.arrays.num_clients,
        )
        self._bank_adapter = SB.ClientStateBank("adapter", rows)
        telemetry.METRICS.gauge(
            "peft.personal_bank_mb", PP.bank_bytes(rows) / 1e6
        )
        SB.note_bank(self._bank_adapter)

    def _ensure_ef_bank(self, state: ServerState) -> None:
        """Create the bulk-mode error-feedback bank lazily: one zero
        row per CLIENT of the wire template (round 0 transmits the
        uncorrected delta, exactly like the stacked zero carry)."""
        if self._ef_bank is not None:
            return
        self._ef_bank = SB.ClientStateBank.zeros(
            "ef_residual", self._wire_template(state.variables),
            self.arrays.num_clients,
        )
        telemetry.METRICS.gauge(
            "compress.ratio",
            C.wire_ratio(self._cspec,
                         self._wire_template(state.variables)),
        )
        SB.note_bank(self._ef_bank)

    def bank_state(self) -> dict:
        """Client-state banks for the checkpoint composite
        (docs/FAULT_TOLERANCE.md "Client-state banks"): ``{name:
        savable rows}``, empty when no bank has been created yet (a
        fresh run has nothing to save — and nothing to restore)."""
        out = {}
        if self._bank_adapter is not None:
            out[self._bank_adapter.name] = self._bank_adapter.savable()
        if self._ef_bank is not None:
            out[self._ef_bank.name] = self._ef_bank.savable()
        return out

    def restore_banks(self, state: ServerState, blob) -> None:
        """Adopt checkpointed bank rows (the restore half of
        :meth:`bank_state`). A None/empty or legacy blob — or a blob
        from a run without this bank — leaves the lazy fresh-bank init
        in place instead of crashing: the run resumes with round-0
        rows, which is exactly what a pre-bank checkpoint encoded."""
        if not blob:
            return
        if ("adapter" in blob and self._peft is not None
                and self._peft.personalized):
            self._ensure_adapter_bank(state)
            self._bank_adapter = SB.ClientStateBank.from_savable(
                "adapter", self._bank_adapter.rows, blob["adapter"]
            )
        if ("ef_residual" in blob and self._bulk.enabled()
                and self._cspec.enabled()):
            self._ensure_ef_bank(state)
            self._ef_bank = SB.ClientStateBank.from_savable(
                "ef_residual", self._ef_bank.rows, blob["ef_residual"]
            )

    def _wire_template(self, variables):
        """What one client's update payload looks like on the wire:
        the full variables, or the aggregated PEFT subtree — the
        error-feedback residual and the codec accounting are sized by
        this (an O(cohort x adapter) carry under peft, never
        O(cohort x model))."""
        return (
            variables if self._peft is None
            else self._peft.agg_variables(variables)
        )

    def _anatomy_path(self) -> str:
        """The anatomy ring's round-body label (docs/OBSERVABILITY.md
        "Round anatomy"); ``ShardedFedAvg`` overrides it."""
        if self._bulk.enabled():
            return "bulk"
        if self._peft is not None and self._peft.personalized:
            return "personal"
        return "stacked"

    # -- public API --------------------------------------------------------
    def run_round(self, state: ServerState):
        if self._bulk.enabled():
            self._note_bulk_dispatch()
            if self._stream_defense is not None:
                self._note_stream_defense(state)
            key = self._program_key()
            n = (
                jnp.asarray(self._n_active, jnp.int32)
                if self._elastic else None
            )
            if self._peft is not None and self._peft.personalized:
                self._ensure_adapter_bank(state)

                def call():
                    return self._round_fn(
                        key, state, self.arrays, n, None,
                        self._bank_adapter,
                    )

                state, m, self._bank_adapter = (
                    E.mirror_jit_cache(self._round_fn, call)
                    if self._elastic else call()
                )
                SB.note_round_io(self._n_blocks, self._n_blocks)
                return state, m
            if self._cspec.enabled():
                self._ensure_ef_bank(state)

                def call():
                    return self._round_fn(
                        key, state, self.arrays, n, self._ef_bank
                    )

                state, m, self._ef_bank = (
                    E.mirror_jit_cache(self._round_fn, call)
                    if self._elastic else call()
                )
                SB.note_round_io(
                    self._n_blocks
                    * (2 if self._stream_defense else 1),
                    self._n_blocks,
                )
                return state, m
            if not self._elastic:
                return self._round_fn(key, state, self.arrays)
            return E.mirror_jit_cache(
                self._round_fn,
                lambda: self._round_fn(key, state, self.arrays, n),
            )
        if self._peft is not None and self._peft.personalized:
            # the bank is a donated operand and comes back updated —
            # the same thread-through discipline as the EF residual
            self._ensure_adapter_bank(state)
            n = (
                jnp.asarray(self._n_active, jnp.int32)
                if self._elastic else None
            )

            def call():
                return self._round_fn(
                    self._bucket, state, self.arrays, n, None,
                    self._bank_adapter,
                )

            state, m, self._bank_adapter = (
                E.mirror_jit_cache(self._round_fn, call)
                if self._elastic else call()
            )
            SB.note_round_io(1, 1)
            return state, m
        compressed = self._cspec.enabled()
        if compressed and self._ef_residual is None:
            self._ef_residual = C.zero_residual(
                self._wire_template(state.variables), self._bucket
            )
            telemetry.METRICS.gauge(
                "compress.ratio",
                C.wire_ratio(self._cspec,
                             self._wire_template(state.variables)),
            )
        key = self._bucket
        if not self._elastic:
            if not compressed:
                return self._round_fn(key, state, self.arrays)
            state, m, self._ef_residual = self._round_fn(
                key, state, self.arrays, None, self._ef_residual
            )
            return state, m
        # the live count rides as a TRACED operand: any cohort size in
        # [1, bucket] reuses the one compiled program; the ProgramSite
        # is the executable store here
        n = jnp.asarray(self._n_active, jnp.int32)
        if not compressed:
            return E.mirror_jit_cache(
                self._round_fn,
                lambda: self._round_fn(key, state, self.arrays, n),
            )
        state, m, self._ef_residual = E.mirror_jit_cache(
            self._round_fn,
            lambda: self._round_fn(
                key, state, self.arrays, n, self._ef_residual
            ),
        )
        return state, m

    def _global_eval(self):
        """The evaluator of the global test set and its operands after
        the variables, where and in the layout that evaluator consumes
        them: here the test set as ``_prepare_data`` placed it on the
        chip; ``ShardedFedAvg`` answers with its mesh's."""
        return self.evaluator, (self.arrays.test_x, self.arrays.test_y)

    def evaluate_global(self, state: ServerState) -> dict:
        evaluator, operands = self._global_eval()
        # h2d_bytes is a COUNT read off the operands, not a timing: what
        # this call re-sends from the host. 0 for both simulators (the
        # test set lives on the chip, or split over the mesh); anything
        # else means an operand was left as host numpy
        h2d = sum(a.nbytes for a in operands if isinstance(a, np.ndarray))
        with span("fedml.eval", phase="eval", h2d_bytes=h2d):
            m = evaluator(state.variables, *operands)
            return {k: float(v) for k, v in m.items()}

    def evaluate_train(self, state: ServerState) -> dict:
        m = self.evaluator(state.variables, self.arrays.x, self.arrays.y)
        return {k: float(v) for k, v in m.items()}

    def run(self, metrics_sink=None) -> ServerState:
        """Round loop (reference ``fedavg_api.train``,
        ``standalone/fedavg/fedavg_api.py:40-81``). With
        ``cfg.fed.profile_rounds > 0`` the perf-observability layer
        (core/perf.py) rides along: jax-profiler capture windows around
        the first K rounds (device-time breakdown) and live ``perf.*``
        gauges — round rate, MFU from the shared analytic cost model,
        and the dispatch-bound detector — for every round. The round
        wall time is taken AFTER the metric host conversion forces the
        device, so it measures execution, not dispatch. With
        ``cfg.fed.fuse_rounds > 1`` the loop advances in fused blocks
        with pipelined host consumption (:meth:`_run_fused`)."""
        import time as _time

        from fedml_tpu.core import perf as P

        state = self.init()
        profiler, monitor = P.build_sim_perf(self)
        try:
            if self._fuse > 1:
                return self._run_fused(
                    state, metrics_sink, profiler, monitor
                )
            # ONE set of boundaries (core/tracing.span): each span is a
            # profiler annotation, a ring event under --trace, and —
            # where it names a phase — the anatomy plane's clock. They
            # sit at sync points this loop ALREADY has (the dispatch
            # return, the one batched device_get), so the off path is
            # a flag check a span and nothing adds a device sync
            path = self._anatomy_path()
            for r in range(self.cfg.fed.num_rounds):
                t0 = _time.perf_counter()
                if profiler is not None:
                    # before the span opens: an annotation is kept
                    # only if its session was on when it began
                    profiler.start_round(r)
                with span("fedml.round", round=r):
                    ANATOMY.begin_round(r, path=path)
                    # enqueue (and any retrace); lands in host_gap
                    with span("fedml.dispatch"):
                        state, train_m = self.run_round(state)
                    # ONE batched D2H for the whole metric dict instead
                    # of a device sync per leaf: the host blocked on the
                    # compiled round's execution (the sims run the whole
                    # round as one program, so `local` carries it)
                    with span("fedml.fetch", phase="local"):
                        train_m = consume_round_counters(
                            jax.device_get(dict(train_m))
                        )
                    record = {
                        "round": r,
                        **{k: float(v) if np.ndim(v) == 0
                           else [float(u) for u in v]
                           for k, v in train_m.items()},
                    }
                    if profiler is not None:
                        profiler.end_round(r)
                    if monitor is not None:
                        monitor.note_round(_time.perf_counter() - t0)
                    if (r + 1) % self.cfg.fed.eval_every == 0 or (
                        r == self.cfg.fed.num_rounds - 1
                    ):
                        test_m = self.evaluate_global(state)
                        record.update(
                            {"test_acc": test_m["acc"],
                             "test_loss": test_m["loss"]}
                        )
                    if metrics_sink is not None:
                        with log_span(record):
                            metrics_sink.log(record)
                    ANATOMY.end_round()
        finally:
            if profiler is not None:
                profiler.finish()
        return state

    def _run_fused(self, state, metrics_sink, profiler, monitor):
        """Fused round loop (docs/PERFORMANCE.md "Round fusion"):
        advance in blocks of up to ``fuse_rounds`` rounds, keeping
        block k+1's dispatch in flight while the host converts block
        k's stacked metrics (one batched transfer per block), and
        syncing only at eval boundaries and profiler-capture windows.
        The loop itself is ``core.fuse.drive`` (shared with the
        harness's fused loop); boundary placement
        (``core.fuse.plan_blocks``) guarantees eval runs on exactly
        the same round's state as the unfused loop, even when
        ``eval_every % fuse_rounds != 0``."""
        from fedml_tpu.core import fuse as F

        cfg = self.cfg.fed
        box = [state]

        def run_block(length):
            box[0], dm = self.run_block(box[0], length)
            return dm

        def make_records(start, rows):
            return [
                {"round": start + i,
                 **{k: float(v) for k, v in
                    consume_round_counters(row).items()}}
                for i, row in enumerate(rows)
            ]

        def log(rec):
            if metrics_sink is not None:
                with log_span(rec):
                    metrics_sink.log(rec)

        def boundary_hook(r_last, last):
            if (r_last + 1) % cfg.eval_every == 0 or (
                r_last == cfg.num_rounds - 1
            ):
                # the block's anatomy entry closed at the pipeline
                # flush; fuse.drive runs this hook in amending mode, so
                # the fedml.eval span's phase lands on that entry
                test_m = self.evaluate_global(box[0])
                last.update({"test_acc": test_m["acc"],
                             "test_loss": test_m["loss"]})
            log(last)

        F.drive(
            run_block,
            F.plan_blocks(0, cfg.num_rounds, self._fuse,
                          cfg.eval_every),
            profiler=profiler,
            monitor=monitor,
            make_records=make_records,
            log=log,
            boundary_hook=boundary_hook,
        )
        return box[0]
