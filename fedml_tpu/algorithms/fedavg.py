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
from fedml_tpu.core.tracing import build_span, span
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

    @build_span
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
        # -- the ONE per-client carry a configuration threads between
        # rounds, as the round body's last operand and last result
        # (`_round`): "adapter", the personalization ClientStateBank
        # (core/statebank.py); "ef_residual", the error-feedback carry —
        # slot-keyed [bucket, ...] rows on the stacked round, a
        # client-id-keyed ClientStateBank in bulk mode (the residual
        # follows the CLIENT across rounds); or None. Never two:
        # compress+personalize is rejected. Created lazily on the first
        # round (`_ensure_carry`); the banks are checkpointed
        # (bank_state/restore_banks)
        self._carry_kind = (
            "adapter" if self._peft is not None and self._peft.personalized
            else "ef_residual" if self._cspec.enabled()
            else None
        )
        self._carry = None
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
        self._round_fn = M.ProgramSite(
            self._round, family=family,
            donate_argnums=self._donate_argnums(),
        )
        # -- fused multi-round execution (core/fuse.py, docs/
        # PERFORMANCE.md "Round fusion"): with fuse_rounds K > 1 ONE
        # compiled program runs K complete rounds as a lax.scan over
        # the round body — ServerState (and the per-client carry)
        # ride as donated scan carries, per-round train
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
                static_argnums=(4,),
                donate_argnums=self._donate_argnums(),
            )
            if self._fuse > 1 else None
        )
        # process-global headroom threshold for the memory monitor
        # (--mem_headroom_warn; docs/OBSERVABILITY.md "Memory &
        # compilation")
        M.MONITOR.headroom_warn = float(
            getattr(cfg.fed, "mem_headroom_warn", 0.9) or 0.9
        )

    def _donate_argnums(self) -> tuple:
        """What a round program (or fused block) donates of ``(state,
        operand, n_active, carry)``: the state always, the carry slot
        when the configuration threads one. Donated operands are audited
        is_deleted after the first execution (core/memscope.py)."""
        return (0,) if self._carry_kind is None else (0, 3)

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
                    n_active=None, carry=None):
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

        ``carry`` is the configuration's one
        :class:`~fedml_tpu.core.statebank.ClientStateBank` (the
        compression error-feedback bank or the PEFT personalization
        bank, `_carry_kind`) or None; it rides the scan carry and comes
        back updated, third of ``(state, metrics, carry)``. A streamed
        defense (:mod:`fedml_tpu.core.streamdef`) turns the body into TWO
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
        if self._carry_kind == "adapter":
            return self._bulk_personal(
                state, view, arrays, ids, live, rkey, carry
            )
        ef_bank = carry

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
        return new_state, train_metrics, new_ef

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
                        arrays: FederatedArrays, n_active, bank):
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
               n_active=None, carry=None):
        """The round body. Every body — this one, the two it hands over
        to, the mesh's ``_sharded_round`` — has this ONE signature and
        returns ``(state, metrics, carry)``: ``n_active`` is the
        elastic live count or None, ``carry`` the configuration's one
        per-client carry (`_carry_kind`) or None. None is an empty
        pytree: a program without the feature has no operand, donation
        or result for it."""
        if self._bulk.enabled():
            # the python-level dispatch keeps the stacked trace below
            # byte-identical when bulk is off
            return self._bulk_round(state, arrays, n_active, carry)
        if self._carry_kind == "adapter":
            # personalized PEFT: private adapter bank in, bank out
            # (fedml_tpu.peft.personal)
            return self._personal_round(state, arrays, n_active, carry)
        residual = carry
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
            # LAST: a reader that syncs on the first value gets
            # train_loss; consumed host-side by consume_round_counters (the
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

    def _fused_block(self, state: ServerState, operand, n_active=None,
                     carry=None, length: int = 1):
        """``length`` complete rounds as ONE program: a ``lax.scan``
        over the round body with ``(state, carry)`` as the scan carry
        (``carry`` the EF residual / bank / adapter bank, or None).
        Each iteration derives its round key from the
        CARRIED ``state.round`` (``_locals`` folds it in), so sampling,
        adversary injection, and the compression quantizer draws are
        bitwise-identical to ``length`` separate ``_round`` calls —
        only XLA's cross-iteration fusion may reassociate float sums
        (the PR-5/PR-7 band, pinned in tests/test_fuse.py). The
        elastic live count is a scan-invariant traced operand: churn
        mid-block is impossible by construction — ``set_cohort_size``
        lands at the next block boundary. In bulk mode the scans nest:
        this one wraps the round body's block scan. Metric leaves stack
        to ``[length, ...]``."""
        def body(sc, _):
            s, m, c = self._round_impl(sc[0], operand, n_active, sc[1])
            return (s, c), m

        (state, carry), ms = jax.lax.scan(
            body, (state, carry), None, length=length
        )
        return state, ms, carry

    def _round_operand(self):
        """Device operand the round body trains from, read at every
        dispatch (the sharded runtime overrides this with its per-shard
        banks)."""
        return self.arrays

    def _program_key(self):
        """Executable identity of the round program: the compiled
        bucket, or the bulk engine's block grid (the sharded runtime
        answers with its per-shard ones). A fused block's key carries
        its length besides."""
        if self._bulk.enabled():
            return (self._n_blocks, self._block_size)
        return self._bucket

    def _dispatch(self, site, state: ServerState, length=None):
        """The host side of a round, and of a fused block of ``length``
        rounds: make sure the carry exists, ONE call through the
        program site, store the carry, note the bank I/O. No branch per
        feature combination — a feature a configuration lacks is a None
        operand."""
        rounds = 1 if length is None else length
        self._ensure_carry(state)
        if self._bulk.enabled():
            # a fused block counts its K rounds so bulk.rounds stays
            # per-round like every fused metric
            BK.note_round(
                self._block_size, self._n_blocks,
                self._slots - self._n_active, rounds=rounds,
            )
            if self._stream_defense is not None:
                self._note_stream_defense(state)
        # the live count rides as a TRACED operand: any cohort size in
        # the bucket reuses the one compiled program; the ProgramSite
        # is the executable store mirror_jit_cache accounts
        n = (
            jnp.asarray(self._n_active, jnp.int32)
            if self._elastic else None
        )
        key = self._program_key()
        args = (state, self._round_operand(), n, self._carry)
        if length is not None:
            key = (key if isinstance(key, tuple) else (key,)) + (length,)
            args += (length,)

        def call():
            return site(key, *args)

        state, m, self._carry = (
            E.mirror_jit_cache(site, call) if self._elastic else call()
        )
        if isinstance(self._carry, SB.ClientStateBank):
            # per round each block gathers and scatters its rows once;
            # a streamed defense's first pass reads them once more
            blocks = rounds * (
                self._n_blocks if self._bulk.enabled() else 1
            )
            SB.note_round_io(
                blocks * (2 if self._stream_defense else 1), blocks
            )
        return state, m

    def run_round(self, state: ServerState):
        return self._dispatch(self._round_fn, state)

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
        return self._dispatch(self._block_fn, state, length)

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

    # -- the per-client carry (core/statebank.py) --------------------------
    def _ensure_carry(self, state: ServerState) -> None:
        """Create the configuration's carry LAZILY on the first round,
        from the CURRENT state — the personalization bank's rows are its
        init-valued adapters, so the repo's
        re-call-init()-for-a-snapshot idiom can never reset a trained
        bank mid-run; the error-feedback carry is zero, one row per
        CLIENT in bulk mode and per bucket slot on the stacked round
        (round 0 transmits the uncorrected delta either way). Its
        lifetime is the simulator's."""
        if self._carry is not None or self._carry_kind is None:
            return
        if self._carry_kind == "adapter":
            rows = PP.init_bank(
                self._peft, state.variables["params"],
                self.arrays.num_clients,
            )
            self._carry = SB.ClientStateBank("adapter", rows)
            telemetry.METRICS.gauge(
                "peft.personal_bank_mb", PP.bank_bytes(rows) / 1e6
            )
        else:
            template = self._wire_template(state.variables)
            self._carry = (
                SB.ClientStateBank.zeros(
                    "ef_residual", template, self.arrays.num_clients
                )
                if self._bulk.enabled()
                else C.zero_residual(template, self._bucket)
            )
            telemetry.METRICS.gauge(
                "compress.ratio", C.wire_ratio(self._cspec, template)
            )
        if isinstance(self._carry, SB.ClientStateBank):
            SB.note_bank(self._carry)

    def bank_state(self) -> dict:
        """Client-state banks for the checkpoint composite
        (docs/FAULT_TOLERANCE.md "Client-state banks"): ``{name:
        savable rows}``, empty when no bank has been created yet (a
        fresh run has nothing to save — and nothing to restore) or the
        carry is not a bank."""
        c = self._carry
        if not isinstance(c, SB.ClientStateBank):
            return {}
        return {c.name: c.savable()}

    def restore_banks(self, state: ServerState, blob) -> None:
        """Adopt checkpointed bank rows (the restore half of
        :meth:`bank_state`). A None/empty or legacy blob — or a blob
        from a run without this bank — leaves the lazy fresh-bank init
        in place instead of crashing: the run resumes with round-0
        rows, which is exactly what a pre-bank checkpoint encoded."""
        kind = self._carry_kind
        if not blob or kind not in blob:
            return
        self._ensure_carry(state)
        if isinstance(self._carry, SB.ClientStateBank):
            self._carry = SB.ClientStateBank.from_savable(
                kind, self._carry.rows, blob[kind]
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
        ``standalone/fedavg/fedavg_api.py:40-81``): ``core.fuse.run_loop``
        from a fresh ``init()``, in fused blocks with pipelined host
        consumption when ``cfg.fed.fuse_rounds > 1``. The three calls
        are looked up on the instance at every round."""
        from fedml_tpu.core import fuse as F

        return F.run_loop(
            self, self.init(), metrics_sink,
            step=lambda state, r: self.run_round(state),
            run_block=lambda state, n: self.run_block(state, n),
            evaluate=lambda state: self.evaluate_global(state),
            path=self._anatomy_path(),
            total=self.cfg.fed.num_rounds,
            eval_every=self.cfg.fed.eval_every,
            fuse=self._fuse,
        )
