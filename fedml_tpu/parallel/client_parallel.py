"""Mesh-sharded FedAvg: the client population statically partitioned over
the ``clients`` axis — each shard owns a block of clients AND only their
samples — with each client's batch optionally sharded over the ``data``
axis.

This is the TPU-native replacement for the reference's two distributed
layers at once:

- ``fedml_api/distributed/fedavg`` (one MPI rank per client, server rank 0,
  pickled state_dicts over ``comm.send``) -> clients become *mesh shards*;
  "upload model / aggregate / broadcast" becomes a weighted pytree ``psum``
  under ``shard_map`` — aggregation rides ICI, no server process exists.
- ``fedml_api/distributed/fedavg_cross_silo`` (DDP inside each silo over
  NCCL, data local to the silo, ``DistWorker.py:31-54``) -> the ``data``
  mesh axis: per-batch gradient ``psum`` inside the compiled local update;
  and like the reference, sample banks stay LOCAL to their shard
  (:class:`fedml_tpu.data.federated.ShardedClientBanks`), so per-device
  HBM for the dataset is ~1/n_shards of the global set.

Cohort sampling is *stratified by shard*: every round each shard samples
``clients_per_round / n_shards`` of its own clients (deterministic in the
round key). :func:`fedml_tpu.core.random.sample_clients_stratified` is the
exact host-side mirror, so a single-device :class:`FedAvgSim` constructed
with that sampler follows the same trajectory — ``tests/test_sharded.py``
proves equality.

The server step itself is the SAME function as the single-device simulator
(:func:`fedml_tpu.algorithms.fedavg.server_update`), instantiated with a
``psum``/``all_gather`` reducer — so the sharded path cannot drift from the
reference-equivalent math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.config import ExperimentConfig
from fedml_tpu.core import bulk as BK
from fedml_tpu.core import elastic as E
from fedml_tpu.core import memscope as M
from fedml_tpu.core import random as R
from fedml_tpu.core import robust
from fedml_tpu.core.tracing import build_span
from fedml_tpu.data.federated import FederatedData, shard_client_banks
from fedml_tpu.algorithms.base import (
    build_cohort_local_update,
    build_evaluator,
    build_local_update,
    cohort_update_supported,
    finalize_sums,
)
from fedml_tpu.algorithms.fedavg import (
    FedAvgSim,
    ServerState,
    fold_block_partials,
    grouped_cohort_call,
    psum_reducer,
    server_update,
    server_update_from_partials,
)
from fedml_tpu.algorithms.stack_utils import (
    lockstep_slot_steps,
    resolve_cohort_groups,
)
from fedml_tpu.models.base import FedModel


class ShardedFedAvg(FedAvgSim):
    """FedAvg with the round compiled over a (clients, data) mesh.

    Performance observability (core/perf.py) rides the inherited
    :meth:`FedAvgSim.run` loop: with ``cfg.fed.profile_rounds > 0`` the
    sharded round gets the same jax-profiler capture windows —
    collectives (the client-axis ``psum``/``all_gather``) show up as
    the breakdown's ``collective`` share — and the live ``perf.mfu``
    gauge, whose peak-FLOPs denominator is the WHOLE mesh
    (``peak_per_chip x mesh.devices.size``, resolved by
    ``perf.build_sim_perf`` from :attr:`mesh`), not one chip."""

    @build_span
    def __init__(
        self,
        model: FedModel,
        data: FederatedData,
        cfg: ExperimentConfig,
        mesh: Mesh,
    ):
        if cfg.adversary.enabled():
            # the sharded round program calls server_update directly —
            # neither the adversary injection gate nor the non-finite
            # screen of FedAvgSim._round runs here, so an "adversarial"
            # sharded experiment would silently measure a clean run
            raise ValueError(
                "adversary injection is not wired into the "
                "mesh-sharded round (it covers the single-process "
                "FedAvgSim and the deploy-path client actor); run the "
                "Byzantine scenario there, or disable cfg.adversary"
            )
        if cfg.fed.compress != "none":
            # same honesty rule as the adversary gate: this runtime's
            # client<->server "wire" is the mesh ICI (psum/all_gather)
            # — there is no serialized delta payload to compress, and
            # silently skipping the codec would report compressed-run
            # results that measured a dense run
            raise ValueError(
                "wire compression is not wired into the mesh-sharded "
                "round (its aggregation rides ICI collectives, not a "
                "serialized wire); model the codec on FedAvgSim or "
                "the --role deploy path, or set compress='none'"
            )
        if (cfg.fed.client_block_size > 0
                and cfg.fed.robust_method not in ("mean", "", None)):
            # the streamed defense sketches (core/streamdef.py) fold
            # through ONE device's block scan; under shard_map each
            # shard would sketch only its own sub-cohort and the
            # cross-shard combine (histogram merge, projection
            # all_gather) is not built — reject rather than silently
            # defend each shard against only its local adversaries
            raise ValueError(
                "streamed Byzantine defenses are not wired into the "
                "mesh-sharded bulk round (the defense sketches fold "
                "on one device; the cross-shard sketch combine is not "
                "built); run defended bulk rounds on FedAvgSim, use "
                "the stacked sharded round (client_block_size=0), or "
                "set robust_method='mean'"
            )
        self.mesh = mesh
        self.client_axis = cfg.mesh.client_axis_name
        self.data_axis = cfg.mesh.data_axis_name
        self.n_client_shards = mesh.shape[self.client_axis]
        self.n_data_shards = mesh.shape[self.data_axis]
        cohort = min(cfg.fed.clients_per_round, cfg.data.num_clients)
        assert cohort % self.n_client_shards == 0, (
            f"effective cohort size {cohort} must divide evenly over the "
            f"{self.n_client_shards}-way clients mesh axis"
        )
        assert data.num_clients % self.n_client_shards == 0, (
            f"population {data.num_clients} must divide evenly over the "
            f"{self.n_client_shards}-way clients mesh axis (static "
            "client->shard placement)"
        )
        self.cohort_per_shard = cohort // self.n_client_shards
        # elastic shape bucketing (core/elastic.py): each shard's slice
        # of the cohort is padded to ITS power-of-two bucket, so a
        # cohort-size change (set_cohort_size) is a masked-row change,
        # not a recompile — the sharded twin of FedAvgSim's bucketing
        if cfg.fed.elastic_buckets:
            self.bucket_per_shard = min(
                E.bucket_for(self.cohort_per_shard),
                data.num_clients // self.n_client_shards,
            )
        else:
            self.bucket_per_shard = self.cohort_per_shard

        # FedAvgSim.__init__ builds the single-device local_update; our
        # _prepare_data override keeps the global arrays host-side and
        # builds the per-shard banks; rebuild the local update with the
        # data axis threaded through, then wrap the round in shard_map.
        super().__init__(model, data, cfg)
        # NOTE: super().__init__ may have LoRA-injected the model
        # (fedml_tpu.peft) — rebuilds below must use the injected one
        model = self.model
        # each device evaluates the test rows it holds (_prepare_data)
        self._mesh_evaluator = build_evaluator(model, self.task, mesh=mesh)
        if self.n_data_shards > 1:
            self.local_update = build_local_update(
                model,
                self.task,
                cfg.train,
                self.batch_size,
                self.arrays.max_client_samples,
                data_axis=self.data_axis,
                data_axis_size=self.n_data_shards,
                partition=self._peft.part if self._peft else None,
            )
        # per-shard cohort-grouped update (data axis 1 only: the cohort
        # network has no per-batch psum seam for intra-client DDP). A
        # shard's cohort runs in size-sorted groups by the rule FedAvgSim
        # uses (stack_utils.resolve_cohort_groups: groups of 5 unless
        # train.cohort_groups says otherwise), so the network is built
        # at the GROUP's width
        self._shard_groups = resolve_cohort_groups(
            cfg.train.cohort_groups, self.cohort_per_shard
        )
        self._shard_cohort_update = (
            build_cohort_local_update(
                model,
                self.task,
                cfg.train,
                self.batch_size,
                self.arrays.max_client_samples,
                self.cohort_per_shard // self._shard_groups,
            )
            if self.n_data_shards == 1
            and cfg.train.cohort_fused
            and cohort_update_supported(model, cfg.train)
            # the widened cohort network bakes the per-shard cohort
            # into its shapes — elastic bucketing uses the vmapped path
            and not self._elastic
            # the bulk engine streams the vmapped update per block
            and not self._bulk.enabled()
            # the partitioned (PEFT) update is vmapped-only
            and self._peft is None
            else None
        )
        # bulk-client streaming over the mesh (core/bulk.py): each
        # shard streams its OWN sub-cohort through blocks of B vmapped
        # local updates and psums only the O(model) partial sums at the
        # end — the stacked wmean/gather collectives never see a
        # [C, ...] operand. Block-count bucketing is per shard.
        if self._bulk.enabled():
            self._shard_blocks = BK.plan_blocks(
                self.cohort_per_shard, self._block_size, self._elastic
            )
            self._shard_slots = self._shard_blocks * self._block_size
            self._shard_max_live = min(
                self._shard_slots,
                data.num_clients // self.n_client_shards,
            )
            # the whole-sim grid the telemetry gauges report
            self._n_blocks = self._shard_blocks * self.n_client_shards
            self._slots = self._shard_slots * self.n_client_shards
            self._max_live = self._shard_max_live * self.n_client_shards
        # instrumented AOT site like the single-device round
        # (core/memscope.py): compile wall + memory_analysis recorded
        # per program, the donated state audited on first execution.
        # Personalized PEFT donates the adapter ClientStateBank too
        # (the carry, the single-device layout) — it shards over the
        # client axis inside the round, each shard owning its own
        # K-row slice.
        self._round_fn = M.ProgramSite(
            self._sharded_round,
            family=(
                "sharded_bulk" if self._bulk.enabled()
                else "sharded_round"
            ),
            donate_argnums=self._donate_argnums(),
        )
        # round fusion (docs/PERFORMANCE.md "Round fusion"): the
        # inherited _fused_block scans over whatever _round_impl names
        # — rebinding it here makes the fused block run the shard_map'd
        # round body, so fuse_rounds composes with the mesh unchanged
        # (same collectives per iteration, same whole-mesh MFU
        # denominator from perf.build_sim_perf). Compression is
        # rejected above, so the block never carries a residual.
        self._round_impl = self._sharded_round

    def _anatomy_path(self) -> str:
        # the anatomy ring labels the round body actually running
        # (docs/OBSERVABILITY.md "Round anatomy"); the inherited run
        # loop times the mesh round at the same sync points
        return "sharded"

    def set_cohort_size(self, n: int) -> None:
        """Elastic cohort change for the sharded runtime: ``n`` must
        divide evenly over the clients axis and each shard's slice must
        fit the compiled per-shard bucket."""
        if not self._elastic:
            raise ValueError(
                "set_cohort_size requires FedConfig(elastic_buckets="
                "True)"
            )
        if n % self.n_client_shards != 0:
            raise ValueError(
                f"cohort size {n} must divide evenly over the "
                f"{self.n_client_shards}-way clients mesh axis"
            )
        per = n // self.n_client_shards
        if self._bulk.enabled():
            if not (1 <= per <= self._shard_max_live):
                raise ValueError(
                    f"per-shard cohort {per} does not fit the compiled "
                    f"{self._shard_blocks}x{self._block_size} per-shard "
                    f"block grid (live per-shard cohort must stay in "
                    f"[1, {self._shard_max_live}])"
                )
            self._n_active = n
            return
        if not (1 <= per <= self.bucket_per_shard):
            raise ValueError(
                f"per-shard cohort {per} does not fit the compiled "
                f"per-shard bucket {self.bucket_per_shard}"
            )
        self._n_active = n

    def _prepare_data(self, data, cfg):
        """Training data lives ONLY in the per-shard banks (per-device HBM
        ~1/n_shards of the global set) and the test set ONLY as
        ``self._test_rows``, its rows split over every device of the
        mesh; the global FederatedArrays stays host numpy
        (``evaluate_train`` still sends its training set from there)."""
        from fedml_tpu.data.federated import arrays_and_batch

        self.arrays, self.batch_size = arrays_and_batch(
            data, cfg.data, device=False
        )
        # placed ONCE with the layout the round's shard_map consumes
        # (leading shard axis over ``clients``, replicated over
        # ``data``): banks left on one device would be re-scattered
        # from it on every round
        self.banks = jax.device_put(
            shard_client_banks(
                data,
                self.n_client_shards,
                pad_multiple=(
                    1 if cfg.data.full_batch else cfg.data.batch_size
                ),
            ),
            NamedSharding(self.mesh, P(self.client_axis)),
        )
        assert self.banks.max_client_samples == self.arrays.max_client_samples
        # the test set likewise, in the layout the mesh evaluator
        # consumes: (x, y, w) padded to a multiple of the device count,
        # w 1 for a real row and 0 for padding, rows over all devices
        x, y = self.arrays.test_x, self.arrays.test_y
        pad = (-len(x)) % self.mesh.devices.size
        rows = lambda a: np.concatenate(
            [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        self._test_rows = jax.device_put(
            (rows(x), rows(y), rows(np.ones(len(x), np.float32))),
            NamedSharding(self.mesh, P(self.mesh.axis_names)),
        )

    def _global_eval(self):
        """The mesh's evaluator and the rows ``_prepare_data`` placed
        for it: every device evaluates its own."""
        return self._mesh_evaluator, self._test_rows

    def _sharded_round(self, state: ServerState, banks, n_active=None,
                       bank=None):
        """One mesh round, with :meth:`FedAvgSim._round`'s signature
        and ``(state, metrics, carry)`` result (the inherited dispatch
        and fused block call through it). Compression is rejected at
        construction, so the carry is the personalized-PEFT adapter
        :class:`~fedml_tpu.core.statebank.ClientStateBank` or None;
        it is sharded over the client axis — inside the
        shard each body sees its own ``[K, ...]`` slice (local ids,
        local sentinel ``K``) and returns the updated slice, which
        shard_map stitches back to the full ``[num_clients, ...]``
        bank."""
        cfg = self.cfg.fed
        rkey = R.round_key(self.root_key, state.round)
        ckey = jax.random.fold_in(rkey, 0)
        K = banks.clients_per_shard
        Kb = self.bucket_per_shard

        cspec = P(self.client_axis)  # shard banks; replicate over data axis
        rep = P()
        red = psum_reducer(self.client_axis)

        def shard_fn(state, x, y, idx, mask, bank_l, n_act):
            # leading shard axis arrives with extent 1 inside the shard
            x, y = x[0], y[0]
            idx, mask = idx[0], mask[0]
            # the bank slice's leading axis is the CLIENT axis itself
            # (num_clients -> K per shard): no extent-1 unwrap
            shard = jax.lax.axis_index(self.client_axis)
            if self._bulk.enabled():
                return self._bulk_shard_body(
                    state, x, y, idx, mask, shard, rkey, ckey, K, n_act,
                    bank_l,
                )
            if bank_l is not None:
                return self._personal_shard_body(
                    state, x, y, idx, mask, shard, rkey, ckey, K, Kb,
                    n_act, bank_l, red,
                )
            # stratified cohort: this shard samples its own clients (LOCAL
            # ids); keys use GLOBAL client ids so the host mirror matches.
            # Under elastic bucketing the shard samples its full BUCKET
            # and a traced per-shard live count masks the padded slots.
            with jax.named_scope("fedml.sample"):
                local = R.sample_stratum(ckey, shard, K, Kb)
                ckeys = jax.vmap(
                    lambda c: R.client_key(rkey, shard * K + c)
                )(local)
            slot_steps = None
            with jax.named_scope("fedml.local"):
                idx_rows, mask_rows = idx[local], mask[local]
                if self._shard_cohort_update is not None:
                    # cohort-grouped fast path per shard: this shard's
                    # slice of the cohort runs as widened networks (see
                    # fedml_tpu.models.cohort), one size-sorted group at
                    # a time, each to its own step count, the network
                    # traced once — purely intra-shard compute, so it
                    # composes with the client-axis psum unchanged
                    stacked_vars, n_k, msums = grouped_cohort_call(
                        self._shard_cohort_update, self._shard_groups,
                        state.variables, idx_rows, mask_rows, x, y, ckeys,
                        traced_once=True,
                    )
                    slot_steps = lockstep_slot_steps(
                        mask_rows, self._shard_groups, self.batch_size,
                        self.cfg.train.epochs,
                    )
                else:
                    stacked_vars, n_k, msums = jax.vmap(
                        self.local_update,
                        in_axes=(None, 0, 0, None, None, 0),
                    )(state.variables, idx_rows, mask_rows, x, y, ckeys)

            # PEFT view: the psum'd aggregation below only ever sees
            # the O(adapter) pruned subtree — the frozen base is a
            # replicated operand merged back bitwise after the step,
            # never re-shipped through a collective
            view = (
                state if self._peft is None
                else self._peft.view_state(state)
            )
            live = None
            if n_act is not None:
                with jax.named_scope("fedml.defense_agg"):
                    live = E.active_mask(
                        Kb, n_act // self.n_client_shards
                    )
                    stacked_vars, n_k, msums = E.mask_padded(
                        stacked_vars, n_k, msums, view.variables, live
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
                    red,
                    valid=live,
                )
                if self._peft is not None:
                    new_state = self._peft.merge_state(new_state, state)
                reduced = jax.tree.map(
                    lambda v: jax.lax.psum(jnp.sum(v), self.client_axis),
                    msums,
                )
                fin = finalize_sums(reduced)
                metrics = {
                    "train_loss": fin["loss"], "train_acc": fin["acc"],
                }
                if slot_steps is not None:
                    # what the lockstep schedule executed this round,
                    # over all shards; the live client steps over it is
                    # the schedule's occupancy (docs/OBSERVABILITY.md)
                    metrics["slot_steps"] = jax.lax.psum(
                        slot_steps, self.client_axis
                    )
            return new_state, metrics, None

        # the adapter bank shards like the sample banks: P on the
        # leading (client) axis of every row leaf — shard s owns rows
        # [s*K, (s+1)*K) of the global bank. The live count is a
        # REPLICATED operand (not a closure): closed-over tracers under
        # shard_map are version-fragile. Either may be None, an empty
        # pytree: its spec then names nothing
        return shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(rep, cspec, cspec, cspec, cspec, cspec, rep),
            out_specs=(rep, rep, cspec),
            check_vma=False,
        )(state, banks.x, banks.y, banks.idx, banks.mask, bank, n_active)

    def _bulk_shard_body(self, state, x, y, idx, mask, shard, rkey,
                         ckey, K, n_act, bank=None):
        """One shard's bulk round body (runs inside the shard_map):
        stream THIS shard's sub-cohort through fixed-size blocks
        folding O(model) partials, then psum the partials over the
        client axis and run the SAME
        :func:`~fedml_tpu.algorithms.fedavg.server_update_from_partials`
        finalize as the single-device bulk round (replicated on every
        shard, like the stacked path's server step). The collectives
        shrink from stacked wmean/gather to one psum of partials."""
        cfg = self.cfg.fed
        view = (
            state if self._peft is None
            else self._peft.view_state(state)
        )
        S = self._shard_slots
        draw = (
            min(S, K) if self._elastic else self.cohort_per_shard
        )
        with jax.named_scope("fedml.sample"):
            local = R.sample_stratum(ckey, shard, K, draw)
        pad = S - draw
        if pad:
            # the LOCAL sentinel (= K, this shard's row count): the
            # clamped sample-bank gather reads a real row but the slot
            # is masked below, and a ClientStateBank scatter DROPS the
            # write entirely (mode="drop") — a padded slot can never
            # alias client 0's bank row
            local = jnp.concatenate(
                [local, jnp.full((pad,), K, jnp.int32)]
            )
        if n_act is not None:
            live = E.active_mask(S, n_act // self.n_client_shards)
        elif S != self.cohort_per_shard:
            live = E.active_mask(S, self.cohort_per_shard)
        else:
            live = None
        if bank is not None:
            return self._bulk_shard_personal(
                state, view, x, y, idx, mask, shard, rkey, K, local,
                live, bank,
            )

        def fold_block(block_ids, block_live):
            with jax.named_scope("fedml.sample"):
                ckeys = jax.vmap(
                    lambda c: R.client_key(rkey, shard * K + c)
                )(block_ids)
            with jax.named_scope("fedml.local"):
                stacked_vars, n_k, msums = jax.vmap(
                    self.local_update,
                    in_axes=(None, 0, 0, None, None, 0),
                )(state.variables, idx[block_ids], mask[block_ids], x,
                  y, ckeys)
            with jax.named_scope("fedml.defense_agg"):
                if block_live is not None:
                    stacked_vars, n_k, msums = E.mask_padded(
                        stacked_vars, n_k, msums, view.variables,
                        block_live,
                    )
                # the sharded stacked path carries no non-finite screen
                # (adversary configs are rejected at construction) —
                # the bulk twin mirrors it: rejected stays 0
                return fold_block_partials(
                    cfg, self.cfg.train, self.steps_per_epoch,
                    self.batch_size, view, stacked_vars, n_k, msums,
                    jnp.zeros((), jnp.float32),
                )

        partials = BK.stream_blocks(
            fold_block, local, live, self._block_size
        )
        with jax.named_scope("fedml.server_update"):
            partials = jax.tree.map(
                lambda v: jax.lax.psum(v, self.client_axis), partials
            )
            new_state = server_update_from_partials(
                cfg, view, partials, rkey
            )
            if self._peft is not None:
                new_state = self._peft.merge_state(new_state, state)
            fin = finalize_sums(partials.msums)
        return new_state, {
            "train_loss": fin["loss"], "train_acc": fin["acc"],
        }, None

    def _local_personal_update(self, state, x, y, idx, mask,
                               shard, rkey, K, ids, priv):
        """One stacked group of personalized local updates on THIS
        shard: merge each client's private adapter row into the shared
        model, train, and split the result back into (shared, private)
        halves — the per-shard twin of the bodies in
        :meth:`FedAvgSim._personal_round` / ``_bulk_personal``. ``ids``
        are LOCAL (in ``[0, K)``, sentinel ``K``); client keys use the
        GLOBAL id ``shard*K + c`` so the host stratified mirror
        matches."""
        plan = self._peft
        base_frozen = plan.private.frozen(state.variables["params"])
        ckeys = jax.vmap(
            lambda c: R.client_key(rkey, shard * K + c)
        )(ids)

        def one(priv_row, idx_row, mask_row, key):
            params_c = plan.private.merge(priv_row, base_frozen)
            vars_c = {**state.variables, "params": params_c}
            out_vars, n_k, msums = self.local_update(
                vars_c, idx_row, mask_row, x, y, key
            )
            trained = out_vars["params"]
            shared = {
                **{k: v for k, v in out_vars.items() if k != "params"},
                "params": plan.private.frozen(trained),
            }
            return (shared, plan.private.trainable(trained), n_k,
                    msums)

        return jax.vmap(one)(priv, idx[ids], mask[ids], ckeys)

    @staticmethod
    def _screen_personal(view, shared, new_priv, n_k, msums, live):
        """The both-halves non-finite screen shared by the stacked and
        bulk personal shard bodies (same contract as the single-device
        paths): a poisoned client contributes nothing to the shared
        aggregate AND keeps its pre-round bank row; non-live slots are
        healed/zero-weight and are neither rejections nor bank writes.
        Returns ``(shared, n_k, keep, rejected)``."""
        if live is not None:
            shared, n_k, msums = E.mask_padded(
                shared, n_k, msums, view.variables, live
            )
        ok = robust.finite_client_mask(
            {"shared": shared, "private": new_priv}, n_k
        )
        lv = jnp.ones(ok.shape, bool) if live is None else live
        ok = ok | ~lv

        def heal(s, g):
            m = ok.reshape((-1,) + (1,) * (s.ndim - 1))
            return jnp.where(m, s, g[None].astype(s.dtype))

        shared = jax.tree.map(heal, shared, view.variables)
        n_k = jnp.where(ok, n_k, jnp.zeros_like(n_k))
        rejected = (ok.shape[0] - jnp.sum(ok)).astype(jnp.float32)
        return shared, n_k, msums, ok & lv, rejected

    def _personal_shard_body(self, state, x, y, idx, mask, shard, rkey,
                             ckey, K, Kb, n_act, bank, red):
        """Stacked personalized round on one shard: gather this
        shard's cohort rows from its bank SLICE, train merged, psum
        only the SHARED half, scatter the trained rows back. The
        no-leak contract is structural exactly as on the single-device
        path — the psum'd view does not contain the private paths, and
        each bank row is written only from its own client's update."""
        cfg = self.cfg.fed
        plan = self._peft
        local = R.sample_stratum(ckey, shard, K, Kb)
        priv = bank.gather(local)
        shared, new_priv, n_k, msums = self._local_personal_update(
            state, x, y, idx, mask, shard, rkey, K, local, priv,
        )
        view = plan.view_state(state)
        live = None
        if n_act is not None:
            live = E.active_mask(Kb, n_act // self.n_client_shards)
        shared, n_k, msums, keep, rejected = self._screen_personal(
            view, shared, new_priv, n_k, msums, live
        )
        new_state = server_update(
            cfg, self.cfg.train, self.steps_per_epoch,
            self.batch_size, view, shared, n_k, rkey, red, valid=live,
        )
        new_state = plan.merge_state(new_state, state)
        new_bank = bank.put(local, new_priv, keep=keep, gathered=priv)
        reduced = jax.tree.map(
            lambda v: jax.lax.psum(jnp.sum(v), self.client_axis), msums
        )
        fin = finalize_sums(reduced)
        metrics = {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            "nonfinite_rejected": jax.lax.psum(
                rejected, self.client_axis
            ),
        }
        return new_state, metrics, new_bank

    def _bulk_shard_personal(self, state, view, x, y, idx, mask, shard,
                             rkey, K, local, live, bank):
        """Personalized PEFT x bulk x mesh: each shard streams its
        sub-cohort through blocks, gathering/scattering its bank SLICE
        through the block scan carry (local sentinel ``K`` — padded
        slots read a clamped row but never write one), then psums the
        O(model) shared partials. The bank never crosses the mesh: it
        is already partitioned the way the round consumes it."""
        cfg = self.cfg.fed
        plan = self._peft

        def fold_block(block_ids, block_live, bk):
            priv = bk.gather(block_ids)
            shared, new_priv, n_k, msums = self._local_personal_update(
                state, x, y, idx, mask, shard, rkey, K, block_ids,
                priv,
            )
            shared, n_k, msums, keep, rejected = self._screen_personal(
                view, shared, new_priv, n_k, msums, block_live
            )
            bk = bk.put(block_ids, new_priv, keep=keep, gathered=priv)
            p = fold_block_partials(
                cfg, self.cfg.train, self.steps_per_epoch,
                self.batch_size, view, shared, n_k, msums, rejected,
            )
            return p, bk

        partials, bank = BK.stream_blocks(
            fold_block, local, live, self._block_size, banks=bank
        )
        partials = jax.tree.map(
            lambda v: jax.lax.psum(v, self.client_axis), partials
        )
        new_state = server_update_from_partials(
            cfg, view, partials, rkey
        )
        new_state = plan.merge_state(new_state, state)
        fin = finalize_sums(partials.msums)
        return new_state, {
            "train_loss": fin["loss"],
            "train_acc": fin["acc"],
            "nonfinite_rejected": partials.rejected,
        }, bank

    def _program_key(self):
        if self._bulk.enabled():
            return (self._shard_blocks, self._block_size)
        return self.bucket_per_shard

    def _round_operand(self):
        return self.banks
