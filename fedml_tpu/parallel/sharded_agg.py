"""Mesh-sharded server aggregation: the weight update partitioned over
the client axis.

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arxiv 2004.13336, PAPERS.md) shards the reduce + optimizer
update across replicas and all-gathers only the final params. This
module applies that scheme to the FL *server*: the deploy actor's
aggregation pass — decompress (when the wire codec is on,
:mod:`fedml_tpu.core.compress`) -> clip -> defense-reduce -> server
optimizer step — used to run replicated on ONE device while the stacked
``[C, ...]`` client deltas are embarrassingly parallel over C. Here the
stack is laid out row-wise over a 1-D ``clients`` mesh
(:func:`fedml_tpu.parallel.mesh.make_client_mesh`) and the update runs
under ``shard_map``:

- **per-client stages shard**: decompress (scatter/dequant per row),
  the delta subtraction, and norm clipping touch only local rows;
- **the reduce crosses shards once**: ``mean``/FedNova partial sums
  meet in a ``psum``; the Krum family's ``O(C^2 D)`` pairwise gram —
  the dominant term at C=1000 — is computed in ROW BLOCKS
  (:func:`fedml_tpu.core.robust.pairwise_sq_dists_rows`), each shard
  scoring its own rows against the gathered stack, with only the
  ``[C]`` score vector all-gathered;
- **only the final params replicate**: the round's output is one
  updated :class:`~fedml_tpu.algorithms.fedavg.ServerState`.

The update body is :func:`fedml_tpu.algorithms.fedavg.server_update`
with a ``psum`` reducer — the SAME function the replicated actor path
and both sims run, so the parity contract is inherited, not re-proven:

- selection/gather rules (``median``, ``trimmed_mean``, ``krum``,
  ``multikrum``'s mask, ``fltrust``) see the identical gathered stack
  and apply identical per-row ops — **bitwise** equal to the
  replicated path;
- sum-based terms (the ``mean`` rule, FedNova, batch_stats averaging)
  reassociate across the shard boundary — parity within the same
  ~1-ulp band as PR 5's bucket padding (pinned with a tight tolerance
  in ``tests/test_compress.py``).

Cohorts that don't fill the mesh are padded to a per-mesh bucket with
PR 5's zero-weight healed rows (:func:`fedml_tpu.core.elastic
.pad_stacked`) — every rule is already mask-aware, so padding is
content-blind; with elastic buckets on, the bucket is additionally the
power-of-two one, so membership churn stays a compile-cache hit.
Executables live in a :class:`~fedml_tpu.core.elastic
.CompiledRoundCache` keyed by the mesh bucket (the cache accepts any
hashable key for executables that vary on more than shape); nothing
is donated on this path (see the constructor note —
the stacked operands alias nothing model-sized, and the threaded
actor's host-side round snapshot can zero-copy alias the state). The
buffer-donation satellite lives in the sim round, whose state and
residual have exactly one owner. Round fusion (docs/PERFORMANCE.md
"Round fusion") likewise lives in the sims — ``ShardedFedAvg`` scans
its shard_map'd round; THIS path closes rounds on the transport
barrier, so there is no multi-round program to fuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.core import compress as C
from fedml_tpu.core import elastic as E
from fedml_tpu.parallel.mesh import make_client_mesh

Pytree = object


def mesh_bucket(c: int, n_shards: int, elastic: bool) -> int:
    """Rows the stacked operand is padded to: a multiple of the mesh
    (every shard gets equal rows), and with ``elastic`` the
    power-of-two bucket on top so churn stays a cache hit."""
    b = E.bucket_for(c) if elastic else c
    return ((b + n_shards - 1) // n_shards) * n_shards


class ShardedAggregator:
    """Client-axis-sharded server update for the deploy actor path
    (``FedConfig.shard_aggregation`` / ``--shard_aggregation``)."""

    def __init__(
        self,
        cfg,
        steps_per_epoch: int,
        batch_size: int,
        mesh: Mesh | None = None,
        spec: C.CompressionSpec | None = None,
        max_entries: int = 8,
    ):
        from fedml_tpu.algorithms.fedavg import psum_reducer

        self.cfg = cfg
        self.steps_per_epoch = steps_per_epoch
        self.batch_size = batch_size
        self.mesh = mesh if mesh is not None else make_client_mesh()
        self.axis = self.mesh.axis_names[0]
        self.n_shards = int(self.mesh.devices.size)
        self._elastic = bool(cfg.fed.elastic_buckets)
        self._spec = spec if spec is not None and spec.enabled() else None
        self._red = psum_reducer(self.axis)
        self._rows = NamedSharding(self.mesh, P(self.axis))
        self._rep = NamedSharding(self.mesh, P())
        # (state, stacked, w, valid, rkey): stacked rows ride sharded,
        # everything else replicated, and the new ServerState comes
        # back replicated — the "all-gather only the final params"
        # edge of the scheme. Nothing is donated here: the stacked
        # [C, ...] operands alias nothing in the model-sized output
        # (donating them would only emit unusable-donation warnings),
        # and donating the old state is unsafe in the threaded actor —
        # on the CPU backend the server's host-side round snapshot can
        # zero-copy ALIAS the state buffers a donation would let the
        # executable overwrite (the aliasing class PR 1's checkpoint
        # fix documents). The sim round, whose state has exactly one
        # owner, is where the donation satellite lives.
        self._update_cache = E.CompiledRoundCache(
            self._sharded_update,
            max_entries=max_entries,
            jit_kwargs=dict(
                in_shardings=(self._rep, self._rows, self._rows,
                              self._rows, self._rep),
                out_shardings=self._rep,
            ),
            family="sharded_agg_update",
        )
        self._decomp_cache = (
            E.CompiledRoundCache(
                self._sharded_decompress,
                max_entries=max_entries,
                jit_kwargs=dict(
                    in_shardings=(self._rows, self._rep),
                    out_shardings=self._rows,
                ),
                family="sharded_agg_decompress",
            )
            if self._spec is not None else None
        )

    # -- compiled bodies ---------------------------------------------------

    def _sharded_update(self, state, stacked_vars, n_k, valid, rkey):
        from fedml_tpu.algorithms.fedavg import server_update

        def body(state, stacked, w, v, key):
            # stacked/w/v arrive as this shard's row block; state/key
            # replicated — server_update with the psum reducer is the
            # sharded sim's exact aggregation body
            return server_update(
                self.cfg.fed,
                self.cfg.train,
                self.steps_per_epoch,
                self.batch_size,
                state,
                stacked,
                w,
                key,
                self._red,
                valid=v,
            )

        return shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(self.axis), P(self.axis), P(self.axis),
                      P()),
            out_specs=P(),
            check_vma=False,
        )(state, stacked_vars, n_k, valid, rkey)

    def _sharded_decompress(self, stacked_payload, global_vars):
        """Stacked compressed payloads (rows sharded) -> stacked dense
        VARIABLES (rows sharded): each shard scatters/dequantizes only
        its own clients' payloads. Padded zero payload rows decompress
        to a delta of exactly zero — i.e. the healed global row."""
        spec = self._spec

        def body(payload, gvars):
            delta = C.decompress_stacked(spec, payload, gvars)
            return jax.tree.map(
                lambda g, d: (g[None] + d).astype(g.dtype), gvars, delta
            )

        return shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(self.axis), P()),
            out_specs=P(self.axis),
            check_vma=False,
        )(stacked_payload, global_vars)

    # -- host-facing API ---------------------------------------------------

    def _place_rows(self, tree):
        return jax.device_put(tree, self._rows)

    def decompress(self, stacked_payload: Pytree, global_vars: Pytree,
                   n_rows: int) -> Pytree:
        """Decompress ``n_rows`` stacked payloads into dense stacked
        variables (rows stay sharded over the mesh; callers slice off
        the padding rows)."""
        bucket = mesh_bucket(n_rows, self.n_shards, self._elastic)
        padded = C.pad_stacked_payload(stacked_payload, bucket)
        dense = self._decomp_cache(
            bucket, self._place_rows(padded),
            jax.device_put(global_vars, self._rep),
        )
        return jax.tree.map(lambda x: x[:n_rows], dense)

    def update(self, state, stacked_vars: Pytree, weights, rkey):
        """One server step over ``stacked_vars`` (``[C, ...]`` dense
        client variables), sharded over the mesh. Pads the cohort to
        the mesh bucket with zero-weight healed rows (mask-aware rules
        make the padding content-blind) and returns the new replicated
        :class:`ServerState`. The old state stays valid (nothing is
        donated — see the constructor note)."""
        c = int(np.shape(np.asarray(weights))[0])
        bucket = mesh_bucket(c, self.n_shards, self._elastic)
        padded, w, valid = E.pad_stacked(
            jax.tree.map(jnp.asarray, stacked_vars), weights,
            state.variables, bucket,
        )
        return self._update_cache(
            bucket,
            jax.device_put(state, self._rep),
            self._place_rows(padded),
            jax.device_put(w, self._rows),
            jax.device_put(valid, self._rows),
            jax.device_put(rkey, self._rep),
        )
