"""Device-resident bulk-client execution: scan-chunked streaming cohorts.

Every simulated client in the stacked round is a row of a ``[C, ...]``
operand inside one compiled program, so HBM grows linearly with cohort
size — the O(C) law ``tests/test_memscope.py`` pins on the round
program's argument bytes. This module is the FedJAX
``for_each_client`` idiom (PAPERS.md), ROADMAP item 2: stream the
sampled cohort through the device in fixed-size **blocks** of ``B``
clients. Each block runs the existing vmapped local update and is
immediately reduced to an O(model) partial —

    delta_wsum += Σ_r n_r · (clipped, tau-normalized) delta_r
    n_sum      += Σ_r n_r
    metric sums, non-param collections alike

— the same ``[weighted-delta-sum, mass, n, metric-sums]`` vocabulary
the :class:`~fedml_tpu.core.async_agg.AsyncBuffer` fold and the tier
machinery's ``[sum, n, count]`` partials already speak. The partials
fold through a ``lax.scan`` carry, so peak round memory is
**O(B + model)**, independent of C; only the final server step
(:func:`fedml_tpu.algorithms.fedavg.server_update_from_partials`)
touches model-sized state.

Contract honesty, stated like :mod:`fedml_tpu.core.elastic` states its
padding tiers:

- **Exact rules**: clip (per-row) + ``mean`` reduce and FedNova
  tau-normalized averaging decompose into partial sums exactly — bulk
  agrees with the stacked round within the reduce-reassociation ulp
  band (blockwise sums then a combine, vs one reduction over C; the
  same equality class as bucket padding / sharded psum, pinned in
  ``tests/test_bulk.py``).
- **Streamed rules**: the selection/gather defenses (``median`` /
  ``trimmed_mean`` / ``krum`` / ``multikrum`` / ``fltrust``) run as
  TWO-PASS streaming computations over this same block scan
  (:mod:`fedml_tpu.core.streamdef`): pass 1 folds an O(sketch) summary
  (coordinate moments, or seeded random projections), the selection is
  decided from the sketch, pass 2 folds the decided aggregate — the
  full ``[C, D]`` stacked-delta matrix is never materialized, and the
  accuracy contract of each sketch is stated honestly in streamdef's
  module doc (and pinned in ``tests/test_streamdef.py``).
- **Banked composition**: per-client O(C) states — the wire codec's
  error-feedback residual, the PEFT private adapter bank — live in a
  :class:`~fedml_tpu.core.statebank.ClientStateBank` keyed by CLIENT
  ID: each block gathers its sampled rows, updates them, and scatters
  them back through the scan carry (``stream_blocks(banks=...)``), so
  ``compress + bulk`` and ``personalize + bulk`` compose at O(block)
  round memory. The ``gauss`` adversary draws per-row noise keyed on
  (round, client id) (:func:`fedml_tpu.core.adversary.
  corrupt_stacked_deltas`), so it composes with the block scan too —
  bitwise-equal to the stacked path at matched seeds.

Elasticity applies to the block COUNT: the scan length is the
power-of-two bucket of ``ceil(C / B)`` blocks, the live cohort count
rides as a traced operand, and a partial final block is healed by the
existing :func:`fedml_tpu.core.elastic.mask_padded` — cohort churn
within the block bucket costs a compile-cache hit, not a recompile.

Telemetry (docs/OBSERVABILITY.md): ``bulk.block_size``,
``bulk.blocks_per_round``, ``bulk.padded_slots`` gauges and the
``bulk.rounds`` counter, written host-side at dispatch (never inside
the compiled program).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from fedml_tpu.core import telemetry
from fedml_tpu.core.elastic import bucket_for

Pytree = Any

#: reduce rules whose aggregate decomposes into streaming partial sums
#: (fednova is an ALGORITHM, not a robust_method, and composes because
#: its tau normalization is per-row before the weighted sum)
BULK_REDUCE_RULES = ("mean",)


@dataclasses.dataclass(frozen=True)
class BulkSpec:
    """Frozen description of the block-streaming mode (rides
    ``FedConfig.client_block_size``; 0 = off, the stacked ``[C, ...]``
    round stays byte-identical)."""

    block_size: int = 0

    def __post_init__(self):
        if self.block_size < 0:
            raise ValueError(
                f"client_block_size must be >= 0 (0 = stacked mode), "
                f"got {self.block_size}"
            )

    @staticmethod
    def from_fed(fed) -> "BulkSpec":
        return BulkSpec(
            block_size=getattr(fed, "client_block_size", 0) or 0
        )

    def enabled(self) -> bool:
        return self.block_size > 0


def check_bulk_compat(fed, adversary=None) -> None:
    """Validate a bulk configuration at construction (and at run.py
    parse time). The PR 14 composition walls have all fallen:

    - selection defenses stream through the two-pass sketches of
      :mod:`fedml_tpu.core.streamdef` (every
      :attr:`~fedml_tpu.core.robust.DefensePipeline.METHODS` rule);
    - ``compress`` keeps its error-feedback residual in a client-id-
      keyed :class:`~fedml_tpu.core.statebank.ClientStateBank` that
      rides the block scan carry;
    - the ``gauss`` adversary draws per-row noise keyed on (round,
      client id), bitwise-equal to the stacked path at matched seeds.

    The method name itself is validated by
    :class:`~fedml_tpu.core.robust.DefensePipeline`; what remains here
    is the fednova×defense wall (owned by
    :func:`~fedml_tpu.core.robust.check_fednova_compat`), enforced by
    the callers. The function stays as the single parse-time/
    construction seam so a future wall fails loudly in one place."""
    del fed, adversary  # everything composes — see docstring


def plan_blocks(cohort: int, block_size: int, elastic: bool) -> int:
    """Number of scan blocks for a ``cohort`` streamed in blocks of
    ``block_size``. Under ``elastic`` the count is bucketed to the next
    power of two — the compiled scan length depends only on the bucket,
    so cohort churn within it is a compile-cache hit (headroom blocks
    are fully masked)."""
    if cohort < 1:
        raise ValueError(f"cohort must be >= 1, got {cohort}")
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    nb = -(-cohort // block_size)
    return bucket_for(nb) if elastic else nb


class RoundPartials(NamedTuple):
    """The O(model) streaming-aggregation vocabulary: what one block of
    local updates reduces to, and what the whole round's scan carry
    accumulates — the same ``[weighted-delta-sum, mass, n,
    metric-sums]`` shape the :class:`~fedml_tpu.core.async_agg.
    AsyncBuffer` fold speaks (its ``sum``/``mass``/``count``), so the
    bulk round, the async server, and the tier partial uplinks all
    aggregate in one algebra. Built per block by
    :func:`fedml_tpu.algorithms.fedavg.fold_block_partials` and
    finalized by ``server_update_from_partials``."""

    delta_wsum: Pytree  # Σ n_r · (clipped[, /tau_r]) delta_r, f32 leaves
    other_wsum: dict  # Σ n_r · non-param collections (batch_stats)
    n_sum: jax.Array  # Σ n_r (the mass)
    tau_wsum: jax.Array  # Σ n_r · tau_r (fednova; 0 otherwise)
    msums: dict  # additive metric sums (scalar leaves)
    rejected: jax.Array  # non-finite rows screened (scalar f32)


def stream_blocks(
    fold_block: Callable[..., Pytree],
    ids: jax.Array,
    live: jax.Array | None,
    block_size: int,
    banks: Pytree | None = None,
    positions: bool = False,
) -> Pytree:
    """Fold ``ids`` (``[S]`` client ids, ``S`` a multiple of
    ``block_size``) through ``fold_block(block_ids[, block_live])`` in
    fixed-size blocks, summing the returned partials through a
    ``lax.scan`` carry — the O(B + model) round body. ``live`` (``[S]``
    bool or None = all live) rides the scan as a per-block operand so a
    traced live count never retraces the program. A single-block cohort
    skips the scan entirely (no loop-carry layout copies for the
    B >= C case).

    ``positions=True`` additionally passes each block's global slot
    indices (``block_pos``, the block's slice of ``arange(S)``) — the
    streaming defenses scatter per-slot sketch rows by position
    (:mod:`fedml_tpu.core.streamdef`).

    ``banks`` (a pytree — typically one or more
    :class:`~fedml_tpu.core.statebank.ClientStateBank`) threads
    client-keyed state through the scan carry with REPLACE semantics:
    ``fold_block`` takes the banks as its last argument, returns
    ``(partials, banks)``, and the partials sum while the banks flow
    through updated in place (gather/scatter per block, donation-
    friendly). The call then returns ``(partials, banks)``."""
    n_slots = ids.shape[0]
    if n_slots % block_size != 0:
        raise ValueError(
            f"slot count {n_slots} is not a multiple of block size "
            f"{block_size}"
        )
    nb = n_slots // block_size
    ids_b = ids.reshape(nb, block_size)
    xs = [ids_b]
    if live is None:
        xs.append(jnp.zeros((nb,), jnp.int32))  # placeholder operand
    else:
        xs.append(live.reshape(nb, block_size))
    if positions:
        xs.append(
            jnp.arange(n_slots, dtype=jnp.int32).reshape(nb, block_size)
        )
    xs = tuple(xs)

    def call(x, bk):
        args = list(x)
        if live is None:
            args[1] = None
        if banks is None:
            return fold_block(*args)
        return fold_block(*args, bk)

    x0 = jax.tree.map(lambda a: a[0], xs)
    if nb == 1:
        return call(x0, banks)
    if banks is None:
        shapes = jax.eval_shape(lambda x: call(x, None), x0)
        zero = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

        def body(carry, x):
            return jax.tree.map(jnp.add, carry, call(x, None)), None

        out, _ = jax.lax.scan(body, zero, xs)
        return out
    p_shapes, _ = jax.eval_shape(call, x0, banks)
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p_shapes)

    def body_banked(carry, x):
        psum, bk = carry
        p, bk = call(x, bk)
        return (jax.tree.map(jnp.add, psum, p), bk), None

    (out, banks), _ = jax.lax.scan(body_banked, (zero, banks), xs)
    return out, banks


def note_round(block_size: int, n_blocks: int, padded_slots: int,
               rounds: int = 1) -> None:
    """Host-side per-dispatch telemetry for the bulk engine
    (docs/OBSERVABILITY.md vocabulary) — called by the drivers'
    ``run_round``/``run_block``, never from inside a compiled
    program. ``rounds`` is the round count this dispatch executes (a
    fused block passes its K, so ``bulk.rounds`` stays per-ROUND like
    every fused metric — the perf.* wall/K discipline). One attribute
    check when the metrics plane is off."""
    m = telemetry.METRICS
    if not m.enabled:
        return
    m.gauge("bulk.block_size", float(block_size))
    m.gauge("bulk.blocks_per_round", float(n_blocks))
    m.gauge("bulk.padded_slots", float(padded_slots))
    m.inc("bulk.rounds", float(rounds))
