"""Round fusion: block planning + pipelined host metric consumption.

The headline MFU problem (ROADMAP item 5, docs/PERFORMANCE.md "Round
fusion") is a host-round-trip problem: the per-round loop dispatches one
compiled round, then immediately blocks converting that round's metric
leaves to host floats before it may dispatch the next — the device idles
for the whole host turnaround, every round. Fusion attacks both halves:

- **fewer dispatches**: with ``FedConfig.fuse_rounds = K`` the sims run
  K complete rounds as ONE compiled program (``lax.scan`` over the round
  body — see ``FedAvgSim._fused_block``), so the per-round host
  turnaround is paid once per block;
- **pipelined consumption**: the round loop keeps block k+1's dispatch
  in flight while the host converts block k's stacked metrics
  (:class:`BlockPipeline` — ONE batched ``jax.device_get`` per block
  instead of one transfer per metric leaf per round), blocking only at
  eval / checkpoint / profiler-capture boundaries.

This module owns the driver-side machinery shared by the two round-loop
drivers (``FedAvgSim.run`` and the experiment harness — the same
mutually-exclusive-drivers pairing that shares ``perf.build_sim_perf``):
:func:`plan_blocks` cuts the round range into blocks that never cross an
eval/checkpoint boundary (so ``eval_every % K != 0`` flushes correctly —
the block shortens to end exactly on the boundary round),
:class:`BlockPipeline` holds the one in-flight block's device metrics,
and :func:`drive` is the loop itself, parameterized by the per-driver
hooks (record shaping, logging, the eval/checkpoint boundary action) so
the two drivers cannot drift.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from fedml_tpu.core.anatomy import ANATOMY
from fedml_tpu.core.tracing import span


def plan_blocks(
    start: int,
    total: int,
    fuse: int,
    eval_every: int,
    checkpoint_every: int = 0,
) -> Iterator[tuple[int, int, bool]]:
    """Cut rounds ``[start, total)`` into fused blocks of at most
    ``fuse`` rounds, never crossing a boundary round. Yields
    ``(block_start, length, boundary)`` where ``boundary`` is True when
    the block's LAST round is an eval round (``(r+1) % eval_every ==
    0``), a checkpoint round, or the final round — the driver must
    flush the metric pipeline and sync there (the state it evaluates /
    checkpoints is exactly the boundary round's, same as the unfused
    loop). With ``fuse == 1`` every round is its own block, which is
    the unfused schedule."""
    if fuse < 1:
        raise ValueError(f"fuse must be >= 1, got {fuse}")

    def is_boundary(r: int) -> bool:
        return (
            (r + 1) % eval_every == 0
            or (checkpoint_every > 0 and (r + 1) % checkpoint_every == 0)
            or r == total - 1
        )

    r = start
    while r < total:
        n = 0
        while n < fuse and r + n < total:
            n += 1
            if is_boundary(r + n - 1):
                break
        yield r, n, is_boundary(r + n - 1)
        r += n


class BlockPipeline:
    """One-deep pipeline of a fused block's device-resident metrics.

    ``push`` stores the just-dispatched block's stacked metrics and
    returns the PREVIOUS block, flushed — since dispatch is async, the
    previous block's ``device_get`` (and the host-side row conversion
    the caller does with it) overlaps the current block's device
    execution. ``flush`` drains the pending block synchronously (eval /
    checkpoint / profiler boundaries, end of run).

    Flushed blocks come back as ``(start, length, rows, wall_s,
    compiled)``: ``rows`` is one host dict per round (sliced
    out of the ``[K, ...]`` stacked leaves — one batched transfer for
    the whole block), ``wall_s`` spans dispatch -> metrics-on-host, i.e.
    the block's execution in the steady state (the next block was
    already enqueued when the flush started waiting), ``compiled``
    echoes the flag the dispatcher pushed (True when this dispatch
    traced a fresh block program — its wall is compile-dominated and
    must stay out of the per-round SLO surface). The ``device_get`` is
    the ``fedml.fetch`` span (core/tracing.py): a sync the pipeline
    already pays, and the anatomy plane's ``local`` attribution — the
    block's anatomy entry (core/anatomy.py) opens and closes here, one
    per fused block; dispatch + host row conversion land in
    ``host_gap``, and the driver's boundary hook amends eval/checkpoint
    onto the entry afterwards."""

    def __init__(self) -> None:
        self._pending: tuple[int, int, Any, float, bool] | None = None

    def push(
        self, start: int, length: int, device_metrics: Any, t0: float,
        compiled: bool = False,
    ) -> tuple[int, int, list[dict], float, bool] | None:
        prev = self.flush()
        self._pending = (start, length, device_metrics, t0, compiled)
        return prev

    def flush(
        self,
    ) -> tuple[int, int, list[dict], float, bool] | None:
        if self._pending is None:
            return None
        import jax

        start, n, dm, t0, compiled = self._pending
        self._pending = None
        ANATOMY.begin_round(start, path="fused", rounds=n)
        with span("fedml.fetch", phase="local", start=start, rounds=n):
            host = jax.device_get(dm)  # one batched D2H for the block
        wall = time.perf_counter() - t0
        ANATOMY.end_round(wall_s=wall)
        rows = [
            {k: np.asarray(v)[i] for k, v in host.items()}
            for i in range(n)
        ]
        return start, n, rows, wall, compiled


def drive(
    run_block: Callable[[int], Any],
    blocks: Iterable[tuple[int, int, bool]],
    *,
    profiler=None,
    monitor=None,
    make_records: Callable[[int, list[dict]], list[dict]],
    log: Callable[[dict], None],
    boundary_hook: Callable[[int, dict], None],
) -> None:
    """The fused round loop, shared by ``FedAvgSim._run_fused`` and the
    harness ``Experiment._fused_loop`` so the two drivers cannot drift.

    - ``run_block(length)`` dispatches one block and returns its
      device-resident stacked metrics (the caller owns the state);
    - ``blocks`` is a :func:`plan_blocks` schedule;
    - ``make_records(start, rows)`` shapes one host row per round into
      the driver's record dicts (consuming device counters);
    - ``log(record)`` emits a finished record;
    - ``boundary_hook(r_last, last_record)`` runs at every boundary
      block with the held last record — the driver evaluates /
      checkpoints there and must log ``last_record`` itself.

    Each iteration is one ``fedml.block`` span (``start``, ``rounds``)
    holding ``fedml.dispatch``, the pipeline's ``fedml.fetch`` and, at a
    boundary, the hook's ``fedml.eval`` / ``fedml.log``
    (core/tracing.py) — the fused twin of the per-round loop's
    ``fedml.round``.

    Pipelining: block k+1's dispatch goes out before block k's metrics
    are fetched, so the host-side conversion overlaps device execution;
    the pipeline drains at boundaries and around profiler captures.
    The FIRST dispatch of each distinct block length traces a fresh
    scan program — that block's wall is compile-dominated, so it is
    flagged to :meth:`PerfMonitor.note_block` as ``compiled`` and
    excluded from the per-round SLO surface like the warmup round
    (otherwise the remainder lengths an eval/checkpoint cadence forces
    would put an XLA compile into the p99)."""
    pipeline = BlockPipeline()
    seen_lengths: set[int] = set()

    def emit(flushed, hold_last=False):
        start, blen, rows, wall, compiled = flushed
        if monitor is not None:
            monitor.note_block(wall, blen, compiled=compiled)
        records = make_records(start, rows)
        last = records.pop() if hold_last else None
        for rec in records:
            log(rec)
        return last

    for bstart, blen, boundary in blocks:
        with span("fedml.block", start=bstart, rounds=blen):
            capturing = profiler is not None and profiler.wants_capture
            if capturing:
                # a capture window must contain exactly this block's
                # device work: drain the pipeline first
                prev = pipeline.flush()
                if prev:
                    emit(prev)
                profiler.start_round(bstart)
            compiled = blen not in seen_lengths
            seen_lengths.add(blen)
            t0 = time.perf_counter()
            with span("fedml.dispatch"):
                dm = run_block(blen)
            prev = pipeline.push(bstart, blen, dm, t0, compiled)
            if prev:
                emit(prev)
            if boundary or capturing:
                last = emit(pipeline.flush(), hold_last=boundary)
                if capturing:
                    profiler.end_round(bstart, rounds=blen)
                if boundary:
                    # the block's anatomy entry is closed: phases the
                    # hook's spans report (eval) amend it
                    with ANATOMY.amending():
                        boundary_hook(bstart + blen - 1, last)
    final = pipeline.flush()
    if final:
        emit(final)
