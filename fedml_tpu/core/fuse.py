"""The host side of a round: the per-round loop, and round fusion's
block planning + pipelined host metric consumption.

:func:`run_loop` is the ONE loop every round driver calls
(``FedAvgSim.run``, so ``ShardedFedAvg``, and the experiment harness for
every simulator, checkpointed or not): the drivers differ only in the
hooks they hand it — how a round is dispatched, which evaluator answers,
where a record goes, what runs after a round (a checkpoint) — and a
resume is a start state and a start round. It runs :func:`drive_rounds`,
or, with ``fuse`` > 1, :func:`drive` over a :func:`plan_blocks` schedule.

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

:func:`plan_blocks` cuts the round range into blocks that never cross an
eval/checkpoint boundary (so ``eval_every % K != 0`` flushes correctly —
the block shortens to end exactly on the boundary round) and
:class:`BlockPipeline` holds the one in-flight block's device metrics.
Both loops shape records (:func:`round_record`, :func:`eval_record`) and
take the hooks the same way, so they cannot drift.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from fedml_tpu.core import memscope as M
from fedml_tpu.core import telemetry
from fedml_tpu.core.anatomy import ANATOMY
from fedml_tpu.core.tracing import log_span, span


def consume_round_counters(train_metrics: dict) -> dict:
    """Pop device-computed counter values out of a round's metric dict
    and feed them to the process metrics registry (the round loops call
    this where they already force the metrics to host, so a sync-free
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


def _scalar(v) -> bool:
    return isinstance(v, (int, float)) or getattr(v, "ndim", None) == 0


def round_record(r: int, host_metrics: dict, resumed: bool) -> dict:
    """One round's metrics, already on the host, as the record both
    loops log: scalars as floats, list-valued counters (``[C]`` leaves
    such as ``moe_rows_held_by_client``) as lists, anything else a
    host-driven sim mixes in left out. A resumed incarnation marks its
    rows: they win over any pre-crash row for the same round."""
    rec: dict = {"round": r}
    if resumed:
        rec["resumed"] = True
    for k, v in consume_round_counters(host_metrics).items():
        if _scalar(v):
            rec[k] = float(v)
        elif getattr(v, "ndim", None) == 1:
            rec[k] = [float(u) for u in v]
    return rec


def eval_record(ev: dict) -> dict:
    """An evaluator's answer as record fields: bare test-split ``acc`` /
    ``loss`` under the ``test_*`` names the summary consumers (battery
    table, wandb groupings) key on, other scalars as they are, the
    ``count`` they were divided by left out."""
    rename = {"acc": "test_acc", "loss": "test_loss"}
    return {rename.get(k, k): float(v) for k, v in ev.items()
            if k != "count" and _scalar(v)}


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


def drive_rounds(
    step: Callable[[Any, int], tuple[Any, Any]],
    state: Any,
    rounds: Iterable[int],
    *,
    path: str,
    eval_due: Callable[[int], bool],
    evaluate: Callable[[Any], dict],
    log: Callable[[dict], None],
    resumed: bool = False,
    profiler=None,
    monitor=None,
    after_round: Callable[[int, Any], None] | None = None,
) -> Any:
    """The per-round loop; returns the state after the last round.

    - ``step(state, r)`` dispatches round ``r`` and returns ``(state,
      metrics)`` — device-resident for the compiled sims, whatever a
      host-driven sim reports otherwise (a non-dict is not logged);
    - ``evaluate(state)`` answers when ``eval_due(r)``; its fields reach
      the record through :func:`eval_record`;
    - ``log(record)`` emits the finished record;
    - ``after_round(r, state)`` runs last (the harness checkpoints
      there), still inside the round's span and anatomy window.

    ONE set of boundaries (core/tracing.span): each span is a profiler
    annotation, a record in the process ring (always), and — where it
    names a phase — the anatomy plane's clock. They sit at sync points
    the loop ALREADY has (the dispatch return, the one batched
    device_get), so a span costs a few microseconds of host time and
    nothing adds a device sync.
    The round wall time is taken AFTER the metric host conversion forces
    the device, so the monitor and a capture window measure execution,
    not dispatch."""
    import jax

    for r in rounds:
        t0 = time.perf_counter()
        if profiler is not None:
            # before the span opens: an annotation is kept only if its
            # session was on when it began
            profiler.start_round(r)
        with span("fedml.round", round=r):
            ANATOMY.begin_round(r, path=path)
            # enqueue (and any retrace); lands in host_gap
            with span("fedml.dispatch"):
                state, m = step(state, r)
            if isinstance(m, dict):
                # ONE batched D2H for the whole metric dict instead of
                # a device sync per leaf: the host blocked on the
                # compiled round's execution (the sims run the whole
                # round as one program, so `local` carries it)
                with span("fedml.fetch", phase="local"):
                    m = jax.device_get(dict(m))
            else:
                m = {}
            record = round_record(r, m, resumed)
            if profiler is not None:
                profiler.end_round(r)
            if monitor is not None:
                monitor.note_round(time.perf_counter() - t0)
            if eval_due(r):
                record.update(eval_record(evaluate(state)))
            log(record)
            if after_round is not None:
                after_round(r, state)
            ANATOMY.end_round()
    return state


def drive(
    run_block: Callable[[Any, int], tuple[Any, Any]],
    state: Any,
    blocks: Iterable[tuple[int, int, bool]],
    *,
    eval_due: Callable[[int], bool],
    evaluate: Callable[[Any], dict],
    log: Callable[[dict], None],
    resumed: bool = False,
    profiler=None,
    monitor=None,
    after_round: Callable[[int, Any], None] | None = None,
) -> Any:
    """The fused round loop, with :func:`drive_rounds`' hooks; returns
    the state after the last block.

    - ``run_block(state, length)`` dispatches one block and returns
      ``(state, device-resident stacked metrics)``;
    - ``blocks`` is a :func:`plan_blocks` schedule;
    - at every boundary block the held last record gets the evaluation
      when ``eval_due``, is logged, and ``after_round(r_last, state)``
      runs — on exactly the boundary round's state.

    Each iteration is one ``fedml.block`` span (``start``, ``rounds``)
    holding ``fedml.dispatch``, the pipeline's ``fedml.fetch`` and, at a
    boundary, ``fedml.eval`` / ``fedml.log`` (core/tracing.py) — the
    fused twin of the per-round loop's ``fedml.round``.

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
        records = [round_record(start + i, row, resumed)
                   for i, row in enumerate(rows)]
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
                state, dm = run_block(state, blen)
            prev = pipeline.push(bstart, blen, dm, t0, compiled)
            if prev:
                emit(prev)
            if boundary or capturing:
                last = emit(pipeline.flush(), hold_last=boundary)
                if capturing:
                    profiler.end_round(bstart, rounds=blen)
                if boundary:
                    r_last = bstart + blen - 1
                    # the block's anatomy entry is closed: phases the
                    # spans below report (eval) amend it
                    with ANATOMY.amending():
                        if eval_due(r_last):
                            last.update(eval_record(evaluate(state)))
                        log(last)
                        if after_round is not None:
                            after_round(r_last, state)
    final = pipeline.flush()
    if final:
        emit(final)
    return state


def run_loop(
    sim: Any,
    state: Any,
    sink: Any,
    *,
    step: Callable[[Any, int], tuple[Any, Any]],
    evaluate: Callable[[Any], dict],
    path: str,
    total: int,
    eval_every: int,
    start: int = 0,
    run_block: Callable[[Any, int], tuple[Any, Any]] | None = None,
    fuse: int = 1,
    checkpoint_every: int = 0,
    after_round: Callable[[int, Any], None] | None = None,
) -> Any:
    """Rounds ``[start, total)`` of ``sim`` from ``state``, every record
    to ``sink`` (None: not logged) inside a ``fedml.log`` span; returns
    the final state. With ``cfg.fed.profile_rounds > 0`` the
    perf-observability layer (core/perf.py) rides along: jax-profiler
    capture windows around the first K rounds (device-time breakdown)
    and live ``perf.*`` gauges — round rate, MFU from the shared
    analytic cost model, and the dispatch-bound detector — for every
    round. ``run_block`` with ``fuse`` > 1 advances in fused blocks
    whose boundaries (:func:`plan_blocks`) guarantee evaluation and
    ``after_round`` see exactly the same round's state as the per-round
    loop, even when ``eval_every % fuse != 0``."""
    from fedml_tpu.core import perf as P

    def log(record):
        if sink is not None:
            with log_span(record):
                sink.log(record)

    profiler, monitor = P.build_sim_perf(sim)
    hooks = dict(
        eval_due=lambda r: (r + 1) % eval_every == 0 or r == total - 1,
        evaluate=evaluate, log=log, resumed=start > 0,
        profiler=profiler, monitor=monitor, after_round=after_round,
    )
    try:
        if run_block is not None and fuse > 1:
            return drive(
                run_block, state,
                plan_blocks(start, total, fuse, eval_every,
                            checkpoint_every),
                **hooks,
            )
        return drive_rounds(
            step, state, range(start, total), path=path, **hooks
        )
    finally:
        if profiler is not None:
            profiler.finish()
