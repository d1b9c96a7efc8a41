"""Round anatomy: per-phase time attribution, straggler accounting,
and SLO-breach-triggered deep profiles.

The perf plane (core/perf.py), the SLO engine (core/slo.py), and the
memory plane (core/memscope.py) answer *what* degraded — ``slo.ok``
flipped, ``perf.mfu`` sagged, headroom shrank. This module is the *why*
plane (docs/OBSERVABILITY.md "Round anatomy"): it attributes each
round's wall time to a fixed phase vocabulary, attributes barrier wait
to the slowest contributors, and — armed with ``--profile_on_breach`` —
captures a one-shot ``jax.profiler`` window at the moment an SLO breach
transition (or a ``mem_headroom`` crossing) happens, so the run
diagnoses itself instead of requiring a human to reproduce the bad
state. The Smart-NIC FL paper (arxiv 2307.06561) motivates exactly this
server-side bottleneck decomposition; FedJAX (arxiv 2108.02117) is the
reminder that throughput claims are only trustworthy when the per-phase
breakdown is measured, not inferred.

Three legs:

- :class:`RoundAnatomy` — per-round phase attribution over the fixed
  vocabulary :data:`PHASES`, timed at sync points the round ALREADY has
  (the run loop's dispatch boundary, the one ``jax.device_get`` host
  force, eval returns, checkpoint blocks; never a new
  ``block_until_ready`` on the hot path). The residual between the
  explicit phases and the round wall is itself exported as
  ``host_gap`` — attribution is conserved, never silently dropped.
  Emits ``perf.phase.<name>_s`` histograms + the ``perf.phase.dominant``
  gauge, keeps a last-N-rounds ring served as the ``/tracez`` section
  of the live listener (core/export.py), and — on the deploy server —
  computes the per-round critical path + straggler attribution from the
  result-arrival timestamps the round close already collects
  (``perf.straggler_wait_s``, capped ``perf.straggler.rank<r>`` via the
  ``gauge_labeled`` cardinality machinery).
- critical-path trace events — rank 0 emits one ``critical_path``
  tracer event per closed round (sync → slowest-contributor wait →
  aggregate); ``scripts/merge_trace.py`` renders them as a dedicated
  track in the merged Perfetto view.
- :class:`BreachProfiler` — a one-shot ``jax.profiler.trace`` window
  (``--profile_window_s``, default 5 s) fired on an SLO breach
  *transition* or a ``mem_headroom`` crossing, capped by
  ``--profile_max_captures`` with a cooldown between windows, written
  under ``<telemetry_dir>/profiles/`` with a flight-recorder event
  linking breach → artifact path. The capture runs on a timer thread
  and NEVER extends a round deadline (docs/FAULT_TOLERANCE.md).

Like every other plane, disabled is the default and costs nothing:
:data:`ANATOMY` starts ``enabled=False`` (every call site guards on one
attribute check and the round results are byte-identical — pinned in
``tests/test_anatomy.py``), and no profiler is armed until
:func:`configure`. ``telemetry.shutdown()`` resets this module lazily,
the same way it resets the memory plane.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import threading
import time
from typing import Any

from fedml_tpu.core import telemetry

#: The fixed phase vocabulary (docs/OBSERVABILITY.md "Round anatomy").
#: Not every path emits every phase — a compiled simulator round is one
#: fused program, so ``local`` carries the whole device execution there,
#: while the deploy server decomposes ``wire``/``defense_agg``/
#: ``server_update``/``checkpoint`` at the boundaries its close path
#: already syncs on. ``host_gap`` is always the residual.
PHASES = (
    "sample",
    "h2d",
    "local",
    "defense_agg",
    "server_update",
    "wire",
    "eval",
    "checkpoint",
    "host_gap",
)

#: ``/tracez`` ring depth: the last N closed rounds' anatomy entries.
RING_CAPACITY = 64

#: Seconds a finished capture window blocks the next one — breaches
#: often arrive in bursts (every tick of a breached window transitions
#: nothing, but flapping SLOs re-transition), and back-to-back windows
#: would trade the whole capture budget for near-duplicate artifacts.
DEFAULT_COOLDOWN_S = 30.0


class RoundAnatomy:
    """Per-round phase attribution + the ``/tracez`` anatomy ring.

    One instance per process (:data:`ANATOMY`). All methods no-op while
    ``enabled`` is False, so the disabled hot path is one attribute
    check at each call site. The sims' loops hold no clock of their
    own: their phases arrive from the span sites
    (:func:`fedml_tpu.core.tracing.span` with ``phase=``), which read
    the clock for the process ring in any case.
    """

    def __init__(self, ring_capacity: int = RING_CAPACITY):
        self.enabled = False
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=ring_capacity
        )
        self._open: dict[str, Any] | None = None
        # inside amending(): a phase with no open round amends the
        # last closed entry instead of being dropped
        self._amend = False
        # deploy server: per-round result-arrival timestamps
        # (rank -> perf_counter seconds), the straggler-attribution and
        # critical-path inputs the close path already collects
        self._arrivals: dict[int, float] = {}
        self._rounds = 0

    # -- round lifecycle ---------------------------------------------------

    def begin_round(self, round_idx: int, path: str = "stacked",
                    rounds: int = 1) -> None:
        """Open the round's attribution window. ``path`` names the round
        body that will run (``stacked``/``bulk``/``fused``/``sharded``/
        ``personal``/``deploy``); ``rounds`` > 1 means this window spans
        a fused block of that many rounds and the per-round histogram
        observations are divided accordingly (the same normalization
        ``PerfMonitor.note_block`` applies to ``perf.round_wall_s``)."""
        if not self.enabled:
            return
        with self._lock:
            self._open = {
                "round": int(round_idx),
                "path": path,
                "rounds": max(1, int(rounds)),
                "t0": time.perf_counter(),
                "phases": {},
            }
            self._arrivals = {}

    def phase(self, name: str, seconds: float) -> None:
        """Attribute ``seconds`` to ``name`` inside the open round
        (accumulating — eval and checkpoint legs may land in several
        pieces). ``name`` must be in :data:`PHASES`; an unknown phase is
        a programming error, not a metric to invent."""
        if not self.enabled:
            return
        if name not in PHASES:
            raise ValueError(
                f"unknown anatomy phase {name!r}; the vocabulary is "
                f"fixed (docs/OBSERVABILITY.md): {PHASES}"
            )
        with self._lock:
            if self._open is not None:
                p = self._open["phases"]
                p[name] = p.get(name, 0.0) + max(0.0, float(seconds))
                return
            late = self._amend
        if late:
            self.amend_last(name, seconds)

    @contextlib.contextmanager
    def amending(self):
        """Phases reported inside land on the LAST closed ring entry
        (:meth:`amend_last`) — the fused drivers close each block's
        entry at the pipeline flush and only then run the boundary
        eval/checkpoint, whose spans (core/tracing.py ``phase=``)
        report here with no round open."""
        self._amend = True
        try:
            yield
        finally:
            self._amend = False

    def note_arrival(self, rank: int, ts: float | None = None) -> None:
        """Deploy server: timestamp a client result's arrival (one host
        clock read on the receive edge — the straggler-attribution
        input)."""
        if not self.enabled:
            return
        with self._lock:
            if self._open is None:
                return
            self._arrivals.setdefault(
                int(rank), time.perf_counter() if ts is None else ts
            )

    def end_round(self, wall_s: float | None = None) -> dict | None:
        """Close the window: compute ``host_gap`` as the residual
        between the explicit phases and the round wall (clamped at 0 —
        clock jitter may oversum by microseconds), emit the
        ``perf.phase.<name>_s`` histograms (per-round normalized for
        fused blocks) + the ``perf.phase.dominant`` gauge, and append
        the entry to the ``/tracez`` ring. Returns the ring entry (None
        while disabled / unopened)."""
        if not self.enabled:
            return None
        with self._lock:
            ent = self._open
            self._open = None
            if ent is None:
                return None
            wall = (time.perf_counter() - ent["t0"]
                    if wall_s is None else float(wall_s))
            phases = ent["phases"]
            explicit = sum(phases.values())
            phases["host_gap"] = max(0.0, wall - explicit)
            k = ent["rounds"]
            dominant = max(phases, key=phases.get) if phases else None
            entry = {
                "round": ent["round"],
                "path": ent["path"],
                "rounds": k,
                "wall_s": wall,
                "phases": {n: phases[n] for n in PHASES if n in phases},
                "dominant": dominant,
                "ts": time.time(),
            }
            self._ring.append(entry)
            self._rounds += 1
        m = telemetry.METRICS
        for name, sec in phases.items():
            m.observe(f"perf.phase.{name}_s", sec / k)
        if dominant is not None:
            m.gauge("perf.phase.dominant", float(PHASES.index(dominant)))
        return entry

    def amend_last(self, name: str, seconds: float) -> None:
        """Attribute ``seconds`` to ``name`` on the LAST closed ring
        entry (what :meth:`phase` does inside :meth:`amending`), so
        boundary phases amend the block they belong to. The entry's
        wall grows by the same amount: attribution stays conserved
        (phases still sum to wall_s) and ``host_gap`` is untouched."""
        if not self.enabled:
            return
        if name not in PHASES:
            raise ValueError(
                f"unknown anatomy phase {name!r}; the vocabulary is "
                f"fixed (docs/OBSERVABILITY.md): {PHASES}"
            )
        sec = max(0.0, float(seconds))
        with self._lock:
            if not self._ring:
                return
            e = self._ring[-1]
            e["phases"][name] = e["phases"].get(name, 0.0) + sec
            e["wall_s"] += sec
            e["dominant"] = max(e["phases"], key=e["phases"].get)
        telemetry.METRICS.observe(f"perf.phase.{name}_s", sec)

    # -- straggler + critical path (deploy server, rank 0) -----------------

    def attribute_stragglers(
        self, round_idx: int, t_sync: float, t_close: float,
        t_agg_s: float = 0.0,
    ) -> int | None:
        """Attribute the closed round's barrier wait to its slowest
        contributors from the arrival timestamps collected by
        :meth:`note_arrival`, and emit the per-round critical path.

        - ``perf.straggler_wait_s`` — seconds the round barrier spent
          waiting after the FIRST result had already arrived (the time
          bought by fixing the slowest contributor);
        - ``perf.straggler.rank<r>`` — each contributor's margin behind
          the first arrival, capped by the ``gauge_labeled``
          cardinality machinery so a 10k-client world stays bounded;
        - ``perf.critical_path_s`` — sync → slowest-contributor arrival
          → aggregate, the longest dependent chain through the round;
        - one ``critical_path`` tracer event carrying the segments,
          which ``scripts/merge_trace.py`` renders as a dedicated track.

        Returns the dominant straggler's rank (None without >= 2
        arrivals — a single contributor has no barrier to wait on).
        """
        if not self.enabled:
            return None
        with self._lock:
            arrivals = dict(self._arrivals)
        if not arrivals:
            return None
        first = min(arrivals.values())
        last_rank = max(arrivals, key=arrivals.get)
        last = arrivals[last_rank]
        m = telemetry.METRICS
        if len(arrivals) >= 2:
            m.observe("perf.straggler_wait_s", last - first)
            for r, at in arrivals.items():
                m.gauge_labeled("perf.straggler", f"rank{r}", at - first)
        critical = (last - t_sync) + t_agg_s
        m.gauge("perf.critical_path_s", max(0.0, critical))
        tr = telemetry.TRACER
        if tr is not None:
            tr.event(
                "critical_path",
                round=int(round_idx),
                rank_path=int(last_rank),
                sync_to_result_s=max(0.0, last - t_sync),
                straggler_wait_s=max(0.0, last - first),
                aggregate_s=max(0.0, t_agg_s),
                total_s=max(0.0, critical),
                closed_after_s=max(0.0, t_close - t_sync),
            )
        return last_rank if len(arrivals) >= 2 else None

    # -- /tracez -----------------------------------------------------------

    def tracez(self, rank: int = 0) -> dict:
        """The ``/tracez`` section payload (core/export.py): the last-N
        closed rounds' anatomy entries, newest last."""
        with self._lock:
            entries = [dict(e) for e in self._ring]
            return {
                "rank": rank,
                "phases": list(PHASES),
                "capacity": self._ring.maxlen,
                "rounds": self._rounds,
                "entries": entries,
            }

    def reset(self) -> None:
        with self._lock:
            self.enabled = False
            self._ring.clear()
            self._open = None
            self._amend = False
            self._arrivals = {}
            self._rounds = 0


class BreachProfiler:
    """One-shot ``jax.profiler.trace`` windows fired on degradation.

    Armed by ``--profile_on_breach`` (requires ``--slo`` or
    ``--mem_headroom_warn`` — without a breach source the trigger can
    never fire, which parse-time validation rejects). Each trigger:

    - is a breach *transition* (ok -> breach from the SLO engine) or the
      memory plane's one-shot ``mem_headroom`` crossing — never one
      capture per breached tick;
    - starts ``jax.profiler.start_trace`` into
      ``<telemetry_dir>/profiles/breach_<n>_<reason>/`` and stops it
      ``window_s`` later from a daemon timer thread, so a capture never
      blocks the round loop or extends a round deadline
      (docs/FAULT_TOLERANCE.md);
    - records one ``breach_profile`` flight event linking the breach to
      the artifact path, and writes a ``breach.json`` manifest inside
      the artifact dir;
    - respects the ``max_captures`` cap and a ``cooldown_s`` gap between
      windows — skipped triggers count ``profile.skipped`` and record a
      ``breach_profile_skipped`` flight event instead of silently
      vanishing.

    ``jax.profiler`` allows ONE live session per process: a trigger
    while another session is active (``--profile_rounds``'s
    ``RoundProfiler``, or an unfinished breach window) is a skip, and a
    start/stop failure marks the profiler broken (``profile.failed``)
    rather than crashing the run — the same containment contract
    ``core/perf.py`` uses. ``clock``/``timer`` are injectable so the
    cap + cooldown semantics are testable without wall sleeps.
    """

    def __init__(
        self,
        out_dir: str,
        window_s: float = 5.0,
        max_captures: int = 3,
        cooldown_s: float = DEFAULT_COOLDOWN_S,
        clock=time.monotonic,
        timer=None,
    ):
        if not (window_s > 0):
            raise ValueError(
                f"--profile_window_s must be > 0, got {window_s!r}"
            )
        if max_captures < 1:
            raise ValueError(
                f"--profile_max_captures must be >= 1, got "
                f"{max_captures!r}"
            )
        self.out_dir = out_dir
        self.window_s = float(window_s)
        self.max_captures = int(max_captures)
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._timer = timer or self._default_timer
        self._lock = threading.Lock()
        self._captures = 0
        self._active_path: str | None = None
        self._last_end: float | None = None
        self._broken = False
        self._pending: threading.Timer | None = None

    @staticmethod
    def _default_timer(delay_s: float, fn) -> threading.Timer:
        t = threading.Timer(delay_s, fn)
        t.daemon = True
        t.start()
        return t

    @property
    def captures(self) -> int:
        return self._captures

    @property
    def active(self) -> bool:
        return self._active_path is not None

    def _skip(self, reason: str, why: str) -> None:
        telemetry.METRICS.inc("profile.skipped")
        telemetry.RECORDER.record(
            "breach_profile_skipped", reason=reason, why=why
        )

    def on_breach(self, reason: str, **attrs) -> str | None:
        """Fire one capture window for this breach (returns the artifact
        directory, or None for a skip/failure)."""
        import jax

        with self._lock:
            if self._broken:
                self._skip(reason, "profiler broken")
                return None
            if self._active_path is not None:
                self._skip(reason, "capture window already open")
                return None
            if self._captures >= self.max_captures:
                self._skip(
                    reason,
                    f"capture cap spent ({self.max_captures})",
                )
                return None
            now = self._clock()
            if (self._last_end is not None
                    and now - self._last_end < self.cooldown_s):
                self._skip(
                    reason,
                    f"cooldown ({self.cooldown_s}s since last window)",
                )
                return None
            n = self._captures + 1
            slug = re.sub(r"[^0-9a-zA-Z_.-]+", "_", reason)[:80]
            path = os.path.join(self.out_dir,
                                f"breach_{n}_{slug}")
            try:
                os.makedirs(path, exist_ok=True)
                jax.profiler.start_trace(path)
            except Exception as err:
                # one live session per process: a collision with
                # --profile_rounds (or a broken runtime) must contain,
                # not crash — the run matters more than its profile
                self._broken = True
                telemetry.METRICS.inc("profile.failed")
                telemetry.RECORDER.record(
                    "breach_profile_failed", reason=reason,
                    error=repr(err),
                )
                return None
            self._captures = n
            self._active_path = path
            telemetry.METRICS.inc("profile.captures")
            telemetry.METRICS.gauge("profile.active", 1.0)
            telemetry.RECORDER.record(
                "breach_profile", reason=reason, path=path,
                window_s=self.window_s, capture=n, **attrs,
            )
            try:
                with open(os.path.join(path, "breach.json"), "w") as f:
                    json.dump(
                        {
                            "reason": reason,
                            "capture": n,
                            "window_s": self.window_s,
                            "ts": time.time(),
                            **{k: repr(v) if not isinstance(
                                v, (int, float, str, bool, type(None))
                            ) else v for k, v in attrs.items()},
                        },
                        f, indent=2,
                    )
            except OSError:
                pass  # the manifest must never fail the capture
            self._pending = self._timer(self.window_s, self._stop)
            return path

    def _stop(self) -> None:
        import jax

        with self._lock:
            path = self._active_path
            if path is None:
                return
            self._active_path = None
            self._pending = None
            self._last_end = self._clock()
            try:
                jax.profiler.stop_trace()
            except Exception as err:
                self._broken = True
                telemetry.METRICS.inc("profile.failed")
                telemetry.RECORDER.record(
                    "breach_profile_failed", path=path, error=repr(err)
                )
                telemetry.METRICS.gauge("profile.active", 0.0)
                return
            telemetry.METRICS.gauge("profile.active", 0.0)
            telemetry.RECORDER.record("breach_profile_done", path=path)

    def close(self) -> None:
        """Stop any open window now (shutdown path — a dangling
        ``jax.profiler`` session would break the next run's profilers
        in-process)."""
        with self._lock:
            pending = self._pending
        if pending is not None:
            try:
                pending.cancel()
            except Exception:
                pass
        self._stop()


#: Process-global anatomy plane — disabled until :func:`configure`.
ANATOMY = RoundAnatomy()
_BREACH: BreachProfiler | None = None


def breach_profiler() -> BreachProfiler | None:
    return _BREACH


def notify_mem_headroom(**fields) -> None:
    """The memory plane's one-shot ``mem_headroom`` crossing forwards
    here (lazily — memscope only calls this if anatomy was ever
    imported), the second breach-profile trigger alongside SLO
    transitions."""
    p = _BREACH
    if p is not None:
        p.on_breach("mem_headroom", **fields)


def _on_slo_transition(spec, breaching: bool, value) -> None:
    if breaching and _BREACH is not None:
        _BREACH.on_breach(
            f"slo_{spec.slug}", slo=spec.describe(),
            scope=spec.scope, value=value,
        )


def configure(
    anatomy: bool = False,
    ring_capacity: int = RING_CAPACITY,
    profile_on_breach: bool = False,
    profile_window_s: float = 5.0,
    profile_max_captures: int = 3,
    cooldown_s: float = DEFAULT_COOLDOWN_S,
) -> None:
    """Arm the round-anatomy plane for THIS process (idempotent; call
    AFTER :func:`telemetry.configure` — the breach profiler needs the
    telemetry dir and subscribes to the SLO engine built there).

    ``anatomy=True`` switches phase attribution + the ``/tracez`` ring
    on. ``profile_on_breach=True`` arms the :class:`BreachProfiler`
    under ``<telemetry_dir>/profiles/`` and registers its SLO-breach
    listener; without a telemetry dir there is nowhere to write the
    artifact, so arming requires one (run.py guarantees it the same way
    ``--trace`` does)."""
    global _BREACH
    if anatomy:
        if ANATOMY._ring.maxlen != ring_capacity:
            ANATOMY._ring = collections.deque(
                ANATOMY._ring, maxlen=ring_capacity
            )
        ANATOMY.enabled = True
    if profile_on_breach and _BREACH is None:
        tdir = telemetry.artifact_dir()
        if tdir is None:
            raise ValueError(
                "--profile_on_breach needs a telemetry dir for its "
                "artifacts (configure telemetry first)"
            )
        _BREACH = BreachProfiler(
            os.path.join(tdir, "profiles"),
            window_s=profile_window_s,
            max_captures=profile_max_captures,
            cooldown_s=cooldown_s,
        )
        eng = telemetry.slo_engine()
        if eng is not None:
            eng.add_transition_listener(_on_slo_transition)


def reset() -> None:
    """Return to the all-disabled state (``telemetry.shutdown()`` calls
    this lazily, like the memory plane's reset)."""
    global _BREACH
    if _BREACH is not None:
        try:
            _BREACH.close()
        except Exception:
            pass
        _BREACH = None
    ANATOMY.reset()
