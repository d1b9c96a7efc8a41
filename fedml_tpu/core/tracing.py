"""Tracing / profiling: structured timers and benchmark log lines.

Reference: wall-clock timers around aggregation
(``FedAVGAggregator.py:60,86-87``) and grep-able "--Benchmark" lines via
``log_round_start/end``
(``fedml_core/distributed/communication/utils.py:4-18``). Here the same
API feeds a structured in-memory trace (exportable to JSON).

:func:`span` is the ONE span primitive (docs/OBSERVABILITY.md "Spans and
scopes"), and every span has two sinks that need no switch: it enters a
``jax.profiler.TraceAnnotation``, so under a profiler session (the
benchmark's ``--trace 1``, ``--profile_rounds``, ``--profile_on_breach``)
it lands in the capture on the device's clock; and it appends to
:data:`RING`, the ONE process ring, which is always on — the rounds
nobody profiled and the set-up before them are there too. With the
anatomy plane on a ``phase=`` site feeds its duration to
``ANATOMY.phase``.

Every event carries a wall-clock ``ts`` (epoch seconds at start), the
emitting ``rank`` and thread id — the coordinates
``scripts/merge_trace.py`` needs to fold per-rank dumps into one
Chrome-trace-event timeline (Perfetto-loadable, pid = rank, tid =
thread) — and ``t0``, ``time.perf_counter()`` at its start: the clock a
harness stamps its own rounds with, so ring spans and a harness's stamps
subtract. Cross-process correlation ids (``trace_id``/``span_id``) ride
in as ordinary attrs from the telemetry layer
(:mod:`fedml_tpu.core.telemetry`).
"""

from __future__ import annotations

import collections
import functools
import json
import os
import sys
import threading
import time
from typing import Any

from jax.profiler import TraceAnnotation

_tls = threading.local()


def _open_spans() -> list:
    """This thread's stack of open spans, ``(name, round)`` each."""
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


class span:
    """``with span(name, **attrs):`` — one boundary, three sinks.

    - ALWAYS a ``jax.profiler.TraceAnnotation(name, **attrs)``: a flag
      check while no profiler session is active, an event with its
      attrs as stats (parent by nesting on the thread) while one is;
    - ALWAYS a ring event in :data:`RING` (or in an explicit
      ``_tracer``), carrying ``t0`` (``perf_counter`` at the start),
      ``seconds`` and ``parent`` — the enclosing span's name on this
      thread — so self time can be computed from the ring;
    - with ``ANATOMY.enabled`` and ``phase=``, the duration goes to
      ``ANATOMY.phase(phase, seconds)``.

    A span without a ``round`` attr inherits the enclosing span's, so
    the spans of one round share that identifier. ``seconds`` holds
    the duration afterwards. The body's value and exceptions pass
    through untouched; a raising body still leaves its ring event,
    tagged ``error``."""

    __slots__ = ("name", "attrs", "phase", "seconds", "_ann", "_tracer",
                 "_anat", "_t0", "_ts", "_parent", "_stack")

    def __init__(self, name: str, phase: str | None = None,
                 _tracer: "Tracer | None" = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.phase = phase
        self.seconds = 0.0
        self._tracer = _tracer

    def __enter__(self) -> "span":
        attrs = self.attrs
        stack = self._stack = _open_spans()
        self._parent = None
        if stack:
            self._parent, rnd = stack[-1]
            if rnd is not None and "round" not in attrs:
                attrs["round"] = rnd
        self._ann = TraceAnnotation(self.name, **attrs)
        anat = None
        if self.phase is not None:
            # anatomy imports telemetry, which imports this module: it
            # is looked up once loaded, never imported from here
            mod = sys.modules.get("fedml_tpu.core.anatomy")
            if mod is not None and mod.ANATOMY.enabled:
                anat = mod.ANATOMY
        self._anat = anat
        stack.append((self.name, attrs.get("round")))
        self._ts = time.time()
        # last before the annotation opens: the two clocks of one span
        # (this one, the capture's) start as close as they can
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        self.seconds = dt = time.perf_counter() - self._t0
        self._stack.pop()
        tr = self._tracer
        if tr is None:
            tr = RING
        # Tracer._base's record, written out: this runs 4-5 times a round
        ev = {
            "kind": "span", "ts": self._ts, "t0": self._t0, "seconds": dt,
            "rank": tr.rank, "tid": threading.get_ident() & 0xFFFFFFFF,
            "name": self.name, "parent": self._parent,
        }
        if self.attrs:
            ev.update(self.attrs)  # may override rank: shared-process worlds
        if exc is not None:
            # the span record must survive a raising body: a failing
            # round still leaves its timing (tagged with the error)
            # instead of silently dropping it
            ev["error"] = repr(exc)
        tr._emit(ev)
        if self._anat is not None:
            self._anat.phase(self.phase, dt)
        return False


def build_span(init):
    """Decorator of a simulator's ``__init__``: what the constructor
    does once its arguments are bound (model creation, banks, placing
    the population and the test set) runs inside a ``fedml.build`` span
    whose ``sim`` attr names the class the constructor is written in —
    a subclass that calls ``super().__init__`` nests its base's."""
    owner = init.__qualname__.rsplit(".", 2)[-2]

    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        with span("fedml.build", sim=owner):
            init(self, *args, **kwargs)

    return __init__


#: round-record keys that are counts of the model's own
#: (``FedModel.counters``), carried on ``fedml.log`` as ``slot_steps`` is
COUNTER_PREFIXES = ("moe_rows_", "attn_keys_", "delta_chunks")


def log_span(record: dict) -> span:
    """The ``fedml.log`` span of one round record (every round loop's
    ``sink.log`` site). A counter the round program reported rides it
    as an attr, so a profiler capture holds it beside the device's
    timeline: ``slot_steps`` (the sharded cohort round) and the model's
    own counters (``FedModel.counters``: the decoder's ``moe_rows_*``,
    ``attn_keys_*`` and ``delta_chunks*``;
    a per-client list rides as its text, ``[a, b]``)."""
    attrs = {"round": record["round"]}
    for name, value in record.items():
        if name == "slot_steps" or name.startswith(COUNTER_PREFIXES):
            # a list rides as text that begins with no digit: the
            # profiler reads "31798,32360" back as the number 31798
            attrs[name] = (
                str([int(v) for v in value])
                if isinstance(value, (list, tuple)) else int(value)
            )
    return span("fedml.log", **attrs)


#: events :data:`RING` holds: set-up (under a hundred spans) and ten
#: minutes of the fastest benchmark cell's rounds — 10.3 rounds/s x
#: 600 s x 4.2 spans a round (round, dispatch, fetch, log, every fifth
#: round eval) = 25,956 — with a quarter to spare; about 16 MB when full
RING_CAPACITY = 32_768


class Tracer:
    """Bounded collector of span / event / round records.

    ``events`` is a ring (``max_events``): a multi-thousand-round run
    keeps the most recent window instead of growing RSS without bound.
    A ring that evicted says so: ``dropped`` counts evictions and
    ``complete_from`` is the ``perf_counter`` time from which it is
    whole — the end of the newest evicted record; a thread's records
    arrive in the order they END, so every span that began at or after
    it is still held (None while nothing was dropped). Both are
    recorded in :meth:`dump`.
    """

    def __init__(self, rank: int | None = None,
                 max_events: int = RING_CAPACITY):
        self.events: collections.deque[dict[str, Any]] = collections.deque(
            maxlen=max_events
        )
        self.dropped = 0
        self.complete_from: float | None = None
        self._open: dict[int, tuple[float, float]] = {}
        self.rank = rank
        self._lock = threading.Lock()

    def _emit(self, ev: dict[str, Any]) -> None:
        with self._lock:
            events = self.events
            if len(events) == events.maxlen:
                old = events[0]
                self.dropped += 1
                self.complete_from = old["t0"] + old["seconds"]
            events.append(ev)

    def clear(self) -> None:
        """Forget everything held (``telemetry.shutdown``; tests)."""
        with self._lock:
            self.events.clear()
            self.dropped = 0
            self.complete_from = None
            self._open.clear()

    def _base(self, kind: str, ts: float, t0: float, seconds: float,
              name: str | None = None) -> dict[str, Any]:
        ev = {
            "kind": kind,
            "ts": ts,
            "t0": t0,
            "seconds": seconds,
            "rank": self.rank,
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if name is not None:
            ev["name"] = name
        return ev

    # -- reference-shaped API (communication/utils.py:4-18) ----------------
    def log_round_start(self, round_idx: int):
        self._open[round_idx] = (time.perf_counter(), time.time())

    def log_round_end(self, round_idx: int):
        t0 = self._open.pop(round_idx, None)
        if t0 is not None:
            ev = self._base(
                "round", t0[1], t0[0], time.perf_counter() - t0[0])
            ev["round"] = round_idx
            self._emit(ev)

    # -- generic spans -----------------------------------------------------
    def span(self, name: str, **attrs):
        """:func:`span` with THIS tracer as its ring (in place of
        :data:`RING`)."""
        return span(name, _tracer=self, **attrs)

    def event(self, name: str, **attrs):
        """Instant event (zero duration) — message sends/delivers, fault
        injections, dead-peer marks."""
        ev = self._base(
            "event", time.time(), time.perf_counter(), 0.0, name)
        ev.update(attrs)  # may override rank: shared-process worlds
        self._emit(ev)

    # -- reporting ---------------------------------------------------------
    def summary(self) -> dict[str, dict]:
        agg: dict[str, dict] = {}
        with self._lock:
            events = list(self.events)
        for e in events:
            key = e.get("name") or e["kind"]
            s = agg.setdefault(key, {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += e["seconds"]
        for s in agg.values():
            s["mean_s"] = s["total_s"] / s["count"]
        return agg

    def dump(self, path: str, **extra):
        """Write ``{rank, dropped, complete_from, events}`` and what
        the caller knows of the run besides (``extra``, top-level keys
        of its own choosing)."""
        with self._lock:
            events = list(self.events)
            dropped, complete_from = self.dropped, self.complete_from
        # atomic replace: a crash mid-flush (or a concurrent
        # merge_trace.py read) must never observe a truncated dump —
        # this artifact exists precisely for crash debugging
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {**extra, "rank": self.rank, "dropped": dropped,
                 "complete_from": complete_from, "events": events},
                f, indent=2, default=repr,
            )
        os.replace(tmp, path)


#: THE process ring: every :func:`span` of this process ends in it, from
#: import on, with no switch. ``telemetry.configure(trace=True)`` hands
#: out this same object as ``telemetry.TRACER`` (rank set), which is what
#: the message-level sites guard on.
RING = Tracer()
