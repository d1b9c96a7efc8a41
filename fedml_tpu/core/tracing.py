"""Tracing / profiling: structured timers and benchmark log lines.

Reference: wall-clock timers around aggregation
(``FedAVGAggregator.py:60,86-87``) and grep-able "--Benchmark" lines via
``log_communication_tick/tock`` + ``log_round_start/end``
(``fedml_core/distributed/communication/utils.py:4-18``). Here the same
API feeds a structured in-memory trace (exportable to JSON).

:func:`span` is the ONE span primitive (docs/OBSERVABILITY.md "Spans and
scopes"): it always enters a ``jax.profiler.TraceAnnotation``, so the
switch for "tracing on" is a profiler session being active (the
benchmark's ``--trace 1``, ``--profile_rounds``, ``--profile_on_breach``)
and host spans land in the capture on the device's clock; with a
configured tracer it also appends to the ring, and with the anatomy plane
on a ``phase=`` site feeds its duration to ``ANATOMY.phase``.

Every event carries a wall-clock ``ts`` (epoch seconds at start), the
emitting ``rank`` and thread id — the coordinates
``scripts/merge_trace.py`` needs to fold per-rank dumps into one
Chrome-trace-event timeline (Perfetto-loadable, pid = rank, tid =
thread). Cross-process correlation ids (``trace_id``/``span_id``) ride
in as ordinary attrs from the telemetry layer
(:mod:`fedml_tpu.core.telemetry`).
"""

from __future__ import annotations

import collections
import json
import logging
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
    - with ``telemetry.TRACER`` configured (or an explicit ``_tracer``)
      a ring event as well, carrying ``parent`` — the enclosing span's
      name on this thread — so self time can be computed from the ring;
    - with ``ANATOMY.enabled`` and ``phase=``, the duration goes to
      ``ANATOMY.phase(phase, seconds)``.

    A span without a ``round`` attr inherits the enclosing span's, so
    the spans of one round share that identifier. The clock is read
    only when the ring or the anatomy plane wants the duration;
    ``seconds`` holds it afterwards (0.0 otherwise). The body's value
    and exceptions pass through untouched; a raising body still leaves
    its ring event, tagged ``error``."""

    __slots__ = ("name", "attrs", "phase", "seconds", "_ann", "_tracer",
                 "_anat", "_t0", "_ts", "_parent")

    def __init__(self, name: str, phase: str | None = None,
                 _tracer: "Tracer | None" = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.phase = phase
        self.seconds = 0.0
        self._tracer = _tracer

    def __enter__(self) -> "span":
        attrs = self.attrs
        stack = _open_spans()
        self._parent = None
        if stack:
            self._parent, rnd = stack[-1]
            if rnd is not None and "round" not in attrs:
                attrs["round"] = rnd
        self._ann = TraceAnnotation(self.name, **attrs)
        tr = self._tracer
        anat = None
        # telemetry imports this module and anatomy imports telemetry:
        # both are looked up once loaded, never imported from here
        if tr is None:
            tel = sys.modules.get("fedml_tpu.core.telemetry")
            if tel is not None:
                tr = self._tracer = tel.TRACER
        if self.phase is not None:
            mod = sys.modules.get("fedml_tpu.core.anatomy")
            if mod is not None and mod.ANATOMY.enabled:
                anat = mod.ANATOMY
        self._anat = anat
        stack.append((self.name, attrs.get("round")))
        if tr is not None or anat is not None:
            self._ts = time.time()
            self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        _open_spans().pop()
        tr, anat = self._tracer, self._anat
        if tr is not None or anat is not None:
            self.seconds = dt = time.perf_counter() - self._t0
            if tr is not None:
                ev = tr._base("span", self._ts, dt, {
                    "name": self.name, "parent": self._parent,
                    **self.attrs,
                })
                if exc is not None:
                    # the span record must survive a raising body: a
                    # failing round still leaves its timing (tagged
                    # with the error) instead of silently dropping it
                    ev["error"] = repr(exc)
                tr._emit(ev)
            if anat is not None:
                anat.phase(self.phase, dt)
        return False


#: round-record keys that are counts of the model's own
#: (``FedModel.counters``), carried on ``fedml.log`` as ``slot_steps`` is
COUNTER_PREFIXES = ("moe_rows_", "attn_keys_")


def log_span(record: dict) -> span:
    """The ``fedml.log`` span of one round record (every round loop's
    ``sink.log`` site). A counter the round program reported rides it
    as an attr, so a profiler capture holds it beside the device's
    timeline: ``slot_steps`` (the sharded cohort round) and the model's
    own counters (``FedModel.counters``: the decoder's ``moe_rows_*``
    and ``attn_keys_*``;
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


class Tracer:
    """Span collector with the reference's tick/tock vocabulary.

    ``events`` is a bounded ring (``max_events``, default 200k): a
    multi-thousand-round deployment with tracing left on keeps the most
    recent window instead of growing RSS without bound; ``dropped``
    counts evictions and is recorded in :meth:`dump`.
    """

    def __init__(self, rank: int | None = None,
                 max_events: int = 200_000):
        self.events: collections.deque[dict[str, Any]] = collections.deque(
            maxlen=max_events
        )
        self.dropped = 0
        self._open: dict[str, tuple[float, float]] = {}
        self.rank = rank
        self._lock = threading.Lock()

    def _emit(self, ev: dict[str, Any]) -> None:
        with self._lock:
            if len(self.events) == self.events.maxlen:
                self.dropped += 1
            self.events.append(ev)

    def _base(self, kind: str, ts: float, seconds: float,
              attrs: dict) -> dict[str, Any]:
        ev = {
            "kind": kind,
            "ts": ts,
            "seconds": seconds,
            "rank": self.rank,
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        ev.update(attrs)  # attrs may override rank (shared-process worlds)
        return ev

    # -- reference-shaped API (communication/utils.py:4-18) ----------------
    def log_communication_tick(self, sender, receiver, tag: str = ""):
        self._open[f"comm:{sender}->{receiver}:{tag}"] = (
            time.perf_counter(), time.time()
        )
        logging.debug("--Benchmark tick comm %s->%s %s", sender, receiver, tag)

    def log_communication_tock(self, sender, receiver, tag: str = ""):
        key = f"comm:{sender}->{receiver}:{tag}"
        t0 = self._open.pop(key, None)
        if t0 is not None:
            dt = time.perf_counter() - t0[0]
            self._emit(self._base(
                "comm", t0[1], dt,
                {"sender": sender, "receiver": receiver, "tag": tag},
            ))
            logging.debug("--Benchmark tock comm %s %fs", key, dt)

    def log_round_start(self, round_idx: int):
        self._open[f"round:{round_idx}"] = (
            time.perf_counter(), time.time()
        )

    def log_round_end(self, round_idx: int):
        t0 = self._open.pop(f"round:{round_idx}", None)
        if t0 is not None:
            self._emit(self._base(
                "round", t0[1], time.perf_counter() - t0[0],
                {"round": round_idx},
            ))

    # -- generic spans -----------------------------------------------------
    def span(self, name: str, **attrs):
        """:func:`span` with THIS tracer as the ring (whatever
        ``telemetry.TRACER`` holds)."""
        return span(name, _tracer=self, **attrs)

    def event(self, name: str, **attrs):
        """Instant event (zero duration) — message sends/delivers, fault
        injections, dead-peer marks."""
        self._emit(self._base(
            "event", time.time(), 0.0, {"name": name, **attrs}
        ))

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

    def dump(self, path: str):
        with self._lock:
            events = list(self.events)
            dropped = self.dropped
        # atomic replace: a crash mid-flush (or a concurrent
        # merge_trace.py read) must never observe a truncated dump —
        # this artifact exists precisely for crash debugging
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {"rank": self.rank, "dropped": dropped, "events": events},
                f, indent=2, default=repr,
            )
        os.replace(tmp, path)
