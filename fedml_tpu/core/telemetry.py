"""Runtime telemetry: metrics registry, trace context, flight recorder.

PR 1 made federated rounds survive drops, stragglers, and crashed
clients — but every one of those events was invisible: transports
counted nothing, the :class:`~fedml_tpu.core.tracing.Tracer` was wired
into nothing, and a quorum-lost abort left no artifact to debug from.
This module is the process-wide telemetry spine the rest of the runtime
hangs off (docs/OBSERVABILITY.md):

- :class:`MetricsRegistry` — thread-safe counters / gauges / histograms
  with a ``snapshot()``. One process-global instance (:data:`METRICS`)
  is instrumented into every transport (messages/bytes sent+received,
  retry attempts and exhaustions, reconnects, chaos faults), the manager
  (heartbeat RTT, dead-peer events, inbox depth) and the distributed
  round loop (wall time, stragglers, quorum renormalizations).
- trace context — ``(trace_id, span_id)`` pairs ride on
  :class:`~fedml_tpu.core.message.Message` envelopes; the thread-local
  *current trace* set at dispatch time makes a handler's outbound sends
  inherit the inbound message's trace id, so a send on rank 0 connects
  to its deliver (and the work it caused) on rank 1 across process
  boundaries. ``scripts/merge_trace.py`` folds the per-rank span dumps
  into one Perfetto-loadable Chrome trace (pid = rank).
- :class:`FlightRecorder` — a bounded ring of recent telemetry events,
  dumped to ``telemetry_dir`` on dead-peer detection, quorum-lost abort,
  and unhandled crash (sys/threading excepthooks), turning PR 1's loud
  failures into debuggable artifacts.

Disabled is the default and costs nothing per message: :data:`METRICS`
starts ``enabled=False`` (every ``inc``/``gauge``/``observe`` early-
returns), :data:`TRACER` is ``None`` (all message-level tracing sites
are guarded by an ``is not None`` check and allocate no ids; the round
loops' ``tracing.span`` boundaries fill the process ring regardless, a
few microseconds a span), and the recorder ring
accepts nothing. :func:`configure` — called by ``run.py`` under
``--telemetry_dir``/``--trace`` and by ``deploy.run_role`` — switches
the plane on for THIS process.

The reference leans on wandb logs and grep-able ``--Benchmark`` lines
(SURVEY.md §5.5); per-rank device/host timelines that line up are the
FedJAX-style stronger form (arxiv 2108.02117), and the transport byte
accounting is what Smart-NIC FL serving work optimizes against (arxiv
2307.06561).
"""

from __future__ import annotations

import atexit
import collections
import glob
import json
import os
import sys
import threading
import time
import uuid
from typing import Any

from fedml_tpu.core.tracing import RING, Tracer


def percentiles_from_histogram(
    h: dict[str, Any], qs: tuple[float, ...] = (0.5, 0.95, 0.99)
) -> dict[str, float]:
    """Estimate quantiles from a histogram's power-of-two buckets.

    The target rank is located in the cumulative bucket counts and
    linearly interpolated inside its bucket ``(2^(k-1), 2^k]``, with
    the interpolation range clamped to the observed ``[min, max]``.

    **Error bound**: the estimate is EXACT whenever the selected
    bucket's value range collapses — single-observation histograms and
    any histogram whose observations all share one value (min == max
    clamps the bucket to a point). Otherwise the error is bounded by
    the selected bucket's width: for power-of-two buckets that means
    the estimate is within a factor of 2 of the true quantile (and
    tighter near the min/max clamps). Good enough for SLO monitoring
    (p99 round latency alarming on 2x regressions), not for
    microsecond-accurate timing — use the trace dumps for that.
    """
    count = h.get("count", 0)
    buckets = h.get("buckets", {})
    if not count or not buckets:
        return {}
    items = sorted(
        (int(k.split("^", 1)[1]), c) for k, c in buckets.items()
    )
    hmin = h.get("min", float("-inf"))
    hmax = h.get("max", float("inf"))
    out: dict[str, float] = {}
    for q in qs:
        target = q * count
        cum = 0
        for k, c in items:
            prev, cum = cum, cum + c
            if cum >= target:
                lo = 0.0 if k <= -20 else 2.0 ** (k - 1)
                hi = 2.0 ** k
                lo = min(max(lo, hmin), hmax)
                hi = max(min(hi, hmax), hmin)
                frac = (target - prev) / c if c else 0.0
                out[f"p{round(q * 100):d}"] = lo + (hi - lo) * frac
                break
    return out


class MetricsRegistry:
    """Thread-safe counters / gauges / histograms.

    Names are flat dotted strings (vocabulary in docs/OBSERVABILITY.md).
    Histograms keep count/sum/min/max plus power-of-two bucket counts —
    enough for a round-latency distribution without per-sample storage;
    ``snapshot()`` adds bucket-interpolated ``p50``/``p95``/``p99``
    estimates per histogram (:func:`percentiles_from_histogram` states
    the error bound — exact for single-valued histograms, within the
    2x bucket width otherwise), which is how a long-lived server
    reports round-latency SLOs without per-sample storage. All writes
    no-op while ``enabled`` is False, so the disabled hot path is one
    attribute check.
    """

    #: default per-family label cardinality cap (see
    #: :meth:`gauge_labeled`): at the 10k-client scale the per-peer
    #: gauge families (`heartbeat_rtt_s.peer<r>`, `inbox_hwm.rank<r>`)
    #: would otherwise grow the registry — and every scrape and
    #: ``snapshot()`` — without bound.
    LABEL_CAP = 64

    def __init__(self, enabled: bool = True,
                 label_cap: int = LABEL_CAP):
        self.enabled = enabled
        self.label_cap = label_cap
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, dict[str, Any]] = {}
        # family -> labels already minted (gauge_labeled's cap ledger)
        self._label_families: dict[str, set[str]] = {}

    def inc(self, name: str, value: float = 1) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_labeled(self, family: str, label: str, value: float,
                      sep: str = ".") -> None:
        """Per-peer/per-rank gauge families with a cardinality cap
        (docs/OBSERVABILITY.md "Live export and SLOs"): the first
        ``label_cap`` distinct labels of a family mint real gauges
        (``<family><sep><label>``, e.g.
        ``manager.heartbeat_rtt_s.peer3``); every label beyond the cap
        folds into ONE ``<family>.other`` overflow gauge and counts
        ``telemetry.label_overflow`` — so a 10k-peer world keeps its
        registry (and every scrape) bounded while the overflow stays
        visible instead of silently dropped."""
        if not self.enabled:
            return
        with self._lock:
            labels = self._label_families.get(family)
            if labels is None:
                labels = self._label_families[family] = set()
            if label in labels or len(labels) < self.label_cap:
                labels.add(label)
                self._gauges[f"{family}{sep}{label}"] = float(value)
            else:
                self._gauges[f"{family}.other"] = float(value)
                self._counters["telemetry.label_overflow"] = (
                    self._counters.get("telemetry.label_overflow", 0) + 1
                )

    def labeled_name(self, family: str, label: str,
                     sep: str = ".") -> str:
        """Resolve (and register) a labeled gauge's FINAL name once —
        for per-message hot paths that cache the returned string and
        then write with plain :meth:`gauge`, keeping the deliver edge
        allocation-free while the family still honors the cardinality
        cap. An over-cap label resolves to the ``<family>.other``
        overflow slot (counted once, at resolution)."""
        with self._lock:
            labels = self._label_families.get(family)
            if labels is None:
                labels = self._label_families[family] = set()
            if label in labels or len(labels) < self.label_cap:
                labels.add(label)
                return f"{family}{sep}{label}"
            self._counters["telemetry.label_overflow"] = (
                self._counters.get("telemetry.label_overflow", 0) + 1
            )
            return f"{family}.other"

    def observe(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = {
                    "count": 0, "sum": 0.0,
                    "min": float("inf"), "max": float("-inf"),
                    "buckets": {},
                }
            h["count"] += 1
            h["sum"] += value
            h["min"] = min(h["min"], value)
            h["max"] = max(h["max"], value)
            # power-of-two bucket upper bounds: le_2^k for the smallest
            # k with value <= 2^k (k in [-20, 20], clamped)
            k = -20
            while k < 20 and value > 2.0 ** k:
                k += 1
            key = f"le_2^{k}"
            h["buckets"][key] = h["buckets"].get(key, 0) + 1

    def merge_histogram(self, name: str, h: dict[str, Any]) -> None:
        """Fold a REMOTE histogram delta (count/sum/min/max + bucket
        deltas in the registry's own ``le_2^k`` keying) into a local
        histogram — the fleet-federation fold (core/export.py): a
        client's heartbeat forwards its bucket deltas, and the server's
        ``fleet.*`` percentiles are computed over the cohort's real
        distribution, not a summary of summaries."""
        if not self.enabled:
            return
        count = int(h.get("count", 0))
        buckets = h.get("buckets", {})
        if count <= 0 and not buckets:
            return
        with self._lock:
            dst = self._hists.get(name)
            if dst is None:
                dst = self._hists[name] = {
                    "count": 0, "sum": 0.0,
                    "min": float("inf"), "max": float("-inf"),
                    "buckets": {},
                }
            dst["count"] += count
            dst["sum"] += float(h.get("sum", 0.0))
            mn, mx = h.get("min"), h.get("max")
            if mn is not None:
                dst["min"] = min(dst["min"], float(mn))
            if mx is not None:
                dst["max"] = max(dst["max"], float(mx))
            for k, v in buckets.items():
                dst["buckets"][k] = dst["buckets"].get(k, 0) + int(v)

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def read_selected(
        self, counters=(), gauges=(), hists=()
    ) -> dict[str, Any]:
        """Targeted, constant-size read of named families — the
        heartbeat fleet-summary path uses this instead of
        :meth:`snapshot`, which deep-copies the WHOLE registry and
        interpolates percentiles for every histogram under the lock on
        every beat. Histogram entries carry the raw shape only (no
        percentiles — the summary ships bucket deltas, not
        estimates)."""
        with self._lock:
            return {
                "counters": {
                    k: self._counters[k] for k in counters
                    if k in self._counters
                },
                "gauges": {
                    k: self._gauges[k] for k in gauges
                    if k in self._gauges
                },
                "histograms": {
                    k: {
                        **self._hists[k],
                        "buckets": dict(self._hists[k]["buckets"]),
                    }
                    for k in hists if k in self._hists
                },
            }

    def snapshot(self) -> dict[str, Any]:
        """Deep-ish copy safe to mutate / serialize. Histogram entries
        carry estimated ``p50``/``p95``/``p99`` alongside the raw
        buckets (see :func:`percentiles_from_histogram` for the
        estimation error bound)."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {
                    k: {
                        **v,
                        "buckets": dict(v["buckets"]),
                        **percentiles_from_histogram(v),
                    }
                    for k, v in self._hists.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._label_families.clear()


class FlightRecorder:
    """Bounded ring of recent telemetry events + crash-artifact writer.

    ``record`` is cheap (deque append under a lock) and a no-op while
    disabled. ``dump`` writes the ring, the metrics snapshot, and the
    trigger reason to ``<dir>/flight_rank<r>_<n>_<reason>.json`` —
    monotonic ``n`` so multiple triggers in one process never clobber
    each other.
    """

    def __init__(self, capacity: int = 1024, enabled: bool = False):
        self.enabled = enabled
        self.dir: str | None = None
        self.rank = 0
        # artifact-name stem: "rank<r>" plus the incarnation suffix a
        # supervised restart gets (so a restarted rank's dumps never
        # clobber its predecessor's — docs/FAULT_TOLERANCE.md
        # "Recovery")
        self.tag = "rank0"
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dumps = 0

    def record(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        ev = {"kind": kind, "ts": time.time(), **fields}
        with self._lock:
            self._ring.append(ev)

    def dump(self, reason: str, **fields) -> str | None:
        """Write the flight artifact; returns its path (None if no
        telemetry dir is configured)."""
        self.record(reason, **fields)
        if self.dir is None:
            return None
        with self._lock:
            self._dumps += 1
            n = self._dumps
            events = list(self._ring)
        path = os.path.join(
            self.dir, f"flight_{self.tag}_{n}_{reason}.json"
        )
        data = {
            "reason": reason,
            "rank": self.rank,
            "ts": time.time(),
            **fields,
            "events": events,
            "metrics": METRICS.snapshot(),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, default=repr)
        os.replace(tmp, path)
        return path


#: Process-global registry — disabled until :func:`configure`.
METRICS = MetricsRegistry(enabled=False)
#: Process-global flight recorder — disabled until :func:`configure`.
RECORDER = FlightRecorder()
#: ``None`` until :func:`configure(trace=True)`, then the process ring
#: (:data:`fedml_tpu.core.tracing.RING`, which ``tracing.span`` fills
#: regardless). Every message-level tracing site guards on ``TRACER is
#: not None`` so the disabled path allocates nothing per message.
TRACER: Tracer | None = None

_DIR: str | None = None
_RANK = 0
# periodic metrics time-series flush (docs/OBSERVABILITY.md
# "Performance observability"): a daemon thread appending snapshot rows
# to metrics_rank<r>.jsonl so a long-lived server reports round-latency
# SLOs over time instead of only an at-exit snapshot
_TS_STOP: threading.Event | None = None
_TS_THREAD: threading.Thread | None = None
# whether the periodic thread APPENDS jsonl rows (an operator asked for
# --metrics_interval) or only ticks the SLO engine (the cadence was
# derived from --slo windows — a long-lived server must not get tens of
# MB of time series it never asked for as a side effect of an SLO)
_TS_ROWS = True
# serializes time-series appends: the periodic flusher and the at-exit
# final row must never interleave a partial JSONL line (the shutdown
# path additionally JOINS the flusher before appending the final row,
# so the file always ends on the end-state snapshot)
_TS_LOCK = threading.Lock()
# the live observability plane (core/export.py, core/slo.py): the
# OpenMetrics HTTP exporter and the SLO engine — both None until
# configure(metrics_port=...) / configure(slos=...), so the default
# path opens no socket and evaluates nothing
_EXPORTER = None
_SLO = None
# incarnation suffix ("" for a rank's first process; "_i<n>" for a
# supervised restart, chosen in configure() so a restarted rank never
# overwrites the artifacts its predecessor flushed —
# scripts/merge_trace.py folds all incarnations of a rank into one pid)
_SUFFIX = ""
_tls = threading.local()
_hooks_installed = False


# -- trace context -----------------------------------------------------------


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def set_current_trace(trace_id: str | None) -> None:
    """Bind the thread's current trace id (set at message dispatch so a
    handler's outbound sends inherit the inbound trace)."""
    _tls.trace = trace_id


def current_trace() -> str | None:
    return getattr(_tls, "trace", None)


def flight_dump(reason: str, **fields) -> str | None:
    """Record + dump a flight artifact (no-op without a telemetry dir).
    The triggers — dead peers, quorum-lost aborts, crashes — call this;
    see docs/OBSERVABILITY.md."""
    return RECORDER.dump(reason, **fields)


# -- lifecycle ---------------------------------------------------------------


def default_dir(out_dir: str, run_name: str) -> str:
    """Where artifacts land when tracing is requested without an
    explicit ``--telemetry_dir`` (one derivation shared by the sim and
    role CLI paths so they can never drift)."""
    return os.path.join(out_dir, run_name, "telemetry")


def artifact_dir() -> str | None:
    """The configured telemetry directory (None while disabled) — where
    satellite layers (the perf profiler's capture windows and
    breakdown artifact, core/perf.py) put their files so everything
    about one run lands in one place."""
    return _DIR


def rank_tag() -> str:
    """This process's artifact-name stem (``rank<r>`` plus the
    incarnation suffix a supervised restart gets)."""
    return f"rank{_RANK}{_SUFFIX}"


def slo_engine():
    """The process SLO engine (None unless ``configure(slos=...)``) —
    read by the ``/statusz`` assembler (core/export.py)."""
    return _SLO


def exporter():
    """The process OpenMetrics exporter (None while disabled)."""
    return _EXPORTER


def configure(
    telemetry_dir: str | None = None,
    rank: int = 0,
    trace: bool = True,
    flight_capacity: int = 1024,
    metrics_interval: float | None = None,
    metrics_port: int | None = None,
    metrics_host: str = "0.0.0.0",
    slos=(),
    slo_scope: str = "",
) -> None:
    """Enable telemetry for THIS process (idempotent).

    - metrics counting switches on unconditionally;
    - ``trace=True`` sets :data:`TRACER` to the process ring
      (:data:`fedml_tpu.core.tracing.RING` — the spans of
      :func:`fedml_tpu.core.tracing.span` are in it whether or not
      this is called) with this rank: the message-level sites, which
      guard on it, start recording into the same ring;
    - a ``telemetry_dir`` additionally arms the flight recorder, the
      crash hooks (sys/threading excepthook -> flight dump), and the
      exit flush that writes ``trace_rank<r>.json`` +
      ``metrics_rank<r>.json``;
    - ``metrics_interval`` (seconds, with a dir) starts the periodic
      time-series flush: append-only ``metrics_rank<r>.jsonl`` rows
      (:func:`start_metrics_timeseries`);
    - ``metrics_port`` starts the OpenMetrics HTTP exporter
      (core/export.py: ``/metrics`` + ``/statusz`` + ``/healthz`` on
      one listener; 0 binds an ephemeral port, read back from
      ``telemetry.exporter().port`` / the ``telemetry.metrics_port``
      gauge / ``export_rank<r>.json``). None (the default) opens no
      socket and adds no work anywhere;
    - ``slos`` (``--slo`` strings, core/slo.py) arms the SLO engine;
      its windowed evaluation rides the time-series cadence (a default
      tick interval is derived from the tightest window when
      ``metrics_interval`` is not set), exports ``slo.*`` burn gauges,
      and writes ``slo_rank<r>.json`` verdicts at shutdown.
      ``slo_scope`` names the job the verdicts belong to (defaults to
      ``rank<r>``).
    """
    global TRACER, _DIR, _RANK, _SUFFIX, _EXPORTER, _SLO
    _RANK = rank
    METRICS.enabled = True
    RECORDER.rank = rank
    if trace:
        TRACER = RING
        TRACER.rank = rank
    if telemetry_dir:
        os.makedirs(telemetry_dir, exist_ok=True)
        _DIR = telemetry_dir
        # a previous incarnation of this rank (supervised restart into
        # the same dir) already left artifacts here: pick the first
        # free "_i<n>" suffix instead of clobbering them. Flight dumps
        # count as evidence too — a chaos os._exit rank dies without
        # ever flushing trace/metrics, and its crash artifacts are
        # exactly what must not be overwritten.
        _SUFFIX = ""
        n = 0
        while any(
            os.path.exists(
                os.path.join(telemetry_dir,
                             f"{kind}_rank{rank}{_SUFFIX}.json")
            )
            for kind in ("trace", "metrics")
        ) or glob.glob(
            os.path.join(telemetry_dir,
                         f"flight_rank{rank}{_SUFFIX}_*.json")
        ):
            n += 1
            _SUFFIX = f"_i{n}"
        RECORDER.dir = telemetry_dir
        RECORDER.tag = f"rank{rank}{_SUFFIX}"
        RECORDER.enabled = True
        RECORDER._ring = collections.deque(
            RECORDER._ring, maxlen=flight_capacity
        )
        _install_hooks()
    if slos and _SLO is None:
        from fedml_tpu.core import slo as _slo_mod

        specs = _slo_mod.parse_specs(
            slos, scope=slo_scope or f"rank{rank}"
        )
        if specs:
            _SLO = _slo_mod.SloEngine(specs, METRICS, recorder=RECORDER)
    if metrics_port is not None and _EXPORTER is None:
        from fedml_tpu.core import export as _export

        _EXPORTER = _export.MetricsExporter(metrics_port,
                                            host=metrics_host)
        METRICS.gauge("telemetry.metrics_port", _EXPORTER.port)
        if _DIR is not None:
            # port discovery for ephemeral binds (--metrics_port 0):
            # scrapers read the bound port from the artifact dir
            try:
                with open(os.path.join(
                        _DIR, f"export_rank{_RANK}{_SUFFIX}.json"),
                        "w") as f:
                    json.dump(
                        {"port": _EXPORTER.port, "rank": rank}, f
                    )
            except OSError:
                pass
    if metrics_interval:
        start_metrics_timeseries(metrics_interval)
    elif _SLO is not None and _TS_THREAD is None:
        # the SLO engine rides the time-series cadence; without an
        # explicit interval, derive one from the tightest window so
        # every window sees several evaluations — but tick-only
        # (rows=False): an SLO must not start a jsonl time series the
        # operator never asked for
        w = min(s.window_s for s in _SLO.specs)
        start_metrics_timeseries(max(0.1, min(1.0, w / 5.0)),
                                 rows=False)


def _timeseries_path() -> str | None:
    if _DIR is None:
        return None
    return os.path.join(_DIR, f"metrics_rank{_RANK}{_SUFFIX}.jsonl")


def _append_timeseries_row() -> None:
    """One snapshot row (histograms compacted: percentiles kept, raw
    buckets dropped — the at-exit ``metrics_rank<r>.json`` carries the
    full shape)."""
    path = _timeseries_path()
    if path is None:
        return
    snap = METRICS.snapshot()
    row = {
        "ts": time.time(),
        "rank": _RANK,
        "counters": snap["counters"],
        "gauges": snap["gauges"],
        "histograms": {
            k: {kk: vv for kk, vv in v.items() if kk != "buckets"}
            for k, v in snap["histograms"].items()
        },
    }
    try:
        # one serialized append per row: the periodic flusher and the
        # at-exit final row must never interleave partial lines
        with _TS_LOCK, open(path, "a") as f:
            f.write(json.dumps(row, default=repr) + "\n")
    except OSError:
        pass


def _ts_tick() -> None:
    """One time-series beat: evaluate the SLO engine (it rides this
    cadence by design), then — when the operator asked for a time
    series — append the snapshot row, so every row already carries the
    fresh ``slo.*`` burn gauges."""
    slo = _SLO
    if slo is not None:
        try:
            slo.tick()
        except Exception:
            pass  # a broken spec must not kill the flusher
    if _TS_ROWS:
        _append_timeseries_row()


def start_metrics_timeseries(interval_s: float,
                             rows: bool = True) -> None:
    """Start the periodic metrics flush for this process (idempotent;
    needs a configured telemetry dir). Every ``interval_s`` seconds a
    snapshot row — counters, gauges, histograms with their
    p50/p95/p99 — is APPENDED to ``metrics_rank<r>.jsonl``, so a
    long-lived deployment's round-latency SLO is a time series, not
    only the at-exit state (the ``.json`` snapshot stays the
    latest-state artifact). The thread is a daemon and dies with the
    process; :func:`shutdown` stops it and writes one final row. With
    an SLO engine configured but no telemetry dir, the thread still
    runs (the engine's windowed ticks ride this cadence) — the row
    append itself stays dir-gated. ``rows=False`` runs the cadence for
    the SLO engine ONLY, appending nothing: the derived-from-``--slo``
    tick must not flood a long-lived server's disk with a time series
    the operator never asked for."""
    global _TS_STOP, _TS_THREAD, _TS_ROWS
    if (_DIR is None and _SLO is None) or interval_s <= 0 \
            or _TS_THREAD is not None:
        return
    _TS_ROWS = rows
    stop = threading.Event()

    def loop():
        while not stop.wait(interval_s):
            _ts_tick()

    t = threading.Thread(target=loop, daemon=True,
                         name="metrics-timeseries")
    _TS_STOP, _TS_THREAD = stop, t
    t.start()


def flush_metrics() -> None:
    """Durably snapshot JUST the metrics registry (cheap, bounded —
    unlike the trace dump, which grows with the run). The server actor
    calls this at every round checkpoint so counters survive a SIGKILL
    instead of dying with the exit-time flush (docs/FAULT_TOLERANCE.md
    "Recovery")."""
    if _DIR is None:
        return
    path = os.path.join(_DIR, f"metrics_rank{_RANK}{_SUFFIX}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(METRICS.snapshot(), f, indent=2, default=repr)
    os.replace(tmp, path)


def _stop_timeseries(write_final: bool) -> None:
    """Stop + JOIN the periodic flusher, then (optionally) append ONE
    final row. The join-before-append ordering is the fix for the
    shutdown race: a fast exit used to let the daemon's in-flight row
    interleave with the final one; now the final row is always the
    file's last line, written after the flusher is provably gone.
    Idempotent — a second flush appends nothing."""
    global _TS_STOP, _TS_THREAD
    stop, thread = _TS_STOP, _TS_THREAD
    _TS_STOP = _TS_THREAD = None
    if stop is not None:
        stop.set()
    if thread is not None:
        thread.join(timeout=2.0)
        if write_final:
            _ts_tick()


def flush() -> None:
    """Write the per-rank trace dump and metrics snapshot now (also runs
    at interpreter exit once a telemetry dir is configured). With the
    time-series flush armed, the flusher is joined first and exactly one
    final row is appended — the tail of the series always reflects the
    end state, and a fast exit cannot interleave a partial row with it.
    SLO verdicts (``slo_rank<r>.json``) are written here too."""
    if _DIR is None:
        return
    if TRACER is not None and TRACER.events:
        TRACER.dump(
            os.path.join(_DIR, f"trace_rank{_RANK}{_SUFFIX}.json")
        )
    _stop_timeseries(write_final=True)
    if _SLO is not None:
        try:
            _SLO.write_verdicts(
                os.path.join(_DIR, f"slo_rank{_RANK}{_SUFFIX}.json"),
                rank=_RANK,
            )
        except Exception:
            pass  # the verdict artifact must never block the flush
    flush_metrics()


def shutdown() -> None:
    """Flush, then return to the all-disabled state (test isolation)."""
    global TRACER, _DIR, _SUFFIX, _EXPORTER, _SLO, _TS_ROWS
    _stop_timeseries(write_final=_DIR is not None)
    flush()
    _TS_ROWS = True
    if _EXPORTER is not None:
        _EXPORTER.stop()
        _EXPORTER = None
    _SLO = None
    try:
        from fedml_tpu.core import export as _export

        _export.reset_status_sources()
    except Exception:
        pass
    try:
        import sys as _sys

        # memory-plane state (program table, headroom flag, high-water
        # mark) resets with the rest of the plane — but only if the
        # module was ever imported; shutdown must not pull it in
        _mem = _sys.modules.get("fedml_tpu.core.memscope")
        if _mem is not None:
            _mem.reset()
    except Exception:
        pass
    try:
        import sys as _sys

        # anatomy-plane state (phase ring, breach profiler) resets the
        # same lazy way — an open capture window is closed here so a
        # dangling jax.profiler session cannot break the next run
        _an = _sys.modules.get("fedml_tpu.core.anatomy")
        if _an is not None:
            _an.reset()
    except Exception:
        pass
    METRICS.enabled = False
    METRICS.reset()
    RECORDER.enabled = False
    RECORDER.dir = None
    RECORDER.tag = "rank0"
    RECORDER._ring.clear()
    RECORDER._dumps = 0
    TRACER = None
    RING.clear()
    RING.rank = None
    _DIR = None
    _SUFFIX = ""
    set_current_trace(None)


def _install_hooks() -> None:
    """Crash hooks + exit flush, installed once per process. They read
    the module globals at fire time, so a later :func:`shutdown` renders
    them inert."""
    global _hooks_installed
    if _hooks_installed:
        return
    _hooks_installed = True

    prev_exc = sys.excepthook

    def on_crash(exc_type, exc, tb):
        if RECORDER.enabled:
            flight_dump("crash", error=repr(exc),
                        error_type=exc_type.__name__)
            flush()
        prev_exc(exc_type, exc, tb)

    sys.excepthook = on_crash

    prev_thread_exc = threading.excepthook

    def on_thread_crash(args):
        if RECORDER.enabled and args.exc_type is not SystemExit:
            flight_dump(
                "crash",
                error=repr(args.exc_value),
                error_type=args.exc_type.__name__,
                thread=getattr(args.thread, "name", None),
            )
        prev_thread_exc(args)

    threading.excepthook = on_thread_crash
    atexit.register(flush)
