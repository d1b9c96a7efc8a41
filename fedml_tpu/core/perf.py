"""Performance observability: device-time breakdowns, MFU gauges, and
the analytic round-cost model behind them.

The runtime's own device-time evidence (docs/OBSERVABILITY.md
"Performance observability"):

- :func:`useful_round_cost` — the analytic USEFUL-FLOPs model of one
  FedAvg round, the ONE definition behind the runtime MFU gauge;
- :class:`RoundProfiler` — programmatic ``jax.profiler`` capture
  windows around the first K compiled rounds (``--profile_rounds K`` /
  ``FedConfig.profile_rounds``), each parsed into a per-round
  **device-time breakdown**: compute vs collective vs host-blocked vs
  idle. Captures land under ``<telemetry_dir>/jax_profile/round<k>/``
  (one window per round, so breakdowns are genuinely per-round; each
  capture holds the loop's ``fedml.*`` spans and the round program's
  ``fedml.*`` scopes, core/tracing.py), the parsed breakdowns — with
  the device time split by scope (:func:`phase_split`) — into
  ``perf_rank<r>.json``;
- :class:`PerfMonitor` — a live ``perf.mfu`` gauge computed from the
  same cost model over a smoothed round rate, plus the
  **dispatch-bound detector**: ``mfu < mfu_floor`` becomes a
  ``perf.dispatch_bound_rounds`` counter, a ``perf.latency_bound``
  gauge, and one flight-recorder event a run;
- trace parsing (:func:`load_trace_events`,
  :func:`device_time_breakdown`) over the ``*.trace.json.gz``
  Chrome-trace files ``jax.profiler`` writes — dependency-free (no
  tensorflow / xplane protobuf needed), and the breakdown computation
  is a pure function over normalized events so tests pin it on
  synthetic captures.

The deploy server actor wires its own ``perf.agg_wall_s`` /
``perf.host_wait_s`` accounting (the server-side time accounting the
Smart-NIC FL serving work optimizes against, arxiv 2307.06561) in
``algorithms/distributed_fedavg.py``; the sims wire this module through
the one round loop, ``core/fuse.run_loop``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from typing import Any

import numpy as np

from fedml_tpu.core import telemetry

# ---------------------------------------------------------------------------
# chip peaks + the analytic round-cost model
# ---------------------------------------------------------------------------

# (bf16 peak FLOP/s, HBM bandwidth B/s, HBM capacity bytes) per chip.
# v5e (TPU v5 lite): 197 bf16 TFLOP/s, ~819 GB/s, 16 GB HBM (Google
# Cloud documentation, "TPU v5e"). The point of MFU here is a stable,
# honest denominator, and the capacity column is the headroom
# denominator the memory monitor (core/memscope.py) alarms against.
PEAKS: dict[str, tuple[float, float, float]] = {
    "TPU v5 lite": (197e12, 819e9, 16e9),
    "TPU v4": (275e12, 1228e9, 32e9),
    "TPU v5p": (459e12, 2765e9, 95e9),
    "TPU v6 lite": (918e12, 1640e9, 32e9),
}


def device_peaks(device) -> tuple[float | None, float | None, float | None]:
    """The :data:`PEAKS` row of a jax device. A device of another
    platform (the CPU test mesh) has no row — it gets no MFU gauge and
    no table headroom rather than a made-up denominator. A TPU whose
    ``device_kind`` is missing from the table is an error, not a
    silent ``None``."""
    row = PEAKS.get(device.device_kind)
    if row is not None:
        return row
    if device.platform == "tpu":
        raise KeyError(
            f"TPU device_kind {device.device_kind!r} is not in "
            "fedml_tpu.core.perf.PEAKS: add its published peaks"
        )
    return (None, None, None)


_COST_CACHE: dict = {}


def useful_round_cost(sim) -> float | None:
    """Analytic FLOPs of the USEFUL work in one round: sampled clients
    x their real serial-equivalent optimizer steps x one fwd+bwd batch.
    The compiled round's own XLA cost analysis is not usable directly —
    the step loop has a data-dependent trip count (padding steps are
    skipped at runtime) and HLO cost analysis counts loop bodies once —
    so MFU is reported against the work the *semantics* require, making
    it an honest utilization number: padding waste and grouped-conv
    expansion lower it, exactly as they should. The runtime
    ``perf.mfu`` gauge (:class:`PerfMonitor`) reads this definition.
    Bytes moved are not modelled here."""
    import jax
    import jax.numpy as jnp

    model, B = sim.model, sim.batch_size
    compute_dtype = jnp.dtype(sim.cfg.train.compute_dtype)

    from fedml_tpu.algorithms.base import (
        _static_vars_to_dtype,
        _tree_to_dtype,
    )

    def step_loss(params, static_vars, x, y):
        # the SAME casting policy as the training loss_fn (params ->
        # compute dtype, batch_stats stay f32) and the SAME task loss
        # (classification CE / nwp token CE / tag BCE), imported so the
        # costed program cannot drift from the real one
        variables = {
            **_static_vars_to_dtype(static_vars, compute_dtype),
            "params": _tree_to_dtype(params, compute_dtype),
        }
        xc = (
            x.astype(compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x
        )
        logits, _ = model.apply_train(variables, xc, jax.random.key(0))
        sums = sim.task.metric_sums(
            logits.astype(jnp.float32), y, jnp.ones((B,), jnp.float32)
        )
        return sums["loss_sum"] / jnp.maximum(sums["w_sum"], 1.0)

    x_shape = (B,) + sim.arrays.x.shape[1:]
    y_shape = (B,) + sim.arrays.y.shape[1:]
    cost_key = (sim.cfg.model.name, x_shape, y_shape, str(compute_dtype))
    if cost_key in _COST_CACHE:
        step_flops = _COST_CACHE[cost_key]
    else:
        variables = model.init(jax.random.key(0))
        params = variables["params"]
        static_vars = {k: v for k, v in variables.items() if k != "params"}
        x = jnp.zeros(x_shape, sim.arrays.x.dtype)
        y = jnp.zeros(y_shape, sim.arrays.y.dtype)
        try:
            ca = (
                jax.jit(jax.grad(step_loss))
                .lower(params, static_vars, x, y)
                .compile()
                .cost_analysis()
            )
            step_flops = float(ca.get("flops") or 0) or None
        except Exception:
            # a CPU backend may have no cost model; on the chip a
            # missing MFU numerator is an error, not a None
            if jax.devices()[0].platform == "tpu":
                raise
            return None
        _COST_CACHE[cost_key] = step_flops
    counts = np.asarray(sim.arrays.counts)
    mean_steps = float(np.mean(np.ceil(counts / B)))
    k = sim.cfg.fed.clients_per_round * mean_steps * sim.cfg.train.epochs
    return step_flops * k if step_flops else None


# ---------------------------------------------------------------------------
# jax-profiler capture parsing (dependency-free Chrome-trace path)
# ---------------------------------------------------------------------------

#: HLO op-name prefixes that are cross-device collectives.
_COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
)
#: HLO op-name prefixes that are host/data movement the device waits on.
_TRANSFER_RE = re.compile(r"^(copy|infeed|outfeed|send|recv|host)")
#: Host-side events that mean "the host is blocked on device/transfer".
_HOST_BLOCK_RE = re.compile(
    r"(Await|BlockHostUntil|BlockUntilReady|SyncAllActivity|"
    r"TransferLiteral|ExecuteOnStream)"
)


def load_trace_events(profile_dir: str) -> list[dict[str, Any]]:
    """Load every ``*.trace.json.gz`` under a jax-profiler session dir
    (``<dir>/plugins/profile/<ts>/<host>.trace.json.gz``) into
    normalized event dicts ``{name, pid, tid, ts, dur, process, args}``
    (``ts``/``dur`` in microseconds, session-relative). Returns ``[]``
    when no capture exists — callers degrade to a host-only breakdown
    instead of crashing a run whose backend skipped the trace."""
    paths = sorted(
        glob.glob(
            os.path.join(profile_dir, "**", "*.trace.json.gz"),
            recursive=True,
        )
    )
    events: list[dict[str, Any]] = []
    for p in paths:
        try:
            with gzip.open(p, "rt") as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError, EOFError):
            continue
        raw = data.get("traceEvents", [])
        procs = {
            e["pid"]: e.get("args", {}).get("name", "")
            for e in raw
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        for e in raw:
            if e.get("ph") != "X":
                continue
            events.append({
                "name": e.get("name", ""),
                "pid": e.get("pid", 0),
                "tid": e.get("tid", 0),
                "ts": float(e.get("ts", 0.0)),
                "dur": float(e.get("dur", 0.0)),
                "process": procs.get(e.get("pid", 0), ""),
                "args": e.get("args", {}) or {},
            })
    return events


def _union_us(intervals: list[tuple[float, float]]) -> float:
    """Total covered microseconds of a set of (start, end) intervals —
    nested/overlapping events (a fusion inside a call, parallel
    threadpool lanes) must not double-count wall time."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def _subtract_us(
    intervals: list[tuple[float, float]],
    cover: list[tuple[float, float]],
) -> float:
    """Microseconds of ``intervals`` NOT covered by ``cover`` (both get
    union-merged first)."""
    both = _union_us(list(intervals) + list(cover))
    return max(0.0, both - _union_us(list(cover)))


def device_time_breakdown(
    events: list[dict[str, Any]], window_s: float | None = None
) -> dict[str, Any]:
    """Fold a capture window's events into the four-way device-time
    breakdown: **compute / collective / host-blocked / idle**.

    Classification:

    - *device op* events are those on a ``/device:*`` plane, or — on
      backends whose XLA thunks run on host threads (the CPU backend;
      what CI exercises) — any event carrying an ``hlo_op`` arg;
    - device ops whose HLO name is a collective prefix (all-reduce /
      all-gather / reduce-scatter / all-to-all / collective-permute /
      collective-broadcast) are **collective**; copy/infeed/outfeed/
      send/recv ops are charged to **host** (data movement the device
      stalls on); everything else is **compute**;
    - host-plane blocking events (buffer awaits, BlockHostUntilReady,
      literal transfers) that do NOT overlap device-busy time are added
      to **host** — the host was stalled while the device did nothing;
    - **idle** is the remainder of the window
      (``window - device_busy - host_blocked``).

    Every duration is the interval-UNION of ITS OWN category's events
    (parallel lanes and nested events never double-count wall time),
    so each ``*_frac`` reads "fraction of the window in which at least
    one op of this kind was running". Categories may OVERLAP in time —
    a collective running concurrently with compute counts fully in
    both, which is the honest view: comm/compute overlap is the
    async-dispatch win, not an accounting error — so the fractions sum
    to 1 only for serial captures. ``window_s`` should be the measured
    wall duration of the capture; when omitted the event span is
    used."""
    device_planes = {
        e["pid"] for e in events if e["process"].startswith("/device:")
    }
    if device_planes:
        dev = [e for e in events if e["pid"] in device_planes
               and e["dur"] > 0]
    else:
        dev = [e for e in events if "hlo_op" in e["args"]
               and e["dur"] > 0]

    def iv(evs):
        return [(e["ts"], e["ts"] + e["dur"]) for e in evs]

    def opname(e):
        return str(e["args"].get("hlo_op") or e["name"])

    coll = [e for e in dev if _COLLECTIVE_RE.match(opname(e))]
    xfer = [e for e in dev if _TRANSFER_RE.match(opname(e))]
    nc = {id(e) for e in coll} | {id(e) for e in xfer}
    comp = [e for e in dev if id(e) not in nc]
    busy_iv = iv(dev)
    busy_us = _union_us(list(busy_iv))
    coll_us = _union_us(iv(coll))
    xfer_us = _union_us(iv(xfer))
    # compute is the union of COMPUTE-classified events, not busy minus
    # the other categories' totals: a collective on a parallel lane
    # must not eat concurrent compute time (per-category unions may
    # overlap; see the docstring)
    compute_us = _union_us(iv(comp))
    host_block = [
        e for e in events
        if e["pid"] not in device_planes and e["dur"] > 0
        and "hlo_op" not in e["args"] and _HOST_BLOCK_RE.search(e["name"])
    ]
    host_block_us = _subtract_us(iv(host_block), busy_iv)

    if window_s is None:
        if events:
            lo = min(e["ts"] for e in events)
            hi = max(e["ts"] + e["dur"] for e in events)
            window_s = (hi - lo) / 1e6
        else:
            window_s = 0.0
    window_us = max(window_s * 1e6, busy_us + host_block_us)
    host_us = xfer_us + host_block_us
    idle_us = max(0.0, window_us - busy_us - host_block_us)

    def frac(us):
        return us / window_us if window_us > 0 else 0.0

    return {
        "window_s": window_us / 1e6,
        "device_busy_s": busy_us / 1e6,
        "compute_s": compute_us / 1e6,
        "collective_s": coll_us / 1e6,
        "host_s": host_us / 1e6,
        "idle_s": idle_us / 1e6,
        "compute_frac": frac(compute_us),
        "collective_frac": frac(coll_us),
        "host_frac": frac(host_us),
        "idle_frac": frac(idle_us),
        "n_device_ops": len(dev),
        "n_events": len(events),
        "device_planes": bool(device_planes),
    }


def phase_split(events: list[dict[str, Any]]) -> dict[str, float] | None:
    """Device-op seconds of a capture by ``fedml.*`` scope
    (docs/OBSERVABILITY.md "Spans and scopes"): every device op is
    joined by its HLO instruction name against
    :func:`fedml_tpu.core.memscope.scope_map` of the program it ran in
    (``hlo_module`` where the event names it, else whichever kept
    program knows the instruction). Ops of a mapped program under no
    scope read ``unscoped``; ops of programs with no map (the
    evaluator, jitted helpers) read ``other_programs``. Durations are
    summed (ops on one device line do not overlap; control-flow
    wrappers are left out). None when no program has a map."""
    from fedml_tpu.core import memscope

    maps: dict[str, dict] = {}
    for family, key, module in memscope.scope_programs():
        m = memscope.scope_map(family, key)
        if m:
            maps.setdefault(module, {}).update(m)
    if not maps:
        return None
    device_planes = {
        e["pid"] for e in events if e["process"].startswith("/device:")
    }
    out: dict[str, float] = {}
    for e in events:
        if e["dur"] <= 0 or not (
            e["pid"] in device_planes if device_planes
            else "hlo_op" in e["args"]
        ):
            continue
        op = str(e["args"].get("hlo_op") or e["name"]).lstrip("%")
        if op.split(".")[0] in ("while", "conditional", "call"):
            continue
        module = e["args"].get("hlo_module")
        if module is not None:
            smap = maps.get(module)
        else:
            smap = next((m for m in maps.values() if op in m), None)
        if smap is None:
            scope = "other_programs"
        else:
            scope = smap.get(op) or "unscoped"
        out[scope] = out.get(scope, 0.0) + e["dur"] / 1e6
    return out


# ---------------------------------------------------------------------------
# runtime layer: capture windows + live gauges
# ---------------------------------------------------------------------------


class RoundProfiler:
    """Programmatic ``jax.profiler`` windows around the first K rounds.

    Each profiled round gets its OWN capture session under
    ``<out_dir>/jax_profile/round<k>/`` — per-round windows make the
    breakdown genuinely per-round without segmenting one long capture,
    and keep the loop's ``fedml.*`` span annotations inside the
    matching round's file. A ``capture.json`` manifest (epoch start + wall
    window) rides next to each capture so ``scripts/merge_trace.py``
    can rebase the session-relative device timestamps onto the host
    span timeline. Parsed breakdowns feed ``perf.profile.*`` gauges and
    are written to ``<out_dir>/perf_<tag>.json`` by :meth:`finish`.

    Profiler failures (an unsupported backend, a second live session)
    disable further captures with a recorded warning — a perf run must
    degrade to wall-clock gauges, never crash the experiment.
    """

    def __init__(self, rounds: int, out_dir: str, tag: str | None = None,
                 flops_per_round: float | None = None,
                 fuse_rounds: int = 1):
        self.rounds = int(rounds)
        self.out_dir = out_dir
        self.tag = tag or telemetry.rank_tag()
        self.flops_per_round = flops_per_round
        # under round fusion (--fuse_rounds K) a capture window spans a
        # whole K-round BLOCK — recorded in the manifest and the
        # breakdown rows so a per-block breakdown is never silently
        # read as per-round (docs/PERFORMANCE.md "Round fusion")
        self.fuse_rounds = max(1, int(fuse_rounds or 1))
        self.capture_dir = os.path.join(out_dir, "jax_profile")
        self.breakdowns: list[dict] = []
        self._active: tuple[int, str, float, float] | None = None
        self._broken = False

    @property
    def wants_capture(self) -> bool:
        """True while another capture window can open (budget left, no
        window active, profiler healthy). The fused round loop checks
        this to drain its metric pipeline around profiled blocks, so a
        capture contains exactly one block's device work."""
        return (not self._broken and self._active is None
                and len(self.breakdowns) < self.rounds)

    def start_round(self, round_idx: int) -> None:
        if (self._broken or self._active is not None
                or len(self.breakdowns) >= self.rounds):
            return
        import jax

        d = os.path.join(self.capture_dir, f"round{round_idx}")
        try:
            os.makedirs(d, exist_ok=True)
            jax.profiler.start_trace(d)
        except Exception as err:
            self._broken = True
            telemetry.RECORDER.record("perf_profile_failed",
                                      error=repr(err))
            return
        self._active = (round_idx, d, time.perf_counter(), time.time())

    def end_round(self, round_idx: int, rounds: int = 1) -> None:
        """Close the window opened for ``round_idx`` (call AFTER the
        round's metrics were forced to host, so the capture contains
        the device execution, not just the dispatch). Under round
        fusion the window covers a whole block: pass ``rounds`` so the
        manifest and the breakdown row say how many rounds the window
        actually contains."""
        if self._active is None or self._active[0] != round_idx:
            return
        import jax

        _, d, t0, epoch0 = self._active
        self._active = None
        window_s = time.perf_counter() - t0
        try:
            jax.profiler.stop_trace()
        except Exception as err:
            self._broken = True
            telemetry.RECORDER.record("perf_profile_failed",
                                      error=repr(err))
            return
        manifest = {"round": round_idx, "t_start": epoch0,
                    "window_s": window_s,
                    "fuse_rounds": self.fuse_rounds,
                    "rounds_in_window": int(rounds)}
        try:
            with open(os.path.join(d, "capture.json"), "w") as f:
                json.dump(manifest, f)
        except OSError:
            pass
        events = load_trace_events(d)
        bd = device_time_breakdown(events, window_s=window_s)
        phases = phase_split(events)
        if phases is not None:
            bd["phases_s"] = phases
        bd["round"] = round_idx
        bd["rounds_in_window"] = int(rounds)
        self.breakdowns.append(bd)
        m = telemetry.METRICS
        m.inc("perf.profiled_rounds")
        for k in ("compute_frac", "collective_frac", "host_frac",
                  "idle_frac"):
            m.gauge(f"perf.profile.{k}", bd[k])
        m.gauge("perf.profile.window_s", bd["window_s"])
        telemetry.RECORDER.record(
            "perf_profile", round=round_idx,
            compute_frac=round(bd["compute_frac"], 4),
            collective_frac=round(bd["collective_frac"], 4),
            host_frac=round(bd["host_frac"], 4),
            idle_frac=round(bd["idle_frac"], 4),
        )

    def finish(self) -> str | None:
        """Write the per-round breakdown artifact; returns its path."""
        if self._active is not None:  # a raising round left it open
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = None
        if not self.breakdowns:
            return None
        path = os.path.join(self.out_dir, f"perf_{self.tag}.json")
        mean = {
            k: float(np.mean([b[k] for b in self.breakdowns]))
            for k in ("compute_frac", "collective_frac", "host_frac",
                      "idle_frac", "window_s")
        }
        # the phase split, where the round program's scope map exists
        # (memscope.scope_map): mean seconds a profiled window by scope
        split = [b["phases_s"] for b in self.breakdowns
                 if "phases_s" in b]
        if split:
            mean["phases_s"] = {
                k: float(np.mean([p.get(k, 0.0) for p in split]))
                for k in sorted({k for p in split for k in p})
            }
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({
                    "tag": self.tag,
                    "flops_per_round": self.flops_per_round,
                    "fuse_rounds": self.fuse_rounds,
                    "rounds": self.breakdowns,
                    "mean": mean,
                }, f, indent=2)
            os.replace(tmp, path)
        except OSError:
            return None
        return path


class PerfMonitor:
    """Live round-rate / MFU gauges + the dispatch-bound detector.

    ``note_round(wall_s)`` per completed round feeds:

    - ``perf.round_wall_s`` histogram (p50/p95/p99 ride the registry's
      percentile estimation — the round-latency SLO surface);
    - ``perf.rounds_per_s`` gauge (EWMA-smoothed);
    - ``perf.mfu`` / ``perf.delivered_flops_per_s`` gauges when the
      analytic round cost (:func:`useful_round_cost`) and the chip
      peak are known;
    - the detector: ``mfu < mfu_floor`` (default 0.005) increments
      ``perf.dispatch_bound_rounds``, sets ``perf.latency_bound`` and
      leaves ONE flight-recorder event per run — the round is bounded
      by dispatch/lowering latency, not the MXU.

    The first ``warmup_rounds`` rounds (default 1) are EXCLUDED from
    the histogram, the EWMA, and the detector — round 0's wall is
    dominated by the XLA compile, and folding it in would both
    skew the p99 the docs call the SLO surface and spuriously consume
    the per-run dispatch-bound event on a healthy run. The skipped
    wall is still visible as the ``perf.warmup_round_wall_s`` gauge.
    """

    def __init__(self, flops_per_round: float | None = None,
                 peak_flops: float | None = None, path: str = "sim",
                 mfu_floor: float = 0.005, smoothing: float = 0.5,
                 warmup_rounds: int = 1):
        self.flops_per_round = flops_per_round
        self.peak_flops = peak_flops
        self.path = path
        self.mfu_floor = mfu_floor
        self.smoothing = smoothing
        self.warmup_rounds = warmup_rounds
        self._avg_wall: float | None = None
        self._flagged = False
        self.rounds = 0

    @property
    def mfu(self) -> float | None:
        if (not self.flops_per_round or not self.peak_flops
                or not self._avg_wall):
            return None
        return self.flops_per_round / (self._avg_wall * self.peak_flops)

    def note_round(self, wall_s: float) -> None:
        self.note_block(wall_s, 1)

    def note_block(self, wall_s: float, rounds: int,
                   compiled: bool = False) -> None:
        """One completed fused block of ``rounds`` rounds: the wall
        DIVIDES by the round count before feeding the SLO histogram,
        the EWMA, and the MFU gauge, so the per-round surface stays
        honest under ``--fuse_rounds`` (a 4-round block at 2 s is
        0.5 s/round, never a 2 s p99 outlier) and the dispatch-bound
        detector keeps comparing per-round numbers. Excluded whole —
        wall gauged as ``perf.warmup_round_wall_s`` instead — are a
        block containing ANY warmup round AND any block flagged
        ``compiled`` (the fused drivers flag the first dispatch of
        each distinct block length: eval/checkpoint remainders trace a
        fresh scan program post-warmup, and that compile must not
        become the p99 or trip the dispatch-bound detector).
        ``note_round`` is the ``rounds=1`` case."""
        if wall_s <= 0 or rounds <= 0:
            return
        first = self.rounds
        self.rounds += rounds
        per = wall_s / rounds
        if compiled or first < self.warmup_rounds:
            telemetry.METRICS.gauge("perf.warmup_round_wall_s", per)
            return
        self._avg_wall = (
            per if self._avg_wall is None
            else (self.smoothing * per
                  + (1 - self.smoothing) * self._avg_wall)
        )
        m = telemetry.METRICS
        for _ in range(rounds):
            m.observe("perf.round_wall_s", per)
        m.gauge("perf.rounds_per_s", 1.0 / self._avg_wall)
        if self.flops_per_round:
            m.gauge("perf.delivered_flops_per_s",
                    self.flops_per_round / self._avg_wall)
        mfu = self.mfu
        if mfu is None:
            return
        m.gauge("perf.mfu", mfu)
        if mfu < self.mfu_floor:
            m.inc("perf.dispatch_bound_rounds", rounds)
            m.gauge("perf.latency_bound", 1.0)
            if not self._flagged:
                self._flagged = True
                telemetry.RECORDER.record(
                    "perf_dispatch_bound", path=self.path,
                    mfu=float(f"{mfu:.3g}"),
                    flops_per_round=self.flops_per_round,
                    note="round time is dispatch/lowering latency, not "
                         "flops — rounds/sec is the meaningful number",
                )
        else:
            m.gauge("perf.latency_bound", 0.0)


def build_sim_perf(sim) -> tuple[RoundProfiler | None,
                                 PerfMonitor | None]:
    """Perf wiring of the round loop (``core/fuse.run_loop``, which
    ``FedAvgSim.run`` and the experiment harness both call).
    Returns ``(None, None)`` unless ``cfg.fed.profile_rounds > 0`` —
    the off path costs one attribute read. The analytic round cost
    covers the FedAvg family; other sims still get wall-clock gauges
    and capture windows, just no MFU. On a TPU a cost model that fails
    is an error, not a missing gauge."""
    cfg = getattr(sim, "cfg", None)
    k = int(getattr(getattr(cfg, "fed", None), "profile_rounds", 0) or 0)
    if k <= 0:
        return None, None
    import jax

    telemetry.METRICS.enabled = True
    out_dir = telemetry.artifact_dir()
    if out_dir is None:
        out_dir = os.path.join(cfg.out_dir, cfg.run_name, "telemetry")
        os.makedirs(out_dir, exist_ok=True)
    from fedml_tpu.algorithms.fedavg import FedAvgSim

    flops = None
    if isinstance(sim, FedAvgSim):
        try:
            flops = useful_round_cost(sim)
        except Exception:
            if jax.devices()[0].platform == "tpu":
                raise
    # the sharded runtime spreads the round over its mesh: the honest
    # denominator is every chip it occupies, not one
    mesh = getattr(sim, "mesh", None)
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    peak = device_peaks(jax.devices()[0])[0]
    fuse = int(getattr(cfg.fed, "fuse_rounds", 1) or 1)
    profiler = RoundProfiler(k, out_dir, flops_per_round=flops,
                             fuse_rounds=fuse)
    monitor = PerfMonitor(
        flops_per_round=flops,
        peak_flops=peak * n_dev if peak else None,
        path=type(sim).__name__,
    )
    return profiler, monitor
