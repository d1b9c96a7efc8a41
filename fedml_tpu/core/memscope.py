"""Memory & compilation observability: per-program HBM accounting,
live device-memory monitoring, and the runtime donation audit.

The observability plane (core/perf.py, core/export.py, core/slo.py)
covers the TIME domain — device-time breakdowns, MFU, SLOs, live
OpenMetrics export — but until now the memory and compilation domain
was blind: the only memory signal in the tree was one ad-hoc
``bytes_in_use`` probe in ``core/mlops.py``, and the donation claims
the compressed/fused paths stake correctness and footprint on were
verified only in tests, never at runtime. This module is the memory
spine (docs/OBSERVABILITY.md "Memory & compilation"):

- **static per-program accounting** (:func:`note_program`): every
  compile site — :class:`~fedml_tpu.core.elastic.CompiledRoundCache`
  (the deploy server's bucket executables, the sharded aggregator),
  and the sims' round / fused-block programs via :class:`ProgramSite`
  — records ``compiled.memory_analysis()`` (temp, argument, output,
  alias, generated-code bytes) as ``mem.program.<slug>.*`` gauges
  keyed by a stable program slug ``<family>.<key parts>`` (family plus
  bucket / fuse length), and the compile wall time as a
  ``mem.compile_s.<family>`` histogram — an eviction-thrash world now
  shows SECONDS burning, not just a flat miss counter;
- **live device-memory monitoring** (:class:`DeviceMemoryMonitor`):
  ``device.memory_stats()`` sampled at round/block boundaries into
  per-device ``mem.bytes_in_use`` / ``mem.peak_bytes`` gauges with a
  run high-water mark and a used-fraction computed against the HBM
  capacity column of :data:`fedml_tpu.core.perf.PEAKS` (or the
  device's own ``bytes_limit`` when it reports one); ONE
  flight-recorder event fires at the first crossing of the headroom
  threshold (``--mem_headroom_warn``, default 0.9). Backends without
  ``memory_stats`` (the CPU backend CI runs) fall back to process RSS
  (``/proc/self/statm``), marked ``source: rss`` and measured against
  total system memory, so the same code path is exercised everywhere;
- **runtime donation audit** (:func:`audit_donation`): after the FIRST
  execution of each donating program, the donated input buffers are
  checked ``is_deleted()`` — a program whose donation silently failed
  (a 2x-footprint regression) counts ``mem.donation_misses`` and
  leaves one flight event naming the program. The test-only donation
  pins (tests/test_fuse.py) are now a standing production invariant.

Everything gates on ``telemetry.METRICS.enabled`` — the off path costs
one attribute check per sample and nothing per metric write. All
``mem.*`` gauges ride ``/metrics`` (core/export.py) unchanged, and
``/statusz`` gains a ``memory`` section (per-device live/peak/headroom,
the per-program table, donation-miss count) via a weak-registered
status source like every actor's.
"""

from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Callable

from fedml_tpu.core import telemetry
from fedml_tpu.core.tracing import span

#: per-program table cap: program slugs are bounded by design (elastic
#: buckets are powers of two, block lengths a small set), but a
#: misbehaving caller keying executables by something unbounded must
#: not grow every /statusz response and scrape forever — beyond the
#: cap new programs fold into one ``mem.program_overflow`` counter.
MAX_PROGRAMS = 64

#: memory_analysis() fields recorded per program (bytes each).
_ANALYSIS_FIELDS = (
    ("temp_bytes", "temp_size_in_bytes"),
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)

_LOCK = threading.Lock()
# slug -> program record (family, key, *_bytes, compile_s, donation)
_PROGRAMS: dict[str, dict[str, Any]] = {}
_STATUS_REGISTERED = False


def program_slug(family: str, key) -> str:
    """Stable dotted slug for one compiled program: the site family
    plus the cache key's parts (bucket, fuse length, ...)."""
    parts = key if isinstance(key, tuple) else (key,)
    return ".".join([str(family)] + [str(p) for p in parts])


class _MemoryStatus:
    """The ``/statusz`` ``memory`` section (one module-held instance —
    export keeps only a weakref)."""

    def status(self) -> dict[str, Any]:
        with _LOCK:
            programs = {k: dict(v) for k, v in _PROGRAMS.items()}
        m = telemetry.METRICS
        return {
            "source": MONITOR.last_source,
            "devices": MONITOR.last_readings,
            "high_water_bytes": MONITOR.high_water,
            "headroom_warn": MONITOR.headroom_warn,
            "donation_audits": m.counter("mem.donation_audits"),
            "donation_misses": m.counter("mem.donation_misses"),
            "programs": programs,
        }


_STATUS = _MemoryStatus()


def _register_status() -> None:
    """Idempotently (re-)register the statusz memory section. Called on
    every record/sample — ``export.reset_status_sources()`` (test
    isolation, telemetry shutdown) clears weak registrations behind our
    back, so a flag alone would go stale."""
    from fedml_tpu.core import export

    export.register_status_source("memory", _STATUS)


# ---------------------------------------------------------------------------
# static per-program accounting
# ---------------------------------------------------------------------------


def note_program(family: str, key, compiled,
                 compile_s: float | None = None) -> dict | None:
    """Record one freshly-compiled executable: its XLA memory analysis
    as ``mem.program.<slug>.*`` gauges and its compile wall time into
    the ``mem.compile_s.<family>`` histogram. Returns the program
    record (None while the metrics plane is off or when the backend
    cannot produce an analysis — accounting must never fail a
    compile)."""
    m = telemetry.METRICS
    if not m.enabled:
        return None
    slug = program_slug(family, key)
    rec: dict[str, Any] = {"family": family, "key": repr(key),
                           "ts": time.time()}
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, list):  # one analysis per partition
            ma = ma[0]
        for name, attr in _ANALYSIS_FIELDS:
            rec[name] = int(getattr(ma, attr, 0) or 0)
    except Exception:
        m.inc("mem.program_analysis_failures")
        for name, _ in _ANALYSIS_FIELDS:
            rec[name] = 0
        rec["analysis_failed"] = True
    if compile_s is not None:
        rec["compile_s"] = float(compile_s)
        m.observe(f"mem.compile_s.{family}", float(compile_s))
    m.inc("mem.compiles")
    with _LOCK:
        if slug not in _PROGRAMS and len(_PROGRAMS) >= MAX_PROGRAMS:
            overflow = True
        else:
            overflow = False
            _PROGRAMS[slug] = rec
    if overflow:
        m.inc("mem.program_overflow")
        return rec
    if not rec.get("analysis_failed"):
        for name, _ in _ANALYSIS_FIELDS:
            m.gauge(f"mem.program.{slug}.{name}", rec[name])
    _register_status()
    telemetry.RECORDER.record(
        "mem_program", program=slug,
        temp_mb=round(rec["temp_bytes"] / 1e6, 3),
        argument_mb=round(rec["argument_bytes"] / 1e6, 3),
        compile_s=round(compile_s, 3) if compile_s is not None else None,
    )
    return rec


def program_record(family: str, key) -> dict | None:
    """Read one recorded program's accounting (the smoke scripts'
    and the tests' assertions)."""
    with _LOCK:
        rec = _PROGRAMS.get(program_slug(family, key))
        return dict(rec) if rec is not None else None


def program_table() -> dict[str, dict]:
    with _LOCK:
        return {k: dict(v) for k, v in _PROGRAMS.items()}


# ---------------------------------------------------------------------------
# scope map: which fedml.* scope each instruction of a program ran under
# ---------------------------------------------------------------------------

# A profiler trace names a device op by its OPTIMIZED HLO instruction
# (``%add_select_fusion.9 = ...``) and, through ``jax.profiler.
# ProfileData``, carries no ``op_name`` — so the ``jax.named_scope``
# phases of the round program (``fedml.sample``, ``fedml.local.grad``,
# ...; docs/OBSERVABILITY.md "Spans and scopes") cannot be read off the
# event. The optimized module's text has the same instruction names
# WITH ``metadata={op_name=".../fedml.local.update/..."}``; every
# :class:`ProgramSite` compile keeps that text here (a str — nothing
# that holds the executable or its device memory), whatever the metrics
# plane says, and :func:`scope_map` parses it when first asked.
# slug -> {"family", "key", "module", "text" | "map", "keep_s"}
_HLO: dict[str, dict[str, Any]] = {}

_SCOPE_RE = re.compile(r"fedml\.[a-z_]+(?:\.[a-z_]+)*")
_INSTR_RE = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"\bfusion\(.*calls=%?([\w.\-]+)")


# The TPU compiler rewrites ``jax.lax.ragged_dot`` into Mosaic calls
# whose ``op_name`` ends in the rewrite's own name (``ragged-dot-none``,
# ``ragged-dot-metadata``) after a PREFIX of the name stack that stops
# short of the model's scopes (``.../fedml.local/closed_call/
# ragged-dot-none`` on the chip's round). The program has one caller
# of it, ``ops/moe.py:moe_layer``, under this scope. Since PR 48 only
# the grouped products that ``ops/moe.py:product_tiles`` leaves to
# ``ragged_dot`` need the rename (float32 operands: the evaluator's
# stack; shapes with few rows a group; a mapped call): the row-tiled
# kernels of ``ops/grouped.py`` (``moe_rows_product``,
# ``moe_matrices_product``) are Pallas calls of the program's own, keep
# their whole ``op_name`` and are found by the scope in it.
_RENAMED = {
    "ragged-dot-none": "fedml.model.moe.experts",
    "ragged-dot-metadata": "fedml.model.moe.experts",
}


def keep_program_hlo(family: str, key, compiled) -> float:
    """Keep one compiled program's optimized HLO text for
    :func:`scope_map`. Returns the seconds it took (also kept as
    ``keep_s`` and observed as ``mem.scope_text_s``); a backend that
    cannot produce the text keeps nothing — this must never fail a
    compile."""
    t0 = time.perf_counter()
    try:
        text = compiled.as_text()
    except Exception:
        return 0.0
    head = text[:256].split(None, 2)
    module = head[1].rstrip(",") if len(head) > 1 else ""
    keep_s = time.perf_counter() - t0
    slug = program_slug(family, key)
    with _LOCK:
        if slug in _HLO or len(_HLO) < MAX_PROGRAMS:
            _HLO[slug] = {"family": family, "key": key, "module": module,
                          "text": text, "keep_s": keep_s}
    telemetry.METRICS.observe("mem.scope_text_s", keep_s)
    return keep_s


def _scope_of(op_name: str) -> str | None:
    renamed = _RENAMED.get(op_name.rsplit("/", 1)[-1])
    if renamed is not None:
        return renamed
    found = _SCOPE_RE.findall(op_name)
    return found[-1] if found else None


def parse_scopes(text: str) -> dict[str, str | None]:
    """``{instruction: scope}`` of one optimized HLO module's text: the
    innermost ``fedml.*`` scope in the instruction's ``op_name`` (the
    last on the name stack — backward ops keep theirs inside
    ``transpose(jvp(...))``), else None. A fusion whose own metadata
    names no scope takes the scope most of its fused instructions
    share. An instruction may run over several lines (a Mosaic call's
    kernel metadata holds newlines): the ``op_name`` that follows it
    is its own. Instruction names are unique within a module, fused
    computations' included."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    inside: dict[str, list[str]] = {}
    comp = None
    unnamed = None  # the last instruction whose line held no op_name
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            op = _OPNAME_RE.search(line)
            if op is not None and unnamed is not None:
                own[unnamed] = _scope_of(op.group(1))
                unnamed = None
            elif not line.startswith(" "):
                m = _COMP_RE.match(line)
                comp = m.group(1) if m else None
            continue
        name = m.group(1)
        op = _OPNAME_RE.search(line)
        scope = _scope_of(op.group(1)) if op is not None else None
        unnamed = name if op is None else None
        own[name] = scope
        if scope is not None and comp is not None:
            inside.setdefault(comp, []).append(scope)
        elif scope is None and " fusion(" in line:
            c = _CALLS_RE.search(line)
            if c is not None:
                calls[name] = c.group(1)
    for name, called in calls.items():
        scopes = inside.get(called)
        if scopes:
            own[name] = max(set(scopes), key=scopes.count)
    return own


def scope_programs() -> list[tuple[str, Any, str]]:
    """``[(family, key, HLO module name)]`` of the programs whose scope
    map can be asked for (a trace's ``XLA Modules`` events are named
    ``<module>(<id>)``)."""
    with _LOCK:
        return [(r["family"], r["key"], r["module"])
                for r in _HLO.values()]


def scope_map(family: str, key) -> dict[str, str | None] | None:
    """``{instruction: scope or None}`` for one compiled program — what
    the benchmark's reader (``benchmarks/lib/program_spans.py``) and
    :meth:`fedml_tpu.core.perf.RoundProfiler.finish` join a trace's
    device ops against. Parsed on the first call (the text is then
    dropped for the map); None for a program this process never
    compiled through a :class:`ProgramSite`."""
    with _LOCK:
        rec = _HLO.get(program_slug(family, key))
        if rec is None:
            return None
        if "map" not in rec:
            rec["map"] = parse_scopes(rec.pop("text"))
        return rec["map"]


# ---------------------------------------------------------------------------
# runtime donation audit
# ---------------------------------------------------------------------------


def audit_donation(family: str, key, donated_leaves) -> bool:
    """Verify a donating program's donated input buffers were actually
    consumed (``is_deleted``) after its first execution. A live donated
    buffer means XLA could not alias it — the program is silently
    paying the 2x footprint its donation was supposed to eliminate.
    Counts ``mem.donation_audits`` / ``mem.donation_misses`` and leaves
    ONE flight event naming the program per miss. Returns True when the
    donation held (also True for an empty leaf list — nothing was
    donated, nothing can miss)."""
    m = telemetry.METRICS
    if not m.enabled:
        return True
    leaves = [lf for lf in donated_leaves if hasattr(lf, "is_deleted")]
    m.inc("mem.donation_audits")
    alive = 0
    for lf in leaves:
        try:
            if not lf.is_deleted():
                alive += 1
        except Exception:
            pass
    slug = program_slug(family, key)
    ok = alive == 0
    with _LOCK:
        rec = _PROGRAMS.get(slug)
        if rec is not None:
            rec["donation"] = "ok" if ok else "missed"
    if not ok:
        m.inc("mem.donation_misses")
        telemetry.RECORDER.record(
            "mem_donation_miss", program=slug, live_buffers=alive,
            note="donated inputs were not deleted — XLA did not alias "
                 "them; the program pays double its claimed footprint",
        )
    _register_status()
    return ok


# ---------------------------------------------------------------------------
# ProgramSite: the sims' jit sites, AOT-compiled + accounted
# ---------------------------------------------------------------------------


class ProgramSite:
    """An instrumented ``jax.jit`` call site: executables are compiled
    ahead-of-time (``.lower().compile()`` — the exact artifacts a
    first jit call would build, byte-identical lowering) and held per
    stable program key, so every compile is TIMED (``mem.compile_s``),
    memory-ACCOUNTED (``mem.program.*``), and — when the site donates —
    donation-AUDITED on its first execution.

    Call as ``site(key, *args)``; ``key`` is the program identity
    (bucket, or ``(bucket, block_length)``) — one executable per key,
    exactly the signature-stability contract the sims already hold (a
    given sim instance's shapes vary only on the key). Exposes
    ``_cache_size`` so :func:`fedml_tpu.core.elastic.mirror_jit_cache`
    keeps feeding the ``elastic.compile_cache_*`` counters unchanged.
    ``static_argnums``/``donate_argnums`` index into ``*args`` (the
    wrapped function's own positions, key excluded)."""

    def __init__(self, fn: Callable, family: str,
                 static_argnums=(), donate_argnums=()):
        import jax

        self.family = family
        self._static = tuple(static_argnums)
        self._donate = tuple(donate_argnums)
        self._jit = jax.jit(fn, static_argnums=self._static,
                            donate_argnums=self._donate)
        self._exes: dict[Any, Any] = {}
        self._audited: set = set()
        self._lock = threading.Lock()

    def _cache_size(self) -> int:
        with self._lock:
            return len(self._exes)

    def __call__(self, key, *args):
        import jax

        with self._lock:
            exe = self._exes.get(key)
        compiled = exe is None
        if compiled:
            # set-up spans (docs/OBSERVABILITY.md "Spans and scopes"):
            # tracing + lowering and the backend's compile (or its
            # cache read) apart; what is left of ``fedml.compile`` is
            # the accounting and the kept module text
            with span("fedml.compile", family=self.family, key=str(key)):
                t0 = time.perf_counter()
                with span("fedml.compile.lower"):
                    lowered = self._jit.lower(*args)
                with span("fedml.compile.backend"):
                    exe = lowered.compile()
                del lowered  # the module's text dies here, as before
                wall = time.perf_counter() - t0
                with self._lock:
                    self._exes[key] = exe
                note_program(self.family, key, exe, compile_s=wall)
                keep_program_hlo(self.family, key, exe)
        audit = bool(self._donate) and key not in self._audited
        donated = (
            [leaf
             for i in self._donate if i < len(args)
             for leaf in jax.tree.leaves(args[i])]
            if audit else None
        )
        if self._static:
            dynamic = tuple(a for i, a in enumerate(args)
                            if i not in self._static)
        else:
            dynamic = args
        if compiled:
            # program load and first enqueue; every later call pays the
            # truth test above and opens no span
            with span("fedml.first_call", family=self.family,
                      key=str(key)):
                out = exe(*dynamic)
        else:
            out = exe(*dynamic)
        if audit:
            self._audited.add(key)
            audit_donation(self.family, key, donated)
        return out


# ---------------------------------------------------------------------------
# live device-memory monitoring
# ---------------------------------------------------------------------------


def read_device_memory() -> tuple[str, list[dict]]:
    """Raw memory readings with NO registry interaction (shared by the
    monitor and the mlops ``SysStats`` sampler — one memory path, not
    two): ``("device", [...])`` from ``device.memory_stats()`` when the
    backend reports it, else ``("rss", [...])`` from
    ``/proc/self/statm`` against total system memory, else
    ``("none", [])``. Each reading carries ``bytes_in_use``,
    ``peak_bytes`` (None when the source has no allocator peak) and
    ``capacity_bytes`` (the device's ``bytes_limit``, the
    :data:`fedml_tpu.core.perf.PEAKS` HBM column, or total RAM)."""
    from fedml_tpu.core import perf

    readings: list[dict] = []
    try:
        import jax

        devices = jax.devices()
    except Exception:
        devices = []
    for i, d in enumerate(devices):
        fn = getattr(d, "memory_stats", None)
        stats = None
        if fn is not None:
            try:
                stats = fn()
            except Exception:
                stats = None
        if not stats or "bytes_in_use" not in stats:
            continue
        kind = getattr(d, "device_kind", "")
        cap = (
            stats.get("bytes_limit")
            or stats.get("bytes_reservable_limit")
            or perf.device_peaks(d)[2]
        )
        readings.append({
            "device": f"d{i}",
            "kind": kind,
            "bytes_in_use": int(stats["bytes_in_use"]),
            "peak_bytes": (
                int(stats["peak_bytes_in_use"])
                if "peak_bytes_in_use" in stats else None
            ),
            "capacity_bytes": int(cap) if cap else None,
        })
    if readings:
        return "device", readings
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        page = os.sysconf("SC_PAGE_SIZE")
        rss = rss_pages * page
        total = os.sysconf("SC_PHYS_PAGES") * page
    except (OSError, ValueError, IndexError):
        return "none", []
    return "rss", [{
        "device": "rss",
        "kind": "host_rss",
        "bytes_in_use": rss,
        "peak_bytes": None,
        "capacity_bytes": total,
    }]


class DeviceMemoryMonitor:
    """Round/block-boundary device-memory sampler.

    ``sample()`` reads every device's ``memory_stats()`` (or the RSS
    fallback) into per-device ``mem.bytes_in_use.<d>`` /
    ``mem.peak_bytes.<d>`` gauges plus the aggregates
    ``mem.bytes_in_use`` (sum), ``mem.peak_bytes`` (max),
    ``mem.high_water_bytes`` (run high-water mark of the sum),
    ``mem.used_frac`` / ``mem.headroom_frac`` (worst device against
    its HBM capacity) and ``mem.source_rss`` (1 on the fallback). The
    FIRST sample whose used fraction crosses ``headroom_warn`` leaves
    exactly one ``mem_headroom`` flight-recorder event for the run —
    an alert trigger, not a per-round log. The off path
    (``METRICS.enabled`` False) is one attribute check."""

    def __init__(self, headroom_warn: float = 0.9):
        self.headroom_warn = float(headroom_warn)
        self.high_water = 0
        self.last_source = "none"
        self.last_readings: list[dict] = []
        self._flagged = False
        self._peak_seen: dict[str, int] = {}

    def reset(self) -> None:
        self.high_water = 0
        self.last_source = "none"
        self.last_readings = []
        self._flagged = False
        self._peak_seen.clear()

    def sample(self, tag: str | None = None) -> dict | None:
        m = telemetry.METRICS
        if not m.enabled:
            return None
        source, readings = read_device_memory()
        if not readings:
            return None
        total = 0
        peak_max = 0
        worst_frac = 0.0
        resolved: list[dict] = []
        for r in readings:
            label = r["device"]
            used = r["bytes_in_use"]
            total += used
            # allocator peak when the source reports one; otherwise the
            # run-max of our own samples (the RSS path, marked as such)
            peak = r["peak_bytes"]
            if peak is None:
                peak = max(self._peak_seen.get(label, 0), used)
            self._peak_seen[label] = peak
            resolved.append(dict(r, peak_bytes=peak))
            peak_max = max(peak_max, peak)
            m.gauge_labeled("mem.bytes_in_use", label, used)
            m.gauge_labeled("mem.peak_bytes", label, peak)
            cap = r["capacity_bytes"]
            if cap:
                worst_frac = max(worst_frac, used / cap)
        self.high_water = max(self.high_water, total)
        m.gauge("mem.bytes_in_use", total)
        m.gauge("mem.peak_bytes", peak_max)
        m.gauge("mem.high_water_bytes", self.high_water)
        m.gauge("mem.source_rss", 1.0 if source == "rss" else 0.0)
        if worst_frac:
            m.gauge("mem.used_frac", worst_frac)
            m.gauge("mem.headroom_frac", max(0.0, 1.0 - worst_frac))
        summary = {
            "source": source,
            "bytes_in_use": total,
            "peak_bytes": peak_max,
            "high_water_bytes": self.high_water,
            "used_frac": worst_frac,
            "readings": resolved,
        }
        self.last_source = source
        self.last_readings = summary["readings"]
        if worst_frac >= self.headroom_warn and not self._flagged:
            self._flagged = True
            telemetry.RECORDER.record(
                "mem_headroom", source=source, tag=tag,
                used_frac=round(worst_frac, 4),
                threshold=self.headroom_warn,
                bytes_in_use=total,
                note="device memory crossed the headroom threshold — "
                     "the next bucket/cohort growth may OOM",
            )
            # the crossing is the second breach-profile trigger
            # (core/anatomy.py) — lazily, like telemetry.shutdown's
            # reset: this module must not pull anatomy in
            import sys as _sys

            _an = _sys.modules.get("fedml_tpu.core.anatomy")
            if _an is not None:
                try:
                    _an.notify_mem_headroom(
                        source=source, used_frac=round(worst_frac, 4),
                        threshold=self.headroom_warn,
                    )
                except Exception:
                    pass  # a profiler failure must not fail sampling
        _register_status()
        return summary


#: Process-global monitor — the round loops and the deploy actor
#: sample it; ``--mem_headroom_warn`` retunes its threshold.
MONITOR = DeviceMemoryMonitor()


def reset() -> None:
    """Return the module to its pristine state (test isolation; called
    by :func:`fedml_tpu.core.telemetry.shutdown`)."""
    with _LOCK:
        _PROGRAMS.clear()
        _HLO.clear()
    MONITOR.reset()
    MONITOR.headroom_warn = 0.9
