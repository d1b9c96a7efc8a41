"""The program's host ring, read back: the ``fedml.*`` spans that
``fedml_tpu.core.tracing.span`` leaves in the process ring
(``tracing.RING``) in EVERY round and through set-up, each with ``t0``
on ``time.perf_counter()`` — the clock ``run.py`` stamps ``T_PROCESS``,
``t_start`` and every round with — reduced to three things no profiler
session is needed for:

1. set-up by phase: ``fedml.build`` (a simulator's constructor),
   ``fedml.compile`` > ``.lower`` / ``.backend`` and ``fedml.first_call``
   (every ``ProgramSite`` compile), the warm-up's ``fedml.eval``, and
   what of ``setup_s`` lies in no span;
2. the round loop's spans over the rounds the profiler did NOT touch
   (``fedml.round`` / ``.dispatch`` / ``.fetch`` / ``.eval`` and the gap
   between two rounds), beside the traced part's;
3. the offset between the host's clock and the capture's, from the
   traced part's ``fedml.dispatch`` spans, which are in both places.

    python3 benchmarks/lib/host_ring.py <ring dump>

prints all three from the dump a traced run leaves beside its trace
(``ring.json``, written by ``Tracer.dump`` with ``t_start``, ``setup_s``
and ``traced_rounds`` as further keys), the third where the capture's
``.xplane.pb`` lies in the same directory.

Thirteen per-layer metrics are read here (``metric``): nine from the
ring, four (``idle_<span>_ms``) from the capture — chip 0's idle time
inside each of the loop's four child spans, cut at the spans' edges
(``program_spans`` prints it by whole gaps). A program without the ring
(the parent of the PR that made it unconditional) gives all thirteen
nothing to read; a ring that has dropped records a reader needs gives
``None``, never a number from a partial ring.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys

if __name__ == "__main__":
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import program_spans, xplane  # noqa: E402

PREFIX = program_spans.PREFIX
ROUND, DISPATCH, FETCH, EVAL, LOG = (
    PREFIX + n for n in ("round", "dispatch", "fetch", "eval", "log"))
BUILD, COMPILE, FIRST_CALL = (
    PREFIX + n for n in ("build", "compile", "first_call"))
RING_FILE = "ring.json"
BETWEEN = "between rounds"
IDLE_SPANS = {"idle_fetch_ms": FETCH, "idle_eval_ms": EVAL,
              "idle_dispatch_ms": DISPATCH, "idle_log_ms": LOG}


# ---------------------------------------------------------------------------
# a run's ring
# ---------------------------------------------------------------------------


def make_run(events, t_start, setup_s, traced_rounds=(), dropped=0,
             complete_from=None) -> dict:
    """What every reader below works on. ``spans``: the ``fedml.*``
    span records as ``(t0, t1, name, record)``, by start (an enclosing
    span before what it holds)."""
    spans = [(e["t0"], e["t0"] + e["seconds"], e["name"], e)
             for e in events
             if e.get("kind") == "span" and "t0" in e
             and str(e.get("name", "")).startswith(PREFIX)]
    spans.sort(key=lambda sp: (sp[0], -sp[1]))
    return {"spans": spans, "t_start": t_start, "setup_s": setup_s,
            "traced": sorted(int(r) for r in traced_rounds),
            "dropped": dropped, "complete_from": complete_from}


def program_ring():
    """This process's ring, or None where the program has none."""
    try:
        from fedml_tpu.core import tracing

        return tracing.RING
    except (ImportError, AttributeError):
        return None


def dump_path(ctx) -> str:
    cell = ctx["cell"]
    trace_dir = os.path.join(cell["bench_dir"], ".trace", cell["name"])
    try:
        return os.path.join(
            os.path.dirname(xplane.find_xplane(trace_dir)), RING_FILE)
    except FileNotFoundError:
        return os.path.join(trace_dir, RING_FILE)


def load(ctx):
    """The run ``ctx`` describes, from the live ring — which is left
    beside the trace on the way, as ``scopes.json`` is; None on a
    program without the ring."""
    ring = program_ring()
    if ring is None:
        return None
    traced = ctx.get("traced_rounds") or []
    path = dump_path(ctx)
    if not os.path.exists(path) and os.path.isdir(os.path.dirname(path)):
        ring.dump(path, t_start=ctx["t_start"], setup_s=ctx["setup_s"],
                  traced_rounds=list(traced))
    return make_run(list(ring.events), ctx["t_start"], ctx["setup_s"],
                    traced, ring.dropped, ring.complete_from)


def from_dump(path: str) -> dict:
    """A dump read back. One that does not say when its window opened
    (a dump an operator made, ``Tracer.dump`` with no further key) is
    cut at its first ``fedml.round`` / ``fedml.block``, and its set-up
    counted from its first record."""
    with open(path) as f:
        dump = json.load(f)
    events = [e for e in dump["events"] if "t0" in e]
    t_start = dump.get("t_start")
    if t_start is None:
        loops = [e["t0"] for e in events
                 if e.get("name") in (ROUND, program_spans.BLOCK)]
        t_start = min(loops) if loops else max(
            (e["t0"] + e["seconds"] for e in events), default=0.0)
    setup_s = dump.get("setup_s")
    if setup_s is None:
        setup_s = t_start - min((e["t0"] for e in events), default=t_start)
    return make_run(events, t_start, setup_s, dump.get("traced_rounds", ()),
                    dump.get("dropped", 0), dump.get("complete_from"))


def whole_from(run, t: float) -> bool:
    """Does the ring still hold every span that began at or after
    ``t``?"""
    return not run["dropped"] or (
        run["complete_from"] is not None and run["complete_from"] <= t)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def setup_spans(run):
    """The spans that began in set-up: before the window opened."""
    lo, hi = run["t_start"] - run["setup_s"], run["t_start"]
    return [sp for sp in run["spans"] if sp[0] < hi and sp[1] > lo]


def _covered(spans, lo, hi) -> float:
    """Seconds of ``[lo, hi]`` inside at least one of ``spans``: nested
    spans count once."""
    return xplane.total(xplane.clip(
        xplane.union([(s, e) for s, e, _, _ in spans]), lo, hi))


def setup_numbers(run):
    """``{build_s, round_compile_s, eval_first_s, setup_named_s,
    setup_unnamed_s}``; a number whose span the ring does not hold is
    None, and all are where the ring dropped records of set-up."""
    lo, hi = run["t_start"] - run["setup_s"], run["t_start"]
    if not whole_from(run, lo):
        return dict.fromkeys(
            ("build_s", "round_compile_s", "eval_first_s", "setup_named_s",
             "setup_unnamed_s"))
    spans = setup_spans(run)

    def of(name):
        own = [sp for sp in spans if sp[2] == name]
        return _covered(own, lo, hi) if own else None

    evals = [sp for sp in spans if sp[2] == EVAL]
    named = _covered(spans, lo, hi)
    return {"build_s": of(BUILD), "round_compile_s": of(COMPILE),
            "eval_first_s": evals[0][1] - evals[0][0] if evals else None,
            "setup_named_s": named,
            "setup_unnamed_s": run["setup_s"] - named if spans else None}


# ---------------------------------------------------------------------------
# the round loop
# ---------------------------------------------------------------------------


def window_spans(run):
    """The window's spans whose body ran to its end (the window closes
    by raising out of the last round's ``log``: that round's
    ``fedml.log`` and ``fedml.round`` are tagged ``error``)."""
    return [sp for sp in run["spans"]
            if sp[0] >= run["t_start"] and "error" not in sp[3]]


def untouched(run, r: int) -> bool:
    """Did the profiler leave round ``r`` alone? The traced rounds did
    not, nor their two neighbours: ``start_trace`` runs inside the
    ``fedml.log`` of the round before the first, and the round after
    the last starts where ``stop_trace`` returned."""
    traced = run["traced"]
    return not traced or r < traced[0] - 1 or r > traced[-1] + 1


def loop_samples(run, keep) -> dict:
    """``{span name or BETWEEN: [seconds]}`` over the rounds ``keep(r)``
    admits. Evaluating rounds give only their ``fedml.eval``: a round
    that holds an evaluation is another quantity than one that does
    not. BETWEEN is the gap from a kept round's end to the next round's
    start, where that one is kept too."""
    spans = window_spans(run)
    evaluating = {sp[3].get("round") for sp in spans if sp[2] == EVAL}
    rounds = {sp[3]["round"]: sp for sp in spans
              if sp[2] == ROUND and "round" in sp[3]}
    out = {n: [] for n in (ROUND, DISPATCH, FETCH, LOG, EVAL, BETWEEN)}
    for s, e, name, rec in spans:
        r = rec.get("round")
        if name not in out or r not in rounds or not keep(r):
            continue
        if (name == EVAL) == (r in evaluating):
            out[name].append(e - s)
    for r, (s, e, _, _) in rounds.items():
        nxt = rounds.get(r + 1)
        if (nxt is not None and keep(r) and keep(r + 1)
                and r not in evaluating):
            out[BETWEEN].append(nxt[0] - e)
    return out


LOOP_METRICS = {"round_untraced_ms": ROUND, "dispatch_untraced_ms": DISPATCH,
                "fetch_untraced_ms": FETCH, "between_untraced_ms": BETWEEN,
                "eval_untraced_ms": EVAL}


def loop_numbers(run):
    """The five ``*_untraced_ms``: medians (ms) over the rounds the
    profiler left alone; None where there is no such span, and all of
    them where the ring dropped records of the window."""
    if not whole_from(run, run["t_start"]):
        return dict.fromkeys(LOOP_METRICS)
    took = loop_samples(run, lambda r: untouched(run, r))
    return {metric: (1e3 * statistics.median(took[name])
                     if took[name] else None)
            for metric, name in LOOP_METRICS.items()}


# ---------------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------------


def quantile95(values):
    """95th percentile, linear between order statistics (as
    ``run.quantile95``)."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def offset_between(run, capture_spans):
    """The capture's clock minus the host's, from the ``fedml.dispatch``
    spans both hold, matched by their ``round`` attr: ``{offset_s``
    (median of capture start - ring ``t0``), ``residual_s`` (p95 of the
    distance from it), ``matched}``; None where no span is in both."""
    ring = {sp[3].get("round"): sp[0] for sp in run["spans"]
            if sp[2] == DISPATCH and sp[0] >= run["t_start"]}
    diffs = [s - ring[st["round"]] for s, _, n, st in capture_spans
             if n == DISPATCH and st.get("round") in ring]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    return {"offset_s": offset, "matched": len(diffs),
            "residual_s": quantile95([abs(d - offset) for d in diffs])}


def clock_offset(ctx):
    """``offset_between`` for the run ``ctx`` describes; adding
    ``offset_s`` to a ring span's ``t0`` places it on the device's
    timeline — the ``fedml.log`` in which ``start_trace`` ran, the one
    in which ``stop_trace`` ran, neither of which the capture holds."""
    run, t = load(ctx), program_spans.analyse(ctx)
    if run is None or t is None:
        return None
    return offset_between(run, t["spans"])


# ---------------------------------------------------------------------------
# the thirteen metrics
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=2)
def idle_in_spans(path: str, chips: int) -> dict:
    """``{span name: seconds}`` of chip 0's idle time in the traced
    part (``program_spans``' window) that lies INSIDE a span of that
    name, for the four spans of ``IDLE_SPANS``: cut at the spans' edges
    exactly, as ``idle_unnamed_ms`` cuts its rest, so the five partition
    the idle time (``program_spans``' printed table names a whole gap
    by its midpoint: summed with the exact rest, a gap across an edge
    counts twice — 2.5 % of the idle time in two cells' first traced
    runs). The four spans are siblings; none holds another."""
    data = xplane.load(path)
    planes = xplane.device_planes(data)[:chips]
    lo, hi = program_spans._window(data, planes)
    busy0 = xplane.union([
        (max(s, lo), min(e, hi))
        for s, e, n in xplane.events(xplane._line(planes[0], xplane.OPS_LINE))
        if e > lo and s < hi and not xplane.is_wrapper(n)])
    idle0 = xplane.subtract([(lo, hi)], busy0)
    spans = program_spans.program_host_spans(data)
    out = {}
    for name in IDLE_SPANS.values():
        own = xplane.union([(s, e) for s, e, n, _ in spans if n == name])
        out[name] = xplane.total(idle0) - xplane.total(
            xplane.subtract(idle0, own))
    return out


def metric(ctx, name: str):
    """One per-layer number by its name, or None."""
    if name in IDLE_SPANS:
        t = program_spans.analyse(ctx)  # None off the chip, or no span
        if t is None or program_ring() is None:
            return None
        cell = ctx["cell"]
        path = xplane.find_xplane(
            os.path.join(cell["bench_dir"], ".trace", cell["name"]))
        idle = idle_in_spans(path, int(ctx["chips"]))
        return 1e3 * idle[IDLE_SPANS[name]] / t["rounds"]
    run = load(ctx)
    if run is None:
        return None
    if name in LOOP_METRICS:
        return loop_numbers(run)[name]
    return setup_numbers(run)[name]


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------


def _attrs(rec) -> str:
    return " ".join(f"{k}={rec[k]}" for k in ("sim", "family", "key",
                                              "round", "error") if k in rec)


def _print_tree(nodes, depth=0):
    for node in nodes:
        print(f"{'  ' * depth}{node['name']:<{34 - 2 * depth}}"
              f"{node['end'] - node['start']:>10.3f} s  self "
              f"{node['self_s']:>8.3f} s  {_attrs(node['stats'])}")
        _print_tree(node["children"], depth + 1)


def _row(label, took):
    if not took:
        return f"{label:>7}{0:>6}{'-':>12}{'-':>12}"
    return (f"{label:>7}{len(took):>6}{1e3 * statistics.median(took):>12.3f}"
            f"{1e3 * quantile95(took):>12.3f}")


def print_tables(path: str) -> None:
    run = from_dump(path)
    lo, hi = run["t_start"] - run["setup_s"], run["t_start"]
    print(f"{len(run['spans'])} fedml.* spans, dropped {run['dropped']}, "
          f"whole from {run['complete_from']}; set-up {run['setup_s']:.3f} s")
    print("\n1. set-up (s; self = minus direct children)")
    _print_tree(program_spans.span_tree(setup_spans(run)))
    for name, value in setup_numbers(run).items():
        print(f"{name:<18}" + ("      None" if value is None
                               else f"{value:>10.3f}"))
    parts, program = {}, ("?", "?")
    for s, e, name, rec in setup_spans(run):
        if name == FIRST_CALL or name.startswith(COMPILE):
            if "family" in rec:
                program = (rec["family"], rec["key"])
            row = parts.setdefault(program, {})
            row[name] = row.get(name, 0.0) + e - s
    for (family, key), row in parts.items():
        print(f"  {family} {key}: " + "  ".join(
            f"{n[len(PREFIX):]} {v:.3f}" for n, v in row.items()))

    traced = run["traced"]
    inside = f"{traced[0]}-{traced[-1]}" if traced else "none"
    print(f"\n2. the round loop (ms): rounds {inside} (the dump's "
          "traced_rounds) against the rounds the profiler left alone")
    kept = loop_samples(run, lambda r: untouched(run, r))
    part = loop_samples(run, lambda r: r in traced)
    print(f"{'span':<16}{'rounds':>7}{'count':>6}{'median':>12}{'p95':>12}")
    for name in (ROUND, DISPATCH, FETCH, LOG, BETWEEN, EVAL):
        print(f"{name:<16}" + _row(inside, part[name]))
        print(f"{'':<16}" + _row("others", kept[name]))
    if not whole_from(run, hi):
        print("the ring dropped records of the window: no reader trusts it")

    try:
        trace = xplane.find_xplane(os.path.dirname(os.path.abspath(path)))
    except FileNotFoundError:
        print("\n3. no .xplane.pb beside the dump: no clock offset")
        return
    capture = program_spans.program_host_spans(xplane.load(trace))
    off = offset_between(run, capture)
    if off is None:
        print("\n3. no fedml.dispatch span in both places: no clock offset")
        return
    print(f"\n3. capture clock - host clock: {off['offset_s']:.9f} s over "
          f"{off['matched']} fedml.dispatch spans, residual (p95) "
          f"{1e6 * off['residual_s']:.1f} us")
    first = min(s for s, _, _, _ in capture)
    for s, e, name, rec in run["spans"]:
        if name == LOG and traced and rec.get("round") in (
                traced[0] - 1, traced[-1]):
            print(f"   fedml.log round={rec['round']}: {1e3 * (e - s):.3f} ms,"
                  f" from {1e3 * (s + off['offset_s'] - first):+.3f} ms of "
                  "the capture's first fedml.* span")


if __name__ == "__main__":
    print_tables(sys.argv[1])
