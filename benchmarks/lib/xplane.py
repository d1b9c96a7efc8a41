"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers
the per-layer metrics read. Uses nothing but ``jax.profiler.
ProfileData``: planes, their lines, events with a start and a duration.

What a TPU trace looks like (read by hand on a v5e trace of this
benchmark, kept as ``benchmarks/fixtures/``): one plane ``/device:TPU:<i>``
a chip, whose line ``XLA Ops`` holds one event per executed HLO
operation (named by the HLO instruction, e.g. ``fusion.123``,
``convolution.45``, ``all-reduce.1``) and whose line ``XLA Modules``
holds one event per executed program (``jit__round(...)``). Host
threads are lines of the plane ``/host:CPU``; the benchmark's own spans
(``bench.run_round``, ``bench.evaluate_global``) are events there, on
the same clock.

All times below are seconds. ``busy`` is the UNION of op intervals on a
chip (ops on one chip's line do not overlap, but nested control-flow
events do, so a union and never a sum).
"""

from __future__ import annotations

import glob
import os
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "psum", "send", "recv")
# events on the ops line that only wrap other ops
WRAPPERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_planes(data):
    return sorted(
        (p for p in data.planes if p.name.startswith("/device:TPU:")),
        key=lambda p: int(p.name.rsplit(":", 1)[1]))


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def events(line):
    """[(start_s, end_s, name)] of a line, sorted by start."""
    out = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
           for e in line.events]
    out.sort()
    return out


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def hlo_name(name: str) -> str:
    """An op event is named by its HLO line, ``%fusion.12 = f32[8]{0}
    fusion(...)``: the instruction's own name is what precedes `` = ``."""
    return name.lstrip("%").split(" = ")[0].split("(")[0].strip()


def is_collective(name: str) -> bool:
    return hlo_name(name).startswith(COLLECTIVES)


def is_wrapper(name: str) -> bool:
    return hlo_name(name).split(".")[0] in WRAPPERS


def host_spans(data):
    """The benchmark's own spans from the host plane: [(s, e, name)]."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [ev for ev in events(line)
                    if ev[2].startswith(SPAN_PREFIX)]
    out.sort()
    return out


def op_family(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: ops grouped by the stem of
    their HLO name. XLA names most fusions after what they hold
    (``add_select_fusion``); a plain ``fusion`` says nothing, so its
    ``kind=`` is added — on a TPU ``kOutput`` fusions are the ones
    built round a convolution or matrix product."""
    base = hlo_name(name)
    stem, _, tail = base.rpartition(".")
    stem = stem if stem and tail.isdigit() else base
    if stem == "fusion" and " kind=k" in name:
        stem += "(" + name.split(" kind=")[1].split(",")[0].strip() + ")"
    return stem


def reduce_trace(path: str, chips: int, rounds: int) -> dict:
    """-> the numbers of one traced part. The steady window runs from
    the first ``bench.run_round`` span's start to the end of the last
    benchmark span; ``rounds`` is how many rounds it holds."""
    data = load(path)
    spans = host_spans(data)
    planes = device_planes(data)[:chips]
    if not planes:
        raise ValueError(f"no /device:TPU plane in {path}")
    starts = [s for s, _, n in spans if n == SPAN_PREFIX + "run_round"]
    if starts:
        lo, hi = starts[0], max(e for _, e, _ in spans)
    else:  # a trace without the spans: the extent of the device ops
        evs = [ev for p in planes for ev in events(_line(p, OPS_LINE))]
        lo, hi = min(s for s, _, _ in evs), max(e for _, e, _ in evs)
    window = hi - lo

    per_chip, compute, exposed, coll, fam = [], [], [], [], {}
    module_busy = {}
    for plane in planes:
        ops = [(s, e, n) for s, e, n in events(_line(plane, OPS_LINE))
               if e > lo and s < hi]
        leaf = [(s, e, n) for s, e, n in ops if not is_wrapper(n)]
        busy = clip(union([(s, e) for s, e, _ in leaf]), lo, hi)
        per_chip.append(total(busy))
        c_iv = clip(union([(s, e) for s, e, n in leaf if is_collective(n)]),
                    lo, hi)
        k_iv = clip(union([(s, e) for s, e, n in leaf
                           if not is_collective(n)]), lo, hi)
        coll.append(total(c_iv))
        compute.append(total(k_iv))
        exposed.append(total(subtract(c_iv, k_iv)))
        if plane is planes[0]:
            for s, e, n in leaf:
                key = op_family(n)
                fam[key] = fam.get(key, 0.0) + (min(e, hi) - max(s, lo))
            busy0 = busy
        mods = _line(plane, MODULES_LINE)
        if mods is not None and plane is planes[0]:
            for s, e, n in events(mods):
                if e > lo and s < hi:
                    key = n.split("(")[0]
                    module_busy[key] = module_busy.get(key, 0.0) + total(
                        clip(busy0, s, e))

    # idle gaps of chip 0, named by the benchmark span the host was in
    gaps = subtract([(lo, hi)], busy0)
    named = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inside = [n for a, b, n in spans if a <= mid <= b]
        what = inside[-1] if inside else "between bench spans (run loop)"
        named.setdefault(what, [0.0, 0.0])
        named[what][0] += e - s
        named[what][1] = max(named[what][1], e - s)
    idle_gaps = sorted(
        ([f"{k} (longest {v[1] * 1e3:.3f} ms)", v[0]]
         for k, v in named.items()), key=lambda kv: -kv[1])
    device_ops = sorted(([k, v] for k, v in fam.items()),
                        key=lambda kv: -kv[1])

    eval_spans = [(s, e) for s, e, n in spans
                  if n == SPAN_PREFIX + "evaluate_global" and s >= lo]
    mean_busy = sum(per_chip) / len(per_chip)
    # skew is read off the compute alone: a chip that waits for a
    # slower one waits INSIDE the all-reduce, which counts as busy
    busiest = max(compute)
    round_mod = [v for k, v in module_busy.items() if "round" in k]
    return {
        "window_s": window,
        "busy_s": mean_busy,
        "per_chip_busy_s": per_chip,
        "rounds": rounds,
        "round_program_busy_s": sum(round_mod) if round_mod else None,
        "module_busy_s": module_busy,
        "collective_s": sum(coll) / len(coll),
        "collective_exposed_s": sum(exposed) / len(exposed),
        "per_chip_compute_s": compute,
        "chip_skew": ((busiest - sum(compute) / len(compute)) / busiest
                      if busiest > 0 else 0.0),
        "eval_span_s": [e - s for s, e in eval_spans],
        "device_ops": device_ops[:10],
        "idle_gaps": idle_gaps[:10],
    }


def describe(path: str, limit: int = 12) -> None:
    """Print the shape of a trace: planes, lines, event counts and a few
    names. The way to look at one by hand."""
    data = load(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            print(f"  line {line.name!r}: {len(evs)} events; "
                  + "; ".join(f"{n[:60]} x{c}" for n, c in top))


if __name__ == "__main__":
    describe(sys.argv[1])
