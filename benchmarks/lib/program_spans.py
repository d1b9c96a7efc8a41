"""The program's OWN spans and scopes in a traced part: what
``fedml_tpu.core.tracing.span`` (host, ``fedml.round`` / ``.dispatch`` /
``.fetch`` / ``.eval`` / ``.log`` / ``.compile``) and ``jax.named_scope``
(device, ``fedml.sample`` / ``.local.gather`` / ``.local.grad`` /
``.local.update`` / ``.defense_agg`` / ``.server_update``) wrote into the
profiler's trace, reduced to three tables:

1. the ``fedml.*`` host spans as a tree per round, with self times;
2. each chip's leaf-op busy time by scope inside the round program's
   module events (chip 0 and the mean over chips);
3. chip 0's idle gaps named by the innermost ``fedml.*`` span the host
   was in, with that span's attrs.

    python3 benchmarks/lib/program_spans.py <xplane.pb> [scopes.json]

prints all three. The window is ``xplane.reduce_trace``'s (first
``bench.run_round`` start to the last ``bench.*`` end), so these numbers
describe the same seconds as the older per-layer metrics.

A device op carries no scope in what ``ProfileData`` shows of a trace
(its name is the optimized HLO line, without ``op_name``), so ops are
joined by instruction name against the program's
``memscope.scope_map(family, key)``, module by module (the module an op
ran in is the ``XLA Modules`` event it lies in). The map is taken from
``scopes.json`` beside the trace where there is one (a fixture's, or the
one a run left), else from the running process, which then leaves the
file. A program without spans, scopes or ``scope_map`` (the parent of
the PR that added them) gives nothing to read: every ``metric`` is None.
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

from lib import xplane  # noqa: E402

PREFIX = "fedml."
ROUND, BLOCK = PREFIX + "round", PREFIX + "block"
SCOPES_FILE = "scopes.json"
UNSCOPED = "unscoped"


def program_scopes() -> dict | None:
    """``{module: {instruction: scope}}`` of the programs this process
    compiled, from the program's own ``memscope.scope_map``; None where
    the program has no such function."""
    try:
        from fedml_tpu.core import memscope

        programs = memscope.scope_programs()
    except (ImportError, AttributeError):
        return None
    out = {}
    for family, key, module in programs:
        smap = memscope.scope_map(family, key)
        if smap:
            out.setdefault(module, {}).update(smap)
    return out or None


def load_scopes(trace_dir: str) -> dict | None:
    path = os.path.join(trace_dir, SCOPES_FILE)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    scopes = program_scopes()
    if scopes is not None:
        with open(path, "w") as f:
            json.dump(scopes, f)
    return scopes


def program_host_spans(data):
    """``[(start_s, end_s, name, stats)]`` of the ``fedml.*`` host
    events, by start (an enclosing span before what it holds)."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((s, s + e.duration_ns * 1e-9, e.name,
                                dict(e.stats)))
    out.sort(key=lambda ev: (ev[0], -ev[1]))
    return out


def span_tree(spans):
    """Nest spans by containment -> ``[node]`` of the outermost ones; a
    node is ``{name, start, end, stats, children, self_s}`` and
    ``self_s`` its duration minus its direct children's."""
    roots, stack = [], []
    for s, e, name, stats in spans:
        node = {"name": name, "start": s, "end": e, "stats": stats,
                "children": []}
        while stack and not (stack[-1]["start"] <= s
                             and e <= stack[-1]["end"]):
            stack.pop()
        (stack[-1]["children"] if stack else roots).append(node)
        stack.append(node)

    def close(node):
        for child in node["children"]:
            close(child)
        node["self_s"] = (node["end"] - node["start"]) - sum(
            c["end"] - c["start"] for c in node["children"])

    for node in roots:
        close(node)
    return roots


def _window(data, planes):
    spans = xplane.host_spans(data)
    starts = [s for s, _, n in spans
              if n == xplane.SPAN_PREFIX + "run_round"]
    if starts:
        return starts[0], max(e for _, e, _ in spans)
    evs = [ev for p in planes
           for ev in xplane.events(xplane._line(p, xplane.OPS_LINE))]
    return min(s for s, _, _ in evs), max(e for _, e, _ in evs)


def _scope_busy(plane, scopes, lo, hi):
    """-> ({scope: seconds}, {(op family, scope): seconds}, merged busy
    intervals) of one chip. Each instant of the round program's busy
    time is given to the leaf op running then (the first, where two
    overlap), so the scopes partition the union exactly."""
    mods = [(s, e, n.split("(")[0])
            for s, e, n in xplane.events(
                xplane._line(plane, xplane.MODULES_LINE))
            if e > lo and s < hi]
    leaf = [(max(s, lo), min(e, hi), n)
            for s, e, n in xplane.events(
                xplane._line(plane, xplane.OPS_LINE))
            if e > lo and s < hi and not xplane.is_wrapper(n)]
    by_scope, by_family, j, covered = {}, {}, 0, lo
    for s, e, n in leaf:
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        take = e - max(s, covered)
        covered = max(covered, e)
        if take <= 0 or j >= len(mods) or mods[j][0] > s:
            continue
        module = mods[j][2]
        if "round" not in module:
            continue  # the evaluator, helpers: not the round program
        scope = (scopes.get(module) or {}).get(
            xplane.hlo_name(n)) or UNSCOPED
        by_scope[scope] = by_scope.get(scope, 0.0) + take
        key = (xplane.op_family(n), scope)
        by_family[key] = by_family.get(key, 0.0) + take
    busy = xplane.union([(s, e) for s, e, _ in leaf])
    return by_scope, by_family, busy


def _idle_by_span(busy0, spans, lo, hi):
    """Chip 0's idle gaps, each named by the innermost ``fedml.*`` span
    its midpoint lies in. -> rows ``[name, total_s, longest_s, count,
    stats of the longest's span]``, longest total first."""
    named = {}
    for s, e in xplane.subtract([(lo, hi)], busy0):
        mid = 0.5 * (s + e)
        inside = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name, stats = ((inside[-1][2], inside[-1][3]) if inside
                       else ("outside every fedml span", {}))
        row = named.setdefault(name, [name, 0.0, 0.0, 0, {}])
        row[1] += e - s
        row[3] += 1
        if e - s > row[2]:
            row[2], row[4] = e - s, stats
    return sorted(named.values(), key=lambda r: -r[1])


@functools.lru_cache(maxsize=4)
def read(path: str, chips: int | None = None, rounds: int | None = None):
    """One trace, read once. ``chips``: the first so many device planes
    (all of them by default); ``rounds``: how many rounds the window
    holds (by default the ``fedml.dispatch`` spans in it)."""
    data = xplane.load(path)
    planes = xplane.device_planes(data)[:chips]
    if not planes:
        raise ValueError(f"no /device:TPU plane in {path}")
    lo, hi = _window(data, planes)
    spans = [sp for sp in program_host_spans(data)
             if sp[1] > lo and sp[0] < hi]
    scopes = load_scopes(os.path.dirname(path))
    if rounds is None:
        rounds = sum(1 for sp in spans if sp[2] == PREFIX + "dispatch")
    out = {"window": (lo, hi), "rounds": rounds, "spans": spans,
           "tree": span_tree(spans), "scopes": scopes is not None}
    per_chip, busy0 = [], None
    for plane in planes:
        by_scope, by_family, busy = _scope_busy(
            plane, scopes or {}, lo, hi)
        per_chip.append((by_scope, sum(by_scope.values())))
        if busy0 is None:
            busy0, out["family_scope_s"] = busy, by_family
    out["per_chip"] = per_chip
    names = sorted({k for by_scope, _ in per_chip for k in by_scope})
    out["scope_busy_s"] = {
        k: sum(c[0].get(k, 0.0) for c in per_chip) / len(per_chip)
        for k in names}
    out["round_program_busy_s"] = (
        sum(c[1] for c in per_chip) / len(per_chip))
    out["idle"] = _idle_by_span(busy0, spans, lo, hi)
    leaves = [sp for sp in spans if sp[2] not in (ROUND, BLOCK)]
    idle0 = xplane.subtract([(lo, hi)], busy0)
    out["idle_unnamed_s"] = xplane.total(xplane.subtract(
        idle0, xplane.union([(s, e) for s, e, _, _ in leaves])))
    return out


def analyse(ctx):
    """The traced part of this run, or None where there is nothing to
    read: off the chip, or a trace that holds no ``fedml.*`` span."""
    if ctx.get("trace") is None or ctx["device"]["platform"] != "tpu":
        return None
    cell = ctx["cell"]
    trace_dir = os.path.join(cell["bench_dir"], ".trace", cell["name"])
    try:
        path = xplane.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    out = read(path, int(ctx["chips"]), len(ctx["traced_rounds"]))
    return out if out["spans"] else None


def _scope_ms(t, scope):
    """Busy ms a traced round under ``scope`` (its sub-scopes apart),
    mean over chips; None without a scope map (a scope no op ran under
    reads 0)."""
    if not t["scopes"]:
        return None
    return 1e3 * t["scope_busy_s"].get(scope, 0.0) / t["rounds"]


def metric(ctx, name: str):
    """One per-layer number by its quantity's name, or None."""
    t = analyse(ctx)
    if t is None:
        return None
    spans, rounds = t["spans"], t["rounds"]
    if name == "loop_self_ms":
        whole = [n for n in t["tree"] if n["name"] == ROUND]
        return (1e3 * statistics.mean(n["self_s"] for n in whole)
                if whole else None)
    if name in ("dispatch_ms", "fetch_wait_ms"):
        span = PREFIX + ("dispatch" if name == "dispatch_ms" else "fetch")
        took = [e - s for s, e, n, _ in spans if n == span]
        return 1e3 * statistics.median(took) if took else None
    if name == "eval_h2d_mb":
        sent = [st["h2d_bytes"] for _, _, n, st in spans
                if n == PREFIX + "eval" and "h2d_bytes" in st]
        return statistics.mean(sent) / 1e6 if sent else None
    if name == "idle_unnamed_ms":
        return 1e3 * t["idle_unnamed_s"] / rounds
    if name == "unscoped_pct":
        if not t["scopes"] or not t["round_program_busy_s"]:
            return None
        return (100.0 * t["scope_busy_s"].get(UNSCOPED, 0.0)
                / t["round_program_busy_s"])
    scope = {"local_gather_ms": "fedml.local.gather",
             "local_grad_ms": "fedml.local.grad",
             "local_update_ms": "fedml.local.update",
             "server_update_ms": "fedml.server_update"}[name]
    return _scope_ms(t, scope)


# ---------------------------------------------------------------------------
# the three tables
# ---------------------------------------------------------------------------


def _print_tree(nodes, depth=0, limit=3):
    for node in nodes[:limit]:
        stats = " ".join(f"{k}={v}" for k, v in sorted(
            node["stats"].items()))
        print(f"{'  ' * depth}{node['name']:<{28 - 2 * depth}} "
              f"{1e3 * (node['end'] - node['start']):10.3f} ms  "
              f"self {1e3 * node['self_s']:9.3f} ms  {stats}")
        _print_tree(node["children"], depth + 1, limit=99)
    if len(nodes) > limit:
        print(f"{'  ' * depth}... {len(nodes) - limit} more")


def print_tables(path: str) -> None:
    t = read(path)
    lo, hi = t["window"]
    rounds = max(1, t["rounds"])
    print(f"window {hi - lo:.6f} s, {t['rounds']} rounds dispatched, "
          f"{len(t['per_chip'])} chips, scope map: "
          f"{'yes' if t['scopes'] else 'NO'}")
    print("\n1. fedml.* host spans (first rounds; ms, self = minus "
          "direct children)")
    _print_tree(t["tree"])
    by_name = {}
    for s, e, n, _ in t["spans"]:
        by_name.setdefault(n, []).append(1e3 * (e - s))
    print(f"\n{'span':<18}{'count':>6}{'median ms':>12}{'mean ms':>12}"
          f"{'total ms':>12}")
    for n, took in sorted(by_name.items()):
        print(f"{n:<18}{len(took):>6}{statistics.median(took):>12.3f}"
              f"{statistics.mean(took):>12.3f}{sum(took):>12.3f}")
    whole = [n for n in t["tree"] if n["name"] == ROUND]
    if whole:
        print(f"fedml.round self time: mean "
              f"{1e3 * statistics.mean(n['self_s'] for n in whole):.3f} ms "
              f"over {len(whole)} whole rounds")
    print("\n2. round-program busy time by scope (ms a traced round)")
    chip0 = t["per_chip"][0][0]
    print(f"{'scope':<22}{'chip 0':>10}{'mean':>10}{'share %':>9}")
    total = t["round_program_busy_s"] or float("nan")
    for scope, sec in sorted(t["scope_busy_s"].items(),
                             key=lambda kv: -kv[1]):
        print(f"{scope:<22}{1e3 * chip0.get(scope, 0.0) / rounds:>10.3f}"
              f"{1e3 * sec / rounds:>10.3f}{100 * sec / total:>9.2f}")
    print(f"{'round program':<22}"
          f"{1e3 * t['per_chip'][0][1] / rounds:>10.3f}"
          f"{1e3 * total / rounds:>10.3f}{100.0:>9.2f}")
    print("\n   chip 0's op families by scope (ms a traced round)")
    for (family, scope), sec in sorted(
            t["family_scope_s"].items(), key=lambda kv: -kv[1])[:16]:
        print(f"   {family:<34}{scope:<22}{1e3 * sec / rounds:>10.3f}")
    print("\n3. chip 0 idle gaps by the innermost fedml.* span")
    print(f"{'span':<28}{'total ms':>11}{'longest ms':>12}{'gaps':>6}"
          "  attrs of the longest")
    for name, tot, longest, count, stats in t["idle"]:
        attrs = " ".join(f"{k}={v}" for k, v in sorted(stats.items()))
        print(f"{name:<28}{1e3 * tot:>11.3f}{1e3 * longest:>12.3f}"
              f"{count:>6}  {attrs}")
    print(f"idle in no fedml.* child span: "
          f"{1e3 * t['idle_unnamed_s'] / rounds:.3f} ms a traced round")


if __name__ == "__main__":
    if len(sys.argv) > 2:  # a scope map kept apart from the trace
        import shutil

        shutil.copy(sys.argv[2], os.path.join(
            os.path.dirname(os.path.abspath(sys.argv[1])), SCOPES_FILE))
    print_tables(sys.argv[1])
