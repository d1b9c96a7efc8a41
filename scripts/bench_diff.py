"""Diff two bench artifacts and flag metric regressions.

The driver's ``BENCH_r<k>.json`` artifacts wrap a bench run as
``{"n", "cmd", "rc", "tail", ...}`` where ``tail`` holds the run's
stdout — one JSON record per metric line. This tool loads two such
artifacts (or raw ``runs/bench_latest.jsonl`` files, or any file of
JSON-record lines), matches records by metric name, and reports every
metric whose value moved beyond a noise threshold — the regression
gate ROADMAP item 5 asks for, so a perf PR's win (or loss) is a
machine-checked diff, not a by-eye comparison of JSON blobs.

Rules:

- direction comes from the unit: ``rounds/sec`` / ``hit_rate`` /
  ``% test acc`` regress DOWN; ``seconds`` / ``ms/round`` regress UP;
- records marked ``fallback`` (CPU measurements — ``bench.py`` marks
  every record of an intentional ``JAX_PLATFORMS=cpu`` run) are NEVER
  compared against unmarked (TPU) baselines: the pair is reported as
  skipped;
- the default threshold (8%) sits above the observed window-to-window
  spread of the rate lines (``window_rates`` in each record bracket
  the best-of-3 estimator at a few percent);
- exit code is 0 in the default ADVISORY mode (CI runs it for the
  report); ``--strict`` exits 1 when any regression is flagged.

Usage::

    python scripts/bench_diff.py BENCH_r04.json runs/bench_latest.jsonl
    python scripts/bench_diff.py old.jsonl new.jsonl --threshold 0.05 --strict
"""

from __future__ import annotations

import argparse
import json
import sys

#: units where larger is better; anything in _LOWER regresses upward.
#: Units in NEITHER table are compared as higher-is-better and the
#: entry is annotated ``unit_assumed`` so a wrong guess is visible.
_HIGHER = ("rounds/sec", "hit_rate", "% test acc", "accuracy", "acc",
           # async/tier stage (bench --async-bench): emit throughput
           # per fan-in and the headline fan-in scaling ratio
           "emits/sec", "ratio",
           # round-fusion stage (bench --fused-bench): the companion
           # fedavg_mfu_*_fused records — the MFU-recovery acceptance
           # surface is a tracked value, not a side-field
           "mfu")
#: "MB peak": the --mem-bench peak-HBM records (peak_round_hbm_mb_*) —
#: memory growth is a regression; the fallback-mark rule above already
#: keeps analytic CPU records from ever diffing against device peaks.
#: "rounds": the rounds-to-target convergence family (bench
#: --lora-bench rounds_to_match_*, future rounds_to_acc_*) — needing
#: more rounds is a regression.
#: "%": the --anatomy-bench percentage records — the tracked one is
#: critical_path_overhead_pct (attribution cost vs anatomy-off; the
#: < 2% acceptance bar), where growth is a regression.
_LOWER = ("seconds", "ms/round", "s", "ms", "MB/round", "MB peak",
          "rounds", "%")


def extract_records(text: str) -> dict[str, dict]:
    """Pull metric records out of arbitrary bench output text: every
    line that parses as a JSON object with a ``metric`` key counts;
    last record per metric wins (the artifacts are append-only)."""
    recs: dict[str, dict] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            recs[rec["metric"]] = rec
    return recs


def load_bench(path: str) -> dict[str, dict]:
    """Load one artifact: a driver ``BENCH_r*.json`` wrapper (records
    live in its ``tail`` string), or a file of JSON-record lines
    (``runs/bench_latest.jsonl``, raw bench stdout)."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        data = None
    if isinstance(data, dict) and "metric" not in data:
        # driver wrapper: records are JSON lines inside the tail (and
        # optionally a pre-parsed record under "parsed")
        recs = extract_records(str(data.get("tail", "")))
        parsed = data.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            recs.setdefault(parsed["metric"], parsed)
        return recs
    if isinstance(data, dict):  # a single record
        return {data["metric"]: data}
    return extract_records(text)


def _direction(unit: str) -> tuple[int, bool]:
    """``(direction, known)``: +1 when larger is better, -1 when
    smaller is better; ``known=False`` for units in neither table
    (assumed higher-is-better, annotated by the caller)."""
    if unit in _LOWER:
        return -1, True
    return 1, unit in _HIGHER


def diff_records(
    old: dict[str, dict], new: dict[str, dict], threshold: float
) -> dict:
    """Compare metric-by-metric; returns ``{regressions, improvements,
    unchanged, skipped, only_old, only_new}`` where each entry names
    the metric and the relative change."""
    out = {"regressions": [], "improvements": [], "unchanged": [],
           "skipped": [], "only_old": [], "only_new": []}
    for name in sorted(set(old) | set(new)):
        o, n = old.get(name), new.get(name)
        if o is None:
            out["only_new"].append(name)
            continue
        if n is None:
            out["only_old"].append(name)
            continue
        o_fb, n_fb = bool(o.get("fallback")), bool(n.get("fallback"))
        if o_fb != n_fb:
            out["skipped"].append({
                "metric": name,
                "reason": "cpu-fallback record on one side only — "
                          "never compared against TPU numbers",
            })
            continue
        ov, nv = o.get("value"), n.get("value")
        if not isinstance(ov, (int, float)) or not isinstance(
                nv, (int, float)) or ov == 0:
            out["skipped"].append(
                {"metric": name, "reason": "non-numeric or zero value"}
            )
            continue
        rel = (nv - ov) / abs(ov)
        entry = {
            "metric": name,
            "old": ov,
            "new": nv,
            "rel_change": round(rel, 4),
            "unit": o.get("unit", ""),
        }
        if o_fb:
            entry["fallback"] = "cpu"  # cpu-vs-cpu: comparable, marked
        direction, known = _direction(o.get("unit", ""))
        if not known:
            entry["unit_assumed"] = "higher-is-better"
        score = rel * direction
        if score < -threshold:
            out["regressions"].append(entry)
        elif score > threshold:
            out["improvements"].append(entry)
        else:
            out["unchanged"].append(entry)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two BENCH_*.json / bench JSONL artifacts and "
                    "flag metric regressions beyond a noise threshold"
    )
    ap.add_argument("old", help="baseline artifact")
    ap.add_argument("new", help="candidate artifact")
    ap.add_argument("--threshold", type=float, default=0.08,
                    help="relative change below which a move is noise "
                         "(default 0.08, above the bench's "
                         "window-to-window spread)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when regressions are flagged "
                         "(default: advisory — report and exit 0)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full diff as one JSON object")
    a = ap.parse_args(argv)

    old, new = load_bench(a.old), load_bench(a.new)
    if not old and not new:
        print("no metric records found in either artifact",
              file=sys.stderr)
        return 0 if not a.strict else 1
    d = diff_records(old, new, a.threshold)
    if a.json:
        print(json.dumps(
            {"old": a.old, "new": a.new, "threshold": a.threshold, **d},
            indent=2,
        ))
    else:
        for e in d["regressions"]:
            note = (" [unit direction assumed higher-is-better]"
                    if "unit_assumed" in e else "")
            print(f"REGRESSION {e['metric']}: {e['old']} -> {e['new']} "
                  f"({e['rel_change']:+.1%}, {e['unit']}){note}")
        for e in d["improvements"]:
            print(f"improved   {e['metric']}: {e['old']} -> {e['new']} "
                  f"({e['rel_change']:+.1%})")
        for e in d["skipped"]:
            print(f"skipped    {e['metric']}: {e['reason']}")
        print(
            f"bench_diff: {len(d['regressions'])} regressions, "
            f"{len(d['improvements'])} improvements, "
            f"{len(d['unchanged'])} within ±{a.threshold:.0%}, "
            f"{len(d['skipped'])} skipped, "
            f"{len(d['only_old'])}/{len(d['only_new'])} only in "
            "old/new"
        )
    if d["regressions"] and a.strict:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
