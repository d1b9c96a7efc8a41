"""Spread of a cell's runs, as the bound rule reads it.

    python3 benchmarks/spread.py <dir with one log a run: SET.SEED.log>

For every end-to-end metric: each set's median and its spread (the
distance between the first and third quartile, ``statistics.quantiles(
values, n=4)``, as a share of the median), the wider of the sets'
spreads, five times it (the bound the contract asks for), and how far
the second set's median lies from the first's. Also every number
``correct`` compared, with its largest reading over the runs.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(directory: str) -> int:
    sets, checks, wrong = {}, {}, []
    for path in sorted(glob.glob(os.path.join(directory, "*.log"))):
        name = os.path.basename(path)
        if name.startswith(("cal", "T.")):
            continue
        lines = [json.loads(x) for x in open(path) if x.startswith("{")]
        if not lines or "metrics" not in lines[-1]:
            wrong.append((name, "no result line"))
            continue
        last = lines[-1]
        if not last["correct"]:
            wrong.append((name, "correct is false"))
        for k, v in last["metrics"].items():
            sets.setdefault(name.split(".")[0], {}).setdefault(
                k, []).append(v["value"])
        sets[name.split(".")[0]].setdefault("memory_peak_bytes", []).append(
            last["device"]["memory_peak_bytes"])
        for rec in lines:
            if rec.get("phase") == "check":
                checks.setdefault(rec["number"], []).append(
                    (rec["value"], rec["limit"]))
    names = sorted(sets)
    for metric in sorted({m for s in sets.values() for m in s}):
        row = {"metric": metric}
        for s in names:
            vals = sets[s].get(metric, [])
            if len(vals) >= 2:
                row[s] = {"n": len(vals), "median": statistics.median(vals),
                          "spread": spread(vals), "min": min(vals),
                          "max": max(vals)}
        spreads = [row[s]["spread"] for s in names if s in row]
        if spreads:
            row["widest_spread"] = max(spreads)
            row["five_times"] = 5 * max(spreads)
        if len(names) >= 2 and all(s in row for s in names[:2]):
            a, b = row[names[0]]["median"], row[names[1]]["median"]
            row["second_vs_first"] = (b - a) / a
        print(json.dumps(row))
    for number, pairs in sorted(checks.items()):
        print(json.dumps({"number": number, "runs": len(pairs),
                          "largest": max(v for v, _ in pairs),
                          "limit": pairs[0][1]}))
    print(json.dumps({"not_correct": wrong}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
