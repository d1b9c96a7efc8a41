"""Read the numbers the limits of ``correct`` are set from.

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3

One process (the round program compiles once): for every seed, the
program's one-step rounds against the float32 reference — the numbers a
benchmark run compares — and, for the control seeds, the reference put
in the program's place in the nearest precision below the one the
configuration states (float8 for bfloat16). Prints one line a reading
and, last, the largest sound reading and the smallest control reading
of every number. Not part of a benchmark run; needs the chip like one.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

import run as R


def _leaf_norms(tree):
    return {
        jax.tree_util.keystr(path): float(
            np.linalg.norm(np.asarray(leaf, np.float64)))
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def worst_norm_gap(got, ref):
    """Worst leaf of ``|norm(got) - norm(ref)|`` against the larger of the
    reference's norm of that leaf and of its median leaf (some leaves'
    gradients are all but zero). -> (gap, leaf name)."""
    g, r = _leaf_norms(got), _leaf_norms(ref)
    if g.keys() != r.keys():
        return float("inf"), "tree structures differ"
    floor = float(np.median(list(r.values())))
    worst = (0.0, "")
    for k, rn in r.items():
        gap = abs(g[k] - rn) / max(rn, floor, 1e-30)
        if not gap <= worst[0]:  # also catches nan
            worst = (gap, k)
    return worst


def for_the_record(program, reference, initial):
    """Numbers tried for ``correct`` and dropped (PERF.md section 6):
    the norm gaps by the worst leaf, the whole gradient's and the whole
    change's relative error. Read here, judged nowhere."""
    from lib.fedref import rel_err, tree_sub

    pg, rg = program["first_grad"], reference["first_grad"]
    moved = tree_sub(program["final"], initial)
    moved_ref = tree_sub(reference["final"], initial)
    rows = []
    for name, (gap, leaf) in (
            ("first_grad_worst_leaf_gap", worst_norm_gap(pg, rg)),
            ("change_worst_leaf_gap", worst_norm_gap(moved, moved_ref))):
        rows.append({"number": name, "value": gap, "leaf": leaf})
    rows.append({"number": "first_grad_rel_err", "value": rel_err(pg, rg)})
    rows.append({"number": "change_rel_err", "value": rel_err(
        moved["params"], moved_ref["params"])})
    return rows


def main(argv=None, root: str = R.ROOT, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from fedml_tpu.core.compile_cache import enable_compile_cache
    from lib import fedref, traffic as TR

    enable_compile_cache()
    cell = R.load_cell(args.workload, root)
    dev = jax.devices()[0]
    if require_chip and (dev.platform != "tpu"
                         or len(jax.devices()) < int(cell["cell"]["chips"])):
        print(f"calibrate.py needs the cell's chips; found {dev.platform} x "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    config, traffic = cell["config"], cell["traffic"]
    cfg = R.experiment_config(config, traffic)
    limits = config["correct_limits"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sim, sound, control = None, {}, {}
    for seed in seeds:
        pop = TR.make_population(config["dataset"], traffic, seed)
        fresh = R.build_sim(cfg, traffic, pop)
        if sim is None:
            sim = fresh  # its compiled round serves every seed
        else:
            name, operand = R._data_operand(fresh)
            setattr(sim, name, operand)
        del fresh
        host_state = R.seed_state(sim, cell["arch"], seed)
        initial = host_state.variables
        program, _, _ = R.drive_check_rounds(
            sim, cfg, sim.batch_size, host_state)
        reference = R.reference_rounds(
            cell, cfg, pop, sim.batch_size, initial)
        _, ok = fedref.compare(program, reference, initial, limits)
        rows, _ = fedref.compare(program, reference, initial, None)
        rows += for_the_record(program, reference, initial)
        for row in rows:
            sound.setdefault(row["number"], []).append(row["value"])
        print(json.dumps({"seed": seed, "side": "program", "ok": ok,
                          "rows": rows}), flush=True)
        if seed in controls:
            lower = R.reference_rounds(
                cell, cfg, pop, sim.batch_size, initial, fedref.FP8)
            _, ok = fedref.compare(lower, reference, initial, limits)
            rows, _ = fedref.compare(lower, reference, initial, None)
            rows += for_the_record(lower, reference, initial)
            for row in rows:
                control.setdefault(row["number"], []).append(row["value"])
            print(json.dumps({"seed": seed, "side": "control_fp8", "ok": ok,
                              "rows": rows}), flush=True)
    summary = {
        k: {"sound_max": max(v), "sound_all": v,
            "control_min": min(control[k]) if k in control else None,
            "control_all": control.get(k), "limit": limits.get(
                k.split(".")[0])}
        for k, v in sound.items()
    }
    print(json.dumps({"workload": args.workload, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
