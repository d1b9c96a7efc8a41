"""One-command experiment battery: the reference's 7-algorithm
comparison (``/root/reference/Makefile:5-13`` ->
``scripts/experiments/run_fed_experiment.sh``: each algorithm x N
seeded repetitions on MNIST, hetero alpha=0.1, r=0.1 -> 6000 samples,
10 clients all participating, 5 local epochs, 50 rounds) driven through
the harness repetition runner.

Usage::

    python scripts/run_battery.py                 # full battery
    python scripts/run_battery.py --reps 5        # reference rep count
    python scripts/run_battery.py --algorithms fedavg fedgdkd --rounds 10

Writes ``<out>/battery.jsonl`` (one summary record per repetition) and
prints a grouped mean +- std table — the equivalent of the reference's
wandb-grouped comparison report.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATTERY_ALGORITHMS = (
    # the Makefile's run-example-experiments list, in its order
    "baseline", "centralized", "fedavg", "fedmd", "fd_faug", "feddtg",
    "fedgdkd",
)


def battery_config(algorithm: str, rounds: int, epochs: int, out_dir: str):
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )

    return ExperimentConfig(
        data=DataConfig(
            dataset="fake_mnist", num_clients=10,
            partition_method="hetero", partition_alpha=0.1,
            batch_size=32, seed=0,
        ),
        model=ModelConfig(
            # the battery's homogeneous client config
            # (experiment_client_configs/homogeneous_all_participating
            # .json: cnn_medium everywhere)
            name="cnn_medium", num_classes=10, input_shape=(28, 28, 1),
        ),
        # reference battery client-optimizer defaults
        # (standalone/utils/config.py:31-37: sgd, lr 0.01, wd 0.001)
        train=TrainConfig(lr=0.01, weight_decay=1e-3, epochs=epochs),
        fed=FedConfig(
            algorithm=algorithm, num_rounds=rounds,
            clients_per_round=10, eval_every=10,
        ),
        seed=0,
        run_name=algorithm,
        out_dir=out_dir,
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--algorithms", nargs="+",
                    default=list(BATTERY_ALGORITHMS))
    ap.add_argument("--reps", type=int, default=1,
                    help="seeded repetitions per algorithm "
                    "(reference battery: 5)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--out", type=str, default="runs/battery")
    args = ap.parse_args()

    from fedml_tpu.experiments.harness import ALGORITHMS, Experiment

    unknown = [a for a in args.algorithms if a not in ALGORITHMS]
    if unknown:
        raise SystemExit(
            f"unknown algorithms {unknown}; known: {sorted(ALGORITHMS)}"
        )

    os.makedirs(args.out, exist_ok=True)
    jsonl_path = os.path.join(args.out, "battery.jsonl")
    rows = []
    t_start = time.perf_counter()
    with open(jsonl_path, "w") as jf:
        for algo in args.algorithms:
            cfg = battery_config(algo, args.rounds, args.epochs, args.out)
            t0 = time.perf_counter()
            try:
                summaries = Experiment(cfg, repetitions=args.reps).run()
            except Exception as err:  # one algorithm must not sink
                print(f"[battery] {algo} FAILED: {err}", flush=True)
                jf.write(json.dumps(
                    {"algorithm": algo, "failed": str(err)}
                ) + "\n")
                jf.flush()
                rows.append((algo, 0, float("nan"), float("nan"),
                             time.perf_counter() - t0))
                continue
            wall = time.perf_counter() - t0
            for rep, s in enumerate(summaries):
                rec = {
                    "algorithm": algo, "rep": rep,
                    **{k: v for k, v in s.items()
                       if isinstance(v, (int, float, str))},
                }
                jf.write(json.dumps(rec) + "\n")
                jf.flush()
            accs = [s.get("test_acc") for s in summaries
                    if s.get("test_acc") is not None]
            mean = sum(accs) / len(accs) if accs else float("nan")
            std = (
                (sum((a - mean) ** 2 for a in accs) / len(accs)) ** 0.5
                if accs else float("nan")
            )
            # reps with a test_acc in their summary (some sims emit
            # other final metrics, e.g. online DSGD's regret)
            rows.append((algo, len(accs), mean, std, wall))
            print(
                f"[battery] {algo}: test_acc {mean:.4f} +- {std:.4f} "
                f"({len(accs)}/{len(summaries)} reps with test_acc, "
                f"{wall:.0f}s)", flush=True,
            )

    print(f"\nBattery summary ({args.reps} reps x {args.rounds} rounds, "
          f"{time.perf_counter() - t_start:.0f}s total) -> {jsonl_path}")
    print(f"{'algorithm':<14} {'reps':>4} {'test_acc':>9} {'std':>8} "
          f"{'wall_s':>7}")
    for algo, n, mean, std, wall in rows:
        print(f"{algo:<14} {n:>4} {mean:>9.4f} {std:>8.4f} {wall:>7.0f}")


if __name__ == "__main__":
    main()
