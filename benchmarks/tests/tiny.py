"""A tiny benchmark tree for the CPU tests: the same harness, files
found by name, at sizes a test can hold (ResNet-20 with BatchNorm or GroupNorm on
16x16 inputs, a few hundred samples)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

ARCHS = {
    "tiny-bn": {"norm": "bn", "in_channels": 3, "stem": 16, "classes": 10,
                "stages": [[16, 3, 1], [32, 3, 2], [64, 3, 2]]},
    "tiny-gn": {"norm": "gn", "in_channels": 3, "stem": 16, "classes": 10,
                "stages": [[16, 3, 1], [32, 3, 2], [64, 3, 2]]},
}
# set from readings at this size (bfloat16 program against the float8
# control, seeds 1-6 / 1-3): head_grad_rel_err sound <= 0.0077, control
# >= 0.053; stats_change_rel_err sound <= 0.0067, control >= 0.029; the
# others at three times the sound runs' largest (0.0052, 0.22, 0.134)
LIMITS = {"loss_rel_gap": 0.015, "head_grad_rel_err": 0.02,
          "stats_change_rel_err": 0.014, "first_grad_norm_gap": 0.3,
          "change_norm_gap": 0.3}


def _config(name, compute_dtype, server):
    gn = name.endswith("gn")
    return {
        "source": "tests", "reference": name + ".py",
        "model": {"name": "resnet20_gn" if gn else "resnet20",
                  "num_classes": 10, "input_shape": [16, 16, 3]},
        "dataset": {"name": "tiny", "input_shape": [16, 16, 3],
                    "classes": 10, "n_train": 400, "n_test": 100},
        "train": {"optimizer": "sgd", "lr": 0.05, "epochs": 1,
                  "compute_dtype": compute_dtype},
        "batch_size": 8,
        "fed": {"algorithm": "fedavg", **server},
        "reduced": [], "correct_limits": LIMITS,
    }


def _traffic(sim="FedAvgSim", chips=1, cohort=4, population=20):
    t = {"sim": sim, "chips": chips, "population": population,
         "partition": {"law": "lda", "alpha": 0.5, "seed": 3},
         "clients_per_round": cohort, "eval_every": 2, "program_seed": 0}
    if sim == "ShardedFedAvg":
        t["mesh"] = {"clients": chips, "data": 1}
    return t


def make_tree(root, compute_dtype="float32"):
    """Write BENCHMARK.json, two configurations and two traffic mixes
    under ``root``; per-layer readers are the benchmark's own."""
    bench = os.path.join(root, "benchmarks")
    os.makedirs(os.path.join(bench, "configs"), exist_ok=True)
    os.makedirs(os.path.join(bench, "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    os.path.join(bench, "layer_metrics"), dirs_exist_ok=True)
    servers = {
        "tiny-bn": {"server_optimizer": "sgd", "server_lr": 1.0},
        "tiny-gn": {"server_optimizer": "adam", "server_lr": 0.001},
    }
    for name, arch in ARCHS.items():
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(_config(name, compute_dtype, servers[name]), f)
        with open(os.path.join(bench, "configs", name + ".py"), "w") as f:
            f.write(f"ARCH = {arch!r}\n")
    mixes = {"c4of20": _traffic(),
             "mesh4-c8of20": _traffic("ShardedFedAvg", 4, 8)}
    for name, t in mixes.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)
    real = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    cells = [
        {"name": "tiny-bn.c4of20", "config": "tiny-bn", "traffic": "c4of20",
         "chips": 1, "why": "test"},
        {"name": "tiny-gn.c4of20", "config": "tiny-gn", "traffic": "c4of20",
         "chips": 1, "why": "test"},
        {"name": "tiny-bn.mesh4", "config": "tiny-bn",
         "traffic": "mesh4-c8of20", "chips": 4, "why": "test"},
    ]

    def strip(m):
        return {k: v for k, v in m.items() if k != "workloads"}

    doc = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "tests",
                     "file": f"benchmarks/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in ARCHS],
        "workloads": cells,
        "end_to_end": [strip(m) for m in real["end_to_end"]],
        "per_layer": [strip(m) for m in real["per_layer"]],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root
