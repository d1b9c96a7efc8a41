"""CI smoke matrix in ONE process.

Runs the same CLI invocations ci.sh used to launch as separate
``python -m fedml_tpu.experiments.run`` processes, but through
``run.main(argv)`` in-process: the argv surface and the harness are
exercised identically while the jax/backend startup and in-process
compile caches are paid once.

Usage: python scripts/smoke_matrix.py <out_dir>
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import time

sys.path.insert(
    0, str(__import__("pathlib").Path(__file__).resolve().parent.parent)
)

from fedml_tpu.experiments import run as cli


def invoke(tag: str, argv: list[str], out_dir: str) -> None:
    t0 = time.perf_counter()
    print(f"  -- {tag}", flush=True)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    assert rc == 0, (tag, rc)
    out = buf.getvalue()
    # every smoke must emit a summary line carrying a real metric
    line = out.strip().splitlines()[0]
    rec = json.loads(line)
    assert any(
        k in rec
        for k in ("train_loss", "train_acc", "test_acc", "regret",
                  "final_regret", "test_auc")
    ), (tag, line)
    with open(os.path.join(out_dir, f"smoke_{tag}.json"), "w") as f:
        f.write(out)
    print(f"     ok ({time.perf_counter() - t0:.1f}s)", flush=True)


def fedavg_args(dataset, model, num_classes, input_shape, out_dir, tag):
    return [
        "--algorithm", "fedavg", "--dataset", dataset, "--model", model,
        "--client_num_in_total", "4", "--client_num_per_round", "2",
        "--comm_round", "2", "--epochs", "1", "--batch_size", "16",
        "--lr", "0.03", "--frequency_of_the_test", "2",
        "--num_classes", str(num_classes),
        "--input_shape", *input_shape.split(),
        "--out_dir", out_dir, "--run_name", f"smoke_{tag}",
    ]


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/fedml_smoke"
    os.makedirs(out_dir, exist_ok=True)
    for ds, model, nc, shape in [
        ("synthetic", "lr", 10, "60"),
        ("fake_mnist", "lr", 10, "28 28 1"),
        ("fake_mnist", "cnn", 10, "28 28 1"),
        ("fake_cifar10", "resnet20", 10, "32 32 3"),
        ("fake_shakespeare", "rnn", 90, "80"),
        ("fake_stackoverflow_lr", "tag_lr", 50, "1000"),
    ]:
        tag = f"fedavg_{ds}_{model}"
        invoke(tag, fedavg_args(ds, model, nc, shape, out_dir, tag),
               out_dir)

    invoke("robust", [
        "--algorithm", "fedavg_robust", "--dataset", "fake_mnist",
        "--model", "lr", "--client_num_in_total", "4",
        "--client_num_per_round", "4", "--comm_round", "2",
        "--epochs", "1", "--batch_size", "16", "--num_classes", "10",
        "--input_shape", "28", "28", "1", "--robust_method", "median",
        "--robust_norm_clip", "1.0", "--robust_noise_stddev", "0.001",
        "--out_dir", out_dir, "--run_name", "smoke_robust",
    ], out_dir)
    invoke("vfl", [
        "--algorithm", "vfl", "--dataset", "fake_vfl",
        "--comm_round", "4", "--lr", "0.1", "--batch_size", "32",
        "--frequency_of_the_test", "4",
        "--out_dir", out_dir, "--run_name", "smoke_vfl",
    ], out_dir)
    invoke("turboaggregate", [
        "--algorithm", "turboaggregate", "--dataset", "fake_mnist",
        "--model", "lr", "--client_num_in_total", "8",
        "--client_num_per_round", "4", "--comm_round", "2",
        "--num_classes", "10", "--input_shape", "28", "28", "1",
        "--frequency_of_the_test", "2",
        "--out_dir", out_dir, "--run_name", "smoke_ta",
    ], out_dir)
    invoke("dol_dsgd", [
        "--algorithm", "dol_dsgd", "--dataset", "fake_susy",
        "--client_num_in_total", "4", "--comm_round", "50",
        "--lr", "0.3", "--out_dir", out_dir, "--run_name", "smoke_dol",
    ], out_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
