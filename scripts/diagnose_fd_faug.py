"""Diagnose whether FD(+FAug)'s knowledge-exchange term does anything.

VERDICT r4 weak #2: the battery showed FD+FAug == local-only baseline
(0.240 vs 0.240 at 50 rounds), indistinguishable from a dead KD path.
This script separates "faithfully weak method" from "silent bug" with
one instrumented run at the battery's partition shape:

1. teacher tensor vs uniform: max |softmax(teacher_row) - 1/K| — a dead
   exchange would leave softmax(zeros) = exactly uniform;
2. per-label teacher coverage (has_teacher fraction);
3. loss delta on one fixed batch with the KD term on vs off;
4. final mean client accuracy across kd_gamma in {0, 0.1(default), 0.5}.

Run: JAX_PLATFORMS=cpu python scripts/diagnose_fd_faug.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.distill import FDSim
from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    GanConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model


def run(kd_gamma: float, rounds: int = 20):
    cfg = ExperimentConfig(
        # the battery shape (MNIST-like, 10 clients, hetero alpha=0.1)
        # on the fast `lr` model so the whole diagnosis runs in minutes
        data=DataConfig(dataset="fake_mnist", num_clients=10,
                        partition_method="hetero", partition_alpha=0.1,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.01, weight_decay=1e-3, epochs=5),
        fed=FedConfig(algorithm="fd_faug", num_rounds=rounds,
                      clients_per_round=10),
        gan=GanConfig(kd_gamma=kd_gamma),
        seed=0,
    )
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    sim = FDSim(model, data, cfg)
    state = sim.init()
    for _ in range(rounds):
        state, _ = sim.run_round(state)
    accs = sim.evaluate_clients(state)
    return sim, state, accs


def main():
    results = {}
    for gamma in (0.0, 0.1, 0.5):
        sim, state, accs = run(gamma)
        mean_acc = float(accs["test_acc"])
        results[gamma] = (sim, state, mean_acc)
        print(f"kd_gamma={gamma}: mean client test acc {mean_acc:.4f}",
              flush=True)

    sim, state, _ = results[0.5]
    K = state.teacher.shape[-1]
    soft = jax.nn.softmax(state.teacher, axis=-1)
    dev = jnp.abs(soft - 1.0 / K)
    print(f"teacher max |softmax - uniform| = {float(dev.max()):.4f} "
          f"(dead exchange would be 0.0)")
    print(f"teacher coverage: {float(state.has_teacher.mean()):.3f} of "
          f"(client,label) pairs have a teacher")

    # loss with the KD term on vs off, same batch, same trained model
    arrays = sim.arrays
    mvars = jax.tree.map(lambda s: s[0], state.model_stack)
    xb = arrays.x[arrays.idx[0][:32]]
    yb = arrays.y[arrays.idx[0][:32]]
    wb = arrays.mask[0][:32]
    import optax

    logits = sim.model.apply_eval(mvars, xb)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, yb)
    t_rows = state.teacher[0][yb]
    kd_ce = optax.softmax_cross_entropy(logits,
                                        jax.nn.softmax(t_rows, axis=-1))
    use = state.has_teacher[0][yb]
    for g in (0.0, 0.1, 0.5):
        gam = g * use
        loss = float(jnp.sum(((1 - gam) * ce + gam * kd_ce) * wb)
                     / jnp.maximum(jnp.sum(wb), 1.0))
        print(f"one-batch loss at gamma={g}: {loss:.5f}")
    print(f"mean |kd_ce - ce| on the batch: "
          f"{float(jnp.mean(jnp.abs(kd_ce - ce))):.5f}")


if __name__ == "__main__":
    main()
