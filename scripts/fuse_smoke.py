"""Round-fusion CPU smoke (ci.sh): a tiny sim at ``--fuse_rounds 4``
must (a) reproduce the unfused run's final loss, (b) compile ONE block
program per (bucket, K) — churn-free blocks after the first are
compile-cache hits, (c) log a stacked metrics row for EVERY round (a
fused block must never swallow its non-boundary rounds' records), and
(d) keep eval on the exact boundary rounds even though
``eval_every % K != 0`` (docs/PERFORMANCE.md "Round fusion").

Run: ``JAX_PLATFORMS=cpu python scripts/fuse_smoke.py``
"""

from __future__ import annotations

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    import jax
    import numpy as np

    from fedml_tpu.config import (
        DataConfig,
        ExperimentConfig,
        FedConfig,
        ModelConfig,
        TrainConfig,
    )
    from fedml_tpu.core import telemetry
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    ROUNDS, FUSE = 8, 4

    def cfg(fuse: int) -> ExperimentConfig:
        return ExperimentConfig(
            data=DataConfig(dataset="fake_mnist", num_clients=8,
                            batch_size=32, seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=1),
            # eval_every=3 does NOT divide K=4: blocks must shorten to
            # flush exactly on rounds 2, 5, 7
            fed=FedConfig(num_rounds=ROUNDS, clients_per_round=4,
                          eval_every=3, fuse_rounds=fuse,
                          elastic_buckets=True),
            seed=0,
        )

    class Sink:
        def __init__(self):
            self.rows = []

        def log(self, row):
            self.rows.append(row)

    telemetry.METRICS.enabled = True

    c_unfused = cfg(1)
    data = load_dataset(c_unfused.data)
    model = create_model(c_unfused.model)
    s_unf = Sink()
    FedAvgSim(model, data, c_unfused).run(metrics_sink=s_unf)

    before = telemetry.METRICS.snapshot()["counters"]
    s_fused = Sink()
    FedAvgSim(model, data, cfg(FUSE)).run(metrics_sink=s_fused)
    after = telemetry.METRICS.snapshot()["counters"]

    # (c) one stacked-metrics row per round, evals on the boundary
    rounds = [r["round"] for r in s_fused.rows]
    assert rounds == list(range(ROUNDS)), rounds
    evals = [r["round"] for r in s_fused.rows if "test_acc" in r]
    assert evals == [2, 5, 7], evals

    # (a) parity with the unfused run (scan reassociation band only)
    unf = {r["round"]: r for r in s_unf.rows}
    for row in s_fused.rows:
        np.testing.assert_allclose(
            row["train_loss"], unf[row["round"]]["train_loss"],
            rtol=1e-5, atol=1e-6,
        )
    final_f = s_fused.rows[-1]
    final_u = unf[ROUNDS - 1]
    np.testing.assert_allclose(final_f["test_loss"],
                               final_u["test_loss"],
                               rtol=1e-5, atol=1e-6)

    # (b) one compile per (bucket, K): the eval cadence cuts the 8
    # rounds into blocks of lengths (3, 3, 2) over ONE bucket ->
    # exactly 2 distinct block programs compile and the repeated
    # length-3 block is a cache hit
    misses = after.get("elastic.compile_cache_misses", 0) - before.get(
        "elastic.compile_cache_misses", 0
    )
    hits = after.get("elastic.compile_cache_hits", 0) - before.get(
        "elastic.compile_cache_hits", 0
    )
    assert misses == 2, (misses, hits)
    assert hits == 1, (misses, hits)

    print(
        f"fuse smoke ok: {ROUNDS} rounds at K={FUSE}, final loss "
        f"{final_f['test_loss']:.4f} == unfused {final_u['test_loss']:.4f}"
        f", {misses} block compiles / {hits} cache hits, evals at "
        f"{evals}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
