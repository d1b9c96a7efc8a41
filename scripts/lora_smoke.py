"""PEFT/LoRA smoke (ci.sh; docs/PERFORMANCE.md "Parameter-efficient
federated fine-tuning").

A CPU-only end-to-end pass over the adapter subsystem
(fedml_tpu/peft/):

1. adapter-only FedAvg on the tiny transformer NWP shape actually
   LEARNS (train loss strictly down over the run);
2. the frozen base is bitwise the init values after every round — no
   optimizer state, no delta, no drift;
3. the per-round wire bytes of the adapter+head subtree with the
   codec stacked are <= 1/50 of the full-delta payload at the SAME
   shape (the delta-size law, the ``peft.wire_ratio`` gauge);
4. the donation audit reports zero misses on the partitioned round
   program;
5. the ``peft.*`` vocabulary is live on a real ``/metrics`` scrape
   (peft_trainable_params / peft_frozen_params / peft_adapter_wire_mb
   / peft_wire_ratio).

Usage: python scripts/lora_smoke.py <workdir>
"""

from __future__ import annotations

import os
import sys
import urllib.request

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    workdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/lora_smoke"
    os.makedirs(workdir, exist_ok=True)

    import jax
    import numpy as np

    from fedml_tpu import peft as PF
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig,
        TrainConfig,
    )
    from fedml_tpu.core import telemetry
    from fedml_tpu.core.compress import CompressionSpec, wire_ratio
    from fedml_tpu.data.natural import synthetic_stackoverflow_nwp
    from fedml_tpu.models import create_model

    tdir = os.path.join(workdir, "telemetry")
    telemetry.configure(telemetry_dir=tdir, rank=0, metrics_port=0)

    vocab = 256
    data = synthetic_stackoverflow_nwp(
        num_clients=8, vocab_size=vocab, seed=0,
        sentences_low=8, sentences_high=24,
    )
    cfg = ExperimentConfig(
        data=DataConfig(dataset="stackoverflow_nwp", num_clients=8,
                        batch_size=8, seed=0),
        model=ModelConfig(
            name="transformer_lm", num_classes=vocab + 4,
            input_shape=(20,),
            extra=(("embed_dim", 32), ("max_len", 32),
                   ("num_heads", 2), ("num_layers", 1),
                   ("vocab_size", vocab + 4)),
        ),
        train=TrainConfig(lr=0.3, epochs=1),
        fed=FedConfig(num_rounds=10, clients_per_round=4,
                      eval_every=10**9, peft="lora", lora_rank=4,
                      lora_alpha=8.0,
                      lora_targets=("q_proj", "v_proj")),
        seed=0,
    )
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    # snapshot the init values from a SEPARATE deterministic init():
    # device_get on the live state would create a zero-copy host view
    # on CPU — an external reference that blocks XLA from consuming
    # the donated buffers and turns the donation audit below into a
    # false miss (the same alias class as the PR 1 checkpoint bug)
    frozen0 = sim._peft.part.frozen(
        jax.device_get(sim.init().variables["params"])
    )

    # -- 1. the adapter run learns ---------------------------------------
    losses = []
    for _ in range(cfg.fed.num_rounds):
        state, m = sim.run_round(state)
        losses.append(float(jax.device_get(m["train_loss"])))
    assert losses[-1] < losses[0] - 0.05, (
        f"adapter-only training did not learn: {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}"
    )

    # -- 2. frozen base bitwise-unchanged --------------------------------
    frozen_n = sim._peft.part.frozen(
        jax.device_get(state.variables["params"])
    )
    for a, b in zip(jax.tree.leaves(frozen0),
                    jax.tree.leaves(frozen_n)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "frozen base drifted"
        )

    # -- 3. the delta-size law at this shape -----------------------------
    params = jax.device_get(state.variables["params"])
    plan = sim._peft
    cspec = CompressionSpec(method="topk_int8", topk_frac=0.01)
    full_bytes = plan.full_wire_bytes(params)
    agg = plan.agg_part.trainable(params)
    lora_bytes = plan.adapter_wire_bytes(params) / wire_ratio(cspec,
                                                              agg)
    reduction = full_bytes / lora_bytes
    assert reduction >= 50.0, (
        f"per-round wire bytes only {reduction:.1f}x below the "
        "full-delta payload (bar: 50x)"
    )

    # -- 4. donation audit: zero misses on the partitioned round ---------
    assert telemetry.METRICS.counter("mem.donation_audits") >= 1
    misses = telemetry.METRICS.counter("mem.donation_misses")
    assert misses == 0, f"donation misses on the peft round: {misses}"

    # -- 5. peft.* vocabulary live on /metrics ---------------------------
    import json

    with open(os.path.join(tdir, "export_rank0.json")) as f:
        port = json.load(f)["port"]
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ).read().decode()
    for name in ("peft_trainable_params", "peft_frozen_params",
                 "peft_adapter_wire_mb", "peft_wire_ratio"):
        assert name in body, f"{name} missing from /metrics"

    telemetry.shutdown()
    print(
        f"lora smoke ok: loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
        "frozen base bitwise, wire reduction "
        f"{reduction:.0f}x (>= 50x bar), 0 donation misses, "
        "peft.* gauges live"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
