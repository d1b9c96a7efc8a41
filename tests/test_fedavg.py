"""End-to-end compiled FedAvg tests, including the reference's convergence
equivalence oracle (``CI-script-fedavg.sh:45-66``): with full-batch data and
one local epoch, FedAvg over all clients == centralized full-batch SGD."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model


def small_cfg(**overrides):
    base = dict(
        data=DataConfig(
            dataset="fake_mnist", num_clients=8, batch_size=32, seed=0
        ),
        model=ModelConfig(name="lr", num_classes=10, input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=3, clients_per_round=4, eval_every=3),
        seed=0,
    )
    base.update(overrides)
    return ExperimentConfig(**base)


def test_fedavg_learns_fake_mnist():
    cfg = small_cfg(
        fed=FedConfig(num_rounds=10, clients_per_round=8, eval_every=10),
        train=TrainConfig(lr=0.1, epochs=2),
    )
    data = load_dataset(cfg.data)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    acc0 = sim.evaluate_global(state)["acc"]
    for _ in range(cfg.fed.num_rounds):
        state, _ = sim.run_round(state)
    acc1 = sim.evaluate_global(state)["acc"]
    assert acc1 > acc0 + 0.2, (acc0, acc1)


def test_equivalence_oracle_fullbatch():
    """Full-batch, e=1, all clients: FedAvg step == centralized GD step.

    This is the reference's mathematical-identity CI test
    (CI-script-fedavg.sh:45-56): averaging full-batch client updates with
    n_k weights equals one pooled full-batch gradient step.
    """
    cfg = small_cfg(
        data=DataConfig(
            dataset="fake_mnist",
            num_clients=4,
            partition_method="homo",
            full_batch=True,
            seed=1,
        ),
        train=TrainConfig(lr=0.05, epochs=1),
        fed=FedConfig(num_rounds=1, clients_per_round=4, eval_every=1),
    )
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    sim = FedAvgSim(model, data, cfg)
    state = sim.init()
    new_state, _ = sim.run_round(state)

    # centralized full-batch gradient step on the pooled data, weighted the
    # same way (sum_k n_k/N * grad_k == pooled gradient for equal-size
    # clients; use the exact per-client weighting for the general case)
    import optax

    init_vars = sim.model.init(
        jax.random.fold_in(sim.root_key, 0x7FFFFFFF)
    )

    def pooled_loss(params):
        arrays = sim.arrays
        total, wsum = 0.0, 0.0
        for c in range(data.num_clients):
            idx = arrays.idx[c]
            m = arrays.mask[c]
            x = arrays.x[idx]
            y = arrays.y[idx]
            logits = model.apply_eval({**init_vars, "params": params}, x)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            total = total + jnp.sum(ce * m)
            wsum = wsum + jnp.sum(m)
        return total / wsum

    grads = jax.grad(pooled_loss)(init_vars["params"])
    expected = jax.tree.map(
        lambda p, g: p - cfg.train.lr * g, init_vars["params"], grads
    )
    got = new_state.variables["params"]
    for e, g in zip(jax.tree.leaves(expected), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(e), np.asarray(g), atol=1e-4)


def test_cohort_sampling_reproducible():
    cfg = small_cfg()
    data = load_dataset(cfg.data)
    sim1 = FedAvgSim(create_model(cfg.model), data, cfg)
    sim2 = FedAvgSim(create_model(cfg.model), data, cfg)
    s1, _ = sim1.run_round(sim1.init())
    s2, _ = sim2.run_round(sim2.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padded_clients_noop():
    """Clients of very different sizes: padding must not distort the
    aggregate (weights are true n_k)."""
    cfg = small_cfg(
        data=DataConfig(
            dataset="fake_mnist",
            num_clients=8,
            partition_method="hetero",
            partition_alpha=0.2,
            batch_size=16,
            seed=3,
        ),
        fed=FedConfig(num_rounds=2, clients_per_round=8, eval_every=2),
    )
    data = load_dataset(cfg.data)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    state, m = sim.run_round(state)
    assert np.isfinite(float(m["train_loss"]))


@pytest.mark.parametrize("algo_cfg", [
    FedConfig(server_optimizer="adam", server_lr=0.01, num_rounds=2,
              clients_per_round=4, eval_every=2),
    FedConfig(server_optimizer="yogi", server_lr=0.01, num_rounds=2,
              clients_per_round=4, eval_every=2),
    FedConfig(algorithm="fednova", num_rounds=2, clients_per_round=4,
              eval_every=2),
    FedConfig(robust_norm_clip=1.0, robust_noise_stddev=0.001, num_rounds=2,
              clients_per_round=4, eval_every=2),
    FedConfig(robust_method="median", num_rounds=2, clients_per_round=4,
              eval_every=2),
    FedConfig(robust_method="trimmed_mean", num_rounds=2,
              clients_per_round=4, eval_every=2),
])
def test_variants_run(algo_cfg):
    cfg = small_cfg(fed=algo_cfg)
    data = load_dataset(cfg.data)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    state, m = sim.run_round(state)
    assert np.isfinite(float(m["train_loss"]))


def test_fedprox_runs():
    cfg = small_cfg(train=TrainConfig(lr=0.1, epochs=1, prox_mu=0.1))
    data = load_dataset(cfg.data)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state, m = sim.run_round(sim.init())
    assert np.isfinite(float(m["train_loss"]))


def test_bf16_compute_path_close_to_f32():
    """Mixed precision (TrainConfig.compute_dtype="bfloat16", the bench fast
    path): params/optimizer stay f32, network runs bf16. The trajectory must
    stay close to the f32 one over a few rounds, and scan_unroll must not
    change results at all."""
    states = {}
    for name, train in {
        "f32": TrainConfig(lr=0.1, epochs=1),
        "f32_unroll": TrainConfig(lr=0.1, epochs=1, scan_unroll=8),
        "bf16": TrainConfig(lr=0.1, epochs=1, compute_dtype="bfloat16"),
    }.items():
        cfg = small_cfg(
            train=train,
            fed=FedConfig(num_rounds=3, clients_per_round=4, eval_every=3),
        )
        data = load_dataset(cfg.data)
        sim = FedAvgSim(create_model(cfg.model), data, cfg)
        state = sim.init()
        for _ in range(3):
            state, _ = sim.run_round(state)
        states[name] = state

    leaves = lambda s: jax.tree.leaves(s.variables["params"])
    for a, b in zip(leaves(states["f32"]), leaves(states["f32_unroll"])):
        np.testing.assert_allclose(a, b, rtol=1e-6)  # unroll: exact
    for a, b in zip(leaves(states["f32"]), leaves(states["bf16"])):
        assert a.dtype == jnp.float32 and b.dtype == jnp.float32
        # bf16 compute: same trajectory up to bf16 resolution
        np.testing.assert_allclose(a, b, atol=0.05, rtol=0.1)


@pytest.mark.slow
def test_space_to_depth_resnet_variant():
    """The TPU-optimized _s2d ResNet layout (space-to-depth stem) trains
    and matches output shapes of the standard variant; measured ~1.5x
    faster on v5e for the bandwidth-bound CIFAR round."""
    from fedml_tpu.models import create_model

    cfg = small_cfg(
        data=DataConfig(dataset="fake_cifar10", num_clients=4,
                        batch_size=16, seed=0, dataset_r=0.05),
        model=ModelConfig(name="resnet8_s2d", num_classes=10,
                          input_shape=(16, 16, 3)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=2, clients_per_round=4, eval_every=2),
    )
    data = load_dataset(cfg.data)
    data.x_train = data.x_train[:, ::2, ::2, :]
    data.x_test = data.x_test[:, ::2, ::2, :]
    model = create_model(cfg.model)
    v = model.init(jax.random.key(0))
    out = model.apply_eval(v, jnp.zeros((2, 16, 16, 3)))
    assert out.shape == (2, 10)
    sim = FedAvgSim(model, data, cfg)
    st = sim.init()
    for _ in range(2):
        st, m = sim.run_round(st)
    assert np.isfinite(float(m["train_loss"]))


def test_cohort_groups_equal_single_group():
    """Size-sorted sub-group scheduling (TrainConfig.cohort_groups) must
    not change any client's trajectory: the aggregated state after rounds
    with cohort_groups=2 equals the single-group fused run (same equality
    class as fused-vs-vmapped; exact here because the model is BN-free)."""
    base = dict(
        data=DataConfig(
            dataset="fake_cifar10", num_clients=8, batch_size=16, seed=0,
            partition_method="hetero", partition_alpha=0.5, dataset_r=0.1,
        ),
        model=ModelConfig(
            name="cnn_custom", num_classes=10, input_shape=(32, 32, 3),
            extra=(("convs", (8,)), ("denses", (16,))),
        ),
        fed=FedConfig(num_rounds=2, clients_per_round=4, eval_every=10),
        seed=0,
    )
    states = {}
    for groups in (1, 2):
        cfg = ExperimentConfig(
            **base,
            train=TrainConfig(lr=0.05, epochs=1, cohort_groups=groups),
        )
        data = load_dataset(cfg.data)
        sim = FedAvgSim(create_model(cfg.model), data, cfg)
        assert sim._cohort_update is not None, "fused path must be active"
        assert sim._cohort_groups == groups
        st = sim.init()
        for _ in range(2):
            st, _ = sim.run_round(st)
        states[groups] = st
    a = jax.tree.leaves(states[1].variables["params"])
    b = jax.tree.leaves(states[2].variables["params"])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-6)


def test_resolve_cohort_groups_policy():
    from fedml_tpu.algorithms.fedavg import _resolve_cohort_groups

    # auto: ~5-client groups, always a divisor, >= 2 clients per group
    assert _resolve_cohort_groups(0, 10) == 2
    assert _resolve_cohort_groups(0, 2) == 1
    assert _resolve_cohort_groups(0, 3) == 1
    assert _resolve_cohort_groups(0, 100) == 20
    # explicit requests: capped at cohort//2, rounded down to a divisor
    assert _resolve_cohort_groups(5, 10) == 5
    assert _resolve_cohort_groups(10, 10) == 5
    assert _resolve_cohort_groups(7, 10) == 5
    assert _resolve_cohort_groups(4, 9) == 3
    assert _resolve_cohort_groups(1, 8) == 1
