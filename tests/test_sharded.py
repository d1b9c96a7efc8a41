"""Mesh-sharded FedAvg tests on the 8-device virtual CPU mesh.

The key invariant: a shard_map-parallel round computes the SAME aggregate as
the single-device vmapped round (the reference's distributed FedAvg is, by
construction, numerically equal to its standalone sim; here we prove it)."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.core import random as R
from fedml_tpu.data.federated import build_federated_data
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model
from fedml_tpu.parallel import ShardedFedAvg, make_mesh


def stratified(n_strata):
    """Host-side mirror of the sharded runtime's per-shard sampling, so a
    single-device FedAvgSim follows the identical trajectory."""
    return lambda k, n, c: R.sample_clients_stratified(k, n, c, n_strata)


def cfg_for(mesh_cfg, **overrides):
    base = dict(
        data=DataConfig(
            dataset="fake_mnist", num_clients=16, batch_size=32, seed=0
        ),
        model=ModelConfig(name="lr", num_classes=10, input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=2, clients_per_round=8, eval_every=2),
        mesh=mesh_cfg,
        seed=0,
    )
    base.update(overrides)
    return ExperimentConfig(**base)


def test_sharded_matches_single_device():
    mesh = make_mesh(client_axis=8, data_axis=1)
    cfg = cfg_for(MeshConfig(client_axis_size=8, data_axis_size=1))
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)

    single = FedAvgSim(model, data, cfg, sampler=stratified(8))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    # the sample banks are sharded: per-device data is ~1/n_shards
    assert sharded.banks.x.shape[0] == 8
    assert sharded.banks.x.shape[1] < data.x_train.shape[0]

    s1, m1 = single.run_round(single.init())
    s2, m2 = sharded.run_round(sharded.init())

    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )
    np.testing.assert_allclose(
        float(m1["train_loss"]), float(m2["train_loss"]), rtol=1e-5
    )


def test_data_axis_matches_single_device():
    """(clients=2, data=4) mesh: intra-client gradient psum must reproduce
    the unsharded batch gradient exactly (the DDP-equivalence property)."""
    mesh = make_mesh(client_axis=2, data_axis=4)
    cfg = cfg_for(
        MeshConfig(client_axis_size=2, data_axis_size=4),
        fed=FedConfig(num_rounds=1, clients_per_round=2, eval_every=1),
        data=DataConfig(
            dataset="fake_mnist", num_clients=4, batch_size=32, seed=0
        ),
    )
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)

    single = FedAvgSim(model, data, cfg, sampler=stratified(2))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    s1, _ = single.run_round(single.init())
    s2, _ = sharded.run_round(sharded.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


@pytest.mark.parametrize("fed", [
    FedConfig(num_rounds=1, clients_per_round=8, eval_every=1,
              algorithm="fednova"),
    pytest.param(
        FedConfig(num_rounds=1, clients_per_round=8, eval_every=1,
                  robust_method="median"),
        marks=pytest.mark.slow),
    pytest.param(
        FedConfig(num_rounds=1, clients_per_round=8, eval_every=1,
                  robust_norm_clip=1.0),
        marks=pytest.mark.slow),
])
def test_sharded_variants_match(fed):
    mesh = make_mesh(client_axis=4, data_axis=1)
    cfg = cfg_for(MeshConfig(client_axis_size=4, data_axis_size=1), fed=fed)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    single = FedAvgSim(model, data, cfg, sampler=stratified(4))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    s1, _ = single.run_round(single.init())
    s2, _ = sharded.run_round(sharded.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


@pytest.mark.slow
def test_sharded_matches_single_device_batchnorm_model():
    """BatchNorm models: masked pad rows enter BN batch statistics, so the
    equality contract requires identical pad CONTENT in both layouts
    (self-padding with the client's own first sample — see
    federated._pad_index_map / shard_client_banks)."""
    mesh = make_mesh(client_axis=4, data_axis=1)
    cfg = cfg_for(
        MeshConfig(client_axis_size=4, data_axis_size=1),
        model=ModelConfig(
            name="resnet8", num_classes=10, input_shape=(16, 16, 3)
        ),
        data=DataConfig(
            dataset="fake_cifar10", num_clients=8, batch_size=16, seed=3,
            partition_method="hetero", partition_alpha=0.5, dataset_r=0.05,
        ),
        fed=FedConfig(num_rounds=1, clients_per_round=4, eval_every=1),
        # this test pins the SHARDING equality contract, so both sides
        # must run the identical (vmapped) local update — the cohort-
        # fused path is numerically equivalent but not bitwise through
        # BN stat updates (tests/test_cohort_conv.py covers that
        # equivalence separately)
        train=TrainConfig(lr=0.1, epochs=1, cohort_fused=False),
    )
    data = load_dataset(cfg.data)
    # shrink images to 16x16 to keep the CPU compile fast
    data.x_train = data.x_train[:, ::2, ::2, :]
    data.x_test = data.x_test[:, ::2, ::2, :]
    model = create_model(cfg.model)
    single = FedAvgSim(model, data, cfg, sampler=stratified(4))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    s1, _ = single.run_round(single.init())
    s2, _ = sharded.run_round(sharded.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


def test_sharded_cohort_path_matches_single_device():
    """With the cohort-grouped fast path active (BN-free conv net, sgd),
    the sharded runtime (per-shard cohort nets of C/n_shards clients)
    must match the single-device mirror (one cohort net of C clients).
    Grouping does not change per-client math, but XLA compiles the two
    group sizes differently (dense expansion reassociates reductions),
    so equality is to f32 round-off, not bitwise."""
    mesh = make_mesh(client_axis=4, data_axis=1)
    cfg = cfg_for(
        MeshConfig(client_axis_size=4, data_axis_size=1),
        model=ModelConfig(
            name="cnn_fedavg", num_classes=10, input_shape=(16, 16, 3)
        ),
        data=DataConfig(
            dataset="fake_cifar10", num_clients=8, batch_size=16, seed=5,
            partition_method="hetero", partition_alpha=0.5, dataset_r=0.05,
        ),
        fed=FedConfig(num_rounds=1, clients_per_round=8, eval_every=1),
    )
    data = load_dataset(cfg.data)
    data.x_train = data.x_train[:, ::2, ::2, :]
    data.x_test = data.x_test[:, ::2, ::2, :]
    model = create_model(cfg.model)
    single = FedAvgSim(model, data, cfg, sampler=stratified(4))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    assert single._cohort_update is not None
    assert sharded._shard_cohort_update is not None
    s1, _ = single.run_round(single.init())
    s2, _ = sharded.run_round(sharded.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3
        )


@pytest.mark.slow
def test_sharded_cohort_one_client_per_shard():
    """cohort_per_shard == 1 (clients_per_round == n_shards): the
    degenerate cohort must route through the per-client apply (stacked
    dense kernels cannot feed the base head) and still match."""
    mesh = make_mesh(client_axis=4, data_axis=1)
    cfg = cfg_for(
        MeshConfig(client_axis_size=4, data_axis_size=1),
        model=ModelConfig(
            name="cnn_fedavg", num_classes=10, input_shape=(16, 16, 3)
        ),
        data=DataConfig(
            dataset="fake_cifar10", num_clients=8, batch_size=16, seed=6,
        ),
        fed=FedConfig(num_rounds=1, clients_per_round=4, eval_every=1),
    )
    data = load_dataset(cfg.data)
    data.x_train = data.x_train[:, ::2, ::2, :]
    data.x_test = data.x_test[:, ::2, ::2, :]
    model = create_model(cfg.model)
    single = FedAvgSim(model, data, cfg, sampler=stratified(4))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    assert sharded._shard_cohort_update is not None
    s1, _ = single.run_round(single.init())
    s2, _ = sharded.run_round(sharded.init())
    for a, b in zip(
        jax.tree.leaves(s1.variables), jax.tree.leaves(s2.variables)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


# -- size-sorted groups inside a shard (TrainConfig.cohort_groups) ---------


def _grouped_job(cohort_groups):
    """2 shards x 8 sampled of 16 clients a shard, heterogeneous sizes:
    wide enough for the grouping rule to split a shard's cohort."""
    cfg = cfg_for(
        MeshConfig(client_axis_size=2, data_axis_size=1),
        model=ModelConfig(
            name="cnn_fedavg", num_classes=10, input_shape=(16, 16, 3)
        ),
        data=DataConfig(
            dataset="fake_cifar10", num_clients=32, batch_size=16, seed=5,
            partition_method="hetero", partition_alpha=0.5, dataset_r=0.2,
        ),
        train=TrainConfig(lr=0.1, epochs=1, cohort_groups=cohort_groups),
        fed=FedConfig(num_rounds=1, clients_per_round=16, eval_every=1),
    )
    data = load_dataset(cfg.data)
    data.x_train = data.x_train[:, ::2, ::2, :]
    data.x_test = data.x_test[:, ::2, ::2, :]
    return cfg, data, create_model(cfg.model)


def _sharded(cohort_groups):
    cfg, data, model = _grouped_job(cohort_groups)
    sim = ShardedFedAvg(
        model, data, cfg, make_mesh(client_axis=2, data_axis=1)
    )
    assert sim._shard_cohort_update is not None
    return sim


@pytest.fixture(scope="module")
def lockstep_round():
    """The same job with every shard in ONE lockstep group, on the mesh
    and on its single-device stratified mirror."""
    sim = _sharded(1)
    assert sim._shard_groups == 1
    s_mesh, m_mesh = sim.run_round(sim.init())
    cfg, data, model = _grouped_job(1)
    single = FedAvgSim(model, data, cfg, sampler=stratified(2))
    s_single, _ = single.run_round(single.init())
    return jax.device_get((s_mesh.variables, s_single.variables, m_mesh))


def _shard_steps(sim, groups):
    """Host mirror of round 0: per shard, the step counts of its sampled
    clients, largest first, as ``[groups, width]``."""
    key = jax.random.fold_in(R.round_key(sim.root_key, 0), 0)
    cohort = np.asarray(R.sample_clients_stratified(
        key, sim.cfg.data.num_clients, sim.cfg.fed.clients_per_round,
        sim.n_client_shards,
    ))
    sizes = np.asarray(sim.arrays.mask).sum(axis=1)[cohort]
    steps = np.ceil(sizes / sim.batch_size).astype(int)
    return [
        np.sort(s)[::-1].reshape(groups, -1)
        for s in steps.reshape(sim.n_client_shards, -1)
    ]


@pytest.mark.parametrize("cohort_groups,groups", [(0, 2), (1, 1), (2, 2),
                                                  (4, 4)])
def test_sharded_cohort_groups_are_scheduling_only(
    lockstep_round, cohort_groups, groups
):
    """A shard's cohort trained in size-sorted groups (the rule
    FedAvgSim uses, honoured on the mesh) equals the lockstep shard and
    the single-device mirror: the same clients take the same steps, only
    padded no-op steps are skipped. ``slot_steps`` counts what the
    schedule executed: per group, its largest member's steps x its
    width, over all shards."""
    v_lockstep, v_single, _ = lockstep_round
    sim = _sharded(cohort_groups)
    assert sim._shard_groups == groups
    per_shard = _shard_steps(sim, groups)
    if groups == 2:
        # the fixture must exercise the mechanism: groups of one shard
        # that stop at different step counts
        assert any(g[0, 0] != g[1, 0] for g in per_shard), per_shard
    state, m = sim.run_round(sim.init())
    for ref, atol, rtol in ((v_lockstep, 2e-6, 2e-5),
                            (v_single, 5e-4, 1e-3)):
        for a, b in zip(jax.tree.leaves(ref),
                        jax.tree.leaves(state.variables)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=atol, rtol=rtol
            )
    want = sum(int(g[:, 0].sum()) * g.shape[1] for g in per_shard)
    assert float(m["slot_steps"]) == want
    live = sum(int(g.sum()) for g in per_shard)
    assert live <= want
    if groups > 1:
        assert want < float(lockstep_round[2]["slot_steps"])


def _lower_round(sim):
    return jax.jit(lambda s, b: sim._sharded_round(s, b)).lower(
        sim.init(), sim.banks
    )


def test_sharded_grouped_round_traces_cohort_body_once(monkeypatch):
    """The groups run under ``lax.map``: however many groups a shard's
    cohort splits into, the cohort network is traced as often as for one
    lockstep group (a Python loop over groups traced it once a group)."""
    from fedml_tpu.models.base import FedModel

    calls = []
    inner = FedModel.apply_cohort_train

    def counting(self, *a, **k):
        calls.append(1)
        return inner(self, *a, **k)

    monkeypatch.setattr(FedModel, "apply_cohort_train", counting)
    counts = {}
    for cohort_groups in (1, 4):
        del calls[:]
        _lower_round(_sharded(cohort_groups))
        counts[cohort_groups] = len(calls)
    assert counts[1] >= 1
    assert counts[4] == counts[1], counts


def test_sharded_single_group_round_is_the_ungrouped_program(monkeypatch):
    """With one group a shard (``cohort_groups=1``, or a shard cohort the
    rule does not split) the round lowers to the program built without
    the grouping branch: the cohort update called straight."""
    from fedml_tpu.parallel import client_parallel as CP

    grouped = _lower_round(_sharded(1)).as_text()
    monkeypatch.setattr(
        CP, "grouped_cohort_call",
        lambda update, groups, *operands, **_: update(*operands),
    )
    assert _lower_round(_sharded(1)).as_text() == grouped
    # a shard cohort under 8 is never split by the automatic rule
    cfg, data, model = _grouped_job(0)
    cfg = cfg_for(cfg.mesh, model=cfg.model, data=cfg.data,
                  train=cfg.train,
                  fed=FedConfig(num_rounds=1, clients_per_round=12,
                                eval_every=1))
    narrow = ShardedFedAvg(model, data, cfg,
                           make_mesh(client_axis=2, data_axis=1))
    assert narrow.cohort_per_shard == 6 and narrow._shard_groups == 1


# -- evaluation on the mesh ---------------------------------------------------


@pytest.fixture(scope="module")
def eval_population():
    cfg = cfg_for(MeshConfig(client_axis_size=4, data_axis_size=1))
    return cfg, load_dataset(cfg.data), create_model(cfg.model)


def _with_test_rows(data, n):
    """``data`` with its test set cut to the first ``n`` rows."""
    return build_federated_data(
        data.x_train, data.y_train, data.x_test[:n], data.y_test[:n],
        data.num_classes, data.num_clients,
    )


@pytest.mark.parametrize("n_test", [37, 256, 1000])
@pytest.mark.parametrize("shape", [(4, 1), (2, 2)], ids=["4x1", "2x2"])
def test_mesh_evaluation_matches_single_device(
        eval_population, shape, n_test):
    """Each device evaluates its own rows of the test set and the sums
    are psummed: the same ``acc`` and ``count`` as FedAvgSim's evaluator
    on the same variables, and the same ``loss`` up to summation order —
    whether or not the set divides by devices x batch (the lr model's
    batch is 256: 37 rows pad a device's 10 to one batch, 1,000 leave
    every device 250 of 256), before and after a round."""
    cfg, data, model = eval_population
    clients, data_axis = shape
    cfg = dataclasses.replace(cfg, mesh=MeshConfig(
        client_axis_size=clients, data_axis_size=data_axis))
    data = _with_test_rows(data, n_test)
    single = FedAvgSim(model, data, cfg)
    sharded = ShardedFedAvg(
        model, data, cfg,
        make_mesh(client_axis=clients, data_axis=data_axis))
    state = sharded.init()
    for _ in range(2):
        got = sharded.evaluate_global(state)
        want = single.evaluator(
            state.variables, single.arrays.test_x, single.arrays.test_y)
        assert got["count"] == float(want["count"]) == n_test
        assert got["acc"] == float(want["acc"])
        assert got["loss"] == pytest.approx(float(want["loss"]), abs=1e-6)
        state, _ = sharded.run_round(state)


def test_mesh_evaluation_sends_nothing_from_the_host(eval_population):
    """The test set is placed once, rows over every device of the mesh
    with a weight per row, and an evaluation moves nothing from the
    host: it runs with host-to-device transfers disallowed."""
    cfg, data, model = eval_population
    mesh = make_mesh(client_axis=4, data_axis=1)
    sharded = ShardedFedAvg(model, _with_test_rows(data, 37), cfg, mesh)
    evaluator, operands = sharded._global_eval()
    assert evaluator is not sharded.evaluator
    rows = NamedSharding(mesh, P(("clients", "data")))
    for a in operands:
        assert isinstance(a, jax.Array) and a.shape[0] == 40
        assert a.sharding.is_equivalent_to(rows, a.ndim)
    np.testing.assert_array_equal(
        np.asarray(operands[2]), np.r_[np.ones(37), np.zeros(3)])
    # the global arrays stay host numpy, as _prepare_data promises
    assert isinstance(sharded.arrays.test_x, np.ndarray)
    state, _ = sharded.run_round(sharded.init())
    with jax.transfer_guard_host_to_device("disallow"):
        m = sharded.evaluate_global(state)
    assert m["count"] == 37
