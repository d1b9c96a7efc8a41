"""Support subsystems: FID, scheduler, MLOps logger, checkpointing, CLI."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.mlops import MLOpsLogger, SysStats
from fedml_tpu.core.scheduler import dp_schedule
from fedml_tpu.metrics.fid import (
    FIDScorer,
    activation_statistics,
    frechet_distance,
)


def test_frechet_distance_zero_for_identical():
    rng = np.random.default_rng(0)
    f = rng.normal(size=(200, 8))
    mu, s = activation_statistics(f)
    assert frechet_distance(mu, s, mu, s) < 1e-6


def test_frechet_distance_orders_distributions():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(300, 8))
    near = base + rng.normal(scale=0.1, size=base.shape)
    far = rng.normal(loc=3.0, size=(300, 8))
    mu0, s0 = activation_statistics(base)
    mu1, s1 = activation_statistics(near)
    mu2, s2 = activation_statistics(far)
    d_near = frechet_distance(mu0, s0, mu1, s1)
    d_far = frechet_distance(mu0, s0, mu2, s2)
    assert d_near < d_far


def test_fid_scorer_end_to_end():
    rng = np.random.default_rng(0)
    real = rng.normal(size=(64, 16, 16, 1)).astype(np.float32)
    fake_close = real + 0.05 * rng.normal(size=real.shape).astype(np.float32)
    fake_far = rng.uniform(-1, 1, real.shape).astype(np.float32)
    scorer = FIDScorer()
    assert scorer.calculate_fid(real, fake_close) < scorer.calculate_fid(
        real, fake_far
    )


def test_scheduler_serial_balances_makespan():
    out = dp_schedule([10, 8, 6, 4, 2], speeds=[1.0, 1.0],
                      memory=[100, 100], mode="serial")
    assert out is not None
    assert out.mapping.shape == (5,)
    # optimal split: {10, 6} vs {8, 4, 2} -> makespan 16 (or symmetric)
    assert out.makespan <= 16.0 + 1e-9
    # cost bookkeeping consistent
    for r in range(2):
        expect = sum(
            w for w, m in zip([10, 8, 6, 4, 2], out.mapping) if m == r
        )
        assert abs(out.costs[r] - expect) < 1e-9


def test_scheduler_memory_infeasible():
    assert dp_schedule([10], speeds=[1.0], memory=[5]) is None


def test_scheduler_heterogeneous_speeds():
    out = dp_schedule([4, 4], speeds=[1.0, 10.0], memory=[100, 100])
    # everything should land on the fast resource (cost 8 < 40)
    assert (out.mapping == 0).all()


def test_mlops_logger_and_sysstats(tmp_path):
    path = str(tmp_path / "mlops.jsonl")
    log = MLOpsLogger(jsonl_path=path)
    log.set_context("run1", edge_id=3)
    log.report_client_training_status(3, "TRAINING")
    log.report_training_progress(0, {"acc": 0.5})
    stats = SysStats().sample()
    assert "cpu_utilization" in stats
    log.report_system_metric(stats)
    log.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 3
    assert lines[0]["status"] == "TRAINING"
    assert lines[1]["round"] == 0


def test_round_checkpointer_roundtrip(tmp_path):
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgSim, ServerState
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.checkpoint import RoundCheckpointer

    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic_1_1", num_clients=6,
                        batch_size=16),
        model=ModelConfig(name="lr", num_classes=10, input_shape=(60,)),
        train=TrainConfig(lr=0.05, epochs=1),
        fed=FedConfig(num_rounds=3, clients_per_round=3),
        seed=0,
    )
    sim = FedAvgSim(create_model(cfg.model), load_dataset(cfg.data), cfg)
    state = sim.init()
    ckpt = RoundCheckpointer(str(tmp_path / "ckpt"))
    restored, start = ckpt.restore_or(state)
    assert start == 0
    state, _ = sim.run_round(state)
    ckpt.save(0, state)
    state, _ = sim.run_round(state)
    ckpt.save(1, state)
    # resume: fresh init, restore -> equals round-2 state
    state2, start2 = ckpt.restore_or(sim.init())
    assert start2 == 2
    for a, b in zip(
        __import__("jax").tree.leaves(state.variables),
        __import__("jax").tree.leaves(state2.variables),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert int(state2.round) == int(state.round)
    ckpt.close()


def test_experiment_harness_and_cli(tmp_path):
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )
    from fedml_tpu.experiments import Experiment

    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic_1_1", num_clients=6,
                        batch_size=16),
        model=ModelConfig(name="lr", num_classes=10, input_shape=(60,)),
        train=TrainConfig(lr=0.05, epochs=1),
        fed=FedConfig(algorithm="fedavg", num_rounds=2,
                      clients_per_round=3, eval_every=2),
        out_dir=str(tmp_path),
        run_name="t",
    )
    summaries = Experiment(cfg, repetitions=2).run()
    assert len(summaries) == 2
    assert "train_loss" in summaries[0]
    assert os.path.exists(tmp_path / "t_rep0" / "metrics.jsonl")
    assert os.path.exists(tmp_path / "t_rep0" / "config.json")


def test_cli_parse_args():
    from fedml_tpu.experiments.run import parse_args

    cfg, args = parse_args([
        "--algorithm", "fedavg", "--dataset", "synthetic_1_1",
        "--model", "lr", "--num_classes", "10", "--input_shape", "60",
        "--comm_round", "3", "--client_num_in_total", "5",
        "--client_num_per_round", "2", "--lr", "0.1",
        "--repetitions", "2",
    ])
    assert cfg.fed.algorithm == "fedavg"
    assert cfg.fed.num_rounds == 3
    assert cfg.data.num_clients == 5
    assert cfg.model.input_shape == (60,)
    assert cfg.train.lr == 0.1
    assert args.repetitions == 2
    assert args.role is None  # no --role => local simulator path


def test_per_client_observability_sink():
    """Per-client Acc/Loss + confusion matrices + label distributions land
    in the sink with reference-shaped keys (parity with
    HeterogeneousModelBaseTrainerAPI._local_test_on_all_clients)."""
    import jax

    from fedml_tpu.config import DataConfig, ModelConfig
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.metrics.observability import (
        build_per_client_eval,
        label_distribution,
        log_per_client_observability,
    )
    from fedml_tpu.metrics.sink import MetricsSink
    from fedml_tpu.models import create_model

    data = load_dataset(
        DataConfig(dataset="fake_mnist", num_clients=3, batch_size=16,
                   seed=0)
    )
    arrays = data.to_arrays(pad_multiple=16)
    model = create_model(
        ModelConfig(name="lr", num_classes=10, input_shape=(28, 28, 1))
    )
    variables = model.init(jax.random.key(0))
    sink = MetricsSink()
    rec = log_per_client_observability(sink, model, variables, arrays, 0)
    for i in range(3):
        assert f"Client {i}/Test/Acc" in rec
        assert f"Client {i}/Train/Loss" in rec
    assert "Train/Acc" in rec and "Test/Acc" in rec
    cm = np.asarray(rec["confusion_test"])
    assert cm.shape == (3, 10, 10)
    # confusion rows sum to the per-client true test counts
    ev = build_per_client_eval(model, 10)
    test = ev(variables, arrays.test_x, arrays.test_y, arrays.test_idx,
              arrays.test_mask)
    np.testing.assert_allclose(cm.sum(axis=(1, 2)),
                               np.asarray(test["count"]), rtol=1e-6)
    ld = np.asarray(rec["label_distribution"])
    assert ld.shape == (3, 10)
    # label counts match the true per-client partition sizes
    np.testing.assert_allclose(
        ld.sum(1),
        [len(data.train_idx_map[i]) for i in range(3)],
    )
    # stacked (personalized) variables path
    stack = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (3,) + l.shape), variables
    )
    ev_s = build_per_client_eval(model, 10, stacked=True)
    out = ev_s(stack, arrays.test_x, arrays.test_y, arrays.test_idx,
               arrays.test_mask)
    np.testing.assert_allclose(np.asarray(out["acc"]),
                               np.asarray(test["acc"]), rtol=1e-6)


def test_mlops_packaging_bundles(tmp_path):
    """build-mlops-package equivalent: client/server zips with
    package/main.py + conf (reference build.sh dist layout)."""
    import zipfile

    from fedml_tpu.config import ExperimentConfig
    from fedml_tpu.mlops import build_mlops_packages

    out = build_mlops_packages(
        ExperimentConfig(), str(tmp_path), world_size=3,
        backend="GRPC", ip_config={0: ("127.0.0.1", 9000)},
    )
    for side in ("client", "server"):
        assert os.path.exists(out[side])
        names = zipfile.ZipFile(out[side]).namelist()
        assert f"fedml-{side}/package/main.py" in names
        assert f"fedml-{side}/package/conf/fedml.json" in names
        src = zipfile.ZipFile(out[side]).read(
            f"fedml-{side}/package/main.py"
        ).decode()
        compile(src, "main.py", "exec")  # entry script is valid python
        conf = json.loads(zipfile.ZipFile(out[side]).read(
            f"fedml-{side}/package/conf/fedml.json"))
        assert conf["world_size"] == 3


def test_mobile_weight_lists_roundtrip(tmp_path):
    """is_mobile JSON weight lists (reference distributed/fedavg/utils.py
    transform_tensor_to_list / transform_list_to_tensor)."""
    import jax

    from fedml_tpu.config import ModelConfig
    from fedml_tpu.mobile import (
        load_weight_lists,
        params_to_weight_lists,
        save_weight_lists,
    )
    from fedml_tpu.models import create_model

    model = create_model(
        ModelConfig(name="lr", num_classes=10, input_shape=(8,))
    )
    variables = model.init(jax.random.key(0))
    payload = params_to_weight_lists(variables)
    assert len(payload["weights"]) == len(jax.tree.leaves(variables))
    p = tmp_path / "w.json"
    save_weight_lists(variables, str(p))
    restored = load_weight_lists(variables, str(p))
    for a, b in zip(jax.tree.leaves(variables), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_tensor_rpc_transport_and_benchmark():
    from fedml_tpu.core.manager import create_transport
    from fedml_tpu.core.transport.tensor_rpc import benchmark_transport

    ip = {0: ("127.0.0.1", 29741), 1: ("127.0.0.1", 29742)}
    a = create_transport("trpc", 0, ip_config=ip)
    b = create_transport("trpc", 1, ip_config=ip)
    a.start()
    b.start()
    res = benchmark_transport(a, b, sizes=(1000, 100000), repeats=2)
    assert len(res) == 2
    assert res[0]["size_bytes"] == 4000
    assert all(r["mean_ms"] > 0 for r in res)
    a.stop()
    b.stop()


def test_mlops_logger_over_pubsub_bus():
    """Transport-backed status channel (reference MLOpsLogger -> MQTT
    status topics): records arrive at bus subscribers as JSON."""
    from fedml_tpu.core.mlops import (
        TOPIC_CLIENT_STATUS,
        TOPIC_TRAINING_PROGRESS,
        MLOpsLogger,
    )
    from fedml_tpu.core.transport.pubsub import TopicBus

    bus = TopicBus()
    got = []
    bus.subscribe(TOPIC_CLIENT_STATUS, lambda t, p: got.append((t, p)))
    bus.subscribe(TOPIC_TRAINING_PROGRESS, lambda t, p: got.append((t, p)))
    logger = MLOpsLogger.over_bus(bus)
    logger.set_context("run42", edge_id=3)
    logger.report_client_training_status(3, "TRAINING")
    logger.report_training_progress(7, {"acc": 0.9})
    assert len(got) == 2
    rec = json.loads(got[0][1])
    assert rec["status"] == "TRAINING" and rec["run_id"] == "run42"
    rec2 = json.loads(got[1][1])
    assert rec2["round"] == 7 and rec2["acc"] == 0.9


def test_tensor_rpc_tensor_first_framing_roundtrip():
    """TensorRpcTransport's tensor-first wire format must round-trip mixed
    payloads exactly (bulk arrays via the native codec region, scalars and
    exotic dtypes via the meta pickle)."""
    from fedml_tpu.core.manager import create_transport
    from fedml_tpu.core.message import Message

    ip = {0: ("127.0.0.1", 29745), 1: ("127.0.0.1", 29746)}
    a = create_transport("trpc", 0, ip_config=ip)
    b = create_transport("trpc", 1, ip_config=ip)
    a.start()
    b.start()
    try:
        payload = {
            "big": np.arange(5000, dtype=np.float32).reshape(50, 100),
            "ints": np.arange(512, dtype=np.int32),
            "tiny": np.ones((3,), np.float32),  # < 256B: pickle side
            "bf16": np.ones((300,), np.float16),
            "scalar": 7,
            "nested": {"s": "hello", "v": np.full((99,), 2.5, np.float64)},
        }
        a.send_message(Message(11, 0, 1, dict(payload)))
        got = b._inbox.get(timeout=30)
        assert got.msg_type == 11 and got.sender == 0
        np.testing.assert_array_equal(got.get("big"), payload["big"])
        np.testing.assert_array_equal(got.get("ints"), payload["ints"])
        np.testing.assert_array_equal(got.get("tiny"), payload["tiny"])
        np.testing.assert_array_equal(got.get("bf16"), payload["bf16"])
        assert got.get("scalar") == 7
        assert got.payload["nested"]["s"] == "hello"
        np.testing.assert_array_equal(
            got.payload["nested"]["v"], payload["nested"]["v"]
        )
        assert got.get("big").flags.writeable
    finally:
        a.stop()
        b.stop()


def test_checkpoint_scope_migration(tmp_path):
    """Checkpoints written by pre-Conv2D builds (flax auto-scopes Conv_N /
    Dense_N) restore into current trees (Conv2D_N / named heads) via the
    scope-migration shim."""
    from fedml_tpu.utils.checkpoint import _migrate_scopes

    template = {
        "params": {
            "Conv2D_0": {"kernel": np.zeros((3, 3, 3, 8))},
            "ConvTranspose2D_0": {"kernel": np.zeros((3, 3, 8, 8))},
            "head": {"kernel": np.zeros((8, 10)), "bias": np.zeros((10,))},
        }
    }
    legacy = {
        "params": {
            "Conv_0": {"kernel": np.ones((3, 3, 3, 8))},
            "ConvTranspose_0": {"kernel": np.full((3, 3, 8, 8), 2.0)},
            "Dense_0": {"kernel": np.full((8, 10), 3.0),
                        "bias": np.full((10,), 4.0)},
        }
    }
    out = _migrate_scopes(template, legacy)
    assert out["params"]["Conv2D_0"]["kernel"][0, 0, 0, 0] == 1.0
    assert out["params"]["ConvTranspose2D_0"]["kernel"][0, 0, 0, 0] == 2.0
    assert out["params"]["head"]["bias"][0] == 4.0
    # unmatched scope -> loud failure, not silent zeros
    import pytest

    with pytest.raises(KeyError):
        _migrate_scopes(
            {"params": {"other": {"kernel": np.zeros((5, 5))}}},
            legacy,
        )


def test_conv2d_padding_forms():
    """Conv2D accepts nn.Conv's int / per-dim-int padding forms and
    rejects CIRCULAR with a clear error."""
    import jax
    import jax.numpy as jnp
    import pytest

    from fedml_tpu.ops.cohort_conv import Conv2D

    x = jnp.ones((1, 8, 8, 3))
    for pad, hw in [(1, 8), ((2, 1), (10, 8)), ("VALID", 6),
                    (((1, 1), (1, 1)), 8)]:
        m = Conv2D(4, (3, 3), padding=pad)
        y = m.apply(m.init(jax.random.key(0), x), x)
        want = hw if isinstance(hw, tuple) else (hw, hw)
        assert y.shape[1:3] == want, (pad, y.shape)
    m = Conv2D(4, (3, 3), padding="CIRCULAR")
    with pytest.raises(ValueError, match="CIRCULAR"):
        m.init(jax.random.key(0), x)


def test_mobile_graph_conversion_roundtrip(tmp_path):
    """MNN-style graph conversion (reference mnn_torch.py): flax LeNet ->
    JSON graph description -> pure-numpy runtime reproduces the flax
    logits; the inverse walk re-enters flax variables exactly."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.mobile.graph import (
        NumpyGraphRunner,
        export_lenet_graph,
        import_lenet_variables,
        load_graph,
        save_graph,
    )
    from fedml_tpu.models.vision_extra import LeNet

    model = LeNet(num_classes=10)
    x = np.asarray(
        jax.random.normal(jax.random.key(0), (4, 28, 28, 1)), np.float32
    )
    variables = model.init(jax.random.key(1), jnp.asarray(x))
    want = np.asarray(model.apply(variables, jnp.asarray(x)))

    graph = export_lenet_graph(variables)
    p = tmp_path / "lenet.graph.json"
    save_graph(graph, str(p))
    runner = NumpyGraphRunner(load_graph(str(p)))
    got = runner(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    back = import_lenet_variables(load_graph(str(p)), variables)
    for a, b in zip(
        jax.tree.leaves(variables), jax.tree.leaves({"params": back["params"]})
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_fid_trained_embed_reproducible_across_processes(tmp_path):
    """The trained-CNN FID embed must give IDENTICAL scores in two fresh
    processes on the same data (verdict: random-projection FID was not
    comparable across runs/machines; the trained embed is deterministic:
    fixed seed, fixed batch order)."""
    import subprocess
    import sys

    script = tmp_path / "fid_run.py"
    script.write_text(
        """
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from fedml_tpu.metrics.fid import make_fid_scorer
rng = np.random.default_rng(7)
x = rng.normal(0.5, 0.2, (96, 8, 8, 1)).astype(np.float32)
y = rng.integers(0, 4, 96)
fake = rng.normal(0.4, 0.3, (64, 8, 8, 1)).astype(np.float32)
scorer = make_fid_scorer(train_data=(x, y), num_classes=4)
print(repr(scorer.calculate_fid(x, fake)))
"""
    )
    import os
    from pathlib import Path

    from fedml_tpu.core.compile_cache import CACHE_DIR

    repo = str(Path(__file__).resolve().parent.parent)
    env = dict(
        os.environ,
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    # warm XLA cache: the two child processes would otherwise pay
    # cold jits, busting the fast tier's budget
    env.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, str(script)], capture_output=True,
            text=True, cwd=repo, env=env, timeout=240,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1], outs
    assert float(outs[0]) > 0


def test_gan_round_logging_grid_and_fid(tmp_path):
    """log_gan_round writes a sink record carrying per-round FID and a
    saved sample-grid artifact (reference fedgdkd/server.py:140-165)."""
    from fedml_tpu.metrics.fid import log_gan_round, sample_grid
    from fedml_tpu.metrics.sink import MetricsSink

    rng = np.random.default_rng(0)

    class FakeArrays:
        test_x = rng.normal(0.5, 0.2, (128, 8, 8, 1)).astype(np.float32)

    class FakeSim:
        arrays = FakeArrays()

        def sample_images(self, state, n, seed=0):
            r = np.random.default_rng(seed)
            return r.normal(0.4, 0.3, (n, 8, 8, 1)).astype(np.float32)

    sink = MetricsSink(path=str(tmp_path / "runs" / "gan.jsonl"))
    rec = log_gan_round(sink, FakeSim(), None, round_idx=3)
    assert rec["fid"] > 0 and rec["round"] == 3
    grid = np.load(rec["sample_grid"])
    assert grid.shape == (64, 64, 1)  # 8x8 tiles of 8x8 images
    assert sink.history[-1]["fid"] == rec["fid"]
    # grid tiling is lossless for the first tile
    imgs = FakeSim().sample_images(None, 64, seed=3)
    np.testing.assert_array_equal(
        sample_grid(imgs)[:8, :8], imgs[0]
    )


def test_experiment_checkpoint_resume(tmp_path):
    """checkpoint_every wires RoundCheckpointer into the harness: a
    restarted run resumes from the latest saved round instead of round 0
    (reference has no framework checkpointing; SURVEY.md 5.4 upgrade)."""
    import dataclasses

    from fedml_tpu.config import (
        DataConfig,
        ExperimentConfig,
        FedConfig,
        ModelConfig,
        TrainConfig,
    )
    from fedml_tpu.experiments.harness import Experiment

    def cfg(rounds):
        return ExperimentConfig(
            data=DataConfig(dataset="fake_mnist", num_clients=4,
                            batch_size=16, seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=1),
            fed=FedConfig(num_rounds=rounds, clients_per_round=4,
                          eval_every=100),
            seed=0,
            run_name="ckpt_run",
            out_dir=str(tmp_path),
            checkpoint_every=2,
        )

    # phase 1: 4 rounds, checkpoints at rounds 1 and 3
    Experiment(cfg(4)).run()
    # phase 2: "restart" asking for 8 rounds -> resumes at round 4
    summaries = Experiment(cfg(8)).run()
    assert summaries

    import json

    with open(tmp_path / "ckpt_run_rep0" / "metrics.jsonl") as f:
        records = [json.loads(l) for l in f if l.strip()]
    rounds = [r["round"] for r in records if "round" in r]
    # phase 1 logged 0..3; phase 2 must continue at 4 (no repeats of
    # 0..3) and announce where it resumed
    assert any(r.get("resumed_from") == 4 for r in records)
    assert rounds[:4] == [0, 1, 2, 3]
    assert rounds[4:] == [4, 5, 6, 7], rounds
