"""Process-SEPARATED deployment tests: N OS processes over real sockets
must reproduce the compiled simulator bit-for-bit-ish (float round-off).

This is the parity leg the reference exercises with ``mpirun -np N``
(``run_fedavg_distributed_pytorch.sh``) and the cross-silo
``run_server.sh``/``run_client.sh`` launchers: until two or more OS
processes complete a federated round over a socket, the actor runtime is
a library, not a system. Every test here spawns real subprocesses via
the public CLI (``python -m fedml_tpu.experiments.run --role ...``).
"""

import json
import os
import pickle
import socket
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _subproc_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # deterministic vs the in-test sim (CPU)
    # conftest.py pins threefry_partitionable=True for the in-test sims;
    # the subprocess ranks must derive the SAME rng stream or the
    # cross-process equality pins compare different initializations
    env["JAX_THREEFRY_PARTITIONABLE"] = "1"
    # (the ranks place their compile cache themselves: run.main calls
    # fedml_tpu.core.compile_cache.enable_compile_cache)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _cfg_dict(tmp_path, algorithm, num_clients, rounds, model="lr"):
    return {
        "data": {"dataset": "fake_mnist", "num_clients": num_clients,
                 "batch_size": 32, "partition_method": "homo", "seed": 0},
        "model": {"name": model, "num_classes": 10,
                  "input_shape": [28, 28, 1]},
        "train": {"lr": 0.1, "epochs": 1},
        "fed": {"algorithm": algorithm, "num_rounds": rounds,
                "clients_per_round": num_clients, "eval_every": rounds},
        "seed": 0,
        "run_name": "deploy",
        "out_dir": str(tmp_path),
    }


def _spawn_world(tmp_path, cfg, world, backend, extra=()):
    """Launch 1 server + world-1 clients through the CLI; returns the
    server's parsed stdout JSON. Fails loudly with all logs on error."""
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    args = ["--config", str(cfg_path), "--backend", backend,
            "--world_size", str(world), "--ready_timeout", "60", *extra]
    if backend in ("tcp", "grpc", "trpc"):
        ports = _free_ports(world)
        ip_path = tmp_path / "ip.json"
        ip_path.write_text(json.dumps(
            {str(r): ["127.0.0.1", ports[r]] for r in range(world)}
        ))
        args += ["--ip_config", str(ip_path)]
    env = _subproc_env()
    procs = []
    for r in range(1, world):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fedml_tpu.experiments.run", *args,
             "--role", "client", "--rank", str(r)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    server = subprocess.Popen(
        [sys.executable, "-m", "fedml_tpu.experiments.run", *args,
         "--role", "server"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        s_out, s_err = server.communicate(timeout=300)
        # longer than the clients' --ready_timeout (60 s): a server
        # failure must surface as the AssertionError below WITH the
        # captured logs, not as an opaque TimeoutExpired here
        outs = [p.communicate(timeout=120)[0] for p in procs]
    except subprocess.TimeoutExpired:
        server.kill()
        for p in procs:
            p.kill()
        raise
    if server.returncode != 0 or any(p.returncode != 0 for p in procs):
        raise AssertionError(
            f"server rc={server.returncode}\n--- server stdout\n{s_out}\n"
            f"--- server stderr\n{s_err}\n--- clients\n" + "\n".join(outs)
        )
    return json.loads(s_out.strip().splitlines()[-1])


def _assert_close(a, b, rtol=1e-5, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def _fedavg_sim_final(cfg_d):
    """The compiled-sim ground truth, recomputed in-process on CPU (same
    derivation as test_runtime.test_distributed_fedavg_loopback_matches_sim)."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms.base import build_local_update, make_task
    from fedml_tpu.config import ExperimentConfig
    from fedml_tpu.core import tree as T
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    cfg = ExperimentConfig.from_dict(cfg_d)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    arrays = data.to_arrays(pad_multiple=cfg.data.batch_size)
    task = make_task(data.task)
    lu = jax.jit(build_local_update(
        model, task, cfg.train,
        min(cfg.data.batch_size, arrays.max_client_samples),
        arrays.max_client_samples,
    ))
    variables = model.init(jax.random.key(cfg.seed))
    root = jax.random.key(cfg.seed)
    n_clients = cfg.data.num_clients
    for rnd in range(cfg.fed.num_rounds):
        outs, ns = [], []
        for c in range(n_clients):
            rng = jax.random.fold_in(jax.random.fold_in(root, rnd), c)
            v, n, _ = lu(variables, arrays.idx[c], arrays.mask[c],
                         arrays.x, arrays.y, rng)
            outs.append(v)
            ns.append(float(n))
        variables = T.tree_weighted_mean(
            T.tree_stack(outs), jnp.asarray(ns)
        )
    return variables


def test_cross_process_fedavg_grpc_matches_sim(tmp_path):
    """CI mini-run (2 OS processes, server + 1 client over gRPC on
    localhost): final global weights == compiled sim to round-off.
    Runs with --telemetry_dir, which must not perturb the math AND must
    produce per-rank span dumps that scripts/merge_trace.py folds into
    one Chrome trace where a server->client message's send and deliver
    share a trace id, plus nonzero transport counters
    (docs/OBSERVABILITY.md acceptance pin)."""
    tdir = tmp_path / "telemetry"
    cfg_d = _cfg_dict(tmp_path, "fedavg", num_clients=1, rounds=2)
    summary = _spawn_world(tmp_path, cfg_d, world=2, backend="grpc",
                           extra=("--telemetry_dir", str(tdir)))
    assert summary["rounds"] == 2
    with open(summary["final_params"], "rb") as f:
        got = pickle.load(f)
    _assert_close(got, _fedavg_sim_final(cfg_d))
    assert 0.0 <= summary["acc"] <= 1.0  # server-side global eval ran

    # per-rank artifacts from both OS processes
    for r in (0, 1):
        assert (tdir / f"trace_rank{r}.json").exists()
        metrics = json.loads((tdir / f"metrics_rank{r}.json").read_text())
        c = metrics["counters"]
        assert c["transport.messages_sent"] > 0
        assert c["transport.bytes_sent"] > 0
        assert c["transport.bytes_received"] > 0
    out = tdir / "merged.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "merge_trace.py"),
         str(tdir), "--out", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr
    merged = json.loads(out.read_text())
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs if e.get("ph") != "M"}
    assert {0, 1} <= pids
    sends = {e["args"]["span_id"]: e for e in evs
             if e.get("name") == "msg_send" and e["pid"] == 0}
    delivers = {e["args"]["span_id"]: e for e in evs
                if e.get("name") == "msg_deliver" and e["pid"] == 1}
    shared = [
        s for s in sends if s in delivers
        and sends[s]["args"]["trace_id"] == delivers[s]["args"]["trace_id"]
    ]
    assert shared, "no server->client send/deliver pair shares a trace id"


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["tcp", "trpc"])
def test_cross_process_fedavg_3proc_matches_sim(tmp_path, backend):
    """1 server + 2 clients as separate OS processes over raw TCP and
    the tensor-native RPC framing."""
    cfg_d = _cfg_dict(tmp_path, "fedavg", num_clients=2, rounds=2)
    summary = _spawn_world(tmp_path, cfg_d, world=3, backend=backend)
    assert summary["rounds"] == 2
    with open(summary["final_params"], "rb") as f:
        got = pickle.load(f)
    _assert_close(got, _fedavg_sim_final(cfg_d))


@pytest.mark.slow
def test_cross_process_fedopt_adam_grpc(tmp_path):
    """The server-optimizer family deploys too: FedOpt(adam) across OS
    processes must match an in-process actor run over loopback (the
    loopback actors are themselves pinned to the compiled sim's
    server_update, so this transitively pins the full chain)."""
    import jax.numpy as jnp
    import threading

    from fedml_tpu.algorithms.distributed_fedavg import (
        FedAvgClientActor,
        FedAvgServerActor,
    )
    from fedml_tpu.config import ExperimentConfig
    from fedml_tpu.core.transport.loopback import LoopbackHub
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    cfg_d = _cfg_dict(tmp_path, "fedopt", num_clients=2, rounds=2)
    cfg_d["fed"]["server_optimizer"] = "adam"
    cfg_d["fed"]["server_lr"] = 1e-2
    summary = _spawn_world(tmp_path, cfg_d, world=3, backend="grpc")
    assert summary["rounds"] == 2
    with open(summary["final_params"], "rb") as f:
        got = pickle.load(f)

    cfg = ExperimentConfig.from_dict(cfg_d)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(3, hub.create(0), model, cfg,
                               num_clients=2, data=data)
    clients = [FedAvgClientActor(r, 3, hub.create(r), model, data, cfg)
               for r in (1, 2)]
    threads = [threading.Thread(target=c.run, daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    server.start_round()
    server.run()
    assert server.done.wait(timeout=30)
    for t in threads:
        t.join(timeout=10)
    _assert_close(got, jax.tree.map(lambda v: v, server.variables))


@pytest.mark.slow
def test_cross_process_fedavg_pubsub_blob_broker(tmp_path):
    """MQTT+S3-shaped deployment across OS processes: control plane
    through the TCP broker DAEMON (separate process), bulk model params
    through the file-backed blob store."""
    broker_port = _free_ports(1)[0]
    broker = subprocess.Popen(
        [sys.executable, "-m", "fedml_tpu.core.transport.broker",
         "--port", str(broker_port)],
        env=_subproc_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        blob_dir = tmp_path / "blobs"
        blob_dir.mkdir()
        cfg_d = _cfg_dict(tmp_path, "fedavg", num_clients=2, rounds=2)
        summary = _spawn_world(
            tmp_path, cfg_d, world=3, backend="pubsub_blob",
            extra=("--broker", f"127.0.0.1:{broker_port}",
                   "--blob_dir", str(blob_dir)),
        )
        assert summary["rounds"] == 2
        with open(summary["final_params"], "rb") as f:
            got = pickle.load(f)
        _assert_close(got, _fedavg_sim_final(cfg_d))
        # per-message blobs were reclaimed after inflation
        assert list(blob_dir.iterdir()) == []
    finally:
        broker.kill()
        broker.communicate(timeout=10)


@pytest.mark.slow
def test_cross_process_splitnn_grpc_matches_sim(tmp_path):
    """Split-family deployment: activations/cut-gradients cross a REAL
    process boundary; server trunk + every client's lower stack must
    match the joint-autodiff sim."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms.split import SplitNNSim
    from fedml_tpu.config import ExperimentConfig
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models.gkt import SplitClientNet, SplitServerNet

    cfg_d = _cfg_dict(tmp_path, "splitnn", num_clients=2, rounds=2,
                      model="cnn")
    cfg_d["data"]["batch_size"] = 8
    cfg_d["train"]["lr"] = 0.05
    summary = _spawn_world(tmp_path, cfg_d, world=3, backend="grpc")
    assert summary["rounds"] == 2

    cfg = ExperimentConfig.from_dict(cfg_d)
    data = load_dataset(cfg.data)
    sim = SplitNNSim(
        SplitClientNet(),
        SplitServerNet(num_classes=cfg.model.num_classes),
        data, cfg,
    )
    state = sim.init()
    sim_metrics = []
    for _ in range(cfg.fed.num_rounds):
        state, m = sim.run_round(state)
        sim_metrics.append({k: float(v) for k, v in m.items()})

    with open(summary["final_params"], "rb") as f:
        server_vars = pickle.load(f)
    _assert_close(server_vars, state.server_vars, rtol=2e-5, atol=1e-6)
    for r in (1, 2):
        with open(os.path.join(str(tmp_path), "deploy",
                               f"final_client{r}_params.pkl"), "rb") as f:
            cv = pickle.load(f)
        _assert_close(cv, jax.tree.map(lambda s: s[r - 1],
                                       state.client_stack),
                      rtol=2e-5, atol=1e-6)
    for got, want in zip(summary["metrics_history"], sim_metrics):
        assert abs(got["train_loss"] - want["train_loss"]) < 1e-4
        assert abs(got["train_acc"] - want["train_acc"]) < 1e-5


def test_broker_roundtrip_and_fanout():
    """Unit: the broker daemon routes publishes to every subscriber
    (including cross-connection), QoS-0 drops with no subscriber."""
    from fedml_tpu.core.transport.broker import BrokerDaemon, RemoteTopicBus

    daemon = BrokerDaemon(port=0).start()
    try:
        a = RemoteTopicBus("127.0.0.1", daemon.port)
        b = RemoteTopicBus("127.0.0.1", daemon.port)
        got_a, got_b = [], []
        evt = threading.Event()
        a.subscribe("t1", lambda t, p: got_a.append((t, p)))
        b.subscribe("t1", lambda t, p: (got_b.append((t, p)), evt.set()))
        # subscription frames race the publish on a fresh conn: publish
        # from a THIRD connection after subs are known to be processed
        c = RemoteTopicBus("127.0.0.1", daemon.port)
        for _ in range(50):
            c.publish("t1", b"payload-1")
            if evt.wait(0.1):
                break
        assert evt.is_set(), "publish never reached subscriber b"
        assert got_b[0] == ("t1", b"payload-1")
        wait_a = threading.Event()
        for _ in range(50):  # a's SUB may have landed after b's
            if got_a:
                break
            wait_a.wait(0.1)
        assert got_a and got_a[0] == ("t1", b"payload-1")
        c.publish("nobody-listens", b"dropped")  # must not error
        a.close(); b.close(); c.close()
    finally:
        daemon.stop()


def test_pubsub_transport_over_broker_echo():
    """PubSubTransport runs unchanged over the socket-served bus."""
    from fedml_tpu.core.manager import create_transport
    from fedml_tpu.core.transport.broker import BrokerDaemon, RemoteTopicBus
    from tests.test_runtime import _echo_world

    daemon = BrokerDaemon(port=0).start()
    try:
        bus_a = RemoteTopicBus("127.0.0.1", daemon.port)
        bus_b = RemoteTopicBus("127.0.0.1", daemon.port)
        a = create_transport("pubsub", 0, bus=bus_a, size=2)
        b = create_transport("pubsub", 1, bus=bus_b, size=2)
        # each transport's SUB rides its own connection and reader
        # thread: rank 0's first PUB can overtake rank 1's SUB at the
        # broker, which drops it (QoS 0) and leaves the echo waiting
        # for ever — start only once the broker holds both
        deadline = time.monotonic() + 10
        while (sum(map(len, daemon._subs.values())) < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        _echo_world(a, b)
        bus_a.close(); bus_b.close()
    finally:
        daemon.stop()
