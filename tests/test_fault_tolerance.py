"""Fault-tolerance suite: chaos-injection transport, retry/backoff,
heartbeats, and straggler-tolerant rounds (docs/FAULT_TOLERANCE.md).

The pins, in dependency order:

1. the retry helper's schedule and abort semantics (pure unit);
2. ChaosTransport's fault stream is seeded-deterministic;
3. the heartbeat monitor detects a silent peer and fires once;
4. FedAvg over loopback AND tcp with seeded drop/delay/dup faults still
   completes all rounds (quorum + deadline absorb the losses);
5. a client crashed at round 1 leaves a completed run whose later rounds
   aggregated only the survivors (renormalized weights);
6. an unreachable quorum aborts with a diagnostic instead of hanging;
7. with faults disabled, the fault-tolerance layer is BYTE-IDENTICAL to
   the plain transport path (same final-params digest) — chaos wrapper,
   round tags, and straggler knobs must be invisible at zero faults;
8. the server ACKs READY before the barrier completes (readiness gate
   regression — a later-rank client must not need work traffic to know
   the server is alive);
9. the broker survives a wedged subscriber (slow-consumer drop);
10. a real deployment whose client PROCESS dies mid-run (chaos
    crash_mode="exit" == deterministic kill -9) completes server-side
    with the survivor cohort.
"""

import socket
import threading
import time

import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core.manager import Manager, ServerManager, create_transport
from fedml_tpu.core.message import Message
from fedml_tpu.core.transport.base import BaseTransport
from fedml_tpu.core.transport.chaos import ChaosTransport, FaultPolicy
from fedml_tpu.core.transport.loopback import LoopbackHub
from fedml_tpu.core.transport.retry import (
    RetryExhausted,
    RetryPolicy,
    call_with_retry,
)
from fedml_tpu.algorithms.distributed_fedavg import (
    FedAvgClientActor,
    FedAvgServerActor,
    RoundPolicy,
)
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model


# ---------------------------------------------------------------------------
# retry/backoff unit
# ---------------------------------------------------------------------------


def test_retry_backoff_schedule_and_success():
    import random

    policy = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=1.0,
                         multiplier=2.0, jitter=0.0)
    rng = random.Random(0)
    delays = [policy.delay(k, rng) for k in range(5)]
    assert delays == [0.1, 0.2, 0.4, 0.8, 1.0]  # capped exponential

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = call_with_retry(
        flaky,
        policy=RetryPolicy(max_attempts=5, base_delay_s=0.001,
                           deadline_s=5.0),
    )
    assert out == "ok" and len(calls) == 3


def test_retry_exhaustion_raises_with_cause_and_runs_cleanup():
    evicted = []

    def always_down():
        raise ConnectionRefusedError("down")

    with pytest.raises(RetryExhausted) as ei:
        call_with_retry(
            always_down,
            policy=RetryPolicy(max_attempts=3, base_delay_s=0.001,
                               deadline_s=1.0),
            describe="probe",
            cleanup=lambda: evicted.append(1),
        )
    assert isinstance(ei.value.__cause__, ConnectionRefusedError)
    assert "probe" in str(ei.value)
    assert len(evicted) == 3  # cleanup ran between every attempt


def test_retry_stop_event_aborts_immediately():
    stop = threading.Event()
    stop.set()
    t0 = time.monotonic()
    with pytest.raises(RetryExhausted):
        call_with_retry(
            lambda: (_ for _ in ()).throw(OSError("x")),
            policy=RetryPolicy(max_attempts=10, base_delay_s=1.0,
                               deadline_s=60.0),
            stop=stop,
        )
    assert time.monotonic() - t0 < 0.5  # no backoff sleeps were taken


# ---------------------------------------------------------------------------
# chaos transport unit
# ---------------------------------------------------------------------------


class _RecordingTransport(BaseTransport):
    def __init__(self, rank=0):
        super().__init__(rank)
        self.sent: list[Message] = []

    def send_message(self, msg: Message) -> None:
        self.sent.append(msg)


def _drive_chaos(policy: FaultPolicy, n=200):
    inner = _RecordingTransport()
    chaos = ChaosTransport(inner, policy)
    for i in range(n):
        chaos.send_message(Message(100, 0, 1, {"i": i}))
    time.sleep(0.4)  # let delay timers + reorder flushes settle
    return inner, chaos


def test_chaos_faults_are_seeded_deterministic():
    policy = FaultPolicy(seed=7, drop_prob=0.2, dup_prob=0.1,
                         delay_prob=0.1, delay_max_s=0.01,
                         reorder_prob=0.1)
    a_inner, a = _drive_chaos(policy)
    b_inner, b = _drive_chaos(policy)
    assert a.stats == b.stats
    assert a.stats["dropped"] > 0 and a.stats["duplicated"] > 0
    # WHICH messages got dropped/duplicated is seed-deterministic (the
    # multiset of deliveries); the wall-clock interleaving of delayed
    # sends is inherently temporal and not part of the contract
    assert sorted(m.get("i") for m in a_inner.sent) == sorted(
        m.get("i") for m in b_inner.sent
    )
    # a different seed yields a different fault pattern
    c_inner, c = _drive_chaos(
        FaultPolicy(seed=8, drop_prob=0.2, dup_prob=0.1, delay_prob=0.1,
                    delay_max_s=0.01, reorder_prob=0.1)
    )
    assert sorted(m.get("i") for m in c_inner.sent) != sorted(
        m.get("i") for m in a_inner.sent
    )


def test_chaos_crash_at_round_goes_silent():
    inner = _RecordingTransport()
    chaos = ChaosTransport(inner, FaultPolicy(crash_at_round=2))
    seen = []

    class Obs:
        def receive_message(self, t, m):
            seen.append(m)

    chaos.add_observer(Obs())
    threading.Thread(
        target=chaos.handle_receive_message, daemon=True
    ).start()
    inner.deliver(Message(1, 0, 1, {"round_idx": 0}))
    inner.deliver(Message(1, 0, 1, {"round_idx": 1}))
    deadline = time.monotonic() + 5
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert [m.get("round_idx") for m in seen] == [0, 1]
    inner.deliver(Message(1, 0, 1, {"round_idx": 2}))  # the fatal one
    time.sleep(0.2)
    assert chaos.crashed.is_set()
    assert len(seen) == 2  # round-2 message swallowed
    chaos.send_message(Message(3, 1, 0, {"after": True}))
    assert inner.sent == []  # dead ranks send nothing
    inner.deliver(Message(1, 0, 1, {"round_idx": 3}))
    time.sleep(0.1)
    assert len(seen) == 2  # and read nothing
    chaos.stop()


def test_fault_policy_validation():
    with pytest.raises(ValueError):
        FaultPolicy(crash_mode="explode")
    with pytest.raises(ValueError):
        RoundPolicy(quorum_fraction=0.0)
    with pytest.raises(ValueError):
        RoundPolicy(round_deadline_s=-1.0)


# ---------------------------------------------------------------------------
# heartbeat / liveness unit
# ---------------------------------------------------------------------------


def test_heartbeat_monitor_detects_silent_peer_once():
    hub = LoopbackHub()
    a = Manager(0, 3, hub.create(0))
    b = Manager(1, 3, hub.create(1))  # beats back
    hub.create(2)  # rank 2 exists but never responds
    dead = []
    a.enable_liveness([1, 2], interval_s=0.1, timeout_s=0.6,
                      on_dead=dead.append)
    b.enable_liveness([0], interval_s=0.1, timeout_s=5.0)
    ta = threading.Thread(target=a.run, daemon=True)
    tb = threading.Thread(target=b.run, daemon=True)
    ta.start(); tb.start()
    deadline = time.monotonic() + 5
    while not dead and time.monotonic() < deadline:
        time.sleep(0.02)
    time.sleep(0.5)  # window for (incorrect) duplicate callbacks
    assert dead == [2]  # the silent peer, exactly once; b stayed live
    a.finish(); b.finish()
    ta.join(timeout=2); tb.join(timeout=2)


# ---------------------------------------------------------------------------
# straggler-tolerant FedAvg worlds (loopback + tcp)
# ---------------------------------------------------------------------------

N_CLIENTS = 2
WORLD = 3  # 1 server + 2 workers


def _cfg(rounds=3):
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=N_CLIENTS,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=N_CLIENTS,
                      eval_every=rounds),
        seed=0,
    )


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _make_world_transports(backend):
    """rank -> transport factory for an in-process world."""
    if backend == "loopback":
        hub = LoopbackHub()
        return lambda r: hub.create(r)
    ports = _free_ports(WORLD)
    ip = {r: ("127.0.0.1", ports[r]) for r in range(WORLD)}
    return lambda r: create_transport("tcp", r, ip_config=ip)


def _run_world(
    make_transport,
    cfg,
    policies: dict[int, FaultPolicy] | None = None,
    round_policy: RoundPolicy | None = None,
    liveness: tuple[float, float] | None = None,
):
    """Drive a full actor world in-process; returns (server, history)."""
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    history = []

    def wrap(rank):
        t = make_transport(rank)
        if policies and rank in policies and policies[rank].enabled():
            t = ChaosTransport(t, policies[rank])
        return t

    server = FedAvgServerActor(
        WORLD, wrap(0), model, cfg, num_clients=N_CLIENTS,
        on_round_done=lambda r, meta: history.append(meta),
        round_policy=round_policy,
    )
    clients = [
        FedAvgClientActor(r, WORLD, wrap(r), model, data, cfg)
        for r in range(1, WORLD)
    ]
    if liveness is not None:
        interval, timeout_s = liveness
        server.enable_liveness(
            range(1, WORLD), interval, timeout_s,
            on_dead=server.on_peer_dead,
        )
        for c in clients:
            c.enable_liveness([0], interval, timeout_s)
    threads = [threading.Thread(target=c.run, daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    server.transport.start()
    server.start_round()
    server.run()  # returns once the actor finished or aborted
    done = server.done.is_set()
    for c in clients:
        # crashed-silent clients swallow FINISH and would pin their run()
        # thread on the inbox; stop the transports before joining
        c.transport.stop()
    for t in threads:
        t.join(timeout=10)
    server.transport.stop()
    assert done or server.failure is not None, "server neither finished nor aborted"
    return server, history


@pytest.mark.parametrize("backend", ["loopback", "tcp"])
def test_fedavg_chaos_matrix_still_completes(backend):
    """Seeded drop/delay/dup on every rank: the run completes all rounds
    — lost traffic is absorbed by quorum + round deadline, late results
    are discarded by round tags."""
    cfg = _cfg(rounds=3)
    chaos = FaultPolicy(seed=3, drop_prob=0.1, delay_prob=0.3,
                        delay_max_s=0.02, dup_prob=0.15)
    policies = {r: chaos for r in range(WORLD)}
    server, history = _run_world(
        _make_world_transports(backend),
        cfg,
        policies=policies,
        round_policy=RoundPolicy(quorum_fraction=0.5,
                                 round_deadline_s=4.0),
    )
    assert server.failure is None
    assert server.done.is_set()
    assert server.round_idx == 3
    # every closed round aggregated at least a quorum of results
    assert all(m["num_results"] >= 1 for m in history)
    digest = _digest(server.variables)
    assert isinstance(digest, str) and len(digest) == 64


def test_fedavg_crashed_client_round1_completes_renormalized():
    """Worker rank 2 crashes when round 1's sync arrives (participated
    in round 0 only). Heartbeats flag it dead; rounds 1+ close over the
    survivor with weights renormalized over the survivor's samples."""
    cfg = _cfg(rounds=3)
    server, history = _run_world(
        _make_world_transports("loopback"),
        cfg,
        policies={2: FaultPolicy(crash_at_round=1)},
        round_policy=RoundPolicy(quorum_fraction=0.5,
                                 round_deadline_s=15.0),
        liveness=(0.1, 0.8),
    )
    assert server.failure is None
    assert server.done.is_set()
    assert server.round_idx == 3
    assert server.dead_peers == {2}
    assert [m["num_results"] for m in history] == [2, 1, 1]
    assert history[-1]["dead_peers"] == [2]


def test_fedavg_quorum_unreachable_aborts_with_diagnostic():
    """Every worker crashes on the FIRST sync: no result can ever
    arrive; the deadline fires under quorum and the server aborts with
    a diagnostic instead of blocking forever on its inbox."""
    cfg = _cfg(rounds=3)
    server, history = _run_world(
        _make_world_transports("loopback"),
        cfg,
        policies={1: FaultPolicy(crash_at_round=0),
                  2: FaultPolicy(crash_at_round=0)},
        round_policy=RoundPolicy(quorum_fraction=1.0,
                                 round_deadline_s=1.5),
    )
    assert not server.done.is_set()
    assert server.failure is not None
    assert "deadline" in server.failure and "quorum" in server.failure
    assert history == []  # no round ever closed


def _digest(tree):
    import hashlib
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def test_zero_fault_path_byte_identical_digest():
    """Regression pin: with FaultPolicy disabled the entire
    fault-tolerance layer (chaos wrapper, round tags, quorum knobs,
    deadline timers) is INVISIBLE — final params digest is byte-equal to
    the plain-transport actor run, which test_runtime pins against the
    compiled simulator's math."""
    cfg = _cfg(rounds=2)

    server_plain, _ = _run_world(_make_world_transports("loopback"), cfg)
    # disabled chaos wrapper on every rank (drop/dup/delay all zero)
    noop = FaultPolicy()
    assert not noop.enabled()
    server_wrapped, _ = _run_world(
        _make_world_transports("loopback"), cfg,
        policies={r: FaultPolicy(dup_prob=0.0) for r in range(WORLD)},
    )
    # straggler knobs armed but never triggered (no faults, generous
    # deadline): still byte-identical
    server_armed, _ = _run_world(
        _make_world_transports("loopback"), cfg,
        round_policy=RoundPolicy(quorum_fraction=0.5,
                                 round_deadline_s=60.0),
    )
    d0 = _digest(server_plain.variables)
    assert _digest(server_wrapped.variables) == d0
    assert _digest(server_armed.variables) == d0


# ---------------------------------------------------------------------------
# readiness ACK regression (deploy barrier)
# ---------------------------------------------------------------------------


def test_ready_is_acked_before_barrier_completes():
    """A client that announces READY gets the S2C ACK immediately — even
    while the barrier is still waiting on other ranks. Pre-ACK, a
    later-rank SplitNN client could only learn the server was alive from
    its first WORK message, which may be minutes away."""
    from fedml_tpu.experiments.deploy import (
        DeployConfig,
        _announce_until_first_message,
        _serve_with_ready_barrier,
    )

    hub = LoopbackHub()
    server = ServerManager(0, 3, hub.create(0))
    kicked = threading.Event()
    dep_server = DeployConfig(role="server", rank=0, world_size=3,
                              heartbeats=False)
    ts = threading.Thread(
        target=_serve_with_ready_barrier,
        args=(server, dep_server, kicked.set),
        daemon=True,
    )
    ts.start()

    client = Manager(1, 3, hub.create(1))
    dep_client = DeployConfig(role="client", rank=1, world_size=3,
                              ready_timeout=10.0, heartbeats=False)
    client.transport.start()
    got, failures = _announce_until_first_message(client, dep_client)
    tc = threading.Thread(target=client.run, daemon=True)
    tc.start()

    # rank 2 never announces: the barrier is incomplete, yet rank 1's
    # readiness is acknowledged
    assert got.wait(timeout=5), "READY was never ACKed"
    assert not kicked.is_set()
    assert not failures

    server.finish_all()  # unblocks both loops
    ts.join(timeout=5)
    tc.join(timeout=5)
    assert not ts.is_alive() and not tc.is_alive()


# ---------------------------------------------------------------------------
# broker: slow subscriber cannot stall routing
# ---------------------------------------------------------------------------


def test_broker_drops_wedged_subscriber_keeps_routing():
    from fedml_tpu.core.transport.broker import (
        BrokerDaemon,
        RemoteTopicBus,
        _OP_SUB,
        _frame,
    )

    daemon = BrokerDaemon(port=0).start()
    try:
        # a raw socket that subscribes and then never reads: its kernel
        # buffer fills, then its broker-side queue, then it gets dropped
        wedged = socket.create_connection(("127.0.0.1", daemon.port))
        wedged.sendall(_frame(_OP_SUB, "t"))

        healthy = RemoteTopicBus("127.0.0.1", daemon.port)
        got = []
        evt = threading.Event()
        healthy.subscribe(
            "t", lambda t, p: (got.append(p), evt.set())
        )
        pub = RemoteTopicBus("127.0.0.1", daemon.port)
        # wait until both subscriptions are registered broker-side
        for _ in range(100):
            pub.publish("t", b"warm")
            if evt.wait(0.05):
                break
        assert evt.is_set()

        payload = b"x" * 65536
        t0 = time.monotonic()
        for _ in range(400):  # >> kernel buffer + per-sub queue of 256
            pub.publish("t", payload)
        # the healthy subscriber still gets traffic promptly
        evt.clear()
        got.clear()
        pub.publish("t", b"after-flood")
        ok = False
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(p == b"after-flood" for p in got):
                ok = True
                break
            time.sleep(0.05)
        assert ok, "healthy subscriber starved behind a wedged one"
        assert time.monotonic() - t0 < 30
        healthy.close(); pub.close(); wedged.close()
    finally:
        daemon.stop()


# ---------------------------------------------------------------------------
# deployment: a client PROCESS dies mid-run; the server completes
# ---------------------------------------------------------------------------


def test_deploy_client_process_killed_mid_run(tmp_path):
    """Acceptance pin: 1 server + 2 client OS processes over gRPC; rank
    2 is killed mid-run (chaos crash_mode="exit" — os._exit on round 1's
    sync, the deterministic kill -9). The server must finish all rounds
    within its straggler budget instead of hanging, reporting rank 2
    dead; the surviving client exits cleanly."""
    import json
    import subprocess
    import sys

    from fedml_tpu.core.transport.chaos import CHAOS_EXIT_CODE
    from tests.test_deploy import (
        REPO,
        _cfg_dict,
        _free_ports as _ports,
        _subproc_env,
    )

    cfg_d = _cfg_dict(tmp_path, "fedavg", num_clients=2, rounds=3)
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg_d))
    ports = _ports(3)
    ip_path = tmp_path / "ip.json"
    ip_path.write_text(json.dumps(
        {str(r): ["127.0.0.1", ports[r]] for r in range(3)}
    ))
    telemetry_dir = tmp_path / "telemetry"
    # heartbeat_timeout must tolerate CPU starvation on a loaded 1-core
    # CI host (three jax processes compiling at once): the timeout only
    # guards against FALSE positives here — the killed client is caught
    # much faster by the server's failed round-sync send (~2s of grpc
    # retries), not by staleness
    base = [sys.executable, "-m", "fedml_tpu.experiments.run",
            "--config", str(cfg_path), "--backend", "grpc",
            "--world_size", "3", "--ip_config", str(ip_path),
            "--ready_timeout", "60",
            "--telemetry_dir", str(telemetry_dir),
            "--heartbeat_interval", "0.5", "--heartbeat_timeout", "12",
            "--quorum_fraction", "0.5", "--round_deadline", "30"]
    env = _subproc_env()
    c1 = subprocess.Popen(
        [*base, "--role", "client", "--rank", "1"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    c2 = subprocess.Popen(
        [*base, "--role", "client", "--rank", "2",
         "--fault_crash_round", "1", "--fault_crash_mode", "exit"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    server = subprocess.Popen(
        [*base, "--role", "server"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        s_out, s_err = server.communicate(timeout=240)
        out1 = c1.communicate(timeout=60)[0]
        out2 = c2.communicate(timeout=60)[0]
    except subprocess.TimeoutExpired:
        for p in (server, c1, c2):
            p.kill()
        raise
    assert server.returncode == 0, (
        f"server rc={server.returncode}\n{s_out}\n{s_err}\n"
        f"c1:\n{out1}\nc2:\n{out2}"
    )
    summary = json.loads(s_out.strip().splitlines()[-1])
    assert summary["rounds"] == 3
    assert summary["dead_peers"] == [2]
    # the surviving client finished cleanly; the chaos-killed one died
    # with the injected exit code (never unwound, like a real kill -9)
    assert c1.returncode == 0, out1
    assert c2.returncode == CHAOS_EXIT_CODE, out2
    # flight-recorder acceptance pin (docs/OBSERVABILITY.md): the dead
    # peer left a debuggable artifact on the server naming rank 2
    dumps = [f for f in telemetry_dir.iterdir()
             if f.name.startswith("flight_rank0")
             and "dead_peer" in f.name]
    assert dumps, sorted(p.name for p in telemetry_dir.iterdir())
    flight = json.loads(dumps[0].read_text())
    assert flight["peer"] == 2
    assert "metrics" in flight and "events" in flight
