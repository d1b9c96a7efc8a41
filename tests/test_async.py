"""Asynchronous + hierarchical aggregation (docs/FAULT_TOLERANCE.md
"Async + tiered worlds").

Tiers of coverage:

1. staleness-weight math pins (poly/const, version-lag accounting) and
   buffer fold determinism under seeded arrival permutations;
2. async-off byte-identity: with the knobs at their defaults the
   deploy path constructs the UNTOUCHED synchronous actor and two
   identical worlds produce byte-identical params;
3. tier partial math: the root folding leaf partials reproduces the
   flat world's aggregate; per-tier quarantine isolation (a leaf's
   Byzantine client never pollutes the sibling leaf's reputation);
4. the open-loop acceptance pin: async emit throughput SCALES with
   aggregator fan-in while sync FedAvg saturates flat (pinned on
   fixed costs);
5. the SIGKILL e2e: an async gRPC root is killed mid-run with folds
   pending; the relaunched incarnation restores the staleness buffer
   — not just the params — from the round checkpoint and converges;
6. satellites: the bounded inbox (shed-oldest-heartbeat, hwm gauge)
   and the partial receive-edge validation.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time

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
from fedml_tpu.core import async_agg as AA
from fedml_tpu.core import tier as TIER
from fedml_tpu.core import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _vars(seed=0, n=7):
    k = jax.random.key(seed)
    return {
        "params": {
            "w": jax.random.normal(k, (n, 3), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32),
        }
    }


def _flat(tree) -> np.ndarray:
    return np.concatenate(
        [np.asarray(x).ravel() for x in jax.tree.leaves(tree)]
    )


# ---------------------------------------------------------------------------
# 1. staleness weights + buffer math
# ---------------------------------------------------------------------------


def test_staleness_weight_pins():
    poly = AA.AsyncConfig(buffer_k=1, staleness_fn="poly",
                          staleness_alpha=0.5)
    assert poly.weight(0) == 1.0
    assert poly.weight(1) == pytest.approx(2.0 ** -0.5)
    assert poly.weight(3) == pytest.approx(0.5)
    const = AA.AsyncConfig(buffer_k=1, staleness_fn="const")
    assert [const.weight(lag) for lag in (0, 1, 9)] == [1.0, 1.0, 1.0]
    steep = AA.AsyncConfig(buffer_k=1, staleness_alpha=2.0)
    assert steep.weight(1) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        poly.weight(-1)
    with pytest.raises(ValueError):
        AA.AsyncConfig(buffer_k=1, staleness_fn="linear")
    with pytest.raises(ValueError):
        AA.AsyncConfig(buffer_k=-1)
    with pytest.raises(ValueError):
        AA.AsyncConfig(buffer_k=1, staleness_alpha=-0.5)


def test_buffer_version_lag_accounting():
    """mass == sum of w(lag) * n_k and the emitted mean is the
    weighted mean — pinned against a hand computation."""
    cfg = AA.AsyncConfig(buffer_k=3, staleness_alpha=0.5)
    template = _vars()
    buf = AA.AsyncBuffer(cfg, template)
    rng = np.random.default_rng(0)
    arrivals = [
        (jax.tree.map(lambda x: jnp.asarray(
            rng.normal(size=x.shape), x.dtype), template),
         float(rng.integers(1, 40)), int(lag))
        for lag in (0, 2, 1)
    ]
    hand_mass = 0.0
    hand_sum = np.zeros_like(_flat(template))
    for delta, n_k, lag in arrivals:
        w = buf.fold(delta, n_k, lag)
        assert w == pytest.approx((1.0 + lag) ** -0.5)
        hand_mass += w * n_k
        hand_sum = hand_sum + w * n_k * _flat(delta)
    assert buf.count == 3 and buf.ready()
    assert buf.mass == pytest.approx(hand_mass)
    mean, mass = buf.emit()
    assert mass == pytest.approx(hand_mass)
    np.testing.assert_allclose(_flat(mean), hand_sum / hand_mass,
                               rtol=1e-6)
    # drained: count/mass reset, version advanced
    assert buf.count == 0 and buf.mass == 0.0 and buf.version == 1
    with pytest.raises(RuntimeError):
        buf.emit()


def test_buffer_fold_determinism_under_permutations():
    """Same seeded arrival order -> byte-identical emission across
    repeats; permuted orders -> equal up to float reassociation."""
    cfg = AA.AsyncConfig(buffer_k=8, staleness_fn="poly")
    template = _vars(seed=3)
    rng = np.random.default_rng(42)
    arrivals = [
        (jax.tree.map(lambda x: jnp.asarray(
            rng.normal(size=x.shape), x.dtype), template),
         float(rng.integers(1, 64)), int(rng.integers(0, 4)))
        for _ in range(8)
    ]

    def run(order):
        buf = AA.AsyncBuffer(cfg, template)
        for i in order:
            buf.fold(*arrivals[i])
        mean, mass = buf.emit()
        return _flat(mean), mass

    base, base_mass = run(range(8))
    again, again_mass = run(range(8))
    np.testing.assert_array_equal(base, again)  # bitwise
    assert base_mass == again_mass
    perm_rng = np.random.default_rng(7)
    for _ in range(3):
        order = perm_rng.permutation(8)
        permuted, pmass = run(order)
        assert pmass == pytest.approx(base_mass, rel=1e-6)
        np.testing.assert_allclose(permuted, base, rtol=1e-5,
                                   atol=1e-7)


def test_buffer_checkpoint_roundtrip():
    cfg = AA.AsyncConfig(buffer_k=4)
    template = _vars(seed=1)
    buf = AA.AsyncBuffer(cfg, template)
    delta = jax.tree.map(jnp.ones_like, template)
    buf.fold(delta, 10.0, 0)
    buf.fold(delta, 5.0, 2)
    buf.version = 6
    blob = buf.state_arrays()
    # simulate the orbax hop: plain numpy in, fresh buffer out
    blob = jax.tree.map(np.asarray, blob)
    restored = AA.AsyncBuffer(cfg, template)
    restored.load_arrays(blob)
    assert restored.count == 2
    assert restored.version == 6
    assert restored.mass == pytest.approx(buf.mass)
    np.testing.assert_array_equal(_flat(restored.sum), _flat(buf.sum))


def test_async_compat_rejections():
    from fedml_tpu.algorithms.async_actors import check_async_compat

    ok = ExperimentConfig(fed=FedConfig(async_buffer_k=2))
    check_async_compat(ok)  # no raise
    check_async_compat(ExperimentConfig())  # disabled: anything goes
    with pytest.raises(ValueError, match="fednova"):
        check_async_compat(ExperimentConfig(
            fed=FedConfig(async_buffer_k=2, algorithm="fednova")
        ))
    with pytest.raises(ValueError, match="shard_aggregation"):
        check_async_compat(ExperimentConfig(
            fed=FedConfig(async_buffer_k=2, shard_aggregation=True)
        ))


def test_config_roundtrips_async_fields():
    cfg = ExperimentConfig(fed=FedConfig(
        async_buffer_k=5, staleness_fn="const", staleness_alpha=1.5,
    ))
    back = ExperimentConfig.from_dict(json.loads(cfg.to_json()))
    assert back.fed.async_buffer_k == 5
    assert back.fed.staleness_fn == "const"
    assert back.fed.staleness_alpha == 1.5


# ---------------------------------------------------------------------------
# 2/3. loopback worlds: byte-identity, tier equivalence, isolation
# ---------------------------------------------------------------------------


def _world_cfg(num_clients, rounds, **fed_kw):
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=num_clients,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=num_clients,
                      eval_every=rounds, **fed_kw),
        seed=0,
    )


def _run_flat_world(cfg, server_cls=None, server_kw=None):
    from fedml_tpu.algorithms.distributed_fedavg import (
        FedAvgClientActor,
        FedAvgServerActor,
    )
    from fedml_tpu.core.transport.loopback import LoopbackHub
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    world = cfg.data.num_clients + 1
    cls = server_cls or FedAvgServerActor
    server = cls(world, hub.create(0), model, cfg,
                 num_clients=cfg.data.num_clients, data=data,
                 **(server_kw or {}))
    threads = []
    for r in range(1, world):
        c = FedAvgClientActor(r, world, hub.create(r), model, data, cfg)
        t = threading.Thread(target=c.run, daemon=True)
        t.start()
        threads.append(t)
    server.start_round()
    server.run()
    assert server.done.is_set(), (server.failure, server.round_idx)
    for t in threads:
        t.join(timeout=30)
    return server


def _run_tier_world(cfg, n_leaves, clients_per_leaf, root_cls=None,
                    adversary_leaf=None, quarantine=None):
    from fedml_tpu.algorithms.async_actors import (
        TierAggregatorActor,
        TierRootActor,
    )
    from fedml_tpu.algorithms.distributed_fedavg import FedAvgClientActor
    from fedml_tpu.core.manager import Manager
    from fedml_tpu.core.transport.loopback import LoopbackHub
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    spec = TIER.TierSpec.parse(f"root:{n_leaves}")
    root_hub = LoopbackHub()
    root = (root_cls or TierRootActor)(
        spec.root_world_size, root_hub.create(0), model, cfg,
        num_clients=cfg.data.num_clients, data=data, tier_spec=spec,
    )
    leaves = []
    threads = []
    leaf_world = clients_per_leaf + 1
    for l in range(1, n_leaves + 1):
        hub = LoopbackHub()
        uplink = Manager(l, spec.root_world_size, root_hub.create(l))
        leaf_cfg = cfg
        if adversary_leaf == l:
            from fedml_tpu.core.adversary import AdversaryPolicy
            import dataclasses as _dc

            leaf_cfg = _dc.replace(cfg, adversary=AdversaryPolicy(
                mode="sign_flip", ranks=(clients_per_leaf,),
                scale=10.0, seed=0,
            ))
        leaf = TierAggregatorActor(
            leaf_world, hub.create(0), uplink, model, leaf_cfg,
            client_base=spec.client_base(l, clients_per_leaf),
            num_clients=cfg.data.num_clients, data=data,
            quarantine=quarantine,
        )
        leaves.append(leaf)
        for r in range(1, leaf_world):
            c = FedAvgClientActor(r, leaf_world, hub.create(r), model,
                                  data, leaf_cfg)
            t = threading.Thread(target=c.run, daemon=True)
            t.start()
            threads.append(t)
        for target in (uplink.run, leaf.run):
            t = threading.Thread(target=target, daemon=True)
            t.start()
            threads.append(t)
    root.start_round()
    root.run()
    assert root.done.is_set(), (root.failure, root.round_idx)
    for t in threads:
        t.join(timeout=30)
    return root, leaves


def test_async_off_is_the_untouched_sync_actor():
    """The byte-identity acceptance: default knobs construct the
    EXACT synchronous actor class (no wrapper, no subclass), its
    config carries disabled async/tier planes, and the world's final
    params are byte-identical run-to-run."""
    from fedml_tpu.algorithms.distributed_fedavg import FedAvgServerActor

    cfg = _world_cfg(2, rounds=3)
    assert not AA.AsyncConfig.from_fed(cfg.fed).enabled()
    a = _run_flat_world(cfg)
    assert type(a) is FedAvgServerActor  # not a subclass
    b = _run_flat_world(cfg)
    np.testing.assert_array_equal(_flat(a.variables),
                                  _flat(b.variables))
    # a config that ROUND-TRIPPED through json with the new fields
    # present drives a byte-identical world too (the new FedConfig
    # fields perturb nothing at their defaults)
    cfg2 = ExperimentConfig.from_dict(json.loads(cfg.to_json()))
    c = _run_flat_world(cfg2)
    np.testing.assert_array_equal(_flat(a.variables),
                                  _flat(c.variables))


def test_async_flat_world_converges_and_counts():
    from fedml_tpu.algorithms.async_actors import AsyncFedAvgServerActor

    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cfg = _world_cfg(2, rounds=5, async_buffer_k=2)
        server = _run_flat_world(cfg,
                                 server_cls=AsyncFedAvgServerActor)
        assert server.round_idx == 5
        c = telemetry.METRICS.snapshot()["counters"]
        assert c.get("async.emits") == 5
        assert c.get("async.folds") == 10  # K=2 folds per emission
        assert np.all(np.isfinite(_flat(server.variables)))
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_tier_root_matches_flat_world():
    """The tree changes WHERE reduction happens, not what is
    computed: a 2-leaf tier world's final params match the flat
    4-client world to float round-off."""
    cfg = _world_cfg(4, rounds=3)
    root, leaves = _run_tier_world(cfg, n_leaves=2, clients_per_leaf=2)
    flat = _run_flat_world(cfg)
    np.testing.assert_allclose(
        _flat(root.variables), _flat(flat.variables),
        rtol=0, atol=1e-6,
    )
    assert all(leaf.partials_sent == 3 for leaf in leaves)


def test_per_tier_quarantine_isolation():
    """A Byzantine client inside leaf 1 trips leaf 1's OWN
    reputation plane; the sibling leaf's tracker and the root's
    (leaf-granularity) tracker never hear about it."""
    from fedml_tpu.core.reputation import QuarantinePolicy

    cfg = _world_cfg(6, rounds=4)
    root, leaves = _run_tier_world(
        cfg, n_leaves=2, clients_per_leaf=3,
        adversary_leaf=1,
        quarantine=QuarantinePolicy(threshold=2.0, decay=0.2,
                                    warmup_rounds=0),
    )
    bad_leaf, good_leaf = leaves
    # per-tier scopes are separate OBJECTS, not shared state
    assert bad_leaf._reputation is not good_leaf._reputation
    assert bad_leaf._reputation is not root._reputation
    # the adversary (last client rank of leaf 1) tripped ITS leaf
    assert bad_leaf.quarantined_ranks == [3], (
        bad_leaf._reputation.scores,
    )
    # ...and NOBODY else's plane: the sibling leaf's same-numbered
    # rank keeps a clean slate, and the root quarantined no leaf
    assert good_leaf.quarantined_ranks == []
    assert good_leaf._reputation.score(3) < 2.0
    assert root.quarantined_ranks == []
    # the run still completed (quarantine excluded, not aborted)
    assert root.round_idx == 4


def test_async_progress_deadline_unwedges_silent_member():
    """A member that never reports (and is never declared dead — no
    heartbeats here) must not wedge the async world: the progress
    deadline force-emits pending folds every window, so the reporting
    member keeps the run moving (`--round_deadline`'s async
    meaning)."""
    from fedml_tpu.algorithms.async_actors import AsyncFedAvgServerActor
    from fedml_tpu.algorithms.distributed_fedavg import (
        FedAvgClientActor,
        RoundPolicy,
    )
    from fedml_tpu.core.transport.loopback import LoopbackHub
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cfg = _world_cfg(2, rounds=2, async_buffer_k=2)
        data = load_dataset(cfg.data)
        model = create_model(cfg.model)
        hub = LoopbackHub()
        server = AsyncFedAvgServerActor(
            3, hub.create(0), model, cfg, num_clients=2, data=data,
            round_policy=RoundPolicy(round_deadline_s=0.5),
        )
        # rank 2 exists in the world but NEVER runs — the silent
        # member a heartbeat-less deployment cannot distinguish from
        # a slow one
        hub.create(2)
        c1 = FedAvgClientActor(1, 3, hub.create(1), model, data, cfg)
        t = threading.Thread(target=c1.run, daemon=True)
        t.start()
        server.start_round()
        server.run()
        assert server.done.is_set(), (server.failure,
                                      server.round_idx)
        assert server.round_idx == 2
        c = telemetry.METRICS.snapshot()["counters"]
        assert c.get("async.forced_emits", 0) >= 1, c
        t.join(timeout=30)
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


# ---------------------------------------------------------------------------
# 4. the open-loop acceptance pin
# ---------------------------------------------------------------------------


def test_open_loop_async_scales_sync_saturates():
    """ROADMAP item 1's acceptance shape, on FIXED aggregation costs
    so the pin is deterministic: emit throughput scales with fan-in
    1 -> 4 while the synchronous barrier saturates flat."""
    kw = dict(n_clients=10_000, buffer_k=4, flush_every=8,
              horizon_s=5.0, seed=0, fold_cost_s=4e-4,
              emit_cost_s=2e-3)
    rates = {
        leaves: AA.simulate_open_loop(n_leaves=leaves,
                                      **kw)["emits_per_sec"]
        for leaves in (1, 2, 4)
    }
    assert rates[1] > 0
    scaling = rates[4] / rates[1]
    assert scaling >= 2.5, rates         # async scales with fan-in
    assert rates[2] > rates[1] * 1.4, rates  # monotone in between
    sync1 = AA.simulate_open_loop(n_leaves=1, sync=True, **kw)
    sync4 = AA.simulate_open_loop(n_leaves=4, sync=True, **kw)
    sync_scaling = (sync4["rounds_per_sec"]
                    / sync1["rounds_per_sec"])
    assert sync_scaling <= 1.3, (sync1, sync4)  # the barrier is flat
    assert scaling > 2 * sync_scaling
    # determinism: same seed, same world, same numbers
    again = AA.simulate_open_loop(n_leaves=4, **kw)
    assert again["emits_per_sec"] == rates[4]


# ---------------------------------------------------------------------------
# 5. SIGKILL-the-async-root e2e (gRPC subprocesses)
# ---------------------------------------------------------------------------


def _free_ports(n):
    import socket

    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _subproc_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_THREEFRY_PARTITIONABLE"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_sigkill_async_root_restores_buffer(tmp_path):
    """Kill -9 the async root precisely when its latest checkpoint
    carries PENDING FOLDS (count > 0) and at least one emitted
    version; the relaunched incarnation must restore the buffer —
    not just the params — resume from the checkpointed version, and
    finish every emission."""
    from fedml_tpu.utils.checkpoint import RoundCheckpointer

    rounds = 10
    cfg = {
        "data": {"dataset": "fake_mnist", "num_clients": 2,
                 "batch_size": 32, "partition_method": "homo",
                 "seed": 0},
        "model": {"name": "lr", "num_classes": 10,
                  "input_shape": [28, 28, 1]},
        "train": {"lr": 0.1, "epochs": 1},
        "fed": {"algorithm": "fedavg", "num_rounds": rounds,
                "clients_per_round": 2, "eval_every": rounds,
                "async_buffer_k": 2},
        "seed": 0,
        "run_name": "async_kill",
        "out_dir": str(tmp_path),
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    ports = _free_ports(3)
    ip_path = tmp_path / "ip.json"
    ip_path.write_text(json.dumps(
        {str(r): ["127.0.0.1", ports[r]] for r in range(3)}
    ))
    args = ["--config", str(cfg_path), "--backend", "grpc",
            "--world_size", "3", "--ip_config", str(ip_path),
            "--ready_timeout", "120", "--checkpoint_every", "1",
            "--heartbeat_interval", "0.5", "--heartbeat_timeout", "15"]
    env = _subproc_env()

    def spawn(role, rank=None, extra=()):
        argv = [sys.executable, "-m", "fedml_tpu.experiments.run",
                *args, "--role", role, *extra]
        if rank is not None:
            argv += ["--rank", str(rank)]
        return subprocess.Popen(argv, env=env, cwd=REPO,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    # client 2's traffic is chaos-delayed: after the fast client's
    # fold lands (count 1), the checkpoint sits at count > 0 for the
    # whole delay — a deterministic-width window for the kill below
    clients = [
        spawn("client", 1),
        spawn("client", 2, extra=("--fault_seed", "3",
                                  "--fault_delay", "1.0",
                                  "--fault_delay_max", "0.8")),
    ]
    server = spawn("server")
    ckpt_dir = os.path.join(str(tmp_path), "async_kill", "ckpt")
    killed = False
    killed_state = None
    deadline = time.monotonic() + 240
    try:
        while time.monotonic() < deadline:
            if server.poll() is not None:
                break  # finished before we found a kill window
            if os.path.isdir(ckpt_dir):
                try:
                    reader = RoundCheckpointer(ckpt_dir)
                    raw, _ = reader.restore_raw()
                    reader.close()
                except Exception:
                    raw = None  # mid-write; retry
                if raw is not None and "async" in raw:
                    count = int(np.asarray(raw["async"]["count"]))
                    version = int(np.asarray(raw["async"]["version"]))
                    if count > 0 and version >= 1:
                        os.kill(server.pid, signal.SIGKILL)
                        killed = True
                        killed_state = (count, version)
                        break
            time.sleep(0.02)
        assert killed, (
            "never observed a checkpoint with pending folds; server "
            f"rc={server.returncode}: {server.communicate()[0]}"
        )
        server.wait(timeout=30)
        # relaunch: same run dir, fresh incarnation
        server2 = spawn("server")
        out2 = server2.communicate(timeout=240)[0]
        assert server2.returncode == 0, out2
        summary = json.loads(out2.strip().splitlines()[-1])
        assert summary["rounds"] == rounds, summary
        assert summary["resumed_from"] >= killed_state[1], (
            summary, killed_state,
        )
        # the buffer itself came back: the pending folds we killed
        # over were restored into the new incarnation's accumulator
        assert summary["async_restored_folds"] == killed_state[0], (
            summary, killed_state,
        )
        assert summary["async_buffer_k"] == 2, summary
        assert np.isfinite(summary.get("loss", float("nan"))), summary
    finally:
        for p in [server, *clients]:
            if p.poll() is None:
                p.kill()
        for c in clients:
            c.communicate()


# ---------------------------------------------------------------------------
# 6. satellites: bounded inbox + partial validation
# ---------------------------------------------------------------------------


def test_bounded_inbox_sheds_oldest_heartbeat_only():
    from fedml_tpu.core.message import (
        MSG_TYPE_C2S_RESULT,
        MSG_TYPE_HEARTBEAT,
        Message,
    )
    from fedml_tpu.core.transport.base import _BoundedInbox

    box = _BoundedInbox(capacity=4)
    hb = lambda i: Message(MSG_TYPE_HEARTBEAT, i, 0, {})
    res = lambda i: Message(MSG_TYPE_C2S_RESULT, i, 0, {"i": i})
    box.put(hb(1))
    box.put(res(2))
    box.put(hb(3))
    box.put(res(4))
    assert box.hwm == 4 and box.shed == 0
    # at capacity: the OLDEST heartbeat (from rank 1) is shed
    assert box.put(res(5)) is True
    assert box.shed == 1
    order = [box.get(timeout=0.1) for _ in range(4)]
    assert [m.msg_type for m in order] == [
        MSG_TYPE_C2S_RESULT, MSG_TYPE_HEARTBEAT, MSG_TYPE_C2S_RESULT,
        MSG_TYPE_C2S_RESULT,
    ]
    assert [m.sender for m in order] == [2, 3, 4, 5]
    with pytest.raises(queue.Empty):
        box.get(timeout=0.05)


def test_bounded_inbox_never_sheds_work():
    from fedml_tpu.core.message import MSG_TYPE_C2S_RESULT, Message
    from fedml_tpu.core.transport.base import _BoundedInbox

    box = _BoundedInbox(capacity=3)
    for i in range(6):
        shed = box.put(Message(MSG_TYPE_C2S_RESULT, i, 0, {}))
        assert shed is False  # no heartbeat to shed -> nothing shed
    # degrades to unbounded rather than dropping work, and the
    # high-water-mark says so
    assert box.qsize() == 6 and box.hwm == 6 and box.shed == 0
    assert [box.get(timeout=0.1).sender for _ in range(6)] == list(
        range(6)
    )


def test_inbox_hwm_gauge_and_shed_counter_surface():
    """The transport deliver edge feeds manager.inbox_hwm /
    manager.inbox_shed (docs/OBSERVABILITY.md)."""
    from fedml_tpu.core.message import (
        MSG_TYPE_C2S_RESULT,
        MSG_TYPE_HEARTBEAT,
        Message,
    )
    from fedml_tpu.core.transport.loopback import LoopbackHub

    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        hub = LoopbackHub()
        t0 = hub.create(0)
        t0._inbox.capacity = 2
        t0.deliver(Message(MSG_TYPE_HEARTBEAT, 1, 0, {}))
        t0.deliver(Message(MSG_TYPE_C2S_RESULT, 1, 0, {}))
        t0.deliver(Message(MSG_TYPE_C2S_RESULT, 1, 0, {}))
        snap = telemetry.METRICS.snapshot()
        assert snap["gauges"]["manager.inbox_hwm.rank0"] >= 2
        assert snap["counters"]["manager.inbox_shed"] == 1
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_tier_spec_parse_and_bases():
    spec = TIER.TierSpec.parse("root:4")
    assert spec.n_leaves == 4
    assert spec.root_world_size == 5
    assert spec.leaf_ranks() == [1, 2, 3, 4]
    assert spec.client_base(1, 10) == 0
    assert spec.client_base(3, 10) == 20
    for bad in ("root", "root:", "root:x", "tree:2", "root:0"):
        with pytest.raises(ValueError):
            TIER.TierSpec.parse(bad)


def test_partial_validation_screens():
    template = _vars()["params"]
    good_sum = jax.tree.map(
        lambda x: np.ones_like(np.asarray(x)), template
    )
    ok = {TIER.KEY_TIER_SUM: good_sum, TIER.KEY_TIER_COUNT: 2}
    assert TIER.validate_partial(template, ok, 64.0) is None
    # non-finite leaf
    bad = {TIER.KEY_TIER_SUM: jax.tree.map(
        lambda x: np.full_like(np.asarray(x), np.nan), template
    ), TIER.KEY_TIER_COUNT: 2}
    assert "finite" in TIER.validate_partial(template, bad, 64.0)
    # wrong shape
    bad_shape = {TIER.KEY_TIER_SUM: jax.tree.map(
        lambda x: np.ones((2, 2), np.float32), template
    ), TIER.KEY_TIER_COUNT: 2}
    assert "shape" in TIER.validate_partial(template, bad_shape, 64.0)
    # bad sample mass / count / structure
    assert TIER.validate_partial(template, ok, float("nan"))
    assert TIER.validate_partial(template, ok, 0.0)
    assert TIER.validate_partial(
        template, {TIER.KEY_TIER_SUM: good_sum,
                   TIER.KEY_TIER_COUNT: 0}, 64.0)
    assert TIER.validate_partial(template, {}, 64.0)
    assert TIER.validate_partial(
        template, {TIER.KEY_TIER_SUM: {"nope": 1},
                   TIER.KEY_TIER_COUNT: 1}, 64.0)
