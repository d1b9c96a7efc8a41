"""Elastic-membership suite: shape-bucketed rounds, the membership
ledger, churn-proof wire framing, and the dynamic-world actor protocol
(docs/FAULT_TOLERANCE.md "Elastic membership").

The pins, in dependency order:

1. bucket padding is CONTENT-BLIND bitwise for every defense rule (the
   masked rows cannot perturb the aggregate no matter what they carry)
   and padded-vs-unpadded parity holds per the core/elastic.py tiers:
   byte-identical for the selection/gather rules, ~1-ulp for the
   sum-based ones, for every cohort size 1..2*bucket;
2. the sealed wire codec detects corruption (CRC) and rolling-restart
   skew (version byte); the chaos ``corrupt`` fault is seeded, counted,
   and healed end to end over a real TCP link;
3. the membership ledger admits JOINs from beyond the launch world with
   a STABLE client id, distinguishes graceful LEAVE from death, evicts
   permanently, and round-trips through checkpoint arrays across a
   DIFFERENT relaunch world size;
4. the elastic simulator compiles its round once per bucket —
   set_cohort_size churn inside the bucket is a compile-cache hit
   (``elastic.compile_cache_{hits,misses}``);
5. actor-level: a loopback world ADMITS a beyond-world JOIN at the next
   round boundary and completes with the grown cohort; a graceful
   LEAVE spends no suspicion (no dead peers, no flight dump) and the
   run completes without the departed rank; an evicted rank's JOIN is
   rejected; a server restored from a checkpoint serves the
   checkpoint's (grown) world, not the launch flag's;
6. the acceptance pin (gRPC, supervised): a late-joining client is
   admitted mid-run, a client LEAVEs gracefully, the server is
   SIGKILLed and restores the ledger from its checkpoint, every round
   completes, and each server incarnation compiles the round function
   at most once per distinct bucket size.
"""

import hashlib
import json
import os
import signal
import socket
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
from fedml_tpu.core import elastic as E
from fedml_tpu.core import telemetry
from fedml_tpu.core.membership import MembershipLedger
from fedml_tpu.core.message import (
    MSG_TYPE_C2S_JOIN,
    MSG_TYPE_C2S_LEAVE,
    Message,
)
from fedml_tpu.core.robust import DefensePipeline
from fedml_tpu.core.transport import wire
from fedml_tpu.core.transport.loopback import LoopbackHub
from fedml_tpu.algorithms.distributed_fedavg import (
    FedAvgClientActor,
    FedAvgServerActor,
)
from fedml_tpu.algorithms.fedavg import FedAvgSim, local_reducer
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(num_clients=3, rounds=4, **fed_kw):
    fed_kw.setdefault("clients_per_round", num_clients)
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=num_clients,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, eval_every=rounds, **fed_kw),
        seed=0,
    )


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# 1. bucket math + padding neutrality (the property pin)
# ---------------------------------------------------------------------------


def test_bucket_for_powers_of_two():
    assert [E.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9, 33)] == [
        1, 2, 4, 4, 8, 8, 16, 64]
    assert E.bucket_for(3, min_bucket=8) == 8
    with pytest.raises(ValueError):
        E.bucket_for(0)


def _delta_case(rng, c):
    deltas = {
        "a": jnp.asarray(rng.normal(size=(c, 3, 2)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(c, 5)), jnp.float32),
    }
    weights = jnp.asarray(rng.integers(1, 40, size=(c,)), jnp.float32)
    zero = {"a": jnp.zeros((3, 2), jnp.float32),
            "b": jnp.zeros((5,), jnp.float32)}
    return deltas, weights, zero


# selection/gather rules reproduce the unpadded aggregate bit-for-bit;
# the sum-based ones feed identical live terms plus exact zeros to a
# WIDER reduce, whose association XLA may pick differently (~1 ulp) —
# see the parity tiers in core/elastic.py
_EXACT_RULES = ("median", "krum", "fltrust")
_ULP_RULES = ("mean", "trimmed_mean", "multikrum")


@pytest.mark.parametrize("rule", _EXACT_RULES + _ULP_RULES)
def test_padded_aggregation_matches_unpadded_every_cohort_size(rule):
    """Cohort sizes 1..2*bucket (buckets 1, 2, 4, 8): the bucket-padded
    reduce equals the unpadded one — byte-identical for the selection
    rules, <= tight-tolerance for the sum-based ones."""
    red = local_reducer()
    pipe = DefensePipeline(method=rule, num_adversaries=1)
    unpadded = jax.jit(lambda d, w: pipe.reduce(d, w, red))
    padded = jax.jit(lambda d, w, v: pipe.reduce(d, w, red, v))
    rng = np.random.default_rng(0)
    for c in range(1, 9):
        deltas, weights, zero = _delta_case(rng, c)
        pd, pw, valid = E.pad_stacked(deltas, weights, zero,
                                      E.bucket_for(c))
        un = unpadded(deltas, weights)
        pa = padded(pd, pw, valid)
        for k in un:
            a, b = np.asarray(un[k]), np.asarray(pa[k])
            if rule in _EXACT_RULES:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{rule} c={c} leaf={k}")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-5, atol=1e-6,
                    err_msg=f"{rule} c={c} leaf={k}")


@pytest.mark.parametrize("rule", _EXACT_RULES + _ULP_RULES)
def test_padding_rows_are_content_blind_bitwise(rule):
    """The churn-proof property the elastic runtime rests on: at a
    fixed bucket, the masked rows CANNOT perturb the aggregate — a
    padded cohort and its garbage-padded twin are byte-identical for
    every rule (the compiled round's output depends only on the live
    rows)."""
    red = local_reducer()
    pipe = DefensePipeline(method=rule, num_adversaries=1)
    padded = jax.jit(lambda d, w, v: pipe.reduce(d, w, red, v))
    rng = np.random.default_rng(1)
    for c in (1, 3, 5, 7):
        deltas, weights, zero = _delta_case(rng, c)
        bucket = E.bucket_for(c)
        pd, pw, valid = E.pad_stacked(deltas, weights, zero, bucket)
        junk = jax.tree.map(
            lambda x: jnp.where(
                valid.reshape((-1,) + (1,) * (x.ndim - 1)), x,
                jnp.asarray(rng.normal(size=x.shape) * 1e3, x.dtype),
            ),
            pd,
        )
        a = padded(pd, pw, valid)
        b = padded(junk, pw, valid)
        for k in a:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]),
                err_msg=f"{rule} c={c} leaf={k}")


def test_pad_stacked_shapes_and_mask():
    rng = np.random.default_rng(2)
    deltas, weights, zero = _delta_case(rng, 3)
    pd, pw, valid = E.pad_stacked(deltas, weights, zero, 8)
    assert pd["a"].shape == (8, 3, 2) and pd["b"].shape == (8, 5)
    assert list(np.asarray(valid)) == [True] * 3 + [False] * 5
    np.testing.assert_array_equal(np.asarray(pw)[3:], 0.0)
    # padded rows replicate the fill tree exactly (delta-zero rows)
    np.testing.assert_array_equal(np.asarray(pd["a"])[3:], 0.0)
    with pytest.raises(ValueError):
        E.pad_stacked(deltas, weights, zero, 2)


def test_trimmed_mean_padded_trim_count_matches_static():
    """The padded path's trim count must come from the SAME host-float
    formula as the static leaf: deriving it in traced f32 rounds
    f32(10) * f32(0.3) up to 3.0000001 and trims one row more than the
    unpadded int(10 * 0.3) == 2 — a wholly different aggregate, not a
    1-ulp reassociation."""
    from fedml_tpu.core import robust

    rng = np.random.default_rng(5)
    for frac in (0.1, 0.25, 0.3, 0.49):
        for n in (3, 7, 10, 13):
            x = jnp.asarray(rng.normal(size=(n, 6)), jnp.float32)
            want = robust.trimmed_mean({"w": x}, frac)["w"]
            bucket = E.bucket_for(n)
            pad = jnp.full((bucket - n, 6), 7.75, jnp.float32)
            padded = {"w": jnp.concatenate([x, pad])}
            valid = jnp.arange(bucket) < n
            got = robust.trimmed_mean(padded, frac, valid=valid)["w"]
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want),
                rtol=2e-6, atol=2e-7,
                err_msg=f"frac={frac} n={n} bucket={bucket}",
            )


# ---------------------------------------------------------------------------
# 2. the compiled-executable LRU
# ---------------------------------------------------------------------------


def test_compiled_round_cache_hits_misses_evictions():
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cache = E.CompiledRoundCache(lambda x: x * 2.0, max_entries=2)
        for bucket in (2, 4, 2, 2, 8, 4):
            out = cache(bucket, jnp.ones((bucket,), jnp.float32))
            np.testing.assert_array_equal(np.asarray(out), 2.0)
        # compiles: 2, 4, 8, then 4 again (evicted when 8 landed; the
        # LRU victim was 2's slot... order: [2,4] -> hit 2 -> [4,2] ->
        # 8 evicts 4 -> [2,8] -> 4 recompiles evicting 2
        assert cache.stats["misses"] == 4
        assert cache.stats["hits"] == 2
        assert cache.stats["evictions"] == 2
        assert len(cache) == 2
        c = telemetry.METRICS.snapshot()["counters"]
        assert c["elastic.compile_cache_misses"] == 4
        assert c["elastic.compile_cache_hits"] == 2
        assert c["elastic.compile_cache_evictions"] == 2
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


# ---------------------------------------------------------------------------
# 3. sealed wire frames + the chaos corrupt fault
# ---------------------------------------------------------------------------


def test_wire_seal_roundtrip_and_crc_detection():
    payload = b"stacked pytree bytes" * 100
    sealed = wire.seal(payload)
    assert wire.open_sealed(sealed) == payload
    # every single-bit flip past the version byte is detected
    for i in (1, 4, wire.SEAL_OVERHEAD, len(sealed) - 1):
        damaged = bytearray(sealed)
        damaged[i] ^= 0x10
        with pytest.raises(wire.CorruptFrameError):
            wire.open_sealed(bytes(damaged))
    with pytest.raises(wire.CorruptFrameError):
        wire.open_sealed(b"\x01\x00")  # truncated below the header


def test_wire_version_mismatch_fails_loudly():
    sealed = bytearray(wire.seal(b"x"))
    sealed[0] = wire.PROTOCOL_VERSION + 1
    with pytest.raises(wire.WireVersionError, match="version mismatch"):
        wire.open_sealed(bytes(sealed))
    # a LEGACY pre-seal frame (starts with the FMG1 message magic) is
    # named specifically in the diagnostic
    with pytest.raises(wire.WireVersionError, match="pre-seal"):
        wire.open_sealed(b"FMG1" + b"\x00" * 16)


def test_flip_bits_is_seeded_and_detected():
    sealed = wire.seal(b"some payload bytes")
    a = wire.flip_bits(sealed, seed=7)
    assert a == wire.flip_bits(sealed, seed=7)
    assert a != wire.flip_bits(sealed, seed=8)
    assert a[0] == sealed[0]  # the version byte is never corrupted
    with pytest.raises(wire.CorruptFrameError):
        wire.open_sealed(a)


def test_chaos_corrupt_fault_detected_and_dropped_over_tcp():
    """End to end over a real socket: a chaos-corrupted frame is
    detected by the receiver's CRC, counted, and dropped — never
    delivered; clean frames keep flowing on the same connection."""
    from fedml_tpu.core.transport.chaos import ChaosTransport, FaultPolicy
    from fedml_tpu.core.transport.tcp import TcpTransport

    socks = [socket.socket() for _ in range(2)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ip = {r: ("127.0.0.1", socks[r].getsockname()[1])
          for r in range(2)}
    for s in socks:
        s.close()
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    recv = TcpTransport(1, ip)
    # protect_types=() so the probe messages draw faults
    chaos = ChaosTransport(
        TcpTransport(0, ip),
        FaultPolicy(seed=3, corrupt_prob=0.5, protect_types=()),
    )
    seen = []

    class Obs:
        def receive_message(self, t, m):
            seen.append(m)

    recv.add_observer(Obs())
    t = threading.Thread(target=recv.handle_receive_message, daemon=True)
    try:
        recv.start()
        t.start()
        n = 40
        for i in range(n):
            chaos.send_message(Message(100, 0, 1, {"i": i}))
        deadline = time.monotonic() + 10
        want = n - chaos.stats["corrupted"]
        while len(seen) < want and time.monotonic() < deadline:
            time.sleep(0.02)
        counters = telemetry.METRICS.snapshot()["counters"]
        assert chaos.stats["corrupted"] > 0
        assert counters.get("transport.corrupt_frames", 0) == (
            chaos.stats["corrupted"]
        )
        # every non-corrupted frame arrived intact; no corrupted one
        # was delivered (the CRC dropped all of them)
        assert len(seen) == want
        delivered = sorted(m.get("i") for m in seen)
        assert len(set(delivered)) == len(delivered)
        assert set(delivered) <= set(range(n))
    finally:
        chaos.stop()
        recv.stop()
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_chaos_corrupt_marker_cleared_on_resend():
    """Application-level retries re-send the same Message OBJECT: a send
    whose draw says 'no corrupt' must clear a stale marker left by an
    earlier corrupted send of that object — otherwise a once-corrupted
    message is re-corrupted on every retry and can never heal."""
    from fedml_tpu.core.transport.chaos import ChaosTransport, FaultPolicy

    class _Inner:
        rank = 0
        _telemetry_deliver = True

        def __init__(self):
            self.markers = []

        def add_observer(self, obs):
            pass

        def send_message(self, msg):
            self.markers.append(getattr(msg, "chaos_corrupt", None))

    inner = _Inner()
    chaos = ChaosTransport(
        inner, FaultPolicy(seed=5, corrupt_prob=0.5, protect_types=())
    )
    msg = Message(100, 0, 1, {"x": 1})
    for _ in range(24):
        chaos.send_message(msg)
    assert chaos.stats["corrupted"] == sum(
        1 for m in inner.markers if m is not None
    )
    first = next(
        i for i, m in enumerate(inner.markers) if m is not None
    )
    assert any(m is None for m in inner.markers[first + 1:]), inner.markers


# ---------------------------------------------------------------------------
# 4. the membership ledger
# ---------------------------------------------------------------------------


def test_ledger_admits_beyond_world_with_stable_client_id():
    led = MembershipLedger(world_size=3, num_clients=4)
    assert led.active_ranks() == [1, 2]
    # a rank beyond the launch world joins mid-run: admitted, active
    # from the NEXT round boundary, with the client id it would have
    # had at launch
    assert led.admit(5, round_idx=3) == "admitted"
    assert led.client_id(5) == (5 - 1) % 4
    assert led.active_ranks() == [1, 2, 5]
    assert led.active_ranks(round_idx=3) == [1, 2]  # not this round
    assert led.active_ranks(round_idx=4) == [1, 2, 5]
    # a second JOIN from an active member is the rejoin path
    assert led.admit(5, round_idx=4) == "member"
    assert led.admit(1, round_idx=4) == "member"


def test_ledger_leave_and_return():
    led = MembershipLedger(3, 2)
    assert led.leave(2, round_idx=1)
    assert led.status(2) == "left"
    assert led.active_ranks() == [1]
    assert not led.leave(2, round_idx=2)  # already gone
    # a LEFT rank may return; same stable identity
    assert led.admit(2, round_idx=5) == "admitted"
    assert led.client_id(2) == 1
    assert led.active_ranks(round_idx=6) == [1, 2]


def test_ledger_eviction_is_permanent_and_counted():
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        led = MembershipLedger(3, 2)
        led.evict(2, round_idx=1)
        assert led.status(2) == "evicted"
        assert led.admit(2, round_idx=5) == "rejected"
        assert led.admit(2, round_idx=9) == "rejected"
        c = telemetry.METRICS.snapshot()["counters"]
        assert c["membership.evictions"] == 1
        assert c["membership.rejected_joins"] == 2
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_ledger_checkpoint_roundtrip_across_world_sizes():
    led = MembershipLedger(3, 4)
    led.admit(5, round_idx=2)
    led.leave(2, round_idx=3)
    led.evict(7, round_idx=3)
    blob = {k: np.array(v) for k, v in led.state_arrays().items()}
    # a relaunch with a DIFFERENT world_size restores the checkpoint's
    # world — the checkpoint, not the launch flag, is authoritative
    for relaunch_world in (2, 3, 9):
        fresh = MembershipLedger(relaunch_world, 4)
        fresh.load_arrays(blob)
        assert fresh.active_ranks() == [1, 5]
        assert fresh.status(2) == "left"
        assert fresh.status(7) == "evicted"
        assert fresh.client_id(5) == 0
        assert fresh.admit(7, round_idx=9) == "rejected"
    bad = dict(blob)
    bad["status"] = bad["status"][:-1]
    with pytest.raises(ValueError, match="disagree"):
        MembershipLedger(3, 4).load_arrays(bad)


# ---------------------------------------------------------------------------
# 5. elastic simulator: one compile per bucket
# ---------------------------------------------------------------------------


def test_sim_elastic_churn_is_cache_hits_not_recompiles():
    cfg = _cfg(num_clients=8, rounds=1, clients_per_round=6,
               elastic_buckets=True)
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        sim = FedAvgSim(create_model(cfg.model),
                        load_dataset(cfg.data), cfg)
        state = sim.init()
        # a seeded churn schedule inside the bucket: every size change
        # is a compile-cache hit, not a retrace
        schedule = [6, 3, 8, 1, 5, 6]
        for n in schedule:
            sim.set_cohort_size(n)
            state, m = sim.run_round(state)
        c = telemetry.METRICS.snapshot()["counters"]
        assert c["elastic.compile_cache_misses"] == 1, c
        assert c["elastic.compile_cache_hits"] == len(schedule) - 1, c
        assert np.isfinite(float(m["train_loss"]))
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_sharded_elastic_churn_is_cache_hits_not_recompiles():
    """The mesh-sharded twin: each shard pads its slice of the cohort
    to ITS bucket, the per-shard live count is a traced operand, and a
    churn schedule over shard-divisible cohort sizes costs one compile
    total."""
    from fedml_tpu.config import MeshConfig
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    mesh = make_mesh(client_axis=4, data_axis=1)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=16,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=1, clients_per_round=8, eval_every=1,
                      elastic_buckets=True),
        mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
        seed=0,
    )
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        sharded = ShardedFedAvg(model, data, cfg, mesh)
        state = sharded.init()
        # steady state first: round 0 compiles (and round 1 retraces
        # once as the donated state picks up its mesh-replicated
        # layout — pre-elastic behavior); churn AFTER that must be
        # pure cache hits
        for _ in range(2):
            state, m = sharded.run_round(state)
        telemetry.METRICS.reset()
        for n in (4, 8, 4):  # per-shard: 1, 2, 1 — inside bucket 2
            sharded.set_cohort_size(n)
            state, m = sharded.run_round(state)
        c = telemetry.METRICS.snapshot()["counters"]
        assert c.get("elastic.compile_cache_misses", 0) == 0, c
        assert c["elastic.compile_cache_hits"] == 3, c
        assert np.isfinite(float(m["train_loss"]))
        with pytest.raises(ValueError, match="divide evenly"):
            sharded.set_cohort_size(9)
        with pytest.raises(ValueError, match="per-shard"):
            sharded.set_cohort_size(12)
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_sim_set_cohort_size_validation():
    cfg = _cfg(num_clients=8, rounds=1, clients_per_round=6,
               elastic_buckets=True)
    sim = FedAvgSim(create_model(cfg.model), load_dataset(cfg.data), cfg)
    with pytest.raises(ValueError, match="does not fit"):
        sim.set_cohort_size(9)
    with pytest.raises(ValueError, match="does not fit"):
        sim.set_cohort_size(0)
    static = FedAvgSim(
        create_model(cfg.model), load_dataset(cfg.data), _cfg(
            num_clients=8, rounds=1, clients_per_round=6))
    with pytest.raises(ValueError, match="elastic_buckets"):
        static.set_cohort_size(3)


# ---------------------------------------------------------------------------
# 6. actor protocol over loopback
# ---------------------------------------------------------------------------


def _launch_clients(hub, world, model, data, cfg, ranks, **kw):
    clients = [
        FedAvgClientActor(r, world, hub.create(r), model, data, cfg,
                          **kw)
        for r in ranks
    ]
    threads = [threading.Thread(target=c.run, daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    return clients, threads


def test_join_beyond_world_admitted_at_next_round_boundary():
    """A rank OUTSIDE the launch world JOINs mid-run: the ledger admits
    it with a stable client id, the next round's broadcast includes it,
    and the run completes with the grown cohort contributing."""
    cfg = _cfg(num_clients=3, rounds=4, elastic_buckets=True)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(3, hub.create(0), model, cfg,
                               num_clients=3)
    clients, threads = _launch_clients(hub, 3, model, data, cfg, [1, 2])
    late_joiner = {}

    def admit_late():
        # wait for round 0 to be underway, then JOIN from rank 3
        deadline = time.monotonic() + 30
        while server.round_idx < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        c3, t3 = _launch_clients(hub, 3, model, data, cfg, [3])
        late_joiner["client"] = c3[0]
        late_joiner["thread"] = t3[0]
        c3[0].send_message(Message(MSG_TYPE_C2S_JOIN, 3, 0, {}))

    joiner = threading.Thread(target=admit_late, daemon=True)
    joiner.start()
    server.transport.start()
    server.start_round()
    server.run()
    joiner.join(timeout=10)
    for c in clients + [late_joiner["client"]]:
        c.transport.stop()
    for t in threads + [late_joiner["thread"]]:
        t.join(timeout=10)
    server.transport.stop()

    assert server.done.is_set(), server.failure
    assert server.membership["active"] == [1, 2, 3]
    assert server.dead_peers == set()
    assert server._ledger.client_id(3) == (3 - 1) % 3


def test_graceful_leave_spends_no_suspicion():
    """A client that LEAVEs after its round-1 result departs without
    being declared dead: the run completes over the survivors, the
    ledger says 'left', and no dead-peer/straggler accounting fires."""
    cfg = _cfg(num_clients=3, rounds=4)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        server = FedAvgServerActor(4, hub.create(0), model, cfg,
                                   num_clients=3)
        stay, stay_t = _launch_clients(hub, 4, model, data, cfg, [1, 2])
        leaver, leaver_t = _launch_clients(
            hub, 4, model, data, cfg, [3], leave_after_round=1)
        server.transport.start()
        server.start_round()
        server.run()
        for c in stay + leaver:
            c.transport.stop()
        for t in stay_t + leaver_t:
            t.join(timeout=10)
        server.transport.stop()

        assert server.done.is_set(), server.failure
        assert leaver[0].left.is_set()
        assert server.membership["left"] == [3]
        assert server.membership["active"] == [1, 2]
        assert server.dead_peers == set()
        c = telemetry.METRICS.snapshot()["counters"]
        assert c.get("membership.leaves", 0) == 1
        assert c.get("round.dead_peers", 0) == 0
        assert c.get("manager.dead_peer_events", 0) == 0
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()


def test_leave_message_handler_and_eviction_api():
    """Library-path LEAVE/evict entries: a LEAVE message marks the rank
    left mid-world; evict_rank bans it; a later JOIN from the evicted
    rank is rejected (never welcomed)."""
    cfg = _cfg(num_clients=3, rounds=2)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(4, hub.create(0), model, cfg,
                               num_clients=3)
    # no clients running: drive the handlers directly
    assert server.on_peer_join(2) == "member"
    server.on_peer_leave(3)
    assert server.membership["left"] == [3]
    assert server.client_ranks() == [1, 2]
    server.evict_rank(2)
    assert server.membership["evicted"] == [2]
    assert server.on_peer_join(2) == "rejected"
    # the ban is authoritative for results too: a RESULT from the
    # evicted rank still in flight when evict_rank voided its pending
    # one must NOT be re-accepted into the round
    from fedml_tpu.core.message import KEY_ROUND, MSG_TYPE_C2S_RESULT
    evicted_result = Message(
        MSG_TYPE_C2S_RESULT, 2, 0, {KEY_ROUND: server.round_idx}
    )
    live_result = Message(
        MSG_TYPE_C2S_RESULT, 1, 0, {KEY_ROUND: server.round_idx}
    )
    with server._lock:
        assert server._discard_locked(evicted_result)
        assert not server._discard_locked(live_result)
    # a returning LEFT rank is re-admitted (next boundary)
    assert server.on_peer_join(3) == "admitted"
    assert server._ledger.status(3) == "active"
    server.transport.stop()


def test_leaver_result_does_not_close_round_early():
    """The fast-path close means every LIVE worker reported: a graceful
    leaver's booked result stays valid for quorum/aggregation but must
    not stand in for a still-computing live member's — otherwise the
    LEAVE would silently discard that member's in-flight result as
    stale."""
    cfg = _cfg(num_clients=3, rounds=2)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(4, hub.create(0), model, cfg,
                               num_clients=3)
    # round 0 underway: ranks 1 and 2 reported, rank 3 still computing
    server._results = {1: object(), 2: object()}
    server.on_peer_leave(2)
    assert server.round_idx == 0, (
        "round closed early on a leaver's booked result"
    )
    assert set(server._results) == {1, 2}  # the leaver's stays booked
    server.transport.stop()


def test_server_restores_grown_world_from_checkpoint(tmp_path):
    """Checkpoint restore across a DIFFERENT world size: a world that
    grew to rank 3 mid-run checkpoints; a relaunch with the ORIGINAL
    world_size serves the checkpoint's grown membership (the restarted
    barrier must wait for the admitted rank, not the launch flag's
    world)."""
    from fedml_tpu.utils.checkpoint import RoundCheckpointer

    cfg = _cfg(num_clients=3, rounds=4, elastic_buckets=True)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(
        3, hub.create(0), model, cfg, num_clients=3,
        checkpointer=RoundCheckpointer(str(tmp_path / "ckpt")),
        checkpoint_every=1,
    )
    clients, threads = _launch_clients(hub, 3, model, data, cfg, [1, 2])
    admitted = {}

    def admit_late():
        deadline = time.monotonic() + 30
        while server.round_idx < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        c3, t3 = _launch_clients(hub, 3, model, data, cfg, [3])
        admitted["c"], admitted["t"] = c3[0], t3[0]
        c3[0].send_message(Message(MSG_TYPE_C2S_JOIN, 3, 0, {}))

    j = threading.Thread(target=admit_late, daemon=True)
    j.start()
    server.transport.start()
    server.start_round()
    server.run()
    j.join(timeout=10)
    for c in clients + [admitted["c"]]:
        c.transport.stop()
    for t in threads + [admitted["t"]]:
        t.join(timeout=10)
    server.transport.stop()
    assert server.done.is_set(), server.failure
    assert server.membership["active"] == [1, 2, 3]

    # relaunch with the LAUNCH world_size — the checkpoint wins
    hub2 = LoopbackHub()
    restored = FedAvgServerActor(
        3, hub2.create(0), model, cfg, num_clients=3,
        checkpointer=RoundCheckpointer(str(tmp_path / "ckpt")),
        checkpoint_every=1,
    )
    assert restored.client_ranks() == [1, 2, 3]
    assert restored.resumed_from == cfg.fed.num_rounds
    restored.transport.stop()


def test_supervisor_never_reactivates_left_clients(tmp_path):
    """A gracefully-LEFT client's clean exit must stay final: the
    Supervisor's server-crash handler reactivates prematurely-FINISHed
    clients, but a rank whose summary line says ``status: "left"`` is
    departed BY DESIGN — respawning it would re-admit a member the
    restored ledger says is gone."""
    from fedml_tpu.experiments.deploy import RankSpec, Supervisor

    sup = Supervisor(
        [RankSpec(r, ["true"]) for r in range(3)],
        log_dir=str(tmp_path),
    )
    lines = {
        1: '{"role": "client", "rank": 1, "status": "finished"}',
        2: '{"role": "client", "rank": 2, "status": "left"}',
    }
    for r, line in lines.items():
        p = tmp_path / f"rank{r}_try0.log"
        # stderr is merged into the same stream: '{'-prefixed shutdown
        # noise AFTER the summary must not mask the verdict
        p.write_text("startup noise\n" + line + "\n"
                     + "{malformed interpreter-shutdown fragment\n")
        sup.log_paths[r].append(str(p))
    assert not sup._client_departed(1)
    assert sup._client_departed(2) == "left"

    # clean exits while the server is down (no rank-0 process): the
    # finished client is judged premature and respawned, the LEFT one
    # stays gone
    sup._on_exit(2, 0)
    assert 2 in sup.departed and 2 not in sup._pending
    sup._on_exit(1, 0)
    assert 1 in sup._pending

    # a server crash reactivates finished clients — but never departed
    sup._pending.clear()
    sup.exited = {1: 0, 2: 0}
    sup._on_exit(0, -9)
    assert 1 in sup._pending and 1 not in sup.exited
    assert 2 not in sup._pending and sup.exited.get(2) == 0


def test_supervisor_never_reactivates_evicted_clients(tmp_path):
    """An evicted client's clean exit is a departure BY DESIGN too: the
    server FINISHes it with ``reason: "evicted"``, the client's summary
    reports ``status: "evicted"``, and the Supervisor must never respawn
    it — a respawned evictee's JOINs are silently rejected forever, so
    reactivation would burn the restart budget on a rank the ledger
    permanently banned."""
    from fedml_tpu.experiments.deploy import RankSpec, Supervisor

    sup = Supervisor(
        [RankSpec(r, ["true"]) for r in range(3)],
        log_dir=str(tmp_path),
    )
    p = tmp_path / "rank2_try0.log"
    p.write_text('{"role": "client", "rank": 2, "status": "evicted"}\n')
    sup.log_paths[2].append(str(p))
    assert sup._client_departed(2) == "evicted"
    sup._on_exit(2, 0)
    assert 2 in sup.departed and 2 in sup.evicted
    assert 2 not in sup._pending
    # a later server crash must not reactivate the evictee
    sup.exited[1] = 0
    plog = tmp_path / "rank1_try0.log"
    plog.write_text('{"role": "client", "rank": 1, "status": "finished"}\n')
    sup.log_paths[1].append(str(plog))
    sup._on_exit(0, -9)
    assert 2 not in sup._pending and sup.exited.get(2) == 0


def test_evict_after_grants_full_quarantine_rounds():
    """``--quarantine_evict_after K`` promises K recoverable rounds in
    quarantine before the permanent ban: the round that TRIPPED the
    quarantine must not count as a round 'sat without release' (with
    K=1 the old ``+ 1`` formula evicted instantly, zero chances to
    earn back)."""
    from fedml_tpu.core.reputation import QuarantinePolicy

    cfg = _cfg(num_clients=3, rounds=8, robust_method="median")
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(
        3, hub.create(0), model, cfg, num_clients=3,
        quarantine=QuarantinePolicy(threshold=0.5, evict_after=1),
    )
    try:
        good = jax.tree.map(np.asarray, server.state.variables)
        # an EWMA far above any release hysteresis: rank 2 cannot earn
        # its way out between the rounds this test closes
        bad = jax.tree.map(lambda v: np.asarray(v) + 1e3,
                           server.state.variables)
        results = {1: (good, 1.0), 2: (bad, 1.0)}
        # simulate the quarantine having TRIPPED at round 5
        server._reputation.ensure_size(3)
        server._reputation.scores[2] = 1e6
        server._reputation.quarantined_at[2] = 5
        # the tripping round closes: excluded, but NOT yet evicted —
        # evict_after=1 promises one full recoverable round
        included, _ = server._score_and_exclude(dict(results), 5)
        assert included == [1]
        assert server._ledger.status(2) != "evicted"
        # one full round sat unreleased: the ban lands
        server._score_and_exclude(dict(results), 6)
        assert server._ledger.status(2) == "evicted"
    finally:
        server.transport.stop()


def test_all_departed_replay_waits_for_admission():
    """The restart replay with EVERY member departed by design must not
    self-abort: no round is in flight pre-kickoff, so the no-live-
    workers check has nothing to abort — and the next admission IS the
    world, effective for the round the server is about to broadcast
    (not one past it, which would leave the restored round empty)."""
    cfg = _cfg(num_clients=3, rounds=4, elastic_buckets=True)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(3, hub.create(0), model, cfg,
                               num_clients=3)
    try:
        # the barrier's presumed-departure replay, pre-kickoff
        server.on_peer_leave(1)
        server.on_peer_leave(2)
        assert server.failure is None
        assert server.client_ranks() == []
        # a fresh rank announces: admitted IMMEDIATELY (no in-flight
        # round whose quorum the admission could retroactively raise)
        assert server.on_peer_join(3) == "admitted"
        assert server._member_workers() == [3]
    finally:
        server.transport.stop()


def test_static_world_drops_beyond_world_join():
    """Without --elastic the pre-elastic contract holds: a JOIN from a
    never-seen rank beyond the launch world is dropped un-ACKed (run.py
    documents 'a static server drops it') — admitting it would shift
    every member's cohort slot in a world configured as fixed. In-world
    rejoins and returning leavers are unaffected."""
    cfg = _cfg(num_clients=3, rounds=4)  # elastic OFF
    model = create_model(cfg.model)
    hub = LoopbackHub()
    server = FedAvgServerActor(3, hub.create(0), model, cfg,
                               num_clients=3)
    try:
        assert server.on_peer_join(7) is None
        assert server._ledger.status(7) is None
        assert server.client_ranks() == [1, 2]
        # in-world membership entries still work without --elastic
        assert server.on_peer_join(2) == "member"
        server.on_peer_leave(2)
        assert server.on_peer_join(2) == "admitted"
    finally:
        server.transport.stop()


def test_presumed_evicted_replay_keeps_ban():
    """The restart path must replay an eviction as an EVICTION: a
    checkpoint that predates the ban restores the rank ACTIVE, and
    replaying the supervisor's knowledge as a mere LEAVE (the
    presumed_left path) would let the banned rank JOIN back in —
    evict_rank (the presumed_evicted path) must keep it out."""
    cfg = _cfg(num_clients=3, rounds=4)
    model = create_model(cfg.model)

    hub = LoopbackHub()
    server = FedAvgServerActor(3, hub.create(0), model, cfg,
                               num_clients=3)
    # the downgrade: LEFT is rejoinable by design
    server.on_peer_leave(2)
    assert server._ledger.admit(2, 0) == "admitted"
    # the fix: a replayed eviction stays terminal
    server.evict_rank(2)
    assert server._ledger.admit(2, 5) == "rejected"
    assert server.membership["evicted"] == [2]
    server.transport.stop()


def test_elastic_rejects_custom_sampler():
    """elastic_buckets + a custom cohort sampler must fail loudly at
    construction: the bucketed round draws its own full-bucket
    permutation, so silently ignoring the sampler would report
    uniform-sampling results under the sampler's name."""
    cfg = _cfg(num_clients=4, rounds=2, elastic_buckets=True)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    with pytest.raises(ValueError, match="custom\\s+cohort sampler"):
        FedAvgSim(model, data, cfg,
                  sampler=lambda key, n, k: jnp.arange(k))


def test_manager_finish_reason_captured():
    """A FINISH carrying ``reason`` (the eviction path) records it on
    the manager so the deploy summary can report ``status: "evicted"``;
    a bare FINISH leaves it None (an ordinary wind-down)."""
    from fedml_tpu.core.manager import Manager
    from fedml_tpu.core.message import MSG_TYPE_FINISH

    hub = LoopbackHub()
    mgr = Manager(1, 2, hub.create(1))
    mgr.receive_message(
        MSG_TYPE_FINISH,
        Message(MSG_TYPE_FINISH, 0, 1, {"reason": "evicted"}),
    )
    assert mgr.finish_reason == "evicted"

    mgr2 = Manager(1, 2, LoopbackHub().create(1))
    mgr2.receive_message(
        MSG_TYPE_FINISH, Message(MSG_TYPE_FINISH, 0, 1, {})
    )
    assert mgr2.finish_reason is None


# ---------------------------------------------------------------------------
# 7. acceptance: supervised gRPC world — join, leave, SIGKILL, compile pin
# ---------------------------------------------------------------------------


def test_supervised_elastic_deploy_join_leave_sigkill(tmp_path):
    """The PR's end-to-end contract: a supervised 1-server + 2-client
    gRPC world runs with --elastic; client rank 3 (beyond the launch
    world) JOINs mid-run and is ADMITTED; client 2 LEAVEs gracefully
    after round 3; once a checkpoint carrying both membership events
    lands, the server is SIGKILLed; its restarted incarnation restores
    the ledger (serves {1, 3}, does not wait for the departed rank 2),
    completes every round, and each incarnation compiled the round
    function at most once per distinct bucket size."""
    from tests.test_deploy import _cfg_dict, _free_ports, _subproc_env
    from fedml_tpu.experiments.deploy import RankSpec, Supervisor

    rounds = 10
    leave_after = 3
    cfg_d = _cfg_dict(tmp_path, "fedavg", num_clients=3, rounds=rounds)
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg_d))
    ports = _free_ports(4)
    ip_path = tmp_path / "ip.json"
    ip_path.write_text(json.dumps(
        {str(r): ["127.0.0.1", ports[r]] for r in range(4)}
    ))
    telemetry_dir = tmp_path / "telemetry"
    base = [sys.executable, "-m", "fedml_tpu.experiments.run",
            "--config", str(cfg_path), "--backend", "grpc",
            "--world_size", "3", "--ip_config", str(ip_path),
            "--ready_timeout", "120", "--elastic",
            "--checkpoint_every", "1",
            "--telemetry_dir", str(telemetry_dir),
            "--heartbeat_interval", "0.5", "--heartbeat_timeout", "10",
            "--quorum_fraction", "0.5", "--round_deadline", "60",
            "--recovery_extensions", "2"]
    client = lambda r, *extra: [*base, "--role", "client",
                                "--rank", str(r), *extra]
    # the LEAVER (rank 2) and the LATE JOINER (rank 3) run OUTSIDE the
    # Supervisor: a graceful LEAVE is a clean exit-0 mid-run, which the
    # supervisor's server-crash reactivation would otherwise respawn —
    # and the pin here is precisely that the restored ledger keeps the
    # departure without anyone bringing the rank back
    specs = [
        RankSpec(0, [*base, "--role", "server"]),
        RankSpec(1, client(1)),
    ]
    sup = Supervisor(specs, max_restarts=3, env=_subproc_env(),
                     cwd=REPO, log_dir=str(tmp_path / "sup_logs"))
    result, errors = {}, []

    def drive():
        try:
            result.update(sup.run(timeout=420))
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=drive, daemon=True)

    import socket
    import subprocess

    def unsup(r, *extra, log_name=None):
        log = open(tmp_path / (log_name or f"rank{r}.log"), "w")
        proc = subprocess.Popen(
            client(r, *extra), env=_subproc_env(), cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT,
        )
        return proc, log

    ckpt_dir = os.path.join(str(tmp_path), "deploy", "ckpt")
    metrics0 = tmp_path / "telemetry" / "metrics_rank0.json"
    late_procs = []
    late_stop = threading.Event()

    def spawn_late():
        late_procs.append(
            unsup(3, log_name=f"rank3_try{len(late_procs)}.log"))
        return late_procs[-1][0]

    def babysit_late():
        # the late joiner lives OUTSIDE the Supervisor (whose world is
        # the launch ranks — and the leaver must NOT be respawned), but
        # it is still a crash-only client: an incarnation whose send
        # lands in the SIGKILLed server's dead window dies on
        # RetryExhausted like any PR 3 client. A real churning device
        # comes back — respawn it and let its JOIN run the rejoin
        # protocol against the restored ledger.
        while not late_stop.is_set():
            p, _ = late_procs[-1]
            if p.poll() is not None and p.returncode != 0:
                spawn_late()
            time.sleep(0.1)

    babysitter = threading.Thread(target=babysit_late, daemon=True)
    leaver = leaver_log = None
    killed = released = False
    try:
        # the LATE JOINER (rank 3, beyond world_size=3) starts FIRST,
        # before there is a server, and is held (SIGSTOP) in its
        # announce loop: its start-up — under a busy machine longer
        # than the world's ten rounds — is then no part of the race
        # between its JOIN and the world's end. The event is its
        # endpoint accepting a connection: the transport starts after
        # model and data are built, right before the first JOIN,
        # which finds no server to reach.
        late = spawn_late()
        warm_by = time.monotonic() + 240
        while True:
            assert late.poll() is None, (
                "late joiner died warming up: "
                + (tmp_path / "rank3_try0.log").read_text()[-2000:]
            )
            try:
                socket.create_connection(
                    ("127.0.0.1", ports[3]), timeout=1).close()
                break
            except OSError:
                assert time.monotonic() < warm_by, "late joiner never up"
                time.sleep(0.05)
        os.kill(late.pid, signal.SIGSTOP)

        t.start()
        leaver, leaver_log = unsup(2, "--leave_after_round",
                                   str(leave_after))
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and not killed:
            steps = []
            if os.path.isdir(ckpt_dir):
                steps = [int(d) for d in os.listdir(ckpt_dir)
                         if d.isdigit()]
            if not released and steps:
                # the world is demonstrably past round 0 (first
                # checkpoint on disk): the held joiner's next JOIN is
                # a MID-RUN admission
                os.kill(late.pid, signal.SIGCONT)
                released = True
                babysitter.start()
            counters = {}
            if metrics0.exists():
                try:
                    counters = json.loads(
                        metrics0.read_text()).get("counters", {})
                except ValueError:
                    pass  # mid-replace read; retry
            # SIGKILL only once the checkpointed state provably carries
            # the admission AND the departure
            if (steps and max(steps) >= leave_after + 1
                    and counters.get("membership.joins", 0) >= 1
                    and counters.get("membership.leaves", 0) >= 1):
                proc = sup.procs.get(0)
                if proc is not None and proc.poll() is None:
                    os.kill(proc.pid, signal.SIGKILL)
                    killed = True
            if not killed and (
                    not t.is_alive()
                    or "transport.bytes_by_type.finish" in counters):
                break  # the world ended first: nothing left to wait for
            time.sleep(0.05)
        assert killed, (
            "join+leave-covering checkpoint never appeared "
            f"(steps={steps}, counters={counters})"
        )

        t.join(timeout=440)
        late_stop.set()
        if babysitter.ident is not None:
            babysitter.join(timeout=10)
        assert not t.is_alive(), f"run never finished: {sup.restarts}"
        assert result, f"supervisor failed: {errors} ({sup.restarts})"
        summary = result["summary"]
        assert summary["rounds"] == rounds, summary
        assert summary["resumed_from"] >= 1, summary
        assert summary["elastic"] is True, summary
        # the world the run ENDED with: the late joiner is active and
        # the graceful leaver stayed LEFT across the restore — the
        # restarted barrier waited for the ledger's world {1, 3}, not
        # the launch flag's {1, 2}
        assert 3 in summary["membership"]["active"], summary
        assert summary["membership"]["left"] == [2], summary
        # the departure spent no suspicion: never declared dead
        assert summary["dead_peers"] == [], summary
        assert np.isfinite(summary["loss"]), summary
        assert result["restarts"][0] >= 1  # the SIGKILLed server
        assert leaver.wait(timeout=30) == 0  # clean exit, no respawn
        # the late joiner's LAST incarnation winds down clean on FINISH
        assert late_procs[-1][0].wait(timeout=30) == 0, late_procs

        # the compile pin, per incarnation: at most one round-fn
        # compile per distinct bucket size (cohorts 2 and 3 -> buckets
        # 2 and 4 -> misses <= 2 in any incarnation's metrics dump)
        checked = 0
        for f in (tmp_path / "telemetry").iterdir():
            if (f.name.startswith("metrics_rank0")
                    and f.suffix == ".json"):
                try:
                    c = json.loads(f.read_text()).get("counters", {})
                except ValueError:
                    continue  # truncated by the kill
                misses = c.get("elastic.compile_cache_misses", 0)
                assert misses <= 2, (f.name, c)
                checked += 1
        assert checked >= 1
    finally:
        late_stop.set()
        owned = late_procs + [(leaver, leaver_log)] * (leaver is not None)
        for proc, log in owned:
            if proc.poll() is None:
                proc.kill()
            log.close()
