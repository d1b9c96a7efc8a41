"""Compressed + mesh-sharded weight-update path (core/compress.py,
parallel/sharded_agg.py; docs/PERFORMANCE.md "Wire compression").

Four tiers:

1. codec properties — seeded-deterministic roundtrips, int8 error
   bounds, exact top-k, composition order, idempotence;
2. error feedback — the telescoping identity (transmitted + carry ==
   truth, exactly) and multi-round unbiasedness of the mean;
3. path integrity — ``compress='none'`` byte-identical (sim state AND
   wire payload), the >=4x delta-payload byte reduction measured by
   the ``transport.bytes_by_type`` counters over a real loopback
   world, decode-error screening, and the convergence pin (noniid
   battery at ``topk_int8`` reaches matched accuracy vs dense);
4. sharded-vs-replicated parity — every DefensePipeline rule x mesh
   size x bucket: selection/gather rules bitwise, sum rules within the
   ~1-ulp reassociation band (the tiers of ``tests/test_elastic.py``).
"""

import threading

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
from fedml_tpu.algorithms.distributed_fedavg import (
    FedAvgClientActor,
    FedAvgServerActor,
)
from fedml_tpu.algorithms.fedavg import (
    FedAvgSim,
    ServerState,
    local_reducer,
    make_server_optimizer,
    server_update,
)
from fedml_tpu.core import compress as C
from fedml_tpu.core import elastic as E
from fedml_tpu.core import telemetry
from fedml_tpu.core import tree as T
from fedml_tpu.core.message import (
    KEY_COMPRESSED,
    KEY_MODEL_PARAMS,
    KEY_NUM_SAMPLES,
    KEY_ROUND,
    MSG_TYPE_C2S_RESULT,
    Message,
)
from fedml_tpu.core.transport.loopback import LoopbackHub
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model
from fedml_tpu.parallel import ShardedAggregator, make_client_mesh
from fedml_tpu.parallel.sharded_agg import mesh_bucket


def _tree(key, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {
        "w": scale * jax.random.normal(k1, (23, 11), jnp.float32),
        "b": scale * jax.random.normal(k2, (17,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# tier 1: codec properties
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bounded():
    spec = C.CompressionSpec(method="int8", stochastic=False)
    x = _tree(jax.random.key(0), scale=3.0)
    rt = C.roundtrip_tree(spec, x, None)
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(rt)):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.abs(a).max() / 127.0
        # round-to-nearest: at most half a quantization step per entry
        assert np.abs(a - b).max() <= scale / 2 + 1e-7
    # all-zero leaf dequantizes to exact zeros (scale 0 guard)
    z = {"w": jnp.zeros((5, 5))}
    np.testing.assert_array_equal(
        np.asarray(C.roundtrip_tree(spec, z, None)["w"]), 0.0
    )


def test_int8_stochastic_rounding_is_seeded_and_unbiased():
    spec = C.CompressionSpec(method="int8", stochastic=True)
    # 0.3 under an absmax of 1.0 sits BETWEEN int8 levels (y = 38.1),
    # so the stochastic round genuinely draws — a tensor whose values
    # land exactly on levels would round identically under every seed
    x = {"w": jnp.concatenate([jnp.full((199,), 0.3),
                               jnp.ones((1,))])}
    key = jax.random.key(7)
    a = C.roundtrip_tree(spec, x, key)
    b = C.roundtrip_tree(spec, x, key)
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    c = C.roundtrip_tree(spec, x, jax.random.key(8))
    assert not np.array_equal(np.asarray(a["w"]), np.asarray(c["w"]))
    # E[Q(x)] = x: the mean over many seeded draws approaches the input
    step = 1.0 / 127
    mean = np.mean([
        np.mean(np.asarray(
            C.roundtrip_tree(spec, x, jax.random.key(i))["w"]
        )[:199])
        for i in range(64)
    ])
    # mean-of-64x199 Bernoulli(0.1)-rounding draws: std ~ step/200
    assert abs(mean - 0.3) < step / 2, mean


def test_topk_keeps_exact_topk_zeroes_rest():
    spec = C.CompressionSpec(method="topk", topk_frac=0.2,
                             stochastic=False)
    x = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(50,)),
                          jnp.float32)}
    rt = np.asarray(C.roundtrip_tree(spec, x, None)["w"])
    k = spec.leaf_k(50)
    kept = np.argsort(-np.abs(np.asarray(x["w"])))[:k]
    np.testing.assert_array_equal(rt[kept], np.asarray(x["w"])[kept])
    mask = np.ones(50, bool)
    mask[kept] = False
    np.testing.assert_array_equal(rt[mask], 0.0)


def test_topk_int8_is_sparsify_then_quantize():
    """The composed codec applies the two primitives in the pinned
    order: top-k first, then int8 over the SURVIVORS (so the int8
    scale is the top value's, not the dense absmax — both orders are
    exercised and must stay distinguishable)."""
    x = {"w": jnp.asarray([10.0, -8.0, 0.5, 0.25, 0.1, 0.05, 0.01,
                           0.004, 0.002, 0.001], jnp.float32)}
    both = C.CompressionSpec(method="topk_int8", topk_frac=0.2,
                             stochastic=False)
    rt = np.asarray(C.roundtrip_tree(both, x, None)["w"])
    # survivors are the top-2; their quantization scale is 10/127
    sparse = np.zeros(10, np.float32)
    sparse[:2] = [10.0, -8.0]
    scale = 10.0 / 127.0
    expected = np.round(sparse / scale) * scale
    np.testing.assert_allclose(rt, expected, rtol=1e-6)
    # the other order (quantize the DENSE tensor, then top-k) keeps
    # the same support here but different values when the dense absmax
    # differs from the survivor absmax — pin the distinction
    dense_q = np.asarray(
        C.roundtrip_tree(
            C.CompressionSpec(method="int8", stochastic=False), x, None
        )["w"]
    )
    assert not np.allclose(dense_q[2:], 0.0)  # int8 alone is dense


@pytest.mark.parametrize("method", ["int8", "topk", "topk_int8"])
def test_deterministic_roundtrip_is_idempotent(method):
    spec = C.CompressionSpec(method=method, topk_frac=0.15,
                             stochastic=False)
    x = _tree(jax.random.key(3))
    once = C.roundtrip_tree(spec, x, None)
    twice = C.roundtrip_tree(spec, once, None)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_payload_validation_catches_malformed():
    spec = C.CompressionSpec(method="topk_int8", topk_frac=0.1)
    x = _tree(jax.random.key(1))
    tmpl = C.payload_template(spec, x)
    good = jax.tree.map(np.asarray,
                        C.compress_tree(spec, x, jax.random.key(2)))
    assert C.validate_payload(tmpl, good) is None
    bad_idx = {**good, "b": {**good["b"],
                             "idx": np.asarray([1000], np.int32)}}
    assert "out of range" in C.validate_payload(tmpl, bad_idx)
    bad_keys = {**good, "b": {"vals": np.zeros(1, np.float32)}}
    assert "keys" in C.validate_payload(tmpl, bad_keys)
    bad_nan = {**good, "b": {**good["b"],
                             "scale": np.asarray(np.nan, np.float32)}}
    assert "non-finite" in C.validate_payload(tmpl, bad_nan)
    # a FINITE scale near f32 max still dequantizes q*scale to inf —
    # the poisoning vector the dense receive screen closes must stay
    # closed on the compressed wire
    bad_big = {**good, "b": {**good["b"],
                             "scale": np.asarray(3e38, np.float32)}}
    assert "out of f32 range" in C.validate_payload(tmpl, bad_big)
    bad_neg = {**good, "b": {**good["b"],
                             "scale": np.asarray(-1.0, np.float32)}}
    assert "out of f32 range" in C.validate_payload(tmpl, bad_neg)


# ---------------------------------------------------------------------------
# tier 2: error feedback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["int8", "topk", "topk_int8"])
def test_error_feedback_telescopes_exactly(method):
    """sum_t transmitted_t + residual_T == sum_t delta_t, to float
    round-off: with error feedback the compression error is carry,
    never accumulating bias."""
    spec = C.CompressionSpec(method=method, topk_frac=0.05)
    rng = np.random.default_rng(0)
    residual = None
    total_tx = {"w": np.zeros((30, 4), np.float32)}
    total_d = {"w": np.zeros((30, 4), np.float32)}
    for t in range(12):
        d = {"w": jnp.asarray(rng.normal(size=(30, 4)), jnp.float32)}
        _, deq, residual = C.apply_with_feedback(
            spec, d, residual, jax.random.key(t)
        )
        total_tx["w"] += np.asarray(deq["w"])
        total_d["w"] += np.asarray(d["w"])
    np.testing.assert_allclose(
        total_tx["w"] + np.asarray(residual["w"]), total_d["w"],
        rtol=1e-4, atol=1e-4,
    )


def test_nonfinite_round_resets_carry_instead_of_poisoning():
    """One NaN delta (lr spike) must cost exactly one round, like the
    dense path's screen: the carry resets instead of memorizing NaN —
    otherwise every later payload would be non-finite and the client
    silently excluded forever."""
    spec = C.CompressionSpec(method="topk_int8", topk_frac=0.2)
    good = {"w": jnp.ones((10,), jnp.float32)}
    bad = {"w": jnp.asarray([np.nan] + [1.0] * 9, jnp.float32)}
    _, _, res = C.apply_with_feedback(spec, good, None,
                                      jax.random.key(0))
    _, deq_bad, res = C.apply_with_feedback(spec, bad, res,
                                            jax.random.key(1))
    # the poisoned round's payload is non-finite (the server drops it)
    assert not np.all(np.isfinite(np.asarray(deq_bad["w"])))
    # ...but the carry reset, so the NEXT round is clean again
    np.testing.assert_array_equal(np.asarray(res["w"]), 0.0)
    _, deq_next, _ = C.apply_with_feedback(spec, good, res,
                                           jax.random.key(2))
    assert np.all(np.isfinite(np.asarray(deq_next["w"])))


def test_without_error_feedback_topk_biases():
    """Control for the telescoping pin: with the carry disabled, a
    persistent small coordinate is NEVER transmitted under top-k, while
    error feedback accumulates it into the carry until it wins a slot."""
    small = np.zeros(40, np.float32)
    small[7] = 0.05  # persistently small vs the big coordinate
    small[0] = 1.0
    d = {"w": jnp.asarray(small)}
    k1 = C.CompressionSpec(method="topk", topk_frac=0.025,
                           error_feedback=False)
    residual = None
    tx = np.zeros(40, np.float32)
    for t in range(30):
        _, deq, residual = C.apply_with_feedback(k1, d, residual,
                                                 None)
        tx += np.asarray(deq["w"])
    assert tx[7] == 0.0  # dropped forever without the carry
    k2 = C.CompressionSpec(method="topk", topk_frac=0.025,
                           error_feedback=True)
    residual, tx = None, np.zeros(40, np.float32)
    for t in range(30):
        _, deq, residual = C.apply_with_feedback(k2, d, residual,
                                                 None)
        tx += np.asarray(deq["w"])
    # the carry eventually promotes coordinate 7 into the top-k
    assert tx[7] > 0.0


# ---------------------------------------------------------------------------
# tier 3: path integrity (sim + wire)
# ---------------------------------------------------------------------------


def _sim_cfg(compress="none", elastic=False, rounds=3, clients=8,
             cohort=4, **fed_kw):
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=clients,
                        batch_size=16, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      eval_every=rounds, compress=compress,
                      compress_topk_frac=0.05,
                      elastic_buckets=elastic, **fed_kw),
        seed=0,
    )


def _build_sim(cfg):
    return FedAvgSim(create_model(cfg.model), load_dataset(cfg.data),
                     cfg)


def test_sim_compress_off_byte_identical():
    """``compress='none'`` (the default) leaves the compiled round
    byte-identical: same state trajectory, and no residual operand is
    ever allocated."""
    a = _build_sim(_sim_cfg())
    b = _build_sim(_sim_cfg("none"))
    sa, sb = a.init(), b.init()
    for _ in range(2):
        sa, _ = a.run_round(sa)
        sb, _ = b.run_round(sb)
    for la, lb in zip(jax.tree.leaves(sa.variables),
                      jax.tree.leaves(sb.variables)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert a._carry is None and b._carry is None


def test_sim_compressed_round_runs_and_reports_residual():
    sim = _build_sim(_sim_cfg("topk_int8"))
    state = sim.init()
    for _ in range(3):
        state, m = sim.run_round(state)
    assert "compress_residual_norm" in m
    assert np.isfinite(float(m["train_loss"]))
    # the carry is live and model-shaped at the bucket extent
    assert jax.tree.leaves(sim._carry)[0].shape[0] == 4


def test_sim_elastic_compressed_churn():
    sim = _build_sim(_sim_cfg("topk_int8", elastic=True))
    state = sim.init()
    state, _ = sim.run_round(state)
    sim.set_cohort_size(2)
    state, m = sim.run_round(state)
    assert np.isfinite(float(m["train_loss"]))


def test_sharded_sim_rejects_compression():
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    cfg = _sim_cfg("int8", clients=16, cohort=8)
    with pytest.raises(ValueError, match="not wired into the mesh"):
        ShardedFedAvg(create_model(cfg.model),
                      load_dataset(cfg.data), cfg,
                      make_mesh(client_axis=8, data_axis=1))


def _run_loopback_world(compress, shard=False, rounds=3, **fed_kw):
    """1 server + 2 clients over the loopback wire codec; returns
    (server, counters)."""
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cfg = ExperimentConfig(
            data=DataConfig(dataset="fake_mnist", num_clients=2,
                            batch_size=16, seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=1),
            fed=FedConfig(num_rounds=rounds, clients_per_round=2,
                          eval_every=rounds, compress=compress,
                          compress_topk_frac=0.05,
                          shard_aggregation=shard, **fed_kw),
            seed=0,
        )
        data = load_dataset(cfg.data)
        model = create_model(cfg.model)
        hub = LoopbackHub()
        server = FedAvgServerActor(3, hub.create(0), model, cfg,
                                   num_clients=2)
        clients = [
            FedAvgClientActor(r, 3, hub.create(r), model, data, cfg)
            for r in (1, 2)
        ]
        threads = [threading.Thread(target=c.run, daemon=True)
                   for c in clients]
        for t in threads:
            t.start()
        server.start_round()
        server.run()
        assert server.done.is_set()
        for t in threads:
            t.join(timeout=20)
        counters = dict(telemetry.METRICS.snapshot()["counters"])
    finally:
        telemetry.METRICS.enabled = was
        telemetry.METRICS.reset()
    return server, counters


def test_wire_bytes_by_type_and_4x_reduction():
    """The acceptance pin: >=4x DELTA-payload reduction, attributable
    via the per-type byte counters (heartbeats/ACKs/syncs counted
    under their own types, so they cannot pollute the claim)."""
    _, dense = _run_loopback_world("none")
    s_comp, comp = _run_loopback_world("topk_int8")
    d = dense["transport.bytes_by_type.c2s_result"]
    c = comp["transport.bytes_by_type.c2s_result"]
    assert d / c >= 4.0, (d, c)
    # the sync broadcast stays dense: its per-type bytes are unchanged
    assert (comp["transport.bytes_by_type.s2c_sync_model"]
            == dense["transport.bytes_by_type.s2c_sync_model"])
    # totals still present and consistent
    assert comp["transport.bytes_sent"] > 0
    assert comp.get("compress.decode_errors", 0) == 0
    # the run actually trained (finite final model)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(s_comp.variables))


def test_wire_compress_off_payload_is_dense_and_identical():
    """With the codec off, the result message carries exactly the
    dense KEY_MODEL_PARAMS payload — no compressed key, no extra
    bytes: the wire is byte-identical to the pre-codec build."""
    _, dense = _run_loopback_world("none")
    assert "compress.decode_errors" not in dense
    # re-encode a dense result message and confirm no compressed key
    cfg = _sim_cfg()
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    seen = []

    class Sink:
        def receive_message(self, t, m):
            seen.append(m)

    t0 = hub.create(0)
    t0.add_observer(Sink())
    client = FedAvgClientActor(1, 2, hub.create(1), model, data, cfg)
    host_vars = jax.tree.map(np.asarray, model.init(jax.random.key(0)))
    client._handle_sync(Message(
        2, 0, 1, {KEY_MODEL_PARAMS: host_vars, "client_index": 0,
                  KEY_ROUND: 0},
    ))
    t0.handle_receive_message(timeout=0.1)
    result = [m for m in seen if m.msg_type == MSG_TYPE_C2S_RESULT]
    assert result and result[0].get(KEY_COMPRESSED) is None
    assert result[0].get(KEY_MODEL_PARAMS) is not None


def test_stale_duplicate_sync_does_not_consume_residual():
    """A delayed duplicate sync of an OLDER round (chaos dup/delay)
    provokes a result the server's round-tag check discards — the
    client must not advance its error-feedback carry for it (the
    dense path loses nothing in the same scenario)."""
    cfg = _sim_cfg("topk_int8", clients=2, cohort=2)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    hub = LoopbackHub()
    hub.create(0)
    client = FedAvgClientActor(1, 3, hub.create(1), model, data, cfg)
    host_vars = jax.tree.map(np.asarray, model.init(jax.random.key(0)))

    def sync(r):
        client._handle_sync(Message(
            2, 0, 1, {KEY_MODEL_PARAMS: host_vars, "client_index": 0,
                      KEY_ROUND: r},
        ))

    sync(0)
    sync(1)
    res_after_1 = jax.tree.map(
        lambda x: np.asarray(x).copy(), client._residual
    )
    sync(0)  # the stale duplicate
    for a, b in zip(jax.tree.leaves(res_after_1),
                    jax.tree.leaves(client._residual)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert client._comp_cache[0] == 1  # cache still holds the latest


def test_quarantine_exclusion_slices_decompressed_stack():
    """The quarantine path on a compressed round: excluded ranks'
    rows are gathered out of the decompressed stack (results hold
    payloads, not dense rows) and the run keeps aggregating."""
    from fedml_tpu.core.reputation import QuarantinePolicy

    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cfg = _sim_cfg("topk_int8", clients=2, cohort=2, rounds=6)
        model = create_model(cfg.model)
        hub = LoopbackHub()
        server = FedAvgServerActor(
            4, hub.create(0), model, cfg, num_clients=2,
            quarantine=QuarantinePolicy(threshold=0.5,
                                        warmup_rounds=0),
        )
        for r in (1, 2, 3):
            hub.create(r)  # endpoints for the round-close broadcasts
        spec = server._cspec
        gvars = server.state.variables
        rkey = jax.random.key(0)
        for rnd in range(4):
            for rank in (1, 2, 3):
                # rank 3 anomalous every round: the EWMA crosses the
                # threshold after a couple of rounds, so later rounds
                # exercise the included != ranks slice of the
                # decompressed stack
                scale = 100.0 if rank == 3 else 0.01
                delta = jax.tree.map(
                    lambda g: scale * jax.random.normal(
                        jax.random.fold_in(rkey,
                                           97 * rnd + rank + g.size),
                        g.shape, jnp.float32,
                    ).astype(g.dtype),
                    server.state.variables,
                )
                payload = jax.tree.map(np.asarray, C.compress_tree(
                    spec, delta,
                    jax.random.fold_in(rkey, 31 * rnd + rank)
                ))
                server._handle_result(Message(
                    MSG_TYPE_C2S_RESULT, rank, 0,
                    {KEY_COMPRESSED: {"codec": spec.method,
                                      "payload": payload},
                     KEY_NUM_SAMPLES: 8.0, KEY_ROUND: rnd},
                ))
        assert server.round_idx == 4
        # the exclusion actually fired (rank 3 quarantined) and later
        # rounds aggregated the kept rows sliced from the stack
        assert server.quarantined_ranks == [3]
        assert all(np.all(np.isfinite(np.asarray(l)))
                   for l in jax.tree.leaves(server.variables))
    finally:
        telemetry.METRICS.enabled = was
        telemetry.METRICS.reset()


def test_server_counts_decode_errors_and_drops():
    """A malformed compressed payload (and a dense result on a
    compressed wire) is counted and dropped, never aggregated."""
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        cfg = _sim_cfg("topk_int8", clients=2, cohort=2)
        model = create_model(cfg.model)
        hub = LoopbackHub()
        server = FedAvgServerActor(3, hub.create(0), model, cfg,
                                   num_clients=2)
        # dense payload on a compressed wire
        server._handle_result(Message(
            MSG_TYPE_C2S_RESULT, 1, 0,
            {KEY_MODEL_PARAMS: jax.tree.map(
                np.asarray, model.init(jax.random.key(0))),
             KEY_NUM_SAMPLES: 5.0, KEY_ROUND: 0},
        ))
        # structurally-wrong compressed payload
        server._handle_result(Message(
            MSG_TYPE_C2S_RESULT, 2, 0,
            {KEY_COMPRESSED: {"codec": "topk_int8",
                              "payload": {"zzz": np.zeros(3)}},
             KEY_NUM_SAMPLES: 5.0, KEY_ROUND: 0},
        ))
        counters = telemetry.METRICS.snapshot()["counters"]
        assert counters.get("compress.decode_errors", 0) == 2
        assert not server._results  # nothing booked
    finally:
        telemetry.METRICS.enabled = was
        telemetry.METRICS.reset()


def test_convergence_matched_accuracy_noniid():
    """The acceptance convergence pin: the noniid battery at
    ``topk_int8`` (with error feedback) reaches the dense run's
    accuracy within the pinned tolerance."""
    kw = dict(clients=8, cohort=4, rounds=40)
    base = dict(dataset="fake_cifar10", num_clients=8, batch_size=16,
                partition_method="hetero", partition_alpha=0.5, seed=0)
    accs = {}
    for method in ("none", "topk_int8"):
        cfg = ExperimentConfig(
            data=DataConfig(**base),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(32, 32, 3)),
            train=TrainConfig(lr=0.05, epochs=1),
            fed=FedConfig(num_rounds=kw["rounds"],
                          clients_per_round=kw["cohort"],
                          eval_every=kw["rounds"], compress=method,
                          compress_topk_frac=0.05),
            seed=0,
        )
        sim = _build_sim(cfg)
        state = sim.init()
        for _ in range(kw["rounds"]):
            state, _ = sim.run_round(state)
        accs[method] = sim.evaluate_global(state)["acc"]
    assert accs["topk_int8"] >= accs["none"] - 0.03, accs


# ---------------------------------------------------------------------------
# tier 4: sharded-vs-replicated parity
# ---------------------------------------------------------------------------


def _agg_state(key):
    params = {"w": jax.random.normal(key, (6, 5), jnp.float32),
              "b": jnp.zeros((5,), jnp.float32)}
    variables = {"params": params}
    opt = make_server_optimizer("sgd", 1.0, 0.0)
    return ServerState(
        variables=variables,
        opt_state=opt.init(params),
        momentum=T.tree_zeros_like(params),
        round=jnp.asarray(0, jnp.int32),
    )


def _agg_case(rng, c, state):
    stacked = {"params": {
        "w": jnp.asarray(rng.normal(size=(c, 6, 5)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(c, 5)), jnp.float32),
    }}
    w = jnp.asarray(rng.integers(1, 50, size=(c,)), jnp.float32)
    return stacked, w


# the parity tiers (core/robust.py / docs/PERFORMANCE.md "Sharded
# server update", mirroring tests/test_elastic.py's padding tiers):
# the selection/gather REDUCE is bitwise — clipped deltas, Krum
# scores, the argmin, and every gather-rule aggregate are pinned
# byte-for-byte by test_sharded_reduce_is_bitwise below — while the
# full update programs differ in fusion boundaries around the
# elementwise optimizer chain (FMA contraction, clip-scale
# reassociation: a measured handful of ulps on the final params; a
# leaf whose global params are zero, like fresh biases, stays
# bitwise). The psum-reduced sum rules additionally reassociate
# across the shard boundary. End-to-end state parity is therefore
# pinned at the same tight band as PR 5's padding tiers.
_RULES = ("median", "krum", "multikrum", "fltrust", "trimmed_mean",
          "mean")


@pytest.mark.parametrize("rule", _RULES)
@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_update_matches_replicated(rule, n_shards):
    fed = FedConfig(
        robust_method=rule, robust_norm_clip=1.0,
        robust_num_adversaries=2 if "krum" in rule else 0,
    )
    cfg = ExperimentConfig(fed=fed)
    rng = np.random.default_rng(5)
    for c in (n_shards, 10, 17):
        state = _agg_state(jax.random.key(c))
        stacked, w = _agg_case(rng, c, state)
        rkey = jax.random.key(99)
        bucket = mesh_bucket(c, n_shards, False)
        padded, pw, valid = E.pad_stacked(stacked, w,
                                          state.variables, bucket)
        replicated = jax.jit(
            lambda s, st, ww, v, k: server_update(
                fed, cfg.train, 1, 32, st, s, ww, k,
                local_reducer(), valid=v,
            )
        )(padded, state, pw, valid, rkey)
        agg = ShardedAggregator(cfg, 1, 32,
                                mesh=make_client_mesh(n_shards))
        sharded = agg.update(state, stacked, w, rkey)
        for a, b in zip(jax.tree.leaves(replicated.variables),
                        jax.tree.leaves(sharded.variables)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_reduce_is_bitwise(n_shards):
    """The selection semantics themselves are BITWISE sharded vs
    replicated: per-row clipped deltas, the row-block Krum scores
    (full-D contraction, never partitioned), the argmin, and every
    gather-rule aggregate — compared at the reduce, before the
    optimizer's elementwise chain where FMA fusion may differ."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.core import robust
    from jax import shard_map
    from fedml_tpu.algorithms.fedavg import psum_reducer

    mesh = make_client_mesh(n_shards)
    rows = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())
    rng = np.random.default_rng(3)
    c = 2 * n_shards
    stacked = {
        "w": jnp.asarray(rng.normal(size=(c, 6, 5)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(c, 5)), jnp.float32),
    }
    wts = jnp.asarray(rng.integers(1, 9, size=(c,)), jnp.float32)
    valid = jnp.ones((c,), bool)

    def replicated(s, w, v):
        d = robust.clip_deltas_by_norm(s, 1.0)
        n_valid = jnp.sum(v.astype(jnp.int32))
        sc = robust.krum_scores(robust.pairwise_sq_dists(d), 1,
                                w > 0, n_valid)
        med = robust.coordinate_median(d, v)
        tm = robust.trimmed_mean(d, 0.1, v)
        flt = robust.fltrust(d, med, weights=w)[0]
        return d, sc, jnp.argmin(sc), med, tm, flt

    def sharded(s, w, v):
        def body(sl, wl, vl):
            d = robust.clip_deltas_by_norm(sl, 1.0)
            red = psum_reducer("clients")
            g, gw, gv = red.gather(d), red.gather(wl), red.gather(vl)
            n_valid = jnp.sum(gv.astype(jnp.int32))
            sc = robust.DefensePipeline._sharded_krum_scores(
                d, g, gw, red, 1, n_valid
            )
            med = robust.coordinate_median(g, gv)
            tm = robust.trimmed_mean(g, 0.1, gv)
            flt = robust.fltrust(g, med, weights=gw)[0]
            return g, sc, jnp.argmin(sc), med, tm, flt

        return shard_map(
            body, mesh=mesh,
            in_specs=(P("clients"), P("clients"), P("clients")),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False,
        )(s, w, v)

    out_rep = jax.jit(replicated)(stacked, wts, valid)
    out_sh = jax.jit(
        sharded, in_shardings=(rows, rows, rows),
        out_shardings=(rep,) * 6,
    )(
        jax.device_put(stacked, rows), jax.device_put(wts, rows),
        jax.device_put(valid, rows),
    )
    for a, b in zip(jax.tree.leaves(out_rep),
                    jax.tree.leaves(out_sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_update_composes_with_elastic_buckets():
    """With elastic buckets on, the mesh bucket is the power-of-two
    one rounded to the mesh — two cohort sizes inside one bucket share
    one executable (churn is a cache hit)."""
    fed = FedConfig(robust_method="median", elastic_buckets=True)
    cfg = ExperimentConfig(fed=fed)
    agg = ShardedAggregator(cfg, 1, 32, mesh=make_client_mesh(4))
    rng = np.random.default_rng(1)
    state = _agg_state(jax.random.key(0))
    for c in (5, 7, 6):  # all land in bucket 8
        stacked, w = _agg_case(rng, c, state)
        state = agg.update(state, stacked, w, jax.random.key(c))
    assert agg._update_cache.stats["misses"] == 1
    assert agg._update_cache.stats["hits"] == 2


def test_sharded_decompress_matches_host_decompress():
    spec = C.CompressionSpec(method="topk_int8", topk_frac=0.1)
    fed = FedConfig(compress="topk_int8", compress_topk_frac=0.1)
    cfg = ExperimentConfig(fed=fed)
    agg = ShardedAggregator(cfg, 1, 32, mesh=make_client_mesh(4),
                            spec=spec)
    gvars = {"w": jax.random.normal(jax.random.key(0), (12, 3)),
             "b": jnp.zeros((7,))}
    deltas = [
        {"w": jax.random.normal(jax.random.key(i), (12, 3)),
         "b": jax.random.normal(jax.random.key(100 + i), (7,))}
        for i in range(6)
    ]
    payloads = [
        C.compress_tree(spec, d, jax.random.key(50 + i))
        for i, d in enumerate(deltas)
    ]
    stacked = T.tree_stack(payloads)
    out = agg.decompress(stacked, gvars, 6)
    for i in range(6):
        want = jax.tree.map(
            lambda g, d: g + d, gvars,
            C.decompress_tree(spec, payloads[i], gvars),
        )
        got = jax.tree.map(lambda x, i=i: x[i], out)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loopback_world_sharded_compressed_defense():
    """End-to-end: a compressed wire + sharded aggregation + a
    selection defense completes and trains (the full tentpole stack
    in one world)."""
    server, counters = _run_loopback_world(
        "topk_int8", shard=True, robust_method="multikrum",
        robust_num_adversaries=1,
    )
    assert server.round_idx == 3
    assert counters.get("compress.decode_errors", 0) == 0
    assert counters["transport.bytes_by_type.c2s_result"] > 0


def test_sharded_vs_replicated_whole_world():
    """The same loopback world aggregated replicated vs mesh-sharded
    ends within the reassociation band (mean rule crosses psum)."""
    s_rep, _ = _run_loopback_world("none")
    s_sh, _ = _run_loopback_world("none", shard=True)
    for a, b in zip(jax.tree.leaves(s_rep.variables),
                    jax.tree.leaves(s_sh.variables)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
