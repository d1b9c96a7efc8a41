"""Device-resident bulk-client engine (core/bulk.py,
docs/PERFORMANCE.md "Bulk-client execution").

The contract, in tiers:

1. **Bulk-off identity**: ``client_block_size = 0`` (the default) takes
   exactly the stacked code path — the round trajectory is
   byte-identical to a default-config sim.
2. **Parity band**: bulk vs stacked at small C agrees within the
   reduce-reassociation ulp band (the streaming reduce sums blockwise
   f32 partials then combines, where the stacked reduce normalizes
   weights first and sums once over C — the same equality class as
   bucket padding / sharded psum). The band used below is
   rtol=2e-5 / atol=1e-7 on f32 leaves: a few ulp at parameter scale,
   the PR-5/PR-7/PR-10 tier.
3. **O(block) memory**: the compiled bulk program's analytic footprint
   is flat in C at fixed B (temp bytes within 1.5x across a 4x cohort
   sweep) while the stacked program's O(C) law is unchanged.
4. **Composition**: the PR-14 walls have fallen — compress rides a
   client-id-keyed error-feedback ClientStateBank through the block
   scan carry (core/statebank.py; convergence + telescoping pins in
   tests/test_statebank.py), selection/gather defenses run as
   block-folded streaming sketches (core/streamdef.py; parity bands +
   the adversary-recovery battery in tests/test_streamdef.py), and the
   gauss adversary keys per row on (round, client id). The quick
   construction-and-round acceptance pins live here.
5. **Elasticity**: cohort churn within the compiled block grid is a
   compile-cache hit; the donation audit passes on the block program.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core import bulk as BK
from fedml_tpu.core import memscope as M
from fedml_tpu.core import random as R
from fedml_tpu.core import telemetry
from fedml_tpu.core.adversary import AdversaryPolicy
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model
from fedml_tpu.parallel import ShardedFedAvg, make_mesh

# the stated ulp band (tier 2 above): reduce reassociation only
RTOL, ATOL = 2e-5, 1e-7


def _cfg(num_clients=8, rounds=3, cohort=8, adversary=None, **fed_kw):
    fed_kw.setdefault("eval_every", rounds)
    kw = {}
    if adversary is not None:
        kw["adversary"] = adversary
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=num_clients,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      **fed_kw),
        seed=0,
        **kw,
    )


def _sim(cfg, **sim_kw):
    return FedAvgSim(
        create_model(cfg.model), load_dataset(cfg.data), cfg, **sim_kw
    )


def _run(sim, rounds):
    state = sim.init()
    ms = []
    for _ in range(rounds):
        state, m = sim.run_round(state)
        ms.append({k: float(v) for k, v in m.items()})
    return state, ms


def _assert_state_close(s1, s2, rtol=RTOL, atol=ATOL):
    for a, b in zip(jax.tree.leaves(s1.variables),
                    jax.tree.leaves(s2.variables)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
        )


def _assert_state_bitwise(s1, s2):
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 1. bulk-off identity + construction surface
# ---------------------------------------------------------------------------


def test_bulk_off_is_default_path_byte_identical():
    s_default, m_default = _run(_sim(_cfg()), 3)
    s_off, m_off = _run(_sim(_cfg(client_block_size=0)), 3)
    _assert_state_bitwise(s_default, s_off)
    assert m_default == m_off


def test_bulk_spec_validation():
    with pytest.raises(ValueError, match="client_block_size"):
        BK.BulkSpec(block_size=-1)
    assert not BK.BulkSpec(0).enabled()
    assert BK.BulkSpec(4).enabled()
    assert BK.plan_blocks(8, 4, elastic=False) == 2
    assert BK.plan_blocks(9, 4, elastic=False) == 3
    # elastic buckets the BLOCK COUNT to the next power of two
    assert BK.plan_blocks(9, 4, elastic=True) == 4
    with pytest.raises(ValueError):
        BK.plan_blocks(0, 4, elastic=False)


# ---------------------------------------------------------------------------
# 2. parity band vs the stacked round
# ---------------------------------------------------------------------------


def test_bulk_matches_stacked_even_blocks():
    """C=8, B=4: two full blocks — the cohort draw is identical
    (same sampler, same key), only the reduction reassociates."""
    s_ref, m_ref = _run(_sim(_cfg()), 3)
    s_bulk, m_bulk = _run(_sim(_cfg(client_block_size=4)), 3)
    _assert_state_close(s_ref, s_bulk)
    for a, b in zip(m_ref, m_bulk):
        assert a["train_loss"] == pytest.approx(b["train_loss"],
                                                rel=1e-5)
        assert a["nonfinite_rejected"] == b["nonfinite_rejected"] == 0.0


def test_bulk_matches_stacked_partial_final_block():
    """C=6, B=4: the second block carries two padded (healed,
    zero-weight) slots — they must not perturb the aggregate."""
    cfg_ref = _cfg(cohort=6)
    s_ref, _ = _run(_sim(cfg_ref), 2)
    s_bulk, _ = _run(_sim(_cfg(cohort=6, client_block_size=4)), 2)
    _assert_state_close(s_ref, s_bulk)


def test_bulk_single_block_shortcut():
    """B >= C: one block, no scan — still the ulp band vs stacked."""
    s_ref, _ = _run(_sim(_cfg(cohort=4)), 2)
    s_bulk, _ = _run(_sim(_cfg(cohort=4, client_block_size=8)), 2)
    _assert_state_close(s_ref, s_bulk)


def test_bulk_batch_stats_parity():
    """Non-param collections (BN running stats) ride the partial sums
    too: Σ n·v / Σ n vs the stacked weighted mean — same band.

    The population is 64 clients so a sampled client trains ~6 steps,
    not the 94 of a 4-client split: on the installed XLA CPU backend a
    client's local update is no longer bitwise the same at vmap width
    2 (a block) and width 4 (the stacked cohort) — one step differs by
    3e-8 — and 94 SGD steps through BatchNorm grow that to 1e-2,
    which says nothing about how the partial sums fold."""
    base = dict(
        data=DataConfig(dataset="fake_cifar10", num_clients=64,
                        batch_size=16, seed=0),
        model=ModelConfig(name="resnet8", num_classes=10,
                          input_shape=(32, 32, 3)),
        train=TrainConfig(lr=0.05, epochs=1),
        seed=0,
    )
    cfg_ref = ExperimentConfig(
        fed=FedConfig(num_rounds=1, clients_per_round=4, eval_every=1),
        **base,
    )
    cfg_bulk = ExperimentConfig(
        fed=FedConfig(num_rounds=1, clients_per_round=4, eval_every=1,
                      client_block_size=2),
        **base,
    )
    data = load_dataset(cfg_ref.data)
    model = create_model(cfg_ref.model)
    s_ref, _ = FedAvgSim(model, data, cfg_ref).run_round(
        FedAvgSim(model, data, cfg_ref).init()
    )
    sim_b = FedAvgSim(model, data, cfg_bulk)
    s_bulk, _ = sim_b.run_round(sim_b.init())
    assert "batch_stats" in s_ref.variables
    _assert_state_close(s_ref, s_bulk, rtol=5e-5, atol=1e-6)


def test_bulk_fednova_parity():
    """FedNova's per-row tau normalization decomposes into the
    Σ n·tau / Σ n·(d/tau) partials exactly."""
    s_ref, _ = _run(_sim(_cfg(algorithm="fednova")), 2)
    s_bulk, _ = _run(
        _sim(_cfg(algorithm="fednova", client_block_size=4)), 2
    )
    _assert_state_close(s_ref, s_bulk)


def test_bulk_clip_noise_parity():
    """Per-row clip (preprocess) and aggregate noise (postprocess,
    same fold_in(rkey, 1) key) compose with the streaming reduce."""
    kw = dict(robust_norm_clip=0.5, robust_noise_stddev=1e-3)
    s_ref, _ = _run(_sim(_cfg(**kw)), 2)
    s_bulk, _ = _run(_sim(_cfg(client_block_size=4, **kw)), 2)
    _assert_state_close(s_ref, s_bulk)


def test_bulk_adversary_parity():
    """Per-row adversary modes (here: a colluding pair) inject
    identically per block — collusion_delta depends only on
    (seed, round, one row's shapes)."""
    adv = AdversaryPolicy(mode="collude", ranks=(1, 3), scale=2.0)
    s_ref, _ = _run(_sim(_cfg(adversary=adv)), 2)
    s_bulk, _ = _run(_sim(_cfg(adversary=adv, client_block_size=4)), 2)
    _assert_state_close(s_ref, s_bulk)


def test_bulk_fuse_composition():
    """Nested scans: the outer fused-round scan wraps the inner block
    scan. Per-round metrics stack [K, ...] like the stacked fused
    path, and the trajectory stays in the band vs unfused stacked."""
    s_ref, _ = _run(_sim(_cfg(rounds=4)), 4)
    sim = _sim(_cfg(rounds=4, client_block_size=4, fuse_rounds=2))
    state = sim.init()
    rows = []
    for _ in range(2):
        state, m = sim.run_block(state, 2)
        host = jax.device_get(m)
        rows.extend(
            {k: float(v[i]) for k, v in host.items()} for i in range(2)
        )
    assert len(rows) == 4
    _assert_state_close(s_ref, state)


# ---------------------------------------------------------------------------
# 3. O(block) memory: the flat-footprint pin + no-O(C)-buffer pin
# ---------------------------------------------------------------------------


def _bulk_mem_cfg(cohort, block, population=64):
    # FIXED population: the dataset argument bytes are constant across
    # the sweep, so any growth in the program footprint is the round's
    # own O(C) term — exactly what bulk must eliminate
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=population,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1, cohort_fused=False),
        fed=FedConfig(num_rounds=1, clients_per_round=cohort,
                      eval_every=10**9, client_block_size=block),
        seed=0,
    )


def test_bulk_program_footprint_flat_in_cohort():
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    try:
        M.reset()
        footprints = {}
        for c in (16, 64):
            cfg = _bulk_mem_cfg(c, block=8)
            sim = _sim(cfg)
            state = sim.init()
            sim.run_round(state)
            rec = M.program_record("sim_bulk", sim._program_key())
            assert rec is not None
            footprints[c] = rec["temp_bytes"] + rec["argument_bytes"]
        # flat in C at fixed B: the acceptance bound (<= 1.5x across a
        # 4x cohort sweep)
        assert footprints[64] <= 1.5 * footprints[16], footprints

        # contrast: the stacked program's footprint grows by the O(C)
        # per-client term over the same sweep (48 extra model+optimizer
        # replicas), while bulk's growth stays a small fraction of it —
        # the law bulk exists to flatten
        stacked = {}
        for c in (16, 64):
            cfg = _bulk_mem_cfg(c, block=0)
            sim = _sim(cfg)
            state = sim.init()
            sim.run_round(state)
            rec = M.program_record("sim_round", sim._bucket)
            stacked[c] = rec["temp_bytes"] + rec["argument_bytes"]
        stacked_growth = stacked[64] - stacked[16]
        bulk_growth = footprints[64] - footprints[16]
        assert stacked_growth > 2_000_000, stacked
        assert abs(bulk_growth) < 0.5 * stacked_growth, (
            footprints, stacked,
        )
    finally:
        telemetry.METRICS.enabled = was
        M.reset()


def test_bulk_compress_composes():
    """compress + bulk: the error-feedback residual lives in a
    client-id-keyed ClientStateBank threaded through the block scan
    carry (core/statebank.py), so the codec no longer reintroduces an
    O(cohort)-shaped round operand — construction succeeds and the
    compressed bulk run converges. (The client-id-vs-slot keying
    contract and the telescoping pin live in tests/test_statebank.py.)"""
    sim = _sim(_cfg(client_block_size=4, compress="int8"))
    _, ms = _run(sim, 3)
    assert sim._carry is not None
    assert sim._carry.num_rows == 8  # one row per CLIENT, not slot
    assert ms[-1]["train_loss"] < ms[0]["train_loss"]
    # both codecs construct
    _sim(_cfg(client_block_size=4, compress="topk_int8"))


# ---------------------------------------------------------------------------
# 4. full-stack composition: the PR-14 walls stay down
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "method", ["median", "trimmed_mean", "krum", "multikrum", "fltrust"]
)
def test_bulk_selection_defenses_compose(method):
    """Every selection/gather defense now runs at bulk scale as a
    block-folded streaming sketch (core/streamdef.py): construction
    succeeds and a defended bulk round stays finite on clean data.
    (Accuracy bands vs the stacked defenses and the adversary-recovery
    battery live in tests/test_streamdef.py.)"""
    kw = {"robust_method": method}
    if method == "krum" or method == "multikrum":
        kw["robust_num_adversaries"] = 1
    sim = _sim(_cfg(client_block_size=4, **kw))
    assert sim._stream_defense == method
    state, _ = _run(sim, 1)
    for leaf in jax.tree.leaves(state.variables):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_bulk_gauss_adversary_parity():
    """The gauss draw keys per ROW on (round, client id), so the bulk
    per-block application is independent of the chunking — the same
    ulp band vs the stacked path as every other adversary mode."""
    adv = AdversaryPolicy(mode="gauss", ranks=(1,), noise_stddev=0.1)
    s_ref, _ = _run(_sim(_cfg(adversary=adv)), 2)
    s_bulk, _ = _run(_sim(_cfg(adversary=adv, client_block_size=4)), 2)
    _assert_state_close(s_ref, s_bulk)


def test_bulk_clip_still_composes():
    # the rejection is about the reduce rule: clip + noise (the
    # pre/post stages) stay legal — constructing must not raise
    _sim(_cfg(client_block_size=4, robust_norm_clip=1.0,
              robust_noise_stddev=0.01))


# ---------------------------------------------------------------------------
# 5. elasticity as cache hits + donation audit + telemetry
# ---------------------------------------------------------------------------


def test_bulk_elastic_churn_is_cache_hit():
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    try:
        sim = _sim(_cfg(num_clients=16, cohort=6, client_block_size=4,
                        elastic_buckets=True))
        # ceil(6/4)=2 blocks -> bucket 2 -> 8 slots
        assert sim._n_blocks == 2 and sim._slots == 8
        state = sim.init()
        state, _ = sim.run_round(state)
        assert sim._round_fn._cache_size() == 1
        before = telemetry.METRICS.counter("elastic.compile_cache_hits")
        for n in (3, 8, 1, 6):
            sim.set_cohort_size(n)
            state, _ = sim.run_round(state)
        assert sim._round_fn._cache_size() == 1  # ONE block program
        assert telemetry.METRICS.counter(
            "elastic.compile_cache_hits"
        ) == before + 4
        with pytest.raises(ValueError, match="block grid"):
            sim.set_cohort_size(9)  # beyond the compiled grid
    finally:
        telemetry.METRICS.enabled = was


def test_bulk_static_set_cohort_size_rejected():
    sim = _sim(_cfg(client_block_size=4))
    with pytest.raises(ValueError, match="elastic_buckets"):
        sim.set_cohort_size(4)


def test_bulk_donation_audit_zero_misses():
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    try:
        M.reset()
        sim = _sim(_cfg(client_block_size=4))
        state = sim.init()
        state, _ = sim.run_round(state)
        jax.block_until_ready(jax.tree.leaves(state))
        assert telemetry.METRICS.counter("mem.donation_audits") >= 1
        assert telemetry.METRICS.counter("mem.donation_misses") == 0
        rec = M.program_record("sim_bulk", sim._program_key())
        assert rec is not None and rec.get("donation") == "ok"
    finally:
        telemetry.METRICS.enabled = was
        M.reset()


def test_bulk_round_gauges():
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    try:
        sim = _sim(_cfg(cohort=6, client_block_size=4))
        state = sim.init()
        sim.run_round(state)
        snap = telemetry.METRICS.snapshot()
        assert snap["gauges"]["bulk.block_size"] == 4.0
        assert snap["gauges"]["bulk.blocks_per_round"] == 2.0
        assert snap["gauges"]["bulk.padded_slots"] == 2.0
        assert snap["counters"]["bulk.rounds"] >= 1

        # bulk.rounds counts ROUNDS, not dispatches: a fused block of
        # K rounds increments by K (the perf.* wall/K discipline)
        fused = _sim(_cfg(rounds=4, cohort=6, client_block_size=4,
                          fuse_rounds=3))
        before = telemetry.METRICS.counter("bulk.rounds")
        state = fused.init()
        fused.run_block(state, 3)
        assert telemetry.METRICS.counter("bulk.rounds") == before + 3
    finally:
        telemetry.METRICS.enabled = was


# ---------------------------------------------------------------------------
# 6. sharded composition: per-shard streams + psum'd partials
# ---------------------------------------------------------------------------


def _stratified(n):
    return lambda k, nc, c: R.sample_clients_stratified(k, nc, c, n)


def test_sharded_bulk_matches_single_device():
    mesh = make_mesh(client_axis=4, data_axis=1)
    base = dict(
        data=DataConfig(dataset="fake_mnist", num_clients=16,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=2, clients_per_round=8, eval_every=2,
                      client_block_size=2),
        mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
        seed=0,
    )
    cfg = ExperimentConfig(**base)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    single = FedAvgSim(model, data, cfg, sampler=_stratified(4))
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    # 8-cohort over 4 shards = 2 per shard, B=2 -> 1 block per shard
    assert sharded._shard_blocks == 1
    s1, m1 = single.run_round(single.init())
    s2, m2 = sharded.run_round(sharded.init())
    _assert_state_close(s1, s2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        float(m1["train_loss"]), float(m2["train_loss"]), rtol=1e-5
    )


def test_sharded_bulk_partial_blocks_and_elastic():
    mesh = make_mesh(client_axis=2, data_axis=1)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=16,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=2, clients_per_round=6, eval_every=2,
                      client_block_size=2, elastic_buckets=True),
        mesh=MeshConfig(client_axis_size=2, data_axis_size=1),
        seed=0,
    )
    data = load_dataset(cfg.data)
    sharded = ShardedFedAvg(create_model(cfg.model), data, cfg, mesh)
    # 3 per shard, B=2 -> 2 blocks -> elastic bucket 2 -> 4 slots/shard
    assert sharded._shard_blocks == 2
    assert sharded._shard_slots == 4
    state = sharded.init()
    state, _ = sharded.run_round(state)
    assert sharded._round_fn._cache_size() == 1
    sharded.set_cohort_size(8)  # 4 per shard: fills the grid
    state, _ = sharded.run_round(state)
    sharded.set_cohort_size(2)
    state, _ = sharded.run_round(state)
    assert sharded._round_fn._cache_size() == 1
    with pytest.raises(ValueError, match="block grid"):
        sharded.set_cohort_size(10)
    for leaf in jax.tree.leaves(state.variables):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_bulk_run_loop_end_to_end():
    """The public run() loop (metrics sink, eval boundaries) drives a
    bulk sim to a finite, improving trajectory."""

    class Sink:
        def __init__(self):
            self.rows = []

        def log(self, row):
            self.rows.append(row)

    sink = Sink()
    sim = _sim(_cfg(num_clients=16, rounds=4, cohort=8,
                    client_block_size=4))
    state = sim.run(metrics_sink=sink)
    assert len(sink.rows) == 4
    assert sink.rows[-1]["train_loss"] < sink.rows[0]["train_loss"]
    assert "test_acc" in sink.rows[-1]
    for leaf in jax.tree.leaves(state.variables):
        assert np.all(np.isfinite(np.asarray(leaf)))
