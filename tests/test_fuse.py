"""Round fusion (docs/PERFORMANCE.md "Round fusion"): K rounds as one
compiled ``lax.scan`` program.

The contract, in tiers:

1. **K=1 identity**: ``fuse_rounds=1`` (the default) takes exactly the
   per-round code path — no block program is even built — and the round
   trajectory is byte-identical to a default-config sim.
2. **Bitwise sampling**: the fused block derives every round key from
   the CARRIED round counter (``fold_in`` of a traced int), which is
   bitwise-identical to the unfused loop's concrete fold — pinned both
   at the key-derivation level and end-to-end (per-round metrics match,
   which they cannot if a single cohort differs).
3. **Parity band**: fused-vs-unfused final state agrees within the
   PR-5/PR-7 reassociation band (XLA may fuse across scan iterations
   differently than across separate dispatches; same equality class as
   bucket padding / sharded reduction).
4. **Composition**: fuse x elastic (churn lands at the block boundary,
   block programs are cache-accounted), fuse x compress (the EF
   residual rides the scan carry and telescopes across blocks), fuse x
   adversary/defense, fuse x sharded (the scan wraps the shard_map'd
   body), and eval boundaries flush when ``eval_every % K != 0``.
5. **Donation**: the block program actually aliases its carries — no
   2x ServerState (or residual) footprint.
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
from fedml_tpu.core import fuse as F
from fedml_tpu.core import random as R
from fedml_tpu.core import telemetry
from fedml_tpu.core.adversary import AdversaryPolicy
from fedml_tpu.core.elastic import CompiledRoundCache
from fedml_tpu.core.perf import PerfMonitor, RoundProfiler
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model


def _cfg(num_clients=8, rounds=4, cohort=4, adversary=None, **fed_kw):
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
    data = load_dataset(cfg.data)
    return FedAvgSim(create_model(cfg.model), data, cfg, **sim_kw)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _run_unfused(sim, rounds):
    state = sim.init()
    ms = []
    for _ in range(rounds):
        state, m = sim.run_round(state)
        ms.append({k: float(v) for k, v in m.items()})
    return state, ms


def _run_fused(sim, rounds, k):
    state = sim.init()
    rows = []
    r = 0
    while r < rounds:
        n = min(k, rounds - r)
        state, m = sim.run_block(state, n)
        host = jax.device_get(m)
        rows.extend(
            {key: float(v[i]) for key, v in host.items()}
            for i in range(n)
        )
        r += n
    return state, rows


class _Sink:
    def __init__(self):
        self.rows = []

    def log(self, row):
        self.rows.append(row)


# ---------------------------------------------------------------------------
# 1. K=1 identity + construction contract
# ---------------------------------------------------------------------------


def test_fuse_one_is_default_path_byte_identical():
    s_default, m_default = _run_unfused(_sim(_cfg()), 4)
    s_one, m_one = _run_unfused(_sim(_cfg(fuse_rounds=1)), 4)
    for a, b in zip(_leaves(s_default), _leaves(s_one)):
        np.testing.assert_array_equal(a, b)
    assert m_default == m_one


def test_fuse_one_builds_no_block_program():
    sim = _sim(_cfg(fuse_rounds=1))
    assert sim._block_fn is None
    with pytest.raises(ValueError, match="fuse_rounds"):
        sim.run_block(sim.init(), 2)


def test_fuse_rounds_validated_at_construction():
    with pytest.raises(ValueError, match="fuse_rounds"):
        _sim(_cfg(fuse_rounds=0))


# ---------------------------------------------------------------------------
# 2. bitwise cohort sampling under the scan carry
# ---------------------------------------------------------------------------


def test_round_keys_bitwise_under_scan():
    """fold_in of the CARRIED (traced) round counter produces exactly
    the bits of the concrete per-round fold — the mechanism behind the
    fused block's bitwise-identical cohort sampling."""
    root = jax.random.key(0)

    def draw(r):
        rkey = R.round_key(root, r)
        return R.sample_clients(jax.random.fold_in(rkey, 0), 10, 4)

    concrete = np.stack([np.asarray(draw(r)) for r in range(6)])

    def body(r, _):
        return r + 1, draw(r)

    _, scanned = jax.jit(
        lambda: jax.lax.scan(body, jnp.asarray(0, jnp.int32), None,
                             length=6)
    )()
    np.testing.assert_array_equal(concrete, np.asarray(scanned))


# ---------------------------------------------------------------------------
# 3. fused-vs-unfused parity (state within the band, metrics per round)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 4])
def test_fused_matches_unfused(k):
    rounds = 4
    s_u, m_u = _run_unfused(_sim(_cfg(rounds=rounds)), rounds)
    s_f, m_f = _run_fused(
        _sim(_cfg(rounds=rounds, fuse_rounds=k)), rounds, k
    )
    assert len(m_f) == rounds
    for r, (a, b) in enumerate(zip(m_u, m_f)):
        assert set(a) == set(b)
        for key in a:
            np.testing.assert_allclose(
                a[key], b[key], rtol=1e-6, atol=1e-7,
                err_msg=f"round {r} metric {key}",
            )
    for a, b in zip(_leaves(s_u), _leaves(s_f)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_fused_partial_tail_block():
    """rounds not divisible by K: the tail block is shorter, the
    trajectory identical."""
    rounds, k = 5, 4
    s_u, m_u = _run_unfused(_sim(_cfg(rounds=rounds)), rounds)
    s_f, m_f = _run_fused(
        _sim(_cfg(rounds=rounds, fuse_rounds=k)), rounds, k
    )
    assert len(m_f) == rounds
    np.testing.assert_allclose(
        m_u[-1]["train_loss"], m_f[-1]["train_loss"],
        rtol=1e-6, atol=1e-7,
    )
    for a, b in zip(_leaves(s_u), _leaves(s_f)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# 4. donation: the block aliases its carries
# ---------------------------------------------------------------------------


def test_block_donates_server_state():
    sim = _sim(_cfg(fuse_rounds=2))
    state = sim.init()
    old_leaf = jax.tree.leaves(state.variables)[0]
    new_state, _ = sim.run_block(state, 2)
    jax.block_until_ready(jax.tree.leaves(new_state))
    assert old_leaf.is_deleted(), (
        "the fused block must donate ServerState (no 2x footprint)"
    )


def test_block_donates_ef_residual():
    sim = _sim(_cfg(fuse_rounds=2, compress="int8"))
    state = sim.init()
    state, _ = sim.run_block(state, 2)  # materializes the residual
    old_res_leaf = jax.tree.leaves(sim._carry)[0]
    state, _ = sim.run_block(state, 2)
    jax.block_until_ready(jax.tree.leaves(state))
    assert old_res_leaf.is_deleted(), (
        "the EF residual is a donated scan carry"
    )


# ---------------------------------------------------------------------------
# 4b. one call contract: (state, operand, n_active, carry) -> (state,
#     metrics, carry), whatever the configuration
# ---------------------------------------------------------------------------

_FEATURES = {
    # feature -> (fed kwargs, the carry the configuration names)
    "plain": ({}, None),
    "elastic": ({"elastic_buckets": True}, None),
    "compressed": ({"compress": "int8"}, "ef_residual"),
    "personalized": (None, "adapter"),  # tests.test_statebank._peft_cfg
}


@pytest.mark.parametrize("bulk", [False, True], ids=["stacked", "bulk"])
@pytest.mark.parametrize("feature", list(_FEATURES))
def test_run_round_one_contract(feature, bulk):
    """Every accepted combination of {plain, elastic, compressed,
    personalized} x {stacked, bulk} goes through the ONE dispatch:
    ``run_round`` returns ``(state, metrics)``, the carry kept between
    rounds is the one the configuration names (None otherwise), and a
    feature the configuration lacks is NO operand, donation or result
    of the compiled program — the plain program has the parent's
    operands (state + data) and donates the state alone."""
    from fedml_tpu.core import memscope
    from fedml_tpu.core import statebank as SB
    from tests.test_statebank import _peft_cfg, _peft_sim

    fed_kw, kind = _FEATURES[feature]
    block = {"client_block_size": 2} if bulk else {}
    if feature == "personalized":
        sim = _peft_sim(_peft_cfg(cohort=4, **block))
    else:
        sim = _sim(_cfg(**fed_kw, **block))
    assert sim._carry_kind == kind and sim._carry is None
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    memscope.reset()
    try:
        state = sim.init()
        n_state = len(jax.tree.leaves(state))
        n_data = len(jax.tree.leaves(sim._round_operand()))
        out = sim.run_round(state)
        assert len(out) == 2
        state, m = out
        assert np.isfinite(float(m["train_loss"]))
        carry = sim._carry
        if kind is None:
            assert carry is None
        elif kind == "adapter" or bulk:
            # client-id-keyed rows, named as the checkpoint names them
            assert isinstance(carry, SB.ClientStateBank)
            assert carry.name == kind
            assert carry.num_rows == sim.arrays.num_clients
            assert set(sim.bank_state()) == {kind}
        else:
            # the stacked round's slot-keyed residual: [bucket, ...]
            assert {leaf.shape[0] for leaf in jax.tree.leaves(carry)} \
                == {sim._bucket}
            assert sim.bank_state() == {}
        n_carry = len(jax.tree.leaves(carry))
        (exe,) = sim._round_fn._exes.values()
        args = jax.tree.leaves(
            exe.args_info, is_leaf=lambda a: hasattr(a, "donated")
        )
        assert len(args) == (
            n_state + n_data + int(sim._elastic) + n_carry
        )
        assert sum(a.donated for a in args) == n_state + n_carry
        assert sim._round_fn._donate == ((0, 3) if kind else (0,))
        # the carry is threaded: the next round consumes (donates) it
        state, m = sim.run_round(state)
        jax.block_until_ready(jax.tree.leaves(state))
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(carry))
        assert type(sim._carry) is type(carry)
        c = telemetry.METRICS.snapshot()["counters"]
        assert c["mem.donation_audits"] == 1
        assert c.get("mem.donation_misses", 0) == 0
    finally:
        telemetry.METRICS.enabled = False
        telemetry.METRICS.reset()
        memscope.reset()


# ---------------------------------------------------------------------------
# 5. composition: elastic / compress / adversary+defense / sharded
# ---------------------------------------------------------------------------


def test_fuse_elastic_churn_lands_at_block_boundary():
    """set_cohort_size between blocks takes effect at the NEXT block
    (the live count is a scan-invariant operand), and repeated block
    shapes are compile-cache hits."""
    telemetry.METRICS.enabled = True

    def snapshot():
        c = telemetry.METRICS.snapshot()["counters"]
        return (c.get("elastic.compile_cache_misses", 0),
                c.get("elastic.compile_cache_hits", 0))

    cfg = _cfg(rounds=4, fuse_rounds=2, elastic_buckets=True)
    sim = _sim(cfg)
    state = sim.init()
    m0, h0 = snapshot()
    state, b1 = sim.run_block(state, 2)
    m1, h1 = snapshot()
    assert (m1 - m0, h1 - h0) == (1, 0)  # first block: one compile
    sim.set_cohort_size(2)
    state, b2 = sim.run_block(state, 2)
    m2, h2 = snapshot()
    assert (m2 - m1, h2 - h1) == (0, 1)  # churn within bucket: a hit

    # the shrunk cohort actually took effect: mirror rounds 2..3 on an
    # unfused elastic sim churned at the same boundary
    ref = _sim(_cfg(rounds=4, elastic_buckets=True))
    rs = ref.init()
    for _ in range(2):
        rs, _ = ref.run_round(rs)
    ref.set_cohort_size(2)
    ref_rows = []
    for _ in range(2):
        rs, m = ref.run_round(rs)
        ref_rows.append(float(m["train_loss"]))
    host = jax.device_get(b2)
    np.testing.assert_allclose(
        ref_rows, np.asarray(host["train_loss"]), rtol=1e-6, atol=1e-7
    )
    for a, b in zip(_leaves(rs), _leaves(state)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("method", ["int8", "topk_int8"])
def test_fuse_compress_parity_and_residual_carry(method):
    """The EF residual rides the scan carry: fused-vs-unfused parity
    holds on the state AND the carried residual, and the per-round
    residual-norm metric rows are present."""
    rounds, k = 4, 2
    sim_u = _sim(_cfg(rounds=rounds, compress=method))
    s_u, m_u = _run_unfused(sim_u, rounds)
    sim_f = _sim(_cfg(rounds=rounds, fuse_rounds=k, compress=method))
    s_f, m_f = _run_fused(sim_f, rounds, k)
    for r, (a, b) in enumerate(zip(m_u, m_f)):
        np.testing.assert_allclose(
            a["train_loss"], b["train_loss"], rtol=1e-5, atol=1e-6,
            err_msg=f"round {r}",
        )
        assert "compress_residual_norm" in b
    for a, b in zip(_leaves(s_u), _leaves(s_f)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for a, b in zip(_leaves(sim_u._carry),
                    _leaves(sim_f._carry)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fuse_adversary_defense_parity():
    adv = AdversaryPolicy(mode="sign_flip", ranks=(1,), seed=3)
    rounds, k = 4, 2
    kw = dict(robust_method="krum", robust_num_adversaries=1)
    s_u, m_u = _run_unfused(
        _sim(_cfg(rounds=rounds, adversary=adv, **kw)), rounds
    )
    s_f, m_f = _run_fused(
        _sim(_cfg(rounds=rounds, fuse_rounds=k, adversary=adv, **kw)),
        rounds, k,
    )
    for a, b in zip(m_u, m_f):
        np.testing.assert_allclose(
            a["train_loss"], b["train_loss"], rtol=1e-6, atol=1e-7
        )
    for a, b in zip(_leaves(s_u), _leaves(s_f)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_fuse_sharded_matches_per_round():
    """ShardedFedAvg.run_block scans the shard_map'd round body: same
    trajectory as its own per-round loop."""
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    mesh = make_mesh(client_axis=4, data_axis=1)

    def build(fuse):
        cfg = ExperimentConfig(
            data=DataConfig(dataset="fake_mnist", num_clients=16,
                            batch_size=32, seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=1),
            fed=FedConfig(num_rounds=4, clients_per_round=8,
                          eval_every=4, fuse_rounds=fuse),
            mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
            seed=0,
        )
        data = load_dataset(cfg.data)
        return ShardedFedAvg(create_model(cfg.model), data, cfg, mesh)

    s_u, m_u = _run_unfused(build(1), 4)
    sharded = build(2)
    state = sharded.init()
    rows = []
    for _ in range(2):
        state, m = sharded.run_block(state, 2)
        host = jax.device_get(m)
        rows.extend(
            {k: float(v[i]) for k, v in host.items()} for i in range(2)
        )
    for a, b in zip(m_u, rows):
        np.testing.assert_allclose(
            a["train_loss"], b["train_loss"], rtol=1e-5, atol=1e-6
        )
    for a, b in zip(_leaves(s_u.variables), _leaves(state.variables)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fuse_rejects_custom_sampler_with_elastic():
    """The existing elastic+sampler rejection is unchanged by fusion
    (construction order: the check precedes the block build)."""
    with pytest.raises(ValueError, match="sampler"):
        _sim(_cfg(fuse_rounds=2, elastic_buckets=True),
             sampler=lambda k, n, c: jnp.arange(c))


# ---------------------------------------------------------------------------
# 6. block planning + the driver loops (eval boundaries, records)
# ---------------------------------------------------------------------------


def test_plan_blocks_cuts_at_boundaries():
    plan = list(F.plan_blocks(0, 7, 2, eval_every=3))
    assert plan == [(0, 2, False), (2, 1, True), (3, 2, False),
                    (5, 1, True), (6, 1, True)]
    # K=1 degenerates to the per-round schedule
    assert [b for b in F.plan_blocks(0, 3, 1, eval_every=2)] == [
        (0, 1, False), (1, 1, True), (2, 1, True)]
    # checkpoint boundaries cut too
    plan = list(F.plan_blocks(0, 8, 4, eval_every=100,
                              checkpoint_every=3))
    assert plan == [(0, 3, True), (3, 3, True), (6, 2, True)]
    # resumed start offset respected
    assert next(iter(F.plan_blocks(5, 8, 4, eval_every=100))) == \
        (5, 3, True)
    with pytest.raises(ValueError):
        list(F.plan_blocks(0, 4, 0, eval_every=1))


def test_run_fused_logs_every_round_and_evals_on_boundary():
    cfg = _cfg(rounds=7, fuse_rounds=4, eval_every=3)
    sink = _Sink()
    _sim(cfg).run(metrics_sink=sink)
    assert [r["round"] for r in sink.rows] == list(range(7))
    assert [r["round"] for r in sink.rows if "test_acc" in r] == \
        [2, 5, 6]
    # the unfused driver logs identical record keys
    ref = _Sink()
    _sim(_cfg(rounds=7, eval_every=3)).run(metrics_sink=ref)
    assert [set(r) for r in ref.rows] == [set(r) for r in sink.rows]
    for a, b in zip(ref.rows, sink.rows):
        np.testing.assert_allclose(
            a["train_loss"], b["train_loss"], rtol=1e-6, atol=1e-7
        )


def test_harness_fused_loop_checkpoint_boundary(tmp_path):
    """The generic harness loop drives run_block sims in blocks,
    checkpoints on the exact boundary round, and a restarted run
    resumes from it."""
    from fedml_tpu.experiments.harness import Experiment

    cfg = dataclasses.replace(
        _cfg(rounds=6, fuse_rounds=4, eval_every=3),
        checkpoint_every=3,
        out_dir=str(tmp_path),
        run_name="fused_ckpt",
    )
    summaries = Experiment(cfg).run()
    assert summaries and "train_loss" in summaries[0]
    import json
    import os

    rows = [
        json.loads(line)
        for line in open(os.path.join(
            tmp_path, "fused_ckpt_rep0", "metrics.jsonl"))
    ]
    assert [r["round"] for r in rows] == list(range(6))
    assert [r["round"] for r in rows if "test_acc" in r] == [2, 5]
    ckpt_dir = os.path.join(tmp_path, "fused_ckpt_rep0", "ckpt")
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)


def _decoder_sim(block):
    from tests import test_decoder as D

    return D._sim(D.TD.tiny_config(), block)


def _sharded_sim(_block):
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    cfg = dataclasses.replace(
        _cfg(num_clients=16, rounds=3, cohort=8, eval_every=2),
        mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
    )
    return ShardedFedAvg(
        create_model(cfg.model), load_dataset(cfg.data), cfg,
        make_mesh(client_axis=4, data_axis=1),
    )


@pytest.mark.parametrize("build,block,path", [
    (_decoder_sim, 0, "stacked"),   # FedAvgSim
    (_decoder_sim, 1, "bulk"),      # bulk FedAvgSim
    (_sharded_sim, 0, "sharded"),   # ShardedFedAvg on the CPU's devices
], ids=["FedAvgSim", "bulk", "ShardedFedAvg"])
def test_checkpointed_harness_logs_what_run_logs(tmp_path, build, block,
                                                 path):
    """There is ONE per-round loop: a run through ``Experiment`` with
    ``checkpoint_every`` set and ``sim.run(metrics_sink=...)`` log the
    same records round for round — list-valued counters included — and
    both open an anatomy round a round; the checkpoint is the loop's
    after-round hook."""
    import os

    from fedml_tpu.core import anatomy
    from fedml_tpu.experiments.harness import Experiment

    class Sink(_Sink):
        path = None

    telemetry.configure(telemetry_dir=str(tmp_path / "telemetry"), rank=0)
    anatomy.configure(anatomy=True)
    try:
        ran = Sink()
        sim = build(block)
        sim.run(metrics_sink=ran)
        rounds = sim.cfg.fed.num_rounds
        by_run = anatomy.ANATOMY.tracez()["entries"]
        anatomy.reset()
        anatomy.configure(anatomy=True)
        harnessed = Sink()
        sim = build(block)
        cfg = dataclasses.replace(
            sim.cfg, checkpoint_every=2, out_dir=str(tmp_path))
        Experiment._run_sim(sim, cfg, harnessed)
        by_harness = anatomy.ANATOMY.tracez()["entries"]
    finally:
        anatomy.reset()
        telemetry.shutdown()
    assert len(ran.rows) == rounds and harnessed.rows == ran.rows
    assert any("test_acc" in r for r in ran.rows)
    if build is _decoder_sim:
        assert all(len(r["moe_rows_held_by_client"]) == 2
                   for r in harnessed.rows)
    for entries in (by_run, by_harness):
        assert [(e["round"], e["path"]) for e in entries] == [
            (r, path) for r in range(rounds)]
    assert os.listdir(os.path.join(tmp_path, "ckpt"))


def test_harness_warns_and_falls_back_without_run_block(tmp_path):
    """fuse_rounds > 1 on a sim without the block protocol warns and
    runs per-round instead of crashing."""
    from fedml_tpu.experiments.harness import Experiment

    cfg = dataclasses.replace(
        _cfg(rounds=2, fuse_rounds=2),
        out_dir=str(tmp_path),
        run_name="nofuse",
    )
    cfg = dataclasses.replace(
        cfg, fed=dataclasses.replace(cfg.fed, algorithm="baseline")
    )
    with pytest.warns(UserWarning, match="fuse_rounds"):
        summaries = Experiment(cfg).run()
    assert summaries


# ---------------------------------------------------------------------------
# 7. perf observability under fusion
# ---------------------------------------------------------------------------


def test_perfmonitor_note_block_divides_wall():
    telemetry.METRICS.enabled = True
    mon = PerfMonitor(flops_per_round=1e9, peak_flops=1e12,
                      warmup_rounds=1)
    mon.note_block(8.0, 4)  # contains the warmup round: excluded whole
    assert mon._avg_wall is None and mon.rounds == 4
    mon.note_block(4.0, 4)
    assert mon.rounds == 8
    assert mon._avg_wall == pytest.approx(1.0)  # 4 s / 4 rounds
    g = telemetry.METRICS.snapshot()["gauges"]
    assert g["perf.rounds_per_s"] == pytest.approx(1.0)
    assert g["perf.mfu"] == pytest.approx(1e9 / 1e12)
    # note_round is the rounds=1 case
    mon2 = PerfMonitor(warmup_rounds=0)
    mon2.note_round(2.0)
    assert mon2._avg_wall == pytest.approx(2.0) and mon2.rounds == 1


def test_round_profiler_fused_manifest(tmp_path):
    prof = RoundProfiler(1, str(tmp_path), tag="t", fuse_rounds=4)
    assert prof.wants_capture
    prof.start_round(0)
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    prof.end_round(0, rounds=4)
    assert not prof.wants_capture  # budget spent
    assert prof.breakdowns[0]["rounds_in_window"] == 4
    import json
    import os

    manifest = json.load(open(os.path.join(
        tmp_path, "jax_profile", "round0", "capture.json")))
    assert manifest["fuse_rounds"] == 4
    assert manifest["rounds_in_window"] == 4
    path = prof.finish()
    assert json.load(open(path))["fuse_rounds"] == 4


def test_run_fused_with_profiler_captures_blocks(tmp_path):
    """--profile_rounds under fusion: windows cover whole blocks, the
    breakdown rows say how many rounds each window held, and the
    perf gauges exist."""
    telemetry.configure(telemetry_dir=str(tmp_path), rank=0)
    try:
        cfg = _cfg(rounds=6, fuse_rounds=2, eval_every=6,
                   profile_rounds=2)
        sink = _Sink()
        _sim(cfg).run(metrics_sink=sink)
        assert [r["round"] for r in sink.rows] == list(range(6))
        import json
        import os

        perf = json.load(open(os.path.join(
            tmp_path, "perf_rank0.json")))
        assert perf["fuse_rounds"] == 2
        assert len(perf["rounds"]) == 2
        for bd in perf["rounds"]:
            assert bd["rounds_in_window"] == 2
            assert bd["n_device_ops"] > 0
        g = telemetry.METRICS.snapshot()["gauges"]
        assert "perf.rounds_per_s" in g
    finally:
        # back to the all-disabled state: a configure() would leave a
        # tracer on for whatever this worker runs next
        telemetry.shutdown()


# ---------------------------------------------------------------------------
# 8. pipeline + cache-key generality
# ---------------------------------------------------------------------------


def test_block_pipeline_one_deep():
    pl = F.BlockPipeline()
    assert pl.flush() is None
    dm1 = {"a": jnp.arange(2.0)}
    assert pl.push(0, 2, dm1, 0.0, compiled=True) is None
    prev = pl.push(2, 2, {"a": jnp.arange(2.0) + 2}, 0.0)
    start, n, rows, wall, compiled = prev
    assert (start, n, compiled) == (0, 2, True)
    assert [float(r["a"]) for r in rows] == [0.0, 1.0]
    assert wall > 0
    start, n, rows, _, compiled = pl.flush()
    assert (start, n, compiled) == (2, 2, False)
    assert [float(r["a"]) for r in rows] == [2.0, 3.0]
    assert pl.flush() is None


def test_drive_flags_first_dispatch_of_each_length_as_compiled():
    """The shared driver excludes the FIRST dispatch of every distinct
    block length from the SLO surface (a fresh scan program compiles
    there — the eval-remainder lengths would otherwise put an XLA
    compile into the p99)."""

    class Monitor:
        def __init__(self):
            self.calls = []

        def note_block(self, wall, rounds, compiled=False):
            self.calls.append((rounds, compiled))

    mon = Monitor()
    dispatched = []

    def run_block(state, n):
        dispatched.append(n)
        return state, {"x": jnp.zeros((n,))}

    logged = []
    F.drive(
        run_block, None,
        F.plan_blocks(0, 10, 4, eval_every=5),  # lengths 4,1,4,1
        monitor=mon,
        eval_due=lambda r: False,
        evaluate=lambda state: {},
        log=logged.append,
    )
    assert dispatched == [4, 1, 4, 1]
    assert [r["round"] for r in logged] == list(range(10))
    # first length-4 and first length-1 blocks are compile-flagged;
    # their repeats are not
    assert mon.calls == [(4, True), (1, True), (4, False), (1, False)]


def test_note_block_compiled_excluded_from_slo():
    mon = PerfMonitor(warmup_rounds=0)
    mon.note_block(10.0, 1, compiled=True)  # fresh compile: excluded
    assert mon._avg_wall is None and mon.rounds == 1
    mon.note_block(2.0, 2)
    assert mon._avg_wall == pytest.approx(1.0)


def test_compiled_round_cache_accepts_tuple_keys():
    calls = []

    def fn(x):
        calls.append(1)
        return x * 2

    cache = CompiledRoundCache(fn, max_entries=4)
    x = jnp.ones((2,))
    cache((2, 4), x)
    cache((2, 8), x)
    cache((2, 4), x)
    assert cache.stats["misses"] == 2
    assert cache.stats["hits"] == 1
    assert len(cache) == 2
