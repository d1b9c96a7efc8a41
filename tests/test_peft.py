"""Parameter-efficient federated fine-tuning (fedml_tpu.peft,
docs/PERFORMANCE.md "Parameter-efficient federated fine-tuning").

The partition contract, in tiers:

1. **Round-0 byte-identity**: LoRA injection leaves the base
   parameters' init draws AND the forward pass bitwise unchanged
   (``lora_b`` is zero-init, flax derives each param's rng from its
   path + name).
2. **Frozen-base invariance**: across any number of rounds, on every
   composition path, the frozen subtree of the server state is
   bitwise the init values — no optimizer state, no delta, no drift.
3. **Adapter-only parity**: the partitioned local update equals a
   masked full-tree SGD step exactly (the trainable gradient does not
   depend on whether frozen gradients were computed).
4. **Composition**: codec roundtrip (O(cohort x adapter) residual),
   bulk block streaming (reduce-reassociation ulp band), fuse K>1,
   elastic churn-as-cache-hits, sharded-vs-single-device parity.
5. **Personalization no-leak**: private adapters never reach the
   server state or another client's bank row.
6. **Loud rejection**: every unsupported combo fails at parse /
   construction with a precise error — no silent vacuous paths.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import peft as PF
from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core import random as R
from fedml_tpu.core import telemetry
from fedml_tpu.algorithms.base import build_local_update, make_task
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.data.natural import synthetic_stackoverflow_nwp
from fedml_tpu.models import create_model
from fedml_tpu.peft import personal as PP
from fedml_tpu.peft.partition import ParamPartition

# the reduce-reassociation band (same tier as tests/test_bulk.py)
RTOL, ATOL = 2e-5, 1e-7

VOCAB = 128  # synthetic stand-in vocab; num_classes = VOCAB + 4


def _model_cfg(**extra):
    kw = {
        "vocab_size": VOCAB + 4, "num_layers": 1, "num_heads": 2,
        "embed_dim": 16, "max_len": 32,
    }
    kw.update(extra)
    return ModelConfig(
        name="transformer_lm", num_classes=VOCAB + 4, input_shape=(20,),
        extra=tuple(sorted(kw.items())),
    )


def _cfg(num_clients=8, rounds=3, cohort=4, **fed_kw):
    fed_kw.setdefault("eval_every", 10**9)
    fed_kw.setdefault("peft", "lora")
    fed_kw.setdefault("lora_rank", 2)
    fed_kw.setdefault("lora_alpha", 4.0)
    return ExperimentConfig(
        data=DataConfig(dataset="fake_stackoverflow_nwp",
                        num_clients=num_clients, batch_size=8, seed=0),
        model=_model_cfg(),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      **fed_kw),
        seed=0,
    )


def _data(cfg):
    # small sequences so max_n stays one batch-multiple and compiles
    # stay fast on the CPU tier
    return synthetic_stackoverflow_nwp(
        num_clients=cfg.data.num_clients, vocab_size=VOCAB, seed=0,
        sentences_low=4, sentences_high=8,
    )


def _sim(cfg, **kw):
    return FedAvgSim(create_model(cfg.model), _data(cfg), cfg, **kw)


def _run(sim, rounds):
    state = sim.init()
    ms = []
    for _ in range(rounds):
        state, m = sim.run_round(state)
        ms.append({k: float(v) for k, v in m.items()})
    return state, ms


def _bitwise(t1, t2, what=""):
    l1, l2 = jax.tree.leaves(t1), jax.tree.leaves(t2)
    assert len(l1) == len(l2), (what, len(l1), len(l2))
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


def _close(t1, t2, rtol=RTOL, atol=ATOL):
    for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


def _frozen_of(sim, state):
    return sim._peft.part.frozen(
        jax.device_get(state.variables["params"])
    )


# ---------------------------------------------------------------------------
# 1. injection + round-0 byte-identity
# ---------------------------------------------------------------------------


def test_lora_spec_validation():
    with pytest.raises(ValueError, match="lora_rank"):
        PF.LoRASpec(rank=0)
    with pytest.raises(ValueError, match="lora_alpha"):
        PF.LoRASpec(alpha=0.0)
    with pytest.raises(ValueError, match="lora_targets"):
        PF.LoRASpec(targets=("bogus",))
    with pytest.raises(ValueError, match="lora_targets"):
        PF.LoRASpec(targets=())
    with pytest.raises(ValueError, match="peft"):
        PF.LoRASpec.from_fed(FedConfig(peft="prefix_tuning"))
    assert PF.LoRASpec.from_fed(FedConfig()) is None


def test_lora_injection_targets_selectable():
    base = create_model(_model_cfg())
    for targets in (("q_proj",), PF.LORA_TARGETS):
        spec = PF.LoRASpec(rank=2, alpha=4.0, targets=targets)
        params = PF.apply_lora(base, spec).init(jax.random.key(0))[
            "params"
        ]
        block = params["Block_0"]
        for t in PF.LORA_TARGETS:
            has = "lora_a" in block[t]
            assert has == (t in targets), (t, targets)


def test_lora_rejects_non_transformer():
    lr = create_model(ModelConfig(name="lr", num_classes=10,
                                  input_shape=(28, 28, 1)))
    with pytest.raises(ValueError, match="TransformerLM"):
        PF.apply_lora(lr, PF.LoRASpec())
    with pytest.raises(ValueError, match="transformer"):
        PF.check_model_supported("resnet56")


def test_round0_byte_identity_vs_base_model():
    """Injection must not perturb the base params' init draws, and the
    zero-init branch must leave the forward bitwise unchanged."""
    base = create_model(_model_cfg())
    lora = PF.apply_lora(
        base, PF.LoRASpec(rank=2, alpha=4.0, targets=PF.LORA_TARGETS)
    )
    key = jax.random.key(7)
    vb = base.init(key)
    vl = lora.init(key)
    plan = PF.PeftPlan(part=PF.adapter_partition())
    # every non-adapter leaf (INCLUDING the trainable head) bitwise
    # equals the base model's init
    priv = PF.private_partition()
    _bitwise(priv.frozen(vl["params"]), vb["params"], "base params")
    tokens = jax.random.randint(jax.random.key(1), (3, 20), 0,
                                VOCAB + 4)
    lb = jax.device_get(base.apply_eval(vb, tokens))
    ll = jax.device_get(lora.apply_eval(vl, tokens))
    assert np.array_equal(
        np.asarray(lb).view(np.int32), np.asarray(ll).view(np.int32)
    ), "round-0 forward is not byte-identical"
    # and the sim's global eval agrees with the base model's at init
    sim = _sim(_cfg())
    state = sim.init()
    del plan, state


# ---------------------------------------------------------------------------
# 2. partition contract
# ---------------------------------------------------------------------------


def test_partition_split_merge_inverse():
    lora = PF.apply_lora(
        create_model(_model_cfg()),
        PF.LoRASpec(rank=2, alpha=4.0, targets=("q_proj", "v_proj")),
    )
    params = lora.init(jax.random.key(0))["params"]
    part = PF.adapter_partition()
    tr, fr = part.trainable(params), part.frozen(params)
    merged = part.merge(tr, fr)
    _bitwise(merged, params, "split/merge inverse")
    assert jax.tree.structure(merged) == jax.tree.structure(params)
    # trainable = adapters + head, nothing else
    paths = [
        "/".join(str(getattr(k, "key", k)) for k in p)
        for p, _ in jax.tree_util.tree_flatten_with_path(tr)[0]
    ]
    assert all(
        p.startswith("lm_head/") or p.endswith(("lora_a", "lora_b"))
        for p in paths
    ), paths
    # the mask view agrees with the pruning
    mask = part.mask(params)
    n_true = sum(jax.tree.leaves(mask))
    assert n_true == len(jax.tree.leaves(tr))
    # merge collision fails loudly
    with pytest.raises(ValueError, match="collision"):
        part.merge(tr, params)


def test_all_trainable_partition_matches_unpartitioned():
    """Vacuity pin: a partition selecting EVERYTHING reproduces the
    unpartitioned local update bitwise — split/merge plumbing adds no
    arithmetic."""
    cfg = _cfg()
    model = PF.apply_lora(
        create_model(cfg.model), PF.LoRASpec(rank=2, alpha=4.0)
    )
    data = _data(cfg)
    from fedml_tpu.data.federated import arrays_and_batch

    arrays, bs = arrays_and_batch(data, cfg.data)
    task = make_task("nwp")
    max_n = arrays.max_client_samples
    lu_ref = build_local_update(model, task, cfg.train, bs, max_n)
    lu_all = build_local_update(
        model, task, cfg.train, bs, max_n,
        partition=ParamPartition(lambda p: True),
    )
    variables = model.init(jax.random.key(0))
    rng = jax.random.key(3)
    out_ref = lu_ref(variables, arrays.idx[0], arrays.mask[0],
                     arrays.x, arrays.y, rng)
    out_all = lu_all(variables, arrays.idx[0], arrays.mask[0],
                     arrays.x, arrays.y, rng)
    _bitwise(jax.device_get(out_ref), jax.device_get(out_all),
             "all-trainable vs unpartitioned")


def test_adapter_only_parity_vs_masked_full_step():
    """One partitioned epoch == a hand-rolled full-tree run with
    frozen updates masked: the trainable gradient does not depend on
    whether frozen gradients were computed, and plain SGD is per-leaf.
    Equality is a few-ulp band, not bitwise — the reference is a
    DIFFERENT program over the same math (XLA fuses the two
    differently), so only the arithmetic is shared."""
    cfg = _cfg()
    model = PF.apply_lora(
        create_model(cfg.model),
        PF.LoRASpec(rank=2, alpha=4.0, targets=("q_proj", "v_proj")),
    )
    data = _data(cfg)
    from fedml_tpu.data.federated import arrays_and_batch
    from fedml_tpu.algorithms.base import _padded_perm

    arrays, bs = arrays_and_batch(data, cfg.data)
    task = make_task("nwp")
    max_n = arrays.max_client_samples
    part = PF.adapter_partition()
    lu = build_local_update(model, task, cfg.train, bs, max_n,
                            partition=part)
    variables = model.init(jax.random.key(0))
    rng = jax.random.key(5)
    out_vars, n_k, _ = jax.device_get(
        lu(variables, arrays.idx[0], arrays.mask[0], arrays.x,
           arrays.y, rng)
    )

    # test-side reference: replicate the exact batch schedule, take
    # full-tree grads, apply p + (-lr) * g to trainable leaves only
    lr = cfg.train.lr
    params = variables["params"]
    mask_row, idx_row = arrays.mask[0], arrays.idx[0]
    steps = max_n // bs
    ekey = jax.random.fold_in(rng, 0)
    perm = _padded_perm(ekey, mask_row, max_n)

    def loss_fn(p, x_b, y_b, w_b, skey):
        logits, _ = model.apply_train({"params": p}, x_b, skey)
        sums = task.metric_sums(logits, y_b, w_b)
        return sums["loss_sum"] / jnp.maximum(sums["w_sum"], 1.0)

    mask_tree = part.mask(params)
    for step in range(steps):
        take = jax.lax.dynamic_slice_in_dim(perm, step * bs, bs)
        b_idx, w_b = idx_row[take], mask_row[take]
        x_b = jnp.take(arrays.x, b_idx, axis=0)
        y_b = jnp.take(arrays.y, b_idx, axis=0)
        skey = jax.random.fold_in(ekey, step)
        grads = jax.grad(loss_fn)(params, x_b, y_b, w_b, skey)
        valid = bool(jnp.sum(w_b) > 0)
        if valid:
            params = jax.tree.map(
                lambda p, g, m: p + (-lr) * g if m else p,
                params, grads, mask_tree,
            )
    _close(
        out_vars["params"],
        part.trainable(jax.device_get(params)),
        rtol=1e-5, atol=1e-8,
    )


def test_frozen_base_and_server_state_shape():
    """Frozen base bitwise-unchanged across rounds; optimizer state and
    momentum exist ONLY at the trainable subtree's shape."""
    sim = _sim(_cfg(rounds=3))
    state = sim.init()
    frozen0 = _frozen_of(sim, state)
    n_tr_leaves = len(jax.tree.leaves(
        sim._peft.part.trainable(state.variables["params"])
    ))
    # gmf 0: no momentum buffer at all; with one, it has the trainable
    # subtree's shape (as the server optimizer's state has)
    assert state.momentum == ()
    import dataclasses
    gmf = dataclasses.replace(
        sim.cfg, fed=dataclasses.replace(sim.cfg.fed, gmf=0.5))
    assert len(jax.tree.leaves(_sim(gmf).init().momentum)) == n_tr_leaves
    state, ms = _run(sim, 3)
    _bitwise(_frozen_of(sim, state), frozen0, "frozen base")
    # the trainable subtree DID move
    tr0 = sim._peft.part.trainable(sim.init().variables["params"])
    trN = sim._peft.part.trainable(state.variables["params"])
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(tr0), jax.tree.leaves(trN))
    )
    assert all(np.isfinite(m["train_loss"]) for m in ms)


def test_peft_off_is_byte_identical():
    """peft='none' takes exactly the pre-PEFT code path."""
    base_cfg = dataclasses.replace(
        _cfg(), fed=FedConfig(num_rounds=2, clients_per_round=4,
                              eval_every=10**9)
    )
    s1, m1 = _run(_sim(base_cfg), 2)
    s2, m2 = _run(_sim(base_cfg), 2)
    _bitwise(s1.variables, s2.variables, "peft-off determinism")
    assert m1 == m2


def test_wire_byte_law_and_compound_ratio():
    """The delta-size law: adapter wire bytes are a small fraction of
    the full model, and with the codec stacked the full-model-
    equivalent reduction clears 100x on the benchmark shape."""
    from fedml_tpu.core.compress import CompressionSpec

    model_cfg = _model_cfg(vocab_size=2004, embed_dim=64,
                           num_layers=2)
    lora = PF.apply_lora(
        create_model(model_cfg),
        PF.LoRASpec(rank=4, alpha=8.0, targets=("q_proj", "v_proj")),
    )
    params = lora.init(jax.random.key(0))["params"]
    plan = PF.PeftPlan(part=PF.adapter_partition())
    dense_full = plan.full_wire_bytes(params)
    dense_agg = plan.adapter_wire_bytes(params)
    assert dense_agg < dense_full / 2
    cspec = CompressionSpec(method="topk_int8", topk_frac=0.01)
    ratio = PF.compound_wire_ratio(plan, cspec, params)
    assert ratio >= 100.0, ratio
    # no codec: the ratio is just the partition's
    assert PF.compound_wire_ratio(plan, None, params) == pytest.approx(
        dense_full / dense_agg
    )


def test_peft_gauges_and_donation_audit():
    telemetry.METRICS.enabled = True
    try:
        telemetry.METRICS.reset()
        sim = _sim(_cfg(rounds=1))
        state = sim.init()
        state, _ = sim.run_round(state)
        jax.block_until_ready(jax.tree.leaves(state))
        snap = telemetry.METRICS.snapshot()
        g = snap["gauges"]
        for name in ("peft.trainable_params", "peft.frozen_params",
                     "peft.adapter_wire_mb", "peft.wire_ratio"):
            assert name in g, (name, sorted(g))
        assert g["peft.trainable_params"] > 0
        assert g["peft.frozen_params"] > g["peft.trainable_params"]
        assert snap["counters"].get("mem.donation_misses", 0) == 0
    finally:
        telemetry.METRICS.enabled = False


# ---------------------------------------------------------------------------
# 3. composition pins
# ---------------------------------------------------------------------------


def test_codec_composition_residual_is_adapter_sized():
    cfg = _cfg(rounds=3, compress="topk_int8",
               compress_topk_frac=0.25)
    sim = _sim(cfg)
    state = sim.init()
    frozen0 = _frozen_of(sim, state)
    state, ms = _run(sim, 3)
    _bitwise(_frozen_of(sim, state), frozen0,
             "frozen base under codec")
    assert all(np.isfinite(m["train_loss"]) for m in ms)
    # the EF residual carries ONLY the aggregated subtree, per slot
    agg = sim._peft.agg_part.trainable(state.variables["params"])
    res_leaves = jax.tree.leaves(sim._carry)
    agg_leaves = jax.tree.leaves(agg)
    assert len(res_leaves) == len(agg_leaves)
    for r, a in zip(res_leaves, agg_leaves):
        assert r.shape == (sim._bucket,) + a.shape, (r.shape, a.shape)


def test_bulk_composition_parity():
    s_ref, m_ref = _run(_sim(_cfg(rounds=2)), 2)
    sim_b = _sim(_cfg(rounds=2, client_block_size=2))
    state = sim_b.init()
    frozen0 = _frozen_of(sim_b, state)
    s_bulk, m_bulk = _run(sim_b, 2)
    _close(s_ref.variables, s_bulk.variables)
    for a, b in zip(m_ref, m_bulk):
        assert a["train_loss"] == pytest.approx(b["train_loss"],
                                                rel=RTOL)
    _bitwise(_frozen_of(sim_b, s_bulk), frozen0,
             "frozen base under bulk")


def test_fuse_composition_parity():
    cfg = _cfg(rounds=4)
    s_ref, m_ref = _run(_sim(cfg), 4)
    sim_f = _sim(dataclasses.replace(
        cfg, fed=dataclasses.replace(cfg.fed, fuse_rounds=2)
    ))
    state = sim_f.init()
    frozen0 = _frozen_of(sim_f, state)
    state, dm1 = sim_f.run_block(state, 2)
    state, dm2 = sim_f.run_block(state, 2)
    _close(s_ref.variables, state.variables)
    fused_losses = [float(v) for v in np.asarray(
        jax.device_get(dm1["train_loss"])
    )] + [float(v) for v in np.asarray(jax.device_get(dm2["train_loss"]))]
    for ref, fused in zip(m_ref, fused_losses):
        assert ref["train_loss"] == pytest.approx(fused, rel=RTOL)
    _bitwise(_frozen_of(sim_f, state), frozen0,
             "frozen base under fusion")


def test_elastic_composition_churn_is_cache_hits():
    sim = _sim(_cfg(rounds=4, elastic_buckets=True))
    state = sim.init()
    frozen0 = _frozen_of(sim, state)
    state, _ = sim.run_round(state)
    for n in (2, 3, 4):
        sim.set_cohort_size(n)
        state, m = sim.run_round(state)
        assert np.isfinite(float(m["train_loss"]))
    # churn across cohorts compiled exactly ONE program
    assert sim._round_fn._cache_size() == 1
    _bitwise(_frozen_of(sim, state), frozen0,
             "frozen base under elastic churn")


def test_sharded_parity_and_frozen_base():
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    cfg = dataclasses.replace(
        _cfg(rounds=2),
        mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
    )
    data = _data(cfg)
    model = create_model(cfg.model)
    mesh = make_mesh(client_axis=4, data_axis=1)
    sharded = ShardedFedAvg(model, data, cfg, mesh)
    st = sharded.init()
    frozen0 = sharded._peft.part.frozen(
        jax.device_get(st.variables["params"])
    )
    for _ in range(2):
        st, m = sharded.run_round(st)
    single = FedAvgSim(
        model, data, cfg,
        sampler=lambda k, n, c: R.sample_clients_stratified(k, n, c, 4),
    )
    st2, _ = _run(single, 2)
    _close(st.variables, st2.variables)
    _bitwise(
        sharded._peft.part.frozen(
            jax.device_get(st.variables["params"])
        ),
        frozen0, "sharded frozen base",
    )


# ---------------------------------------------------------------------------
# 4. personalization
# ---------------------------------------------------------------------------


def test_personalize_no_leak_and_bank_semantics():
    cfg = _cfg(num_clients=8, rounds=3, cohort=3,
               peft_personalize=True)
    sim = _sim(cfg)
    state = sim.init()
    plan = sim._peft
    # the bank is created LAZILY on the first round (so a later
    # init()-for-a-snapshot call can never reset a trained bank)
    assert sim._carry is None
    params0 = jax.device_get(state.variables["params"])
    server_adapters0 = plan.private.trainable(params0)
    # the pre-round-0 baseline: every row at the init adapter values
    bank = jax.device_get(PP.init_bank(plan, params0, 8))
    sampled_ever = set()
    for r in range(3):
        prev_bank = bank
        state, m = sim.run_round(state)
        bank = jax.device_get(sim._carry.rows)
        # recompute the round's cohort from the same seeded draw
        rkey = R.round_key(sim.root_key, jnp.asarray(r, jnp.int32))
        cohort = set(np.asarray(jax.device_get(sim.sampler(
            jax.random.fold_in(rkey, 0), 8, 3
        ))).tolist())
        sampled_ever |= cohort
        for c in range(8):
            row_prev = [np.asarray(l[c]) for l in
                        jax.tree.leaves(prev_bank)]
            row_new = [np.asarray(l[c]) for l in
                       jax.tree.leaves(bank)]
            same = all(np.array_equal(a, b)
                       for a, b in zip(row_prev, row_new))
            if c in cohort:
                assert not same, f"sampled client {c} row did not train"
            else:
                assert same, f"unsampled client {c} row changed"
        assert np.isfinite(float(m["train_loss"]))
    # no-leak pin 1: the server state's adapter leaves are bitwise the
    # init values — private adapters never reached the aggregate
    _bitwise(
        plan.private.trainable(
            jax.device_get(state.variables["params"])
        ),
        server_adapters0, "server-side adapters",
    )
    # no-leak pin 2: two trained clients' rows differ from each other
    trained = sorted(sampled_ever)[:2]
    assert len(trained) >= 2
    a, b = trained
    assert any(
        not np.array_equal(np.asarray(l[a]), np.asarray(l[b]))
        for l in jax.tree.leaves(bank)
    ), "personalized adapters identical across clients"
    # the shared head DID aggregate
    head0 = params0["lm_head"]
    headN = jax.device_get(state.variables["params"])["lm_head"]
    assert not np.array_equal(np.asarray(head0["kernel"]),
                              np.asarray(headN["kernel"]))
    # per-client personalized model differs from the global model
    pv = PP.personal_variables(
        plan, state.variables, sim._carry.rows, a
    )
    gm = sim.evaluate_global(state)
    assert set(gm) >= {"acc", "loss"}
    assert any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(pv),
                        jax.tree.leaves(state.variables))
        if np.shape(x) == np.shape(y)
    )


# ---------------------------------------------------------------------------
# 5. loud rejections + config plumbing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fed_kw,err", [
    (dict(peft_personalize=True, compress="int8"), "compress"),
    (dict(peft_personalize=True, robust_method="krum"),
     "robust_method"),
    (dict(peft="none", peft_personalize=True), "peft_personalize"),
])
def test_personalize_rejection_table(fed_kw, err):
    # bulk / elastic / fuse_rounds now COMPOSE with personalization
    # (the adapter bank threads the scan carry — tests/test_statebank.py);
    # compress and defended robust_method remain loud rejections.
    with pytest.raises(ValueError, match=err):
        _sim(_cfg(**fed_kw))


@pytest.mark.parametrize("fed_kw", [
    dict(peft_personalize=True, client_block_size=2),
    dict(peft_personalize=True, elastic_buckets=True),
    dict(peft_personalize=True, fuse_rounds=2),
])
def test_personalize_composition_accepted(fed_kw):
    sim = _sim(_cfg(num_clients=8, rounds=2, cohort=4, **fed_kw))
    state = sim.init()
    state, m = sim.run_round(state)
    assert np.isfinite(float(m["train_loss"]))
    assert sim._carry is not None


def test_personalize_bank_survives_init_snapshot():
    """The repo's call-init()-again-for-a-snapshot idiom must not
    reset a trained personalization bank."""
    sim = _sim(_cfg(num_clients=8, rounds=2, cohort=3,
                    peft_personalize=True))
    state = sim.init()
    state, _ = sim.run_round(state)
    trained = jax.device_get(sim._carry.rows)
    sim.init()  # snapshot idiom — must be side-effect-free here
    _bitwise(jax.device_get(sim._carry.rows), trained,
             "bank after init() snapshot")


def test_vocab_smaller_than_data_rejected():
    cfg = _cfg()
    small = dataclasses.replace(
        cfg, model=_model_cfg(vocab_size=8)
    )
    with pytest.raises(ValueError, match="vocab_size"):
        FedAvgSim(create_model(small.model), _data(cfg), small)


def test_personalize_checkpoint_accepted():
    # the private bank rides the round checkpoint as the harness's
    # {"server", "bank"} composite now (tests/test_statebank.py pins
    # the bitwise kill/restore), so the combo constructs AND parses
    cfg = dataclasses.replace(_cfg(peft_personalize=True),
                              checkpoint_every=5)
    sim = _sim(cfg)
    state = sim.init()
    state, _ = sim.run_round(state)
    assert "adapter" in sim.bank_state()
    from fedml_tpu.experiments.run import parse_args

    parsed, _ = parse_args(["--algorithm", "fedavg", "--dataset",
                            "fake_stackoverflow_nwp", "--model",
                            "transformer_lm", "--peft", "lora",
                            "--peft_personalize",
                            "--checkpoint_every", "5"])
    assert parsed.fed.peft_personalize
    assert parsed.checkpoint_every == 5


def test_personalize_adversary_rejected():
    from fedml_tpu.core.adversary import AdversaryPolicy

    cfg = dataclasses.replace(
        _cfg(peft_personalize=True),
        adversary=AdversaryPolicy(mode="sign_flip", ranks=(0,)),
    )
    with pytest.raises(ValueError, match="adversary"):
        _sim(cfg)


def test_personalize_sharded_accepted():
    # the adapter bank shards over the client axis now — the sharded
    # round trains it in place and the no-leak pin still holds
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    cfg = dataclasses.replace(
        _cfg(num_clients=8, rounds=2, cohort=4,
             peft_personalize=True),
        mesh=MeshConfig(client_axis_size=4, data_axis_size=1),
    )
    sim = ShardedFedAvg(create_model(cfg.model), _data(cfg), cfg,
                        make_mesh(client_axis=4, data_axis=1))
    state = sim.init()
    params0 = jax.device_get(state.variables["params"])
    server_adapters0 = sim._peft.private.trainable(params0)
    for _ in range(2):
        state, m = sim.run_round(state)
        assert np.isfinite(float(m["train_loss"]))
    # no-leak: the server state's adapter leaves are bitwise init
    _bitwise(
        sim._peft.private.trainable(
            jax.device_get(state.variables["params"])
        ),
        server_adapters0, "sharded server-side adapters",
    )
    assert sim._carry is not None


def test_peft_rejects_non_transformer_sim():
    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=4,
                        batch_size=8, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        fed=FedConfig(num_rounds=1, clients_per_round=2,
                      peft="lora"),
        seed=0,
    )
    with pytest.raises(ValueError, match="TransformerLM"):
        FedAvgSim(create_model(cfg.model),
                  load_dataset(cfg.data), cfg)


def test_parse_time_rejections():
    from fedml_tpu.experiments.run import parse_args

    base = ["--algorithm", "fedavg", "--dataset",
            "fake_stackoverflow_nwp", "--model", "transformer_lm"]
    with pytest.raises(SystemExit):
        parse_args(base + ["--peft", "lora", "--lora_rank", "0"])
    with pytest.raises(SystemExit):
        parse_args(base + ["--peft", "lora", "--lora_targets", "nope"])
    with pytest.raises(SystemExit):
        parse_args(["--algorithm", "fedmd", "--dataset",
                    "fake_stackoverflow_nwp", "--model",
                    "transformer_lm", "--peft", "lora"])
    with pytest.raises(SystemExit):
        parse_args(base + ["--model", "lr", "--peft", "lora"])
    with pytest.raises(SystemExit):
        parse_args(base + ["--peft", "lora", "--peft_personalize",
                           "--compress", "int8"])
    cfg, _ = parse_args(base + ["--peft", "lora", "--lora_rank", "8",
                                "--lora_targets", "q_proj", "mlp_up"])
    assert cfg.fed.peft == "lora"
    assert cfg.fed.lora_rank == 8
    assert cfg.fed.lora_targets == ("q_proj", "mlp_up")


def test_config_json_roundtrip():
    cfg = _cfg(peft_personalize=False)
    cfg = dataclasses.replace(
        cfg, fed=dataclasses.replace(
            cfg.fed, lora_targets=("q_proj", "mlp_down")
        )
    )
    back = ExperimentConfig.from_dict(json.loads(cfg.to_json()))
    assert back.fed.peft == "lora"
    assert back.fed.lora_rank == cfg.fed.lora_rank
    assert back.fed.lora_targets == ("q_proj", "mlp_down")
    assert isinstance(back.fed.lora_targets, tuple)
    hash(back.fed)  # stays jit-static usable


# ---------------------------------------------------------------------------
# 6. synthetic StackOverflow fallback contract
# ---------------------------------------------------------------------------


def test_synthetic_stackoverflow_contract():
    fd = synthetic_stackoverflow_nwp(num_clients=6, vocab_size=500,
                                     seed=3)
    assert len(fd.train_idx_map) == 6
    assert fd.x_train.dtype == np.int32
    assert fd.x_train.shape[1] == 20  # the [B, T] contract
    assert fd.y_train.shape == fd.x_train.shape
    assert fd.num_classes == 504 and fd.task == "nwp"
    assert fd.x_train.min() >= 0 and fd.x_train.max() <= 503
    assert np.all(fd.x_train[:, 0] == 501)  # bos-first like TFF
    # y is x shifted left (next-token targets)
    np.testing.assert_array_equal(fd.y_train[:, :-1],
                                  fd.x_train[:, 1:])
    fd2 = synthetic_stackoverflow_nwp(num_clients=6, vocab_size=500,
                                      seed=3)
    np.testing.assert_array_equal(fd.x_train, fd2.x_train)
    # non-IID: client unigram histograms differ
    h = []
    for c in (0, 1):
        idx = fd.train_idx_map[c]
        h.append(np.bincount(fd.x_train[idx].ravel(), minlength=504))
    assert not np.array_equal(h[0], h[1])


def test_stackoverflow_loader_fallback_dispatch():
    # the stand-in is an EXPLICIT dataset name
    cfg = DataConfig(dataset="synthetic_stackoverflow_nwp",
                     num_clients=4, seed=1)
    fd = load_dataset(cfg)
    assert len(fd.train_idx_map) == 4
    assert fd.num_classes == 10004  # real vocab ids preserved
    # the REAL dataset name with missing files hard-fails (a typo'd
    # data_dir must never silently train on synthetic data)
    with pytest.raises(FileNotFoundError):
        load_dataset(DataConfig(dataset="stackoverflow_nwp",
                                data_dir="/nonexistent-peft-test",
                                num_clients=4, seed=1))
    # the library opt-in still exists for offline callers
    from fedml_tpu.data.natural import load_stackoverflow_nwp

    fd2 = load_stackoverflow_nwp("/nonexistent-peft-test",
                                 fallback_clients=4, fallback_seed=1)
    np.testing.assert_array_equal(fd.x_train, fd2.x_train)
