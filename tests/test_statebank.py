"""Sharded client-state banks (core/statebank.py,
docs/FAULT_TOLERANCE.md "Client-state banks").

The contract, in tiers:

1. **Bank semantics**: sentinel ids clamp on gather and DROP on
   scatter (a pad slot can never collide with client 0); ``put``'s
   ``keep`` mask writes the pre-round row back value-identically for
   screened slots; the bank is a pytree whose static name survives
   jit.
2. **Identity-keyed carry**: the compress error-feedback residual
   follows the CLIENT, not the cohort slot — an unsampled client's
   row is untouched across rounds, a sampled client's row trains.
3. **Crash survival**: the ``{"server", "bank"}`` checkpoint
   composite restores every bank row bitwise through the harness
   seams, a resumed run continues bit-identically to an uninterrupted
   one, and a LEGACY bare-state checkpoint restores with fresh banks
   instead of crashing.
4. **No-leak under composition**: personalization over bulk / elastic
   / fuse keeps private rows out of the server aggregate.
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
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core import random as R
from fedml_tpu.core import statebank as SB
from fedml_tpu.core import telemetry
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.data.natural import synthetic_stackoverflow_nwp
from fedml_tpu.experiments.harness import Experiment
from fedml_tpu.models import create_model
from fedml_tpu.utils.checkpoint import RoundCheckpointer

VOCAB = 128


def _cfg(num_clients=8, rounds=3, cohort=8, **fed_kw):
    fed_kw.setdefault("eval_every", rounds)
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=num_clients,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      **fed_kw),
        seed=0,
    )


def _sim(cfg):
    return FedAvgSim(create_model(cfg.model), load_dataset(cfg.data),
                     cfg)


def _peft_cfg(num_clients=8, rounds=3, cohort=3, **fed_kw):
    fed_kw.setdefault("eval_every", 10**9)
    fed_kw.setdefault("peft", "lora")
    fed_kw.setdefault("lora_rank", 2)
    fed_kw.setdefault("lora_alpha", 4.0)
    fed_kw.setdefault("peft_personalize", True)
    kw = {
        "vocab_size": VOCAB + 4, "num_layers": 1, "num_heads": 2,
        "embed_dim": 16, "max_len": 32,
    }
    return ExperimentConfig(
        data=DataConfig(dataset="fake_stackoverflow_nwp",
                        num_clients=num_clients, batch_size=8, seed=0),
        model=ModelConfig(name="transformer_lm", num_classes=VOCAB + 4,
                          input_shape=(20,),
                          extra=tuple(sorted(kw.items()))),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      **fed_kw),
        seed=0,
    )


def _peft_sim(cfg):
    data = synthetic_stackoverflow_nwp(
        num_clients=cfg.data.num_clients, vocab_size=VOCAB, seed=0,
        sentences_low=4, sentences_high=8,
    )
    return FedAvgSim(create_model(cfg.model), data, cfg)


def _bitwise(t1, t2, what=""):
    l1, l2 = jax.tree.leaves(t1), jax.tree.leaves(t2)
    assert len(l1) == len(l2), (what, len(l1), len(l2))
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


# ---------------------------------------------------------------------------
# 1. bank semantics
# ---------------------------------------------------------------------------


def test_bank_geometry_and_constructors():
    tmpl = {"a": jnp.ones((3,), jnp.float32),
            "b": jnp.zeros((2, 2), jnp.float32)}
    z = SB.ClientStateBank.zeros("z", tmpl, 5)
    br = SB.ClientStateBank.broadcast("b", tmpl, 5)
    assert z.num_rows == 5 and z.sentinel == 5
    assert z.rows["a"].shape == (5, 3)
    assert float(jnp.sum(jnp.abs(z.rows["a"]))) == 0.0
    # broadcast: every row IS the template
    np.testing.assert_array_equal(np.asarray(br.rows["a"][3]),
                                  np.asarray(tmpl["a"]))
    # per-row bytes: (3 + 4) f32 = 28; resident = 5x that
    assert z.row_bytes() == 28
    assert z.resident_bytes() == 5 * 28


def test_sentinel_gather_clamps_and_scatter_drops():
    bank = SB.ClientStateBank(
        "t", {"v": jnp.arange(4, dtype=jnp.float32)[:, None]}
    )
    ids = SB.pad_ids(jnp.asarray([1], jnp.int32), 3, bank.sentinel)
    np.testing.assert_array_equal(np.asarray(ids), [1, 4, 4])
    g = bank.gather(ids)
    # OOB gather clamps to the LAST row (callers mask it downstream)
    np.testing.assert_array_equal(
        np.asarray(g["v"][:, 0]), [1.0, 3.0, 3.0]
    )
    new = {"v": jnp.full((3, 1), 9.0)}
    out = bank.put(ids, new)
    # only the real id wrote; the sentinel writes were DROPPED — row 3
    # (the clamp target) is untouched, and row 0 never collided
    np.testing.assert_array_equal(
        np.asarray(out.rows["v"][:, 0]), [0.0, 9.0, 2.0, 3.0]
    )


def test_put_keep_mask_preserves_screened_rows():
    bank = SB.ClientStateBank(
        "t", {"v": jnp.arange(4, dtype=jnp.float32)[:, None]}
    )
    ids = jnp.asarray([0, 2], jnp.int32)
    new = {"v": jnp.full((2, 1), 7.0)}
    keep = jnp.asarray([True, False])
    out = bank.put(ids, new, keep=keep)
    # id 0 kept its update; id 2 (screened) wrote its pre-round value
    np.testing.assert_array_equal(
        np.asarray(out.rows["v"][:, 0]), [7.0, 1.0, 2.0, 3.0]
    )
    # the gathered= fast path is value-identical
    out2 = bank.put(ids, new, keep=keep, gathered=bank.gather(ids))
    _bitwise(out.rows, out2.rows, "gathered= fast path")


def test_bank_is_a_jit_transparent_pytree():
    bank = SB.ClientStateBank("ef", {"v": jnp.ones((4, 2))})

    @jax.jit
    def bump(b):
        return b.put(jnp.asarray([1], jnp.int32),
                     {"v": jnp.zeros((1, 2))})

    out = bump(bank)
    assert isinstance(out, SB.ClientStateBank)
    assert out.name == "ef"  # static aux survives the round trip
    np.testing.assert_array_equal(np.asarray(out.rows["v"][1]),
                                  [0.0, 0.0])


def test_bank_telemetry_vocabulary():
    was = telemetry.METRICS.enabled
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    try:
        bank = SB.ClientStateBank.zeros(
            "t", {"v": jnp.ones((3,), jnp.float32)}, 10
        )
        SB.note_bank(bank)
        SB.note_round_io(4, 4)
        snap = telemetry.METRICS.snapshot()
        gauges = dict(snap["gauges"])
        assert gauges["bank.rows"] == 10.0
        assert gauges["bank.row_bytes"] == 12.0
        counters = dict(snap["counters"])
        assert counters["bank.gathers"] == 4
        assert counters["bank.scatters"] == 4
        assert "bank.resident_mb" in gauges
    finally:
        telemetry.METRICS.enabled = was
        telemetry.METRICS.reset()


# ---------------------------------------------------------------------------
# 2. the EF residual follows the client, not the slot
# ---------------------------------------------------------------------------


def test_ef_bank_rows_follow_client_identity():
    sim = _sim(_cfg(num_clients=8, rounds=2, cohort=4,
                    client_block_size=2, compress="int8"))
    state = sim.init()
    state, _ = sim.run_round(state)
    assert sim._carry is not None
    rows = jax.device_get(sim._carry.rows)
    # recompute round 0's cohort from the same seeded draw
    rkey = R.round_key(sim.root_key, jnp.asarray(0, jnp.int32))
    cohort = set(np.asarray(jax.device_get(
        sim.sampler(jax.random.fold_in(rkey, 0), 8, 4)
    )).tolist())
    for c in range(8):
        row = [np.asarray(l[c]) for l in jax.tree.leaves(rows)]
        nonzero = any(np.any(r != 0) for r in row)
        if c in cohort:
            assert nonzero, f"sampled client {c} EF row stayed zero"
        else:
            assert not nonzero, f"unsampled client {c} EF row changed"


# ---------------------------------------------------------------------------
# 3. crash survival: the {"server", "bank"} composite
# ---------------------------------------------------------------------------


def test_checkpoint_composite_restores_banks_bitwise(tmp_path):
    cfg = _peft_cfg(num_clients=8, rounds=2, cohort=3)
    sim = _peft_sim(cfg)
    state = sim.init()
    for r in range(2):
        state, _ = sim.run_round(state)
    ckpt = RoundCheckpointer(str(tmp_path / "ck"), keep=2)
    try:
        Experiment._save_state(ckpt, sim, 1, state)
        # a FRESH sim (the post-SIGKILL world) restores both planes
        sim2 = _peft_sim(cfg)
        state2 = sim2.init()
        state2, nxt = Experiment._restore_state(ckpt, sim2, state2)
        assert nxt == 2
        _bitwise(jax.device_get(state2.variables),
                 jax.device_get(state.variables), "server plane")
        assert sim2._carry is not None
        _bitwise(jax.device_get(sim2._carry.rows),
                 jax.device_get(sim._carry.rows),
                 "adapter bank rows")
    finally:
        ckpt.close()


def test_checkpoint_composite_restores_ef_bank(tmp_path):
    cfg = _cfg(num_clients=8, rounds=2, cohort=4,
               client_block_size=2, compress="int8")
    sim = _sim(cfg)
    state = sim.init()
    state, _ = sim.run_round(state)
    assert "ef_residual" in sim.bank_state()
    ckpt = RoundCheckpointer(str(tmp_path / "ck"), keep=2)
    try:
        Experiment._save_state(ckpt, sim, 0, state)
        sim2 = _sim(cfg)
        state2 = sim2.init()
        state2, nxt = Experiment._restore_state(ckpt, sim2, state2)
        assert nxt == 1
        assert sim2._carry is not None
        _bitwise(jax.device_get(sim2._carry.rows),
                 jax.device_get(sim._carry.rows), "EF bank rows")
    finally:
        ckpt.close()


def test_resume_continues_bit_identically(tmp_path):
    """The SIGKILL pin: interrupt after round 1, restore into a fresh
    process-equivalent sim, finish — bitwise equal to never dying."""
    cfg = _peft_cfg(num_clients=8, rounds=4, cohort=3)
    # the uninterrupted run
    sim_a = _peft_sim(cfg)
    state_a = sim_a.init()
    for _ in range(4):
        state_a, _ = sim_a.run_round(state_a)
    # the interrupted run: 2 rounds, save, "die", restore, finish
    sim_b = _peft_sim(cfg)
    state_b = sim_b.init()
    for _ in range(2):
        state_b, _ = sim_b.run_round(state_b)
    ckpt = RoundCheckpointer(str(tmp_path / "ck"), keep=2)
    try:
        Experiment._save_state(ckpt, sim_b, 1, state_b)
        sim_c = _peft_sim(cfg)
        state_c = sim_c.init()
        state_c, nxt = Experiment._restore_state(ckpt, sim_c, state_c)
        for _ in range(nxt, 4):
            state_c, _ = sim_c.run_round(state_c)
    finally:
        ckpt.close()
    _bitwise(jax.device_get(state_c.variables),
             jax.device_get(state_a.variables), "resumed server state")
    _bitwise(jax.device_get(sim_c._carry.rows),
             jax.device_get(sim_a._carry.rows),
             "resumed adapter bank")


def test_legacy_bare_checkpoint_restores_with_fresh_banks(tmp_path):
    """A pre-bank checkpoint (bare server state) must resume, not
    crash: the banks come back at their lazy round-0 init."""
    cfg = _peft_cfg(num_clients=8, rounds=2, cohort=3)
    sim = _peft_sim(cfg)
    state = sim.init()
    state, _ = sim.run_round(state)
    ckpt = RoundCheckpointer(str(tmp_path / "ck"), keep=2)
    try:
        ckpt.save(0, state)  # the legacy format: no "bank" plane
        sim2 = _peft_sim(cfg)
        state2 = sim2.init()
        state2, nxt = Experiment._restore_state(ckpt, sim2, state2)
        assert nxt == 1
        _bitwise(jax.device_get(state2.variables),
                 jax.device_get(state.variables), "legacy server plane")
        assert sim2._carry is None  # fresh lazy init pending
        state2, m = sim2.run_round(state2)
        assert np.isfinite(float(m["train_loss"]))
    finally:
        ckpt.close()


# ---------------------------------------------------------------------------
# 4. no-leak under composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fed_kw", [
    dict(client_block_size=2),
    dict(elastic_buckets=True),
    dict(fuse_rounds=2),
])
def test_personalize_composition_no_leak(fed_kw):
    cfg = _peft_cfg(num_clients=8, rounds=2, cohort=3, **fed_kw)
    sim = _peft_sim(cfg)
    state = sim.init()
    params0 = jax.device_get(state.variables["params"])
    server_adapters0 = sim._peft.private.trainable(params0)
    if cfg.fed.fuse_rounds > 1:
        state, ms = sim.run_block(state, 2)
        assert np.all(np.isfinite(np.asarray(ms["train_loss"])))
    else:
        for _ in range(2):
            state, m = sim.run_round(state)
            assert np.isfinite(float(m["train_loss"]))
    # pin 1: the server aggregate's adapter leaves stay bitwise init
    _bitwise(
        sim._peft.private.trainable(
            jax.device_get(state.variables["params"])
        ),
        server_adapters0, "server-side adapters",
    )
    # pin 2: at least one sampled client's row trained away from init
    bank = jax.device_get(sim._carry.rows)
    init = jax.device_get(
        SB.ClientStateBank.broadcast(
            "i", sim._peft.private.trainable(params0), 8
        ).rows
    )
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(bank), jax.tree.leaves(init))
    ), "no adapter row trained"
