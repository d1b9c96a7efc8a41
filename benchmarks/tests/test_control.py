"""``correct`` can come out false: the control (the reference in the
nearest precision below the configuration's bfloat16, in the program's
place) fails a limit, and a broken timed path fails the run."""

import json

import pytest
from conftest import run_cell


def test_lower_precision_control_is_not_correct(tiny_bf16, capsys):
    """float8 in the program's place, three seeds, at the test size —
    and the bfloat16 program itself within the same limits."""
    import calibrate

    rc = calibrate.main(
        ["--workload", "tiny-bn.c4of20", "--seeds", "1,2,3",
         "--control-seeds", "1,2,3"], root=tiny_bf16, require_chip=False)
    assert rc == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()
             if line.startswith("{")]
    sides = {"program": [], "control_fp8": []}
    for rec in lines[:-1]:
        sides[rec["side"]].append(rec["ok"])
    assert sides["program"] == [True] * 3
    assert sides["control_fp8"] == [False] * 3


def _unchanged_state(sim):
    """A step that returns its state unchanged (but counts the round)."""
    import jax

    inner = sim.run_round

    def broken(state):
        kept = jax.tree.map(jax.numpy.copy, state)
        new, metrics = inner(state)
        return kept._replace(round=new.round), metrics

    sim.run_round = broken


def _half_update(sim):
    """An aggregate altered where it is produced: half the update."""
    import jax

    inner = sim.run_round

    def broken(state):
        old = jax.tree.map(jax.numpy.copy, state.variables["params"])
        new, metrics = inner(state)
        params = jax.tree.map(lambda o, n: o + 0.5 * (n - o), old,
                              new.variables["params"])
        return new._replace(
            variables={**new.variables, "params": params}), metrics

    sim.run_round = broken


@pytest.mark.parametrize("break_path,number", [
    (_unchanged_state, "change_norm_gap"),
    (_half_update, "head_grad_rel_err"),
])
def test_broken_timed_path_is_not_correct(tiny_f32, capsys, break_path,
                                          number):
    rc, lines = run_cell(tiny_f32, "tiny-bn.c4of20", capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert number in failed, failed


def test_half_the_clients_left_out_is_not_correct(tiny_f32, capsys,
                                                  monkeypatch):
    """Every second client's rows masked out of the rounds the
    reference follows: the loss and the gradient both miss their
    limits."""
    import jax.numpy as jnp
    import run

    inner = run.one_batch_operand

    def cut(sim, batch):
        op = inner(sim, batch)
        keep = (jnp.arange(op.mask.shape[-2]) % 2).astype(op.mask.dtype)
        return op.replace(mask=op.mask * keep[:, None])

    monkeypatch.setattr(run, "one_batch_operand", cut)
    rc, lines = run_cell(tiny_f32, "tiny-bn.c4of20", capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is False
