"""The plain float32 reference against the sim at a tiny size: with the
program computing in float32 too, the two must agree to rounding."""

import pytest
from conftest import run_cell


@pytest.mark.parametrize("workload", [
    "tiny-bn.c4of20",   # BatchNorm, FedAvg
    "tiny-gn.c4of20",   # GroupNorm, server Adam
    "tiny-bn.mesh4",    # ShardedFedAvg, stratified cohort
])
def test_sim_matches_reference_in_float32(tiny_f32, capsys, workload):
    rc, lines = run_cell(tiny_f32, workload, capsys=capsys)
    assert rc == 0
    checks = {c["number"]: c["value"] for c in lines
              if c.get("phase") == "check"}
    # float32 on both sides: what is left is reassociation (the cohort-
    # widened convolutions sum in another order than the reference's
    # matrix products). Forward quantities keep it; a norm taken by the
    # worst leaf after three rounds through twenty normalised layers
    # grows it to 9e-3 (measured).
    for number in ("loss_rel_gap.round1", "loss_rel_gap.round2",
                   "loss_rel_gap.round3", "head_grad_rel_err"):
        assert checks[number] < 1e-3, (number, checks[number])
    for number in ("first_grad_norm_gap", "change_norm_gap"):
        assert checks[number] < 3e-2, (number, checks[number])


def test_step_flops_matches_a_hand_count():
    from lib import refnet

    arch = {"norm": "bn", "in_channels": 3, "stem": 16, "classes": 10,
            "stages": [[16, 1, 1], [32, 1, 2]]}
    macs = (32 * 32 * 9 * 3 * 16            # stem
            + 32 * 32 * 9 * 16 * 16 * 2     # block 0
            + 16 * 16 * 9 * (16 * 32 + 32 * 32) + 16 * 16 * 16 * 32
            + 32 * 10)                      # head
    assert refnet.step_flops(arch, 4) == 6.0 * macs * 4


def test_resnet56_and_resnet18_sizes():
    """The references have the published parameter counts."""
    import jax
    import run

    counts = {}
    for cfg in ("resnet56-cifar10", "resnet18gn-fedcifar100"):
        arch = run._load_py(
            f"{run.HERE}/configs/{cfg}.py", "ref").ARCH
        from lib import refnet

        v = jax.eval_shape(lambda k: refnet.init(arch, k), jax.random.key(0))
        counts[cfg] = sum(x.size for x in jax.tree.leaves(v["params"]))
    # He et al. 2016 table 6: ResNet-56, 0.85M parameters
    assert 0.84e6 < counts["resnet56-cifar10"] < 0.87e6
    # ResNet-18 with a 100-way head and 3x3 stem: 11.2M
    assert 11.1e6 < counts["resnet18gn-fedcifar100"] < 11.3e6
