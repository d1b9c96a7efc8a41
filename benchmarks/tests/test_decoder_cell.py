"""``laguna-xs2-c2of32-b2x2048`` rehearsed on the CPU through the
harness itself: the configuration's own ``.py`` and ``.json`` shrunk to
tiny widths (``tiny_decoder.py``), its own traffic file at 8 clients.
A sound run is ``correct``; the float8 control in the program's place
is not; a program that skips half its held experts is not."""

import json

import pytest
from conftest import run_cell

import tiny_decoder as TD


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TD.make_tree(str(tmp_path_factory.mktemp("decoder_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TD.make_tree(
        str(tmp_path_factory.mktemp("decoder_bf16")), "bfloat16")


def test_sound_run_is_correct_and_carries_the_counters(tree_f32, capsys):
    rc, lines = run_cell(tree_f32, TD.CELL, seed=2 ** 31 + 7, capsys=capsys)
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert result["checks"]["compiled_in_window_s"]["value"] == 0
    assert result["checks"]["loss_rel_gap.round1"]["value"] < 1e-4
    assert result["checks"]["head_grad_rel_err"]["value"] < 1e-3


def test_traced_rehearsal_reads_the_counters_off_the_records(tree_f32,
                                                             capsys):
    """Off the chip there is no device trace, so every device number is
    left out; the rehearsal still runs every reader of the cell."""
    rc, lines = run_cell(tree_f32, TD.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.chip1" in metrics and "round_p95_ms.chip1" in metrics
    for device_number in ("attn_ms", "moe_experts_ms",
                          "attn_kernel_roofline_pct",
                          "moe_experts_roofline_pct", "moe_held_share_pct"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TD.CELL, "--seeds", "1,2,3",
         "--control-seeds", "1,2,3"], root=tree_bf16, require_chip=False)
    assert rc == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()
             if line.startswith("{")]
    sides = {"program": [], "control_fp8": []}
    for rec in lines[:-1]:
        sides[rec["side"]].append(rec["ok"])
    assert sides == {"program": [True] * 3, "control_fp8": [False] * 3}, (
        lines[-1]["summary"])


def test_half_the_held_experts_skipped_is_not_correct(tree_f32, capsys,
                                                      monkeypatch):
    """A broken timed path: the expert layer computes only the first
    half of the experts it holds."""
    from fedml_tpu.models import decoder

    whole = decoder.moe_layer

    def half(params, h, held, top_k, scale):
        first, count = held
        params = {**params, **{k: params[k][:count // 2]
                               for k in ("w1", "w3", "w2")}}
        return whole(params, h, (first, count // 2), top_k, scale)

    def break_path(sim):
        monkeypatch.setattr(decoder, "moe_layer", half)

    rc, lines = run_cell(tree_f32, TD.CELL, capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert "head_grad_rel_err" in failed or any(
        n.startswith("loss_rel_gap") for n in failed), failed


def test_kernel_work_counts():
    """The counting functions behind the two roofline shares, on the
    published shapes."""
    from lib import decoder_kernels as K

    extra = TD.real_config()["model"]["extra"]
    assert K.blocks_visited(2048, 512, None) == 10  # of 16: causal
    assert K.blocks_visited(2048, 512, 512) == 7  # itself and one back
    assert K.blocks_visited(2048, 256, 512) == 1 + 2 + 6 * 3  # two back
    flops, nbytes = K.attention_work(extra, 2048, 2, 512)
    pairs = 2 * 48 * 10 + 3 * 64 * 7  # heads x visited pairs, 5 layers
    assert flops == 2 * pairs * 2 * 512 * 512 * 128 * 9
    assert nbytes > 0
    flops, nbytes = K.experts_work(extra, 4096.0, 1.0)
    assert flops == 4096 * 3 * 2 * 2048 * 512 * 4
    # 128 rows an expert: the matrices weigh more than the rows
    assert 12 * 32 * 2048 * 512 * 2 < nbytes < 2 * 12 * 32 * 2048 * 512 * 2
    assert K.sparse_layers(extra) == 4
