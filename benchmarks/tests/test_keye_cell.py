"""``keye-vl2-c2of32-b1x8192`` rehearsed on the CPU through the harness
itself: the configuration's own ``.py`` and ``.json`` shrunk to tiny
widths (``tiny_keye.py``), its own traffic file at 8 clients. A sound
run is ``correct`` and its records carry the attention counters; the
float8 control in the program's place is not; a program whose selection
keeps half the keys it should is not. The new readers on spans and
records of the form a traced run leaves, counters absent included, and
the counting functions of ``lib/sparse_attention.py`` on the published
shapes."""

import json

import pytest
from conftest import BENCH, run_cell
from lib import decoder_kernels as K
from lib import program_spans as PS
from lib import sparse_attention as S

import run
import tiny_keye as TK

NEW = ("attn_index_ms", "attn_select_ms", "attn_selected_share_pct",
       "sparse_attn_roofline_pct", "attn_index_roofline_pct")


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TK.make_tree(str(tmp_path_factory.mktemp("keye_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TK.make_tree(str(tmp_path_factory.mktemp("keye_bf16")), "bfloat16")


def test_sound_run_is_correct_and_carries_the_counters(tree_f32, capsys):
    # 3 s: at the cell's lr a 1 s window holds 8-10 rounds, whose loss
    # falls by less than two rounds' clients differ (+0.008 at 8 rounds)
    rc, lines = run_cell(tree_f32, TK.CELL, seed=2 ** 31 + 7, seconds=3.0,
                         capsys=capsys)
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert result["checks"]["compiled_in_window_s"]["value"] == 0
    assert result["checks"]["loss_rel_gap.round1"]["value"] < 1e-4
    assert result["checks"]["head_grad_rel_err"]["value"] < 1e-3


def test_traced_rehearsal_runs_every_reader_of_the_cell(tree_f32, capsys):
    """Off the chip there is no device trace, so every device number and
    every counter read off a trace is left out; the rehearsal still runs
    every reader the cell lists, the new ones among them."""
    cell = run.load_cell(TK.CELL, tree_f32)
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= listed and "attn_ms.keye" in listed
    assert "attn_kernel_roofline_pct" not in listed  # the other kernel's
    rc, lines = run_cell(tree_f32, TK.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.keye" in metrics and "round_p95_ms.keye" in metrics
    for device_number in NEW + ("attn_ms.keye", "moe_experts_ms.keye",
                                "moe_experts_roofline_pct.keye"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TK.CELL, "--seeds", "1,2,3",
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


def test_half_the_keys_selected_is_not_correct(tree_f32, capsys,
                                               monkeypatch):
    """A broken timed path: the selection keeps half the keys the
    configuration says (``topk`` 8 for 16)."""
    from fedml_tpu.models import decoder

    whole = decoder.select_top_k

    def break_path(sim):
        monkeypatch.setattr(
            decoder, "select_top_k", lambda scores, k: whole(scores, k // 2))

    rc, lines = run_cell(tree_f32, TK.CELL, capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert "head_grad_rel_err" in failed or any(
        n.startswith("loss_rel_gap") for n in failed), failed


# -- the new readers on what a traced run leaves ---------------------------

TRACED = [5, 6, 7]
SCOPE_S = {S.INDEX: 0.3, S.SELECT: 0.6, S.KERNEL: 3.0,
           "fedml.model.attn": 1.5}


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def _traced_ctx(monkeypatch, counters=True, scopes=True):
    """A context as ``run_cell`` hands the readers after a traced run
    on the chip, the trace's reduction stubbed: three traced rounds of 4
    client steps each at the published shapes."""
    config = TK.real_config()
    extra, seq = config["model"]["extra"], config["model"]["input_shape"][0]
    selected, causal = S.expected_keys(seq, 2048)
    layers = len(extra["layer_types"])

    def counted(r):
        rec = {"round": r, "moe_rows_routed": 1.0}
        if counters:
            rec.update(attn_keys_selected=4.0 * layers * selected,
                       attn_keys_causal=4.0 * layers * causal)
        return rec

    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "spans": spans, "scopes": scopes, "rounds": len(TRACED),
        "scope_busy_s": SCOPE_S if scopes else {}})
    return {"cell": {"config": config}, "traced_rounds": TRACED,
            "records": [counted(r) for r in (4, 7, 8)],
            "client_steps": 4 * len(TRACED),
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}


def test_new_readers_on_a_traced_runs_spans_and_records(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    assert _read("attn_index_ms", ctx) == pytest.approx(100.0)
    assert _read("attn_select_ms", ctx) == pytest.approx(200.0)
    assert _read("attn_ms.keye", ctx) == pytest.approx(1500.0)
    share = _read("attn_selected_share_pct", ctx)
    assert share == pytest.approx(100 * 14_681_088 / 33_558_528)  # 43.7
    extra = ctx["cell"]["config"]["model"]["extra"]
    calls = 12 * 5  # client steps x sparse-attention layers x batch 1
    flops, nbytes = S.attention_work(
        extra, 8192, 12 * 5 * 14_681_088, calls)
    assert flops == 12 * 5 * 14_681_088 * 32 * 2 * 128 * 9
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("sparse_attn_roofline_pct", ctx) == pytest.approx(
        100 * least / 3.0)
    assert 0 < _read("sparse_attn_roofline_pct", ctx) < 100
    assert 0 < _read("attn_index_roofline_pct", ctx) < 100


@pytest.mark.parametrize("counters, scopes", [(False, True), (True, False)])
def test_a_program_without_the_counters_or_scopes_gives_nothing(
        counters, scopes, monkeypatch):
    """The parent of the PR that added them: the line leaves the metric
    out and nothing raises."""
    ctx = _traced_ctx(monkeypatch, counters=counters, scopes=scopes)
    if not counters:
        assert _read("attn_selected_share_pct", ctx) is None
        assert _read("attn_index_ms", ctx) == pytest.approx(100.0)
    else:
        assert _read("attn_index_ms", ctx) is None
        assert _read("attn_select_ms", ctx) is None
        assert _read("attn_selected_share_pct", ctx) is not None
    assert _read("sparse_attn_roofline_pct", ctx) is None
    assert _read("attn_index_roofline_pct", ctx) is None


def test_off_the_chip_or_on_another_model_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": [], "client_steps": 12,
           "cell": {"config": TK.real_config()}}
    for name in NEW:
        assert _read(name, ctx) is None
    ctx["cell"] = {"config": {"model": {"name": "resnet56"}}}
    assert S.sparse_sizes(ctx) is None
    assert _read("sparse_attn_roofline_pct", ctx) is None


def test_work_counts_on_the_published_shapes():
    extra = TK.real_config()["model"]["extra"]
    selected, causal = S.expected_keys(8192, 2048)
    assert (selected, causal) == (14_681_088, 33_558_528)
    assert S.expected_keys(64, 2048) == (2080, 2080)
    assert S.sparse_sizes({"cell": {"config": TK.real_config()}})[1:] == (
        8192, 5)
    flops, nbytes = S.index_work(extra, 8192, causal, 1.0)
    # scores twice a step (the recomputation), never backward
    assert flops >= 2 * causal * 16 * 2 * 64
    assert nbytes > 2 * 4 * 8192 * 8192
    # the existing readers find what they need in this model.extra
    assert K.sparse_layers(extra) == 5
    flops, _ = K.experts_work(extra, 4096.0, 1.0)
    assert flops == 4096 * 3 * 2 * 2048 * 768 * 4
