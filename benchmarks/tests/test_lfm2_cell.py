"""``lfm2-8b-c2of32-b1x8192`` rehearsed on the CPU through the harness
itself: the configuration's own ``.py`` and ``.json`` shrunk to tiny
widths (``tiny_lfm2.py``), its own traffic file at 8 clients. A sound
run is ``correct`` and its records carry the expert counters; the
float8 control in the program's place is not; a program that runs its
convolution's taps in reverse is not. The cell's readers on spans and
records of the form a traced run leaves, scopes or counters absent
included, and the counting functions of ``lib/short_conv.py`` on the
published shapes."""

import json

import pytest
from conftest import BENCH, run_cell
from lib import decoder_kernels as K
from lib import program_spans as PS
from lib import short_conv as SC

import run
import tiny_lfm2 as TL

NEW = ("conv_mixer_ms", "conv_mix_ms", "conv_mix_roofline_pct",
       "attn64_kernel_roofline_pct")
SUFFIXED = ("eval_ms", "round_p95_ms", "attn_ms", "moe_route_ms",
            "moe_experts_ms", "head_loss_ms", "mlp_dense_ms", "embed_ms",
            "moe_sort_ms", "moe_held_share_pct", "moe_load_max_over_mean",
            "moe_compact_share_pct", "moe_experts_roofline_pct",
            "moe_product_calls_per_step")
CONV, MIX, ATTN, KERNEL, MLP, ROUTE, EXPERTS = (
    "fedml.model.conv", "fedml.model.conv.mix", "fedml.model.attn",
    "fedml.model.attn.kernel", "fedml.model.mlp", "fedml.model.moe.route",
    "fedml.model.moe.experts")


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TL.make_tree(str(tmp_path_factory.mktemp("lfm2_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TL.make_tree(
        str(tmp_path_factory.mktemp("lfm2_bf16")), "bfloat16")


def test_sound_run_is_correct_and_carries_the_counters(tree_f32, capsys):
    rc, lines = run_cell(tree_f32, TL.CELL, seed=2 ** 31 + 7, seconds=3.0,
                         capsys=capsys)
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert result["checks"]["compiled_in_window_s"]["value"] == 0
    assert result["checks"]["loss_rel_gap.round1"]["value"] < 1e-4
    # the tied table's gradient, both roads summed
    assert result["checks"]["head_grad_rel_err"]["value"] < 1e-3


def test_traced_rehearsal_runs_every_reader_of_the_cell(tree_f32, capsys):
    """Off the chip there is no device trace, so every device number and
    every counter read off a trace is left out; the rehearsal still runs
    every reader the cell lists, the new ones among them."""
    cell = run.load_cell(TL.CELL, tree_f32)
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= listed
    assert {name + ".lfm2" for name in SUFFIXED} <= listed
    # the siblings' counts of other head sizes and kernels are not
    # pointed at this cell, nor are their unsuffixed entries
    assert not listed & {"attn_kernel_roofline_pct", "attn_index_ms",
                         "attn_share_roofline_pct", "attn_dq_pass_ms",
                         "latent_attn_roofline_pct", "ssm_ms", "embed_ms",
                         "moe_experts_roofline_pct", "mlp_dense_ms",
                         "moe_product_calls_per_step", "moe_router_ms"}
    rc, lines = run_cell(tree_f32, TL.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.lfm2" in metrics and "round_p95_ms.lfm2" in metrics
    for device_number in NEW + ("attn_ms.lfm2", "embed_ms.lfm2",
                                "moe_experts_roofline_pct.lfm2"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TL.CELL, "--seeds", "1,2,3",
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


def test_taps_run_in_reverse_are_not_correct(tree_f32, capsys, monkeypatch):
    """A broken timed path: the convolution weighs ``t - 2`` with the tap
    that belongs to ``t``. The mixers' outputs differ, and the
    comparison shows it."""
    from fedml_tpu.models import decoder

    taps = decoder.causal_depthwise_conv

    def break_path(sim):
        monkeypatch.setattr(
            decoder, "causal_depthwise_conv",
            lambda x, kernel, bias=None: taps(x, kernel[::-1], bias))

    rc, lines = run_cell(tree_f32, TL.CELL, capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert "head_grad_rel_err" in failed or any(
        n.startswith("loss_rel_gap") for n in failed), failed


# -- the cell's readers on what a traced run leaves ------------------------

TRACED = [5, 6, 7]
SCOPE_S = {CONV: 0.48, MIX: 0.12, ATTN: 0.09, KERNEL: 0.24, MLP: 0.36,
           ROUTE: 0.15, "fedml.model.moe": 0.03, EXPERTS: 0.33,
           "fedml.model.head": 0.12, "fedml.model.embed": 0.006}
ROUTED = 4 * 4 * 8192 * 4.0  # a round: 4 steps x 4 layers x tokens x ways
ROWS_HELD = ROUTED / 4  # ... of which the uniform share lands here
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def _traced_ctx(monkeypatch, counters=True, scopes=True, without=()):
    """A context as ``run_cell`` hands the readers after a traced run
    on the chip, the trace's reduction stubbed: three traced rounds of 4
    client steps each at the published shapes."""
    config = TL.real_config()

    def counted(r):
        if not counters:
            return {"round": r}
        return {"round": r, "moe_rows_routed": ROUTED,
                "moe_rows_held": ROWS_HELD, "moe_rows_compact": ROUTED,
                "moe_rows_max_expert": 16 * 1300.0}

    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    busy = {k: v for k, v in SCOPE_S.items() if k not in without}
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "spans": spans, "scopes": scopes, "rounds": len(TRACED),
        "scope_busy_s": busy if scopes else {}})
    return {"cell": {"config": config}, "traced_rounds": TRACED,
            "records": [counted(r) for r in (4, 7, 8)],
            "client_steps": 4 * len(TRACED), "spans": [],
            "round_p95_ms": 1512.0, "peaks": PEAKS}


def test_readers_on_a_traced_runs_spans_and_records(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    assert _read("conv_mixer_ms", ctx) == pytest.approx(200.0)
    assert _read("conv_mix_ms", ctx) == pytest.approx(40.0)
    assert _read("attn_ms.lfm2", ctx) == pytest.approx(110.0)
    assert _read("mlp_dense_ms.lfm2", ctx) == pytest.approx(120.0)
    assert _read("moe_route_ms.lfm2", ctx) == pytest.approx(60.0)
    assert _read("moe_experts_ms.lfm2", ctx) == pytest.approx(110.0)
    assert _read("head_loss_ms.lfm2", ctx) == pytest.approx(40.0)
    assert _read("embed_ms.lfm2", ctx) == pytest.approx(2.0)
    assert _read("round_p95_ms.lfm2", ctx) == 1512.0
    assert _read("eval_ms.lfm2", ctx) is None  # no span given
    assert _read("moe_held_share_pct.lfm2", ctx) == pytest.approx(25.0)
    assert _read("moe_compact_share_pct.lfm2", ctx) == pytest.approx(100)
    assert _read("moe_load_max_over_mean.lfm2", ctx) == pytest.approx(
        16 * 1300.0 * 8 / ROWS_HELD)
    extra = ctx["cell"]["config"]["model"]["extra"]
    flops, nbytes = SC.mix_work(extra, 8192, 1)
    least = 12 * max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12  # the memory bounds it
    assert _read("conv_mix_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.12)
    flops, nbytes = SC.attention_work(extra, 8192, 1, 512)
    least = 12 * max(flops / 197e12, nbytes / 819e9)
    assert _read("attn64_kernel_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.24)
    flops, nbytes = K.experts_work(extra, 3 * ROWS_HELD, 12 * 4)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_experts_roofline_pct.lfm2", ctx) == (
        pytest.approx(100 * least / 0.33))
    for share in ("conv_mix_roofline_pct", "attn64_kernel_roofline_pct",
                  "moe_experts_roofline_pct.lfm2"):
        assert 0 < _read(share, ctx) < 100


@pytest.mark.parametrize("counters, scopes", [(False, True), (True, False)])
def test_a_program_without_the_counters_or_scopes_gives_nothing(
        counters, scopes, monkeypatch):
    """The parent of the PR that added them: the line leaves the metric
    out and nothing raises."""
    ctx = _traced_ctx(monkeypatch, counters=counters, scopes=scopes)
    if not counters:
        assert _read("moe_experts_roofline_pct.lfm2", ctx) is None
        assert _read("moe_held_share_pct.lfm2", ctx) is None
        assert _read("conv_mixer_ms", ctx) == pytest.approx(200.0)
        assert _read("conv_mix_roofline_pct", ctx) is not None
    else:
        for name in NEW + ("attn_ms.lfm2", "moe_route_ms.lfm2",
                           "moe_experts_roofline_pct.lfm2"):
            assert _read(name, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_readers_scope_gives_nothing(
        name, monkeypatch):
    """Each new reader with its scope absent from a trace that has the
    others (a stack with no convolution layer, or one whose attention
    does not run the kernel) and present."""
    present = _traced_ctx(monkeypatch)
    assert _read(name, present) > 0
    scope = KERNEL if name.startswith("attn64") else MIX
    absent = _traced_ctx(
        monkeypatch, without=(scope, CONV) if name == "conv_mixer_ms"
        else (scope,))
    assert _read(name, absent) is None
    assert _read("attn_ms.lfm2", absent) is not None


def test_off_the_chip_or_on_another_model_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": [], "client_steps": 12,
           "peaks": PEAKS, "cell": {"config": TL.real_config()}}
    for name in NEW:
        assert _read(name, ctx) is None
    with open(f"{BENCH}/configs/resnet56-cifar10.json") as f:
        ctx["cell"] = {"config": json.load(f)}
    for name in NEW:
        assert _read(name, ctx) is None
    # stacks without a short convolution are another count's
    for name in ("laguna-xs2-share8", "joyai-llm-flash-share16",
                 "nemotron3-super-share64"):
        with open(f"{BENCH}/configs/{name}.json") as f:
            extra = json.load(f)["model"]["extra"]
        assert SC.mix_work(extra, 8192, 1) is None
        assert SC.attention_work(extra, 8192, 1, 512) is None


def test_the_counts_on_the_published_shapes():
    """Five convolution layers of 8,192 x 2,048 elements: the four
    arrays of one forward pass at two bytes, 0.67 GB a step, 0.82 ms of
    the chip's memory; one attention layer of 32 heads of 64 over 8
    key-value heads, 136 causal pairs of blocks of 512, 2 + 5 products
    a pair."""
    extra = TL.real_config()["model"]["extra"]
    flops, nbytes = SC.mix_work(extra, 8192, 1)
    elements = 5 * 8192 * 2048
    assert nbytes == 2 * 4 * elements == 671_088_640
    assert flops == 7 * elements
    assert SC.mix_work(extra, 8192, 2) == (2 * flops, 2 * nbytes)
    assert K.blocks_visited(8192, 512, None) == 136
    flops, nbytes = SC.attention_work(extra, 8192, 1, 512)
    assert flops == 32 * 136 * 7 * 2.0 * 512 * 512 * 64
    assert nbytes == 2 * 6 * (32 + 8) * 8192 * 64
    # compute-bound: 5.2 ms a step at the chip's peak against 0.3
    assert flops / 197e12 > 10 * nbytes / 819e9
    # a sequence of one block is one pair
    assert SC.attention_work(extra, 256, 1, 512)[0] == (
        32 * 7 * 2.0 * 256 * 256 * 64)
