"""``joyai-flash-c2of32-b1x8192`` rehearsed on the CPU through the
harness itself: the configuration's own ``.py`` and ``.json`` shrunk to
tiny widths (``tiny_joyai.py``), its own traffic file at 8 clients. A
sound run is ``correct`` and its records carry the expert counters; the
float8 control in the program's place is not; a program that turns the
rotary part in halves is not. The cell's
readers on spans and records of the form a traced run leaves, scopes or
counters absent included, and the counting function of
``lib/latent_attention.py`` on the published shapes."""

import json

import pytest
from conftest import BENCH, run_cell
from lib import decoder_kernels as K
from lib import latent_attention as LA
from lib import program_spans as PS

import run
import tiny_joyai as TJ

NEW = ("attn_latent_ms", "latent_attn_roofline_pct", "mlp_dense_ms")
SUFFIXED = ("eval_ms", "round_p95_ms", "attn_ms", "moe_route_ms",
            "moe_experts_ms", "head_loss_ms", "moe_held_share_pct",
            "moe_load_max_over_mean", "moe_compact_share_pct",
            "moe_experts_roofline_pct", "moe_product_calls_per_step")
ATTN, LATENT, KERNEL, MLP, ROUTE, EXPERTS = (
    "fedml.model.attn", "fedml.model.attn.latent", "fedml.model.attn.kernel",
    "fedml.model.mlp", "fedml.model.moe.route", "fedml.model.moe.experts")


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TJ.make_tree(str(tmp_path_factory.mktemp("joyai_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TJ.make_tree(
        str(tmp_path_factory.mktemp("joyai_bf16")), "bfloat16")


def test_sound_run_is_correct_and_carries_the_counters(tree_f32, capsys):
    rc, lines = run_cell(tree_f32, TJ.CELL, seed=2 ** 31 + 7, seconds=3.0,
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
    cell = run.load_cell(TJ.CELL, tree_f32)
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= listed
    assert {name + ".joyai" for name in SUFFIXED} <= listed
    # the counts of one head size and the other configurations' kernels
    # are not pointed at this cell
    assert not listed & {"attn_kernel_roofline_pct", "attn_index_ms",
                         "attn_share_roofline_pct", "attn_dq_pass_ms",
                         "moe_experts_roofline_pct", "ssm_ms",
                         "moe_product_calls_per_step", "moe_router_ms"}
    rc, lines = run_cell(tree_f32, TJ.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.joyai" in metrics and "round_p95_ms.joyai" in metrics
    for device_number in NEW + ("attn_ms.joyai",
                                "moe_experts_roofline_pct.joyai"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TJ.CELL, "--seeds", "1,2,3",
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


def test_a_rotary_part_turned_in_halves_is_not_correct(
        tree_f32, capsys, monkeypatch):
    """A broken timed path: the rotary part of queries and keys paired
    ``i`` with ``i + rot / 2``, as every other configuration's is. The
    scores differ, and the comparison shows it."""
    from fedml_tpu.models import decoder

    def break_path(sim):
        monkeypatch.setattr(decoder, "ADJACENT", "nowhere")

    rc, lines = run_cell(tree_f32, TJ.CELL, capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert "head_grad_rel_err" in failed or any(
        n.startswith("loss_rel_gap") for n in failed), failed


# -- the cell's readers on what a traced run leaves ------------------------

TRACED = [5, 6, 7]
SCOPE_S = {ATTN: 0.09, LATENT: 0.36, KERNEL: 1.5, MLP: 0.3, ROUTE: 0.21,
           "fedml.model.moe": 0.03, EXPERTS: 0.45, "fedml.model.head": 0.33}
ROUTED = 4 * 4 * 8192 * 8.0  # a round: 4 steps x 4 layers x tokens x ways
ROWS_HELD = ROUTED / 16  # ... of which the uniform share lands here


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def _traced_ctx(monkeypatch, counters=True, scopes=True):
    """A context as ``run_cell`` hands the readers after a traced run
    on the chip, the trace's reduction stubbed: three traced rounds of 4
    client steps each at the published shapes."""
    config = TJ.real_config()

    def counted(r):
        if not counters:
            return {"round": r}
        return {"round": r, "moe_rows_routed": ROUTED,
                "moe_rows_held": ROWS_HELD, "moe_rows_compact": ROUTED,
                "moe_rows_max_expert": 16 * 300.0}

    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "spans": spans, "scopes": scopes, "rounds": len(TRACED),
        "scope_busy_s": SCOPE_S if scopes else {}})
    return {"cell": {"config": config}, "traced_rounds": TRACED,
            "records": [counted(r) for r in (4, 7, 8)],
            "client_steps": 4 * len(TRACED), "spans": [],
            "round_p95_ms": 1512.0,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}


def test_readers_on_a_traced_runs_spans_and_records(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    assert _read("attn_latent_ms", ctx) == pytest.approx(120.0)
    # the latent projections are an innermost scope: NOT in attn_ms
    assert _read("attn_ms.joyai", ctx) == pytest.approx(530.0)
    assert _read("mlp_dense_ms", ctx) == pytest.approx(100.0)
    assert _read("moe_route_ms.joyai", ctx) == pytest.approx(80.0)
    assert _read("moe_experts_ms.joyai", ctx) == pytest.approx(150.0)
    assert _read("head_loss_ms.joyai", ctx) == pytest.approx(110.0)
    assert _read("round_p95_ms.joyai", ctx) == 1512.0
    assert _read("eval_ms.joyai", ctx) is None  # no span given
    assert _read("moe_held_share_pct.joyai", ctx) == pytest.approx(6.25)
    assert _read("moe_compact_share_pct.joyai", ctx) == pytest.approx(100)
    assert _read("moe_load_max_over_mean.joyai", ctx) == pytest.approx(
        16 * 300.0 * 16 / ROWS_HELD)
    extra = ctx["cell"]["config"]["model"]["extra"]
    flops, nbytes = LA.latent_attention_work(extra, 8192, 1, 512)
    least = 12 * max(flops / 197e12, nbytes / 819e9)
    assert _read("latent_attn_roofline_pct", ctx) == pytest.approx(
        100 * least / 1.5)
    flops, nbytes = K.experts_work(extra, 3 * ROWS_HELD, 12 * 4)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_experts_roofline_pct.joyai", ctx) == (
        pytest.approx(100 * least / 0.45))
    assert 0 < _read("latent_attn_roofline_pct", ctx) < 100
    assert 0 < _read("moe_experts_roofline_pct.joyai", ctx) < 100


@pytest.mark.parametrize("counters, scopes", [(False, True), (True, False)])
def test_a_program_without_the_counters_or_scopes_gives_nothing(
        counters, scopes, monkeypatch):
    """The parent of the PR that added them: the line leaves the metric
    out and nothing raises."""
    ctx = _traced_ctx(monkeypatch, counters=counters, scopes=scopes)
    if not counters:
        assert _read("moe_experts_roofline_pct.joyai", ctx) is None
        assert _read("moe_held_share_pct.joyai", ctx) is None
        assert _read("attn_latent_ms", ctx) == pytest.approx(120.0)
        assert _read("latent_attn_roofline_pct", ctx) is not None
    else:
        for name in NEW + ("attn_ms.joyai", "moe_route_ms.joyai",
                           "moe_experts_roofline_pct.joyai"):
            assert _read(name, ctx) is None


def test_a_program_without_the_new_scopes_gives_no_ms(monkeypatch):
    """A stack of grouped-query heads has no latent projections, one
    with neither a dense layer nor a shared expert nothing under
    ``fedml.model.mlp``: no scope, nothing to read."""
    ctx = _traced_ctx(monkeypatch)
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "spans": [], "scopes": True, "rounds": 3,
        "scope_busy_s": {k: v for k, v in SCOPE_S.items()
                         if k not in (LATENT, MLP)}})
    assert _read("attn_latent_ms", ctx) is None
    assert _read("mlp_dense_ms", ctx) is None
    assert _read("attn_ms.joyai", ctx) == pytest.approx(530.0)


def test_off_the_chip_or_on_another_model_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": [], "client_steps": 12,
           "cell": {"config": TJ.real_config()}}
    for name in NEW:
        assert _read(name, ctx) is None
    with open(f"{BENCH}/configs/resnet56-cifar10.json") as f:
        ctx["cell"] = {"config": json.load(f)}
    assert _read("latent_attn_roofline_pct", ctx) is None
    # grouped-query heads of one size are another count's
    for name in ("laguna-xs2-share8", "smallthinker-21b-share4"):
        with open(f"{BENCH}/configs/{name}.json") as f:
            extra = json.load(f)["model"]["extra"]
        assert LA.latent_attention_work(extra, 8192, 1, 512) is None


def test_latent_attention_work_on_the_published_shapes():
    """32 heads with keys of 192 beside values of 128, 136 causal pairs
    of blocks of 512, one forward and one backward call a step: 320 +
    832 = 1,152 x 2 b^2 operations a visited pair."""
    extra = TJ.real_config()["model"]["extra"]
    record = extra["latent_attention"]
    assert LA.pair_widths(record) == (320, 832)
    assert K.blocks_visited(8192, 512, None) == 136
    flops, nbytes = LA.latent_attention_work(extra, 8192, 1, 512)
    assert flops == 5 * 32 * 136 * 1152 * 2.0 * 512 * 512
    assert nbytes == 5 * 2 * 32 * 8192 * 6 * (192 + 128)
    # compute-bound: 66.7 ms a step at the chip's peak against 6.1
    assert flops / 197e12 > 10 * nbytes / 819e9
    # two sequences a step are twice the work; a sequence of one block
    # is one pair
    assert LA.latent_attention_work(extra, 8192, 2, 512)[0] == 2 * flops
    assert LA.latent_attention_work(extra, 256, 1, 512)[0] == (
        5 * 32 * 1152 * 2.0 * 256 * 256)
