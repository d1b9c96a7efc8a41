"""``nemotron3s-c2of32-b1x8192`` rehearsed on the CPU through the
harness itself: the configuration's own ``.py`` and ``.json`` shrunk to
tiny widths (``tiny_nemotron.py``), its own traffic file at 8 clients. A
sound run is ``correct`` and its records carry the expert counters; the
float8 control in the program's place is not; a program whose scan
forgets what the earlier chunks left is not. The new readers on spans
and records of the form a traced run leaves, scopes or counters absent
included, and the counting functions of ``lib/state_space.py`` on the
published shapes."""

import json

import pytest
from conftest import BENCH, run_cell
from lib import program_spans as PS
from lib import state_space as SS

import run
import tiny_nemotron as TN

NEW = ("ssm_ms", "ssm_scan_ms", "moe_latent_ms", "ssm_scan_roofline_pct",
       "latent_experts_roofline_pct")
SUFFIXED = ("eval_ms", "round_p95_ms", "attn_ms", "moe_route_ms",
            "moe_experts_ms", "head_loss_ms", "moe_held_share_pct",
            "moe_load_max_over_mean", "moe_compact_share_pct")


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TN.make_tree(str(tmp_path_factory.mktemp("nemotron_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TN.make_tree(
        str(tmp_path_factory.mktemp("nemotron_bf16")), "bfloat16")


def test_sound_run_is_correct_and_carries_the_counters(tree_f32, capsys):
    rc, lines = run_cell(tree_f32, TN.CELL, seed=2 ** 31 + 7, seconds=3.0,
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
    cell = run.load_cell(TN.CELL, tree_f32)
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= listed
    assert {name + ".nemotron" for name in SUFFIXED} <= listed
    # the other configurations' kernels are not pointed at this cell
    assert not listed & {"moe_experts_roofline_pct", "attn_index_ms",
                         "moe_experts_roofline_pct.keye",
                         "attn_kernel_roofline_pct"}
    rc, lines = run_cell(tree_f32, TN.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.nemotron" in metrics
    assert "round_p95_ms.nemotron" in metrics
    for device_number in NEW + ("attn_ms.nemotron", "moe_experts_ms.nemotron"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TN.CELL, "--seeds", "1,2,3",
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


def test_a_scan_that_forgets_the_earlier_chunks_is_not_correct(
        tree_f32, capsys, monkeypatch):
    """A broken timed path: no state enters any chunk, so a token reads
    its own chunk's 16 tokens and nothing before them."""
    import jax.numpy as jnp

    from fedml_tpu.ops import ssm

    def break_path(sim):
        monkeypatch.setattr(
            ssm, "entering_states", lambda carry, own: jnp.zeros_like(own))

    rc, lines = run_cell(tree_f32, TN.CELL, capsys=capsys,
                         break_path=break_path)
    assert rc == 0
    assert lines[-1]["correct"] is False
    failed = {c["number"] for c in lines
              if c.get("phase") == "check" and not c["ok"]}
    assert "head_grad_rel_err" in failed or any(
        n.startswith("loss_rel_gap") for n in failed), failed


# -- the new readers on what a traced run leaves ---------------------------

TRACED = [5, 6, 7]
SCOPE_S = {SS.SSM: 1.2, SS.SCAN: 0.6, SS.LATENT: 0.3,
           "fedml.model.moe.experts": 0.15, "fedml.model.attn": 0.09,
           "fedml.model.attn.kernel": 0.06}
ROWS_HELD = 4 * 5 * 2816.0  # a round: 4 steps x 5 layers x the uniform share


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def _traced_ctx(monkeypatch, counters=True, scopes=True):
    """A context as ``run_cell`` hands the readers after a traced run
    on the chip, the trace's reduction stubbed: three traced rounds of 4
    client steps each at the published shapes."""
    config = TN.real_config()

    def counted(r):
        rec = {"round": r, "moe_rows_routed": 4 * 5 * 8192 * 22.0}
        if counters:
            rec["moe_rows_held"] = ROWS_HELD
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
    assert _read("ssm_ms", ctx) == pytest.approx(600.0)
    assert _read("ssm_scan_ms", ctx) == pytest.approx(200.0)
    assert _read("moe_latent_ms", ctx) == pytest.approx(100.0)
    assert _read("attn_ms.nemotron", ctx) == pytest.approx(50.0)
    assert _read("moe_experts_ms.nemotron", ctx) == pytest.approx(50.0)
    extra = ctx["cell"]["config"]["model"]["extra"]
    flops, nbytes = SS.scan_work(extra, 8192)
    calls = 12 * 5  # client steps x state-space layers x batch 1
    least = max(calls * flops / 197e12, calls * nbytes / 819e9)
    assert _read("ssm_scan_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.6)
    flops, nbytes = SS.latent_experts_work(extra, 3 * ROWS_HELD, 12 * 5)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("latent_experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.15)
    assert 0 < _read("ssm_scan_roofline_pct", ctx) < 100
    assert 0 < _read("latent_experts_roofline_pct", ctx) < 100


@pytest.mark.parametrize("counters, scopes", [(False, True), (True, False)])
def test_a_program_without_the_counters_or_scopes_gives_nothing(
        counters, scopes, monkeypatch):
    """The parent of the PR that added them: the line leaves the metric
    out and nothing raises."""
    ctx = _traced_ctx(monkeypatch, counters=counters, scopes=scopes)
    if not counters:
        assert _read("latent_experts_roofline_pct", ctx) is None
        assert _read("ssm_scan_roofline_pct", ctx) is not None
        assert _read("ssm_ms", ctx) == pytest.approx(600.0)
    else:
        for name in NEW:
            assert _read(name, ctx) is None


def test_off_the_chip_or_on_another_model_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": [], "client_steps": 12,
           "cell": {"config": TN.real_config()}}
    for name in NEW:
        assert _read(name, ctx) is None
    for other in ("keye-vl2-a3b-share8", "resnet56-cifar10"):
        with open(f"{BENCH}/configs/{other}.json") as f:
            ctx["cell"] = {"config": json.load(f)}
        assert SS.state_space_sizes(ctx) is None
        assert _read("ssm_scan_roofline_pct", ctx) is None
        assert _read("latent_experts_roofline_pct", ctx) is None


def test_work_counts_on_the_published_shapes():
    extra = TN.real_config()["model"]["extra"]
    ctx = {"cell": {"config": TN.real_config()}}
    assert SS.state_space_sizes(ctx)[1:] == (8192, 5)
    assert SS.held(extra["state_space"]) == (16, 1)
    flops, nbytes = SS.scan_work(extra, 8192)
    per_head = 128 * 128 * 64 * 3 + 128 * 64 * 128 * (3 + 4)
    assert flops == 2.0 * 64 * (128 * 128 * 128 * 4 + 16 * per_head)
    # the entering states, float32, written once and read once
    assert nbytes > 2 * 64 * 16 * 64 * 128 * 4
    flops, nbytes = SS.latent_experts_work(extra, 2816.0, 1.0)
    assert flops == 2816 * 2 * 2 * 1024 * 2688 * 4
    assert nbytes > 8 * 8 * 1024 * 2688 * 2  # the matrices, 8 calls
