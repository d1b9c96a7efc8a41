"""``ling3-flash-c2of32-b1x8192`` rehearsed on the CPU through the
harness itself: the configuration's own ``.py`` and ``.json`` shrunk to
tiny widths (``tiny_ling.py``), its own traffic file at 8 clients. A
sound run is ``correct`` and its records carry the expert counters, the
open groups' among them; the float8 control in the program's place is
not; a delta rule that forgets its state between chunks is not. The
cell's readers on spans and records of the form a traced run leaves,
scopes or counters absent included; the counting functions of
``lib/delta_rule.py`` and ``lib/latent_share.py`` against hand-reckoned
values; ``step_flops`` against a sum written out."""

import json

import pytest
from conftest import BENCH, run_cell
from lib import decoder_kernels as K
from lib import delta_rule as DR
from lib import latent_attention as LA
from lib import latent_share as LS
from lib import program_spans as PS

import run
import tiny_ling as TL

#: the two entries this PR could register under the 128 the benchmark's
#: file may hold, and the three readers that wait for a ``benchmark`` PR
REGISTERED = ("delta_mixer_ms", "delta_scan_roofline_pct")
WAITING = ("delta_scan_ms", "latent_share_roofline_pct",
           "moe_group_open_pct")
#: entries that stood, with this cell appended to their ``workloads``
APPENDED = ("attn_ms", "attn_latent_ms", "mlp_dense_ms", "moe_route_ms",
            "moe_experts_ms", "moe_experts_roofline_pct",
            "moe_held_share_pct", "moe_load_max_over_mean",
            "moe_compact_share_pct", "moe_tiled_share_pct", "head_loss_ms",
            "eval_ms.chip1", "round_p95_ms.chip1")
DELTA, MIX, SCAN, ATTN, LATENT, KERNEL, MLP, ROUTE, EXPERTS = (
    "fedml.model.delta", "fedml.model.delta.mix", "fedml.model.delta.scan",
    "fedml.model.attn", "fedml.model.attn.latent", "fedml.model.attn.kernel",
    "fedml.model.mlp", "fedml.model.moe.route", "fedml.model.moe.experts")


@pytest.fixture(scope="module")
def tree_f32(tmp_path_factory):
    return TL.make_tree(str(tmp_path_factory.mktemp("ling_f32")))


@pytest.fixture(scope="module")
def tree_bf16(tmp_path_factory):
    return TL.make_tree(
        str(tmp_path_factory.mktemp("ling_bf16")), "bfloat16")


def test_the_cells_files_load_and_name_what_the_issue_names():
    """The real files: one configuration, one one-chip cell on the
    traffic five siblings share, the reference's five exports, limits
    for the four numbers, the two new entries LAST and every other
    metric of the cell an entry that stood."""
    cell = run.load_cell(TL.CELL)
    assert cell["cell"] == {
        "name": TL.CELL, "config": TL.CONFIG, "traffic": TL.TRAFFIC,
        "chips": 1, "why": cell["cell"]["why"]}
    assert len(cell["cell"]["why"]) <= 200
    assert set(cell["config"]["correct_limits"]) == {
        "loss_rel_gap", "head_grad_rel_err", "first_grad_norm_gap",
        "change_norm_gap"}
    listed = [m["name"] for m in cell["per_layer"]]
    assert tuple(listed[-2:]) == REGISTERED
    assert set(APPENDED) <= set(listed)
    # embed_ms and moe_sort_ms stand as rehearsal tests pin them
    assert not set(listed) & (set(WAITING) | {
        "embed_ms", "moe_sort_ms",
        "latent_attn_roofline_pct", "moe_product_calls_per_step",
        "attn_kernel_roofline_pct", "ssm_ms", "conv_mixer_ms"})
    with open(f"{BENCH}/../BENCHMARK.json") as f:
        bench = json.load(f)
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in REGISTERED:
            assert m["workloads"] == [TL.CELL]


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
    assert result["checks"]["head_grad_rel_err"]["value"] < 1e-3


def test_traced_rehearsal_runs_every_reader_of_the_cell(tree_f32, capsys):
    """Off the chip there is no device trace, so every device number and
    every counter read off a trace is left out; the rehearsal still runs
    every reader the cell lists, the new ones among them."""
    rc, lines = run_cell(tree_f32, TL.CELL, seconds=6.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert "eval_ms.chip1" in metrics and "round_p95_ms.chip1" in metrics
    for device_number in REGISTERED + ("attn_ms", "moe_experts_roofline_pct"):
        assert device_number not in metrics


def test_lower_precision_control_is_not_correct(tree_bf16, capsys):
    import calibrate

    rc = calibrate.main(
        ["--workload", TL.CELL, "--seeds", "2,3,4",
         "--control-seeds", "2,3"], root=tree_bf16, require_chip=False)
    assert rc == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()
             if line.startswith("{")]
    sides = {"program": [], "control_fp8": []}
    for rec in lines[:-1]:
        sides[rec["side"]].append(rec["ok"])
    assert sides == {"program": [True] * 3, "control_fp8": [False] * 2}, (
        lines[-1]["summary"])


def test_a_state_forgotten_between_chunks_is_not_correct(
        tree_f32, capsys, monkeypatch):
    """A broken timed path: every chunk of the delta rule starts from an
    empty state. The outputs after the first chunk differ, and the
    comparison shows it."""
    import jax.numpy as jnp

    from fedml_tpu.ops import delta

    def break_path(sim):
        monkeypatch.setattr(
            delta, "entering_states",
            lambda decay, kt, w, u: jnp.zeros(
                (*decay.shape, u.shape[-1]), jnp.float32))

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
SCOPE_S = {DELTA: 1.2, MIX: 0.6, SCAN: 0.9, ATTN: 0.09, LATENT: 0.06,
           KERNEL: 0.12, MLP: 0.3, ROUTE: 0.21, "fedml.model.moe": 0.03,
           EXPERTS: 0.15, "fedml.model.head": 0.33}
TOKENS = 4 * 4 * 8192.0  # a round: 4 steps x 4 sparse layers x tokens
ROUTED = TOKENS * 8  # ... x ways
ROWS_HELD = ROUTED / 64  # ... of which the uniform share lands here


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def _traced_ctx(monkeypatch, counters=True, scopes=True, config=None):
    """A context as ``run_cell`` hands the readers after a traced run
    on the chip, the trace's reduction stubbed: three traced rounds of 4
    client steps each at the published shapes."""
    config = config or TL.real_config()

    def counted(r):
        if not counters:
            return {"round": r}
        return {"round": r, "moe_rows_routed": ROUTED,
                "moe_rows_held": ROWS_HELD, "moe_rows_compact": ROUTED,
                "moe_rows_max_expert": 8 * 40.0,
                "moe_rows_tiled": ROWS_HELD,
                "moe_tokens_group_open": TOKENS / 2}

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
    assert _read("delta_mixer_ms", ctx) == pytest.approx(900.0)
    assert _read("delta_scan_ms", ctx) == pytest.approx(300.0)
    assert _read("attn_latent_ms", ctx) == pytest.approx(20.0)
    # the latent projections are an innermost scope: NOT in attn_ms
    assert _read("attn_ms", ctx) == pytest.approx(70.0)
    assert _read("mlp_dense_ms", ctx) == pytest.approx(100.0)
    assert _read("moe_route_ms", ctx) == pytest.approx(80.0)
    assert _read("moe_experts_ms", ctx) == pytest.approx(50.0)
    assert _read("head_loss_ms", ctx) == pytest.approx(110.0)
    assert _read("round_p95_ms.chip1", ctx) == 1512.0
    assert _read("moe_held_share_pct", ctx) == pytest.approx(100 / 64)
    assert _read("moe_tiled_share_pct", ctx) == pytest.approx(100)
    assert _read("moe_group_open_pct", ctx) == pytest.approx(50.0)
    extra = ctx["cell"]["config"]["model"]["extra"]
    work = [DR.scan_work(extra, 8192, l) for l in range(5)]
    least = 12 * max(sum(w[0] for w in work) / 197e12,
                     sum(w[1] for w in work) / 819e9)
    assert _read("delta_scan_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.9)
    flops, nbytes = LS.latent_share_work(extra, 8192, 1, 512)
    least = 12 * max(flops / 197e12, nbytes / 819e9)
    assert _read("latent_share_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.12)
    flops, nbytes = K.experts_work(extra, 3 * ROWS_HELD, 12 * 4)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 0.15)
    for share in ("delta_scan_roofline_pct", "latent_share_roofline_pct",
                  "moe_experts_roofline_pct"):
        assert 0 < _read(share, ctx) < 100


@pytest.mark.parametrize("counters, scopes", [(False, True), (True, False)])
def test_a_program_without_the_counters_or_scopes_gives_nothing(
        counters, scopes, monkeypatch):
    """The parent of this PR: the line leaves the metric out and nothing
    raises."""
    ctx = _traced_ctx(monkeypatch, counters=counters, scopes=scopes)
    if not counters:
        assert _read("moe_group_open_pct", ctx) is None
        assert _read("moe_held_share_pct", ctx) is None
        assert _read("delta_mixer_ms", ctx) == pytest.approx(900.0)
        assert _read("delta_scan_roofline_pct", ctx) is not None
    else:
        for name in REGISTERED + WAITING[:2] + ("attn_ms", "moe_route_ms"):
            assert _read(name, ctx) is None


def test_a_stack_without_such_layers_gives_nothing(monkeypatch):
    """JoyAI's stack has no delta-rule layer and one group; its program
    has no such scope and (the parent's) no such counter."""
    with open(f"{BENCH}/configs/joyai-llm-flash-share16.json") as f:
        ctx = _traced_ctx(monkeypatch, config=json.load(f))
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "spans": [], "scopes": True, "rounds": 3,
        "scope_busy_s": {k: v for k, v in SCOPE_S.items()
                         if k not in (DELTA, MIX, SCAN)}})
    assert _read("delta_mixer_ms", ctx) is None
    assert _read("delta_scan_ms", ctx) is None
    assert _read("delta_scan_roofline_pct", ctx) is None
    extra = ctx["cell"]["config"]["model"]["extra"]
    # all heads held: the share's count is the whole stack's
    assert LS.latent_share_work(extra, 8192, 1, 512) == (
        LA.latent_attention_work(extra, 8192, 1, 512))


def test_off_the_chip_or_on_another_model_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": [], "client_steps": 12,
           "cell": {"config": TL.real_config()}}
    for name in REGISTERED + WAITING:
        assert _read(name, ctx) is None
    with open(f"{BENCH}/configs/resnet56-cifar10.json") as f:
        ctx["cell"] = {"config": json.load(f)}
    for name in REGISTERED + WAITING:
        assert _read(name, ctx) is None
    assert DR.delta_sizes(ctx) is None


# -- the counts, by hand ----------------------------------------------------


def test_delta_rule_work_on_the_published_shapes():
    """16 heads of 128 x 128 held, 128 chunks of 64 tokens a layer: per
    chunk and head forward 2,016 x 128 (A) + 2,080 x 128 (P) + 2,016 x
    256 (the solve) + 3 x 64 x 128 x 128 (two reads, one write of a
    state) + 2,080 x 128 (P against the values) = 4,452,352
    multiply-accumulates, 1.42 times the sequential form's 3 x 64 x 128
    x 128; three passes, two operations each."""
    extra = TL.real_config()["model"]["extra"]
    assert DR.chunk_macs(64, 128) == 4_452_352 == (
        2016 * 128 + 2080 * 128 + 2016 * 256 + 3 * 64 * 128 * 128
        + 2080 * 128)
    assert DR.chunk_macs(64, 128) / (3 * 64 * 128 * 128) == pytest.approx(
        1.4154, abs=1e-4)
    flops, nbytes = DR.scan_work(extra, 8192, 0)
    assert flops == 2 * 3 * 128 * 16 * 4_452_352
    rows = 8192 * 16 * 128 * 2  # q, k, v, o or a cotangent, bfloat16
    decays, writes = 8192 * 16 * 128 * 4, 8192 * 16 * 4
    states = 128 * 16 * 128 * 128 * 4
    assert nbytes == (4 * rows + decays + writes + states) + (
        4 * rows + decays + writes + states + 3 * rows + decays + writes)
    assert nbytes == 840_433_664
    # bound by the bytes: 1.02 ms a layer and step against 0.28 ms
    assert nbytes / 819e9 > 3 * flops / 197e12
    # the fallback's 8 heads are half the work; a sequence of one chunk
    half = {**extra, "query_heads_held": [0, 8]}
    assert DR.scan_work(half, 8192, 0) == (flops / 2, nbytes / 2)
    assert DR.scan_work(extra, 64, 0)[0] == flops / 128
    assert DR.heads_held({**extra, "query_heads_held": None}, 0) == 32


def test_latent_share_work_counts_the_heads_held():
    """ONE latent layer of 16 held heads with keys of 192 beside values
    of 128, 136 causal pairs of blocks of 512: half of what
    ``lib/latent_attention.py`` counts for the 32 published heads."""
    extra = TL.real_config()["model"]["extra"]
    flops, nbytes = LS.latent_share_work(extra, 8192, 1, 512)
    assert flops == 16 * 136 * 1152 * 2.0 * 512 * 512
    assert nbytes == 2 * 16 * 8192 * 6 * (192 + 128)
    whole = LA.latent_attention_work(extra, 8192, 1, 512)
    assert (2 * flops, 2 * nbytes) == whole
    assert LS.latent_share_work(
        {**extra, "layer_types": ["delta_attention"] * 6}, 8192, 1, 512
    ) is None


def test_step_flops_is_the_published_arithmetic_written_out():
    """The share's matrix work a token, forward: five delta-rule mixers
    (six projections of 2560 x 2048, the write strength's 2560 x 16, the
    SEQUENTIAL rule's three products of 128 x 128 a head), the latent
    layer, two dense and four sparse feed-forwards, the head; times 3
    for the backward pass, 2 operations, 8,192 tokens."""
    ref = run.load_cell(TL.CELL)["reference"]
    macs = (
        5 * (6 * 2560 * 2048 + 2560 * 16)
        + 5 * 16 * 3 * 128 * 128
        + 2560 * (16 * 192 + 576 + 16) + 512 * 16 * 256
        + 16 * 128 * 2560
        + 16 * (192 + 128) * 8193 / 2
        + 2 * 3 * 2560 * 6144
        + 4 * 2560 * 512
        + 4 * 8 * 8 / 512 * 3 * 2560 * 768
        + 4 * 3 * 2560 * 768
        + 2560 * 19648)
    assert sum(ref.token_macs().values()) == pytest.approx(macs, rel=1e-12)
    assert ref.token_macs()["delta_rule"] == 5 * 16 * 3 * 128 * 128
    assert ref.step_flops(1) == pytest.approx(6 * macs * 8192, rel=1e-12)
    assert ref.step_flops(2) == 2 * ref.step_flops(1)
    # 375.6 M multiply-accumulates a token (ISSUE 49's 348 M are the
    # matrices a token meets, without the scores), 18.5 TFLOP a step
    assert macs == 375_572_992
