"""``embed_ms``: the device time a traced round spends under
``fedml.model.embed`` — on the table ``program_spans`` makes of a trace
(busy seconds by scope), on the trace recorded from the tiny four-chip
cell, a program without the scope, and through the tiny SmallThinker
cell's own list of readers. A file of its own: a PR that claims a gain
edits no file the benchmark has."""

import json
import os

import pytest
from conftest import BENCH
from lib import program_spans as PS
from test_program_spans import traced  # noqa: F401  (the recorded trace)

import run
import tiny_smallthinker as TS

NAME = "embed_ms"
EMBED = "fedml.model.embed"
DECODER_CELLS = ["laguna-xs2-c2of32-b2x2048", "keye-vl2-c2of32-b1x8192",
                 "nemotron3s-c2of32-b1x8192", "smallthinker-c2of32-b1x8192",
                 "joyai-flash-c2of32-b1x8192"]


def _read(ctx):
    return run._load_py(run.reader_path(BENCH, NAME), "bench_metric").read(
        ctx)


@pytest.mark.parametrize("scope_busy_s, want", [
    # the SmallThinker cell's traced rounds before PR 43: one scatter of
    # 61.5 ms and the gather, over 10 traced rounds
    ({EMBED: 0.6170, "fedml.model.head": 1.1622}, 61.70),
    # the scope's own time alone: its neighbours' are not counted in
    ({EMBED: 0.0608, "fedml.model.moe.route": 1.0789,
      "fedml.local.grad": 0.2714}, 6.08),
])
def test_the_scopes_time_of_a_traced_run(scope_busy_s, want, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "scopes": True, "rounds": 10, "scope_busy_s": scope_busy_s})
    assert _read({}) == pytest.approx(want)


@pytest.mark.parametrize("table", [
    None,  # off the chip, or a trace without a fedml span
    {"scopes": False, "rounds": 10, "scope_busy_s": {}},  # no scope map
    {"scopes": True, "rounds": 10,
     "scope_busy_s": {"fedml.local.grad": 1.0}},  # no decoder stack
], ids=["no_trace", "no_scope_map", "no_embed_scope"])
def test_without_a_trace_or_the_scope_there_is_nothing_to_read(
        table, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: table)
    assert _read({}) is None


def test_off_the_chip_there_is_no_trace():
    assert _read({"trace": None, "device": {"platform": "cpu"},
                  "traced_rounds": [5, 6, 7], "records": [],
                  "cell": {"config": TS.real_config()}}) is None


def test_on_a_recorded_trace_of_a_program_without_an_embedding(traced):  # noqa: F811
    """The tiny four-chip cell's trace (convolutions) through the real
    reduction: scopes, and none of them the embedding's."""
    t = PS.analyse(traced)
    assert t["scope_busy_s"] and EMBED not in t["scope_busy_s"]
    assert _read(traced) is None


def test_the_metric_is_asked_of_the_five_decoder_cells(tmp_path):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "rounds_per_s", "workloads": DECODER_CELLS}
    assert run.reader_path(BENCH, NAME).endswith(
        os.path.join("layer_metrics", "embed_ms.py"))
    # the tiny cell's rehearsal runs every reader its cell lists
    cell = run.load_cell(TS.CELL, TS.make_tree(str(tmp_path)))
    assert NAME in {m["name"] for m in cell["per_layer"]}
