"""``moe_sort_ms``: the device time a traced round spends in the round
program's ``sort`` operations, read off the operations by family — on
the table ``program_spans`` makes of a trace (chip 0's busy seconds by
op family and scope), and on the trace recorded from the tiny four-chip
cell, a program without the sparse layer's scope. A file of its own: a
PR that claims a gain edits no file the benchmark has."""

import json
import os

import pytest
from conftest import BENCH
from lib import program_spans as PS
from test_program_spans import traced  # noqa: F401  (the recorded trace)

import run

NAME = "moe_sort_ms"
ROUTE, ROUTER, EMBED = ("fedml.model.moe.route", "fedml.model.moe.router",
                        "fedml.model.embed")
DECODER_CELLS = ["laguna-xs2-c2of32-b2x2048", "keye-vl2-c2of32-b1x8192",
                 "nemotron3s-c2of32-b1x8192", "smallthinker-c2of32-b1x8192",
                 "joyai-flash-c2of32-b1x8192"]


def _read(ctx):
    return run._load_py(run.reader_path(BENCH, NAME), "bench_metric").read(
        ctx)


@pytest.mark.parametrize("families, want", [
    # the router ranks by a sort of [N, E]: with the ids' and the
    # embedding's, over 10 traced rounds
    ({("sort", ROUTE): 0.1689, ("sort", EMBED): 0.0021,
      ("fusion(kCustom)", ROUTE): 0.3}, 17.10),
    # a router of its own scope: its sort counts wherever it is booked
    ({("sort", ROUTER): 0.02, ("sort", ROUTE): 0.01,
      ("sort", PS.UNSCOPED): 0.005}, 3.5),
    # no operation of the family ran: the other families are not read
    ({("moe_rank_top_k", ROUTE): 0.05, ("sort_fusion", ROUTE): 0.2,
      ("fusion(kLoop)", ROUTE): 0.1}, 0.0),
])
def test_the_sorts_are_read_off_the_ops_by_family(
        families, want, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "scopes": True, "rounds": 10, "family_scope_s": families,
        "scope_busy_s": {ROUTE: 0.8, "fedml.model.attn": 2.0}})
    assert _read({}) == pytest.approx(want)


@pytest.mark.parametrize("table", [
    None,  # off the chip, or a trace without a fedml span
    {"scopes": False, "rounds": 10, "family_scope_s": {},
     "scope_busy_s": {}},  # no scope map: the parent of PR 24
    {"scopes": True, "rounds": 10, "scope_busy_s": {"fedml.local.grad": 1.0},
     "family_scope_s": {("sort", "fedml.sample"): 1.0}},
], ids=["no_trace", "no_scope_map", "no_sparse_layer"])
def test_a_program_without_the_sparse_layers_scope_gives_nothing(
        table, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: table)
    assert _read({}) is None


def test_on_a_recorded_trace_of_a_program_without_a_router(traced):  # noqa: F811
    """The tiny four-chip cell's trace (convolutions, no sparse layer)
    through the real reduction: its table has op families by scope, and
    no routing scope, so there is nothing to read."""
    t = PS.analyse(traced)
    assert t["family_scope_s"] and ROUTE not in t["scope_busy_s"]
    assert _read(traced) is None


def test_the_metric_is_asked_of_the_five_decoder_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "rounds_per_s", "workloads": DECODER_CELLS}
    assert run.reader_path(BENCH, NAME).endswith(
        os.path.join("layer_metrics", "moe_sort_ms.py"))
