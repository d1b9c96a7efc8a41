"""``moe_product_calls_per_step``: the grouped-product calls a sparse
layer makes an optimizer step, counted on the trace's own op events —
on a trace written out by hand (a round program's module events, the
products of its sparse layers, and what must not count: the products'
metadata, another program's products, operations before the window) and
on the trace recorded from the tiny four-chip cell, a program with no
sparse layer. A file of its own: a PR that claims a gain edits no file
the benchmark has."""

import json
import os
from types import SimpleNamespace as NS

import pytest
from conftest import BENCH
from lib import program_spans as PS
from lib import xplane
from test_program_spans import traced  # noqa: F401  (the recorded trace)

import run

NAME = "moe_product_calls_per_step"
SCOPE = "fedml.model.moe.experts"
ROUND, EVAL = "jit__round", "jit_evaluate"
DECODER_CELLS = ["laguna-xs2-c2of32-b2x2048", "keye-vl2-c2of32-b1x8192",
                 "nemotron3s-c2of32-b1x8192", "smallthinker-c2of32-b1x8192"]
STEPS, ROUNDS = 4, 3  # 2 clients a round x 2 steps; traced rounds


def _reader():
    return run._load_py(run.reader_path(BENCH, NAME), "bench_metric")


def _event(start_s, name, took_s=1e-4):
    return NS(start_ns=int(start_s * 1e9), duration_ns=int(took_s * 1e9),
              name=name)


def _hand_written(calls_a_step: int, layers: int):
    """-> (trace, scope map, window): :data:`ROUNDS` round programs of
    :data:`STEPS` steps x ``layers`` sparse layers x ``calls_a_step``
    products each, a second apart from 1 s on, an evaluation after
    each; a warm-up round before the window."""
    names = [f"ragged-dot-none.{i}" if i else "ragged-dot-none"
             for i in range(calls_a_step * layers)]
    scopes = {ROUND: {**{n: SCOPE for n in names},
                      "ragged-dot-metadata.1": SCOPE, "fusion.7": SCOPE,
                      "fusion.8": "fedml.model.moe.route"},
              EVAL: {"ragged-dot-none.3": SCOPE}}
    modules, ops = [], []
    for r in range(-1, ROUNDS):
        t = 1.0 + r
        modules += [_event(t, f"{ROUND}(123)", 0.5),
                    _event(t + 0.6, f"{EVAL}(456)", 0.2)]
        for step in range(STEPS):
            at = t + 0.1 * step
            ops += [_event(at + 1e-3 * i,
                           f"%{n} = bf16[128,64]{{1,0}} custom-call(%a)")
                    for i, n in enumerate(names)]
            ops += [_event(at + 0.05, "%ragged-dot-metadata.1 = (s32[17])"),
                    _event(at + 0.06, "%fusion.7 = bf16[8] fusion(%b)"),
                    _event(at + 0.07, "%fusion.8 = bf16[8] fusion(%c)")]
        ops.append(_event(t + 0.65, "%ragged-dot-none.3 = bf16[8,8] x(%d)"))
    chip = NS(name="/device:TPU:0", lines=[
        NS(name=xplane.MODULES_LINE, events=modules),
        NS(name=xplane.OPS_LINE, events=ops)])
    return NS(planes=[chip]), scopes, (1.0, 1.0 + ROUNDS)


@pytest.mark.parametrize("calls_a_step, layers", [(12, 5), (9, 5), (10, 4),
                                                  (8, 5), (6, 5)])
def test_the_products_are_counted_on_the_ops_by_name(
        calls_a_step, layers, monkeypatch, tmp_path):
    """12 -> 9 (three matrices an expert) and 8 -> 6 (two) a layer step,
    10 where a layer lets its last product go: the round program's
    ``ragged-dot-none*`` events in the window, and none of the rest."""
    data, scopes, window = _hand_written(calls_a_step, layers)
    reader = _reader()
    assert reader.product_calls(data, scopes, *window) == (
        ROUNDS * STEPS * layers * calls_a_step)
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(xplane, "load", lambda path: data)
    monkeypatch.setattr(PS, "load_scopes", lambda trace_dir: scopes)
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "scopes": True, "rounds": ROUNDS, "window": window})
    monkeypatch.setattr(xplane, "find_xplane",
                        lambda trace_dir: str(tmp_path / "t.xplane.pb"))
    extra = {"mlp_layer_types": ["dense"] + ["sparse"] * layers}
    ctx = {"client_steps": ROUNDS * STEPS, "cell": {
        "bench_dir": str(tmp_path), "name": "cell", "config": {"model": {
            "name": "decoder", "extra": extra, "input_shape": [8192]}}}}
    assert reader.read(ctx) == pytest.approx(calls_a_step)
    # no sparse layer, no decoder, no step counted: nothing to read
    dense = {"mlp_layer_types": ["dense"]}
    for change in ({"extra": dense}, {"name": "resnet"}):
        model = {**ctx["cell"]["config"]["model"], **change}
        assert reader.read({**ctx, "cell": {
            **ctx["cell"], "config": {"model": model}}}) is None
    assert reader.read({**ctx, "client_steps": 0}) is None


@pytest.mark.parametrize("table", [
    None,  # off the chip, or a trace without a fedml span
    {"scopes": False, "rounds": 10, "window": (0.0, 1.0)},  # no scope map
], ids=["no_trace", "no_scope_map"])
def test_a_run_without_a_trace_or_a_scope_map_gives_nothing(
        table, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: table)
    assert _reader().read({"client_steps": 12}) is None


def test_on_a_recorded_trace_of_a_program_without_sparse_layers(traced):  # noqa: F811
    """The tiny four-chip cell's trace (convolutions) through the real
    reduction: no operation of the family ran, under any scope; and the
    cell's model is no decoder stack, so there is nothing to read."""
    t = PS.analyse(traced)
    path = traced["path"]
    reader = _reader()
    assert reader.product_calls(
        xplane.load(path), PS.load_scopes(os.path.dirname(path)),
        *t["window"]) == 0
    model = {"name": "resnet_gn", "extra": {}, "input_shape": [16, 16, 3]}
    assert reader.read({**traced, "client_steps": 16, "cell": {
        **traced["cell"], "config": {"model": model}}}) is None
    sparse = {"name": "decoder", "input_shape": [64],
              "extra": {"mlp_layer_types": ["sparse"]}}
    assert reader.read({**traced, "client_steps": 16, "cell": {
        **traced["cell"], "config": {"model": sparse}}}) is None


def test_the_metric_is_asked_of_the_four_decoder_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "calls/step", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "rounds_per_s", "workloads": DECODER_CELLS}
    assert run.reader_path(BENCH, NAME).endswith(
        os.path.join("layer_metrics", NAME + ".py"))
