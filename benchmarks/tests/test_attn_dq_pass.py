"""``attn_dq_pass_ms``: the device time a traced round spends in the
blockwise attention kernel's separate dq pass, read off the round
program's operations by name — on the table ``program_spans`` makes of a
trace (chip 0's busy seconds by op family and scope), and on the trace
recorded from the tiny four-chip cell, a program without the kernel's
scope. A file of its own: a PR that claims a gain edits no file the
benchmark has."""

import json
import os

import pytest
from conftest import BENCH
from lib import program_spans as PS
from test_program_spans import traced  # noqa: F401  (the recorded trace)

import run

NAME = "attn_dq_pass_ms"
KERNEL = "fedml.model.attn.kernel"
DECODER_CELLS = ["laguna-xs2-c2of32-b2x2048", "keye-vl2-c2of32-b1x8192",
                 "nemotron3s-c2of32-b1x8192"]


def _read(ctx):
    return run._load_py(run.reader_path(BENCH, NAME), "bench_metric").read(
        ctx)


@pytest.mark.parametrize("families, want", [
    # two backward kernels a layer: the dq pass is its own operation
    ({("splash_mqa_dq_no_residuals", KERNEL): 1.633,
      ("splash_mqa_dkv_no_residuals", KERNEL): 2.144,
      ("splash_mqa_fwd_residuals", KERNEL): 1.695}, 163.3),
    # one walk: dq comes out of the dk/dv kernel, no such operation ran
    ({("splash_mqa_dkv_no_residuals", KERNEL): 2.6,
      ("splash_mqa_fwd_residuals", KERNEL): 1.695,
      ("reduce", KERNEL): 0.2}, 0.0),
    # some layers of a stack keep the pass (booked wherever the op lies)
    ({("splash_mqa_dq_no_residuals", KERNEL): 0.2,
      ("splash_mqa_dq_no_residuals", PS.UNSCOPED): 0.1,
      ("splash_mqa_dkv_no_residuals", KERNEL): 0.4}, 30.0),
])
def test_the_dq_pass_is_read_off_the_ops_by_name(
        families, want, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: {
        "scopes": True, "rounds": 10, "family_scope_s": families,
        "scope_busy_s": {KERNEL: 6.5, "fedml.model.attn": 2.0}})
    assert _read({}) == pytest.approx(want)


@pytest.mark.parametrize("table", [
    None,  # off the chip, or a trace without a fedml span
    {"scopes": False, "rounds": 10, "family_scope_s": {},
     "scope_busy_s": {}},  # no scope map: the parent of PR 24
    {"scopes": True, "rounds": 10, "scope_busy_s": {"fedml.local.grad": 1.0},
     "family_scope_s": {("fusion(kOutput)", "fedml.local.grad"): 1.0}},
], ids=["no_trace", "no_scope_map", "no_kernel_scope"])
def test_a_program_without_the_kernels_scope_gives_nothing(
        table, monkeypatch):
    monkeypatch.setattr(PS, "analyse", lambda ctx: table)
    assert _read({}) is None


def test_on_a_recorded_trace_of_a_program_without_attention(traced):  # noqa: F811
    """The tiny four-chip cell's trace (convolutions, no attention)
    through the real reduction: its table has op families by scope, and
    none under the kernel's scope, so there is nothing to read."""
    t = PS.analyse(traced)
    assert t["family_scope_s"] and KERNEL not in t["scope_busy_s"]
    assert _read(traced) is None


def test_the_metric_is_asked_of_the_three_decoder_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "rounds_per_s", "workloads": DECODER_CELLS}
    assert run.reader_path(BENCH, NAME).endswith(
        os.path.join("layer_metrics", "attn_dq_pass_ms.py"))
