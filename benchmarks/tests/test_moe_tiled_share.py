"""``moe_tiled_share_pct``: of the assignments that landed on held
experts in the traced rounds, the share whose grouped products ran in
the row-tiled kernels, read off what the program reports — the
``moe_rows_tiled`` and ``moe_rows_held`` attrs of the traced rounds'
``fedml.log`` spans, and the last traced round (whose span the profiler
cuts) off its record. In a file of its own, as
``test_moe_compact_share.py`` is."""

import json
import os

import pytest
from conftest import BENCH
from lib import program_spans as PS

import run

TRACED = [5, 6, 7]
NAME = "moe_tiled_share_pct"


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


@pytest.mark.parametrize("tiled, want", [
    ({5: 130.0, 6: 130.0, 7: 130.0}, 100.0),  # the rule took every call
    ({5: 0.0, 6: 0.0, 7: 0.0}, 0.0),  # ... or left all to ragged_dot
    ({5: 130.0, 7: 130.0}, None),  # a traced round counted nowhere
    ({}, None),  # the parent: no such counter
])
def test_tiled_share_is_read_off_the_log_spans_and_records(
        tiled, want, monkeypatch):
    counted = lambda r: {"round": r, "moe_rows_routed": 1024.0,
                         "moe_rows_held": 130.0,
                         **({"moe_rows_tiled": tiled[r]}
                            if r in tiled else {})}
    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    monkeypatch.setattr(PS, "analyse", lambda ctx: {"spans": spans})
    ctx = {"traced_rounds": TRACED,
           "records": [counted(r) for r in (4, 7, 8)]}
    assert _read(NAME, ctx) == (
        want if want is None else pytest.approx(want))


def test_off_the_chip_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": []}
    assert _read(NAME, ctx) is None


def test_the_metric_is_asked_of_the_six_decoder_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    decoders = [w["name"] for w in bench["workloads"]
                if w["config"] != "resnet56-cifar10"]
    assert entry["workloads"] == decoders and len(decoders) == 6
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "round program", "rounds_per_s", "program_counter")
