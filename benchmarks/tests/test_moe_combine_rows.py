"""``moe_combine_rows_pct``: the rows the sparse layers' combine read
back into the tokens as a share of the assignments the routers made,
read off what the program reports — the ``moe_rows_combined`` and
``moe_rows_routed`` attrs of the traced rounds' ``fedml.log`` spans, in
the span format ``program_spans.program_host_spans`` reads off a trace,
and the last traced round (whose span the profiler cuts) off its
record. A token reads a row a slot, ``min(top_k, held experts)``: 8 of
22 ways at the Nemotron share's shapes, every way where the held
experts are the more. A file of its own beside ``test_moe_compact_
share.py``'s: a PR that claims a gain edits no file the benchmark has."""

import json
import os

import pytest
from conftest import BENCH
from lib import program_spans as PS
from test_moe_compact_share import TRACED, _read

import run

NAME = "moe_combine_rows_pct.nemotron"
# a round of the cell: 4 client steps x 5 sparse layers x 8,192 tokens
CALLS = 4 * 5 * 8192.0


@pytest.mark.parametrize("ways, slots, want", [
    (22, 8, 36.36),  # 8 held of 512, 22 ways
    (8, 8, 100.0),  # a stack whose ways are the fewer: 8 over 32 or 16 held
    (22, None, None),  # the parent: no such counter
])
def test_combine_rows_are_read_off_the_log_spans_and_records(
        ways, slots, want, monkeypatch):
    counted = lambda r: {"round": r, "moe_rows_routed": CALLS * ways,
                         "moe_rows_held": CALLS * ways / 64,
                         "moe_rows_compact": CALLS * ways,
                         **({"moe_rows_combined": CALLS * slots}
                            if slots else {})}
    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    monkeypatch.setattr(PS, "analyse", lambda ctx: {"spans": spans})
    ctx = {"traced_rounds": TRACED,
           "records": [counted(r) for r in (4, 7, 8)]}
    assert _read(NAME, ctx) == (
        want if want is None else pytest.approx(want, abs=0.005))
    # the counters beside it are read as before, with or without it
    assert _read("moe_held_share_pct.nemotron", ctx) == pytest.approx(
        100 / 64)
    assert _read("moe_compact_share_pct.nemotron", ctx) == pytest.approx(
        100.0)


def test_a_traced_round_counted_nowhere_gives_nothing(monkeypatch):
    counted = lambda r: {"round": r, "moe_rows_routed": CALLS * 22,
                         "moe_rows_combined": CALLS * 8}
    monkeypatch.setattr(PS, "analyse", lambda ctx: {"spans": [
        (5.0, 5.1, "fedml.log", counted(5))]})
    ctx = {"traced_rounds": TRACED, "records": [counted(7)]}
    assert _read(NAME, ctx) is None


def test_off_the_chip_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": []}
    assert _read(NAME, ctx) is None


def test_the_metric_is_asked_of_the_nemotron_cell_alone():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "round program",
        "moves": "rounds_per_s",
        "workloads": ["nemotron3s-c2of32-b1x8192"]}
    assert run.reader_path(BENCH, NAME).endswith(
        os.path.join("layer_metrics", "moe_combine_rows_pct.py"))
