"""``moe_compact_share_pct``: the share of the routers' assignments made
in sparse-layer calls that went through the bounded row buffer, read off
what the program reports — the ``moe_rows_compact`` and
``moe_rows_routed`` attrs of the traced rounds' ``fedml.log`` spans, in
the span format ``program_spans.program_host_spans`` reads off a trace,
and the last traced round (whose span the profiler cuts) off its
record. A case beside ``test_decoder_cell.py``'s, in a file of its own:
a PR that claims a gain edits no file the benchmark has."""

import pytest
from conftest import BENCH
from lib import program_spans as PS

import run

TRACED = [5, 6, 7]


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


@pytest.mark.parametrize("compact, want", [
    ({5: 1024.0, 6: 1024.0, 7: 1024.0}, 100.0),
    ({5: 1024.0, 6: 0.0, 7: 512.0}, 50.0),
    ({5: 1024.0, 7: 1024.0}, None),  # a traced round counted nowhere
    ({}, None),  # the parent: no such counter
])
def test_compact_share_is_read_off_the_log_spans_and_records(
        compact, want, monkeypatch):
    counted = lambda r: {"round": r, "moe_rows_routed": 1024.0,
                         "moe_rows_held": 130.0,
                         **({"moe_rows_compact": compact[r]}
                            if r in compact else {})}
    spans = [(float(r), r + 0.1, "fedml.log", counted(r))
             for r in TRACED[:-1]]
    monkeypatch.setattr(PS, "analyse", lambda ctx: {"spans": spans})
    ctx = {"traced_rounds": TRACED,
           "records": [counted(r) for r in (4, 7, 8)]}
    assert _read("moe_compact_share_pct", ctx) == (
        want if want is None else pytest.approx(want))
    # the counters beside it are read as before
    assert _read("moe_held_share_pct", ctx) == pytest.approx(
        100 * 130 / 1024)


def test_off_the_chip_there_is_nothing_to_read():
    ctx = {"trace": None, "device": {"platform": "cpu"},
           "traced_rounds": TRACED, "records": []}
    assert _read("moe_compact_share_pct", ctx) is None
