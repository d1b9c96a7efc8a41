"""``lockstep_occupancy_pct``: live client steps over the ``slot_steps``
the program's ``fedml.log`` spans carry, in the span format
``program_spans.program_host_spans`` reads off a trace —
``(start_s, end_s, name, stats)``."""

import gzip
import json
import os
import shutil

import pytest
from conftest import BENCH
from lib import program_spans as PS

import run

NAME = "lockstep_occupancy_pct"
TRACED = [5, 6, 7, 8]


def _reader():
    return run._load_py(run.reader_path(BENCH, NAME), "bench_metric")


def _spans(slot_steps):
    """Rounds as the program writes them; ``slot_steps``: round -> count
    on that round's ``fedml.log`` span."""
    out = []
    for i, r in enumerate(TRACED):
        t = 10.0 * i
        out.append((t, t + 9.0, "fedml.round", {"round": r}))
        out.append((t + 0.1, t + 1.0, "fedml.dispatch", {"round": r}))
        out.append((t + 1.0, t + 8.0, "fedml.fetch", {"round": r}))
        if r in slot_steps:
            out.append((t + 8.0, t + 8.1, "fedml.log",
                        {"round": r, "slot_steps": slot_steps[r]}))
    return out


def _ctx(monkeypatch, spans, records=(), client_steps=150):
    monkeypatch.setattr(
        PS, "analyse", lambda ctx: {"spans": spans} if spans else None)
    return {"traced_rounds": TRACED, "client_steps": client_steps,
            "records": list(records)}


def test_occupancy_is_client_steps_over_the_spans_slot_steps(monkeypatch):
    slots = {5: 40, 6: 60, 7: 50, 8: 50}
    ctx = _ctx(monkeypatch, _spans(slots))
    assert _reader().read(ctx) == pytest.approx(100.0 * 150 / 200)
    # spans of rounds outside the traced part are not counted
    extra = _spans(slots) + [(99.0, 99.1, "fedml.log",
                              {"round": 9, "slot_steps": 1000})]
    assert _reader().read(_ctx(monkeypatch, extra)) == pytest.approx(75.0)


def test_the_round_whose_log_span_the_profiler_cut_is_read_off_its_record(
        monkeypatch):
    """The harness stops the profiler inside the last traced round's
    ``log()``, so that round's ``fedml.log`` span is never in the trace
    (``test_program_spans``: ROUNDS - 1 whole rounds); the sink's record
    of the round holds the same count."""
    spans = _spans({5: 40, 6: 60, 7: 50})
    records = [{"round": r, "train_loss": 1.0, "slot_steps": float(n)}
               for r, n in ((4, 999), (5, 41), (6, 60), (7, 50), (8, 50))]
    ctx = _ctx(monkeypatch, spans, records)
    # round 5's span wins over its record; round 8 comes from the record
    assert _reader().read(ctx) == pytest.approx(100.0 * 150 / 200)
    # a traced round counted nowhere: no number, not a wrong one
    assert _reader().read(_ctx(monkeypatch, spans, records[:-1])) is None


def test_a_program_without_the_counter_gives_nothing_to_read(
        monkeypatch, tmp_path):
    """The parent: its ``fedml.log`` spans carry no ``slot_steps`` and
    its records none — on PR 24's recorded four-chip trace, unpatched."""
    cell = "tiny-bn.mesh4"
    deep = tmp_path / ".trace" / cell
    deep.mkdir(parents=True)
    stem = os.path.join(BENCH, "fixtures", "tiny-bn.mesh4.spans.v5e-4")
    for src, dst in ((".xplane.pb.gz", "x.xplane.pb"),
                     (".scopes.json.gz", PS.SCOPES_FILE)):
        with gzip.open(stem + src, "rb") as f, open(deep / dst, "wb") as g:
            shutil.copyfileobj(f, g)
    ctx = {"cell": {"bench_dir": str(tmp_path), "name": cell},
           "device": {"platform": "tpu"}, "chips": 4, "trace": {},
           "traced_rounds": [0, 1, 2, 3], "client_steps": 40,
           "records": [{"round": r, "train_loss": 1.0} for r in range(4)]}
    assert any(sp[2] == "fedml.log" for sp in PS.analyse(ctx)["spans"])
    assert _reader().read(ctx) is None
    # off the chip, or with no trace: nothing either
    assert _reader().read(dict(ctx, trace=None)) is None
    no_spans = _ctx(monkeypatch, [])
    assert _reader().read(no_spans) is None


def test_benchmark_json_names_the_metric_after_what_was_there():
    doc = json.load(open(os.path.join(os.path.dirname(BENCH),
                                      "BENCHMARK.json")))
    entry = doc["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "round program",
        "moves": "rounds_per_s",
        "workloads": ["resnet56-mesh4-c80of1000"]}
    assert os.path.exists(run.reader_path(BENCH, NAME))
