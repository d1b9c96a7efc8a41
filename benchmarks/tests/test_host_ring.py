"""The host ring read back (``lib/host_ring.py``) and the thirteen
per-layer readers over it: on made-up ring records with known medians
and a hand-made ``ctx`` — no chip, no sim — and, for the four
``idle_<span>_ms`` readers, on PR 24's recorded four-chip trace."""

import json
import os

import pytest
from conftest import BENCH
from lib import host_ring as HR
from lib import program_spans as PS
# PR 24's four-chip trace laid out as a run leaves it -> ctx
from test_program_spans import traced  # noqa: F401

import run

RING_READERS = ["build_s", "round_compile_s", "eval_first_s",
                "setup_unnamed_s", "round_untraced_ms",
                "dispatch_untraced_ms", "fetch_untraced_ms",
                "between_untraced_ms", "eval_untraced_ms"]
IDLE_READERS = ["idle_fetch_ms", "idle_eval_ms", "idle_dispatch_ms",
                "idle_log_ms"]
T_START, SETUP_S = 1000.0, 60.0
TRACED = [10, 11, 12, 13, 14]
ROUNDS = 30


def _span(name, t0, seconds, **attrs):
    return {"kind": "span", "ts": 1.7e9 + t0, "t0": t0, "seconds": seconds,
            "rank": None, "tid": 1, "name": name, "parent": None, **attrs}


def made_up_events():
    """Set-up: a ShardedFedAvg build of 10 s holding FedAvgSim's 6 s,
    one compile of 20 s (lower 12, backend 7) with its first call of
    2 s, the warm-up's evaluation of 3 s: 35 s named of 60.
    The window: 30 rounds every 100 ms. A round's span is 90 ms
    (dispatch 4, fetch 80, log 1) and the gap to the next 10 ms; every
    fifth round evaluates (12 ms, the span 102 ms); the traced rounds
    10-14 read dispatch 7 / fetch 70 / eval 40, their neighbours 9 and
    15 hold half a second of ``log`` each."""
    lo = T_START - SETUP_S
    evs = [
        _span("fedml.build", lo + 5.0, 6.0, sim="FedAvgSim",
              parent="fedml.build"),
        _span("fedml.build", lo + 4.0, 10.0, sim="ShardedFedAvg"),
        _span("fedml.compile.lower", lo + 20.0, 12.0,
              parent="fedml.compile"),
        _span("fedml.compile.backend", lo + 32.0, 7.0,
              parent="fedml.compile"),
        _span("fedml.compile", lo + 20.0, 20.0, family="sharded_round",
              key="20"),
        _span("fedml.first_call", lo + 40.0, 2.0, family="sharded_round",
              key="20"),
        _span("fedml.eval", lo + 50.0, 3.0, h2d_bytes=0),
        {"kind": "event", "ts": 1.7e9, "t0": lo + 1.0, "seconds": 0.0,
         "rank": None, "tid": 1, "name": "msg_send"},
    ]
    t = T_START + 0.001
    for r in range(ROUNDS):
        traced, evaluates = r in TRACED, (r + 1) % 5 == 0
        dispatch, fetch = (0.007, 0.070) if traced else (0.004, 0.080)
        log = 0.5 if r in (TRACED[0] - 1, TRACED[-1] + 1) else 0.001
        ev = (0.040 if traced else 0.012) if evaluates else 0.0
        s = t
        evs.append(_span("fedml.dispatch", s, dispatch, round=r,
                         parent="fedml.round"))
        s += dispatch
        evs.append(_span("fedml.fetch", s, fetch, round=r,
                         parent="fedml.round"))
        s += fetch + 0.003
        if evaluates:
            evs.append(_span("fedml.eval", s, ev, round=r,
                             parent="fedml.round"))
            s += ev
        last = r == ROUNDS - 1  # the window closes inside its log()
        closed = {"error": "_WindowClosed()"} if last else {}
        evs.append(_span("fedml.log", s, log, round=r,
                         parent="fedml.round", **closed))
        s += log + 0.002
        evs.append(_span("fedml.round", t, s - t, round=r, **closed))
        t = s + 0.010
    return evs


class FakeRing:
    def __init__(self, events, dropped=0, complete_from=None):
        self.events, self.dropped = events, dropped
        self.complete_from = complete_from
        self.dumps = []

    def dump(self, path, **extra):
        self.dumps.append(path)
        with open(path, "w") as f:
            json.dump({**extra, "rank": None, "dropped": self.dropped,
                       "complete_from": self.complete_from,
                       "events": self.events}, f)


@pytest.fixture
def ctx(tmp_path):
    (tmp_path / ".trace" / "cell").mkdir(parents=True)
    return {"cell": {"bench_dir": str(tmp_path), "name": "cell"},
            "device": {"platform": "cpu"}, "chips": 1, "trace": None,
            "t_start": T_START, "setup_s": SETUP_S,
            "traced_rounds": list(TRACED)}


def _read(name, ctx):
    return run._load_py(run.reader_path(BENCH, name), "bench_metric").read(
        ctx)


def test_nine_ring_readers_on_a_made_up_run(ctx, monkeypatch):
    ring = FakeRing(made_up_events())
    monkeypatch.setattr(HR, "program_ring", lambda: ring)
    got = {name: _read(name, ctx) for name in RING_READERS}
    assert got["build_s"] == pytest.approx(10.0)  # the nested one once
    assert got["round_compile_s"] == pytest.approx(20.0)
    assert got["eval_first_s"] == pytest.approx(3.0)
    # named union 10 + 20 + 2 + 3; the rest of set-up is in no span
    assert got["setup_unnamed_s"] == pytest.approx(SETUP_S - 35.0)
    assert got["round_untraced_ms"] == pytest.approx(90.0)
    assert got["dispatch_untraced_ms"] == pytest.approx(4.0)
    assert got["fetch_untraced_ms"] == pytest.approx(80.0)
    assert got["between_untraced_ms"] == pytest.approx(10.0)
    assert got["eval_untraced_ms"] == pytest.approx(12.0)
    # the ring was left beside where the trace would lie, once
    assert len(ring.dumps) == 1
    dump = json.load(open(ring.dumps[0]))
    assert (dump["t_start"], dump["setup_s"], dump["traced_rounds"]) == (
        T_START, SETUP_S, TRACED)


def test_untraced_rounds_leave_out_the_traced_part_and_its_neighbours():
    r = HR.make_run(made_up_events(), T_START, SETUP_S, TRACED)
    kept = [i for i in range(ROUNDS) if HR.untouched(r, i)]
    assert kept == list(range(9)) + list(range(16, ROUNDS))
    took = HR.loop_samples(r, lambda i: HR.untouched(r, i))
    # 23 untouched rounds; the last raised out of its log(); 4, 19 and
    # 24 evaluate (29 is the one that raised)
    assert len(took[HR.ROUND]) == len(took[HR.FETCH]) == 23 - 1 - 3
    assert len(took[HR.EVAL]) == 3
    assert max(took[HR.LOG]) == pytest.approx(0.001)  # not the 0.5 s
    # a gap needs both its rounds: 8 -> 9 and 28 -> 29 are out, so are
    # the gaps after the evaluating rounds 4, 19 and 24
    assert len(took[HR.BETWEEN]) == (8 - 1) + (12 - 2)
    # the traced part itself, as the CLI prints it beside the others
    part = HR.loop_samples(r, lambda i: i in TRACED)
    assert sorted(part[HR.DISPATCH]) == pytest.approx(4 * [0.007])
    assert part[HR.EVAL] == pytest.approx([0.040])
    # with nothing traced every round counts
    whole = HR.make_run(made_up_events(), T_START, SETUP_S)
    assert all(HR.untouched(whole, i) for i in range(ROUNDS))


def test_setup_named_and_unnamed_add_up_to_setup_s():
    n = HR.setup_numbers(HR.make_run(made_up_events(), T_START, SETUP_S))
    assert n["setup_named_s"] + n["setup_unnamed_s"] == pytest.approx(
        SETUP_S)
    assert n["setup_named_s"] == pytest.approx(35.0)
    # a window that opened mid-span cuts the span at its edge
    late = HR.setup_numbers(HR.make_run(
        made_up_events(), T_START - SETUP_S + 30.0, 30.0))
    assert late["round_compile_s"] == pytest.approx(10.0)
    assert late["setup_unnamed_s"] == pytest.approx(30.0 - (10.0 + 10.0))


def test_a_ring_that_dropped_what_a_reader_needs_gives_none(
        ctx, monkeypatch):
    events = made_up_events()
    # whole only from round 3 on: neither set-up nor the window is
    ring = FakeRing(events, dropped=40, complete_from=T_START + 0.3)
    monkeypatch.setattr(HR, "program_ring", lambda: ring)
    for name in RING_READERS:
        assert _read(name, ctx) is None, name
    # whole from inside set-up on: the window's readers read, set-up's
    # do not
    ring = FakeRing(events, dropped=3, complete_from=T_START - 30.0)
    monkeypatch.setattr(HR, "program_ring", lambda: ring)
    assert _read("fetch_untraced_ms", ctx) == pytest.approx(80.0)
    assert _read("setup_unnamed_s", ctx) is None
    assert _read("build_s", ctx) is None


def test_a_program_without_the_ring_gives_nothing_to_read(
        ctx, traced, monkeypatch):
    """The parent of the PR that made the ring unconditional: none of
    the thirteen, on the chip's trace or off it."""
    monkeypatch.setattr(HR, "program_ring", lambda: None)
    for name in RING_READERS + IDLE_READERS:
        assert _read(name, ctx) is None, name
        assert _read(name, traced) is None, name
    # ... and a ring that holds no span of the program's gives none
    monkeypatch.setattr(HR, "program_ring", lambda: FakeRing([]))
    for name in RING_READERS:
        assert _read(name, ctx) is None, name


def test_this_programs_ring_is_found():
    from fedml_tpu.core import tracing

    assert HR.program_ring() is tracing.RING


def test_idle_readers_partition_chip_0s_idle_time(traced, monkeypatch):
    monkeypatch.setattr(HR, "program_ring", lambda: FakeRing([]))
    idle = {name: _read(name, traced) for name in IDLE_READERS}
    assert all(isinstance(v, float) and v >= 0 for v in idle.values())
    assert idle["idle_fetch_ms"] > 0 and idle["idle_eval_ms"] > 0
    unnamed = _read("idle_unnamed_ms", traced)
    t = PS.analyse(traced)
    lo, hi = t["window"]
    chip0_idle_ms = 1e3 * ((hi - lo) - traced["trace"]["per_chip_busy_s"][0])
    # both the four and the unnamed rest are cut at the spans' edges:
    # a partition, where the printed table (whole gaps by midpoint)
    # beside the exact rest is not
    assert sum(idle.values()) + unnamed == pytest.approx(
        chip0_idle_ms / 4, rel=1e-9)
    assert sum(idle.values()) + unnamed == pytest.approx(
        1e3 * sum(row[1] for row in t["idle"]) / 4, rel=1e-9)
    # on this tiny cell one gap of 5.07 ms, "fedml.fetch" by its
    # midpoint, lies half inside the dispatch before it
    printed = {row[0]: 1e3 * row[1] / 4 for row in t["idle"]}
    assert idle["idle_fetch_ms"] < printed["fedml.fetch"]
    assert idle["idle_dispatch_ms"] > printed["fedml.dispatch"]
    assert _read("host_gap_ms", traced) == pytest.approx(chip0_idle_ms / 4)


def test_clock_offset_recovers_a_planted_offset(traced, monkeypatch):
    """The capture's ``fedml.dispatch`` spans, moved to a host clock
    that stands 12,345.678 s behind with up to 40 us of jitter."""
    import random

    planted, rng = 12345.678, random.Random(0)
    capture = [sp for sp in PS.analyse(traced)["spans"]
               if sp[2] == HR.DISPATCH]
    assert len(capture) == 4
    events = [_span(HR.DISPATCH, s - planted + rng.uniform(-4e-5, 4e-5),
                    e - s, round=st["round"]) for s, e, _, st in capture]
    events.append(_span(HR.DISPATCH, capture[-1][1] - planted + 1.0, 0.004,
                        round=99))  # a round the capture does not hold
    ring = FakeRing(events)
    monkeypatch.setattr(HR, "program_ring", lambda: ring)
    traced = dict(traced, t_start=capture[0][0] - planted - 1.0,
                  setup_s=0.0)
    off = HR.clock_offset(traced)
    assert off["matched"] == 4
    assert off["offset_s"] == pytest.approx(planted, abs=4e-5)
    assert 0 < off["residual_s"] < 8e-5
    # nothing in both places: no offset
    monkeypatch.setattr(HR, "program_ring", lambda: FakeRing(
        [_span(HR.DISPATCH, 5.0, 0.004, round=99)]))
    assert HR.clock_offset(traced) is None


def test_the_tables_print_from_a_dump(tmp_path, traced, capsys):
    path = tmp_path / HR.RING_FILE
    FakeRing(made_up_events()).dump(
        str(path), t_start=T_START, setup_s=SETUP_S, traced_rounds=TRACED)
    HR.print_tables(str(path))
    out = capsys.readouterr().out
    for needle in ("1. set-up", "sim=ShardedFedAvg", "family=sharded_round",
                   "setup_unnamed_s", "sharded_round 20: compile 20.000",
                   "compile.lower 12.000", "first_call 2.000",
                   "2. the round loop", "rounds 10-14", "others",
                   "between rounds", "no .xplane.pb beside the dump"):
        assert needle in out, needle
    # a dump an operator made says nothing of the window: it is cut at
    # the first fedml.round
    bare = tmp_path / "bare.json"
    FakeRing(made_up_events()).dump(str(bare))
    r = HR.from_dump(str(bare))
    assert r["t_start"] == pytest.approx(T_START + 0.001)
    assert r["traced"] == []
    assert HR.setup_numbers(r)["build_s"] == pytest.approx(10.0)
    # beside a capture the two clocks are compared
    events = [_span(HR.DISPATCH, s - 7.0, e - s, round=st["round"])
              for s, e, n, st in PS.analyse(traced)["spans"]
              if n == HR.DISPATCH]
    beside = os.path.join(os.path.dirname(traced["path"]), HR.RING_FILE)
    FakeRing(events).dump(beside, traced_rounds=[0, 1, 2, 3],
                          t_start=events[0]["t0"] - 1.0, setup_s=0.0)
    HR.print_tables(beside)
    os.remove(beside)
    assert "capture clock - host clock: 7.000000000 s over 4" in (
        capsys.readouterr().out)


def test_benchmark_json_holds_the_thirteen_with_readers():
    doc = json.load(open(os.path.join(os.path.dirname(BENCH),
                                      "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    ends = {m["name"] for m in doc["end_to_end"]}
    for name in RING_READERS + IDLE_READERS:
        m = per_layer[name]
        assert "workloads" not in m, name  # every cell reports it
        assert m["moves"] in ends and m["better"] == "lower"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py")), name
    assert {per_layer[n]["layer"] for n in RING_READERS[:4]} == {
        "entry and set-up"}
    assert {per_layer[n]["moves"] for n in RING_READERS[:4]} == {"setup_s"}
    assert {per_layer[n]["layer"] for n in RING_READERS[4:]} == {
        "round loop"}
    assert {per_layer[n]["layer"] for n in IDLE_READERS} == {"device"}
    assert {per_layer[n]["source"] for n in IDLE_READERS} == {
        "device_trace"}
