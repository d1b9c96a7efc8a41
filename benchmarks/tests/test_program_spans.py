"""The program's own spans and scopes read back from a trace: the span
tree and its self times, the round program's busy time by scope, idle
gaps named by program span, and the ten per-layer readers — on a trace
recorded on four v5e chips from the tiny four-chip cell with the program
that writes them (PR 24), its scope map beside it."""

import gzip
import json
import os
import shutil

import pytest
from conftest import BENCH
from lib import program_spans as PS
from lib import xplane

import run

STEM = os.path.join(BENCH, "fixtures", "tiny-bn.mesh4.spans.v5e-4")
CELL = "tiny-bn.mesh4"
ROUNDS = 4  # two evaluation periods of the tiny cell (eval_every 2)
NEW = ["loop_self_ms", "dispatch_ms", "fetch_wait_ms", "eval_h2d_mb.mesh4",
       "idle_unnamed_ms", "local_gather_ms", "local_grad_ms",
       "local_update_ms", "server_update_ms.mesh4", "unscoped_pct"]
# the tiny test set: 100 samples of 16x16x3 float32, int32 labels
TINY_TEST_SET_BYTES = 100 * 16 * 16 * 3 * 4 + 100 * 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The fixture laid out as a run leaves it: ``<bench_dir>/.trace/
    <cell>/.../x.xplane.pb`` with ``scopes.json`` beside it. -> ctx"""
    bench_dir = tmp_path_factory.mktemp("bench")
    deep = bench_dir / ".trace" / CELL / "plugins" / "profile" / "t"
    deep.mkdir(parents=True)
    for src, dst in ((".xplane.pb.gz", "x.xplane.pb"),
                     (".scopes.json.gz", PS.SCOPES_FILE)):
        with gzip.open(STEM + src, "rb") as f, open(deep / dst, "wb") as g:
            shutil.copyfileobj(f, g)
    path = str(deep / "x.xplane.pb")
    return {
        "cell": {"bench_dir": str(bench_dir), "name": CELL},
        "device": {"platform": "tpu"}, "chips": 4,
        "traced_rounds": list(range(ROUNDS)),
        "trace": xplane.reduce_trace(path, chips=4, rounds=ROUNDS),
        "path": path,
    }


def test_span_tree_and_self_times():
    spans = [(0.0, 10.0, "fedml.round", {"round": 1}),
             (1.0, 3.0, "fedml.dispatch", {"round": 1}),
             (1.5, 2.5, "fedml.compile", {}),
             (3.0, 8.0, "fedml.fetch", {"round": 1}),
             (11.0, 12.0, "fedml.dispatch", {"round": 2})]
    first, orphan = PS.span_tree(spans)
    assert first["name"] == "fedml.round" and first["self_s"] == 3.0
    assert [c["name"] for c in first["children"]] == [
        "fedml.dispatch", "fedml.fetch"]
    dispatch = first["children"][0]
    assert dispatch["self_s"] == 1.0
    assert dispatch["children"][0]["name"] == "fedml.compile"
    # a span whose parent the capture cut stands on its own
    assert orphan["name"] == "fedml.dispatch" and orphan["self_s"] == 1.0


def test_host_spans_nest_per_round_with_their_attrs(traced):
    t = PS.analyse(traced)
    assert t["rounds"] == ROUNDS and t["scopes"]
    whole = [n for n in t["tree"] if n["name"] == "fedml.round"]
    # the profiler stops inside the last round's log(): that round's
    # own span is cut, its children are there
    assert len(whole) == ROUNDS - 1
    for node in whole:
        kids = [c["name"] for c in node["children"]]
        assert kids[:2] == ["fedml.dispatch", "fedml.fetch"]
        assert kids[-1] == "fedml.log"
        assert all(c["stats"]["round"] == node["stats"]["round"]
                   for c in node["children"])
        assert 0 <= node["self_s"] < node["end"] - node["start"]
    names = [sp[2] for sp in t["spans"]]
    assert names.count("fedml.dispatch") == ROUNDS
    assert names.count("fedml.fetch") == ROUNDS
    assert names.count("fedml.eval") == 2
    assert "fedml.compile" not in names  # nothing compiled in the window


def test_phase_split_conserves(traced):
    t = PS.analyse(traced)
    busy = t["round_program_busy_s"]
    # the scopes partition the round program's busy time, chip by chip,
    # and it is the time device_round_ms reads
    assert sum(t["scope_busy_s"].values()) == pytest.approx(busy, rel=1e-9)
    for by_scope, total in t["per_chip"]:
        assert sum(by_scope.values()) == pytest.approx(total, rel=1e-9)
    chip0 = t["per_chip"][0][1]
    assert chip0 == pytest.approx(
        traced["trace"]["round_program_busy_s"], rel=0.01)
    scoped = {k for k in t["scope_busy_s"] if k != PS.UNSCOPED}
    assert {"fedml.local.grad", "fedml.local.update",
            "fedml.server_update"} <= scoped
    assert all(k.startswith("fedml.") for k in scoped)
    # the gradient leads; what no scope names is a small part
    assert max(t["scope_busy_s"], key=t["scope_busy_s"].get) == (
        "fedml.local.grad")
    assert t["scope_busy_s"].get(PS.UNSCOPED, 0.0) < 0.10 * busy
    # chip 0's op families by scope partition its round-program time too
    assert sum(t["family_scope_s"].values()) == pytest.approx(
        chip0, rel=1e-9)
    # the collective sits in the server step
    reduce_rows = {scope for (family, scope) in t["family_scope_s"]
                   if family == "all-reduce"}
    assert reduce_rows == {"fedml.server_update"}


def test_idle_gaps_are_named_by_the_innermost_program_span(traced):
    t = PS.analyse(traced)
    lo, hi = t["window"]
    idle = dict((row[0], row) for row in t["idle"])
    assert sum(row[1] for row in t["idle"]) == pytest.approx(
        (hi - lo) - traced["trace"]["per_chip_busy_s"][0], rel=1e-6)
    # never the enclosing round where a child span holds the gap
    assert "fedml.eval" in idle and "fedml.fetch" in idle
    assert idle["fedml.eval"][4]["h2d_bytes"] == TINY_TEST_SET_BYTES
    # (a gap is named by its midpoint; what lies in NO child span is cut
    # exactly, so a gap across a span's edge leaves a little)
    total = sum(row[1] for row in t["idle"])
    assert 0 <= t["idle_unnamed_s"] < 0.02 * total
    assert idle.get("fedml.round", [0, 0.0])[1] < 0.01 * total


def test_every_new_reader_reads_a_number_on_the_chip_and_none_off_it(
        traced):
    off = dict(traced, device={"platform": "cpu"}, trace=None)
    values = {}
    for name in NEW:
        reader = run._load_py(run.reader_path(BENCH, name), "bench_metric")
        values[name] = reader.read(traced)
        assert isinstance(values[name], float), name
        assert values[name] >= 0
        assert reader.read(off) is None, name
    assert values["eval_h2d_mb.mesh4"] == TINY_TEST_SET_BYTES / 1e6
    # the phases and what no scope names add up to device_round_ms
    t = PS.analyse(traced)
    per_round = 1e3 * sum(t["scope_busy_s"].values()) / ROUNDS
    device_round_ms = run._load_py(
        run.reader_path(BENCH, "device_round_ms"), "m").read(traced)
    assert per_round == pytest.approx(device_round_ms, rel=0.01)
    named = sum(values[k] for k in (
        "local_gather_ms", "local_grad_ms", "local_update_ms",
        "server_update_ms.mesh4"))
    rest = 1e3 * sum(t["scope_busy_s"].get(k, 0.0) for k in (
        "fedml.sample", "fedml.local", "fedml.defense_agg",
        PS.UNSCOPED)) / ROUNDS
    assert named + rest == pytest.approx(per_round, rel=1e-9)
    assert values["unscoped_pct"] == pytest.approx(
        100 * t["scope_busy_s"].get(PS.UNSCOPED, 0.0)
        / t["round_program_busy_s"])
    assert values["fetch_wait_ms"] > 0 and values["dispatch_ms"] > 0
    assert values["loop_self_ms"] < 0.5 and values["idle_unnamed_ms"] < 0.5


def test_a_program_without_spans_or_scopes_gives_nothing_to_read(
        tmp_path):
    """The parent of the PR that added them: its trace (PR 23's
    fixture) holds no ``fedml.*`` span and its process no scope map."""
    deep = tmp_path / ".trace" / CELL
    deep.mkdir(parents=True)
    old = os.path.join(BENCH, "fixtures", "tiny-bn.mesh4.v5e-4.xplane.pb.gz")
    with gzip.open(old, "rb") as f, open(deep / "x.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    ctx = {"cell": {"bench_dir": str(tmp_path), "name": CELL},
           "device": {"platform": "tpu"}, "chips": 4,
           "traced_rounds": list(range(ROUNDS)), "trace": {"rounds": ROUNDS}}
    for name in NEW:
        reader = run._load_py(run.reader_path(BENCH, name), "bench_metric")
        assert reader.read(ctx) is None, name
    # ... and with no trace at all
    ctx["cell"]["name"] = "no-such-cell"
    assert PS.metric(ctx, "dispatch_ms") is None


def test_the_tables_print(traced, capsys):
    PS.print_tables(traced["path"])
    out = capsys.readouterr().out
    for needle in ("1. fedml.* host spans", "fedml.round self time",
                   "2. round-program busy time by scope",
                   "fedml.local.grad", "op families by scope",
                   "3. chip 0 idle gaps", "h2d_bytes="):
        assert needle in out, needle


def test_benchmark_json_holds_the_ten_new_metrics_with_readers():
    doc = json.load(open(os.path.join(os.path.dirname(BENCH),
                                      "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    assert set(NEW) <= set(per_layer)
    # appended, after what was there
    assert [m["name"] for m in doc["per_layer"]][-len(NEW):] == NEW
    layers = {m["layer"] for m in doc["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = per_layer[name]
        assert m["moves"] == "rounds_per_s" and m["layer"] in layers
        assert os.path.exists(run.reader_path(BENCH, name))
        assert m["source"] == ("program_counter" if name.startswith(
            "eval_h2d_mb") else "device_trace")


def _wire(buf):
    """Fields of one protobuf message: ``(number, wire type, value)``."""
    i = 0

    def varint():
        nonlocal i
        value, shift = 0, 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if not byte & 0x80:
                return value

    while i < len(buf):
        key = varint()
        number, kind = key >> 3, key & 7
        if kind == 0:
            value = varint()
        else:
            size = varint() if kind == 2 else (8 if kind == 1 else 4)
            value = buf[i:i + size]
            i += size
        yield number, kind, value


def test_the_join_agrees_with_the_traces_own_op_names(traced):
    """The trace does hold each op's ``op_name`` — as the ``tf_op`` stat
    of its event METADATA, which ``ProfileData`` does not show. Read
    with a wire decoder, it gives the same scope as the join by
    instruction name against the program's scope map."""
    import re

    with open(os.path.join(os.path.dirname(traced["path"]),
                           PS.SCOPES_FILE)) as f:
        smap = json.load(f)["jit__sharded_round"]
    last_scope = re.compile(r"fedml\.[a-z_]+(?:\.[a-z_]+)*")
    with open(traced["path"], "rb") as f:
        space = f.read()
    same = differ = 0
    for number, _, plane in _wire(space):
        fields = list(_wire(plane)) if number == 1 else []
        if not any(n == 2 and v == b"/device:TPU:0" for n, _, v in fields):
            continue
        stat_names = {}
        for n, _, entry in fields:
            if n == 5:  # stat_metadata: id -> XStatMetadata{name=2}
                kv = dict((a, c) for a, _, c in _wire(entry))
                stat_names[kv[1]] = dict(
                    (a, c) for a, _, c in _wire(kv[2])).get(2, b"").decode()
        for n, _, entry in fields:
            if n != 4:  # event_metadata: id -> XEventMetadata
                continue
            meta = list(_wire(dict(
                (a, c) for a, _, c in _wire(entry))[2]))
            name = next(c for a, _, c in meta if a == 2).decode()
            op_name = None
            for a, _, stat in meta:
                if a == 5:
                    st = dict((x, y) for x, _, y in _wire(stat))
                    if stat_names.get(st.get(1)) == "tf_op":
                        op_name = st[5].decode()
            if (not name.startswith("%") or op_name is None
                    or "_sharded_round" not in op_name
                    or xplane.is_wrapper(name)):
                continue
            found = last_scope.findall(op_name)
            if smap.get(xplane.hlo_name(name)) == (
                    found[-1] if found else None):
                same += 1
            else:
                differ += 1
    assert same > 500 and differ <= 0.01 * same, (same, differ)
