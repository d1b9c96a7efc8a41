"""Tracer + transformer-as-FedModel tests."""

import pytest
import jax
import jax.numpy as jnp

from fedml_tpu.core.tracing import Tracer


def test_tracer_comm_and_rounds(tmp_path):
    """The collector's three kinds: a round (``log_round_start`` /
    ``_end``), an instant event (what the message sites write) and a
    span; every record stands on both clocks."""
    tr = Tracer()
    tr.log_round_start(0)
    tr.event("msg_send", sender=0, receiver=1, tag="sync")
    tr.log_round_end(0)
    tr.log_round_end(5)  # never started: no record
    with tr.span("aggregate", round=0):
        pass
    s = tr.summary()
    assert s["msg_send"]["count"] == 1
    assert s["round"]["count"] == 1
    assert s["aggregate"]["count"] == 1
    kinds = [e["kind"] for e in tr.events]
    assert kinds == ["event", "round", "span"]
    assert all(e["ts"] > 0 and "t0" in e and e["seconds"] >= 0
               for e in tr.events)
    send, rnd, _ = tr.events
    assert rnd["t0"] <= send["t0"] <= rnd["t0"] + rnd["seconds"]
    assert not hasattr(tr, "log_communication_tick")
    tr.dump(str(tmp_path / "trace.json"), note="x")
    import json

    dump = json.loads((tmp_path / "trace.json").read_text())
    assert dump["note"] == "x" and dump["dropped"] == 0
    assert dump["complete_from"] is None and len(dump["events"]) == 3


@pytest.mark.slow
def test_transformer_fedmodel_in_fedavg():
    """The transformer works as a federated NWP model end-to-end."""
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )
    from fedml_tpu.data.loaders import make_fake_text_dataset
    from fedml_tpu.models import create_model

    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_shakespeare", num_clients=4,
                        batch_size=8, seed=0),
        model=ModelConfig(
            name="transformer_lm", num_classes=90, input_shape=(80,),
            extra=(("vocab_size", 90), ("num_layers", 1),
                   ("num_heads", 2), ("embed_dim", 32), ("max_len", 80)),
        ),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=1, clients_per_round=2),
        seed=0,
    )
    data = make_fake_text_dataset(cfg.data, n_train=64, n_test=16)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    state, m = sim.run_round(state)
    assert jnp.isfinite(m["train_loss"])


# ---------------------------------------------------------------------------
# the one span primitive (core/tracing.span) and the fedml.* scopes
# (docs/OBSERVABILITY.md "Spans and scopes")
# ---------------------------------------------------------------------------

import glob  # noqa: E402
import weakref  # noqa: E402

import numpy as np  # noqa: E402

from fedml_tpu.algorithms.fedavg import FedAvgSim  # noqa: E402
from fedml_tpu.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core import anatomy, memscope, telemetry, tracing  # noqa: E402
from fedml_tpu.core.anatomy import ANATOMY  # noqa: E402
from fedml_tpu.core.tracing import RING, span  # noqa: E402
from fedml_tpu.data.loaders import load_dataset  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.parallel import ShardedFedAvg, make_mesh  # noqa: E402

SCOPES = ("fedml.sample", "fedml.local.gather", "fedml.local.grad",
          "fedml.local.update", "fedml.server_update")


def _cfg(kind="lr", rounds=3, clients=8, cohort=4, **fed_kw):
    fed_kw.setdefault("eval_every", 2)
    if kind == "lr":  # the vmapped local update
        data = DataConfig(dataset="fake_mnist", num_clients=clients,
                          batch_size=32, seed=0)
        model = ModelConfig(name="lr", num_classes=10,
                            input_shape=(28, 28, 1))
    else:  # a cohort-grouped net (build_cohort_local_update)
        data = DataConfig(dataset="fake_cifar10", num_clients=clients,
                          batch_size=16, seed=0, dataset_r=0.1)
        model = ModelConfig(
            name="cnn_custom", num_classes=10, input_shape=(32, 32, 3),
            extra=(("convs", (8,)), ("denses", (16,))))
    return ExperimentConfig(
        data=data, model=model, train=TrainConfig(lr=0.05, epochs=1),
        fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                      **fed_kw),
        seed=0,
    )


def _sim(kind="lr", sharded=False, **kw):
    if sharded:
        kw.setdefault("clients", 16)
        kw.setdefault("cohort", 8)
    cfg = _cfg(kind, **kw)
    model, data = create_model(cfg.model), load_dataset(cfg.data)
    if not sharded:
        return FedAvgSim(model, data, cfg)
    return ShardedFedAvg(model, data, cfg, make_mesh(
        client_axis=4, data_axis=1, devices=jax.devices()[:4]))


def _captured_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``fedml.*`` host
    events of a finished ``jax.profiler`` capture, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fedml."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


def _run_captured(sim, trace_dir, sink=None):
    from fedml_tpu.metrics import MetricsSink

    jax.profiler.start_trace(str(trace_dir))
    try:
        return sim.run(metrics_sink=sink or MetricsSink())
    finally:
        jax.profiler.stop_trace()


@pytest.fixture
def ring():
    """The process ring, emptied: earlier tests' sims filled it."""
    RING.clear()
    yield RING
    RING.clear()


def _ring_spans(ring, name=None):
    return [e for e in ring.events if e["kind"] == "span"
            and (name is None or e["name"] == name)]


def test_span_lands_in_the_ring_with_no_configure(ring):
    """No profiler session, no ``telemetry.configure``, no anatomy: the
    span is in the process ring all the same, on the host's clock, and
    the body's value and exception pass."""
    import time

    assert telemetry.TRACER is None and not ANATOMY.enabled
    before = time.perf_counter()

    def body():
        with span("fedml.x", round=3, phase="eval") as sp:
            assert tracing._open_spans()[-1] == ("fedml.x", 3)
            with span("fedml.y"):
                pass
            return sp, 41 + 1

    sp, value = body()
    after = time.perf_counter()
    assert value == 42
    with pytest.raises(KeyError):
        with span("fedml.z"):
            raise KeyError("boom")
    assert tracing._open_spans() == []
    assert ANATOMY.tracez()["entries"] == []
    y, x, z = _ring_spans(ring)
    assert (y["name"], y["parent"], y["round"]) == ("fedml.y", "fedml.x", 3)
    assert (x["name"], x["parent"], x["round"]) == ("fedml.x", None, 3)
    assert x["seconds"] == sp.seconds
    assert "KeyError" in z["error"] and "error" not in x
    # t0 is perf_counter() at the start: the child lies inside its parent
    assert before <= x["t0"] <= y["t0"]
    assert y["t0"] + y["seconds"] <= x["t0"] + x["seconds"] <= after
    assert x["ts"] > 1e9 and x["rank"] is None


def test_configure_hands_out_the_same_ring(ring, tmp_path):
    """``telemetry.configure(trace=True)`` creates no second collector:
    ``TRACER`` IS the process ring with the rank set, spans opened
    before it are still there, and the message-level sites (which guard
    on ``TRACER``) wrote nothing before it."""
    from fedml_tpu.core.message import Message
    from fedml_tpu.core.transport.loopback import LoopbackHub

    def deliver(i):  # transport/base.py:deliver, one of the sites
        msg = Message(100, 0, 1, {"i": i})
        msg.trace = ("trace", f"span{i}")
        receiver.deliver(msg)

    receiver = LoopbackHub().create(1)
    with span("fedml.build", sim="early"):
        deliver(0)
    assert [e["name"] for e in ring.events] == ["fedml.build"]
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=4)
    try:
        assert telemetry.TRACER is ring is tracing.RING
        assert ring.rank == 4
        deliver(1)
        with span("fedml.round", round=0):
            pass
        assert [(e["kind"], e["name"]) for e in ring.events] == [
            ("span", "fedml.build"), ("event", "msg_deliver"),
            ("span", "fedml.round")]
        assert ring.events[1]["span_id"] == "span1"
        assert ring.events[-1]["rank"] == 4
    finally:
        telemetry.shutdown()
    # back to the unconfigured state: silent sites, an empty ring
    assert telemetry.TRACER is None
    assert len(ring.events) == 0 and ring.rank is None


def test_ring_is_bounded_and_says_what_it_dropped():
    tr = Tracer(max_events=4)
    for i in range(4):
        with tr.span("s", i=i):
            pass
    assert (tr.dropped, tr.complete_from) == (0, None)
    ends = [e["t0"] + e["seconds"] for e in tr.events]
    for i in range(4, 7):
        with tr.span("s", i=i):
            pass
    assert len(tr.events) == 4 and tr.dropped == 3
    assert [e["i"] for e in tr.events] == [3, 4, 5, 6]
    # whole from the end of the newest evicted record on: every span
    # that began at or after it is held
    assert tr.complete_from == ends[2]
    assert all(e["t0"] >= tr.complete_from for e in tr.events)
    assert tracing.RING.events.maxlen == tracing.RING_CAPACITY
    tr.clear()
    assert (len(tr.events), tr.dropped, tr.complete_from) == (0, 0, None)


def test_span_ring_records_parent_round_and_error(ring, tmp_path):
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    try:
        with span("fedml.round", round=7):
            with span("fedml.dispatch"):
                pass
            with pytest.raises(ValueError):
                with span("fedml.eval", h2d_bytes=12):
                    raise ValueError("bad")
        evs = {e["name"]: e for e in telemetry.TRACER.events
               if e["kind"] == "span"}
        assert evs["fedml.round"]["parent"] is None
        assert evs["fedml.dispatch"]["parent"] == "fedml.round"
        assert evs["fedml.eval"]["parent"] == "fedml.round"
        # one identifier for the spans of one round, inherited
        assert {e["round"] for e in evs.values()} == {7}
        assert evs["fedml.eval"]["h2d_bytes"] == 12
        assert "ValueError" in evs["fedml.eval"]["error"]
        # self time is computable from the ring alone
        kids = sum(e["seconds"] for e in evs.values()
                   if e["parent"] == "fedml.round")
        assert 0 <= evs["fedml.round"]["seconds"] - kids
        # Tracer.span is the same primitive on an explicit ring
        own = Tracer()
        with own.span("a"):
            with own.span("b", round=1):
                pass
        assert [(e["name"], e["parent"]) for e in own.events] == [
            ("b", "a"), ("a", None)]
        assert not _ring_spans(ring, "a")
    finally:
        telemetry.shutdown()


def test_build_and_compile_leave_setup_spans(ring):
    """Constructing a simulator leaves one ``fedml.build``; a
    ``ProgramSite`` compile leaves ``fedml.compile`` > ``.lower``,
    ``.backend`` and one ``fedml.first_call``; a second call of the
    same key leaves none."""
    sim = _sim(rounds=1)
    (build,) = _ring_spans(ring, "fedml.build")
    assert build["sim"] == "FedAvgSim" and build["parent"] is None
    assert not _ring_spans(ring, "fedml.compile")
    state, _ = sim.run_round(sim.init())
    (comp,) = _ring_spans(ring, "fedml.compile")
    (low,) = _ring_spans(ring, "fedml.compile.lower")
    (back,) = _ring_spans(ring, "fedml.compile.backend")
    (first,) = _ring_spans(ring, "fedml.first_call")
    assert comp["family"] == first["family"] == "sim_round"
    assert comp["key"] == first["key"]
    assert low["parent"] == back["parent"] == "fedml.compile"
    assert comp["t0"] <= low["t0"] <= back["t0"]
    assert (back["t0"] + back["seconds"] <= comp["t0"] + comp["seconds"]
            <= first["t0"])
    assert low["seconds"] + back["seconds"] <= comp["seconds"]
    n = len(ring.events)
    sim.run_round(state)
    assert len(ring.events) == n  # no span outside a loop, none new


def test_sharded_build_nests_its_base(ring):
    _sim(sharded=True, rounds=1)
    inner, outer = _ring_spans(ring, "fedml.build")
    assert (outer["sim"], outer["parent"]) == ("ShardedFedAvg", None)
    assert (inner["sim"], inner["parent"]) == ("FedAvgSim", "fedml.build")
    assert outer["t0"] <= inner["t0"]
    assert (inner["t0"] + inner["seconds"]
            <= outer["t0"] + outer["seconds"])


def test_run_loop_leaves_three_rounds_in_order(ring):
    """``run_loop`` over three rounds with no profiler and no
    configure: three ``fedml.round``, each holding dispatch, fetch and
    log (and the evaluation where one is due) with ``t0``s in order."""
    from fedml_tpu.metrics import MetricsSink

    sim = _sim(rounds=3)
    sim.run(metrics_sink=MetricsSink())
    rounds = _ring_spans(ring, "fedml.round")
    assert [r["round"] for r in rounds] == [0, 1, 2]
    assert all(a["t0"] + a["seconds"] <= b["t0"]
               for a, b in zip(rounds, rounds[1:]))
    for r in rounds:
        kids = sorted((e for e in _ring_spans(ring)
                       if e["parent"] == "fedml.round"
                       and e["round"] == r["round"]),
                      key=lambda e: e["t0"])
        want = ["fedml.dispatch", "fedml.fetch", "fedml.log"]
        if r["round"] in (1, 2):  # eval_every=2, and the last round
            want.insert(2, "fedml.eval")
        assert [k["name"] for k in kids] == want
        assert r["t0"] <= kids[0]["t0"]
        assert all(a["t0"] + a["seconds"] <= b["t0"]
                   for a, b in zip(kids, kids[1:]))
        assert (kids[-1]["t0"] + kids[-1]["seconds"]
                <= r["t0"] + r["seconds"])
    # the first dispatch compiled, under its own spans
    (comp,) = _ring_spans(ring, "fedml.compile")
    assert (comp["parent"], comp["round"]) == ("fedml.dispatch", 0)


def test_span_phase_feeds_anatomy_and_amends(tmp_path):
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    anatomy.configure(anatomy=True)
    try:
        ANATOMY.begin_round(0)
        with span("fedml.fetch", phase="local") as sp:
            pass
        with span("fedml.log"):  # no phase: host_gap
            pass
        entry = ANATOMY.end_round()
        assert entry["phases"]["local"] == sp.seconds > 0
        assert set(entry["phases"]) == {"local", "host_gap"}
        # with the round closed a phase is dropped ...
        with span("fedml.eval", phase="eval"):
            pass
        assert "eval" not in ANATOMY.tracez()["entries"][-1]["phases"]
        # ... unless the driver says it belongs to the closed entry
        with ANATOMY.amending():
            with span("fedml.eval", phase="eval") as ev:
                pass
        last = ANATOMY.tracez()["entries"][-1]
        assert last["phases"]["eval"] == ev.seconds
        assert abs(sum(last["phases"].values()) - last["wall_s"]) < 1e-9
        with pytest.raises(ValueError):
            with span("fedml.z", phase="not_a_phase"):
                pass
    finally:
        anatomy.reset()
        telemetry.shutdown()


def test_capture_holds_nested_round_spans(tmp_path):
    """Under a profiler session the host plane holds, for every round,
    ``fedml.round > fedml.dispatch, fedml.fetch, fedml.eval, fedml.log``
    nested in that order, with ``round`` stats; FedAvgSim's test set
    lives on the device, so an evaluation re-sends nothing."""
    sim = _sim(rounds=3)
    sim.run()  # compile outside the capture
    _run_captured(sim, tmp_path)
    spans = _captured_spans(tmp_path)
    rounds = [s for s in spans if s[0] == "fedml.round"]
    assert [s[3]["round"] for s in rounds] == [0, 1, 2]
    for name, lo, hi, stats in rounds:
        kids = [s for s in spans if s[0] != "fedml.round"
                and lo <= s[1] and s[2] <= hi]
        want = ["fedml.dispatch", "fedml.fetch", "fedml.log"]
        if stats["round"] in (1, 2):  # eval_every=2, and the last round
            want.insert(2, "fedml.eval")
        assert [k[0] for k in kids] == want
        assert all(k[3]["round"] == stats["round"] for k in kids)
        # children do not overlap
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    evals = [s for s in spans if s[0] == "fedml.eval"]
    assert [e[3]["h2d_bytes"] for e in evals] == [0, 0]
    assert not any(s[0] == "fedml.compile" for s in spans)


def test_capture_sharded_eval_counts_h2d_bytes(tmp_path):
    """ShardedFedAvg keeps its arrays on the host but its test set
    split over the mesh: no evaluation re-sends anything, and every
    ``fedml.eval`` span says so."""
    sim = _sim(sharded=True, rounds=2)
    assert isinstance(sim.arrays.test_x, np.ndarray)
    _run_captured(sim, tmp_path)
    spans = _captured_spans(tmp_path)
    evals = [s for s in spans if s[0] == "fedml.eval"]
    assert [e[3]["h2d_bytes"] for e in evals] == [0]
    # the first round compiled inside the capture, under its own span
    (comp,) = [s for s in spans if s[0] == "fedml.compile"]
    assert comp[3]["family"] == "sharded_round"
    disp = [s for s in spans if s[0] == "fedml.dispatch"][0]
    assert disp[1] <= comp[1] and comp[2] <= disp[2]


@pytest.mark.parametrize("kind", ["cnn", "lr"],
                         ids=["sharded-cohort", "sharded-vmapped"])
def test_capture_log_span_carries_slot_steps(kind, tmp_path):
    """The sharded cohort round counts the slot-steps its lockstep
    schedule executed; the count reaches the round record and rides the
    round's ``fedml.log`` span. The vmapped round reports none."""
    from fedml_tpu.metrics import MetricsSink

    sim = _sim(kind, sharded=True, rounds=2)
    sink = MetricsSink()
    _run_captured(sim, tmp_path, sink)
    logs = [s for s in _captured_spans(tmp_path) if s[0] == "fedml.log"]
    assert [s[3]["round"] for s in logs] == [0, 1]
    if kind == "lr":
        assert not any("slot_steps" in r for r in sink.history)
        assert not any("slot_steps" in s[3] for s in logs)
        return
    counts = [r["slot_steps"] for r in sink.history]
    assert all(c >= sim.cfg.fed.clients_per_round for c in counts)
    assert [s[3]["slot_steps"] for s in logs] == counts


@pytest.mark.parametrize("kind,sharded", [
    ("lr", False), ("cnn", False), ("cnn", True), ("lr", True),
], ids=["vmapped", "cohort", "sharded-cohort", "sharded-vmapped"])
def test_compiled_round_holds_every_scope(kind, sharded):
    """Both local-update builders and both round bodies carry the
    scopes into the OPTIMIZED module's metadata."""
    sim = _sim(kind, sharded=sharded)
    state = sim.init()
    if sharded:
        assert (sim._shard_cohort_update is not None) == (kind == "cnn")
        compiled = jax.jit(sim._sharded_round).lower(
            state, sim.banks).compile()
    else:
        assert (sim._cohort_update is not None) == (kind == "cnn")
        compiled = jax.jit(sim._round).lower(state, sim.arrays).compile()
    text = compiled.as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "fedml.local/" in text
    # backward ops keep theirs inside the transposed name stack
    assert any("fedml.local.grad" in ln and "transpose(jvp(" in ln
               for ln in text.splitlines())


def test_scope_map_covers_module_and_frees_executable():
    memscope.reset()
    sim = _sim("cnn", rounds=1)
    state, _ = sim.run_round(sim.init())
    ((family, key, module),) = memscope.scope_programs()
    assert (family, module) == ("sim_round", "jit__round")
    exe = sim._round_fn._exes[key]
    text = exe.as_text()
    smap = memscope.scope_map(family, key)
    assert smap is memscope.scope_map(family, key)  # parsed once
    # every instruction of the module is in the map, under a scope of
    # the vocabulary or under None
    names = {ln.split(" = ")[0].split()[-1].lstrip("%")
             for ln in text.splitlines()
             if ln.startswith(" ") and " = " in ln}
    assert names and names <= set(smap)
    vocab = set(SCOPES) | {"fedml.local", "fedml.defense_agg"}
    assert {s for s in smap.values() if s is not None} <= vocab
    assert set(SCOPES) <= set(smap.values())
    # a fusion reads the scope of its own metadata, or its body's
    fusions = [n for n in smap if "fusion" in n and smap[n]]
    assert fusions
    assert memscope.scope_map("sim_round", "no-such-key") is None
    # the map holds text, never the executable: it dies with the sim
    ref = weakref.ref(exe)
    del exe, sim, state
    import gc

    gc.collect()
    assert ref() is None
    assert memscope.scope_map(family, key) is smap
    memscope.reset()
    assert memscope.scope_programs() == []


@pytest.mark.parametrize("which", ["FedAvgSim", "ShardedFedAvg", "fused"])
def test_results_bit_identical_under_profiler(which, tmp_path):
    """Spans and scopes only annotate: the trajectory under an active
    profiler session equals the one without."""
    def build():
        if which == "ShardedFedAvg":
            return _sim(sharded=True, rounds=2)
        if which == "fused":
            return _sim(rounds=4, fuse_rounds=2)
        return _sim(rounds=2)

    plain = build().run()
    traced = _run_captured(build(), tmp_path)
    for a, b in zip(jax.tree.leaves(plain.variables),
                    jax.tree.leaves(traced.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    names = {s[0] for s in _captured_spans(tmp_path)}
    if which == "fused":
        assert {"fedml.block", "fedml.dispatch", "fedml.fetch",
                "fedml.eval"} <= names
    else:
        assert {"fedml.round", "fedml.dispatch", "fedml.fetch",
                "fedml.eval"} <= names


def test_annotating_is_not_a_knob():
    """The switch for spans in a capture is the profiler session: no
    flag, field or argument selects it."""
    import inspect

    from fedml_tpu.experiments import deploy, run

    assert "jax_profiler" not in inspect.signature(
        telemetry.configure).parameters
    assert "use_jax_profiler" not in inspect.signature(
        Tracer.__init__).parameters
    import dataclasses

    gone = "trace" + "_jax"  # spelled apart: a grep for it finds nothing
    fields = {f.name for f in dataclasses.fields(deploy.DeployConfig)}
    assert "trace" in fields and gone not in fields
    assert '"--trace"' in inspect.getsource(run)
    assert gone not in inspect.getsource(run)
    assert not hasattr(telemetry, "maybe_span")  # callers use tracing.span
