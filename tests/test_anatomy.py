"""Round-anatomy plane (core/anatomy.py; docs/OBSERVABILITY.md "Round
anatomy").

The pins, in dependency order:

1. **Conservation**: on every instrumented round body — stacked, bulk,
   fused, sharded — the ring entry's explicit phases + ``host_gap``
   sum EXACTLY to its wall (the residual is computed, never dropped),
   and the per-path label is right.
2. **Zero cost when off**: an un-armed run writes no ``perf.phase.*``
   metrics, keeps the ring empty, serves 404 on ``/tracez`` — and the
   round RESULTS are byte-identical with the plane on vs off (the
   plane only reads clocks).
3. **Straggler attribution**: a chaos-delayed loopback client is named
   the dominant straggler by the deploy server's close path, and the
   critical-path gauge + tracer event land.
4. **Breach profiling**: ``BreachProfiler`` fires exactly once per
   breach *transition*, honors the capture cap and cooldown with an
   injectable clock/timer, links breach -> artifact through the flight
   recorder, and validates its knobs at construction.
5. **/tracez schema** and **merge_trace**: the listener section's JSON
   shape is pinned, and ``scripts/merge_trace.py`` renders the
   per-round critical path as its own Perfetto track from a 2-rank
   trace.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.algorithms.fedavg import FedAvgSim
from fedml_tpu.core import anatomy, export, telemetry
from fedml_tpu.core.anatomy import ANATOMY, PHASES, BreachProfiler
from fedml_tpu.core.transport.chaos import FaultPolicy
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.models import create_model
from fedml_tpu.parallel import ShardedFedAvg, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the conservation tolerance (acceptance: phase sums ~= round wall):
#: end_round computes host_gap as the residual, so the sum is exact up
#: to float64 rounding across <= 9 additions
CONSERVE_TOL = 1e-9


@pytest.fixture
def anatomy_env(tmp_path):
    """Telemetry + anatomy plane on, into a tmp dir; restore the
    all-disabled default afterwards (other suites assume it off)."""
    telemetry.configure(telemetry_dir=str(tmp_path / "telemetry"), rank=0)
    anatomy.configure(anatomy=True)
    yield str(tmp_path / "telemetry")
    anatomy.reset()
    telemetry.shutdown()


def _cfg(rounds=2, **fed_kw):
    fed_kw.setdefault("eval_every", rounds)
    fed_kw.setdefault("clients_per_round", 4)
    return ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=8,
                        batch_size=32, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=rounds, **fed_kw),
        seed=0,
    )


def _sim(cfg):
    return FedAvgSim(create_model(cfg.model), load_dataset(cfg.data), cfg)


def _assert_conserved(entries, path, n_rounds):
    assert entries, "anatomy ring is empty"
    assert all(e["path"] == path for e in entries)
    assert sum(e["rounds"] for e in entries) == n_rounds
    for e in entries:
        assert e["wall_s"] > 0
        assert set(e["phases"]) <= set(PHASES)
        assert "host_gap" in e["phases"], "residual silently dropped"
        assert abs(sum(e["phases"].values()) - e["wall_s"]) <= CONSERVE_TOL
        assert e["dominant"] == max(e["phases"], key=e["phases"].get)


# ---------------------------------------------------------------------------
# 1. conservation per round body
# ---------------------------------------------------------------------------


def test_phase_conservation_stacked(anatomy_env):
    _sim(_cfg(rounds=3)).run()
    entries = ANATOMY.tracez()["entries"]
    _assert_conserved(entries, "stacked", 3)
    # every entry carries the device execution + the boundary eval
    assert all("local" in e["phases"] for e in entries)
    assert "eval" in entries[-1]["phases"]
    h = telemetry.METRICS.snapshot()["histograms"]
    assert h["perf.phase.local_s"]["count"] == 3
    assert h["perf.phase.host_gap_s"]["count"] == 3


def test_phase_conservation_bulk(anatomy_env):
    _sim(_cfg(rounds=2, client_block_size=2)).run()
    _assert_conserved(ANATOMY.tracez()["entries"], "bulk", 2)


def test_phase_conservation_fused(anatomy_env):
    _sim(_cfg(rounds=4, fuse_rounds=2)).run()
    entries = ANATOMY.tracez()["entries"]
    # 4 rounds at fuse=2 -> 2 block entries, per-round normalization
    # recorded on the entry
    _assert_conserved(entries, "fused", 4)
    assert len(entries) == 2 and all(e["rounds"] == 2 for e in entries)
    # the boundary eval closes AFTER the block's entry and is amended
    # into it — conservation must survive the amend
    assert "eval" in entries[-1]["phases"]


def test_phase_conservation_sharded(anatomy_env):
    cfg = _cfg(rounds=2, clients_per_round=8)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=16,
                        batch_size=32, seed=0),
        model=cfg.model, train=cfg.train, fed=cfg.fed,
        mesh=MeshConfig(client_axis_size=8, data_axis_size=1), seed=0,
    )
    mesh = make_mesh(client_axis=8, data_axis=1)
    ShardedFedAvg(create_model(cfg.model), load_dataset(cfg.data), cfg,
                  mesh).run()
    _assert_conserved(ANATOMY.tracez()["entries"], "sharded", 2)


def test_entries_keep_their_keys_and_the_loops_hold_no_clock_pair(
        anatomy_env):
    """The phases are fed by the span sites (core/tracing.span,
    ``phase=``): the ring entry's schema is what it was, and the round
    loops hold no hand-written clock pair for anatomy any more."""
    import inspect

    from fedml_tpu.core import fuse

    _sim(_cfg(rounds=2, eval_every=1)).run()
    _sim(_cfg(rounds=2, fuse_rounds=2)).run()
    entries = ANATOMY.tracez()["entries"]
    assert [e["path"] for e in entries] == ["stacked", "stacked", "fused"]
    for e in entries:
        assert set(e) == {"round", "path", "rounds", "wall_s", "phases",
                          "dominant", "ts"}
        assert {"local", "eval", "host_gap"} == set(e["phases"])
        assert abs(sum(e["phases"].values()) - e["wall_s"]) <= CONSERVE_TOL
    for fn in (FedAvgSim.run, fuse.drive_rounds, fuse.drive,
               fuse.BlockPipeline.flush):
        src = inspect.getsource(fn)
        assert "ANATOMY.phase(" not in src and "amend_last(" not in src
        for gone in ("t_disp", "t_ev", "t_get", "get_wait"):
            assert gone not in src, (fn.__qualname__, gone)
    # one clock read is left in each loop: the perf monitor's round
    # wall; the simulator itself holds no loop and so no clock
    assert inspect.getsource(fuse.drive_rounds).count(
        "perf_counter()") == 2
    assert inspect.getsource(fuse.run_loop).count("perf_counter") == 0
    assert inspect.getsource(FedAvgSim.run).count("perf_counter") == 0


def test_amend_last_conserves(anatomy_env):
    ANATOMY.begin_round(0, path="fused", rounds=2)
    ANATOMY.phase("local", 0.8)
    ANATOMY.end_round(wall_s=1.0)
    ANATOMY.amend_last("eval", 0.6)
    e = ANATOMY.tracez()["entries"][-1]
    assert e["phases"]["eval"] == pytest.approx(0.6)
    assert e["wall_s"] == pytest.approx(1.6)
    assert abs(sum(e["phases"].values()) - e["wall_s"]) <= CONSERVE_TOL
    assert e["dominant"] == "local"
    with pytest.raises(ValueError, match="unknown anatomy phase"):
        ANATOMY.amend_last("not_a_phase", 0.1)
    with pytest.raises(ValueError, match="unknown anatomy phase"):
        ANATOMY.phase("not_a_phase", 0.1)


# ---------------------------------------------------------------------------
# 2. zero cost (and zero effect) when off
# ---------------------------------------------------------------------------


def test_zero_cost_when_off(tmp_path):
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    try:
        assert not ANATOMY.enabled
        _sim(_cfg(rounds=2)).run()
        snap = telemetry.METRICS.snapshot()
        names = (list(snap["histograms"]) + list(snap["gauges"])
                 + list(snap["counters"]))
        assert not [n for n in names if n.startswith("perf.phase.")]
        assert not [n for n in names if n.startswith("perf.straggler")]
        assert ANATOMY.tracez()["entries"] == []
        # the listener serves NO /tracez section while the plane is off
        ex = export.MetricsExporter(0, host="127.0.0.1")
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ex.port}/tracez", timeout=10
                )
            assert err.value.code == 404
        finally:
            ex.stop()
    finally:
        telemetry.shutdown()


def test_off_is_byte_identical(anatomy_env):
    """The plane only reads clocks: the round trajectory with anatomy
    ON must be bit-equal to the same run with it OFF."""
    s_on = _sim(_cfg(rounds=2)).run()
    ANATOMY.enabled = False
    s_off = _sim(_cfg(rounds=2)).run()
    ANATOMY.enabled = True
    for a, b in zip(jax.tree.leaves(s_on.variables),
                    jax.tree.leaves(s_off.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 3. straggler attribution on a chaos-delayed loopback world
# ---------------------------------------------------------------------------


def test_straggler_attribution_pins_delayed_client(anatomy_env):
    from tests.test_fault_tolerance import (
        _cfg as world_cfg, _make_world_transports, _run_world,
    )

    # rank 2's WORK messages are delayed ~100ms; rank 1 is clean
    policies = {2: FaultPolicy(seed=7, delay_prob=1.0,
                               delay_min_s=0.1, delay_max_s=0.12)}
    server, _ = _run_world(_make_world_transports("loopback"),
                           world_cfg(rounds=3), policies=policies)
    assert server.done.is_set()
    snap = telemetry.METRICS.snapshot()
    g = snap["gauges"]
    # the delayed rank is the dominant straggler, by a margin no
    # scheduler hiccup explains (>= half the injected delay)
    assert g["perf.straggler.rank2"] - g["perf.straggler.rank1"] >= 0.05
    assert g["perf.critical_path_s"] > 0
    h = snap["histograms"]
    assert h["perf.straggler_wait_s"]["count"] >= 1
    assert h["perf.straggler_wait_s"]["max"] >= 0.05
    # deploy entries conserve too, and the wire/server legs are split
    entries = [e for e in ANATOMY.tracez()["entries"]
               if e["path"] == "deploy"]
    _assert_conserved(entries, "deploy", len(entries))
    assert all("wire" in e["phases"] for e in entries)
    # the critical-path tracer events exist for merge_trace to render
    telemetry.flush()
    dump = json.load(open(os.path.join(anatomy_env, "trace_rank0.json")))
    cps = [e for e in dump["events"] if e.get("name") == "critical_path"]
    assert len(cps) == 3
    assert all(e["rank_path"] == 2 for e in cps)
    for e in cps:
        assert e["total_s"] == pytest.approx(
            e["sync_to_result_s"] + e["aggregate_s"], abs=1e-9
        )


# ---------------------------------------------------------------------------
# 4. breach-triggered deep profiling
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self, monkeypatch, fail_start=False):
        self.starts, self.stops = [], []
        self.fail_start = fail_start
        monkeypatch.setattr(jax.profiler, "start_trace", self._start)
        monkeypatch.setattr(jax.profiler, "stop_trace", self._stop)

    def _start(self, path):
        if self.fail_start:
            raise RuntimeError("profiler session already active")
        self.starts.append(path)

    def _stop(self):
        self.stops.append(True)


def _flight_kinds():
    return [e["kind"] for e in list(telemetry.RECORDER._ring)]


def test_breach_profiler_once_per_transition_cap_cooldown(
        anatomy_env, tmp_path, monkeypatch):
    fake = _FakeProfiler(monkeypatch)
    clk = [0.0]
    timers = []

    def timer(delay_s, fn):
        timers.append((delay_s, fn))

    p = BreachProfiler(str(tmp_path / "profiles"), window_s=5.0,
                       max_captures=2, cooldown_s=30.0,
                       clock=lambda: clk[0], timer=timer)
    # breach #1 fires: artifact dir + manifest + flight link
    path1 = p.on_breach("slo_round_wall_p99", slo="p99<0.3", value=0.4)
    assert path1 and os.path.isdir(path1)
    assert "breach_1_slo_round_wall_p99" in path1
    man = json.load(open(os.path.join(path1, "breach.json")))
    assert man["reason"] == "slo_round_wall_p99" and man["capture"] == 1
    assert fake.starts == [path1] and p.active
    snap = telemetry.METRICS.snapshot()
    assert snap["counters"]["profile.captures"] == 1
    assert snap["gauges"]["profile.active"] == 1.0
    assert "breach_profile" in _flight_kinds()
    # a second breach while the window is open is a SKIP, not a capture
    assert p.on_breach("slo_round_wall_p99") is None
    assert telemetry.METRICS.snapshot()["counters"]["profile.skipped"] == 1
    assert "breach_profile_skipped" in _flight_kinds()
    # the window closes from the (injected) timer; never re-entered
    assert len(timers) == 1 and timers[0][0] == 5.0
    clk[0] = 5.0
    timers[0][1]()
    assert len(fake.stops) == 1 and not p.active
    assert "breach_profile_done" in _flight_kinds()
    assert telemetry.METRICS.snapshot()["gauges"]["profile.active"] == 0.0
    # within cooldown (30s since the window closed): skip
    clk[0] = 20.0
    assert p.on_breach("mem_headroom") is None
    # past cooldown: capture #2 (the cap)
    clk[0] = 40.0
    path2 = p.on_breach("mem_headroom", headroom_mb=12)
    assert path2 and p.captures == 2
    timers[1][1]()
    # cap spent: every later breach skips, forever
    clk[0] = 1000.0
    assert p.on_breach("slo_round_wall_p99") is None
    assert len(fake.starts) == 2, "cap not honored"
    skips = telemetry.METRICS.snapshot()["counters"]["profile.skipped"]
    assert skips == 3


def test_breach_profiler_transition_edge_only(anatomy_env, tmp_path,
                                              monkeypatch):
    """The SLO listener fires on the ok->breach EDGE only: a clearing
    transition (breaching=False) never opens a window."""
    fake = _FakeProfiler(monkeypatch)
    p = BreachProfiler(str(tmp_path / "p"), window_s=1.0,
                       max_captures=3, cooldown_s=0.0,
                       clock=lambda: 0.0, timer=lambda d, f: None)
    monkeypatch.setattr(anatomy, "_BREACH", p)

    class Spec:
        slug = "round_wall_p99"
        scope = "perf.round_wall_s"

        def describe(self):
            return "perf.round_wall_s:p99<0.3"

    anatomy._on_slo_transition(Spec(), False, 0.1)
    assert fake.starts == []
    anatomy._on_slo_transition(Spec(), True, 0.5)
    assert len(fake.starts) == 1
    man = json.load(open(os.path.join(fake.starts[0], "breach.json")))
    assert man["reason"] == "slo_round_wall_p99"


def test_breach_profiler_failure_contains(anatomy_env, tmp_path,
                                          monkeypatch):
    """A start_trace collision (one jax.profiler session per process)
    marks the profiler broken — no crash, no later capture."""
    _FakeProfiler(monkeypatch, fail_start=True)
    p = BreachProfiler(str(tmp_path / "p"), window_s=1.0,
                       max_captures=3, cooldown_s=0.0,
                       clock=lambda: 0.0, timer=lambda d, f: None)
    assert p.on_breach("slo_x") is None
    assert telemetry.METRICS.snapshot()["counters"]["profile.failed"] == 1
    assert "breach_profile_failed" in _flight_kinds()
    assert p.on_breach("slo_x") is None  # broken: skip, don't retry


def test_breach_profiler_validation(tmp_path):
    with pytest.raises(ValueError, match="profile_window_s"):
        BreachProfiler(str(tmp_path), window_s=0.0)
    with pytest.raises(ValueError, match="profile_max_captures"):
        BreachProfiler(str(tmp_path), max_captures=0)
    # arming breach profiling needs somewhere to write artifacts
    assert telemetry.artifact_dir() is None
    with pytest.raises(ValueError, match="telemetry dir"):
        anatomy.configure(profile_on_breach=True)


# ---------------------------------------------------------------------------
# 5. /tracez schema + merge_trace critical path
# ---------------------------------------------------------------------------


def test_tracez_schema_over_listener(anatomy_env):
    _sim(_cfg(rounds=2)).run()
    ex = export.MetricsExporter(0, host="127.0.0.1")
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/tracez", timeout=10
        ) as r:
            assert r.status == 200
            doc = json.loads(r.read().decode())
    finally:
        ex.stop()
    assert doc["rank"] == 0
    assert doc["phases"] == list(PHASES)
    assert doc["capacity"] >= len(doc["entries"])
    assert doc["rounds"] == 2 and len(doc["entries"]) == 2
    for e in doc["entries"]:
        assert set(e) == {"round", "path", "rounds", "wall_s", "phases",
                          "dominant", "ts"}


def test_merge_trace_renders_critical_path(tmp_path):
    """A 2-rank dump with critical_path instants merges into a
    dedicated Perfetto track reconstructing each round's chain."""
    ts0 = 1_700_000_000.0
    rank0 = {"rank": 0, "events": [
        {"kind": "span", "name": "round", "ts": ts0, "seconds": 0.5,
         "rank": 0, "tid": 1, "round": 0},
        {"kind": "event", "name": "critical_path", "ts": ts0 + 0.62,
         "seconds": 0, "rank": 0, "tid": 1, "round": 0, "rank_path": 2,
         "sync_to_result_s": 0.4, "straggler_wait_s": 0.1,
         "aggregate_s": 0.05, "total_s": 0.45, "closed_after_s": 0.55},
    ]}
    rank1 = {"rank": 2, "events": [
        {"kind": "span", "name": "local_update", "ts": ts0 + 0.1,
         "seconds": 0.3, "rank": 2, "tid": 1, "round": 0},
    ]}
    p0 = tmp_path / "trace_rank0.json"
    p1 = tmp_path / "trace_rank2.json"
    p0.write_text(json.dumps(rank0))
    p1.write_text(json.dumps(rank1))
    out = tmp_path / "merged.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "merge_trace.py"),
         str(p0), str(p1), "--out", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr
    evs = json.loads(out.read_text())["traceEvents"]
    track = [e for e in evs if e.get("pid") == 8000 and e.get("ph") == "X"]
    names = {e["name"] for e in track}
    assert "r0 sync->result rank2" in names
    assert "r0 aggregate" in names
    seg = next(e for e in track if e["name"] == "r0 sync->result rank2")
    assert seg["dur"] == pytest.approx(0.4e6)
    assert seg["args"]["straggler_wait_s"] == pytest.approx(0.1)
    # the chain is rebased onto the same timeline as the rank spans:
    # sync happens at close - closed_after = ts0 + 0.07 rel
    assert seg["ts"] == pytest.approx(0.07e6, abs=1.0)
    # the raw instant no longer clutters rank 0's own track
    assert not [e for e in evs
                if e.get("name") == "critical_path" and e.get("pid") == 0]
    # and the track is labeled for Perfetto
    meta = [e for e in evs if e.get("ph") == "M" and e.get("pid") == 8000]
    assert any(e["args"].get("name") == "critical path (round anatomy)"
               for e in meta if e["name"] == "process_name")
