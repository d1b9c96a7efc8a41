"""Performance-observability suite (core/perf.py + the percentile /
time-series satellites; docs/OBSERVABILITY.md
"Performance observability").

The pins, in dependency order:

1. device-time breakdown parsing: synthetic capture events fold into
   the compute/collective/host/idle split with interval-union
   semantics (nested/parallel events never double-count wall time),
   both for device-plane captures (TPU shape) and the hlo_op-tagged
   host-thread shape the CPU backend emits;
2. a REAL ``jax.profiler`` capture on the CPU backend round-trips
   through :class:`RoundProfiler` into a breakdown artifact with
   actual XLA ops in it;
3. ``useful_round_cost`` equals a hand-lowered ``cost_analysis``
   step-FLOPs value times the sampled-work multiplier, and the live
   ``perf.mfu`` gauge agrees with the analytic MFU over the same rate
   (the acceptance bar is 10%);
4. the dispatch-bound detector turns ``mfu < floor`` into the
   ``perf.*`` counter + flight-recorder event;
5. percentile estimation: exact for single-valued histograms, bounded
   by the power-of-two bucket width across buckets, surfaced in
   ``snapshot()``, ``summary.json``, and the periodic
   ``metrics_rank<r>.jsonl`` time series.
"""

import json
import os
import time

import pytest

from fedml_tpu.core import perf, telemetry
from fedml_tpu.core.telemetry import (
    MetricsRegistry,
    percentiles_from_histogram,
)


@pytest.fixture
def telem(tmp_path):
    tdir = str(tmp_path / "telemetry")
    telemetry.configure(telemetry_dir=tdir, rank=0)
    yield tdir
    telemetry.shutdown()


def _ev(name, ts_us, dur_us, pid=1, process="/device:TPU:0", tid=0,
        args=None):
    return {"name": name, "pid": pid, "tid": tid, "ts": float(ts_us),
            "dur": float(dur_us), "process": process,
            "args": args or {}}


# ---------------------------------------------------------------------------
# 1. breakdown parsing on synthetic captures
# ---------------------------------------------------------------------------


def test_breakdown_device_plane_four_way_split():
    events = [
        _ev("fusion.1", 0, 40),
        _ev("all-reduce.2", 40, 20),
        _ev("copy-start.3", 60, 10),
        # a host-plane bookkeeping event that must NOT count as device
        _ev("ThreadpoolListener::Record", 0, 90, pid=9,
            process="/host:CPU"),
    ]
    bd = perf.device_time_breakdown(events, window_s=100e-6)
    assert bd["device_busy_s"] == pytest.approx(70e-6)
    assert bd["compute_s"] == pytest.approx(40e-6)
    assert bd["collective_s"] == pytest.approx(20e-6)
    assert bd["host_s"] == pytest.approx(10e-6)
    assert bd["idle_s"] == pytest.approx(30e-6)
    assert bd["compute_frac"] == pytest.approx(0.4)
    assert bd["idle_frac"] == pytest.approx(0.3)
    assert bd["n_device_ops"] == 3
    assert bd["device_planes"] is True
    # for a SERIAL capture the four categories tile the window
    assert (bd["compute_s"] + bd["collective_s"] + bd["host_s"]
            + bd["idle_s"]) == pytest.approx(bd["window_s"])


def test_breakdown_parallel_lanes_do_not_eat_compute():
    # collective + copy + compute all concurrent on separate lanes
    # (async-dispatch overlap): each category is its OWN union — the
    # collective must not swallow the compute that ran under it
    events = [
        _ev("all-reduce.1", 0, 10, tid=1),
        _ev("copy.2", 0, 10, tid=2),
        _ev("fusion.3", 0, 10, tid=3),
    ]
    bd = perf.device_time_breakdown(events, window_s=20e-6)
    assert bd["device_busy_s"] == pytest.approx(10e-6)
    assert bd["compute_s"] == pytest.approx(10e-6)
    assert bd["collective_s"] == pytest.approx(10e-6)
    assert bd["host_s"] == pytest.approx(10e-6)
    assert bd["idle_s"] == pytest.approx(10e-6)


def test_breakdown_union_never_double_counts():
    # nested + overlapping compute events: 0-50 and 25-75 cover 75us
    events = [_ev("fusion.1", 0, 50), _ev("dot.2", 25, 50)]
    bd = perf.device_time_breakdown(events, window_s=100e-6)
    assert bd["device_busy_s"] == pytest.approx(75e-6)
    assert bd["compute_s"] == pytest.approx(75e-6)
    assert bd["idle_s"] == pytest.approx(25e-6)


def test_breakdown_cpu_shape_hlo_ops_and_host_block():
    # the CPU backend has no /device: plane; XLA thunks are host events
    # carrying an hlo_op arg, and buffer awaits mark host-blocked time
    events = [
        _ev("dot.3", 0, 30, pid=7, process="/host:CPU",
            args={"hlo_op": "dot.3"}),
        _ev("reduce.8", 10, 30, pid=7, process="/host:CPU",
            args={"hlo_op": "reduce.8"}),
        # await overlaps busy [0,40] for 20us; only the extra 20 counts
        _ev("TfrtCpuBuffer::Await", 20, 40, pid=7, process="/host:CPU"),
        _ev("ParseArguments", 0, 5, pid=7, process="/host:CPU"),
    ]
    bd = perf.device_time_breakdown(events, window_s=100e-6)
    assert bd["device_planes"] is False
    assert bd["n_device_ops"] == 2
    assert bd["device_busy_s"] == pytest.approx(40e-6)
    assert bd["compute_s"] == pytest.approx(40e-6)
    assert bd["host_s"] == pytest.approx(20e-6)  # non-overlapping await
    assert bd["idle_s"] == pytest.approx(40e-6)


def test_breakdown_empty_capture_degrades():
    bd = perf.device_time_breakdown([], window_s=1e-3)
    assert bd["n_events"] == 0 and bd["device_busy_s"] == 0.0
    assert bd["idle_s"] == pytest.approx(1e-3)


# ---------------------------------------------------------------------------
# 2. a real CPU capture through RoundProfiler
# ---------------------------------------------------------------------------


def test_round_profiler_real_cpu_capture(tmp_path, telem):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sum(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()  # compile outside the window
    prof = perf.RoundProfiler(rounds=1, out_dir=str(tmp_path),
                              tag="rank0")
    prof.start_round(0)
    f(x).block_until_ready()
    prof.end_round(0)
    # a second round is NOT captured (budget of 1)
    prof.start_round(1)
    prof.end_round(1)
    path = prof.finish()
    assert path is not None and os.path.exists(path)
    data = json.load(open(path))
    assert len(data["rounds"]) == 1
    bd = data["rounds"][0]
    assert bd["round"] == 0 and bd["window_s"] > 0
    assert bd["n_device_ops"] > 0, bd  # real XLA ops were parsed
    assert bd["compute_s"] > 0
    # the capture session + manifest landed per round
    rdir = os.path.join(str(tmp_path), "jax_profile", "round0")
    assert json.load(open(os.path.join(rdir, "capture.json")))["round"] == 0
    # gauges + flight event fed
    g = telemetry.METRICS.snapshot()["gauges"]
    assert "perf.profile.compute_frac" in g
    assert any(e["kind"] == "perf_profile"
               for e in list(telemetry.RECORDER._ring))


# ---------------------------------------------------------------------------
# 3. MFU: shared analytic cost model + live gauge
# ---------------------------------------------------------------------------


def _tiny_sim(cpr=2, profile_rounds=0, num_rounds=2):
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    cfg = ExperimentConfig(
        data=DataConfig(dataset="fake_mnist", num_clients=4,
                        batch_size=16, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(28, 28, 1)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=num_rounds, clients_per_round=cpr,
                      eval_every=10**9, profile_rounds=profile_rounds),
        seed=0,
    )
    return FedAvgSim(create_model(cfg.model), load_dataset(cfg.data),
                     cfg)


def _hand_step_flops(sim):
    """The test's OWN lowering of one training step's grad — the pin
    useful_round_cost must agree with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, B = sim.model, sim.batch_size
    variables = model.init(jax.random.key(0))
    params = variables["params"]
    static = {k: v for k, v in variables.items() if k != "params"}
    x = jnp.zeros((B,) + sim.arrays.x.shape[1:], sim.arrays.x.dtype)
    y = jnp.zeros((B,) + sim.arrays.y.shape[1:], sim.arrays.y.dtype)

    def loss(p):
        logits, _ = model.apply_train(
            {**static, "params": p}, x, jax.random.key(0)
        )
        sums = sim.task.metric_sums(
            logits.astype(jnp.float32), y, jnp.ones((B,), jnp.float32)
        )
        return sums["loss_sum"] / jnp.maximum(sums["w_sum"], 1.0)

    ca = jax.jit(jax.grad(loss)).lower(params).compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    steps = float(np.mean(np.ceil(np.asarray(sim.arrays.counts) / B)))
    return float(ca["flops"]), steps


def test_useful_round_cost_matches_hand_computed_cost_analysis():
    sim = _tiny_sim(cpr=2)
    got = perf.useful_round_cost(sim)
    assert got is not None and got > 0
    step_flops, mean_steps = _hand_step_flops(sim)
    expected = step_flops * 2 * mean_steps * sim.cfg.train.epochs
    assert got == pytest.approx(expected, rel=1e-3)
    # linear in the sampled cohort (same cached step program)
    sim4 = _tiny_sim(cpr=4)
    assert perf.useful_round_cost(sim4) == pytest.approx(2 * got,
                                                         rel=1e-6)


def test_perf_monitor_warmup_round_is_excluded(telem):
    telemetry.METRICS.reset()
    mon = perf.PerfMonitor(flops_per_round=1e9, peak_flops=1e12)
    mon.note_round(30.0)  # the compile round: must not skew anything
    snap = telemetry.METRICS.snapshot()
    assert "perf.round_wall_s" not in snap["histograms"]
    assert "perf.mfu" not in snap["gauges"]
    assert snap["gauges"]["perf.warmup_round_wall_s"] == 30.0
    mon.note_round(0.001)  # first REAL round
    snap = telemetry.METRICS.snapshot()
    assert snap["histograms"]["perf.round_wall_s"]["count"] == 1
    # the EWMA never saw the 30s compile: MFU reflects steady state
    assert snap["gauges"]["perf.mfu"] == pytest.approx(1.0)


def test_perf_monitor_mfu_gauge_agrees_with_analytic(telem):
    telemetry.METRICS.reset()
    mon = perf.PerfMonitor(flops_per_round=1e9, peak_flops=1e12,
                           path="test", warmup_rounds=0)
    mon.note_round(0.001)  # 1000 rounds/s -> delivered 1e12 -> MFU 1.0
    g = telemetry.METRICS.snapshot()["gauges"]
    assert g["perf.mfu"] == pytest.approx(1.0)
    assert g["perf.rounds_per_s"] == pytest.approx(1000.0)
    assert g["perf.delivered_flops_per_s"] == pytest.approx(1e12)
    assert g["perf.latency_bound"] == 0.0
    # the analytic MFU over the same rate: identical (<10%)
    analytic_mfu = 1e9 * g["perf.rounds_per_s"] / 1e12
    assert abs(g["perf.mfu"] - analytic_mfu) <= 0.1 * analytic_mfu
    # the wall-time histogram is the SLO surface
    h = telemetry.METRICS.snapshot()["histograms"]["perf.round_wall_s"]
    assert h["count"] == 1 and "p50" in h


def test_dispatch_bound_detector_fires_counter_and_flight_event(telem):
    telemetry.METRICS.reset()
    mon = perf.PerfMonitor(flops_per_round=1e3, peak_flops=1e12,
                           path="FedAvgSim", warmup_rounds=0)
    mon.note_round(0.01)  # MFU 1e-7 << 0.005: dispatch-bound
    mon.note_round(0.01)
    snap = telemetry.METRICS.snapshot()
    assert snap["counters"]["perf.dispatch_bound_rounds"] == 2
    assert snap["gauges"]["perf.latency_bound"] == 1.0
    assert snap["gauges"]["perf.mfu"] < 0.005
    flagged = [e for e in list(telemetry.RECORDER._ring)
               if e["kind"] == "perf_dispatch_bound"]
    assert len(flagged) == 1  # one flight event per run, not per round
    assert flagged[0]["path"] == "FedAvgSim"


def test_build_sim_perf_inert_without_profile_rounds():
    sim = _tiny_sim(cpr=2, profile_rounds=0)
    assert perf.build_sim_perf(sim) == (None, None)


def test_sim_run_with_profile_rounds_writes_breakdown_and_gauges(
        tmp_path):
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    try:
        sim = _tiny_sim(cpr=2, profile_rounds=1, num_rounds=2)
        sim.run()
        path = tmp_path / "t" / "perf_rank0.json"
        assert path.exists()
        data = json.load(open(path))
        assert len(data["rounds"]) == 1
        assert data["rounds"][0]["n_device_ops"] > 0
        assert data["flops_per_round"] and data["flops_per_round"] > 0
        snap = telemetry.METRICS.snapshot()
        g = snap["gauges"]
        assert "perf.rounds_per_s" in g
        assert "perf.profile.compute_frac" in g
        # every post-warmup round fed the SLO histogram (round 0 is the
        # compile round, excluded by design; its wall is a gauge)
        assert snap["histograms"]["perf.round_wall_s"]["count"] == 1
        assert "perf.warmup_round_wall_s" in g
    finally:
        telemetry.shutdown()


def test_profile_rounds_breakdown_gains_the_phase_split(tmp_path):
    """The operator's own capture reads the round program's scopes:
    each profiled window's device time split by ``fedml.*`` scope
    through ``memscope.scope_map`` (docs/OBSERVABILITY.md "Spans and
    scopes"), the same join the benchmark's reader makes."""
    from fedml_tpu.core import memscope

    memscope.reset()
    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    try:
        sim = _tiny_sim(cpr=2, profile_rounds=2, num_rounds=3)
        sim.run()
        data = json.load(open(tmp_path / "t" / "perf_rank0.json"))
        assert len(data["rounds"]) == 2
        for row in data["rounds"]:
            phases = row["phases_s"]
            assert {"fedml.local.grad", "fedml.local.update",
                    "fedml.server_update"} <= set(phases)
            assert all(v >= 0 for v in phases.values())
            # every op of the round program is counted once, somewhere
            assert sum(phases.values()) > 0
        mean = data["mean"]["phases_s"]
        assert mean["fedml.local.grad"] == pytest.approx(sum(
            r["phases_s"]["fedml.local.grad"] for r in data["rounds"]
        ) / 2)
        # a synthetic capture: the join is by module, then instruction
        fam, key, module = memscope.scope_programs()[0]
        smap = memscope.scope_map(fam, key)
        grad = next(n for n, s in smap.items() if s == "fedml.local.grad")
        none = next(n for n, s in smap.items() if s is None)
        ev = lambda name, mod, dur: {
            "name": name, "pid": 1, "tid": 1, "ts": 0.0, "dur": dur,
            "process": "/host:CPU",
            "args": {"hlo_op": name, "hlo_module": mod}}
        split = perf.phase_split([
            ev(grad, module, 2e6), ev(none, module, 1e6),
            ev("while.3", module, 9e6), ev("fusion.1", "jit_evaluate", 4e6),
        ])
        assert split == {"fedml.local.grad": 2.0, "unscoped": 1.0,
                         "other_programs": 4.0}
    finally:
        telemetry.shutdown()
    assert perf.phase_split([]) is None  # no program kept, no split


# ---------------------------------------------------------------------------
# 5. percentile estimation + its surfaces
# ---------------------------------------------------------------------------


def test_percentiles_exact_for_singletons_and_constant_histograms():
    reg = MetricsRegistry()
    reg.observe("one", 3.3)
    h = reg.snapshot()["histograms"]["one"]
    assert h["p50"] == h["p95"] == h["p99"] == pytest.approx(3.3)
    for _ in range(100):
        reg.observe("const", 0.7)
    h = reg.snapshot()["histograms"]["const"]
    assert h["p50"] == h["p95"] == h["p99"] == pytest.approx(0.7)


def test_percentiles_bounded_error_across_buckets():
    reg = MetricsRegistry()
    values = list(range(1, 101))  # uniform 1..100
    for v in values:
        reg.observe("lat", float(v))
    h = reg.snapshot()["histograms"]["lat"]
    # bucket-width bound: the estimate is within a factor of 2 of the
    # true quantile (docstring contract), monotone, and inside [min, max]
    for key, true in (("p50", 50), ("p95", 95), ("p99", 99)):
        assert true / 2 <= h[key] <= true * 2, (key, h[key])
    assert h["min"] <= h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    # two-point histogram: the p99 bucket is clamped by the max
    reg2 = MetricsRegistry()
    reg2.observe("two", 1.0)
    reg2.observe("two", 100.0)
    h2 = reg2.snapshot()["histograms"]["two"]
    assert h2["p50"] == pytest.approx(1.0)  # singleton bucket, exact
    assert 64.0 <= h2["p99"] <= 100.0  # inside the clamped top bucket


def test_percentiles_from_histogram_handles_empty():
    assert percentiles_from_histogram({"count": 0, "buckets": {}}) == {}


def test_sink_summary_exposes_registry_percentiles(tmp_path):
    from fedml_tpu.metrics.sink import MetricsSink

    telemetry.configure(telemetry_dir=str(tmp_path / "t"), rank=0)
    try:
        telemetry.METRICS.reset()
        telemetry.METRICS.observe("round.wall_s", 0.5)
        sink = MetricsSink(path=str(tmp_path / "m" / "metrics.jsonl"))
        sink.log({"acc": 1.0})
        sink.close()
        summary = json.load(open(tmp_path / "m" / "summary.json"))
        th = summary["telemetry_histograms"]["round.wall_s"]
        assert th["p50"] == pytest.approx(0.5)
        assert th["count"] == 1 and "buckets" not in th
        assert summary["acc"] == 1.0
    finally:
        telemetry.shutdown()


def test_metrics_timeseries_appends_rows(tmp_path):
    tdir = tmp_path / "t"
    telemetry.configure(telemetry_dir=str(tdir), rank=0,
                        metrics_interval=0.05)
    try:
        telemetry.METRICS.inc("x")
        telemetry.METRICS.observe("lat", 0.25)
        time.sleep(0.25)
    finally:
        telemetry.shutdown()
    rows = [json.loads(line)
            for line in open(tdir / "metrics_rank0.jsonl")]
    assert len(rows) >= 2  # periodic ticks + the shutdown row
    last = rows[-1]
    assert last["rank"] == 0 and last["counters"]["x"] == 1
    h = last["histograms"]["lat"]
    assert h["p50"] == pytest.approx(0.25)
    assert "buckets" not in h  # rows are compact; the .json keeps them
    assert rows[0]["ts"] <= last["ts"]
