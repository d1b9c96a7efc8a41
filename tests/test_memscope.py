"""Memory & compilation observability suite (core/memscope.py;
docs/OBSERVABILITY.md "Memory & compilation").

The pins, in dependency order:

1.  per-program accounting: a compiled program's ``memory_analysis()``
    lands as the five ``mem.program.<slug>.*`` gauges under a stable
    slug and its compile wall in the ``mem.compile_s.<family>``
    histogram — for :class:`ProgramSite` (the sims' jit sites) AND
    :class:`CompiledRoundCache` (the deploy/sharded executables);
2.  the live monitor: CPU devices report no ``memory_stats``, so the
    sample falls back to process RSS with ``source: rss`` marked, the
    run high-water mark is monotone, and the headroom flight event
    fires exactly ONCE per run (a trigger, not a per-round log);
3.  the donation audit: a donating program's consumed carries pass, an
    undonated control is flagged (``mem.donation_misses`` + one flight
    event naming the program), and the count never double-fires for
    one program;
4.  the capacity column of the peaks table;
5.  ``/metrics`` exposition of a registry carrying ``mem.*`` gauges +
    compile histograms passes the PR 11 STRICT parser (the renderer
    still never grades its own homework);
6.  zero-cost-when-off: a disabled registry takes no samples and
    records no programs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.core import elastic as E
from fedml_tpu.core import export, memscope, telemetry


@pytest.fixture
def metrics_on():
    telemetry.METRICS.enabled = True
    telemetry.METRICS.reset()
    telemetry.RECORDER.enabled = True
    telemetry.RECORDER._ring.clear()
    memscope.reset()
    yield telemetry.METRICS
    telemetry.METRICS.enabled = False
    telemetry.METRICS.reset()
    telemetry.RECORDER.enabled = False
    telemetry.RECORDER._ring.clear()
    memscope.reset()
    export.reset_status_sources()


def _cfg(c=4, rounds=2, **fed_kw):
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic_1_1", num_clients=c,
                        batch_size=16, seed=0),
        model=ModelConfig(name="lr", num_classes=10,
                          input_shape=(60,)),
        train=TrainConfig(lr=0.1, epochs=1, cohort_fused=False),
        fed=FedConfig(num_rounds=rounds, clients_per_round=c,
                      eval_every=rounds, **fed_kw),
        seed=0,
    )


def _sim(cfg):
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    return FedAvgSim(create_model(cfg.model), load_dataset(cfg.data),
                     cfg)


_FIELDS = ("temp_bytes", "argument_bytes", "output_bytes",
           "alias_bytes", "generated_code_bytes")


# ---------------------------------------------------------------------------
# 1. per-program accounting
# ---------------------------------------------------------------------------


def test_program_site_records_analysis_and_compile_time(metrics_on):
    site = memscope.ProgramSite(lambda x: x * 2.0, family="toy")
    out = site(8, jnp.ones((8, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    rec = memscope.program_record("toy", 8)
    assert rec is not None
    for f in _FIELDS:
        assert rec[f] >= 0
    assert rec["argument_bytes"] == 8 * 4 * 4
    assert rec["compile_s"] > 0
    snap = metrics_on.snapshot()
    for f in _FIELDS:
        assert f"mem.program.toy.8.{f}" in snap["gauges"], (
            sorted(snap["gauges"])
        )
    h = snap["histograms"]["mem.compile_s.toy"]
    assert h["count"] == 1 and h["sum"] > 0
    # second call with the same key: cached executable, no new compile
    site(8, jnp.ones((8, 4)))
    assert metrics_on.snapshot()["histograms"][
        "mem.compile_s.toy"]["count"] == 1
    assert site._cache_size() == 1


def test_program_site_leaves_setup_spans_once_a_key():
    """Every compile of a ``ProgramSite`` is in the process ring with
    no plane switched on: ``fedml.compile`` (family, key) holding
    ``.lower`` and ``.backend``, then ``fedml.first_call`` round the
    call that compiled; a key's later calls open no span."""
    from fedml_tpu.core import tracing

    assert telemetry.TRACER is None
    site = memscope.ProgramSite(lambda x, k: x * k, family="spans",
                                static_argnums=(1,))
    tracing.RING.clear()
    for key in (3, 3, 5, 3, 5):
        out = site(key, jnp.ones((4,)), key)
        np.testing.assert_array_equal(np.asarray(out), float(key))
    evs = list(tracing.RING.events)
    assert [e["name"] for e in evs] == 2 * [
        "fedml.compile.lower", "fedml.compile.backend", "fedml.compile",
        "fedml.first_call"]
    assert [e["key"] for e in evs if "key" in e] == ["3", "3", "5", "5"]
    assert {e["family"] for e in evs if "family" in e} == {"spans"}
    low, back, comp, first = evs[:4]
    assert low["parent"] == back["parent"] == "fedml.compile"
    assert comp["parent"] is first["parent"] is None
    assert (comp["t0"] <= low["t0"] <= low["t0"] + low["seconds"]
            <= back["t0"] <= comp["t0"] + comp["seconds"] <= first["t0"])
    tracing.RING.clear()


def test_sim_round_program_slug_and_cohort_growth(metrics_on):
    """The FedAvgSim round registers under (family=sim_round,
    key=bucket) and its argument bytes grow with the cohort — the O(C)
    law the bulk-client engine must flatten."""
    recs = {}
    for c in (4, 8):
        sim = _sim(_cfg(c=c))
        state = sim.init()
        state, _ = sim.run_round(state)
        jax.block_until_ready(jax.tree.leaves(state))
        recs[c] = memscope.program_record("sim_round", c)
        del sim, state
    assert recs[4] and recs[8]
    assert recs[8]["argument_bytes"] > recs[4]["argument_bytes"]
    g = metrics_on.snapshot()["gauges"]
    assert "mem.program.sim_round.4.argument_bytes" in g
    assert "mem.program.sim_round.8.argument_bytes" in g


def test_fused_block_program_slug_carries_length(metrics_on):
    sim = _sim(_cfg(rounds=2, fuse_rounds=2))
    state = sim.init()
    state, _ = sim.run_block(state, 2)
    jax.block_until_ready(jax.tree.leaves(state))
    rec = memscope.program_record("sim_block", (4, 2))
    assert rec is not None, sorted(memscope.program_table())
    assert "mem.program.sim_block.4.2.temp_bytes" in (
        metrics_on.snapshot()["gauges"]
    )


def test_compiled_round_cache_records_compile_time(metrics_on):
    """Satellite 2: a CompiledRoundCache miss is no longer a bare
    counter bump — the compile wall lands in mem.compile_s and the
    executable's analysis in mem.program.*."""
    cache = E.CompiledRoundCache(lambda x: x + 1.0, family="cachefam")
    cache(4, jnp.ones((4,)))
    cache(4, jnp.ones((4,)))  # hit: no second entry
    cache(8, jnp.ones((8,)))  # second bucket: second entry
    snap = metrics_on.snapshot()
    h = snap["histograms"]["mem.compile_s.cachefam"]
    assert h["count"] == 2 and h["sum"] > 0
    assert "mem.program.cachefam.4.argument_bytes" in snap["gauges"]
    assert "mem.program.cachefam.8.argument_bytes" in snap["gauges"]
    assert snap["counters"]["elastic.compile_cache_misses"] == 2
    assert snap["counters"]["elastic.compile_cache_hits"] == 1


def test_program_table_is_capped(metrics_on):
    site = memscope.ProgramSite(lambda x: x + 1.0, family="burst")
    for i in range(memscope.MAX_PROGRAMS + 3):
        site(i, jnp.ones((i + 1,)))
    assert len(memscope.program_table()) == memscope.MAX_PROGRAMS
    assert metrics_on.counter("mem.program_overflow") == 3


# ---------------------------------------------------------------------------
# 2. the live monitor
# ---------------------------------------------------------------------------


def test_monitor_falls_back_to_rss_and_marks_source(metrics_on):
    sample = memscope.MONITOR.sample()
    assert sample is not None
    assert sample["bytes_in_use"] > 0
    # the CPU backend CI runs reports no memory_stats -> RSS fallback,
    # marked; a TPU host would report "device" and the gauge flips
    g = metrics_on.snapshot()["gauges"]
    if sample["source"] == "rss":
        assert g["mem.source_rss"] == 1.0
        assert g["mem.bytes_in_use.rss"] == sample["bytes_in_use"]
    else:
        assert g["mem.source_rss"] == 0.0
    assert g["mem.bytes_in_use"] == sample["bytes_in_use"]
    assert g["mem.high_water_bytes"] >= sample["bytes_in_use"]
    # capacity known on both paths (total RAM on rss) -> headroom rides
    assert "mem.used_frac" in g and 0 < g["mem.used_frac"] <= 1.0
    assert "mem.headroom_frac" in g


def test_monitor_high_water_is_monotone(metrics_on):
    s1 = memscope.MONITOR.sample()
    s2 = memscope.MONITOR.sample()
    assert s2["high_water_bytes"] >= s1["high_water_bytes"]


def test_headroom_flight_event_fires_exactly_once(metrics_on):
    memscope.MONITOR.headroom_warn = 1e-9
    memscope.MONITOR.sample()
    memscope.MONITOR.sample()
    memscope.MONITOR.sample()
    events = [e for e in telemetry.RECORDER._ring
              if e.get("kind") == "mem_headroom"]
    assert len(events) == 1, events
    assert events[0]["threshold"] == 1e-9
    assert events[0]["used_frac"] > 0


def test_monitor_disabled_is_inert():
    telemetry.METRICS.enabled = False
    memscope.MONITOR.reset()
    assert memscope.MONITOR.sample() is None
    assert memscope.MONITOR.high_water == 0


def test_read_device_memory_no_registry_interaction():
    """mlops' SysStats path: readings come back even with the metrics
    plane off (one memory path serves both planes)."""
    telemetry.METRICS.enabled = False
    source, readings = memscope.read_device_memory()
    assert source in ("device", "rss")
    assert readings and readings[0]["bytes_in_use"] > 0
    assert readings[0]["capacity_bytes"] > 0


def test_sysstats_uses_documented_vocabulary(metrics_on):
    from fedml_tpu.core.mlops import SysStats

    out = SysStats().sample()
    assert "mem.source" in out and "mem.bytes_in_use" in out, (
        sorted(out)
    )
    assert "device_memory_in_use" not in out  # the ad-hoc name is gone
    assert out["mem.bytes_in_use"] > 0


# ---------------------------------------------------------------------------
# 3. the donation audit
# ---------------------------------------------------------------------------


def test_donating_round_passes_audit(metrics_on):
    sim = _sim(_cfg())
    state = sim.init()
    state, _ = sim.run_round(state)
    jax.block_until_ready(jax.tree.leaves(state))
    c = metrics_on.snapshot()["counters"]
    assert c.get("mem.donation_audits", 0) == 1
    assert c.get("mem.donation_misses", 0) == 0
    assert memscope.program_record("sim_round", 4)["donation"] == "ok"
    # the audit runs once per program, not once per round
    state, _ = sim.run_round(state)
    assert metrics_on.counter("mem.donation_audits") == 1


def test_fused_block_donates_state_and_residual(metrics_on):
    sim = _sim(_cfg(rounds=4, fuse_rounds=2, compress="int8"))
    state = sim.init()
    state, _ = sim.run_block(state, 2)
    jax.block_until_ready(jax.tree.leaves(state))
    c = metrics_on.snapshot()["counters"]
    assert c.get("mem.donation_misses", 0) == 0, c
    assert memscope.program_record(
        "sim_block", (4, 2))["donation"] == "ok"


def test_undonated_control_is_flagged_once(metrics_on):
    x = jnp.ones((16, 16))
    jax.block_until_ready(jax.jit(lambda v: v * 2.0)(x))
    ok = memscope.audit_donation("ctl", 0, jax.tree.leaves(x))
    assert not ok
    c = metrics_on.snapshot()["counters"]
    assert c["mem.donation_misses"] == 1
    events = [e for e in telemetry.RECORDER._ring
              if e.get("kind") == "mem_donation_miss"]
    assert len(events) == 1
    assert events[0]["program"] == "ctl.0"
    assert events[0]["live_buffers"] == 1


def test_audit_empty_leaves_is_vacuously_ok(metrics_on):
    assert memscope.audit_donation("empty", 0, [])
    assert metrics_on.counter("mem.donation_misses") == 0


# ---------------------------------------------------------------------------
# 4. the peaks table's capacity column
# ---------------------------------------------------------------------------


def test_peaks_table_has_capacity_column():
    from fedml_tpu.core import perf

    for kind, row in perf.PEAKS.items():
        assert len(row) == 3 and row[2] > 0, (kind, row)
    import types

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert perf.device_peaks(v5e) == (197e12, 819e9, 16e9)
    # another platform has no row: no gauge, not a made-up denominator
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert perf.device_peaks(cpu) == (None, None, None)


# ---------------------------------------------------------------------------
# 5. /metrics exposition + /statusz memory section
# ---------------------------------------------------------------------------


def test_mem_metrics_pass_strict_openmetrics_parser(metrics_on):
    from test_export import strict_parse

    site = memscope.ProgramSite(lambda x: x * 3.0, family="expo")
    site(4, jnp.ones((4,)))
    memscope.MONITOR.sample()
    text = export.render_openmetrics(metrics_on.snapshot())
    parsed = strict_parse(text)
    mem_names = [n for n in parsed["types"]
                 if n.startswith("mem_")]
    assert any(n.startswith("mem_program_expo") for n in mem_names), (
        mem_names
    )
    assert "mem_bytes_in_use" in parsed["types"]
    assert parsed["types"]["mem_compile_s_expo"] == "histogram"


def test_statusz_memory_section(metrics_on):
    site = memscope.ProgramSite(lambda x: x * 3.0, family="statz")
    site(4, jnp.ones((4,)))
    memscope.MONITOR.sample()
    doc = export.status_snapshot()
    mem = doc.get("memory")
    assert mem is not None, sorted(doc)
    assert mem["source"] in ("device", "rss")
    assert mem["devices"] and mem["devices"][0]["bytes_in_use"] > 0
    assert "statz.4" in mem["programs"]
    assert mem["donation_audits"] == 0.0
    assert mem["headroom_warn"] == memscope.MONITOR.headroom_warn


# ---------------------------------------------------------------------------
# 6. zero-cost-when-off
# ---------------------------------------------------------------------------


def test_disabled_plane_records_nothing():
    telemetry.METRICS.enabled = False
    memscope.reset()
    site = memscope.ProgramSite(lambda x: x + 1.0, family="off")
    out = site(2, jnp.ones((2,)))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    assert memscope.program_table() == {}
    assert memscope.audit_donation("off", 2, [jnp.ones(())])
    assert memscope.MONITOR.sample() is None
