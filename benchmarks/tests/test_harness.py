"""The harness itself, rehearsed on the CPU: arguments, files found by
name, the last line's format — and that it refuses to stand in for a
chip."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, run_cell

import run

ROOT = os.path.dirname(BENCH)


def test_benchmark_json_names_files_that_exist():
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in doc["configs"]}
    e2e = {m["name"] for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    assert "setup_s" in e2e
    for c in doc["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        ref = os.path.join(ROOT, os.path.dirname(c["file"]), cfg["reference"])
        assert os.path.exists(ref), ref
    for w in doc["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
        t = json.load(open(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")))
        assert int(t["chips"]) == w["chips"]
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(run.reader_path(BENCH, m["name"])), m["name"]
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)


@pytest.mark.parametrize("workload", ["tiny-bn.c4of20", "tiny-gn.c4of20",
                                      "tiny-bn.mesh4"])
def test_rehearsal_prints_the_contract_line(tiny_f32, capsys, workload):
    rc, lines = run_cell(tiny_f32, workload, seed=2 ** 31 + 5, capsys=capsys)
    assert rc == 0
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert set(last["metrics"]) == {"rounds_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    # a rehearsal names the CPU it ran on and carries no device numbers
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"]
    # every earlier record names platform, kind and count
    assert all({"platform", "kind", "count"} <= set(rec["device"])
               for rec in lines[:-1])
    checks = [rec for rec in lines if rec.get("phase") == "check"]
    assert checks and all("limit" in c and "value" in c for c in checks)


def test_traced_rehearsal_reports_host_numbers_only(tiny_f32, capsys,
                                                    monkeypatch):
    import time

    import jax

    stop = jax.profiler.stop_trace

    def slow_stop():  # on four chips the trace takes 43 s to write
        stop()
        time.sleep(3.0)

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    rc, lines = run_cell(tiny_f32, "tiny-bn.c4of20", seconds=4.0, trace=1,
                         capsys=capsys)
    assert rc == 0 and lines[-1]["correct"] is True
    # the window ran on until it held --seconds outside the traced part,
    # long enough for its loss to be judged; tail and evaluation are
    # read outside that part
    part = [rec for rec in lines if rec.get("phase") == "traced_part"][0]
    assert part["profiler_on_s"] > 3.0
    assert part["window_s"] - part["profiler_on_s"] >= 4.0
    assert 0 < part["rounds_outside_it"] < part["rounds_in_window"]
    trained = [rec for rec in lines if rec.get("number")
               == "train_loss_last_minus_first_quarter"][0]
    assert trained["judged"] and trained["ok"] and trained["value"] < 0
    metrics = lines[-1]["metrics"]
    # readers that find nothing to read return nothing
    # ... and a quantity split by suffix is read by the quantity's reader
    assert {"data_build_s", "trace_lower_s", "backend_compile_s",
            "eval_ms.mesh4", "round_p95_ms.mesh4"} <= set(metrics)
    for device_metric in ("useful_mxu_pct", "device_idle_pct",
                          "device_round_ms", "host_gap_ms", "peak_hbm_mb"):
        assert device_metric not in metrics
    assert "rounds_per_s" not in metrics
    assert "breakdown" not in lines[-1]


def test_a_window_that_does_not_train_is_not_correct():
    falling = [2.0 - 0.1 * i for i in range(12)]
    assert run.loss_row(falling)["ok"]
    assert not run.loss_row(falling[::-1])["ok"]
    assert not run.loss_row([2.0] * 12)["ok"]
    assert not run.loss_row([2.0] * 11 + [float("nan")])["ok"]
    short = run.loss_row(falling[::-1][:run.LOSS_ROUNDS_MIN - 1])
    assert short["ok"] and not short["judged"]


def test_same_seed_same_inputs():
    import numpy as np
    from lib import traffic as TR

    ds = {"input_shape": [8, 8, 3], "classes": 10, "n_train": 400,
          "n_test": 50}
    t = {"population": 20, "partition": {"law": "lda", "alpha": 0.5,
                                         "seed": 3}}
    a, b, c = (TR.make_population(ds, t, s) for s in (5, 5, 6))
    assert np.array_equal(a["x_train"], b["x_train"])
    assert not np.array_equal(a["x_train"], c["x_train"])
    # every seed runs the same client sizes and label histograms
    assert np.array_equal(a["sizes"], c["sizes"])
    hist = lambda p: [np.bincount(p["y_train"][v], minlength=10).tolist()
                      for v in p["train_map"].values()]
    assert hist(a) == hist(c)
    assert sorted(np.concatenate(list(a["train_map"].values()))) == list(
        range(400))


def test_without_a_chip_the_command_fails_and_prints_no_result(tiny_f32):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
             "workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_unknown_device_kind_is_an_error():
    from lib.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
