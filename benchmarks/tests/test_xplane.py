"""The reduction from a profiler trace to busy/idle, per-op time,
exposed collectives and per-chip busy time."""

import os

import pytest
from conftest import BENCH
from lib import xplane


def test_interval_arithmetic():
    merged = xplane.union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)])
    assert merged == [(0, 3), (5, 6)]
    assert xplane.total(merged) == 4
    assert xplane.clip(merged, 2, 5.5) == [(2, 3), (5, 5.5)]
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (6, 10)]
    assert xplane.subtract([(0, 2), (4, 8)], [(1, 5), (7, 9)]) == [
        (0, 1), (5, 7)]
    assert xplane.subtract([(0, 1)], []) == [(0, 1)]


def test_names():
    assert xplane.is_collective("%all-reduce.12")
    assert xplane.is_collective("all-gather-start.3")
    assert not xplane.is_collective("fusion.77")
    assert xplane.is_wrapper("%while.4") and not xplane.is_wrapper(
        "%while_body_fusion.4")
    assert xplane.op_family("%fusion.123") == "fusion"
    assert xplane.op_family("convolution_add_fusion.7") == (
        "convolution_add_fusion")
    assert xplane.op_family("copy") == "copy"
    assert xplane.op_family(
        "%fusion.5 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput, calls=%f"
    ) == "fusion(kOutput)"


@pytest.fixture(scope="module")
def v5e_trace(tmp_path_factory):
    """A trace recorded on one TPU v5e chip (PR 23): four rounds and two
    evaluations of the tiny BatchNorm cell through ``run.py --trace 1``."""
    import gzip
    import shutil

    src = os.path.join(BENCH, "fixtures", "tiny-bn.c4of20.v5e.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(dst)


def test_reduction_of_a_recorded_tpu_trace(v5e_trace):
    t = xplane.reduce_trace(v5e_trace, chips=1, rounds=4)
    # as read on the chip machine when the trace was taken
    assert t["window_s"] == pytest.approx(0.017228319, rel=1e-6)
    assert t["busy_s"] == pytest.approx(0.004203208, rel=1e-6)
    assert t["per_chip_busy_s"] == [t["busy_s"]]
    assert t["module_busy_s"]["jit__round"] == pytest.approx(
        0.004050655, rel=1e-6)
    assert t["module_busy_s"]["jit_evaluate"] == pytest.approx(
        0.000152553, rel=1e-6)
    assert t["round_program_busy_s"] == t["module_busy_s"]["jit__round"]
    # every busy second lies inside one of the two programs
    assert sum(t["module_busy_s"].values()) == pytest.approx(
        t["busy_s"], rel=1e-9)
    # one chip: nothing to exchange, nothing to skew
    assert t["collective_s"] == 0 and t["collective_exposed_s"] == 0
    assert t["chip_skew"] == 0
    assert len(t["eval_span_s"]) == 2
    ops = dict(map(tuple, t["device_ops"]))
    # the convolution fusions lead, named apart from elementwise ones
    assert t["device_ops"][0][0] == "fusion(kOutput)"
    assert "fusion(kLoop)" in ops
    assert "add_select_fusion" in ops
    assert all(" = " not in name and "%" not in name for name in ops)
    assert sum(ops.values()) <= t["busy_s"] * (1 + 1e-9)
    # idle gaps are named by what the host was doing, and add up
    named = sum(seconds for _, seconds in t["idle_gaps"])
    assert named == pytest.approx(t["window_s"] - t["busy_s"], rel=1e-6)
    assert any(name.startswith("bench.evaluate_global")
               for name, _ in t["idle_gaps"])


def test_trace_shape_as_described(v5e_trace):
    data = xplane.load(v5e_trace)
    planes = xplane.device_planes(data)
    assert [p.name for p in planes] == ["/device:TPU:0"]
    lines = {line.name for line in planes[0].lines}
    assert {xplane.OPS_LINE, xplane.MODULES_LINE} <= lines
    spans = xplane.host_spans(data)
    assert [n for _, _, n in spans].count("bench.run_round") == 4


def test_reduction_of_a_recorded_four_chip_trace(tmp_path):
    """Four rounds of the tiny mesh cell (``ShardedFedAvg``, 4x1) on four
    v5e chips (PR 23): collectives, their exposed part, per-chip busy
    time and the skew of the chips' compute."""
    import gzip
    import shutil

    src = os.path.join(BENCH, "fixtures", "tiny-bn.mesh4.v5e-4.xplane.pb.gz")
    dst = tmp_path / "mesh4.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    t = xplane.reduce_trace(str(dst), chips=4, rounds=4)
    assert len(t["per_chip_busy_s"]) == len(t["per_chip_compute_s"]) == 4
    assert t["busy_s"] == pytest.approx(0.002811728, rel=1e-6)
    assert "jit__sharded_round" in t["module_busy_s"]
    # the psum of the aggregation is in the trace, and nothing hides it
    assert t["collective_s"] == pytest.approx(0.0005190495, rel=1e-6)
    assert t["collective_exposed_s"] == pytest.approx(
        t["collective_s"], rel=1e-9)
    assert "all-reduce" in dict(map(tuple, t["device_ops"]))
    # with the waiting inside the all-reduce counted, all chips read
    # equally busy (0.3 % apart); their compute alone differs
    busy = t["per_chip_busy_s"]
    assert (max(busy) - min(busy)) / max(busy) < 0.01
    assert t["chip_skew"] == pytest.approx(0.1153011, rel=1e-5)
    for chip in range(4):
        assert t["per_chip_compute_s"][chip] < busy[chip]
