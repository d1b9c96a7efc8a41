"""Tests of the benchmark's own code: ``pytest benchmarks/tests`` with
``JAX_PLATFORMS=cpu``. Four virtual CPU devices stand in for the
four-chip cell; nothing here measures anything."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (HERE, BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_f32(tmp_path_factory):
    import tiny

    return tiny.make_tree(str(tmp_path_factory.mktemp("tiny_f32")), "float32")


@pytest.fixture(scope="session")
def tiny_bf16(tmp_path_factory):
    import tiny

    return tiny.make_tree(
        str(tmp_path_factory.mktemp("tiny_bf16")), "bfloat16")


def run_cell(root, workload, seed=11, seconds=1.0, trace=0, capsys=None,
             break_path=None):
    """Drive ``run.main`` past its look for a chip. -> (rc, lines)."""
    import json

    import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, require_chip=False, break_path=break_path)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(line) for line in out if line.startswith("{")]
