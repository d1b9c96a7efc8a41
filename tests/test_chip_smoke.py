"""What keeps a run without a chip from passing for a run on one:
``chip_smoke.py`` fails on the CPU, no program that reaches the chip
has a fallback, the compile cache is placed in one way, the peaks table
refuses an unknown TPU, and the ``--supervise`` parent stays off every
backend."""

import importlib.util
import inspect
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_chip_smoke_fails_without_a_tpu():
    """The CPU rehearsal: phase a refuses the device, exit code is not
    0, and no line claims ok."""
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert '"phase": "a_device", "ok": false' in out.stdout


def test_chip_smoke_four_chip_mode_fails_without_a_tpu():
    out = _run(["chip_smoke.py", "--chips", "4"])
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("env_dir", ["/some/dir", None])
def test_compile_cache_is_placed_one_way(monkeypatch, env_dir):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and the
    code sets nothing; where it is not, one fixed directory inside the
    checkout — never a temporary name, a pid or a time."""
    import jax

    from fedml_tpu.core import compile_cache

    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache"
        )
        assert updates["jax_compilation_cache_dir"] == os.path.join(
            REPO, ".jax_cache"
        )
        assert updates["jax_persistent_cache_min_compile_time_secs"] >= 1.0
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert updates == {}


def test_unknown_tpu_kind_is_an_error_not_a_default():
    from fedml_tpu.core import perf

    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        perf.device_peaks(unknown)


@pytest.mark.parametrize("entry", ["chip_smoke.py", "benchmarks/run.py"])
def test_entry_point_has_no_fallback_path(entry):
    """The programs that reach the chip have no probe child and no
    road to another backend."""
    spec = importlib.util.spec_from_file_location(
        "entry_point_under_test", os.path.join(REPO, entry)
    )
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)  # benchmarks/run.py puts its own directory first
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path
    leftovers = [n for n in dir(mod)
                 if "fallback" in n.lower() or "probe" in n.lower()]
    assert leftovers == []
    src = inspect.getsource(mod.main)
    assert "subprocess" not in src and "--fallback" not in src


def test_flash_attention_does_not_pick_interpret_mode_itself():
    from fedml_tpu.ops.flash_attention import flash_attention

    sig = inspect.signature(flash_attention)
    assert sig.parameters["interpret"].default is False


def test_supervisor_parent_never_initialises_a_backend(tmp_path):
    """Each rank the supervisor starts is the one process that may own
    a chip, so the parent (argument handling, telemetry, the restart
    loop) must not have touched one when it exits."""
    code = (
        "import atexit, sys\n"
        "from jax._src import xla_bridge\n"
        "from fedml_tpu.experiments import deploy, run\n"
        "class Sup:\n"
        "    def __init__(self, specs, **kw): pass\n"
        "    def run(self): return {'summary': {}, 'restarts': 0}\n"
        "deploy.Supervisor = Sup\n"
        "atexit.register(lambda: print('backends_at_exit',\n"
        "    xla_bridge.backends_are_initialized()))\n"
        "sys.exit(run.main(['--supervise', '--world_size', '3',\n"
        "    '--backend', 'tcp', '--dataset', 'fake_mnist',\n"
        "    '--model', 'lr', '--telemetry_dir', sys.argv[1]]))\n"
    )
    out = _run(["-c", code, str(tmp_path / "tel")])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "backends_at_exit False" in out.stdout
