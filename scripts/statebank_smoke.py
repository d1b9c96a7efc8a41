"""Client-state bank smoke (ci.sh; docs/FAULT_TOLERANCE.md
"Client-state banks").

The composed world PR 14 could not run — compress + streamed defense +
bulk — end to end on CPU, plus the crash contract:

1. a compressed (int8), median-defended, block-streamed run CONVERGES
   on the mnist_lr family shape (test accuracy up >= 0.15 over 12
   rounds, loss strictly down);
2. the defended+compressed block program's argument AND temp bytes
   stay FLAT (<= 1.5x) from C=64 to C=256 at B=16 and FIXED
   population — the EF bank rides as an O(population) donated operand
   whose bytes never scale with the cohort;
3. a SIGKILLed run restores its banks BITWISE: a child process
   checkpoints every round (the ``{"server", "bank"}`` composite) and
   records each round's bank digest; the parent SIGKILLs it mid-run,
   relaunches, and the relaunch must resume from round > 0 with a
   bank digest equal to the recorded one, then finish every round
   with a finite, decreasing loss;
4. the donation audit reports ZERO misses on the composed program;
5. the ``bank.*`` vocabulary (rows / row_bytes / resident_mb gauges,
   gathers / scatters counters) serves over a real /metrics scrape.

Usage: python scripts/statebank_smoke.py <workdir>
       (the child mode is internal: ``... <workdir> child``)
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CHILD_ROUNDS = 6


def _cfg_mod():
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig,
        TrainConfig,
    )

    def cfg(cohort, block, rounds=1, population=None, epochs=1,
            **fed_kw):
        population = cohort if population is None else population
        fed_kw.setdefault("eval_every", 10**9)
        fed_kw.setdefault("compress", "int8")
        fed_kw.setdefault("robust_method", "median")
        return ExperimentConfig(
            data=DataConfig(dataset="fake_mnist",
                            num_clients=population, batch_size=32,
                            seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=epochs,
                              cohort_fused=False),
            fed=FedConfig(num_rounds=rounds, clients_per_round=cohort,
                          client_block_size=block, **fed_kw),
            seed=0,
        )

    return cfg


def _build(conf):
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    return FedAvgSim(create_model(conf.model), load_dataset(conf.data),
                     conf)


def _bank_digest(sim) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    banks = sim.bank_state()
    for name in sorted(banks):
        h.update(name.encode())
        for leaf in jax.tree.leaves(banks[name]):
            h.update(np.ascontiguousarray(
                np.asarray(jax.device_get(leaf))
            ).tobytes())
    return h.hexdigest()


def child(workdir: str) -> int:
    """One harness-shaped run leg: restore (if a checkpoint exists),
    then run + checkpoint every round, recording each round's bank
    digest so the relaunch can prove the restore was bitwise."""
    from fedml_tpu.experiments.harness import Experiment
    from fedml_tpu.utils.checkpoint import RoundCheckpointer

    cfg = _cfg_mod()(cohort=8, block=4, rounds=CHILD_ROUNDS,
                     population=16, epochs=2)
    sim = _build(cfg)
    state = sim.init()
    ckpt = RoundCheckpointer(os.path.join(workdir, "ckpt"), keep=2)
    state, start = Experiment._restore_state(ckpt, sim, state)
    marker = os.path.join(workdir, "progress.json")
    if start > 0:
        # the relaunch leg: the restored bank must be BITWISE the one
        # the dead process recorded at its last completed round
        with open(marker) as f:
            recorded = json.load(f)
        assert recorded["round"] == start - 1, (recorded, start)
        got = _bank_digest(sim)
        assert got == recorded["bank_sha"], (
            "bank restore not bitwise: "
            f"{got} != {recorded['bank_sha']}"
        )
        with open(os.path.join(workdir, "resumed.json"), "w") as f:
            json.dump({"resumed_from": start}, f)
    losses = []
    for r in range(start, CHILD_ROUNDS):
        state, m = sim.run_round(state)
        losses.append(float(m["train_loss"]))
        Experiment._save_state(ckpt, sim, r, state)
        tmp = marker + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"round": r, "bank_sha": _bank_digest(sim),
                       "loss": losses[-1]}, f)
        os.replace(tmp, marker)
        time.sleep(0.3)  # give the parent a window to SIGKILL
    ckpt.close()
    with open(os.path.join(workdir, "done.json"), "w") as f:
        json.dump({"losses": losses, "start": start}, f)
    return 0


def main() -> int:
    workdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/bank_smoke"
    if len(sys.argv) > 2 and sys.argv[2] == "child":
        return child(workdir)
    os.makedirs(workdir, exist_ok=True)

    import jax
    import numpy as np

    from fedml_tpu.core import memscope as M
    from fedml_tpu.core import telemetry

    tdir = os.path.join(workdir, "telemetry")
    telemetry.configure(telemetry_dir=tdir, rank=0, metrics_port=0)
    cfg = _cfg_mod()

    # -- 1. compress + defense + bulk converges --------------------------
    conv = cfg(16, block=4, rounds=12, population=32, epochs=2)
    sim = _build(conv)
    state = sim.init()
    acc0 = sim.evaluate_global(state)["acc"]
    first = last = None
    for _ in range(conv.fed.num_rounds):
        state, m = sim.run_round(state)
        last = float(m["train_loss"])
        first = last if first is None else first
    acc1 = sim.evaluate_global(state)["acc"]
    assert last < first, f"loss did not fall: {first} -> {last}"
    assert acc1 > acc0 + 0.15, f"no convergence: {acc0} -> {acc1}"
    assert sim._carry is not None and sim._stream_defense == "median"

    # -- 2. flat bytes across the cohort sweep, banks riding -------------
    foot = {}
    for c in (64, 256):
        s = _build(cfg(c, block=16, population=256))
        st = s.init()
        st, _ = s.run_round(st)
        jax.block_until_ready(jax.tree.leaves(st))
        rec = M.program_record("sim_bulk", s._program_key())
        assert rec is not None, "bulk program accounting missing"
        foot[c] = rec
        del s, st
    for field in ("argument_bytes", "temp_bytes"):
        lo, hi = foot[64][field], foot[256][field]
        assert max(lo, hi) <= 1.5 * max(1, min(lo, hi)), (
            f"{field} not flat across C with banks riding: {lo} -> {hi}"
        )

    # -- 3. SIGKILL mid-run; relaunch restores the banks bitwise ---------
    kdir = os.path.join(workdir, "kill")
    os.makedirs(kdir, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    argv = [sys.executable, os.path.abspath(__file__), kdir, "child"]
    marker = os.path.join(kdir, "progress.json")
    p = subprocess.Popen(argv, env=env, cwd=REPO)
    deadline = time.time() + 300
    while time.time() < deadline:
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    if json.load(f)["round"] >= 1:
                        break
            except (json.JSONDecodeError, KeyError):
                pass
        if p.poll() is not None:
            raise AssertionError(
                f"child exited ({p.returncode}) before the kill window"
            )
        time.sleep(0.05)
    else:
        p.kill()
        raise AssertionError("child never reached round 1")
    os.kill(p.pid, signal.SIGKILL)  # the deterministic preemption
    p.wait()
    assert not os.path.exists(os.path.join(kdir, "done.json")), (
        "child finished before the SIGKILL — no crash was tested"
    )
    r2 = subprocess.run(argv, env=env, cwd=REPO, timeout=600)
    assert r2.returncode == 0, "relaunch leg failed"
    with open(os.path.join(kdir, "resumed.json")) as f:
        resumed = json.load(f)["resumed_from"]
    assert resumed > 0, "relaunch did not resume from the checkpoint"
    with open(os.path.join(kdir, "done.json")) as f:
        done = json.load(f)
    assert done["start"] == resumed
    assert all(np.isfinite(v) for v in done["losses"])

    # -- 4. donation audit: zero misses on the composed program ----------
    assert telemetry.METRICS.counter("mem.donation_audits") >= 1
    misses = telemetry.METRICS.counter("mem.donation_misses")
    assert misses == 0, f"donation misses with banks riding: {misses}"

    # -- 5. bank.* vocabulary live on /metrics ---------------------------
    with open(os.path.join(tdir, "export_rank0.json")) as f:
        port = json.load(f)["port"]
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ).read().decode()
    for name in ("bank_rows", "bank_row_bytes", "bank_resident_mb",
                 "bank_gathers", "bank_scatters",
                 "defense_sketch_bins", "defense_sketch_mb"):
        assert name in body, f"{name} missing from /metrics"

    telemetry.shutdown()
    print(
        "statebank smoke ok: compress+defense+bulk acc "
        f"{acc0:.3f} -> {acc1:.3f}, flat bytes across 4x cohort, "
        f"SIGKILL resume from round {resumed} with bitwise banks, "
        "0 donation misses, bank.* gauges live"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
